"""Jaxpr traces and backend compiles inside the window's layout
dispatches: the sum of the ``retraces`` that the program's
``lv.layout.dispatch`` span counted (``jax.monitoring`` events) over the
window's dispatches (``bench/scopes.py``).  A warmed-up window reads 0."""
from bench.scopes import retraces, window_records


def read(trace: dict, run: dict) -> float | None:
    recs = window_records(trace, "layout.dispatch",
                          steps=run["counts"]["steps"])
    return None if recs is None else retraces(recs)
