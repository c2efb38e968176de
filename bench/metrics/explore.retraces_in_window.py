"""Jaxpr traces and backend compiles inside the window's explore calls:
the sum of the ``retraces`` that the program's ``lv.explore.call`` span
counted (``jax.monitoring`` events) over the window's calls
(``bench/scopes.py``).  A warmed-up window reads 0."""
from bench.scopes import retraces, window_records


def read(trace: dict, run: dict) -> float | None:
    recs = window_records(trace, "explore.call",
                          calls=run["counts"]["calls"])
    return None if recs is None else retraces(recs)
