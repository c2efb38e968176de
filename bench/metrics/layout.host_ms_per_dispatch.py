"""Host time per layout dispatch, in ms: the mean of the program's
``lv.layout.dispatch`` span (one chunk of ``run_layout``'s loop: the
step ids and lr positions, then the ``layout_chunk`` call) over the
window's dispatches, from the program's record (``bench/scopes.py``)."""
from bench.scopes import mean_ms, window_records


def read(trace: dict, run: dict) -> float | None:
    recs = window_records(trace, "layout.dispatch",
                          steps=run["counts"]["steps"])
    return None if recs is None else mean_ms(recs)
