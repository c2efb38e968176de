"""Host time per explore call, in ms: the mean of the program's
``lv.explore.call`` span (key, ``fold_in``, tile lookup, dispatch; it
never blocks) over the window's calls, from the program's record
(``bench/scopes.py``)."""
from bench.scopes import mean_ms, window_records


def read(trace: dict, run: dict) -> float | None:
    recs = window_records(trace, "explore.call",
                          calls=run["counts"]["calls"])
    return None if recs is None else mean_ms(recs)
