"""Device time per explored row of the candidate gather, in ms: the
``lv.explore.gather`` scope of ``explore_rows_round`` (candidate ids,
``x[cand]``, squared distances, tile padding and keys) over the window's
explored rows (``bench/scopes.py``)."""
from bench.scopes import device_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = device_seconds(trace, "explore_rows_round",
                             "lv.explore.gather")
    rows = run["counts"]["rows"]
    if seconds is None or not rows:
        return None
    return 1e3 * seconds / rows
