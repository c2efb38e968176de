"""Device time per explored row of the candidate merge, in ms: the
``lv.explore.merge`` scope of ``explore_rows_round`` (the argsort-dedup
top-K of ``merge_candidates``) over the window's explored rows
(``bench/scopes.py``)."""
from bench.scopes import device_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = device_seconds(trace, "explore_rows_round",
                             "lv.explore.merge")
    rows = run["counts"]["rows"]
    if seconds is None or not rows:
        return None
    return 1e3 * seconds / rows
