"""Device time per explore call of the reverse adjacency, in ms: the
``lv.explore.reverse`` scope of ``explore_rows_round`` (argsort over the
N·K graph ids, segment ranks, scatter), which each call rebuilds over
the whole graph, over the window's calls (``bench/scopes.py``)."""
from bench.scopes import device_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = device_seconds(trace, "explore_rows_round",
                             "lv.explore.reverse")
    calls = run["counts"]["calls"]
    if seconds is None or not calls:
        return None
    return 1e3 * seconds / calls
