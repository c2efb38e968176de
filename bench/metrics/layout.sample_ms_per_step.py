"""Device time per layout step of the alias draws, in ms: the
``lv.layout.sample`` scope of ``layout_chunk`` (step key, edge and
negative alias draws, collision mask, lr) over the window's steps
(``bench/scopes.py``)."""
from bench.scopes import device_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = device_seconds(trace, "layout_chunk", "lv.layout.sample")
    steps = run["counts"]["steps"]
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
