"""Device time of the fused edge-step kernel per step and slab of y, in
ms: the Pallas kernel time of ``layout_chunk`` (its only kernel, as
``edge_step_roofline`` reads it) over the window's steps times the
number of row tiles each step runs, the ``slabs`` count of the window's
``lv.layout.dispatch`` records (``bench/scopes.py``).  None without a
chip plane, or where the program's records carry no such count."""
from bench.scopes import window_records
from bench.trace import kernel_seconds


def read(trace: dict, run: dict) -> float | None:
    steps = run["counts"]["steps"]
    recs = window_records(trace, "layout.dispatch", steps=steps)
    if recs is None:
        return None
    slabs = {counts.get("slabs") for _, _, counts, _ in recs}
    if None in slabs:
        return None
    if len(slabs) != 1:
        raise ValueError(f"the window's dispatches ran {sorted(slabs)} slabs")
    seconds = kernel_seconds(trace, "layout_chunk")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / (steps * slabs.pop())
