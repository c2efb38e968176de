"""Roofline share of the fused edge-step kernel
(``kernels/largevis_step.py``), in %.

The least time for the bytes an edge step must move
(``counts.edge_step_bytes``: the 2 + M rows of every edge read and
written, their ids and the negative masks) at the chip's HBM bandwidth,
over the kernel's device time in the trace: the Pallas kernel time of
``layout_chunk``, whose only Pallas kernel it is."""
from bench import counts
from bench.trace import kernel_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = kernel_seconds(trace, "layout_chunk")
    c = run["counts"]
    if seconds is None or run["peak"] is None or not c["steps"]:
        return None
    per_step = counts.edge_step_bytes(s=c["s"], batch=c["batch"],
                                      negatives=c["negatives"])
    return counts.roofline_share(seconds, bytes_=c["steps"] * per_step,
                                 peak=run["peak"])[0]
