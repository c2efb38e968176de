"""Device time per layout step spent outside the edge-step kernel, in ms:
the ``layout_chunk`` program's device time (alias draws, the planar
copies of y, the rest of the step) less its Pallas kernel's, over the
steps."""
from bench.trace import kernel_seconds, program_seconds


def read(trace: dict, run: dict) -> float | None:
    total = program_seconds(trace, "layout_chunk")
    steps = run["counts"]["steps"]
    if total is None or not steps:
        return None
    return 1e3 * (total - kernel_seconds(trace, "layout_chunk")) / steps
