"""Share of the window in which no operation ran on the chip, in %:
1 - busy/window from the device plane of the trace."""
from bench.trace import idle_share


def read(trace: dict, run: dict) -> float | None:
    return idle_share(trace)
