"""Roofline share of the neighbor-explore program (``_explore_rows_round``,
the rows entry of ``neighbor_explore``), in %.

The least time for the bytes the round must move
(``counts.explore_bytes``: the K^2 + K candidate rows of every explored
row, the id lists, and one read of the graph per call) at the chip's HBM
bandwidth, over the program's device time in the trace."""
from bench import counts
from bench.trace import program_seconds


def read(trace: dict, run: dict) -> float | None:
    seconds = program_seconds(trace, "explore_rows_round")
    c = run["counts"]
    if seconds is None or run["peak"] is None or not c["calls"]:
        return None
    per_call = counts.explore_bytes(c["rows"] // c["calls"], n=c["n"],
                                    k=c["k"], d=c["d"])
    return counts.roofline_share(seconds, bytes_=c["calls"] * per_call,
                                 peak=run["peak"])[0]
