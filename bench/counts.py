"""Operations and bytes that a kernel or program must at least move,
worked out from its shapes, and the roofline share they give.

Every count is the least the algorithm needs, not what an implementation
happens to touch: a roofline share over 100% then means the count is too
high or the measured time leaves out part of the work.
"""
from __future__ import annotations

import json
import os

F32 = 4
I32 = 4
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind (``peaks.json``).

    An unknown kind is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def explore_bytes(rows: int, *, n: int, k: int, d: int,
                  r_cap: int = 0) -> int:
    """One neighbor-explore call over ``rows`` rows of an (n, k) graph.

    Per explored row: its own vector, the ids of its k neighbours and of
    their k^2 neighbours, its r_cap reverse neighbours, the vectors of
    all k^2 + r_cap candidates, its stored distances, and the merged
    (ids, distances) written back.  Per call: the whole graph read once
    to build the reverse adjacency, and the (n, r_cap) table written."""
    r_cap = r_cap or k
    cand = k * k + r_cap
    per_row = (d * F32 + k * I32 + cand * I32 + cand * d * F32
               + k * F32 + 2 * k * (I32 + F32))
    per_call = n * k * I32 + n * r_cap * I32
    return rows * per_row + per_call


def edge_step_bytes(*, s: int, batch: int, negatives: int) -> int:
    """One edge step: the s coordinates of the 2 + M rows of every edge
    (its two ends and its M negatives) read and written once, their row
    ids, the M negative masks and the rate."""
    rows = batch * (2 + negatives)
    return (2 * rows * s * F32 + rows * I32 + batch * negatives * F32
            + F32)


def roofline_share(seconds: float, *, flops: float = 0.0,
                   bytes_: float = 0.0, peak: dict) -> tuple[float, str]:
    """(share in %, bound) of the least time the chip could take over the
    measured ``seconds``.  The bound is whichever of FLOPs at the bf16
    peak or bytes at the HBM peak takes longer."""
    if seconds <= 0:
        raise ValueError("roofline share of a zero time")
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
