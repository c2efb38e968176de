"""Pallas kernel: fused LargeVis edge-sampling gradient (the split path).

One grid step processes a tile of sampled edges: attractive force on the
positive pair, repulsive forces on M negatives, reference-impl per-coordinate
clipping — all fused in VMEM so the edge batch streams through HBM once.

Operands are **planar**: each coordinate of each operand is a lane-dense
(B/128, 128) plane, edges along lanes.  The embedding dim s (2 or 3) never
sits on the lane axis, so no block is padded 128/s-fold and no in-kernel
reshape crosses the lane axis (Mosaic refuses the ``(t, m*s) <-> (t, m, s)``
shape casts a row-major layout needs).  The force math is
``ref.edge_forces`` — the oracle's own function — so the kernel and
``ref.largevis_grads_ref`` are the same float ops per edge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

LANES = 128
# sublane-aligned edge block: an (8, 128) f32 tile of one plane
_BLOCK = 8 * LANES


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(yi_ref, yj_ref, yn_ref, mask_ref, gi_ref, gj_ref, gn_ref, *,
            gamma: float, a: float, clip: float, eps: float, m: int,
            s: int):
    gi, gj, gn = ref.edge_forces(
        [yi_ref[c] for c in range(s)], [yj_ref[c] for c in range(s)],
        [[yn_ref[mm, c] for c in range(s)] for mm in range(m)],
        [mask_ref[mm] for mm in range(m)],
        gamma=gamma, a=a, clip=clip, eps=eps)
    for c in range(s):
        gi_ref[c] = gi[c]
        gj_ref[c] = gj[c]
        for mm in range(m):
            gn_ref[mm, c] = gn[mm][c]


def _resolve_interpret(interpret) -> bool:
    """Backend-aware default (mirrors ops.py): ``None`` -> interpret mode
    everywhere except TPU, where the kernel compiles."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


@functools.partial(jax.jit, static_argnames=("gamma", "a", "clip", "eps",
                                             "tile", "interpret"))
def largevis_grads(yi, yj, yneg, neg_mask, *, gamma: float = 7.0,
                   a: float = 1.0, clip: float = 5.0, eps: float = 0.1,
                   tile: int = 2048, interpret: bool | None = None):
    """yi/yj: (B,s); yneg: (B,M,s); neg_mask: (B,M) -> (gi, gj, gneg).

    Any B: the batch is zero-padded to whole edge blocks (``tile`` edges
    each, rounded to a multiple of 1024) and the padded rows are sliced
    off."""
    interpret = _resolve_interpret(interpret)
    B, s = yi.shape
    M = yneg.shape[1]
    # an edge block is the whole 128-padded batch or a multiple of _BLOCK
    # edges, so every plane block is (8, 128)-aligned
    t = min(_round_up(tile, _BLOCK), _round_up(B, LANES))
    bp = _round_up(B, t)
    tq, q = t // LANES, bp // LANES

    def planes(x):            # (B, ..., s) -> (..., s, B/128, 128)
        x = jnp.pad(x.astype(jnp.float32),
                    [(0, bp - B)] + [(0, 0)] * (x.ndim - 1))
        x = jnp.moveaxis(x, 0, -1)
        return x.reshape(x.shape[:-1] + (q, LANES))

    def unplanes(x):          # inverse of planes, padding dropped
        x = x.reshape(x.shape[:-2] + (bp,))
        return jnp.moveaxis(x, -1, 0)[:B]

    def spec(lead):
        nl = len(lead)
        return pl.BlockSpec(lead + (tq, LANES),
                            lambda e: (0,) * nl + (e, 0))

    kern = functools.partial(_kernel, gamma=gamma, a=a, clip=clip, eps=eps,
                             m=M, s=s)
    gi, gj, gn = pl.pallas_call(
        kern,
        grid=(bp // t,),
        in_specs=[spec((s,)), spec((s,)), spec((M, s)), spec((M,))],
        out_specs=[spec((s,)), spec((s,)), spec((M, s))],
        out_shape=[jax.ShapeDtypeStruct((s, q, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((s, q, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((M, s, q, LANES), jnp.float32)],
        interpret=interpret,
    )(planes(yi), planes(yj), planes(yneg),
      planes(neg_mask))
    return unplanes(gi), unplanes(gj), unplanes(gn)
