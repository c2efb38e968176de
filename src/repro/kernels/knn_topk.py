"""Pallas kernels for the KNN hot spot.

``pairwise_sqdist`` — blocked pairwise squared distances:
D[i,j] = |a_i|^2 + |b_j|^2 - 2 a_i . b_j — the -2ab^T term is an MXU matmul;
tiles are chosen so (bm, bk) + (bk, bn) + (bm, bn) blocks live in VMEM and
the contraction dim is 128-aligned (inputs are zero-padded to multiples of
the tile).  Grid is (M/bm, N/bn, d/bk) with a VMEM f32 accumulator; norms
are folded in on the last k-step.

``topk_sqdist`` — streaming fused distance -> top-k: a flash-attention-style
fold that keeps a running (bm, k) best-ids/best-similarities state in VMEM
and folds each (bm, bn) distance tile into it inside the column-tile grid
loop, so the (M, N) distance matrix and the post-hoc top_k/merge passes
never materialize.  Self-edges, padding, bucket-code mismatches and
duplicates of the running state are masked in-kernel (the shared
``ref._mask_tile``).  The merge is k rounds of max-extraction — plain
max/min/where/iota, no sort, so it lowers under Mosaic — and is
bit-identical to ``lax.top_k``'s earliest-index tie order, which is what
the streaming jnp oracle (``ref.topk_sqdist_ref``, also the CPU production
path) uses; tests assert bitwise (ids, dists) equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as ref_lib
from repro.kernels.largevis_grad import _resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)                   # (bm, bk)
    b = b_ref[...].astype(jnp.float32)                   # (bn, bk)
    acc_ref[...] += -2.0 * jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.sum(a * a, axis=1, keepdims=True)
    acc_ref[...] += jnp.sum(b * b, axis=1)[None, :]

    @pl.when(k_idx == n_k - 1)
    def _done():
        o_ref[...] = jnp.maximum(acc_ref[...], 0.0)


def _pad_to(x, m, axis):
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pairwise_sqdist(a: jax.Array, b: jax.Array, *, bm: int = 256,
                    bn: int = 256, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """a: (M,d), b: (N,d) -> (M,N) squared distances (f32).

    ``interpret=None`` resolves per backend (the shared largevis_grad
    helper, PR-2 fix): compiled on TPU, interpret mode (kernel body as
    XLA ops) elsewhere, e.g. this CPU container.  The old hard
    ``interpret=True`` default silently ran the interpreter path on TPU
    for every direct caller that forgot to override it.
    """
    interpret = _resolve_interpret(interpret)
    M, d = a.shape
    N = b.shape[0]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, d)
    ap = _pad_to(_pad_to(a, bm_, 0), bk_, 1)
    bp = _pad_to(_pad_to(b, bn_, 0), bk_, 1)
    Mp, dp = ap.shape
    Np = bp.shape[0]
    n_k = dp // bk_
    grid = (Mp // bm_, Np // bn_, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# streaming fused distance -> top-k
# ---------------------------------------------------------------------------


def _select_topk(s_all, i_all, k: int):
    """Top-k of each row of ``s_all`` by repeated max-extraction.

    Bit-identical to ``lax.top_k(s_all, k)`` + gathering ``i_all`` at the
    winning positions: equal values resolve to the earliest position (the
    documented top_k tie order), and extracted slots drop to -inf, which
    is strictly below every live value (masked candidates sit at
    ``ref.INVALID_SIM`` = -3e38 > -inf), so a slot is never re-taken.
    Only max/min/where/sum/iota — lowers under Mosaic, where lax.top_k
    does not.
    """
    bm, W = s_all.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (bm, W), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)

    def pick(t, st):
        os_, oi_, cs = st
        m = jnp.max(cs, axis=1, keepdims=True)                    # (bm, 1)
        p = jnp.min(jnp.where(cs == m, pos, W), axis=1, keepdims=True)
        hit = pos == p
        sel_i = jnp.sum(jnp.where(hit, i_all, 0), axis=1, keepdims=True)
        os_ = jnp.where(slot == t, m, os_)
        oi_ = jnp.where(slot == t, sel_i, oi_)
        cs = jnp.where(hit, -jnp.inf, cs)
        return os_, oi_, cs

    os0 = jnp.zeros((bm, k), s_all.dtype)
    oi0 = jnp.zeros((bm, k), jnp.int32)
    os_, oi_, _ = jax.lax.fori_loop(0, k, pick, (os0, oi0, s_all))
    return os_, oi_


def _topk_kernel(*refs, k: int, n_n: int, has_codes: bool, has_init: bool,
                 dedup: bool):
    it = iter(refs)
    a_ref, b_ref, aid_ref, bid_ref = next(it), next(it), next(it), next(it)
    ca_ref = next(it) if has_codes else None
    cb_ref = next(it) if has_codes else None
    ii_ref = next(it) if has_init else None
    is_ref = next(it) if has_init else None
    oi_ref, od_ref, si_ref, ss_ref = next(it), next(it), next(it), next(it)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        if has_init:
            si_ref[...] = ii_ref[...]
            ss_ref[...] = jnp.maximum(-is_ref[...], ref_lib.INVALID_SIM)
        else:
            si_ref[...] = jnp.full_like(si_ref, -1)
            ss_ref[...] = jnp.full_like(ss_ref, ref_lib.INVALID_SIM)

    a = a_ref[...].astype(jnp.float32)                            # (bm, dp)
    b = b_ref[...].astype(jnp.float32)                            # (bn, dp)
    an = jnp.sum(a * a, axis=1)
    bn_norm = jnp.sum(b * b, axis=1)
    s = ref_lib._sim_tile(a, b, an, bn_norm)                      # (bm, bn)
    si, ss = si_ref[...], ss_ref[...]
    s = ref_lib._mask_tile(
        s, aid_ref[...][:, 0], bid_ref[...][0, :],
        ca_ref[...] if has_codes else None,
        cb_ref[...] if has_codes else None, si, dedup)
    s_all = jnp.concatenate([ss, s], axis=1)
    i_all = jnp.concatenate(
        [si, jnp.broadcast_to(bid_ref[...][0:1, :], s.shape)], axis=1)
    ns, ni = _select_topk(s_all, i_all, k)
    ss_ref[...] = ns
    si_ref[...] = ni

    @pl.when(j == n_n - 1)
    def _done():
        oi_ref[...] = si_ref[...]
        od_ref[...] = jnp.maximum(-ss_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("k", "dedup", "bm", "bn",
                                             "lane", "interpret"))
def topk_sqdist(a: jax.Array, b: jax.Array, k: int, *,
                a_ids: jax.Array | None = None,
                b_ids: jax.Array | None = None,
                codes_a: jax.Array | None = None,
                codes_b: jax.Array | None = None,
                init_ids: jax.Array | None = None,
                init_dists: jax.Array | None = None,
                dedup: bool = False, bm: int = 256, bn: int = 512,
                lane: int = 128, interpret: bool | None = None):
    """Streaming fused distance->top-k Pallas kernel.

    a: (M, d), b: (N, d) -> (ids (M, k) int32, sqdists (M, k) f32),
    distances ascending.  Semantics, masking and tie order are exactly
    ``ref.topk_sqdist_ref`` (bit-identical when called with the same
    bm/bn/lane); see its docstring for the a_ids/b_ids/codes/init/dedup
    contract.  Grid is (M/bm, N/bn) with the column dimension innermost;
    the (bm, k) running state lives in VMEM scratch across the column
    sweep and the output block is written on the last column step.
    ``lane`` (default 128) zero-pads d to the MXU lane width.

    ``interpret=None`` resolves per backend (compiled on TPU, interpret
    elsewhere).  On CPU, ``ops.topk_sqdist`` routes impl="auto" to the
    jnp streaming oracle instead — the interpreter is Python-slow.
    """
    interpret = _resolve_interpret(interpret)
    M, d = a.shape
    N = b.shape[0]
    bm_ = min(bm, M)
    bn_ = min(bn, N)
    a_ids = (jnp.full((M,), -1, jnp.int32) if a_ids is None
             else a_ids.astype(jnp.int32))
    b_ids = (jnp.arange(N, dtype=jnp.int32) if b_ids is None
             else b_ids.astype(jnp.int32))
    pad = ref_lib._pad_dim
    ap = pad(pad(a.astype(jnp.float32), bm_, 0), lane, 1)
    bp = pad(pad(b.astype(jnp.float32), bn_, 0), lane, 1)
    Mp, dp = ap.shape
    Np = bp.shape[0]
    aip = pad(a_ids, bm_, 0)[:, None]                             # (Mp, 1)
    bip = jnp.pad(b_ids, (0, Np - N), constant_values=-1)[None, :]
    n_m, n_n = Mp // bm_, Np // bn_
    grid = (n_m, n_n)

    operands = [ap, bp, aip, bip]
    in_specs = [
        pl.BlockSpec((bm_, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((bn_, dp), lambda i, j: (j, 0)),
        pl.BlockSpec((bm_, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((1, bn_), lambda i, j: (0, j)),
    ]
    has_codes = codes_a is not None
    if has_codes:
        T = codes_a.shape[1]
        operands += [pad(codes_a.astype(jnp.int32), bm_, 0),
                     pad(codes_b.astype(jnp.int32), bn_, 0).T]
        in_specs += [pl.BlockSpec((bm_, T), lambda i, j: (i, 0)),
                     pl.BlockSpec((T, bn_), lambda i, j: (0, j))]
    has_init = init_ids is not None
    if has_init:
        operands += [pad(init_ids.astype(jnp.int32), bm_, 0),
                     pad(init_dists.astype(jnp.float32), bm_, 0)]
        in_specs += [pl.BlockSpec((bm_, k), lambda i, j: (i, 0)),
                     pl.BlockSpec((bm_, k), lambda i, j: (i, 0))]

    idx, dist = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, n_n=n_n, has_codes=has_codes,
                          has_init=has_init, dedup=dedup),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bm_, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((bm_, k), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Mp, k), jnp.int32),
                   jax.ShapeDtypeStruct((Mp, k), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm_, k), jnp.int32),
                        pltpu.VMEM((bm_, k), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return idx[:M], dist[:M]
