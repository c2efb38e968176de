"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# knn_topk: blocked pairwise squared distances
# ---------------------------------------------------------------------------

def pairwise_sqdist_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """a: (M,d), b: (N,d) -> (M,N) squared euclidean distances, f32."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    an = jnp.sum(a * a, axis=-1, keepdims=True)          # (M,1)
    bn = jnp.sum(b * b, axis=-1, keepdims=True).T        # (1,N)
    d = an + bn - 2.0 * (a @ b.T)
    return jnp.maximum(d, 0.0)


# ---------------------------------------------------------------------------
# knn_topk: streaming fused distance -> top-k (flash-attention-style fold)
# ---------------------------------------------------------------------------

# Similarity value marking a masked candidate (padding, self-edge, bucket
# mismatch, duplicate-of-state).  Strictly above the kernel's -inf "already
# taken" marker so the selection loop and lax.top_k agree on tie order, and
# strictly below any real similarity (|2ab - |a|^2 - |b|^2| < 3e38 for any
# finite f32 coordinates that don't themselves overflow).
INVALID_SIM = -3.0e38  # Python float: jnp scalars would be captured
# The distance an invalid slot surfaces as (= -INVALID_SIM): callers seed
# running state with this, and -INVALID_DIST round-trips to INVALID_SIM
# exactly (IEEE negation is exact).
INVALID_DIST = 3.0e38  # constants inside the Pallas kernel body


def _pad_dim(x, m, axis):
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


def _sim_tile(a, b, an, bn):
    """Negated squared distance, unclamped: s = 2 a.b - |a|^2 - |b|^2.

    Shared by the streaming ref and the Pallas kernel (bit-identical op
    order: ((2ab - an) - bn)); larger similarity = closer.  The clamp to
    non-negative distance happens once on the final (M, k) output instead
    of per (bm, bn) tile — one fewer full pass over every candidate tile.
    """
    s = 2.0 * jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return s - an[:, None] - bn[None, :]


def _mask_bad(s, a_ids, b_ids):
    """Invalidate padding (b_id < 0) and self-edges (b_id == a_id).

    A numerical no-op when the tile holds no negative b_id and the a/b id
    ranges are disjoint — the streaming ref exploits that by cond-ing the
    pass away on non-overlapping tiles (most of them: the diagonal plus
    the ragged tail), while the kernel applies it unconditionally (one
    VPU pass next to the MXU matmul); outputs are identical either way.
    """
    bad = (b_ids[None, :] < 0) | (b_ids[None, :] == a_ids[:, None])
    return jnp.where(bad, INVALID_SIM, s)


def _mask_tile(s, a_ids, b_ids, codes_a, codes_bt, state_ids, dedup: bool,
               skip_bad: bool = False):
    """Invalidate padding (b_id < 0), self-edges, bucket mismatches and —
    when ``dedup`` — candidates whose id already sits in the running state.
    Shared by the streaming ref and the Pallas kernel (the ref conds the
    bad-mask separately and passes ``skip_bad=True``).

    ``codes_a`` is (bm, T) and ``codes_bt`` (T, bn), column codes
    transposed: the bucket match is an OR of T (bm, bn) compares.  A
    (bm, bn, T) compare would pad T to 128 lanes in VMEM — 113 MB at the
    ring's (256, 512) tile against a 16 MB limit."""
    if not skip_bad:
        s = _mask_bad(s, a_ids, b_ids)
    if codes_a is not None:
        match = codes_a[:, 0:1] == codes_bt[0:1, :]
        for t in range(1, codes_a.shape[1]):
            match = match | (codes_a[:, t:t + 1] == codes_bt[t:t + 1, :])
        s = jnp.where(match, s, INVALID_SIM)
    if dedup:
        dup = (b_ids[None, :, None] == state_ids[:, None, :]).any(-1)
        s = jnp.where(dup, INVALID_SIM, s)
    return s


def topk_sqdist_ref(a: jax.Array, b: jax.Array, k: int, *,
                    a_ids: jax.Array | None = None,
                    b_ids: jax.Array | None = None,
                    codes_a: jax.Array | None = None,
                    codes_b: jax.Array | None = None,
                    init_ids: jax.Array | None = None,
                    init_dists: jax.Array | None = None,
                    dedup: bool = False,
                    bm: int = 2048, bn: int | None = None, lane: int = 1,
                    merge: str = "auto"):
    """Streaming fused distance->top-k: the pure-jnp oracle AND the CPU
    production path (``ops.topk_sqdist`` routes impl="auto" here off-TPU).

    For each row of ``a`` (M, d), returns the ``k`` nearest rows of ``b``
    (N, d) as (ids (M, k) int32, sqdists (M, k) f32), distances ascending.
    The (M, N) distance matrix never materializes: column tiles of ``b``
    are folded into a running (bm, k) best state carried through a
    ``lax.scan``, exactly like flash-attention folds softmax tiles.  The
    fold works in *similarity* space (s = 2ab - |a|^2 - |b|^2, i.e. the
    negated squared distance) so ``lax.top_k`` applies directly — no
    negate pass, no clamp pass per tile; both happen once on the final
    (M, k) state.  Row tiles go through ``lax.map`` so the whole call is
    one dispatch (the ``brute_force_knn`` pattern).

    Masking/merging semantics (shared with the Pallas kernel, which is
    bit-identical — tests assert bitwise equality on ids AND dists):

      * ``b_ids`` (N,) gives candidate ids (default ``arange(N)``);
        negative ids are padding and never selected over real candidates.
      * ``a_ids`` (M,) enables self-edge masking (b_id == a_id).
      * ``codes_a`` (M, T) / ``codes_b`` (N, T): keep only pairs sharing
        a bucket code in at least one of T trees (the sharded pipeline's
        forest mask, applied per tile instead of as an (M, N) buffer).
      * ``init_ids``/``init_dists`` (M, k) seed the running state — this
        is how the sharded ring carries its top-k across ring steps and
        how ``forest_knn`` folds tree t+1 into the tree-t result; empty
        slots are (id=-1, dist=INVALID_DIST).
      * ``dedup=True`` masks candidates already present in the running
        state (cross-tree duplicates).  Costs a (bm, bn, k) compare per
        tile — enable only where duplicates are possible.

    Invalid output slots (fewer than k valid candidates) surface as
    (id=-1-or-masked-id, dist=INVALID_DIST-ish); they order after every
    real neighbor.

    ``lane`` pads d to a multiple (the kernel needs 128 for the MXU; the
    CPU default of 1 skips the pad — at d=100 the zero columns would
    inflate the matmul ~28% for nothing).  ``merge`` picks the fold
    formulation: "concat" top_k's over [state | tile] directly; "tile"
    top_k's the tile first and merges the (bm, 2k) shortlist — the same
    output bit-for-bit (top-k of a union is top-k of state ∪ top-k(tile),
    and both keep state-before-tile, earliest-position tie order) but
    cheaper when many column tiles would each pay the (bm, k+bn) concat
    copy; "auto" uses "tile" for a single column tile and from 8 tiles
    up.  Bitwise equality with the kernel therefore holds at equal
    (bm, bn, lane) for EITHER merge.
    """
    M, d = a.shape
    N = b.shape[0]
    bm = min(bm, M)
    if bn is None:
        # wider tiles amortize the per-tile merge once the column count
        # is large (the tile-shortlist regime); 4096 wins in between
        bn = 8192 if N >= 65536 else 4096
    bn = min(bn, N)
    a_ids = (jnp.full((M,), -1, jnp.int32) if a_ids is None
             else a_ids.astype(jnp.int32))
    b_ids = (jnp.arange(N, dtype=jnp.int32) if b_ids is None
             else b_ids.astype(jnp.int32))
    # pad: rows to a bm multiple, cols to a bn multiple, d to a lane
    # multiple (zero features add exact 0.0 terms; at equal lane the ref
    # and the kernel reduce over the same shapes -> the same bits)
    ap = _pad_dim(_pad_dim(a.astype(jnp.float32), bm, 0), lane, 1)
    bp = _pad_dim(_pad_dim(b.astype(jnp.float32), bn, 0), lane, 1)
    aip = _pad_dim(a_ids, bm, 0)
    bip = jnp.pad(b_ids, (0, bp.shape[0] - N), constant_values=-1)
    if codes_a is not None:
        codes_a = _pad_dim(codes_a.astype(jnp.int32), bm, 0)
        codes_b = _pad_dim(codes_b.astype(jnp.int32), bn, 0)
    if init_ids is not None:
        init_ids = _pad_dim(init_ids.astype(jnp.int32), bm, 0)
        init_s = jnp.maximum(-_pad_dim(init_dists.astype(jnp.float32),
                                       bm, 0), INVALID_SIM)
    n_m = ap.shape[0] // bm
    n_n = bp.shape[0] // bn
    if merge == "auto":
        # "tile" wins when the concat copy dominates: many column tiles
        # (each pays it) or a single tile (top_k the tile directly);
        # "concat" wins in between, where its single top_k beats the
        # double top_k per tile
        merge = "concat" if 1 < n_n < 8 else "tile"
    bT = bp.reshape(n_n, bn, -1)
    biT = bip.reshape(n_n, bn)
    cbT = (jnp.swapaxes(codes_b.reshape(n_n, bn, -1), 1, 2)
           if codes_a is not None else None)
    # per-column-tile id range, hoisted: the self/padding mask pass is a
    # numerical no-op unless the tile contains a negative id or its id
    # range overlaps the row tile's — cond it away elsewhere (one fewer
    # full (bm, bn) pass on most tiles; see _mask_bad)
    b_lo = jnp.min(biT, axis=1)
    b_hi = jnp.max(biT, axis=1)

    def row_tile(args):
        at, ait, cat, st0 = args
        an = jnp.sum(at * at, axis=1)
        a_lo, a_hi = jnp.min(ait), jnp.max(ait)

        def fold(carry, xs):
            si, ss = carry
            bt, bit, cbt, blo, bhi = xs
            bn_norm = jnp.sum(bt * bt, axis=1)
            s = _sim_tile(at, bt, an, bn_norm)
            need_bad = (blo < 0) | ((bhi >= a_lo) & (blo <= a_hi))
            s = jax.lax.cond(need_bad,
                             lambda t: _mask_bad(t, ait, bit),
                             lambda t: t, s)
            s = _mask_tile(s, None, bit, cat, cbt, si, dedup,
                           skip_bad=True)
            if merge == "tile":
                # shortlist the tile first: the (bm, k+bn) concat copy of
                # the full tile never happens; bitwise-identical (see
                # docstring)
                ts, ti = jax.lax.top_k(s, min(k, s.shape[1]))
                s_all = jnp.concatenate([ss, ts], axis=1)
                i_all = jnp.concatenate([si, bit[ti]], axis=1)
            else:
                s_all = jnp.concatenate([ss, s], axis=1)
                i_all = jnp.concatenate(
                    [si, jnp.broadcast_to(bit[None, :], s.shape)], axis=1)
            ns, ni = jax.lax.top_k(s_all, k)
            return (jnp.take_along_axis(i_all, ni, axis=1), ns), None

        (si, ss), _ = jax.lax.scan(fold, st0, (bT, biT, cbT, b_lo, b_hi))
        return si, jnp.maximum(-ss, 0.0)

    caT = codes_a.reshape(n_m, bm, -1) if codes_a is not None else None
    if init_ids is not None:
        st0 = (init_ids.reshape(n_m, bm, k), init_s.reshape(n_m, bm, k))
    else:
        st0 = (jnp.full((n_m, bm, k), -1, jnp.int32),
               jnp.full((n_m, bm, k), INVALID_SIM))
    idx, dist = jax.lax.map(
        row_tile, (ap.reshape(n_m, bm, -1), aip.reshape(n_m, bm), caT, st0))
    return idx.reshape(-1, k)[:M], dist.reshape(-1, k)[:M]


# ---------------------------------------------------------------------------
# largevis_grad: fused attractive + repulsive forces (f(x) = 1/(1+a x^2))
# ---------------------------------------------------------------------------

def _unfused(p):
    """``p`` (>= +0 or NaN) unchanged, as a value no compiler folds into
    the add that consumes it.

    A product feeding an add may be contracted into one fused
    multiply-add (one rounding instead of two) or not, depending on how
    the backend vectorizes the surrounding loop — XLA's CPU backend does
    both within one program — so two layouts of the same math would
    round differently.  The integer identity ``b | (b >> 31)`` (b >= 0
    for every non-negative float) is opaque to those rewrites."""
    b = jax.lax.bitcast_convert_type(p, jnp.int32)
    return jax.lax.bitcast_convert_type(b | (b >> 31), jnp.float32)


def edge_forces(yi, yj, yn, mask, *, gamma: float, a: float, clip: float,
                eps: float):
    """The Eqn (6) forces of one edge batch, per coordinate.

    ``yi``/``yj`` are lists of s coordinate arrays, ``yn`` is a list of M
    such lists and ``mask`` a list of M arrays (or None), all of one
    shape: the edges.  Every op is elementwise over the edges, and the
    sums over s and over M are explicit left folds, so the ref, the split
    kernel and the fused kernel — which call this on different edge
    layouts — compute the same float ops for every edge.  The squares
    pass :func:`_unfused` before they are summed.  Returns (gi, gj, gn)
    in the same nesting, clipped per coordinate.  ``a`` must be positive.
    """
    s = len(yi)

    def sq_norm(v):
        acc = _unfused(v[0] * v[0])
        for c in range(1, s):
            acc = acc + _unfused(v[c] * v[c])
        return acc

    def one_plus_a(d2):
        return 1.0 + (d2 if a == 1.0 else _unfused(a * d2))

    # positive edge: d/dyi [-log f] = 2a(yi-yj) / (1 + a d2)
    dij = [yi[c] - yj[c] for c in range(s)]
    den = one_plus_a(sq_norm(dij))
    gpos = [(2.0 * a / den) * dij[c] for c in range(s)]
    # negative: d/dyi [-gamma log(1-f)] = -2 gamma (yi-yn) / ((eps+d2)(1+a d2))
    gneg = []
    for mm, ynm in enumerate(yn):
        din = [yi[c] - ynm[c] for c in range(s)]
        dn2 = sq_norm(din)
        den = (eps + dn2) * one_plus_a(dn2)
        g = [-2.0 * gamma * din[c] / den for c in range(s)]
        if mask is not None:
            g = [gc * mask[mm] for gc in g]
        gneg.append(g)
    gi = []
    for c in range(s):
        g = gpos[c]
        if gneg:
            nsum = gneg[0][c]
            for mm in range(1, len(gneg)):
                nsum = nsum + gneg[mm][c]
            g = g + nsum
        gi.append(jnp.clip(g, -clip, clip))
    gj = [jnp.clip(-g, -clip, clip) for g in gpos]
    gn = [[jnp.clip(-g, -clip, clip) for g in gm] for gm in gneg]
    return gi, gj, gn


def largevis_grads_ref(yi, yj, yneg, *, gamma: float = 7.0, a: float = 1.0,
                       clip: float = 5.0, eps: float = 0.1,
                       neg_mask=None):
    """Gradients of the (negated, minimized) edge log-likelihood, Eqn (6).

    yi, yj: (B,s) endpoint embeddings of sampled positive edges.
    yneg:   (B,M,s) embeddings of sampled negative vertices.
    neg_mask: (B,M) 1.0 valid / 0.0 skip (collision with i or j).

    Returns (gi, gj, gneg): ascent directions are NEGATED (gradient of the
    loss to MINIMIZE), per-coordinate clipped to [-clip, clip] like the
    reference implementation.  The math is :func:`edge_forces`.
    """
    f32 = jnp.float32
    yi, yj, yneg = yi.astype(f32), yj.astype(f32), yneg.astype(f32)
    s, M = yi.shape[1], yneg.shape[1]
    gi, gj, gn = edge_forces(
        [yi[:, c] for c in range(s)], [yj[:, c] for c in range(s)],
        [[yneg[:, mm, c] for c in range(s)] for mm in range(M)],
        None if neg_mask is None else [neg_mask[:, mm] for mm in range(M)],
        gamma=gamma, a=a, clip=clip, eps=eps)
    return (jnp.stack(gi, -1), jnp.stack(gj, -1),
            jnp.stack([jnp.stack(g, -1) for g in gn], 1))


# ---------------------------------------------------------------------------
# largevis_step: fully-fused gather -> grad -> scatter-update edge step
# ---------------------------------------------------------------------------

def fused_edge_step_ref(y, i, j, negs, neg_mask, lr, *, gamma: float = 7.0,
                        a: float = 1.0, clip: float = 5.0,
                        eps: float = 0.1, n_frozen: int = 0):
    """Pure-jnp oracle for ``largevis_step.fused_edge_step``.

    One SGD update of the (N, s) embedding over a sampled edge batch:
    gather the rows, compute the Eqn (6) forces (``largevis_grads_ref``),
    and scatter-accumulate ``-lr*g`` back into ``y``.

    Duplicate-index contract: intra-batch duplicates (the same row drawn as
    i, j and/or a negative, possibly by several edges) ACCUMULATE — every
    update lands.  The update stream is per-edge interleaved,
    ``[i_e, j_e, negs_e,0..M-1] for e = 0..B-1``, and XLA's scatter-add
    applies duplicate updates in stream order, which is exactly the order
    the fused kernel's sequential phase-1 loop uses — the kernel is
    bit-reproducible against this oracle (asserted by tests).

    ``lr`` may be a scalar (the layout drivers) or a (B,) per-edge vector
    (the serving engine, whose lockstep slots sit at different schedule
    positions); a scalar is the same computation as the broadcast vector.

    ``n_frozen``: rows with index < n_frozen never change — the
    out-of-sample transform mode, where the fitted corpus embedding is
    frozen and only appended query rows move.  Frozen-row updates are
    masked to -0.0, and x + (-0.0) == x bitwise for every f32 (including
    both zeros), so frozen rows are BIT-identical to their inputs.
    """
    f32 = jnp.float32
    y = y.astype(f32)
    gi, gj, gneg = largevis_grads_ref(y[i], y[j], y[negs], gamma=gamma,
                                      a=a, clip=clip, eps=eps,
                                      neg_mask=neg_mask)
    s = y.shape[1]
    idx = jnp.concatenate([i[:, None], j[:, None], negs], axis=1).reshape(-1)
    upd = jnp.concatenate([gi[:, None], gj[:, None], gneg],
                          axis=1).reshape(-1, s)
    lr = jnp.asarray(lr, f32)
    if lr.ndim:                       # (B,) per-edge -> per update row
        lr = jnp.repeat(lr, 2 + negs.shape[1])[:, None]
    upd = -lr * upd
    if n_frozen:
        upd = jnp.where((idx >= n_frozen)[:, None], upd, f32(-0.0))
    return y.at[idx].add(upd)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,T,H,hd) (heads pre-broadcast).  f32 softmax."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
