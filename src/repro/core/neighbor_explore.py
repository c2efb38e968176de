"""Neighbor exploring (paper §3.1 step 3): "a neighbor of my neighbor is
also likely to be my neighbor."

Per iteration, each node's candidates are its neighbors' neighbors
(old_knn(old_knn(i)), Algo 1's double loop) plus its *reverse* neighbors
(nodes that list i — NN-Descent's bidirectional exploration; the paper's
C++ reference also builds reverse edges before exploring).  The per-node
max-heap becomes a batched dedup'd top-k.  Work is tiled over nodes to
bound the gather footprint; ``sample`` can cap candidate columns (0 = use
all K^2, the paper-faithful default).  Each iteration is ONE jitted
dispatch (``_explore_round``): the reverse pass and a ``lax.map`` over
row tiles live in the same program — the old driver paid n_tiles + 1
host dispatches per iteration.  Unlike the tile-structured distance
paths (brute force / windows / ring, which stream through
``kernels.ops.topk_sqdist``), the candidate fill here gathers per-row
id lists with heavy within-row duplication, so the merge stays on the
argsort-dedup ``merge_candidates``.

``sharded_explore_round`` is the multi-device tile driver: it runs INSIDE
a shard_map body (one tile of rows per shard), exchanges the KNN graph
across shards (which is how each shard learns its rows' reverse
neighbors), and fills candidate distances by streaming the point shards
around the device ring — no shard ever holds more than its own (N/P, d)
slab of points plus one in-flight remote slab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import knn as knn_lib
from repro.core.flat import row_major
from repro.runtime import spans


def reverse_rows(knn_idx: jax.Array, rows: jax.Array,
                 r_cap: int) -> jax.Array:
    """(R, r_cap) reverse neighbours of ``rows``: for each, the first
    ``r_cap`` sources ``i`` whose list holds it, in ascending ``i``, padded
    with the row's own index (made inert by merge_candidates'
    self-suppression).  ``rows`` may repeat ids.

    One sort of the graph's edges by (destination, source); then each
    row's segment is found by binary search and its first ``r_cap``
    entries read out: O(N*K log) for the sort and O(R * (log(N*K) +
    r_cap)) after it, whatever the in-degrees.  The edges enter the sort
    column by column: the transposed graph, its rows padded to whole
    tiles, flattens with no gather (``core.flat``) and no slow-compiling
    relayout; the pads name row N, which no row asks for."""
    N, K = knn_idx.shape
    R = rows.shape[0]
    with spans.scope("explore.reverse"):
        n_pad = -(-N // 1024) * 1024
        dst = jnp.pad(knn_idx.T, ((0, 0), (0, n_pad - N)), constant_values=N)
        src = jax.lax.broadcasted_iota(jnp.int32, dst.shape, 1)
        # unstable: entries with equal keys are equal
        dst_s, src_s = jax.lax.sort((dst.reshape(-1), src.reshape(-1)),
                                    num_keys=2, is_stable=False)
        # each row's segment [lo, hi): hi is where row + 1's would start
        bounds = jnp.searchsorted(dst_s, jnp.concatenate([rows, rows + 1]))
        lo, hi = bounds[:R], bounds[R:]
        at = lo[:, None] + jnp.arange(r_cap, dtype=lo.dtype)
        found = src_s[jnp.minimum(at, dst_s.shape[0] - 1)]
        return jnp.where(at < hi[:, None], found, rows[:, None])


def reverse_neighbors(knn_idx: jax.Array, r_cap: int) -> jax.Array:
    """(N, r_cap) reverse adjacency of every row (``reverse_rows`` over
    all of them)."""
    return reverse_rows(knn_idx, jnp.arange(knn_idx.shape[0],
                                            dtype=jnp.int32), r_cap)


def _neighbors_of(knn_idx, nbrs):
    """``knn_idx[nbrs].reshape(T, K * K)``, gathered straight into
    (T, K*K) without the flattening reshape (see ``core.flat``)."""
    r, c = row_major(nbrs.shape[1], knn_idx.shape[1])
    return knn_idx[nbrs[:, r], c]


def _tile_explore(x, knn_idx, knn_dist, rows, rev, key, sample: int):
    """One tile of nodes with their (T, r_cap) reverse neighbours
    ``rev``; returns merged (idx (T,K), dist (T,K))."""
    T = rows.shape[0]
    K = knn_idx.shape[1]
    with spans.scope("explore.gather"):
        nbrs = knn_idx[rows]                              # (T, K)
        fwd = _neighbors_of(knn_idx, nbrs)                # neighbors' nbrs
        cand = jnp.concatenate([fwd, rev], axis=1)
        if sample and sample < cand.shape[1]:
            cols = jax.random.randint(key, (T, sample), 0, cand.shape[1])
            cand = jnp.take_along_axis(cand, cols, axis=1)
        xc = x[cand]                                      # (T, C, d)
        xa = x[rows][:, None, :]
        diff = (xc - xa).astype(jnp.float32)
        cd = jnp.sum(diff * diff, axis=-1)                # (T, C)
    with spans.scope("explore.merge"):
        ids = jnp.concatenate([nbrs, cand], axis=1)
        ds = jnp.concatenate([knn_dist[rows], cd], axis=1)
        return knn_lib.merge_candidates(ids, ds, K, self_idx=rows)


def sharded_explore_round(x_loc, ids_loc, knn_idx_loc, knn_dist_loc, *,
                          axis: str, n_shards: int, n_real: int,
                          key=None, sample: int = 0, r_cap: int = 0,
                          tile: int = 0):
    """One neighbor-exploring round for this shard's tile of rows.

    Must be called inside a shard_map body over mesh axis ``axis``.

    x_loc        (n_loc, d)   this shard's point slab
    ids_loc      (n_loc,)     global ids of the slab (contiguous range)
    knn_idx_loc  (n_loc, K)   current graph rows (global ids)
    knn_dist_loc (n_loc, K)

    The graph (N*K ints — output-sized, NOT a candidate buffer) is
    all-gathered so each shard can read its rows' forward and reverse
    neighbors; candidate *coordinates* are never gathered: distances are
    filled over ``n_shards`` ring steps, each touching only the remote
    (n_loc, d) slab currently held.  Within each ring step the
    coordinate gather runs over row tiles (``lax.map``, same element
    budget as single-device ``neighbor_explore``) so the (T, C, d)
    gather temporary stays bounded by the tile — without this the step
    materialized an O(n_loc * K^2 * d) buffer, ~15 GB at the paper's
    N=1M on one shard (the blow-up ``tests/memcheck.py`` now forbids).
    Candidate *id/distance* tables stay whole-slab: (n_loc, C) working
    sets are the per-shard output-order footprint the ring design
    budgets for.  Returns merged (idx, dist) for the local rows.
    """
    n_loc, K = knn_idx_loc.shape
    r_cap = r_cap or K
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    # candidate ids (the reverse adjacency in its own scope, inside) and
    # the ring pass that fills their distances
    with spans.scope("explore.gather"):
        # --- candidate ids from the exchanged graph ---------------------
        g_idx = jax.lax.all_gather(knn_idx_loc, axis, tiled=True)  # (Np, K)
        rev = reverse_neighbors(g_idx, r_cap)                  # (Np, r_cap)
        rev_loc = jax.lax.dynamic_slice_in_dim(rev, ids_loc[0], n_loc)
        fwd = _neighbors_of(g_idx, knn_idx_loc)
        cand = jnp.concatenate([fwd, rev_loc], axis=1)         # (n_loc, C)
        if sample and sample < cand.shape[1]:
            cols = jax.random.randint(key, (n_loc, sample), 0, cand.shape[1])
            cand = jnp.take_along_axis(cand, cols, axis=1)
        cand = jnp.where(cand >= n_real, ids_loc[:, None], cand)  # pad->self

        # --- ring pass: fill candidate distances from streamed slabs ----
        C = cand.shape[1]
        d = x_loc.shape[1]
        budget = 64 * (1 << 20)                  # ~256 MB of f32 per gather
        T = int(tile) or max(16, min(n_loc, budget // max(1, C * d)))
        n_tiles = -(-n_loc // T)
        pad = n_tiles * T - n_loc
        if pad:
            cand_p = jnp.concatenate([cand, jnp.zeros((pad, C), cand.dtype)])
            x_p = jnp.concatenate([x_loc, jnp.zeros((pad, d), x_loc.dtype)])
        else:
            cand_p, x_p = cand, x_loc
        cand_t = cand_p.reshape(n_tiles, T, C)
        x_t = x_p.reshape(n_tiles, T, d)

        def ring_step(_, carry):
            cd, rx, roff = carry

            def one(args):
                cand_b, cd_b, x_b = args
                rel = cand_b - roff
                in_rng = (rel >= 0) & (rel < n_loc)
                xc = rx[jnp.clip(rel, 0, n_loc - 1)]            # (T, C, d)
                diff = (xc - x_b[:, None, :]).astype(jnp.float32)
                dd = jnp.sum(diff * diff, axis=-1)
                return jnp.where(in_rng, dd, cd_b)

            cd = jax.lax.map(one, (cand_t, cd, x_t))
            rx = jax.lax.ppermute(rx, axis, perm)
            roff = jax.lax.ppermute(roff, axis, perm)
            return cd, rx, roff

        cd0 = jnp.full((n_tiles, T, C), knn_lib.INF, jnp.float32)
        cd, _, _ = jax.lax.fori_loop(
            0, n_shards, ring_step, (cd0, x_loc, ids_loc[0]))
        cd = cd.reshape(n_tiles * T, C)[:n_loc]

    with spans.scope("explore.merge"):
        ids = jnp.concatenate([knn_idx_loc, cand], axis=1)
        ds = jnp.concatenate([knn_dist_loc, cd], axis=1)
        return knn_lib.merge_candidates(ids, ds, K, self_idx=ids_loc)


def _explore_tiles(x, knn_idx, knn_dist, rows, ikey, *, sample: int,
                   tile: int, r_cap: int):
    """Explore ``rows`` against the whole graph: merged (ids, dists) of
    shape (R, K), one per row.

    Rows pad to a tile multiple by repeating the first row; padded
    results are sliced off.  The reverse lists are built for the padded
    rows alone (``reverse_rows``) and each tile takes its slice of them
    with its rows, under ``jax.lax.map``."""
    K = knn_idx.shape[1]
    R = rows.shape[0]
    n_tiles = -(-R // tile)
    with spans.scope("explore.gather"):
        rows_p = jnp.concatenate(
            [rows, jnp.broadcast_to(rows[:1], (n_tiles * tile - R,))])
        tkeys = jax.vmap(lambda t: jax.random.fold_in(ikey, t))(
            jnp.arange(n_tiles))
    rev = reverse_rows(knn_idx, rows_p, r_cap)

    def one(args):
        r, rv, tk = args
        return _tile_explore(x, knn_idx, knn_dist, r, rv, tk, sample)

    ti, td = jax.lax.map(one, (rows_p.reshape(n_tiles, tile),
                               rev.reshape(n_tiles, tile, r_cap), tkeys))
    with spans.scope("explore.writeback"):
        return ti.reshape(-1, K)[:R], td.reshape(-1, K)[:R]


@functools.partial(jax.jit, static_argnames=("sample", "tile", "r_cap"))
def _explore_round(x, knn_idx, knn_dist, ikey, *, sample: int, tile: int,
                   r_cap: int):
    """One full exploring iteration as ONE device dispatch: every row,
    its row tiles under ``jax.lax.map`` (the ``brute_force_knn`` pattern)
    rather than one dispatch per tile.  Rows pad to a tile multiple with
    row 0; padded rows never survive the final slice."""
    rows = jnp.arange(knn_idx.shape[0], dtype=jnp.int32)
    return _explore_tiles(x, knn_idx, knn_dist, rows, ikey, sample=sample,
                          tile=tile, r_cap=r_cap)


@functools.partial(jax.jit, static_argnames=("sample", "tile", "r_cap"))
def _explore_rows_round(x, knn_idx, knn_dist, rows, ikey, *, sample: int,
                        tile: int, r_cap: int):
    """One exploring iteration over a SUBSET of rows (incremental graph
    maintenance after ``transform.knn_insert``): the same tiles as
    ``_explore_round``, but only ``rows`` are explored and written back —
    one sort of the graph, then O(len(rows)) work."""
    ti, td = _explore_tiles(x, knn_idx, knn_dist, rows, ikey, sample=sample,
                            tile=tile, r_cap=r_cap)
    with spans.scope("explore.writeback"):
        return knn_idx.at[rows].set(ti), knn_dist.at[rows].set(td)


def neighbor_explore(x, knn_idx, knn_dist, *, iters: int = 1,
                     sample: int = 0, key=None, tile: int | None = None,
                     r_cap: int = 0, rows=None):
    """Refine (knn_idx, knn_dist) for ``iters`` rounds.

    sample=0 explores the full candidate set (paper-faithful); tile bounds
    the (tile, K^2, d) gather — shrink it for large K/d.  The default
    None resolves tile through the autotuner, but ONLY when sample == 0:
    with sampling on, the per-tile ``fold_in`` key stream makes the tile
    size part of the result, so the tuner must never touch it (the
    results-preservation contract in ``runtime/autotune.py``) and the
    legacy 1024 is used.  Each iteration is one jitted dispatch
    (``_explore_round``); the graph feeds back between iterations.

    ``rows`` (optional int32 array of row indices) restricts exploring to
    those rows — the incremental-insert repair mode: candidate generation
    still reads the FULL graph (forward and reverse), but only the given
    rows are recomputed and written back.
    """
    N, K = knn_idx.shape
    n_rows = N if rows is None else int(rows.shape[0])
    if n_rows == 0:
        return knn_idx, knn_dist
    with spans.span("explore.call", rows=n_rows):
        if key is None:
            key = jax.random.key(0)
        r_cap = r_cap or K
        if tile is None:
            tile = 1024
            if sample == 0:      # tile is results-neutral only un-sampled
                from repro.runtime import autotune
                tile = autotune.get(
                    "neighbor_explore", dict(n=n_rows, k=K, d=x.shape[1]),
                    autotune.legacy_default("neighbor_explore"))["tile"]
        # keep the per-tile gather under ~256 MB f32
        budget = 64 * (1 << 20)
        tile = max(16, min(tile, n_rows,
                           budget // max(1, (K * K + K) * x.shape[1])))
        static = dict(sample=sample, tile=tile, r_cap=r_cap)
        for it in range(iters):
            ikey = jax.random.fold_in(key, it)
            if rows is None:
                args = (x, knn_idx, knn_dist, ikey)
                fn, program = _explore_round, "explore_round"
            else:
                args = (x, knn_idx, knn_dist, rows, ikey)
                fn, program = _explore_rows_round, "explore_rows_round"
            spans.note(program, fn, *args, **static)
            knn_idx, knn_dist = fn(*args, **static)
    return knn_idx, knn_dist
