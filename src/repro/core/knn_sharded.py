"""Sharded multi-device KNN-graph construction (paper §3.1 at scale).

The single-device pipeline (`core/knn.py`) holds all N points on one
device.  Here the point set is sharded over the mesh "data" axis and the
graph is built with a fixed per-device memory footprint:

  1. **Codes** — every shard computes sign-random-projection bucket codes
     for its own slab with a shared projection matrix (one matmul).
  2. **Fused ring pass** — point slabs circulate the device ring
     (`ppermute`); at each of the P ring steps a shard folds the
     in-flight remote slab straight into its running (N/P, k) best state
     through the streaming fused distance->top-k op
     (`kernels.ops.topk_sqdist`): bucket-mismatch/self/padding masking
     and the top-k merge happen inside the fold, so the old per-step
     re-merge concat is gone and distance/bucket-match work is bounded
     by the op's (bm, bn) tiles (at most one (N/P, N/P) tile when the
     slab fits a single tile) — and certainly no (N, N) matrix or
     all-gathered candidate buffer.
  3. **Sharded neighbor exploring** — `neighbor_explore.
     sharded_explore_round` exchanges the (N, K) graph (output-sized),
     derives forward + reverse neighbor candidates per local row, and
     fills candidate distances with a second ring pass over point slabs.

Set ``LargeVisConfig(distributed=True)`` (optionally ``data_shards``) to
route `build_knn_graph` / `largevis()` through this pipeline, or call
:func:`build_knn_graph_sharded` with an explicit mesh.  On CPU, expose
host devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knn as knn_lib
from repro.core.neighbor_explore import sharded_explore_round
from repro.kernels import ops
from repro.kernels.ref import INVALID_DIST
from repro.runtime import sharding as sh
from jax import shard_map


@functools.lru_cache(maxsize=32)
def _make_sharded_fn(mesh, axis: str, *, n_shards: int, n_real: int, k: int,
                     n_trees: int, depth: int, iters: int, sample: int,
                     impl: str = "auto"):
    """jit'd shard_map pipeline for fixed static shapes/hyper-params."""
    from jax.sharding import PartitionSpec as P
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def body(x_loc, ids_loc, proj, seed):
        n_loc = x_loc.shape[0]
        dev = jax.lax.axis_index(axis)

        # ---- per-shard projection codes (shared hyperplanes) ----------
        if n_trees:
            codes = knn_lib.hash_codes(x_loc, None, n_trees, depth,
                                       proj=proj)
        else:                                   # exact mode: no bucketing
            codes = jnp.zeros((n_loc, 1), jnp.int32)

        # ---- ring pass: fused streaming top-k, state carried across
        # ring steps (kernels/knn_topk.py topk_sqdist: each remote slab
        # folds straight into the running (n_loc, k) best state — the
        # per-step re-merge concat is gone and distance/bucket-match
        # work is bounded by the op's (bm, bn) tiles, never a full
        # re-merged candidate buffer; with n_loc below the tile size a
        # single (n_loc, n_loc) tile is the whole step, same as before)
        def ring_step(_, carry):
            bi, bd, rx, rc, rid = carry
            rid_eff = jnp.where(rid >= n_real, -1, rid)    # padding -> mask
            bi, bd = ops.topk_sqdist(
                x_loc, rx, k, a_ids=ids_loc, b_ids=rid_eff,
                codes_a=codes if n_trees else None,
                codes_b=rc if n_trees else None,
                init_ids=bi, init_dists=bd, impl=impl)
            rx = jax.lax.ppermute(rx, axis, perm)
            rc = jax.lax.ppermute(rc, axis, perm)
            rid = jax.lax.ppermute(rid, axis, perm)
            return bi, bd, rx, rc, rid

        bi = jnp.full((n_loc, k), -1, jnp.int32)
        bd = jnp.full((n_loc, k), INVALID_DIST, jnp.float32)
        bi, bd, _, _, _ = jax.lax.fori_loop(
            0, n_shards, ring_step, (bi, bd, x_loc, codes, ids_loc))

        # ---- sharded neighbor exploring -------------------------------
        for it in range(iters):
            ikey = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(seed[0]), dev), it)
            bi, bd = sharded_explore_round(
                x_loc, ids_loc, bi, bd, axis=axis, n_shards=n_shards,
                n_real=n_real, key=ikey, sample=sample)
        return bi, bd

    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(), P()),
        out_specs=(P(axis, None), P(axis, None)), check_vma=False)
    return jax.jit(sharded)


def build_knn_graph_sharded(x: jax.Array, key, cfg, *, mesh=None,
                            axis: str = "data", fault=None):
    """Sharded version of `knn.build_knn_graph`: (idx (N,K), sqdist (N,K)).

    ``mesh`` defaults to a 1-D "data" mesh over ``cfg.data_shards``
    devices (0 = all available).  N need not divide the shard count —
    points are zero-padded and padded ids are suppressed by the tile
    masks before any top-k.

    ``fault``: the per-shard ``knn_ring_step:<s>`` sites fire once per
    shard before the ring dispatch; an injected shard fault surfaces as
    ``ShardFailedError`` (stage ``"knn"``) for the mesh-recovery loop.
    """
    if mesh is None:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(getattr(cfg, "data_shards", 0))
    n_shards = mesh.shape[axis]
    N, d = x.shape
    k = min(cfg.n_neighbors, N - 1)
    depth = cfg.tree_depth or knn_lib._auto_depth(N, cfg.leaf_target)
    # shared contiguous-block row layout (runtime/sharding.py): every
    # sharded stage downstream pads rows the same way, so graph tensors
    # line up shard-for-shard without repartitioning between stages
    xp = sh.pad_rows(x.astype(jnp.float32), n_shards)
    ids = jnp.arange(xp.shape[0], dtype=jnp.int32)
    kp, ks = jax.random.split(key)
    proj = jax.random.normal(kp, (d, max(cfg.n_trees, 1) * depth),
                             jnp.float32)
    seed = jax.random.randint(ks, (1,), 0, np.int32(2**31 - 1))
    fn = _make_sharded_fn(
        mesh, axis, n_shards=n_shards, n_real=N, k=k, n_trees=cfg.n_trees,
        depth=depth, iters=cfg.n_explore_iters, sample=cfg.explore_sample,
        impl=getattr(cfg, "knn_impl", "auto"))
    if fault is not None:
        from repro.runtime.fault_tolerance import fire_per_shard
        fire_per_shard(fault, "knn_ring_step", n_shards, stage="knn")
    idx, dist = fn(xp, ids, proj, seed)
    return idx[:N], dist[:N]
