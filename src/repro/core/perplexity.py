"""Edge weights for the KNN graph (paper Eqn 1-2, same scheme as t-SNE).

sigma_i is calibrated per node so the conditional distribution p_{.|i} over
its K neighbors has a target perplexity u: a fixed-iteration vectorized
bisection on beta_i = 1/(2 sigma_i^2) — all N rows in parallel (the paper's
sequential per-point search is embarrassingly parallel).

Symmetrization w_ij = (p_{j|i} + p_{i|j}) / 2N needs the reverse weight
p_{i|j}: for each directed edge (i, j) we look up i inside knn(j) — a tiled
(T, K, K) gather + compare.  The tile loop is a ``lax.scan`` inside ONE
module-level jit (``_symmetrize_scan``), so ``symmetrize`` compiles once
per (N, K, tile) and never re-traces per call or per tile — the earlier
form re-created a ``jax.jit`` wrapper on every call and dispatched one
device round trip per tile.

Distributed mode: both stages also come as shard_map drivers over the
1-D "data" mesh (``calibrate_p_sharded`` / ``symmetrize_sharded`` /
``edge_weights_sharded``), sharing the row layout of the sharded KNN
ring (``runtime/sharding.py::rows_per_shard``).  Calibration is
embarrassingly row-parallel (every op in ``_calibrate_rows`` is
row-local), so sharding it is a pure row split.  Symmetrization needs
the reverse lookup p_{i|j}, i.e. other shards' rows: the (N, K) graph
and p table are exchanged with ``all_gather(tiled=True)`` — the same
output-sized exchange ``neighbor_explore.sharded_explore_round``
performs — while the (T, K, K) reverse-gather temporaries stay bounded
by the row tile, never O(N*K*K).  Both sharded stages run the identical
per-row arithmetic as their single-device forms, so results are
**bitwise equal** to the single-device oracle (asserted by
``tests/test_graph_sharded.py`` and the hypothesis property test).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.runtime import sharding as sh
from jax import shard_map


def _calibrate_rows(knn_sqdist: jax.Array, perplexity, iters: int):
    """Row-local bisection body shared by the single-device jit and the
    shard_map driver (each shard calls this on its own row block —
    every op below reduces over axis=1 only, so a row's result is
    independent of which rows it is blocked with)."""
    d2 = knn_sqdist.astype(jnp.float32)
    d2 = d2 - d2.min(axis=1, keepdims=True)               # stability shift
    target = jnp.log(perplexity)                          # nats

    def entropy(beta):
        logits = -beta[:, None] * d2
        logz = jax.nn.logsumexp(logits, axis=1)
        p = jnp.exp(logits - logz[:, None])
        return logz + beta * jnp.sum(p * d2, axis=1), p

    def body(carry, _):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        h, _ = entropy(mid)
        too_flat = h > target                              # entropy high -> increase beta
        lo = jnp.where(too_flat, mid, lo)
        hi = jnp.where(too_flat, hi, mid)
        return (lo, hi), None

    n = d2.shape[0]
    lo = jnp.zeros((n,), jnp.float32)
    hi = jnp.full((n,), 1e5, jnp.float32) / (
        jnp.maximum(jnp.mean(d2, axis=1), 1e-8))
    (lo, hi), _ = jax.lax.scan(body, (lo, hi), None, length=iters)
    _, p = entropy(0.5 * (lo + hi))
    return p


@functools.partial(jax.jit, static_argnames=("iters",))
def calibrate_p(knn_sqdist: jax.Array, perplexity: float,
                iters: int = 64) -> jax.Array:
    """Row-stochastic p_{j|i} (N, K) at the target perplexity (Eqn 1)."""
    return _calibrate_rows(knn_sqdist, perplexity, iters)


def _reverse_p_tile(knn_idx, p, rows):
    """p_{i|j} for each edge (i, j=knn[i][k]) in a tile of rows."""
    nbrs = knn_idx[rows]                                  # (T, K)
    back = knn_idx[nbrs]                                  # (T, K, K) = knn(j)
    hit = back == rows[:, None, None]                     # where knn(j) == i
    pj = p[nbrs]                                          # (T, K, K) = p_{.|j}
    return jnp.sum(jnp.where(hit, pj, 0.0), axis=-1)      # (T, K)


def _reverse_rows_scan(knn_idx, p, rows, *, tile: int):
    """Reverse weights p_{i|j} for ``rows``, scanned in tiles of ``tile``.

    Rows are padded to a whole number of tiles by repeating the last row
    index; padded outputs are sliced off.  Each real row sees the
    identical per-row gather/compare/sum regardless of the tile grouping
    or which rows it shares a call with — the bitwise-equality basis for
    the sharded driver, whose shards run this very function on their own
    row blocks against the gathered table."""
    n_rows = rows.shape[0]
    K = knn_idx.shape[1]
    n_tiles = -(-n_rows // tile)
    pad = n_tiles * tile - n_rows
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.broadcast_to(rows[-1:], (pad,))])

    def body(_, rows_t):
        return None, _reverse_p_tile(knn_idx, p, rows_t)

    _, rev = jax.lax.scan(body, None, rows.reshape(n_tiles, tile))
    return rev.reshape(n_tiles * tile, K)[:n_rows]


@functools.partial(jax.jit, static_argnames=("tile",))
def _symmetrize_scan(knn_idx: jax.Array, p: jax.Array, *,
                     tile: int) -> jax.Array:
    """One compiled computation: scan `_reverse_p_tile` over row tiles."""
    N = knn_idx.shape[0]
    rows = jnp.arange(N, dtype=jnp.int32)
    rev = _reverse_rows_scan(knn_idx, p, rows, tile=tile)
    return (p + rev) / (2.0 * N)


def symmetrize(knn_idx: jax.Array, p: jax.Array, *,
               tile: int | None = None) -> jax.Array:
    """w_ij = (p_{j|i} + p_{i|j}) / (2N) per directed edge slot (Eqn 2).

    ``tile`` (row-tile of the scanned reverse gather) defaults to the
    autotuner's choice — the reverse weights are identical for any tile
    grouping (see ``_reverse_rows_scan``), so this is purely a
    performance knob.  ``AUTOTUNE=off`` reproduces the legacy 4096."""
    if tile is None:
        from repro.runtime import autotune
        N, K = knn_idx.shape
        tile = autotune.get("symmetrize", dict(n=N, k=K),
                            autotune.legacy_default("symmetrize"))["tile"]
    return _symmetrize_scan(knn_idx, p, tile=int(min(tile, knn_idx.shape[0])))


def edge_weights(knn_idx, knn_sqdist, perplexity: float, *,
                 iters: int = 64) -> jax.Array:
    p = calibrate_p(knn_sqdist, perplexity, iters=iters)
    return symmetrize(knn_idx, p)


def perplexity_of(p: jax.Array) -> jax.Array:
    """Realized perplexity per row (for validation)."""
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=1)
    return jnp.exp(h)


# ---------------------------------------------------------------------------
# Sharded drivers (1-D "data" mesh — same row layout as the KNN ring)
# ---------------------------------------------------------------------------

def _default_mesh(mesh, cfg_shards: int = 0):
    if mesh is not None:
        return mesh
    from repro.launch.mesh import make_data_mesh
    return make_data_mesh(cfg_shards)


@functools.lru_cache(maxsize=32)
def _make_calibrate_sharded(mesh, axis: str, iters: int):
    """jit'd shard_map row-parallel calibration (cached per mesh/iters —
    shapes re-specialize inside the jit cache)."""
    from jax.sharding import PartitionSpec as P

    def body(d2_loc, perp):
        return _calibrate_rows(d2_loc, perp, iters)

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis, None), P()),
                   out_specs=P(axis, None), check_vma=False)
    return jax.jit(fn)


def calibrate_p_sharded(knn_sqdist, perplexity: float, *, iters: int = 64,
                        mesh=None, axis: str = "data",
                        fault=None) -> jax.Array:
    """Row-parallel :func:`calibrate_p` under shard_map.

    Rows pad to a shard multiple (zero rows bisect harmlessly and are
    sliced off); every surviving row is bitwise-equal to the
    single-device result because the body is row-local.  ``fault``
    fires the per-shard ``calibrate_shard:<s>`` sites before the
    dispatch (shard faults -> ``ShardFailedError``, stage
    ``"calibrate"``)."""
    mesh = _default_mesh(mesh)
    n_shards = mesh.shape[axis]
    N = knn_sqdist.shape[0]
    d2 = sh.pad_rows(jnp.asarray(knn_sqdist), n_shards)
    fn = _make_calibrate_sharded(mesh, axis, iters)
    if fault is not None:
        from repro.runtime.fault_tolerance import fire_per_shard
        fire_per_shard(fault, "calibrate_shard", n_shards, stage="calibrate")
    return fn(d2, jnp.float32(perplexity))[:N]


@functools.lru_cache(maxsize=32)
def _make_symmetrize_sharded(mesh, axis: str, n_real: int, tile: int):
    from jax.sharding import PartitionSpec as P

    def body(idx_loc, p_loc, rows_loc):
        # the (Np, K) graph + p table are output-sized — the same
        # exchange sharded_explore_round performs; the (T, K, K)
        # reverse-gather temporaries stay bounded by the row tile
        g_idx = jax.lax.all_gather(idx_loc, axis, tiled=True)
        g_p = jax.lax.all_gather(p_loc, axis, tiled=True)
        rev = _reverse_rows_scan(g_idx, g_p, rows_loc, tile=tile)
        return (p_loc + rev) / (2.0 * n_real)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis, None), P(axis, None), P(axis)),
                   out_specs=P(axis, None), check_vma=False)
    return jax.jit(fn)


def symmetrize_sharded(knn_idx, p, *, tile: int = 4096, mesh=None,
                       axis: str = "data", fault=None) -> jax.Array:
    """Sharded :func:`symmetrize`: each shard computes its own rows'
    reverse weights against the all-gathered graph.

    Padded graph rows hold index 0 with zero p — no real row ever
    gathers from them (real knn entries are < N), so per-row results
    are bitwise-equal to the single-device scan.  ``fault`` fires the
    per-shard ``symmetrize_exchange:<s>`` sites before the all-gather
    dispatch (shard faults -> ``ShardFailedError``, stage
    ``"symmetrize"``)."""
    mesh = _default_mesh(mesh)
    n_shards = mesh.shape[axis]
    N = knn_idx.shape[0]
    idx_p = sh.pad_rows(jnp.asarray(knn_idx, jnp.int32), n_shards)
    p_p = sh.pad_rows(jnp.asarray(p, jnp.float32), n_shards)
    rows = jnp.arange(idx_p.shape[0], dtype=jnp.int32)
    tile = int(min(tile, sh.rows_per_shard(N, n_shards)))
    fn = _make_symmetrize_sharded(mesh, axis, N, tile)
    if fault is not None:
        from repro.runtime.fault_tolerance import fire_per_shard
        fire_per_shard(fault, "symmetrize_exchange", n_shards,
                       stage="symmetrize")
    return fn(idx_p, p_p, rows)[:N]


def edge_weights_sharded(knn_idx, knn_sqdist, perplexity: float, *,
                         iters: int = 64, mesh=None,
                         axis: str = "data", fault=None) -> jax.Array:
    """Sharded :func:`edge_weights`: calibration + symmetrization on the
    data mesh, bitwise-equal to the single-device composition.
    ``fault`` threads into both sharded stages' per-shard sites."""
    mesh = _default_mesh(mesh)
    p = calibrate_p_sharded(knn_sqdist, perplexity, iters=iters, mesh=mesh,
                            axis=axis, fault=fault)
    return symmetrize_sharded(knn_idx, p, mesh=mesh, axis=axis, fault=fault)
