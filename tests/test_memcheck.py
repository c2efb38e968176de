"""Peak-memory invariants for every `largevis(distributed=True)` stage.

Each test lowers a pipeline stage from ``jax.ShapeDtypeStruct`` specs at
paper-adjacent scale (N=250k tier-1; N=1M behind ``-m slow``) and runs
the shared ``memcheck.check_stage`` harness: no single buffer above a
stage-specific linear bound, and no buffer shaped like the forbidden
O(N·K·K) candidate-coordinate blow-up or O(N²/P) distance matrix — in
both the StableHLO lowering and the XLA-optimized HLO.

Lowering from specs allocates nothing, so checking million-point shapes
is cheap; the harness proves the *compiled program* cannot allocate the
forbidden temporary, which is stronger than observing one run's RSS.

The tests adapt to the visible device count (``make_data_mesh(0)``):
under the CI mesh-smoke job (4 host devices) the same invariants are
checked against the real per-device partitioning.
"""
import jax
import jax.numpy as jnp
import pytest

import memcheck

from repro.core import knn_sharded
from repro.core import perplexity
from repro.core import sampler as S
from repro.launch.mesh import make_data_mesh
from repro.launch.steps import make_largevis_step_sharded
from repro.runtime import sharding as sh

N = 250_000
K = 15
D = 16
SDS = jax.ShapeDtypeStruct
F32, I32 = jnp.float32, jnp.int32


def _padded(n, mesh):
    p = mesh.shape["data"]
    return sh.rows_per_shard(n, p) * p


def _graph_stage_checks(n):
    """Run the calibrate / symmetrize / sampler-build invariants at n."""
    mesh = make_data_mesh(0)
    np_ = _padded(n, mesh)
    nk = n * K * 4                                   # one (N, K) f32
    e = n * K                                        # directed edge count

    memcheck.check_stage(
        f"calibrate_p[n={n}]",
        perplexity.calibrate_p.lower(SDS((n, K), F32), 50.0, iters=64),
        limit_bytes=4 * nk, forbidden=[(n, K, K)],
        temp_limit_bytes=8 * nk)

    memcheck.check_stage(
        f"calibrate_p_sharded[n={n}]",
        perplexity._make_calibrate_sharded(mesh, "data", 64).lower(
            SDS((np_, K), F32), SDS((), F32)),
        limit_bytes=4 * nk, forbidden=[(n, K, K)],
        temp_limit_bytes=8 * nk)

    memcheck.check_stage(
        f"symmetrize[n={n}]",
        perplexity._symmetrize_scan.lower(SDS((n, K), I32),
                                          SDS((n, K), F32), tile=4096),
        limit_bytes=4 * nk, forbidden=[(n, K, K)],
        temp_limit_bytes=8 * nk)

    tile = int(min(4096, sh.rows_per_shard(n, mesh.shape["data"])))
    memcheck.check_stage(
        f"symmetrize_sharded[n={n}]",
        perplexity._make_symmetrize_sharded(mesh, "data", n, tile).lower(
            SDS((np_, K), I32), SDS((np_, K), F32), SDS((np_,), I32)),
        limit_bytes=4 * nk, forbidden=[(n, K, K)],
        temp_limit_bytes=8 * nk)

    # alias builds walk the graph in chunks: the working set beyond the
    # (E,) tables is a chunk's, never E * K
    with jax.enable_x64(True):
        memcheck.check_stage(
            f"edge_sampler[n={n}]",
            S._build_edge_sampler_device.lower(
                SDS((n, K), I32), SDS((n, K), F32)),
            limit_bytes=6 * e * 8, forbidden=[(n, K, K)],
            temp_limit_bytes=16 * e * 8)
        memcheck.check_stage(
            f"neg_sampler[n={n}]",
            S._build_negative_sampler_device.lower(
                SDS((n, K), I32), SDS((n, K), F32), power=0.75),
            limit_bytes=6 * e * 8, forbidden=[(n, K, K)],
            temp_limit_bytes=16 * e * 8)
        memcheck.check_stage(
            f"sampler_sharded[n={n}]",
            S._make_sharded_builder_fn(mesh, "data", n, 0.75).lower(
                SDS((np_, K), I32), SDS((np_, K), F32), SDS((np_,), I32)),
            limit_bytes=6 * e * 8, forbidden=[(n, K, K)],
            temp_limit_bytes=16 * e * 8)


def _knn_stage_check(n):
    """KNN ring + explore: candidate *id/distance* tables are the
    accepted per-shard working set (O(n_loc * K^2) ints), but candidate
    *coordinates* (the extra ×d) and any (n, n) distance matrix are
    forbidden — the explore ring tiles its gathers instead."""
    mesh = make_data_mesh(0)
    p = mesh.shape["data"]
    n_loc = sh.rows_per_shard(n, p)
    c = K * K + K
    fn = knn_sharded._make_sharded_fn(
        mesh, "data", n_shards=p, n_real=n, k=K, n_trees=4, depth=8,
        iters=1, sample=0, impl="auto")
    memcheck.check_stage(
        f"knn_ring[n={n},p={p}]",
        fn.lower(SDS((n_loc * p, D), F32), SDS((n_loc * p,), I32),
                 SDS((D, 32), F32), SDS((1,), I32)),
        limit_bytes=n_loc * c * D * 4 // 3,
        forbidden=[(n_loc, c, D), (n_loc, n_loc * p), (n, n)])


def _layout_stage_check(n):
    """Sharded local-SGD step: tables + y only, no (B, n) or (n, n)."""
    mesh = make_data_mesh(0)
    p = mesh.shape["data"]
    e = sh.rows_per_shard(n, p) * p * K
    batch = 4096
    step, specs, in_sh, out_sh = make_largevis_step_sharded(
        mesh, n_nodes=n, n_edges=e, batch=batch)
    memcheck.check_stage(
        f"layout_step_sharded[n={n},p={p}]",
        jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(0,)).lower(*specs),
        limit_bytes=4 * e * 4, forbidden=[(batch, n), (n, n)])


def test_graph_stage_memory_invariants():
    _graph_stage_checks(N)


def test_knn_stage_memory_invariants():
    _knn_stage_check(N)


def test_layout_stage_memory_invariants():
    _layout_stage_check(N)


@pytest.mark.slow
def test_stage_memory_invariants_1m():
    """The acceptance-criteria scale: one million points."""
    _graph_stage_checks(1_000_000)
    _knn_stage_check(1_000_000)
    _layout_stage_check(1_000_000)
