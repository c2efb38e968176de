"""Fully-fused edge-step kernel (kernels/largevis_step.py) + its routing.

Covers: bit-reproducibility against the pure-jnp oracle (including batches
dense with duplicate i/j/neg indices, and a numpy sequential loop that pins
the canonical per-edge update order), edge-tile invariance, tile padding
for odd (collision-capped) batches and multi-tile batches, collision-masked
negatives leaving their target rows bitwise untouched, trajectory parity
fused-vs-split through all three drivers (scan engine, per-step loop,
shard_map local-SGD), and HLO checks that the fused path materializes no
gather/concat intermediate buffers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hlo_checks

from repro.configs.largevis_default import LargeVisConfig, RoutingConfig
from repro.core import layout as layout_lib
from repro.core import sampler as sampler_lib
from repro.kernels import ops, ref
from repro.kernels.largevis_step import fused_edge_step
from repro.launch.mesh import make_mesh

KEY = jax.random.key(11)
# the kernel (interpret mode off TPU); "auto" runs its oracle off TPU
FUSED = RoutingConfig(layout_step="fused")
SPLIT = RoutingConfig(layout_step="split")
GAMMA, A, CLIP = 7.0, 1.0, 5.0

# the bitwise contract is against the *compiled* oracle: eager op-by-op
# execution skips the multiply-add fusion XLA applies inside any jit
# (including the kernel's), which shifts values by ~1 ulp
_ref_step = jax.jit(ref.fused_edge_step_ref,
                    static_argnames=("gamma", "a", "clip", "eps"))


def _rand_batch(N, B, M, s=2, seed=0):
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 5)
    y = jax.random.normal(ks[0], (N, s), jnp.float32)
    i = jax.random.randint(ks[1], (B,), 0, N)
    j = jax.random.randint(ks[2], (B,), 0, N)
    negs = jax.random.randint(ks[3], (B, M), 0, N)
    mask = ((negs != i[:, None]) & (negs != j[:, None])).astype(jnp.float32)
    return y, i, j, negs, mask


@pytest.mark.parametrize("N,B,tile", [
    (300, 64, 64),       # one edge tile (tiles round up to 128 edges)
    (300, 37, 16),       # odd batch -> padded remainder tile
    (500, 1500, 512),    # multi-tile grid + padding (T=3)
])
def test_kernel_matches_ref_oracle_bitwise(N, B, tile):
    y, i, j, negs, mask = _rand_batch(N, B, 5)
    got = fused_edge_step(y, i, j, negs, mask, 0.37, gamma=GAMMA, a=A,
                          clip=CLIP, tile=tile, interpret=True)
    want = _ref_step(y, i, j, negs, mask, 0.37, gamma=GAMMA, a=A, clip=CLIP)
    assert np.array_equal(np.asarray(got), np.asarray(want)), float(
        np.abs(np.asarray(got) - np.asarray(want)).max())


def test_duplicate_indices_accumulate_in_canonical_order():
    """A tiny embedding makes every batch dense with duplicates (the same
    row drawn as i, j and negative, many times over).  The kernel, the ref
    oracle and a numpy sequential loop in the canonical per-edge order
    [i_e, j_e, negs_e,0..M-1] must all agree bitwise — accumulation, not
    last-write-wins, and one ordering contract everywhere."""
    N, B, M, s = 8, 128, 5, 2
    y, i, j, negs, mask = _rand_batch(N, B, M, s, seed=3)
    lr = 0.21
    got = fused_edge_step(y, i, j, negs, mask, lr, gamma=GAMMA, a=A,
                          clip=CLIP, tile=32, interpret=True)
    want = _ref_step(y, i, j, negs, mask, lr, gamma=GAMMA, a=A, clip=CLIP)
    assert np.array_equal(np.asarray(got), np.asarray(want))

    # numpy sequential loop in the canonical order: pins accumulate-not-
    # overwrite semantics (allclose, not bitwise — numpy does not fuse
    # multiply-adds the way the compiled grads do)
    gi, gj, gneg = ref.largevis_grads_ref(y[i], y[j], y[negs], gamma=GAMMA,
                                          a=A, clip=CLIP, neg_mask=mask)
    yn = np.asarray(y).copy()
    ui = np.asarray(-jnp.float32(lr) * gi)
    uj = np.asarray(-jnp.float32(lr) * gj)
    un = np.asarray(-jnp.float32(lr) * gneg)
    i_n, j_n, n_n = np.asarray(i), np.asarray(j), np.asarray(negs)
    for e in range(B):
        yn[i_n[e]] += ui[e]
        yn[j_n[e]] += uj[e]
        for m in range(M):
            yn[n_n[e, m]] += un[e, m]
    np.testing.assert_allclose(np.asarray(got), yn, atol=1e-4, rtol=1e-4)


def test_edge_tiles_bitwise_identical():
    """The edge tile only sets how many row ids sit in SMEM per grid
    step: every tiling applies the same update stream."""
    y, i, j, negs, mask = _rand_batch(400, 700, 5, seed=5)
    outs = [np.asarray(fused_edge_step(y, i, j, negs, mask, 0.5, gamma=GAMMA,
                                       a=A, clip=CLIP, tile=t,
                                       interpret=True))
            for t in (128, 256, 1024)]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


def test_masked_negatives_leave_rows_untouched():
    """A collision-masked negative contributes exactly zero: rows that are
    only ever referenced through masked negatives keep their bits."""
    N, B, M = 50, 16, 5
    ks = jax.random.split(KEY, 4)
    y = jax.random.normal(ks[0], (N, 2), jnp.float32)
    # edges live entirely in rows [0, 40); negatives all point at row 47,
    # every one masked out
    i = jax.random.randint(ks[1], (B,), 0, 40)
    j = jax.random.randint(ks[2], (B,), 0, 40)
    negs = jnp.full((B, M), 47, jnp.int32)
    mask = jnp.zeros((B, M), jnp.float32)
    out = fused_edge_step(y, i, j, negs, mask, 0.8, gamma=GAMMA, a=A,
                          clip=CLIP, interpret=True)
    assert np.array_equal(np.asarray(out[47]), np.asarray(y[47]))
    # the positive-pair updates still landed
    assert not np.array_equal(np.asarray(out[:40]), np.asarray(y[:40]))
    # and rows nobody references at all keep their bits too
    assert np.array_equal(np.asarray(out[40:47]), np.asarray(y[40:47]))
    assert np.array_equal(np.asarray(out[48:]), np.asarray(y[48:]))


def test_padding_rows_are_noops():
    """Tile padding points padded edges at row 0 and never applies them;
    a batch whose real edges avoid row 0 must leave row 0 bitwise intact."""
    N, B, M = 64, 13, 5          # 13 pads up to one 128-edge tile
    ks = jax.random.split(KEY, 4)
    y = jax.random.normal(ks[0], (N, 2), jnp.float32)
    i = jax.random.randint(ks[1], (B,), 1, N)
    j = jax.random.randint(ks[2], (B,), 1, N)
    negs = jax.random.randint(ks[3], (B, M), 1, N)
    mask = ((negs != i[:, None]) & (negs != j[:, None])).astype(jnp.float32)
    out = fused_edge_step(y, i, j, negs, mask, 0.9, gamma=GAMMA, a=A,
                          clip=CLIP, tile=16, interpret=True)
    assert np.array_equal(np.asarray(out[0]), np.asarray(y[0]))


def test_ops_impl_routes():
    """ops.largevis_edge_step: "fused"/"pallas" hit the kernel, "ref" and
    (off TPU) "auto" hit the oracle, and all agree bitwise (compiled, as
    the step bodies use them — eager execution skips XLA's multiply-add
    fusion)."""
    y, i, j, negs, mask = _rand_batch(200, 96, 5, seed=7)
    outs = [np.asarray(jax.jit(
        lambda *args: ops.largevis_edge_step(
            *args, gamma=GAMMA, a=A, clip=CLIP, impl=impl)
    )(y, i, j, negs, mask, 0.3)) for impl in ("fused", "pallas", "ref",
                                              "auto")]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


def test_fused_step_supported_on_cpu():
    # interpret mode has no VMEM residency bound
    assert ops.fused_step_supported(10_000_000, 2)


# ---------------------------------------------------------------------------
# driver-level trajectory parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def odd_graph():
    """603 nodes -> collision-capped batch 301 (odd): every dispatch runs
    the kernel's padded-tile path."""
    rng = np.random.default_rng(9)
    n, k = 603, 8
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    es = sampler_lib.build_edge_sampler(idx, w)
    ns = sampler_lib.build_negative_sampler(idx, w)
    return n, es, ns


def _run(n, es, ns, **over):
    over = {"samples_per_node": 80, "batch_size": 4096, **over}
    return layout_lib.run_layout(KEY, es, ns, n, LargeVisConfig(**over))


def test_scan_driver_parity_fused_vs_split(odd_graph):
    n, es, ns = odd_graph
    assert layout_lib._collision_capped_batch(4096, n) % 2 == 1
    r_fused = _run(n, es, ns, routing=FUSED)
    r_split = _run(n, es, ns, routing=SPLIT)
    assert r_fused.steps == r_split.steps
    a, b = np.asarray(r_fused.y), np.asarray(r_split.y)
    assert np.array_equal(a, b), float(np.abs(a - b).max())


def test_loop_driver_parity_fused_vs_split(odd_graph):
    n, es, ns = odd_graph
    r_fused = _run(n, es, ns, routing=FUSED, steps_per_dispatch=1,
                   samples_per_node=20)
    r_split = _run(n, es, ns, routing=SPLIT, steps_per_dispatch=1,
                   samples_per_node=20)
    assert np.array_equal(np.asarray(r_fused.y), np.asarray(r_split.y))


def test_local_sgd_driver_parity_fused_vs_split(odd_graph):
    n, es, ns = odd_graph
    mesh = make_mesh((1,), ("data",))
    cfg_f = LargeVisConfig(sync_every=4, samples_per_node=32, batch_size=256,
                           routing=FUSED)
    cfg_s = dataclasses.replace(cfg_f, routing=SPLIT)
    r_f = layout_lib.run_layout_local_sgd(KEY, es, ns, n, cfg_f, mesh)
    r_s = layout_lib.run_layout_local_sgd(KEY, es, ns, n, cfg_s, mesh)
    assert np.array_equal(np.asarray(r_f.y), np.asarray(r_s.y))


# ---------------------------------------------------------------------------
# HLO: the fused path materializes no gather/concat intermediates
# ---------------------------------------------------------------------------

def test_fused_hlo_emits_no_split_buffers():
    """The split step materializes a (B*(2+M), s) concatenated update
    buffer (and flattened (B, M*s) kernel operands on the Pallas-grads
    path); the fused lowering must contain neither."""
    n, B, M, s = 2000, 256, 5, 2
    rng = np.random.default_rng(1)
    idx = rng.integers(0, n, (n, 8)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, 8)).astype(np.float32)
    es = sampler_lib.build_edge_sampler(idx, w)
    ns = sampler_lib.build_negative_sampler(idx, w)
    cfg = LargeVisConfig(n_negatives=M, batch_size=B)
    kwargs = layout_lib._step_kwargs(es, ns, n, cfg, B)
    y0 = jax.random.normal(KEY, (n, s), jnp.float32)

    def lower(fused):
        kw = dict(kwargs, layout_step="fused" if fused else "split")
        return layout_lib.layout_step.lower(
            y0, KEY, jnp.float32(0.1), **kw).as_text()

    concat_buf = ((2 + M) * B, s)
    flat_neg = (B, M * s)
    hlo_fused = lower(True)
    hlo_checks.assert_no_buffer(hlo_fused, concat_buf, "f32",
                                what="concatenated update buffer")
    hlo_checks.assert_no_buffer(hlo_fused, flat_neg, "f32",
                                what="flattened negative operand")
    # contrast: the split path really does build the concat update buffer
    hlo_split = lower(False)
    assert hlo_checks.has_buffer(hlo_split, concat_buf, "f32")
