"""Kernel autotuner (runtime/autotune.py) + embedding-tiled fused step.

Covers: versioned cache round-trip and wholesale version rejection, mode
resolution (``off`` returns the legacy default verbatim and ignores every
cache; ``cache`` consults user cache then the committed table, with
``default`` acting as a key whitelist), pow2 shape bucketing, the sweep's
paired adopt rule (beat the incumbent by > 3 % or keep the default), the
``sample > 0`` key-stream gate on neighbor_explore, bitwise equality of
the embedding-tiled fused step against the untiled kernel and the ref
oracle (multi-tile, odd N, duplicate-dense batches, frozen rows, per-edge
lr), an HLO check that the tiled lowering holds no second full-embedding
temporary beyond the aliased in/out, and the lifted size bound on
``ops.fused_step_supported``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hlo_checks

from repro.kernels import ops, ref
from repro.kernels.largevis_step import fused_edge_step
from repro.runtime import autotune

BACKEND = jax.default_backend()
GAMMA, A, CLIP = 7.0, 1.0, 5.0

_ref_step = jax.jit(ref.fused_edge_step_ref,
                    static_argnames=("gamma", "a", "clip", "eps", "n_frozen"))


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Isolated cache dir, no committed table, guaranteed mode restore."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    monkeypatch.setattr(autotune, "_defaults_path",
                        lambda: tmp_path / "no_committed_table.json")
    autotune._mem.clear()
    yield tmp_path
    autotune.set_mode(None)
    autotune._mem.clear()


# ---------------------------------------------------------------------------
# cache plumbing + mode resolution
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_key_whitelist(tuner):
    """A written entry is served back — but only through the default's
    keys, so a cached config can never leak an unknown kwarg into a call
    site with a different signature."""
    autotune.set_mode("cache")
    shape = dict(n=8000, k=20)
    key = autotune.bucket_key("symmetrize", shape)
    autotune._write_entry(BACKEND, key,
                          {"config": dict(tile=512, rogue_kw=7)})
    autotune._mem.clear()
    got = autotune.get("symmetrize", shape, dict(tile=4096))
    assert got == dict(tile=512)          # tuned value in, rogue key out


def test_version_mismatch_rejected_wholesale(tuner):
    autotune.set_mode("cache")
    shape = dict(n=8000, k=20)
    key = autotune.bucket_key("symmetrize", shape)
    path = autotune._cache_path(BACKEND)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({
        "version": autotune.AUTOTUNE_VERSION + 1,
        "entries": {key: {"config": dict(tile=512)}}}))
    assert autotune._read_entries(path) == {}
    assert autotune.get("symmetrize", shape, dict(tile=4096)) == \
        dict(tile=4096)
    # corrupt file: same answer, no crash
    path.write_text("{not json")
    autotune._mem.clear()
    assert autotune.get("symmetrize", shape, dict(tile=4096)) == \
        dict(tile=4096)


def test_off_mode_returns_default_verbatim(tuner):
    """``off`` is the bitwise CI anchor: a poisoned cache entry must not
    reach the call site."""
    shape = dict(n=8000, k=20)
    key = autotune.bucket_key("symmetrize", shape)
    autotune._write_entry(BACKEND, key, {"config": dict(tile=13)})
    autotune.set_mode("off")
    assert autotune.get("symmetrize", shape, dict(tile=4096)) == \
        dict(tile=4096)
    autotune.set_mode("cache")
    assert autotune.get("symmetrize", shape, dict(tile=4096)) == \
        dict(tile=13)


def test_user_cache_wins_over_committed_table(tuner, monkeypatch):
    autotune.set_mode("cache")
    shape = dict(n=8000, k=20)
    key = autotune.bucket_key("symmetrize", shape)
    table = tuner / "table.json"
    table.write_text(json.dumps({
        "version": autotune.AUTOTUNE_VERSION,
        "entries": {key: {"config": dict(tile=256)}}}))
    monkeypatch.setattr(autotune, "_defaults_path", lambda: table)
    assert autotune.get("symmetrize", shape, dict(tile=4096)) == \
        dict(tile=256)                     # committed table on user miss
    autotune._write_entry(BACKEND, key, {"config": dict(tile=512)})
    autotune._mem.clear()
    assert autotune.get("symmetrize", shape, dict(tile=512)) == \
        dict(tile=512)                     # user cache wins


def test_shape_bucketing_pow2():
    assert autotune.bucket_shape(dict(n=1000, k=20)) == dict(n=1024, k=32)
    k_a = autotune.bucket_key("k", dict(n=1000), backend="cpu")
    k_b = autotune.bucket_key("k", dict(n=1024), backend="cpu")
    k_c = autotune.bucket_key("k", dict(n=1025), backend="cpu")
    assert k_a == k_b != k_c
    assert k_a.startswith("cpu/k/")


def test_legacy_default_registry():
    assert autotune.legacy_default("largevis_edge_step") == \
        dict(tile=2048, y_tile=0)
    assert autotune.legacy_default("topk_sqdist", backend="tpu") == \
        dict(bm=256, bn=512, lane=128)
    with pytest.raises(KeyError):
        autotune.legacy_default("no_such_kernel")


# ---------------------------------------------------------------------------
# sweep decision rule (timing faked — the adopt logic, not the clock)
# ---------------------------------------------------------------------------

def _fake_builder(shape, backend):
    cands = [dict(tile=2), dict(tile=3)]
    return cands, lambda cfg: (lambda: cfg["tile"])


def _fake_timer(times_paired):
    """best_of_interleaved stub: shortlist pass ranks candidate tile=3
    fastest; the paired pass returns ``times_paired``."""
    def fake(fns, repeats):
        if len(fns) == 2:
            return None, list(times_paired)
        return None, [1.0, 0.9, 0.5][:len(fns)]
    return fake


def test_sweep_adopts_clear_winner(tuner, monkeypatch):
    from repro.runtime import timing
    monkeypatch.setitem(autotune._SWEEPS, "fake_kernel", _fake_builder)
    monkeypatch.setattr(timing, "best_of_interleaved",
                        _fake_timer((1.0, 0.5)))
    chosen = autotune.sweep("fake_kernel", dict(n=100), dict(tile=1))
    assert chosen == dict(tile=3)
    # persisted: a fresh cache-mode lookup serves it
    autotune._mem.clear()
    autotune.set_mode("cache")
    assert autotune.get("fake_kernel", dict(n=100), dict(tile=1)) == \
        dict(tile=3)


def test_sweep_keeps_default_on_noise_margin(tuner, monkeypatch):
    """A paired win inside ADOPT_MARGIN is indistinguishable from load
    noise on a single-core box — ties keep the legacy default."""
    from repro.runtime import timing
    monkeypatch.setitem(autotune._SWEEPS, "fake_kernel", _fake_builder)
    monkeypatch.setattr(timing, "best_of_interleaved",
                        _fake_timer((1.0, 0.99)))
    assert autotune.sweep("fake_kernel", dict(n=100), dict(tile=1)) == \
        dict(tile=1)


def test_sweep_mode_sweeps_on_miss(tuner, monkeypatch):
    from repro.runtime import timing
    monkeypatch.setitem(autotune._SWEEPS, "fake_kernel", _fake_builder)
    monkeypatch.setattr(timing, "best_of_interleaved",
                        _fake_timer((1.0, 0.5)))
    autotune.set_mode("sweep")
    assert autotune.get("fake_kernel", dict(n=100), dict(tile=1)) == \
        dict(tile=3)


def test_unknown_kernel_sweep_is_identity(tuner):
    assert autotune.sweep("no_such_kernel", dict(n=4), dict(tile=9)) == \
        dict(tile=9)


# ---------------------------------------------------------------------------
# call-site contracts
# ---------------------------------------------------------------------------

def test_off_mode_topk_bitwise_vs_explicit_legacy(tuner):
    """AUTOTUNE=off through the ops layer == the legacy config passed
    explicitly, bitwise — the pre-autotuner repo is reproducible."""
    autotune.set_mode("off")
    ka, kb = jax.random.split(jax.random.key(7))
    a = jax.random.normal(ka, (300, 16), jnp.float32)
    b = jax.random.normal(kb, (500, 16), jnp.float32)
    d_off, i_off = ops.topk_sqdist(a, b, 10)
    legacy = autotune.legacy_default("topk_sqdist")
    autotune.set_mode("cache")
    d_leg, i_leg = ops.topk_sqdist(a, b, 10, **legacy)
    assert np.array_equal(np.asarray(d_off), np.asarray(d_leg))
    assert np.array_equal(np.asarray(i_off), np.asarray(i_leg))


def test_explore_sample_gate_never_consults_tuner(tuner, monkeypatch):
    """``neighbor_explore`` with ``sample > 0`` folds the tile index into
    its key stream — tuning the tile would change which candidates are
    drawn.  The call site must not consult the tuner there (and must
    consult it for the deterministic ``sample == 0`` path)."""
    from repro.core import knn, neighbor_explore as ne
    x = jax.random.normal(jax.random.key(3), (200, 8), jnp.float32)
    idx, dist = knn.brute_force_knn(x, 5)
    calls = []
    real_get = autotune.get

    def spy(kernel, shape, default):
        calls.append(kernel)
        return real_get(kernel, shape, default)

    monkeypatch.setattr(autotune, "get", spy)
    ne.neighbor_explore(x, idx, dist, iters=1, sample=16,
                        key=jax.random.key(4))
    assert "neighbor_explore" not in calls
    ne.neighbor_explore(x, idx, dist, iters=1, sample=0)
    assert "neighbor_explore" in calls


def test_routing_config_sets_mode(tuner):
    from repro.configs.largevis_default import LargeVisConfig, RoutingConfig
    from repro.core.largevis import _apply_autotune_mode
    _apply_autotune_mode(LargeVisConfig(
        routing=RoutingConfig(autotune="off")))
    assert autotune.mode() == "off"
    _apply_autotune_mode(LargeVisConfig())     # auto -> env default
    assert autotune.mode() == "cache"


# ---------------------------------------------------------------------------
# embedding-tiled fused step: bitwise contract + VMEM residency
# ---------------------------------------------------------------------------

def _batch(N, B, M, s=2, seed=0, lo=0):
    ks = jax.random.split(jax.random.fold_in(jax.random.key(11), seed), 5)
    y = jax.random.normal(ks[0], (N, s), jnp.float32)
    i = jax.random.randint(ks[1], (B,), lo, N)
    j = jax.random.randint(ks[2], (B,), lo, N)
    negs = jax.random.randint(ks[3], (B, M), lo, N)
    mask = ((negs != i[:, None]) & (negs != j[:, None])).astype(jnp.float32)
    return y, i, j, negs, mask


@pytest.mark.parametrize("y_tile", [1, 1024, 1500, 2048, 4000])
def test_tiled_matches_untiled_and_ref_bitwise(y_tile):
    """Odd N=2500 against tiles (rounded up to 1024 rows) that divide
    unevenly (padded slab), exceed N (clamped), and everything between —
    all bitwise equal to the untiled kernel and the compiled oracle."""
    y, i, j, negs, mask = _batch(2500, 29, 4, s=3, seed=1)
    kw = dict(gamma=GAMMA, a=A, clip=CLIP, interpret=True)
    tiled = fused_edge_step(y, i, j, negs, mask, 0.37, y_tile=y_tile, **kw)
    flat = fused_edge_step(y, i, j, negs, mask, 0.37, **kw)
    want = _ref_step(y, i, j, negs, mask, 0.37, gamma=GAMMA, a=A, clip=CLIP)
    assert np.array_equal(np.asarray(tiled), np.asarray(flat))
    assert np.array_equal(np.asarray(tiled), np.asarray(want))


@pytest.mark.parametrize("y_tile", [1, 1024, 2048])
def test_tiled_duplicate_dense_frozen_per_edge_lr(y_tile):
    """Six rows spread over three 1024-row tiles, each drawn many times
    per batch, the first three frozen, per-edge learning rates: the tiled
    accumulation order and the frozen no-op writes must survive tiling
    bitwise."""
    N, B, M, s = 2100, 64, 3, 2
    rows = jnp.asarray([0, 5, 1030, 1100, 2050, 2090], jnp.int32)
    y, i, j, negs, mask = _batch(6, B, M, s=s, seed=2)
    i, j, negs = rows[i], rows[j], rows[negs]
    y = jax.random.normal(jax.random.key(8), (N, s), jnp.float32)
    lr = jax.random.uniform(jax.random.key(9), (B,), jnp.float32, 0.1, 0.9)
    kw = dict(gamma=GAMMA, a=A, clip=CLIP, n_frozen=1050, interpret=True)
    tiled = fused_edge_step(y, i, j, negs, mask, lr, y_tile=y_tile, **kw)
    flat = fused_edge_step(y, i, j, negs, mask, lr, **kw)
    want = _ref_step(y, i, j, negs, mask, lr, gamma=GAMMA, a=A, clip=CLIP,
                     n_frozen=1050)
    assert np.array_equal(np.asarray(tiled), np.asarray(flat))
    assert np.array_equal(np.asarray(tiled), np.asarray(want))
    assert np.array_equal(np.asarray(tiled[:1050]), np.asarray(y[:1050]))
    assert not np.array_equal(np.asarray(tiled[2050]), np.asarray(y[2050]))


def test_ops_route_applies_y_tile_bitwise(tuner):
    """A cached y_tile flows through ops.largevis_edge_step and changes
    nothing but the tiling."""
    autotune.set_mode("cache")
    y, i, j, negs, mask = _batch(3000, 40, 5, seed=3)
    kw = dict(gamma=GAMMA, a=A, clip=CLIP, impl="fused")
    base = ops.largevis_edge_step(y, i, j, negs, mask, 0.5, **kw)
    key = autotune.bucket_key("largevis_edge_step",
                              dict(n=3000, b=40, m=5, s=2))
    autotune._write_entry(BACKEND, key, {"config": dict(y_tile=1024)})
    autotune._mem.clear()
    jax.clear_caches()                    # tiles are static jit args
    assert autotune.get("largevis_edge_step", dict(n=3000, b=40, m=5, s=2),
                        dict(tile=2048, y_tile=0))["y_tile"] == 1024
    tuned = ops.largevis_edge_step(y, i, j, negs, mask, 0.5, **kw)
    assert np.array_equal(np.asarray(base), np.asarray(tuned))


def test_tiled_hlo_no_second_full_embedding():
    """The tiled lowering holds one planar (s, R/128, 128) slab of y plus
    the gathered-rows scratch — every buffer other than the
    whole-embedding in/out (and its padded planar copy) must fit in one
    slab/scratch."""
    N, s, B, M, R = 3000, 2, 64, 3, 1024       # pads to n_pad = 3072
    y, i, j, negs, mask = _batch(N, B, M, s=s, seed=4)

    def f(y_, i_, j_, negs_, mask_):
        return fused_edge_step(y_, i_, j_, negs_, mask_, 0.5, gamma=GAMMA,
                               a=A, clip=CLIP, y_tile=R, interpret=True)

    txt = jax.jit(f).lower(y, i, j, negs, mask).as_text()
    n_pad = -(-N // R) * R
    # whole-embedding shapes: y in and out, and the wrapper's copy of y
    # into the kernel's planar layout and back (padded, transposed, then
    # reshaped to the aliased (s, n_pad/128, 128) operand) -- the drivers
    # carry y as (N, s), so these copies are made on every call
    whole = {(N, s), (n_pad, s), (s, n_pad), (s, n_pad // 128, 128)}
    limit = 4 * max(R * s, 128 * (2 + M) * s)
    offenders = sorted({
        (nb, dt, shape) for dt, shape, nb in hlo_checks.iter_buffers(txt)
        if shape not in whole and nb > limit}, reverse=True)
    assert not offenders, offenders[:8]
    # sanity: the slab and the padded planar copy really are in the lowering
    assert hlo_checks.has_buffer(txt, (s, R // 128, 128), "f32")
    assert hlo_checks.has_buffer(txt, (s, n_pad // 128, 128), "f32")


def test_fused_step_supported_lifts_size_bound():
    """The VMEM ceiling is a tiling decision, not a routing rejection:
    any N is supported, with a tile chosen past the budget (3/4 of the
    core's VMEM: 12,582,912 rows at s=2 on v5e)."""
    n = 16_000_000
    assert ops.fused_step_supported(n, 2)
    assert ops._fused_y_tile(100, 2) == 0          # fits: stay untiled
    big_tile = ops._fused_y_tile(n, 2)
    assert 0 < big_tile < n
    assert 4 * 2 * big_tile <= ops._fused_y_budget()


@pytest.mark.parametrize("n,slabs", [(70_000, 1), (2_228_224, 1),
                                     (2_837_395, 1), (12_582_912, 1),
                                     (12_582_913, 2), (40_000_000, 4)])
def test_edge_step_slabs_follow_the_budget(tuner, n, slabs):
    """The slab count the dispatch span records: one slab up to the VMEM
    budget (the wikidoc100 configuration's 2,228,224 rows and WikiDoc's
    published 2,837,395 among them), then ceil(n / tile)."""
    autotune.set_mode("cache")
    assert ops.edge_step_slabs(n, 2, 4096, 5, layout_step="fused") == slabs
