"""Device-resident sampler subsystem (core/sampler.py) + its plumbing.

Covers, per the stage-boundary refactor contract:

* the jitted device alias builder vs the numpy-Vose oracle — alias tables
  need not be identical (any table with the right marginals is valid), so
  the check reconstructs exact per-index marginal probabilities from
  (threshold, alias) and compares those;
* empirical edge / negative sample frequencies against w_ij and deg^0.75;
* EdgeSampler/NodeSampler pytree flatten/unflatten round trips through
  ``jax.jit`` with static metadata preserved;
* degenerate inputs: all-zero weights, a single edge, E not a power of 2;
* HLO/no-host assertions: the device builders lower with zero host
  callbacks and never touch the Python Vose loop (monkeypatch-proven),
  and ``symmetrize`` is ONE compiled computation reused across calls
  (no per-call retrace, no per-tile dispatch);
* bitwise trajectory parity pre/post refactor: pinned-seed layouts with
  host-built tables, driven through the new sampler-pytree plumbing, must
  reproduce the pre-refactor unpacked-six-array step stream exactly on
  all three drivers (per-step loop, scanned chunks, local-SGD) and
  through end-to-end ``largevis()``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hlo_checks

from repro.configs.largevis_default import LargeVisConfig
from repro.core import layout as layout_lib
from repro.core import perplexity
from repro.core import sampler as S
from repro.core.largevis import build_graph, largevis
from repro.core.sampler import sample_alias
from repro.data.synthetic import gaussian_mixture
from repro.kernels import ops
from repro.launch.mesh import make_mesh

KEY = jax.random.key(0)


def _marginals(threshold, alias):
    """Exact per-index probability the (threshold, alias) table samples
    index k: (threshold_k + sum over slots aliasing k of (1-threshold))/n."""
    t = np.asarray(threshold, np.float64)
    a = np.asarray(alias)
    mass = t.copy()
    np.add.at(mass, a, 1.0 - t)
    return mass / t.shape[0]


# ---------------------------------------------------------------------------
# device builder vs the numpy-Vose oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("probs", [
    np.array([0.1, 0.0, 0.4, 0.5]),
    np.ones(7),
    np.random.default_rng(0).random(1000) ** 2 + 1e-9,
    np.random.default_rng(1).pareto(1.5, 513) + 1e-9,     # heavy tail
    np.concatenate([np.zeros(50), np.random.default_rng(2).random(77)]),
])
def test_device_alias_marginals_match_oracle(probs):
    thr_h, ali_h = S.build_alias(probs)
    thr_d, ali_d = S.build_alias_device(jnp.asarray(probs, jnp.float32))
    want = probs / probs.sum()
    np.testing.assert_allclose(_marginals(thr_h, ali_h), want, atol=5e-5)
    np.testing.assert_allclose(_marginals(thr_d, ali_d), want, atol=5e-5)
    t = np.asarray(thr_d)
    assert ((t >= 0.0) & (t <= 1.0)).all()
    a = np.asarray(ali_d)
    assert ((a >= 0) & (a < len(probs))).all()


def _heavy_tailed(kind: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(17)
    if kind == "lognormal":
        return rng.lognormal(0.0, 3.0, n)
    if kind == "exact_zeros":
        return np.where(rng.random(n) < 0.4, 0.0, rng.lognormal(0.0, 1.0, n))
    if kind == "one_spike":
        w = rng.uniform(0.5, 1.5, n)
        w[n // 3] = 1e6 * w.max()
        return w
    return np.ones(n)                                   # all equal


@pytest.mark.parametrize("kind", [
    "uniform", "lognormal", "exact_zeros", "one_spike", "all_equal"])
def test_device_alias_marginals_exact_at_scale(kind):
    """Marginals of the exact device build against float64 targets at
    E >= 1e6 (past the E ~ 1e5 where float32 prefix sums lose every
    slot): total variation within 1e-7, zero weights never drawn, and a
    rebuild gives the same bits.  ``uniform`` keeps the per-slot relative
    error of each index within 1e-5."""
    if kind == "uniform":
        p = np.random.default_rng(17).uniform(0.1, 2.0, 300_000)
    else:
        p = _heavy_tailed(kind, 1_200_000)
    p = p.astype(np.float32)
    thr, ali = S.build_alias_device(jnp.asarray(p))
    want = p.astype(np.float64)
    want /= want.sum()
    got = _marginals(thr, ali)
    assert 0.5 * np.abs(got - want).sum() <= 1e-7
    assert (got[want == 0] == 0).all()
    if kind == "uniform":
        rel = np.abs(got - want) / want
        assert rel.max() < 1e-5, rel.max()
    thr2, ali2 = S.build_alias_device(jnp.asarray(p))
    assert np.array_equal(np.asarray(thr), np.asarray(thr2))
    assert np.array_equal(np.asarray(ali), np.asarray(ali2))


@pytest.mark.parametrize("chunk", [128, 1000, 4500])
def test_device_alias_tables_do_not_depend_on_chunks(monkeypatch, chunk):
    """The build walks the graph in chunks of rows (the last window
    shifted back to end at N) with carried prefix sums; any chunking gives
    the same tables as one chunk, bit for bit."""
    rng = np.random.default_rng(23)
    w = rng.lognormal(0.0, 2.0, (301, 17)).astype(np.float32)
    w[40:90] = 0.0                                   # rows of exact zeros
    w[250, 3] = 1e5 * w.max()

    def tables():
        with jax.enable_x64(True):
            return jax.jit(S._alias_tables)(jnp.asarray(w).reshape(-1))

    whole = tables()
    monkeypatch.setattr(S, "CHUNK", chunk)
    part = tables()
    for a, b in zip(whole, part):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    want = w.reshape(-1).astype(np.float64)
    np.testing.assert_allclose(_marginals(*part[:2]), want / want.sum(),
                               atol=1e-10)


def test_edge_sampler_impls_same_marginals():
    rng = np.random.default_rng(5)
    idx = rng.integers(0, 40, (40, 7)).astype(np.int32)
    w = rng.uniform(0.0, 2.0, (40, 7)).astype(np.float32)
    eh = S.build_edge_sampler(idx, w, impl="host")
    ed = S.build_edge_sampler(idx, w, impl="device")
    np.testing.assert_array_equal(np.asarray(eh.src), np.asarray(ed.src))
    np.testing.assert_array_equal(np.asarray(eh.dst), np.asarray(ed.dst))
    np.testing.assert_allclose(_marginals(eh.threshold, eh.alias),
                               _marginals(ed.threshold, ed.alias), atol=5e-6)
    nh = S.build_negative_sampler(idx, w, impl="host")
    nd = S.build_negative_sampler(idx, w, impl="device")
    np.testing.assert_allclose(_marginals(nh.threshold, nh.alias),
                               _marginals(nd.threshold, nd.alias), atol=5e-6)


def test_device_edge_sample_frequencies_follow_weights():
    """Empirical slot frequencies ~ w_ij / sum(w) (paper's p(e) ∝ w_ij)."""
    rng = np.random.default_rng(7)
    idx = rng.integers(0, 4, (4, 3)).astype(np.int32)
    w = rng.uniform(0.1, 2.0, (4, 3)).astype(np.float32)
    es = S.build_edge_sampler(idx, w, impl="device")
    e = sample_alias(KEY, es.threshold, es.alias, (200_000,))
    freq = np.bincount(np.asarray(e), minlength=12) / 200_000
    np.testing.assert_allclose(freq, w.reshape(-1) / w.sum(), atol=0.01)


def test_device_negative_sampler_power_law():
    """Same fixture as the host-path test: deg^0.75 noise distribution."""
    idx = jnp.array([[1], [0], [0], [0]], jnp.int32)   # node 0 high degree
    w = jnp.ones((4, 1), jnp.float32)
    ns = S.build_negative_sampler(idx, w, power=0.75, impl="device")
    s = np.asarray(ns.sample(KEY, (100_000,)))
    freq = np.bincount(s, minlength=4) / 100_000
    want = np.array([4.0, 2.0, 1.0, 1.0]) ** 0.75
    want /= want.sum()
    np.testing.assert_allclose(freq, want, atol=0.01)


# ---------------------------------------------------------------------------
# pytree behaviour
# ---------------------------------------------------------------------------

def test_sampler_pytrees_roundtrip_through_jit():
    rng = np.random.default_rng(9)
    idx = rng.integers(0, 30, (30, 5)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (30, 5)).astype(np.float32)
    es = S.build_edge_sampler(idx, w, impl="device")
    ns = S.build_negative_sampler(idx, w, impl="device")

    leaves, treedef = jax.tree_util.tree_flatten(es)
    assert len(leaves) == 4                      # src, dst, threshold, alias
    es_r = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(es_r, S.EdgeSampler) and es_r.n_edges == es.n_edges
    assert len(jax.tree_util.tree_leaves(ns)) == 2

    # identity jit: structure, static metadata and leaf values survive
    es_j, ns_j = jax.jit(lambda a, b: (a, b))(es, ns)
    assert isinstance(es_j, S.EdgeSampler) and isinstance(ns_j, S.NodeSampler)
    assert es_j.n_edges == es.n_edges and ns_j.n_nodes == ns.n_nodes
    for got, want in zip(jax.tree_util.tree_leaves(es_j), leaves):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # samplers are legal jit *inputs*; draws are deterministic and in range
    i1, j1 = jax.jit(lambda s, k: s.sample(k, 64))(es, KEY)
    i2, j2 = jax.jit(lambda s, k: s.sample(k, 64))(es, KEY)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(j1), np.asarray(j2))
    assert ((np.asarray(i1) >= 0) & (np.asarray(i1) < 30)).all()


# ---------------------------------------------------------------------------
# degenerate inputs
# ---------------------------------------------------------------------------

def test_device_builder_all_zero_weights_uniform():
    idx = np.arange(1, 7, dtype=np.int32).reshape(6, 1) % 6
    w = np.zeros((6, 1), np.float32)
    es = S.build_edge_sampler(idx, w, impl="device")
    np.testing.assert_allclose(_marginals(es.threshold, es.alias),
                               np.full(6, 1 / 6), atol=1e-6)
    i, j = es.sample(KEY, 128)
    assert jnp.isfinite(es.threshold).all()
    assert ((np.asarray(i) >= 0) & (np.asarray(i) < 6)).all()


def test_device_builder_single_edge():
    idx = np.array([[0]], np.int32)
    w = np.array([[3.0]], np.float32)
    es = S.build_edge_sampler(idx, w, impl="device")
    assert es.n_edges == 1
    np.testing.assert_allclose(_marginals(es.threshold, es.alias), [1.0])
    i, j = es.sample(KEY, 16)
    assert (np.asarray(i) == 0).all() and (np.asarray(j) == 0).all()


@pytest.mark.parametrize("e_total", [15, 37, 1001])   # never a power of two
def test_device_builder_non_power_of_two(e_total):
    rng = np.random.default_rng(e_total)
    p = rng.random(e_total) + 1e-6
    thr, ali = S.build_alias_device(jnp.asarray(p, jnp.float32))
    np.testing.assert_allclose(_marginals(thr, ali), p / p.sum(), atol=5e-5)


# ---------------------------------------------------------------------------
# zero host involvement (HLO + monkeypatch), single-computation symmetrize
# ---------------------------------------------------------------------------

def test_device_builders_lower_without_host_callbacks():
    idx = jnp.zeros((64, 4), jnp.int32)
    w = jnp.ones((64, 4), jnp.float32)
    with jax.enable_x64(True):
        lowereds = (
            S._build_edge_sampler_device.lower(idx, w),
            S._build_negative_sampler_device.lower(idx, w, power=0.75),
            S._alias_jit.lower(jnp.ones(256, jnp.float32)),
        )
    for lowered in lowereds:
        hlo = lowered.as_text()
        hlo_checks.assert_no_op(hlo, "callback", "infeed",
                                what="host involvement in device builder")
        hlo_checks.assert_has_op(hlo, "while",
                                 what="chunked prefix-sum construction")
        assert "i64" in hlo, "the pairing's sums are 64-bit integers"


def test_device_builders_never_run_python_vose(monkeypatch):
    """impl="device" must execute zero Python-level per-edge iteration:
    with the host Vose loop booby-trapped, the device path still builds."""
    def boom(*_a, **_k):
        raise AssertionError("host Vose loop reached from impl='device'")

    monkeypatch.setattr(S, "build_alias", boom)
    rng = np.random.default_rng(11)
    idx = rng.integers(0, 50, (50, 4)).astype(np.int32)
    w = rng.uniform(0.1, 1.0, (50, 4)).astype(np.float32)
    es = S.build_edge_sampler(idx, w, impl="device")
    ns = S.build_negative_sampler(idx, w, impl="device")
    assert jnp.isfinite(es.threshold).all() and jnp.isfinite(ns.threshold).all()
    with pytest.raises(AssertionError, match="host Vose"):
        S.build_edge_sampler(idx, w, impl="host")


def test_symmetrize_is_single_compiled_computation():
    """The scanned symmetrize compiles once per (shape, tile) and reuses
    the executable — the pre-refactor form re-created a jax.jit wrapper
    (fresh cache, full retrace) on every call plus one dispatch per tile."""
    rng = np.random.default_rng(13)
    idx = jnp.asarray(rng.integers(0, 200, (200, 6)), jnp.int32)
    p = jax.random.uniform(KEY, (200, 6))

    before = perplexity._symmetrize_scan._cache_size()
    w1 = perplexity.symmetrize(idx, p, tile=64)
    w2 = perplexity.symmetrize(idx, p, tile=64)
    after = perplexity._symmetrize_scan._cache_size()
    assert after - before <= 1, "symmetrize re-traced on a repeat call"
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))

    hlo = perplexity._symmetrize_scan.lower(idx, p, tile=64).as_text()
    hlo_checks.assert_has_op(hlo, "while",
                             what="tile loop fused into the computation")
    hlo_checks.assert_no_op(hlo, "callback")

    # padded remainder tiles (200 % 64 != 0) match the exact-tile values
    w3 = perplexity.symmetrize(idx, p, tile=50)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w3), atol=1e-7)


# ---------------------------------------------------------------------------
# bitwise trajectory parity pre/post refactor (host-built tables through
# the new pytree plumbing vs the pre-refactor unpacked-array step stream)
# ---------------------------------------------------------------------------

def _old_sgd_step(y, key, t_frac, *, edge_src, edge_dst, edge_thr,
                  edge_alias, neg_thr, neg_alias, n_negatives, n_nodes,
                  gamma=7.0, a=1.0, clip=5.0, rho0=1.0, batch=4096):
    """The pre-refactor step body, verbatim: six unpacked table arrays,
    explicit sample_alias + gathers.  The refactored pytree step must
    produce this exact computation."""
    ke, kn, _ = jax.random.split(key, 3)
    e = sample_alias(ke, edge_thr, edge_alias, (batch,))
    i, j = edge_src[e], edge_dst[e]
    negs = sample_alias(kn, neg_thr, neg_alias, (batch, n_negatives))
    neg_mask = ((negs != i[:, None]) &
                (negs != j[:, None])).astype(jnp.float32)
    lr = rho0 * jnp.maximum(1.0 - t_frac, 1e-4)
    return ops.largevis_edge_step(y, i, j, negs, neg_mask, lr,
                                  gamma=gamma, a=a, clip=clip)


_old_step_jit = jax.jit(
    _old_sgd_step, donate_argnums=(0,),
    static_argnames=("n_negatives", "n_nodes", "gamma", "a", "clip",
                     "batch"))


@pytest.fixture(scope="module")
def parity_fixture():
    rng = np.random.default_rng(21)
    n, k = 500, 8
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    es = S.build_edge_sampler(idx, w, impl="host")
    ns = S.build_negative_sampler(idx, w, impl="host")
    return n, es, ns


def _old_tables(es, ns):
    return dict(edge_src=es.src, edge_dst=es.dst, edge_thr=es.threshold,
                edge_alias=es.alias, neg_thr=ns.threshold,
                neg_alias=ns.alias)


def _reference_run_layout(key, es, ns, n, cfg):
    """Pre-refactor run_layout, inlined: per-step loop over the unpacked
    six-array step with the identical key stream and t/T schedule."""
    ky, kr = jax.random.split(key)
    y = (jax.random.normal(ky, (n, cfg.out_dim), jnp.float32)
         * cfg.init_scale)
    total = int(cfg.samples_per_node) * n
    batch = layout_lib._collision_capped_batch(cfg.batch_size, n, total)
    steps = max(1, total // batch)
    tables = _old_tables(es, ns)
    for t in range(steps):
        y = _old_step_jit(y, jax.random.fold_in(kr, t),
                          jnp.float32(t / steps), n_negatives=cfg.n_negatives,
                          n_nodes=n, gamma=cfg.gamma, a=cfg.prob_a,
                          clip=cfg.grad_clip, rho0=cfg.rho0, batch=batch,
                          **tables)
    return y


@pytest.mark.parametrize("steps_per_dispatch", [1, 64])
def test_pytree_plumbing_parity_loop_and_scan_drivers(parity_fixture,
                                                      steps_per_dispatch):
    """Drivers 1+2 (per-step loop, scanned chunks): host tables through
    the new pytree plumbing == the pre-refactor unpacked step stream."""
    n, es, ns = parity_fixture
    cfg = LargeVisConfig(samples_per_node=60, batch_size=4096,
                         steps_per_dispatch=steps_per_dispatch)
    got = layout_lib.run_layout(KEY, es, ns, n, cfg).y
    want = _reference_run_layout(KEY, es, ns, n, cfg)
    assert np.array_equal(np.asarray(got), np.asarray(want)), float(
        np.abs(np.asarray(got) - np.asarray(want)).max())


def test_pytree_plumbing_parity_local_sgd_driver(parity_fixture):
    """Driver 3 (shard_map local-SGD, 1-device mesh): same tables, same
    round-seed schedule, bitwise-identical trajectory."""
    n, es, ns = parity_fixture
    cfg = LargeVisConfig(sync_every=4, samples_per_node=16, batch_size=128)
    mesh = make_mesh((1,), ("data",))
    got = layout_lib.run_layout_local_sgd(KEY, es, ns, n, cfg, mesh).y

    # pre-refactor reference: unpacked-array steps, replicated schedule
    ky, kr = jax.random.split(KEY)
    y = (jax.random.normal(ky, (n, cfg.out_dim), jnp.float32)
         * cfg.init_scale)
    batch = layout_lib._collision_capped_batch(cfg.batch_size, n)
    total = int(cfg.samples_per_node) * n
    steps = max(1, total // batch)
    H = cfg.sync_every
    n_rounds = max(1, steps // H)
    seeds = np.asarray(jax.random.randint(kr, (n_rounds,), 0, 2**31 - 1,
                                          dtype=jnp.int32))
    dt = 1.0 / max(steps, 1)
    tables = _old_tables(es, ns)
    for r in range(n_rounds):
        base_key = jax.random.fold_in(jax.random.key(int(seeds[r])), 0)
        t_fracs = (jnp.float32(r * H * dt)
                   + jnp.float32(dt) * jnp.arange(H, dtype=jnp.float32))
        for h in range(H):
            y = _old_step_jit(y, jax.random.fold_in(base_key, h),
                              t_fracs[h], n_negatives=cfg.n_negatives,
                              n_nodes=n, gamma=cfg.gamma, a=cfg.prob_a,
                              clip=cfg.grad_clip, rho0=cfg.rho0,
                              batch=batch, **tables)
        # pmean over a 1-device mesh is the identity
    assert np.array_equal(np.asarray(got), np.asarray(y)), float(
        np.abs(np.asarray(got) - np.asarray(y)).max())


def test_largevis_end_to_end_bitwise_vs_host_table_path():
    """Acceptance: end-to-end largevis() on a pinned seed == the
    pre-refactor host-built-table composition, bit for bit."""
    x, _ = gaussian_mixture(jax.random.key(5), 400, 16, 4)
    cfg = LargeVisConfig(n_neighbors=10, n_trees=4, n_explore_iters=1,
                         window=32, perplexity=8.0, samples_per_node=100,
                         batch_size=4096, sampler_impl="host")
    got = largevis(x, KEY, cfg=cfg).y

    kg, kl = jax.random.split(KEY)
    idx, dist, w, _ = build_graph(x, kg, cfg=cfg)
    es = S.build_edge_sampler(idx, w, impl="host")
    ns = S.build_negative_sampler(idx, w, power=cfg.neg_power, impl="host")
    want = _reference_run_layout(kl, es, ns, x.shape[0], cfg)
    assert np.array_equal(np.asarray(got), np.asarray(want)), float(
        np.abs(np.asarray(got) - np.asarray(want)).max())


def test_largevis_device_tables_deterministic_and_finite():
    """The device stage boundary is reproducible end to end: same key,
    same tables, same layout — and sampler_s timing is recorded."""
    x, _ = gaussian_mixture(jax.random.key(6), 300, 16, 4)
    cfg = LargeVisConfig(n_neighbors=8, n_trees=4, n_explore_iters=1,
                         window=32, perplexity=6.0, samples_per_node=60,
                         batch_size=2048, sampler_impl="device")
    r1 = largevis(x, KEY, cfg=cfg)
    r2 = largevis(x, KEY, cfg=cfg)
    assert np.array_equal(np.asarray(r1.y), np.asarray(r2.y))
    assert jnp.isfinite(r1.y).all()
    assert "sampler_s" in r1.timings and "layout_s" in r1.timings
