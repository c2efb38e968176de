"""Sharded graph-preparation stages (PR 6): correctness + device residency.

In-process tests adapt to the visible device count via
``make_data_mesh(0)`` — under the default single-device pytest run they
exercise the full shard_map plumbing at P=1 (which must be *bitwise*
the single-device path); under the CI mesh-smoke job
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) the same tests
run the real 4-way partitioning.  The ``slow`` subprocess test forces
an 8-device mesh regardless of the parent's configuration.

Two-level sampler correctness is exercised WITHOUT a mesh: the stacked
per-shard tables are plain arrays and ``sample()`` is pure jnp, so a
hand-stacked 2-shard sampler checks the stratified-sampling math
(P(shard) * P(edge | shard) = w_e / T) directly, with a chi-square
bound on empirical frequencies.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.largevis_default import LargeVisConfig
from repro.core import layout as layout_lib
from repro.core import perplexity as perp
from repro.core import sampler as S
from repro.core.largevis import build_graph, largevis
from repro.launch.mesh import make_data_mesh
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.key(0)


def _graph(n, k, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.empty((n, k), np.int32)
    for i in range(n):                      # distinct neighbors, no self
        idx[i] = rng.choice([j for j in range(n) if j != i], k,
                            replace=False)
    d2 = rng.uniform(0.1, 4.0, (n, k)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(d2)


# ---------------------------------------------------------------------------
# bitwise equality vs the single-device oracle (P = visible device count)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [403, 256])   # indivisible and divisible
def test_sharded_weights_bitwise_equal(n):
    idx, d2 = _graph(n, 7, seed=n)
    p_ref = perp.calibrate_p(d2, 5.0)
    p_sh = perp.calibrate_p_sharded(d2, 5.0)
    assert np.array_equal(np.asarray(p_ref), np.asarray(p_sh))

    w_ref = perp.symmetrize(idx, p_ref)
    w_sh = perp.symmetrize_sharded(idx, p_sh)
    assert np.array_equal(np.asarray(w_ref), np.asarray(w_sh))

    e_ref = perp.edge_weights(idx, d2, 5.0)
    e_sh = perp.edge_weights_sharded(idx, d2, 5.0)
    assert np.array_equal(np.asarray(e_ref), np.asarray(e_sh))


def test_sharded_sampler_tables_match_flat():
    """On a 1-shard mesh the per-shard tables ARE the flat tables and the
    sample() key streams match the flat samplers bitwise."""
    idx, _ = _graph(203, 5, seed=3)
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.integers(1, 16, idx.shape).astype(np.float32))
    es, ns = S.build_samplers_sharded(idx, w)
    ef = S.build_edge_sampler(idx, w, impl="device")
    nf = S.build_negative_sampler(idx, w, impl="device")
    if es.n_shards == 1:
        for a, b in ((es.src[0], ef.src), (es.dst[0], ef.dst),
                     (es.threshold[0], ef.threshold),
                     (es.alias[0], ef.alias)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    k = jax.random.key(9)
    if es.n_shards == 1:
        sa, da = es.sample(k, 512)
        sb, db = ef.sample(k, 512)
        assert np.array_equal(np.asarray(sa), np.asarray(sb))
        assert np.array_equal(np.asarray(da), np.asarray(db))
        assert np.array_equal(np.asarray(ns.sample(k, (512,))),
                              np.asarray(nf.sample(k, (512,))))
    # regardless of shard count: every drawn id is a valid node
    ids = np.asarray(ns.sample(k, (2048,)))
    assert ((ids >= 0) & (ids < idx.shape[0])).all()


# ---------------------------------------------------------------------------
# two-level sampler math (mesh-free, hand-stacked 2-shard tables)
# ---------------------------------------------------------------------------

def _stack_edge_sampler(idx, w, n_shards=2):
    """Build a ShardedEdgeSampler by slicing the graph into row blocks
    and alias-building each block independently (what the shard_map
    builder computes per device)."""
    n = idx.shape[0]
    n_loc = n // n_shards
    parts, totals = [], []
    for s in range(n_shards):
        sl = slice(s * n_loc, (s + 1) * n_loc)
        es = S.build_edge_sampler(np.asarray(idx)[sl], np.asarray(w)[sl],
                                  impl="device")
        # slice-local src ids -> global
        parts.append((np.asarray(es.src) + s * n_loc, np.asarray(es.dst),
                      np.asarray(es.threshold), np.asarray(es.alias)))
        totals.append(float(np.asarray(w)[sl].sum()))
    src = jnp.asarray(np.stack([p[0] for p in parts]))
    dst = jnp.asarray(np.stack([p[1] for p in parts]))
    thr = jnp.asarray(np.stack([p[2] for p in parts]))
    ali = jnp.asarray(np.stack([p[3] for p in parts]))
    sthr, sali = S.build_alias(np.asarray(totals))
    return S.ShardedEdgeSampler(src, dst, thr, ali, jnp.asarray(sthr),
                                jnp.asarray(sali), n_shards, n * idx.shape[1])


def _chi2_ok(obs, expected_p, n_draws):
    """Chi-square statistic below mean + 5 sigma of its null
    distribution (df = bins - 1) — no scipy needed."""
    exp = expected_p * n_draws
    stat = float(np.sum((obs - exp) ** 2 / np.maximum(exp, 1e-12)))
    df = len(expected_p) - 1
    return stat < df + 5.0 * np.sqrt(2.0 * df), stat, df


def test_two_level_edge_sampler_matches_global_distribution():
    n, k = 64, 4
    idx, _ = _graph(n, k, seed=7)
    rng = np.random.default_rng(8)
    w = rng.uniform(0.2, 3.0, (n, k)).astype(np.float32)
    sampler = _stack_edge_sampler(idx, jnp.asarray(w), n_shards=2)

    m = 1 << 19
    src, dst = sampler.sample(jax.random.key(5), m)
    pair = np.asarray(src).astype(np.int64) * n + np.asarray(dst)
    # unique global pair id per edge slot (distinct neighbors per row)
    slot_pair = (np.repeat(np.arange(n), k).astype(np.int64) * n
                 + np.asarray(idx).reshape(-1))
    counts = np.zeros(n * k)
    uniq, cnt = np.unique(pair, return_counts=True)
    lookup = {p: i for i, p in enumerate(slot_pair)}
    for p, c in zip(uniq, cnt):
        counts[lookup[int(p)]] = c
    ok, stat, df = _chi2_ok(counts, w.reshape(-1) / w.sum(), m)
    assert ok, f"edge chi-square {stat:.1f} too high for df={df}"


def test_two_level_negative_sampler_matches_global_distribution():
    n, k = 64, 4
    idx, _ = _graph(n, k, seed=9)
    rng = np.random.default_rng(10)
    w = rng.uniform(0.2, 3.0, (n, k)).astype(np.float32)
    # global noise mass: deg(j)^0.75 with deg = out + in weighted degree
    deg = w.sum(1).copy()
    np.add.at(deg, np.asarray(idx).reshape(-1), w.reshape(-1))
    mass = deg ** 0.75

    n_shards, n_loc = 2, n // 2
    thr, ali, totals = [], [], []
    for s in range(n_shards):
        t, a = S.build_alias(mass[s * n_loc:(s + 1) * n_loc])
        thr.append(t); ali.append(a)
        totals.append(mass[s * n_loc:(s + 1) * n_loc].sum())
    sthr, sali = S.build_alias(np.asarray(totals))
    sampler = S.ShardedNodeSampler(
        jnp.asarray(np.stack(thr)), jnp.asarray(np.stack(ali)),
        jnp.asarray(sthr), jnp.asarray(sali), n_shards, n)

    m = 1 << 19
    ids = np.asarray(sampler.sample(jax.random.key(6), (m,)))
    counts = np.bincount(ids, minlength=n).astype(float)
    ok, stat, df = _chi2_ok(counts, mass / mass.sum(), m)
    assert ok, f"negative chi-square {stat:.1f} too high for df={df}"


def test_sharded_builder_marginals_reconstruct_weights():
    """Exactness (not sampling): threshold/alias tables from the sharded
    builder reconstruct each edge's draw probability w_e / T_s, and the
    shard table reconstructs T_s / T."""
    idx, _ = _graph(150, 6, seed=11)       # 150 rows, P | 150 not needed
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.uniform(0.1, 2.0, idx.shape).astype(np.float32))
    es, ns = S.build_samplers_sharded(idx, w)
    P_, E = es.threshold.shape
    w_np = np.asarray(w)
    n_loc = -(-idx.shape[0] // P_)
    for s in range(P_):
        thr = np.asarray(es.threshold[s], np.float64)
        ali = np.asarray(es.alias[s])
        marg = thr.copy()
        np.add.at(marg, ali, 1.0 - thr)
        marg /= E
        rows = slice(s * n_loc, min((s + 1) * n_loc, idx.shape[0]))
        w_loc = w_np[rows].reshape(-1)
        want = np.zeros(E)
        want[:w_loc.size] = w_loc / w_loc.sum()
        np.testing.assert_allclose(marg, want, atol=5e-7)
    sm = np.asarray(es.shard_threshold, np.float64).copy()
    np.add.at(sm, np.asarray(es.shard_alias), 1.0 - sm)
    sm /= P_
    tot = np.array([w_np[s * n_loc:(s + 1) * n_loc].sum() for s in range(P_)])
    np.testing.assert_allclose(sm, tot / tot.sum(), atol=5e-7)


# ---------------------------------------------------------------------------
# layout trajectories + end-to-end device residency
# ---------------------------------------------------------------------------

def test_local_sgd_trajectory_sharded_vs_flat_samplers():
    """Through the local-SGD driver the sharded sampler pytrees must
    reproduce the flat-sampler trajectory bitwise at one device (same
    tables, same key stream); integer weights keep the alias builds
    float-associativity-free."""
    idx, _ = _graph(203, 5, seed=13)
    rng = np.random.default_rng(14)
    w = jnp.asarray(rng.integers(1, 16, idx.shape).astype(np.float32))
    ef = S.build_edge_sampler(idx, w, impl="device")
    nf = S.build_negative_sampler(idx, w, impl="device")
    es, ns = S.build_samplers_sharded(idx, w)
    if es.n_shards != 1:
        pytest.skip("bitwise parity only defined at one device")
    mesh = make_mesh((1,), ("data",))
    cfg = LargeVisConfig(samples_per_node=60, batch_size=64, sync_every=4)
    r_flat = layout_lib.run_layout_local_sgd(KEY, ef, nf, 203, cfg, mesh)
    r_shard = layout_lib.run_layout_local_sgd(KEY, es, ns, 203, cfg, mesh)
    assert np.array_equal(np.asarray(r_flat.y), np.asarray(r_shard.y))


def test_distributed_pipeline_device_resident(monkeypatch):
    """largevis(distributed=True) end to end: the host Vose path is
    booby-trapped AND device->host transfers are disallowed across the
    graph-prep stages — KNN, calibration, symmetrization, and the
    sampler build never leave the mesh."""
    from repro.data.synthetic import gaussian_mixture

    def boom(*_a, **_k):
        raise AssertionError("host alias build reached in distributed mode")

    monkeypatch.setattr(S, "build_alias", boom)
    x, _ = gaussian_mixture(jax.random.key(4), 403, 12, 4)
    cfg = LargeVisConfig(n_neighbors=7, n_trees=2, n_explore_iters=1,
                         window=16, perplexity=5.0, samples_per_node=40,
                         batch_size=64, sync_every=4, distributed=True)
    with jax.transfer_guard_device_to_host("disallow"):
        idx, dist, w, _ = build_graph(x, jax.random.key(5), cfg=cfg)
        es, ns = S.build_samplers_sharded(idx, w, power=cfg.neg_power)
        jax.block_until_ready((es.threshold, ns.threshold))
    res = largevis(x, jax.random.key(6), cfg=cfg)
    assert res.y.shape == (403, cfg.out_dim)
    assert bool(jnp.all(jnp.isfinite(res.y)))


def test_distributed_linear_knn_routing():
    """``knn_distributed=False`` under ``distributed=True`` (the fig6
    scaling configuration): stage 1 is the paper's linear forest KNN —
    bitwise the non-distributed graph — while the weights still come
    out of the sharded calibrate/symmetrize drivers, bitwise-equal to
    the flat oracle, and the graph-prep stages stay device-resident."""
    from repro.data.synthetic import gaussian_mixture

    x, _ = gaussian_mixture(jax.random.key(7), 403, 12, 4)
    cfg = LargeVisConfig(n_neighbors=7, n_trees=2, n_explore_iters=1,
                         window=16, perplexity=5.0, samples_per_node=40,
                         batch_size=64, sync_every=4, distributed=True,
                         knn_distributed=False)
    with jax.transfer_guard_device_to_host("disallow"):
        idx, dist, w, _ = build_graph(x, jax.random.key(5), cfg=cfg)
        jax.block_until_ready(w)
    cfg_flat = dataclasses.replace(cfg, distributed=False)
    idx_f, dist_f, w_f, _ = build_graph(x, jax.random.key(5), cfg=cfg_flat)
    assert np.array_equal(np.asarray(idx), np.asarray(idx_f))
    assert np.array_equal(np.asarray(dist), np.asarray(dist_f))
    assert np.array_equal(np.asarray(w), np.asarray(w_f))


# ---------------------------------------------------------------------------
# real multi-device equality (8 host CPU devices, subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, SRC)
import jax, jax.numpy as jnp, numpy as np

from repro.configs.largevis_default import LargeVisConfig
from repro.core import perplexity as perp
from repro.core import sampler as S
from repro.core import layout as layout_lib
from repro.core.largevis import largevis
from repro.data.synthetic import gaussian_mixture
from repro.launch.mesh import make_data_mesh

assert len(jax.devices()) == 8, jax.devices()
rng = np.random.default_rng(0)
n, k = 2003, 9                                   # 2003 % 8 != 0
idx = np.stack([rng.choice(n - 1, k, replace=False) for _ in range(n)])
idx = jnp.asarray(np.where(idx >= np.arange(n)[:, None], idx + 1, idx),
                  jnp.int32)
d2 = jnp.asarray(rng.uniform(0.1, 4.0, (n, k)).astype(np.float32))

p_ref = perp.calibrate_p(d2, 7.0)
p_sh = perp.calibrate_p_sharded(d2, 7.0)
assert np.array_equal(np.asarray(p_ref), np.asarray(p_sh))
w_ref = perp.symmetrize(idx, p_ref)
w_sh = perp.symmetrize_sharded(idx, p_sh)
assert np.array_equal(np.asarray(w_ref), np.asarray(w_sh))
print("WEIGHTS_BITWISE_OK")

wi = jnp.asarray(rng.integers(1, 16, (n, k)).astype(np.float32))
es, ns = S.build_samplers_sharded(idx, wi)
assert es.n_shards == 8 and es.threshold.shape[0] == 8
n_loc = es.threshold.shape[1] // k
wi_np = np.asarray(wi)
for s in range(8):
    rows = slice(s * n_loc, min((s + 1) * n_loc, n))
    m = rows.stop - rows.start
    if m == n_loc:
        # full shard: tables bitwise == a standalone build of the slice
        ef = S.build_edge_sampler(np.asarray(idx)[rows], wi_np[rows],
                                  impl="device")
        assert np.array_equal(np.asarray(es.threshold[s]),
                              np.asarray(ef.threshold)), s
        assert np.array_equal(np.asarray(es.alias[s]),
                              np.asarray(ef.alias)), s
    # every shard (incl. the zero-padded last one): the table's marginals
    # reconstruct exactly w_e / T_s, zero mass on padded slots
    E = es.threshold.shape[1]
    marg = np.asarray(es.threshold[s], np.float64).copy()
    np.add.at(marg, np.asarray(es.alias[s]), 1.0 - marg)
    marg /= E
    w_loc = wi_np[rows].reshape(-1)
    want = np.zeros(E)
    want[:w_loc.size] = w_loc / w_loc.sum()
    np.testing.assert_allclose(marg, want, atol=5e-7)
print("SHARD_TABLES_OK")

x, _ = gaussian_mixture(jax.random.key(1), 1603, 12, 4)
cfg = LargeVisConfig(n_neighbors=7, n_trees=2, n_explore_iters=1,
                     window=16, perplexity=5.0, samples_per_node=60,
                     batch_size=64, sync_every=4, distributed=True)
res = largevis(x, jax.random.key(2), cfg=cfg)
assert res.y.shape == (1603, 2)
assert bool(jnp.all(jnp.isfinite(res.y)))
print("E2E_OK")
"""


@pytest.mark.slow
def test_sharded_stages_eight_devices():
    script = _SCRIPT.replace("SRC", repr(os.path.join(REPO, "src")))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "WEIGHTS_BITWISE_OK" in proc.stdout
    assert "SHARD_TABLES_OK" in proc.stdout
    assert "E2E_OK" in proc.stdout
