"""Out-of-sample projection, incremental graph maintenance, and the
continuous-batching projection server."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import LargeVis, LargeVisConfig
from repro.core import knn as knn_lib
from repro.core import transform as tr
from repro.core.neighbor_explore import _explore_round, neighbor_explore
from repro.data.synthetic import mnist_like
from repro.kernels import ops, ref

KEY = jax.random.key(0)

N_CORPUS, N_QUERY = 400, 120
# samples_per_node high enough that the corpus layout actually converges:
# an under-converged embedding fragments class clusters, and then the
# weighted-mean init (correctly) lands between fragments — the quality
# margin below is about the PROJECTION, so give it a converged corpus.
CFG = LargeVisConfig(n_neighbors=12, n_trees=4, samples_per_node=2000,
                     batch_size=128, perplexity=10.0, transform_steps=48)


@pytest.fixture(scope="module")
def data():
    x, labels = mnist_like(KEY, N_CORPUS + N_QUERY, 16, 5)
    return x, np.asarray(labels)


@pytest.fixture(scope="module")
def fitted(data):
    x, _ = data
    return LargeVis(cfg=CFG).fit(x[:N_CORPUS], jax.random.key(1))


def _knn_accuracy(y_corpus, labels_corpus, y_query, labels_query, k=5):
    """Classify each query by majority label of its k nearest corpus
    points in the 2-D embedding."""
    d = np.sum((y_query[:, None, :] - y_corpus[None, :, :]) ** 2, axis=-1)
    nn = np.argsort(d, axis=1)[:, :k]
    votes = labels_corpus[nn]
    pred = np.array([np.bincount(v).argmax() for v in votes])
    return float(np.mean(pred == labels_query))


# ---------------------------------------------------------------------------
# Frozen-rows kernel mode
# ---------------------------------------------------------------------------

def test_frozen_rows_bitwise_kernel_vs_ref():
    """n_frozen mode: kernel == jitted oracle bitwise, frozen rows
    bit-identical to their inputs — for scalar AND per-edge lr."""
    k = jax.random.key(3)
    n, s, b, m, nf = 64, 2, 40, 5, 48
    y = jax.random.normal(jax.random.fold_in(k, 0), (n, s), jnp.float32)
    i = jax.random.randint(jax.random.fold_in(k, 1), (b,), 0, n)
    j = jax.random.randint(jax.random.fold_in(k, 2), (b,), 0, n)
    negs = jax.random.randint(jax.random.fold_in(k, 3), (b, m), 0, n)
    mask = (negs != i[:, None]).astype(jnp.float32)
    for lr in (0.5, jax.random.uniform(jax.random.fold_in(k, 4), (b,))):
        got = ops.largevis_edge_step(y, i, j, negs, mask, lr, n_frozen=nf,
                                     impl="fused")
        oracle = jax.jit(functools.partial(
            ref.fused_edge_step_ref, n_frozen=nf))(y, i, j, negs, mask, lr)
        assert np.array_equal(
            np.asarray(got).view(np.uint32),
            np.asarray(oracle).view(np.uint32))
        assert np.array_equal(
            np.asarray(got[:nf]).view(np.uint32),
            np.asarray(y[:nf]).view(np.uint32))


def test_per_edge_lr_scalar_broadcast_bitwise():
    """A broadcast (B,) lr vector reproduces the scalar-lr path bitwise."""
    k = jax.random.key(5)
    n, s, b, m = 50, 2, 32, 4
    y = jax.random.normal(jax.random.fold_in(k, 0), (n, s), jnp.float32)
    i = jax.random.randint(jax.random.fold_in(k, 1), (b,), 0, n)
    j = jax.random.randint(jax.random.fold_in(k, 2), (b,), 0, n)
    negs = jax.random.randint(jax.random.fold_in(k, 3), (b, m), 0, n)
    mask = (negs != i[:, None]).astype(jnp.float32)
    a = ops.largevis_edge_step(y, i, j, negs, mask, 0.7, impl="fused")
    v = ops.largevis_edge_step(y, i, j, negs, mask, jnp.full((b,), 0.7),
                               impl="fused")
    assert np.array_equal(np.asarray(a).view(np.uint32),
                          np.asarray(v).view(np.uint32))


# ---------------------------------------------------------------------------
# Out-of-sample projection
# ---------------------------------------------------------------------------

def test_transform_freezes_corpus_bitwise(data, fitted):
    """The projection's concat embedding keeps every corpus row
    bit-identical (the kernel's n_frozen masking), and the fitted carrier is
    not mutated."""
    x, _ = data
    r = fitted.result_
    y_before = np.asarray(r.y, np.float32).copy()
    nn_idx, nn_dist = tr.query_neighbors(x[N_CORPUS:], r.x, CFG.n_neighbors)
    from repro.core.perplexity import calibrate_p
    p = calibrate_p(nn_dist, float(CFG.n_neighbors),
                    iters=CFG.perplexity_iters)
    y0 = tr._weighted_mean_init(p, nn_idx, r.y)
    y_full = jnp.concatenate([jnp.asarray(r.y, jnp.float32),
                              y0.astype(jnp.float32)])
    out = tr._project_scan(
        y_full, jax.random.key(9), jnp.log(p), nn_idx, r.neg_sampler,
        n_negatives=CFG.n_negatives, steps=int(CFG.transform_steps),
        rho0=float(CFG.rho0), prob_fn=CFG.prob_fn, a=CFG.prob_a,
        gamma=CFG.gamma, clip=CFG.grad_clip,
        layout_step="fused")
    assert np.array_equal(
        np.asarray(out[:N_CORPUS]).view(np.uint32),
        y_before.view(np.uint32))
    # the public path leaves the carrier untouched
    fitted.transform(x[N_CORPUS:])
    assert np.array_equal(np.asarray(r.y, np.float32).view(np.uint32),
                          y_before.view(np.uint32))


def test_project_scan_donates_embedding(fitted):
    """The scan is compiled with the (N+Q, s) buffer donated (aliased
    input->output), so projection adds no second embedding-sized buffer."""
    r = fitted.result_
    q, k = 8, CFG.n_neighbors
    y_full = jnp.zeros((N_CORPUS + q, 2), jnp.float32)
    kwargs = dict(n_negatives=CFG.n_negatives, steps=4, rho0=1.0,
                  prob_fn="inv_quadratic", a=1.0, gamma=7.0, clip=5.0,
                  layout_step="fused")
    compiled = tr._project_scan.lower(
        y_full, jax.random.key(0), jnp.zeros((q, k)),
        jnp.zeros((q, k), jnp.int32), r.neg_sampler, **kwargs).compile()
    assert "input_output_alias" in compiled.as_text()


def test_transform_quality_within_refit_margin(data, fitted):
    """Acceptance: projecting held-out queries lands them well enough that
    a KNN classifier in embedding space is within 0.05 of refitting the
    whole dataset from scratch."""
    x, labels = data
    y_corpus = np.asarray(fitted.embedding_)
    y_query = np.asarray(fitted.transform(x[N_CORPUS:]))
    assert np.isfinite(y_query).all()
    acc_transform = _knn_accuracy(y_corpus, labels[:N_CORPUS],
                                  y_query, labels[N_CORPUS:])

    refit = LargeVis(cfg=CFG).fit(x, jax.random.key(1))
    y_all = np.asarray(refit.embedding_)
    acc_refit = _knn_accuracy(y_all[:N_CORPUS], labels[:N_CORPUS],
                              y_all[N_CORPUS:], labels[N_CORPUS:])
    assert acc_transform >= acc_refit - 0.05, (acc_transform, acc_refit)


# ---------------------------------------------------------------------------
# Incremental graph maintenance
# ---------------------------------------------------------------------------

def test_knn_insert_recall_vs_fresh_build(data, fitted):
    """Insert-maintained graph recall tracks a fresh brute-force build."""
    x, _ = data
    r = fitted.result_
    x_all, idx_all, dist_all = tr.knn_insert(
        r.x, r.knn_idx, r.knn_dist, x[N_CORPUS:], key=jax.random.key(7),
        cfg=CFG)
    assert idx_all.shape == (N_CORPUS + N_QUERY, CFG.n_neighbors)
    fresh_idx, _ = knn_lib.brute_force_knn(x, k=CFG.n_neighbors)
    hit = (np.asarray(idx_all)[:, :, None]
           == np.asarray(fresh_idx)[:, None, :]).any(axis=1)
    recall = float(hit.mean())
    assert recall > 0.9, recall
    # distances stay consistent with the ids they claim
    x_np = np.asarray(x_all)
    row = x_np[10] - x_np[np.asarray(idx_all)[10]]
    np.testing.assert_allclose(np.sum(row * row, axis=1),
                               np.asarray(dist_all)[10], rtol=1e-4,
                               atol=1e-4)


def test_neighbor_explore_rows_subset(data):
    """rows= explores only the given rows: untouched rows bit-identical,
    explored rows never get worse."""
    x, _ = data
    x = x[:200]
    idx, dist = knn_lib.brute_force_knn(x, k=8)
    # corrupt some rows to give exploring work to do
    bad = jnp.arange(0, 200, 7, dtype=jnp.int32)
    idx = idx.at[bad].set(jnp.broadcast_to(
        jnp.arange(8, dtype=jnp.int32), (bad.shape[0], 8)))
    xb = np.asarray(x)
    corrupted = xb[np.asarray(bad)][:, None, :] - xb[None, :8, :]
    dist = dist.at[bad].set(jnp.asarray(
        np.sum(corrupted * corrupted, axis=-1), jnp.float32))
    idx2, dist2 = neighbor_explore(x, idx, dist, iters=2,
                                   key=jax.random.key(3), rows=bad)
    untouched = np.setdiff1d(np.arange(200), np.asarray(bad))
    assert np.array_equal(np.asarray(idx2)[untouched],
                          np.asarray(idx)[untouched])
    assert float(jnp.mean(dist2[bad])) <= float(jnp.mean(dist[bad]))


def test_explore_rows_match_full_round():
    """Exploring a block of rows (wrapping at N, with a repeat) gives on
    those rows the same (ids, dists) as one full round over every row
    from the same graph, and leaves the other rows alone.  The graph is
    skewed, so some rows are named by more than K others."""
    n, k, d = 211, 6, 16
    rng = np.random.default_rng(1)
    hot = (rng.zipf(1.4, (n, k)) - 1) % n
    idx = np.where(rng.random((n, k)) < 0.7, hot, rng.integers(0, n, (n, k)))
    idx = np.where(idx == np.arange(n)[:, None], (idx + 1) % n, idx)
    assert (np.bincount(idx.ravel(), minlength=n) > k).sum() >= 5
    x = jax.random.normal(jax.random.key(5), (n, d), jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    diff = x[idx] - x[:, None, :]
    dist = jnp.sum(diff * diff, axis=-1)
    key = jax.random.key(9)
    full_i, full_d = _explore_round(x, idx, dist, key, sample=0, tile=64,
                                    r_cap=k)
    rows = jnp.asarray(np.r_[(n - 30 + np.arange(70)) % n, 3], jnp.int32)
    sub_i, sub_d = neighbor_explore(x, idx, dist, iters=1, sample=0, key=key,
                                    tile=16, rows=rows)
    np.testing.assert_array_equal(np.asarray(sub_i[rows]),
                                  np.asarray(full_i[rows]))
    np.testing.assert_array_equal(np.asarray(sub_d[rows]),
                                  np.asarray(full_d[rows]))
    rest = np.setdiff1d(np.arange(n), np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(sub_i)[rest],
                                  np.asarray(idx)[rest])


def test_estimator_insert_grows_model(data, fitted):
    """insert() returns coords for the new points, grows every carrier
    field consistently, and never moves existing embedding rows."""
    x, _ = data
    import pickle
    m = pickle.loads(pickle.dumps(fitted))     # work on a copy
    y_before = np.asarray(m.embedding_).copy()
    y_new = m.insert(x[N_CORPUS:])
    assert y_new.shape == (N_QUERY, 2)
    r = m.result_
    n_all = N_CORPUS + N_QUERY
    assert r.x.shape[0] == n_all
    assert r.y.shape[0] == n_all
    assert r.knn_idx.shape == (n_all, CFG.n_neighbors)
    assert r.weights.shape == (n_all, CFG.n_neighbors)
    assert r.neg_sampler.n_nodes == n_all
    assert np.array_equal(np.asarray(r.y[:N_CORPUS]), y_before)
    # the grown model serves transforms
    yq = m.transform(x[:3])
    assert np.isfinite(np.asarray(yq)).all()


# ---------------------------------------------------------------------------
# Continuous-batching projection server
# ---------------------------------------------------------------------------

def test_projection_engine_round_trip(data, fitted):
    """More requests than slots: everything retires with finite coords,
    latencies are recorded, and the corpus stays bit-frozen through all
    the traffic."""
    from repro.launch.serve_projection import ProjectionEngine, ProjectRequest
    x, _ = data
    y_ref = np.asarray(fitted.embedding_, np.float32).copy()
    eng = ProjectionEngine(fitted.result_, slots=16, seed=2)
    reqs = [ProjectRequest(i, np.asarray(x[N_CORPUS + i % N_QUERY]))
            for i in range(50)]
    for r in reqs:
        eng.submit(r)
    n_steps = eng.run()
    assert all(r.done for r in reqs)
    ys = np.stack([r.y for r in reqs])
    assert np.isfinite(ys).all()
    assert all(r.latency >= 0 for r in reqs)
    assert n_steps >= int(CFG.transform_steps)
    assert np.array_equal(
        np.asarray(eng.y_full[:N_CORPUS]).view(np.uint32),
        y_ref.view(np.uint32))


def test_projection_engine_deterministic(data, fitted):
    """Same seed + same submission order -> bitwise-identical results."""
    from repro.launch.serve_projection import ProjectionEngine, ProjectRequest
    x, _ = data

    def serve():
        eng = ProjectionEngine(fitted.result_, slots=8, seed=4)
        reqs = [ProjectRequest(i, np.asarray(x[N_CORPUS + i]))
                for i in range(12)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return np.stack([r.y for r in reqs])

    a, b = serve(), serve()
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))
