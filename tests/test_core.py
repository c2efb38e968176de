"""LargeVis core: KNN construction, exploring, weights, samplers, layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.largevis_default import LargeVisConfig
from repro.core import knn as knn_lib
from repro.core import metrics, perplexity
from repro.core import sampler as sampler_lib
from repro.core.largevis import largevis
from repro.core.neighbor_explore import (_explore_rows_round, neighbor_explore,
                                         reverse_neighbors, reverse_rows)
from repro.data.synthetic import gaussian_mixture

import hlo_checks

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def blobs():
    x, labels = gaussian_mixture(KEY, 2000, 32, 8)
    return x, labels


@pytest.fixture(scope="module")
def true_knn(blobs):
    x, _ = blobs
    return knn_lib.brute_force_knn(x, 15)


def test_brute_force_knn_correct(blobs):
    x, _ = blobs
    idx, dist = knn_lib.brute_force_knn(x[:300], 5)
    # exact check vs numpy on a small slice
    xn = np.asarray(x[:300], np.float64)
    d = ((xn[:, None] - xn[None]) ** 2).sum(-1)
    np.fill_diagonal(d, np.inf)
    want = np.argsort(d, axis=1)[:, :5]
    got_d = np.sort(np.asarray(dist), axis=1)
    want_d = np.sort(np.take_along_axis(d, want, 1), axis=1)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-3)


def test_forest_then_explore_recall_progression(blobs, true_knn):
    """Paper C1 (Fig 3): exploring lifts recall toward 1.0 in <=3 iters."""
    x, _ = blobs
    true_idx, _ = true_knn
    recalls = []
    for iters in (0, 1, 3):
        cfg = LargeVisConfig(n_neighbors=15, n_trees=4, n_explore_iters=iters,
                             window=32)
        idx, _ = knn_lib.build_knn_graph(x, KEY, cfg)
        recalls.append(knn_lib.knn_recall(idx, true_idx))
    assert recalls[1] > recalls[0] + 0.1, recalls
    assert recalls[2] > 0.9, recalls


def test_explore_never_worsens(blobs, true_knn):
    """Monotone invariant: merged top-k keeps current neighbors unless a
    strictly closer candidate exists — recall cannot decrease."""
    x, _ = blobs
    true_idx, _ = true_knn
    cfg = LargeVisConfig(n_neighbors=15, n_trees=2, n_explore_iters=0,
                         window=16)
    idx, dist = knn_lib.build_knn_graph(x, KEY, cfg)
    r_prev = knn_lib.knn_recall(idx, true_idx)
    for _ in range(2):
        idx, dist = neighbor_explore(x, idx, dist, iters=1, key=KEY)
        r = knn_lib.knn_recall(idx, true_idx)
        assert r >= r_prev - 1e-6
        r_prev = r


def test_brute_force_map_odd_tiles(blobs):
    """The lax.map oracle handles N % tile != 0 (padded row tiles) and
    never materializes an (N, N) distance matrix when tiled."""
    x, _ = blobs
    x = x[:403]
    idx, dist = knn_lib.brute_force_knn(x, 7, tile=128)
    assert idx.shape == (403, 7) and dist.shape == (403, 7)
    idx_n = np.asarray(idx)
    assert (idx_n != np.arange(403)[:, None]).all(), "self edges"
    assert ((idx_n >= 0) & (idx_n < 403)).all(), "padded rows leaked"
    xn = np.asarray(x, np.float64)
    d = ((xn[:, None] - xn[None]) ** 2).sum(-1)
    np.fill_diagonal(d, np.inf)
    want_d = np.sort(np.sort(d, axis=1)[:, :7], axis=1)
    np.testing.assert_allclose(np.sort(np.asarray(dist), axis=1), want_d,
                               rtol=1e-4, atol=1e-3)
    # one dispatch, tiled: the loop is inside the program, and no tile is
    # ever the full (N, N) matrix
    hlo = knn_lib.brute_force_knn.lower(x, 7, tile=128).as_text()
    assert "403x403" not in hlo, "full NxN distance matrix materialized"


def test_forest_knn_scan_matches_tree_loop(blobs):
    """The lax.scan over stacked tree codes is bitwise the per-tree Python
    loop over the same window fold, the lowered program holds no
    (N, n_trees*(k+1)) all-trees candidate concat, and the compiled body
    appears ONCE regardless of n_trees (same HLO op counts for 2 vs 4
    trees — the old loop unrolled the tree body n_trees times)."""
    from repro.kernels import ref as ref_lib
    x, _ = blobs
    N, k, n_trees, window = x.shape[0], 15, 4, 32
    depth = knn_lib._auto_depth(N, 64)
    idx, dist = knn_lib.forest_knn(x, KEY, n_trees=n_trees, depth=depth,
                                   k=k, window=window)
    # reference: Python loop over trees, same fold (the scan is pure
    # dispatch restructuring — trajectories must be bitwise identical)
    codes = knn_lib.hash_codes(x, KEY, n_trees, depth)
    run_i = jnp.full((N, k), -1, jnp.int32)
    run_d = jnp.full((N, k), ref_lib.INVALID_DIST, jnp.float32)
    for t in range(n_trees):
        run_i, run_d = knn_lib._window_fold_one_tree(
            x, codes[:, t], k, window, run_i, run_d, "auto")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(run_i))
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(run_d))

    def hlo(nt):
        return knn_lib.forest_knn.lower(
            x, KEY, n_trees=nt, depth=depth, k=k, window=window).as_text()

    h4 = hlo(n_trees)
    assert f"{N}x{n_trees * (k + 1)}x" not in h4, (
        "all-trees candidate concat materialized")
    # one scan body regardless of n_trees: every op the tree body lowers
    # to (sorts from the per-tree argsort, the fold's top-k) appears the
    # same number of times whether the forest has 2 or 4 trees
    h2 = hlo(2)
    for marker in ("sort(", "top-k", "while("):
        assert h4.count(marker) == h2.count(marker), (
            marker, h4.count(marker), h2.count(marker))


def test_merge_candidates_dedup_and_self():
    ids = jnp.array([[1, 1, 2, 0], [3, 2, 2, 1]], jnp.int32)
    d = jnp.array([[1., 1., 2., 3.], [5., 1., 1., 2.]], jnp.float32)
    self_idx = jnp.array([0, 1], jnp.int32)
    mi, md = knn_lib.merge_candidates(ids, d, 2, self_idx=self_idx)
    # row 0: self (0) suppressed, dup 1 suppressed -> [1, 2]
    assert set(np.asarray(mi[0]).tolist()) == {1, 2}
    # row 1: self (1) suppressed, dup 2 suppressed -> [2, 3]
    assert set(np.asarray(mi[1]).tolist()) == {2, 3}


def test_reverse_neighbors_contains_true_reverse():
    idx = jnp.array([[1, 2], [2, 0], [0, 1], [0, 1]], jnp.int32)
    rev = reverse_neighbors(idx, 4)
    # node 0 is listed by 1, 2, 3
    assert {1, 2, 3} <= set(np.asarray(rev[0]).tolist())


def _skewed_graph(n, k, seed=0):
    """(n, k) ids drawn Zipf-skewed: a few rows are named far more than k
    times, many never."""
    rng = np.random.default_rng(seed)
    hot = (rng.zipf(1.4, (n, k)) - 1) % n
    return np.where(rng.random((n, k)) < 0.7, hot,
                    rng.integers(0, n, (n, k))).astype(np.int32)


def _reverse_lists_np(idx, rows, r_cap):
    """For each row r of ``rows``: the first ``r_cap`` sources i whose
    list holds r, in ascending i (once per mention), padded with r."""
    out = np.repeat(np.asarray(rows, np.int32)[:, None], r_cap, axis=1)
    for j, r in enumerate(rows):
        src = np.nonzero(idx == r)[0][:r_cap]
        out[j, :src.shape[0]] = src
    return out


@pytest.mark.parametrize("r_cap", [4, 10, 16])
@pytest.mark.parametrize("kind", ["all", "contiguous", "wrapping",
                                  "repeats"])
def test_reverse_rows_match_definition(kind, r_cap):
    """The reverse lists, for every row (``reverse_neighbors``) or a block
    of rows (``reverse_rows``), are exactly the first r_cap sources in
    ascending order padded with self, also for rows whose in-degree
    exceeds r_cap (the last slot of those is a real source, not self)."""
    n, k = 500, 10
    idx = _skewed_graph(n, k)
    indeg = np.bincount(idx.ravel(), minlength=n)
    assert (indeg > r_cap).sum() >= 5
    rows = {"all": np.arange(n),
            "contiguous": np.arange(120, 184),
            "wrapping": (n - 25 + np.arange(60)) % n,
            "repeats": np.concatenate([np.argsort(-indeg)[:8], [0, 0, 7],
                                       np.argsort(-indeg)[:8]]),
            }[kind].astype(np.int32)
    if kind == "all":
        got = reverse_neighbors(jnp.asarray(idx), r_cap)
    else:
        got = reverse_rows(jnp.asarray(idx), jnp.asarray(rows), r_cap)
    assert got.shape == (rows.shape[0], r_cap)
    np.testing.assert_array_equal(np.asarray(got),
                                  _reverse_lists_np(idx, rows, r_cap))


def test_explore_rows_round_holds_no_full_reverse_table():
    """The rows round builds reverse lists for its rows only: no (N, r_cap)
    table of ids over the whole graph is in its program."""
    n, k, d, r_cap = 211, 6, 16, 5
    x = jax.random.normal(jax.random.key(5), (n, d), jnp.float32)
    idx = jnp.asarray(_skewed_graph(n, k, seed=1))
    dist = jnp.ones((n, k), jnp.float32)
    rows = jnp.arange(24, dtype=jnp.int32)
    text = _explore_rows_round.lower(x, idx, dist, rows, jax.random.key(0),
                                     sample=0, tile=8,
                                     r_cap=r_cap).as_text()
    hlo_checks.assert_has_op(text, "sort", what="one sort of the graph")
    hlo_checks.assert_no_buffer(text, (n, r_cap), what="full reverse table")


def test_perplexity_calibration(blobs):
    x, _ = blobs
    idx, dist = knn_lib.brute_force_knn(x, 30)
    p = perplexity.calibrate_p(dist, 10.0)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-4)
    realized = perplexity.perplexity_of(p)
    assert float(jnp.median(jnp.abs(realized - 10.0))) < 0.5


def test_symmetrize_weight_symmetry(blobs):
    """w_ij == w_ji whenever both directed edges exist."""
    x, _ = blobs
    idx, dist = knn_lib.brute_force_knn(x[:500], 10)
    w = perplexity.edge_weights(idx, dist, 5.0)
    idx_n, w_n = np.asarray(idx), np.asarray(w)
    W = {}
    for i in range(idx_n.shape[0]):
        for k in range(idx_n.shape[1]):
            W[(i, idx_n[i, k])] = w_n[i, k]
    checked = 0
    for (i, j), wij in W.items():
        if (j, i) in W:
            assert abs(wij - W[(j, i)]) < 1e-9
            checked += 1
    assert checked > 100


def test_alias_sampler_distribution():
    probs = np.array([0.1, 0.0, 0.4, 0.5])
    thr, alias = sampler_lib.build_alias(probs)
    idx = sampler_lib.sample_alias(KEY, jnp.asarray(thr), jnp.asarray(alias),
                                   (200_000,))
    freq = np.bincount(np.asarray(idx), minlength=4) / 200_000
    np.testing.assert_allclose(freq, probs, atol=0.01)
    assert freq[1] == 0.0


def test_negative_sampler_power_law():
    idx = jnp.array([[1], [0], [0], [0]], jnp.int32)   # node 0 high degree
    w = jnp.ones((4, 1), jnp.float32)
    ns = sampler_lib.build_negative_sampler(idx, w, power=0.75)
    s = np.asarray(ns.sample(KEY, (100_000,)))
    freq = np.bincount(s, minlength=4) / 100_000
    # deg = [out 1 + in 3, 1+1, 1, 1] = [4, 2, 1, 1] -> ^0.75 normalized
    want = np.array([4.0, 2.0, 1.0, 1.0]) ** 0.75
    want /= want.sum()
    np.testing.assert_allclose(freq, want, atol=0.01)


def test_layout_separates_clusters(blobs):
    """Paper C4 proxy: default hyper-params produce a layout whose 2D KNN
    classifier beats chance by a wide margin."""
    x, labels = blobs
    cfg = LargeVisConfig(n_neighbors=15, n_trees=4, n_explore_iters=2,
                         window=32, perplexity=10.0, samples_per_node=2000,
                         batch_size=4096)
    res = largevis(x, KEY, cfg=cfg)
    acc = metrics.knn_classifier_accuracy(res.y, labels, k=5)
    assert acc > 0.8, acc                                 # chance = 0.125
    assert jnp.isfinite(res.y).all()


def test_layout_gradient_direction():
    """Attractive edges pull together; negatives push apart (Eqn 6 signs)."""
    from repro.kernels.ref import largevis_grads_ref
    yi = jnp.array([[0.0, 0.0]])
    yj = jnp.array([[1.0, 0.0]])
    yn = jnp.array([[[50.0, 50.0]]])       # far negative: repulsion ~ 0
    gi, gj, gn = largevis_grads_ref(yi, yj, yn, neg_mask=jnp.ones((1, 1)))
    step_i = yi - 0.1 * gi
    assert jnp.linalg.norm(step_i - yj) < jnp.linalg.norm(yi - yj)
    # the positive partner moves toward yi too
    step_j = yj - 0.1 * gj
    assert jnp.linalg.norm(step_j - yi) < jnp.linalg.norm(yj - yi)
    # a CLOSE negative is pushed away from yi by its own step
    yn_close = jnp.array([[[0.3, 0.3]]])
    _, _, gn2 = largevis_grads_ref(yi, yj, yn_close,
                                   neg_mask=jnp.ones((1, 1)))
    step_n = yn_close - 0.1 * gn2
    assert jnp.linalg.norm(step_n[0, 0] - yi[0]) > jnp.linalg.norm(
        yn_close[0, 0] - yi[0])
