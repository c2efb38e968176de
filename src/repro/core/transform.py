"""Out-of-sample projection + incremental graph maintenance.

The paper's pipeline is batch-only: the layout exists for exactly the
points the KNN graph was built over.  This module adds the two *online*
operations on top of a fitted model, reusing the batch machinery:

* :func:`project` — embed Q held-out queries into a FROZEN fitted layout.
  One ``ops.topk_sqdist(queries, corpus, k)`` call finds each query's
  corpus neighborhood; the existing row-local perplexity bisection
  (``perplexity.calibrate_p``) turns the neighbor distances into a
  per-query distribution p_{.|q} (Eqn 1 applied to the query row); each
  query initializes at the p-weighted mean of its neighbors' fitted
  coordinates and then runs a short scan of the SAME fused edge step the
  batch layout uses (``layout_engine.apply_edge_batch``) over the concat
  embedding [corpus; queries] with ``n_frozen = N`` — corpus rows
  contribute attractive/repulsive forces but their updates are masked to
  -0.0 inside the kernel, so the fitted embedding stays BIT-identical
  (asserted in tests/test_transform.py).  Positive edges are drawn
  q -> neighbor ∝ p_{.|q} (the alias-sampler analogue for a row-local
  distribution is one ``categorical``), negatives from the fitted noise
  sampler.

* :func:`knn_insert` — grow the (N, K) KNN graph by Q new points without
  a rebuild.  New rows get one streaming top-k against the corpus merged
  (``knn.merge_candidates``) with a query-vs-query top-k; existing rows
  adopt new points through a reverse-candidate scatter (sorted by
  destination, carrying distances along); then
  ``neighbor_explore(rows=touched)`` repairs only the affected rows
  through the standard exploring machinery.  Recall against a fresh build
  is pinned in tests/test_transform.py.

Both entry points are wrapped by the :class:`repro.LargeVis` estimator
(``transform`` / ``insert``); the continuous-batching projection server
(``launch/serve_projection.py``) drives :func:`sample_query_edges` +
``apply_edge_batch`` directly with per-slot learning rates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.largevis_default import LargeVisConfig
from repro.core import knn as knn_lib
from repro.core import neighbor_explore as explore_lib
from repro.core import perplexity as perp_lib
from repro.core.layout_engine import apply_edge_batch
from repro.core.sampler import NodeSampler
from repro.kernels import ops


def uniform_node_sampler(n: int) -> NodeSampler:
    """Uniform noise distribution as a degenerate alias table (threshold 1
    everywhere -> every draw keeps its uniform bin).  The fallback when a
    fitted negative sampler is not available."""
    return NodeSampler(threshold=jnp.ones((n,), jnp.float32),
                       alias=jnp.arange(n, dtype=jnp.int32), n_nodes=n)


def query_neighbors(x_new, x, k: int, *, impl: str = "auto"):
    """Each query's k nearest corpus points: ids (Q, k), sqdists (Q, k).

    One streaming fused distance->top-k call — no (Q, N) distance matrix
    at any Q/N (see ``kernels.ops.topk_sqdist``)."""
    return ops.topk_sqdist(jnp.asarray(x_new), jnp.asarray(x), k, impl=impl)


@jax.jit
def _weighted_mean_init(p, nn_idx, y):
    """Init each query at the p-weighted mean of its neighbors' coords."""
    return jnp.einsum("qk,qks->qs", p, y[nn_idx])


def sample_query_edges(key, p_log, nn_idx, neg_sampler, n_negatives: int):
    """One positive + M negatives per query row.

    Positive: neighbor column ∝ exp(p_log) per row (the row-local analogue
    of the batch pipeline's alias edge sampling).  Negatives: the fitted
    noise distribution; collisions with the positive are masked exactly as
    in ``layout_engine.sgd_edge_step``.  Returns (j, negs, neg_mask)."""
    kj, kn = jax.random.split(key)
    cols = jax.random.categorical(kj, p_log, axis=-1)            # (Q,)
    j = jnp.take_along_axis(nn_idx, cols[:, None], axis=1)[:, 0]
    negs = neg_sampler.sample(kn, (p_log.shape[0], n_negatives))
    neg_mask = (negs != j[:, None]).astype(jnp.float32)
    return j, negs, neg_mask


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("n_negatives", "steps", "rho0",
                                    "prob_fn", "a", "gamma", "clip",
                                    "layout_step"))
def _project_scan(y_full, base_key, p_log, nn_idx, neg_sampler, *,
                  n_negatives: int, steps: int, rho0: float,
                  prob_fn: str, a: float, gamma: float, clip: float,
                  layout_step: str):
    """``steps`` frozen-corpus SGD steps over [corpus; queries].

    ``y_full`` is donated (one (N+Q, s) buffer for the whole scan); rows
    below ``N+Q - Q`` are frozen via the kernel's ``n_frozen`` masking.
    The (key, lr) stream mirrors ``scan_layout_steps``: step k folds k
    into ``base_key`` and sits at schedule position k/steps."""
    n_frozen = y_full.shape[0] - p_log.shape[0]
    q = p_log.shape[0]
    i = n_frozen + jnp.arange(q, dtype=jnp.int32)
    step_ids = jnp.arange(steps, dtype=jnp.int32)
    t_fracs = step_ids.astype(jnp.float32) / steps

    def one(y, sx):
        sid, tf = sx
        key = jax.random.fold_in(base_key, sid)
        j, negs, neg_mask = sample_query_edges(
            key, p_log, nn_idx, neg_sampler, n_negatives)
        lr = rho0 * jnp.maximum(1.0 - tf, 1e-4)
        y = apply_edge_batch(
            y, i, j, negs, neg_mask, lr, prob_fn=prob_fn, a=a, gamma=gamma,
            clip=clip, layout_step=layout_step, n_frozen=n_frozen)
        return y, None

    y_full, _ = jax.lax.scan(one, y_full, (step_ids, t_fracs))
    return y_full


def project(x_new, *, x, y, key=None, cfg: LargeVisConfig | None = None,
            neg_sampler=None, nn_idx=None, nn_dist=None):
    """Project queries into a fitted layout; the corpus never moves.

    x_new (Q, d) queries; x (N, d) fitted corpus points; y (N, s) fitted
    layout.  ``neg_sampler`` is the fitted noise :class:`NodeSampler`
    (uniform fallback when absent); ``nn_idx``/``nn_dist`` skip the
    corpus top-k when the caller already has the query neighborhoods
    (the serving engine batches that call across admits).

    Returns ``(y_new (Q, s), aux)`` with ``aux = {nn_idx, nn_dist, p}``
    — the query neighborhoods feed :func:`knn_insert` and the estimator's
    ``insert``.
    """
    cfg = cfg if cfg is not None else LargeVisConfig()
    if key is None:
        key = jax.random.key(cfg.seed)
    x_new = jnp.asarray(x_new)
    n = x.shape[0]
    if x_new.shape[0] == 0:
        return jnp.zeros((0, y.shape[1]), y.dtype), {
            "nn_idx": jnp.zeros((0, min(cfg.n_neighbors, n)), jnp.int32),
            "nn_dist": jnp.zeros((0, min(cfg.n_neighbors, n)), jnp.float32),
            "p": jnp.zeros((0, min(cfg.n_neighbors, n)), jnp.float32)}
    k = min(cfg.n_neighbors, n)
    if nn_idx is None:
        nn_idx, nn_dist = query_neighbors(x_new, x, k)
    p = perp_lib.calibrate_p(nn_dist, min(cfg.perplexity, float(k)),
                             iters=cfg.perplexity_iters)
    y0 = _weighted_mean_init(p, nn_idx, jnp.asarray(y))
    y_full = jnp.concatenate([jnp.asarray(y, jnp.float32),
                              y0.astype(jnp.float32)])
    if neg_sampler is None:
        neg_sampler = uniform_node_sampler(n)
    rho0 = cfg.transform_rho0 or cfg.rho0
    y_full = _project_scan(
        y_full, key, jnp.log(p), nn_idx, neg_sampler,
        n_negatives=cfg.n_negatives, steps=int(cfg.transform_steps),
        rho0=float(rho0), prob_fn=cfg.prob_fn, a=cfg.prob_a,
        gamma=cfg.gamma, clip=cfg.grad_clip,
        layout_step=cfg.routing.layout_step)
    return y_full[n:], {"nn_idx": nn_idx, "nn_dist": nn_dist, "p": p}


# ---------------------------------------------------------------------------
# Incremental KNN graph maintenance
# ---------------------------------------------------------------------------

def _reverse_candidates(dst, src, dist, n: int, r_cap: int):
    """Scatter directed candidate edges (src -> dst) into per-``dst`` slots.

    A sorted scatter (sort by destination, rank within segment, cap at
    ``r_cap``) that carries the candidate distance along.  Unfilled slots
    hold the row's own index at INF distance — inert under
    ``merge_candidates``."""
    e = dst.shape[0]
    order = jnp.argsort(dst)
    dst_s, src_s, d_s = dst[order], src[order], dist[order]
    seg_start = jnp.searchsorted(dst_s, jnp.arange(n))
    rank = jnp.arange(e) - seg_start[dst_s]
    keep = rank < r_cap
    slot = jnp.clip(rank, 0, r_cap - 1)
    ids = jnp.full((n, r_cap), -1, jnp.int32)
    ids = ids.at[dst_s, slot].set(jnp.where(keep, src_s, -1))
    ds = jnp.full((n, r_cap), knn_lib.INF, jnp.float32)
    ds = ds.at[dst_s, slot].set(jnp.where(keep, d_s, knn_lib.INF))
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    return jnp.where(ids < 0, rows, ids), ds


@functools.partial(jax.jit, static_argnames=("k",))
def _insert_merge(x, knn_idx, knn_dist, x_new, qc_idx, qc_dist, *, k: int):
    """Pure merge step of :func:`knn_insert`: build the (N+Q, k) graph.

    Query rows: corpus top-k merged with a query-vs-query top-k (global
    ids N..N+Q-1).  Corpus rows: existing lists merged with the reverse
    candidates induced by the queries' corpus neighborhoods."""
    n, q = x.shape[0], x_new.shape[0]
    self_q = n + jnp.arange(q, dtype=jnp.int32)

    # --- query rows -----------------------------------------------------
    kq = min(k, q)
    qq_idx, qq_dist = ops.topk_sqdist(x_new, x_new, kq)
    q_ids = jnp.concatenate([qc_idx, n + qq_idx], axis=1)
    q_ds = jnp.concatenate([qc_dist, qq_dist], axis=1)
    q_idx, q_dist = knn_lib.merge_candidates(q_ids, q_ds, k, self_idx=self_q)

    # --- corpus rows: adopt new points via reverse candidates -----------
    rev_ids, rev_ds = _reverse_candidates(
        qc_idx.reshape(-1),
        jnp.repeat(self_q, qc_idx.shape[1]),
        qc_dist.reshape(-1), n, r_cap=min(k, max(q, 1)))
    c_ids = jnp.concatenate([knn_idx, rev_ids], axis=1)
    c_ds = jnp.concatenate([knn_dist, rev_ds], axis=1)
    c_idx, c_dist = knn_lib.merge_candidates(
        c_ids, c_ds, k, self_idx=jnp.arange(n, dtype=jnp.int32))

    changed = jnp.any((c_idx != knn_idx) | (c_dist != knn_dist), axis=1)
    return (jnp.concatenate([c_idx, q_idx]),
            jnp.concatenate([c_dist, q_dist]), changed)


def knn_insert(x, knn_idx, knn_dist, x_new, *, key=None,
               cfg: LargeVisConfig | None = None, explore_iters: int = 1,
               qc_idx=None, qc_dist=None):
    """Insert Q new points into an (N, K) KNN graph without a rebuild.

    Returns ``(x_all (N+Q, d), knn_idx (N+Q, K), knn_dist (N+Q, K))``.

    Three phases: (1) one streaming top-k gives each new point its corpus
    neighborhood (reused from :func:`project` via ``qc_idx``/``qc_dist``
    when available); (2) a jitted merge splices the new rows in and lets
    corpus rows adopt closer new points through a reverse-candidate
    scatter; (3) ``explore_iters`` rounds of neighbor exploring over ONLY
    the touched rows (new rows + corpus rows whose lists changed) repair
    second-order effects — "a neighbor of my (new) neighbor" — through
    the same machinery the batch build uses, at O(touched) not O(N).
    """
    cfg = cfg if cfg is not None else LargeVisConfig()
    if key is None:
        key = jax.random.key(cfg.seed)
    x = jnp.asarray(x)
    x_new = jnp.asarray(x_new, x.dtype)
    n, k = knn_idx.shape
    if x_new.shape[0] == 0:
        return x, knn_idx, knn_dist
    if qc_idx is None:
        qc_idx, qc_dist = query_neighbors(x_new, x, k)
    x_all = jnp.concatenate([x, x_new])
    idx_all, dist_all, changed = _insert_merge(
        x, knn_idx, knn_dist, x_new, qc_idx, qc_dist, k=k)
    if explore_iters:
        touched = np.concatenate([
            np.nonzero(np.asarray(changed))[0],
            np.arange(n, n + x_new.shape[0])]).astype(np.int32)
        idx_all, dist_all = explore_lib.neighbor_explore(
            x_all, idx_all, dist_all, iters=explore_iters,
            sample=cfg.explore_sample, key=key, rows=jnp.asarray(touched))
    return x_all, idx_all, dist_all
