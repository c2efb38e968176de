"""LargeVis top-level API: data matrix in, 2D/3D layout out.

    from repro import LargeVis                 # the estimator front door
    model = LargeVis(n_neighbors=50).fit(x)
    coords = model.embedding_                  # (N, 2)

    from repro.core.largevis import largevis   # functional form
    result = largevis(x, key=jax.random.key(0))
    coords = result.y          # (N, 2)

``largevis()`` is the functional core the :class:`repro.LargeVis`
estimator wraps — both run the identical pipeline with the identical key
stream, so their outputs are bitwise-equal (pinned in tests/test_api.py).

Pipeline = the paper's two stages: (1) approximate KNN graph (projection
forest + neighbor exploring + perplexity-calibrated weights), (2)
probabilistic layout via edge-sampling SGD.

``LargeVisConfig(distributed=True, data_shards=P)`` routes stage 1
through the sharded multi-device pipeline (`core/knn_sharded.py`) — the
point set is sharded over a 1-D "data" mesh and the graph is built with
ring-streamed distance tiles (see README, "Multi-device on CPU").

Stage 2 steps through the scan-fused layout engine
(`core/layout_engine.py`): ``cfg.steps_per_dispatch`` SGD steps per
device dispatch with a donated coordinate buffer.  Passing a
``callback`` selects the per-step Python loop (one dispatch per step)
so progress can be observed mid-layout.

The stage-1 -> stage-2 hand-off is device-resident: with
``cfg.sampler_impl`` ``"device"``/``"auto"`` the alias tables are built
by a jitted, exact fixed-point prefix-sum construction (`core/sampler.py`)
directly from the device graph, and the samplers flow into every layout
driver as JAX pytrees — no host materialization of ``idx``/``weights``
between the stages, which keeps the boundary on device instead of
minutes of single-core Vose at the paper's E = 150M.
"""
from __future__ import annotations

import dataclasses
import time
import warnings

import jax

from repro.configs.largevis_default import LargeVisConfig
from repro.core import knn as knn_lib
from repro.core import layout as layout_lib
from repro.core import perplexity as perp_lib
from repro.core import sampler as sampler_lib
from repro.runtime.fault_tolerance import DegradedModeWarning, InjectedFault


@dataclasses.dataclass
class LargeVisResult:
    """Fitted-model carrier: everything the online operations need.

    Field contract (what ``transform`` reads vs what ``insert`` rewrites):

    * ``y``/``knn_idx``/``knn_dist``/``weights`` — the fitted embedding
      and graph.  ``transform`` treats ALL of them as **frozen**: a
      projection never mutates the carrier, and the corpus rows of the
      concat embedding it optimizes are bit-identical to ``y`` (the
      kernel's ``n_frozen`` masking — asserted in tests).  ``insert``
      **rewrites** them: rows are appended and existing rows may adopt
      new neighbors (graph + weights) — but never move in ``y``.
    * ``x`` — the corpus points (needed by ``transform``/``insert`` for
      query neighborhoods; ``None`` when built by the pre-PR-7 shim path
      that never captured inputs).
    * ``edge_sampler``/``neg_sampler`` — the alias-table pytrees from the
      stage boundary; ``transform`` draws negatives from ``neg_sampler``;
      ``insert`` rebuilds both.  ``None`` under ``distributed`` sharded
      layouts (per-shard tables stay on their mesh).
    * ``cfg``/``key`` — the exact config and top-level PRNG key of the
      fit, so any stage can be re-derived; frozen forever.
    * ``timings``/``edge_samples`` — diagnostics; ``insert`` leaves them
      describing the original fit.
    """
    y: jax.Array                 # (N, s) layout
    knn_idx: jax.Array           # (N, K)
    knn_dist: jax.Array          # (N, K) squared distances
    weights: jax.Array           # (N, K) symmetrized edge weights
    timings: dict
    edge_samples: int
    x: jax.Array | None = None           # (N, d) corpus points
    edge_sampler: object | None = None   # sampler.EdgeSampler pytree
    neg_sampler: object | None = None    # sampler.NodeSampler pytree
    cfg: LargeVisConfig | None = None
    key: jax.Array | None = None         # top-level fit key (pre-split)


def _apply_autotune_mode(cfg: LargeVisConfig) -> None:
    """Honor ``cfg.routing.autotune`` for this process.

    ``"auto"`` restores env control (the AUTOTUNE variable, default
    ``cache``); anything else pins the mode.  ``set_mode`` clears the jit
    caches only on an actual change, so repeated fits with the same
    setting pay nothing."""
    m = getattr(getattr(cfg, "routing", None), "autotune", "auto")
    from repro.runtime import autotune
    autotune.set_mode(None if m in ("auto", None) else m)


def _data_mesh(cfg: LargeVisConfig):
    """The 1-D "data" mesh every distributed stage shares."""
    from repro.launch.mesh import make_data_mesh
    return make_data_mesh(cfg.data_shards)


def _stage_ckpt(x, key, cfg: LargeVisConfig):
    """StageCheckpointer for the graph-prep stages, else None.

    Unlike the layout's, this fingerprint includes a strided sample of
    the DATA — resuming a prep stage against different points would
    silently hand stage 2 another dataset's graph."""
    if getattr(cfg, "checkpoint", None) is None:
        return None
    from repro.checkpoint.largevis_state import (StageCheckpointer,
                                                 run_fingerprint)
    return StageCheckpointer(cfg.checkpoint, run_fingerprint(x, key, cfg))


def build_graph(x, key, *, cfg: LargeVisConfig | None = None, fault=None):
    """Stage 1: KNN graph + calibrated weights.

    ``cfg`` is keyword-only as of PR 7 (``cfg=None`` means a fresh
    default — never the shared ``DEFAULT`` singleton).

    With ``cfg.distributed`` every sub-stage runs on the same 1-D
    "data" mesh: the ring-streamed KNN build, then row-parallel
    perplexity calibration and all-gather symmetrization
    (`core/perplexity.py` sharded drivers) — the graph never leaves the
    mesh between KNN and weights, and the sharded weights are
    bitwise-equal to the single-device path.

    With ``cfg.checkpoint`` each sub-stage result (``graph``: the KNN
    index/distances; ``weights``: the calibrated+symmetrized edge
    weights) is persisted atomically at its boundary and restored on a
    rerun — a kill anywhere in stage 1 resumes at the last completed
    sub-stage with bitwise-equal outputs (the graph is deterministic in
    ``(x, key, cfg)``, which is exactly what the fingerprint binds).
    Checkpoints are **topology-portable**: arrays are stored global with
    the writing mesh as a metadata tag, the fingerprint excludes the
    mesh shape, and restores re-shard onto the current mesh
    (``StageCheckpointer.restore``) — a run checkpointed on P devices
    resumes on any P', and the sharded graph-prep stages are themselves
    bitwise P-invariant, so the resumed outputs match a single-device
    run exactly (tests/test_elastic.py).
    ``fault`` fires at sites ``stage:graph`` / ``stage:weights`` after
    each boundary commits (the kill-matrix hook), plus the per-shard
    sites ``knn_ring_step:<s>`` / ``calibrate_shard:<s>`` /
    ``symmetrize_exchange:<s>`` inside the sharded stages — an injected
    shard fault surfaces as
    :class:`~repro.runtime.fault_tolerance.ShardFailedError` for the
    mesh-recovery loop in :func:`largevis`."""
    cfg = cfg if cfg is not None else LargeVisConfig()
    _apply_autotune_mode(cfg)
    ckpt = _stage_ckpt(x, key, cfg)
    mesh = _data_mesh(cfg) if cfg.distributed else None
    idx = dist = w = None
    topo = None
    if ckpt is not None:
        from repro.checkpoint.largevis_state import topology_tag
        topo = {"topology": topology_tag(cfg, x.shape[0])}
        jnp = jax.numpy
        cached = ckpt.restore("graph", mesh=mesh)
        if cached is not None:
            idx = jnp.asarray(cached[0]["idx"])
            dist = jnp.asarray(cached[0]["dist"])
        cached = ckpt.restore("weights", mesh=mesh)
        if cached is not None and idx is not None:
            w = jnp.asarray(cached[0]["w"])
    t0 = time.time()
    if idx is None:
        idx, dist = knn_lib.build_knn_graph(x, key, cfg, fault=fault)
        # block (no transfer) so knn_s/weights_s split the stages honestly —
        # async dispatch would otherwise smear KNN compute into weights_s
        jax.block_until_ready((idx, dist))
        if ckpt is not None:
            ckpt.save("graph", {"idx": idx, "dist": dist}, extra=topo)
        if fault is not None:
            fault.fire("stage:graph")
    t1 = time.time()
    if w is None:
        if cfg.distributed:
            w = perp_lib.edge_weights_sharded(idx, dist, cfg.perplexity,
                                              iters=cfg.perplexity_iters,
                                              mesh=mesh, fault=fault)
        else:
            w = perp_lib.edge_weights(idx, dist, cfg.perplexity,
                                      iters=cfg.perplexity_iters)
        jax.block_until_ready(w)
        if ckpt is not None:
            ckpt.save("weights", {"w": w}, extra=topo)
        if fault is not None:
            fault.fire("stage:weights")
    t2 = time.time()
    return idx, dist, w, {"knn_s": t1 - t0, "weights_s": t2 - t1}


def layout_graph(knn_idx, weights, key, *, cfg: LargeVisConfig | None = None,
                 callback=None, return_samplers: bool = False, fault=None):
    """Stage 2: probabilistic layout of a weighted KNN graph.

    ``cfg`` is keyword-only as of PR 7.  With ``return_samplers=True`` the
    return value grows to ``(res, (edge_sampler, neg_sampler), timings)``
    so fitted-model callers (``largevis()`` -> :class:`LargeVisResult`)
    can carry the stage-boundary pytrees without rebuilding them;
    sharded (``distributed``) samplers stay on their mesh and are
    surfaced as ``None``.

    ``cfg.sampler_impl`` selects the alias-table builder at the stage
    boundary: ``"device"`` (what ``"auto"`` resolves to) builds the tables
    in one jitted computation straight from the (possibly sharded) device
    graph — stage-1 outputs never round-trip through the host; ``"host"``
    is the numpy Vose oracle.  The ``sampler_s`` timing isolates table
    construction from the layout itself (tables are blocked on, so async
    dispatch cannot smear build time into ``layout_s``).

    With ``cfg.distributed`` the alias tables are built *per shard* on
    the data mesh (`sampler.build_samplers_sharded`: each shard owns the
    alias table over its own edges plus a tiny replicated
    shard-selection table) and the layout runs through the local-SGD
    driver with the edge tables left sharded — samplers stay
    device-resident pytrees end to end, exactly like the single-device
    boundary.

    Robustness: with ``cfg.checkpoint`` the single-device alias tables
    are persisted at the stage boundary (``samplers``) and the layout
    self-checkpoints per chunk (see ``run_layout``); a failed device
    sampler build demotes to the host Vose oracle with one
    ``DegradedModeWarning``.  The distributed path skips the sampler
    checkpoint (per-shard tables stay on their mesh; the build is
    deterministic and cheap to redo) and checkpoints the layout at round
    granularity.  ``fault`` fires ``stage:samplers`` after the boundary
    commits and threads into the layout driver."""
    cfg = cfg if cfg is not None else LargeVisConfig()
    _apply_autotune_mode(cfg)
    ckpt = None if cfg.distributed else _stage_ckpt(weights, key, cfg)
    edge_s = neg_s = None
    if ckpt is not None:
        from repro.checkpoint import largevis_state as lvs
        cached = ckpt.load("samplers")
        if cached is not None:
            tree, _, extra = cached
            edge_s, neg_s = lvs._samplers_from_tree(
                tree, extra["sampler_static"])
    t0 = time.time()
    if cfg.distributed:
        edge_s, neg_s = sampler_lib.build_samplers_sharded(
            knn_idx, weights, power=cfg.neg_power, mesh=_data_mesh(cfg))
    elif edge_s is None:
        try:
            edge_s = sampler_lib.build_edge_sampler(knn_idx, weights,
                                                    impl=cfg.sampler_impl)
            neg_s = sampler_lib.build_negative_sampler(knn_idx, weights,
                                                       power=cfg.neg_power,
                                                       impl=cfg.sampler_impl)
        except InjectedFault:
            raise
        except Exception as e:
            # degraded mode: a backend failure in the jitted device build
            # falls back to the numpy Vose oracle instead of crashing
            if cfg.sampler_impl == "host":
                raise
            warnings.warn(DegradedModeWarning(
                "sampler_build", cfg.sampler_impl, "host", e), stacklevel=2)
            edge_s = sampler_lib.build_edge_sampler(knn_idx, weights,
                                                    impl="host")
            neg_s = sampler_lib.build_negative_sampler(knn_idx, weights,
                                                       power=cfg.neg_power,
                                                       impl="host")
        jax.block_until_ready((edge_s.threshold, neg_s.threshold))
        if ckpt is not None:
            from repro.checkpoint import largevis_state as lvs
            tree, static = lvs._samplers_to_tree(edge_s, neg_s)
            ckpt.save("samplers", tree, extra={"sampler_static": static})
        if fault is not None:
            fault.fire("stage:samplers")
    jax.block_until_ready((edge_s.threshold, neg_s.threshold))
    t1 = time.time()
    if cfg.distributed:
        res = layout_lib.run_layout_local_sgd(key, edge_s, neg_s,
                                              knn_idx.shape[0], cfg,
                                              _data_mesh(cfg), fault=fault,
                                              weights=weights)
    else:
        res = layout_lib.run_layout(key, edge_s, neg_s, knn_idx.shape[0],
                                    cfg, callback=callback, fault=fault)
    t2 = time.time()
    timings = {"sampler_s": t1 - t0, "layout_s": t2 - t1}
    if return_samplers:
        samplers = (None, None) if cfg.distributed else (edge_s, neg_s)
        return res, samplers, timings
    return res, timings


def largevis(x, key=None, *, cfg: LargeVisConfig | None = None,
             callback=None, fault=None) -> LargeVisResult:
    """Run the full pipeline; the functional core of :class:`repro.LargeVis`.

    ``cfg`` is keyword-only as of PR 7.  The result is a full fitted-model
    carrier (corpus points, samplers, cfg, key), so ``repro.core.transform``
    and the estimator's online operations can run against it directly.

    Crash safety (PR 8): set ``cfg.checkpoint`` and rerun the *same call*
    after a crash — each completed stage (``graph``, ``weights``,
    ``samplers``, per-chunk ``layout``) restores from disk and the final
    embedding is bitwise-equal to an uninterrupted run (tests/test_resume.py
    kills at every boundary).  ``fault`` takes a
    :class:`~repro.runtime.fault_tolerance.FaultInjector` for those tests.

    Elasticity (PR 10): stage checkpoints are topology-portable, and a
    shard lost mid-run (``ShardFailedError`` from a per-shard fault
    site, or a real device drop surfaced the same way) does not kill
    the job — one :class:`DegradedModeWarning` is emitted, the mesh is
    rebuilt with half the shards (``data_shards: P -> max(1, P//2)``)
    and the pipeline re-enters from the last committed stage via the
    re-shard restore path.  Only an unrecoverable failure (already at
    one shard) propagates.  When checkpointing is enabled a
    :class:`~repro.runtime.fault_tolerance.PreemptionGuard` is armed
    for the duration of the fit: SIGTERM/SIGINT runs a synchronous save
    of the newest layout state before the process exits by the signal.
    """
    cfg = cfg if cfg is not None else LargeVisConfig()
    _apply_autotune_mode(cfg)
    if key is None:
        key = jax.random.key(cfg.seed)
    from repro.runtime.fault_tolerance import (PreemptionGuard,
                                               ShardFailedError)
    guard = None
    if getattr(cfg, "checkpoint", None) is not None \
            and PreemptionGuard.active() is None:
        import signal as _signal
        guard = PreemptionGuard(signals=(_signal.SIGTERM, _signal.SIGINT),
                                exit_after_save=True).activate()
    try:
        while True:
            try:
                return _largevis_once(x, key, cfg=cfg, callback=callback,
                                      fault=fault)
            except ShardFailedError as e:
                if not cfg.distributed:
                    raise
                n_shards = int(_data_mesh(cfg).shape["data"])
                if n_shards <= 1:
                    raise       # nothing left to shed — a real failure
                new_shards = max(1, n_shards // 2)
                warnings.warn(DegradedModeWarning(
                    e.stage, f"mesh[{n_shards}]", f"mesh[{new_shards}]", e),
                    stacklevel=2)
                # injector hit counts persist across the retry, so the
                # same injected fault cannot re-fire on the smaller mesh
                cfg = dataclasses.replace(cfg, data_shards=new_shards)
    finally:
        if guard is not None:
            guard.restore_handlers()


def _largevis_once(x, key, *, cfg, callback, fault):
    """One pipeline pass on cfg's current mesh (see :func:`largevis`)."""
    kg, kl = jax.random.split(key)
    idx, dist, w, t_graph = build_graph(x, kg, cfg=cfg, fault=fault)
    res, (edge_s, neg_s), t_layout = layout_graph(
        idx, w, kl, cfg=cfg, callback=callback, return_samplers=True,
        fault=fault)
    return LargeVisResult(y=res.y, knn_idx=idx, knn_dist=dist, weights=w,
                          timings={**t_graph, **t_layout},
                          edge_samples=res.edge_samples,
                          x=jax.numpy.asarray(x), edge_sampler=edge_s,
                          neg_sampler=neg_s, cfg=cfg, key=key)
