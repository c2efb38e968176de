"""Layout optimization: batched edge-sampling SGD (paper §3.2, TPU-adapted).

The paper's Hogwild (batch-1 async updates) becomes batched synchronous
edge-sampling SGD with scatter-add — intra-batch collisions resolve
deterministically, and the paper's own sparsity argument ("conflicting
updates are rare") is why the batched dynamics match batch-1 dynamics.  For
multi-device runs, ``sync_every`` (H) gives local-SGD semantics: each shard
updates its own replica for H steps, then replicas average — the principled
TPU analogue of Hogwild staleness (DESIGN.md §2).

lr schedule: rho_t = rho0 * (1 - t/T), batch-size-corrected; per-coordinate
gradient clip as in the reference implementation.

Stepping goes through ``core/layout_engine.py``: ``run_layout`` dispatches
``cfg.steps_per_dispatch`` scanned steps per device round trip (donated y
buffer, no per-step host sync); the per-step Python loop survives only for
visual-progress callbacks and as ``steps_per_dispatch<=1`` debug mode.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layout_engine
from repro.core.layout_engine import sgd_edge_step
from repro.core.sampler import (EdgeSampler, NodeSampler,
                                ShardedEdgeSampler, ShardedNodeSampler)
from jax import shard_map
from repro.kernels import ops
from repro.runtime import spans
from repro.runtime.fault_tolerance import (DegradedModeWarning,
                                           DivergenceWarning, InjectedFault,
                                           LayoutDivergedError,
                                           PreemptionGuard,
                                           TopologyChangeWarning, Watchdog,
                                           fire_per_shard)


@functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=layout_engine.STATIC_ARGNAMES)
def layout_step(y, key, t_frac, **kw):
    """One jitted SGD step (see ``layout_engine.sgd_edge_step``).

    Per-step dispatch entry point — kept for the callback/visual-progress
    driver and external single-step users; bulk stepping should go through
    ``layout_engine.layout_chunk``, which runs H of these per dispatch.
    """
    return sgd_edge_step(y, key, t_frac, **kw)


@dataclasses.dataclass
class LayoutResult:
    y: jax.Array
    steps: int
    edge_samples: int
    # robustness diagnostics (PR 8): divergence rollbacks taken, the final
    # lr backoff scale, and the watchdog's straggler dispatches
    rollbacks: int = 0
    rho0_scale: float = 1.0
    stragglers: list = dataclasses.field(default_factory=list)


@jax.jit
def layout_health(y):
    """Jitted per-dispatch health probe: one reduction pass over (N, s).

    Returns ``(nonfinite_count, max_abs)`` — non-finite entries are
    excluded from the max so a single NaN cannot mask a norm blowup."""
    finite = jnp.isfinite(y)
    nonfinite = jnp.sum(~finite)
    max_abs = jnp.max(jnp.abs(jnp.where(finite, y, 0.0)))
    return nonfinite, max_abs


def _layout_stage_ckpt(key, n_nodes, cfg, edge_sampler=None, table=None):
    """StageCheckpointer for the layout stage, else None.

    The layout trajectory is a pure function of (samplers, key, cfg, N),
    so the fingerprint binds all four — the sampler via a strided sample
    of its alias threshold table, which is itself a deterministic
    function of the input data.  A directory written by a different
    run (other data, key, or hyper-params) can never resume into this
    one, even at identical N.

    ``table`` overrides the sampler-derived fingerprint data.  The
    local-SGD driver passes the *global* edge weights here: a
    :class:`~repro.core.sampler.ShardedEdgeSampler`'s threshold table is
    laid out per shard — (P, E_loc) — so fingerprinting it would bind
    the checkpoint to the mesh shape and break topology-portable resume,
    while the weights are identical on every mesh."""
    ckpt_cfg = getattr(cfg, "checkpoint", None)
    if ckpt_cfg is None:
        return None
    from repro.checkpoint.largevis_state import (StageCheckpointer,
                                                 run_fingerprint)
    if table is not None:
        table = np.asarray(table).reshape(-1, 1)
    elif edge_sampler is not None:
        table = np.asarray(edge_sampler.threshold).reshape(-1, 1)
    fp = run_fingerprint(table, key, cfg) + f"-n{n_nodes}"
    return StageCheckpointer(ckpt_cfg, fp)


def _collision_capped_batch(batch_size: int, n_nodes: int,
                            total: int = 0) -> int:
    """Batched-synchronous updates track the paper's batch-1 async dynamics
    only while intra-batch collisions are rare (§3.2's sparsity argument).
    A batch larger than ~N/2 guarantees every node collects several stale
    summed updates per step and the layout overshoots (on a 2000-node
    graph, batch 4096 drops the KNN-classifier accuracy from 0.98 to
    0.74), so cap the batch by the node count."""
    cap = max(1, n_nodes // 2)
    if total:
        cap = min(cap, max(total, 1))
    return min(batch_size, cap)


def _step_kwargs(edge_sampler: EdgeSampler, neg_sampler: NodeSampler,
                 n_nodes: int, cfg, batch: int) -> dict:
    """The sgd_edge_step keyword bundle shared by every driver below.

    Samplers ride through as pytrees — the jitted entry points see two
    structured arguments, not six unpacked table arrays."""
    return dict(
        edge_sampler=edge_sampler, neg_sampler=neg_sampler,
        n_negatives=cfg.n_negatives, n_nodes=n_nodes, prob_fn=cfg.prob_fn,
        a=cfg.prob_a, gamma=cfg.gamma, clip=cfg.grad_clip, rho0=cfg.rho0,
        batch=batch, layout_step=cfg.routing.layout_step)


# ---------------------------------------------------------------------------
# Local-SGD multi-device mode (the TPU analogue of the paper's Hogwild)
# ---------------------------------------------------------------------------

def make_local_sgd_fns(mesh, cfg, n_nodes: int, *, batch: int):
    """Returns the jitted H-local-steps-then-sync round function.

    Each device holds its own full replica of Y (leading replica axis,
    sharded over "data"), samples its own edge stream (RNG folded with the
    device index), and applies ``sync_every`` (H) local updates between
    syncs.  The sync is a **psum of deltas** (``y0 + psum(y - y0)``), not
    a mean: the paper's async SGD applies every sampled edge's update at
    full ``lr`` (stale reads tolerated — "conflicting updates are rare on
    sparse graphs"), and summing the replica drifts is exactly that; a
    pmean would scale every per-sample step by 1/P, silently under-
    stepping the schedule P-fold (measured: 2000-node fixture at P=8
    drops from ~0.95 to ~0.75 KNN-classifier accuracy).  The flip side is
    that the collision argument now bounds the *global* concurrent batch
    ``batch * P`` — the driver caps it at ~N/2.  H=1 degenerates to
    synchronous data-parallel; at P=1 psum == pmean == identity, so
    single-device trajectories are unchanged bitwise.

    The H local steps are one ``layout_engine.scan_layout_steps`` scan per
    shard_map body (formerly a hand-rolled ``fori_loop`` over the jitted
    per-step fn — same dynamics, one compiled loop instead of H inlined
    step bodies).

    Samplers may be the flat :class:`EdgeSampler`/:class:`NodeSampler`
    (tables replicated, every device draws global indices) or the
    sharded pair from ``sampler.build_samplers_sharded``: the stacked
    per-shard edge tables enter sharded over "data" (each device holds
    ONLY its own shard's table — the reference implementation's
    per-thread sampling range, i.e. stratified edge sampling), while the
    negative tables stay replicated (O(N) total) so collisions against
    any node mask correctly.  At one device the two modes produce the
    identical trajectory bitwise (same tables, same key stream).
    """
    from jax.sharding import PartitionSpec as P
    dp_spec = P("data", None, None)
    rep = P()
    H = max(1, cfg.sync_every)

    def _edge_in_spec(edge_sampler):
        """Spec pytree for the edge sampler argument: sharded stacked
        tables get their leading (P,) axis over "data" with the tiny
        shard-selection table replicated; flat samplers replicate."""
        if isinstance(edge_sampler, ShardedEdgeSampler):
            if edge_sampler.n_shards != mesh.shape["data"]:
                raise ValueError(
                    f"sampler built for {edge_sampler.n_shards} shards, "
                    f"mesh has {mesh.shape['data']}")
            t = P("data", None)
            return ShardedEdgeSampler(t, t, t, t, rep, rep,
                                      edge_sampler.n_shards,
                                      edge_sampler.n_edges)
        return rep

    def local_steps(y_rep, seed, t_frac0, dt_frac, edge_sampler,
                    neg_sampler):
        """H local steps on each replica (shard_map over 'data').

        Flat sampler pytrees enter replicated — a single ``P()`` spec
        per sampler covers every leaf (jax prefix-pytree semantics);
        sharded edge samplers enter with their stacked tables split over
        the mesh (see ``_edge_in_spec``)."""

        def body(y_loc, seed, t_frac0, dt_frac, edge_sampler, neg_sampler):
            dev = jax.lax.axis_index("data")
            # a sharded edge sampler arrives as this device's (1, E_loc)
            # block: sample the local shard's edges (stratified)
            es = (edge_sampler.local()
                  if isinstance(edge_sampler, ShardedEdgeSampler)
                  else edge_sampler)
            base_key = jax.random.fold_in(jax.random.key(seed[0]), dev)
            step_ids = jnp.arange(H, dtype=jnp.int32)
            t_fracs = t_frac0 + dt_frac * step_ids.astype(jnp.float32)
            y = layout_engine.scan_layout_steps(
                y_loc[0], base_key, step_ids, t_fracs,
                edge_sampler=es, neg_sampler=neg_sampler,
                n_negatives=cfg.n_negatives, n_nodes=n_nodes,
                prob_fn=cfg.prob_fn, a=cfg.prob_a, gamma=cfg.gamma,
                clip=cfg.grad_clip, rho0=cfg.rho0, batch=batch,
                layout_step=cfg.routing.layout_step)
            # Hogwild-sum sync: the round-start state is this body's own
            # input (replicas enter a round identical), so the delta
            # combine costs no extra dispatch or y0 copy.  Skipped
            # entirely at P=1: `y0 + (y - y0)` is NOT bitwise `y`
            # (rounding), and the single-device trajectory must stay
            # bit-identical to the flat drivers
            if mesh.shape["data"] == 1:
                return y[None]
            return (y_loc[0] + jax.lax.psum(y - y_loc[0], "data"))[None]

        return shard_map(
            body, mesh=mesh,
            in_specs=(dp_spec, rep, rep, rep, _edge_in_spec(edge_sampler),
                      rep),
            out_specs=dp_spec, check_vma=False,
        )(y_rep, seed, t_frac0, dt_frac, edge_sampler, neg_sampler)

    return jax.jit(local_steps, donate_argnums=(0,))


def run_layout_local_sgd(key, edge_sampler: EdgeSampler,
                         neg_sampler: NodeSampler, n_nodes: int, cfg,
                         mesh, *, fault=None, weights=None) -> LayoutResult:
    """Multi-device local-SGD layout driver (paper's async SGD, TPU form).

    Checkpointing (``cfg.checkpoint``) is at **round** granularity: after
    the psum-of-deltas sync every replica holds the identical embedding,
    so persisting ``y_rep[0]`` at a round boundary and re-broadcasting on
    resume reconstructs the exact distributed state.  The round seeds are
    pre-derived in one batch from ``kr``, so a resumed run replays the
    same per-round key stream — killed+resumed is bitwise-equal to
    uninterrupted, exactly as on the single-device path.

    Elastic resume: pass ``weights`` (the global edge weights) so the
    fingerprint is topology-invariant (see ``_layout_stage_ckpt``); each
    save carries a topology tag and the global edge-sample count.  A
    checkpoint written on the SAME shard count resumes bitwise; one
    written on a DIFFERENT shard count resumes from the last committed
    round boundary with the completed sample count remapped onto the new
    mesh's round structure, announced exactly once with
    :class:`TopologyChangeWarning` (the per-replica key streams are
    P-dependent by construction, so a cross-topology trajectory cannot
    be bitwise-continued — the embedding state is, the schedule restarts
    at the boundary).

    ``fault`` fires ``layout_round``/``layout_saved`` (kill matrix) plus
    the per-shard ``local_sgd_round:<s>`` sites after every round —
    injected shard exceptions surface as ``ShardFailedError`` (stage
    ``"layout"``) for the mesh-recovery loop, and callable specs may
    inflate one shard's observed round time: a per-shard
    :class:`Watchdog` tracks each shard's round times and a straggling
    shard is flagged *by index* in ``result.stragglers`` entries
    ``(shard, round, dt, median)`` with one summary RuntimeWarning.

    A process-wide active :class:`PreemptionGuard` (armed by
    ``largevis()`` when checkpointing is on) gets its save_fn pointed at
    the newest completed round each round, so SIGTERM/SIGINT commits a
    resumable stage checkpoint before the process dies."""
    n_dev = mesh.shape["data"]
    stage_ckpt = _layout_stage_ckpt(key, n_nodes, cfg, edge_sampler,
                                    table=weights)
    ckpt_cfg = getattr(cfg, "checkpoint", None)
    ky, kr = jax.random.split(key)
    y0 = (jax.random.normal(ky, (n_nodes, cfg.out_dim), jnp.float32)
          * cfg.init_scale)

    # the replicas' batches apply concurrently between syncs (Hogwild-sum
    # combine), so the collision cap bounds the GLOBAL concurrent batch
    # batch * n_dev at ~N/2, split evenly per replica (at n_dev=1 this is
    # exactly the single-device cap)
    batch = max(1, _collision_capped_batch(cfg.batch_size * n_dev,
                                           n_nodes) // n_dev)
    total = int(cfg.samples_per_node) * n_nodes
    steps = max(1, total // (batch * n_dev))
    H = max(1, cfg.sync_every)
    n_rounds = max(1, steps // H)

    topo = {"distributed": True, "data_shards": int(n_dev),
            "n_rows": int(n_nodes)}
    start_round = 0
    if stage_ckpt is not None:
        loaded = stage_ckpt.load("layout")
        if loaded is not None:
            tree, saved_round, extra = loaded
            y0 = jnp.asarray(tree["y"], jnp.float32)
            saved_topo = (extra or {}).get("topology") or {}
            saved_shards = int(saved_topo.get("data_shards", n_dev))
            if saved_shards == n_dev:
                start_round = int(saved_round)   # bitwise continuation
            else:
                # same embedding state, new round structure: place the
                # resume point at the boundary covering the samples the
                # old mesh had already committed
                samples_done = int((extra or {}).get(
                    "samples_done", int(saved_round) * H * batch * n_dev))
                start_round = samples_done // (H * batch * n_dev)
                warnings.warn(TopologyChangeWarning(
                    "layout", saved_shards, n_dev, start_round),
                    stacklevel=2)
    start_round = min(int(start_round), n_rounds)
    y_rep = jnp.broadcast_to(y0, (n_dev,) + y0.shape)
    from jax.sharding import NamedSharding, PartitionSpec as P
    y_rep = jax.device_put(y_rep, NamedSharding(mesh, P("data", None, None)))

    local_steps = make_local_sgd_fns(mesh, cfg, n_nodes, batch=batch)
    dt = 1.0 / max(steps, 1)
    # one batched draw + one device->host transfer for ALL round seeds:
    # deriving each round's seed with int(...) inside the loop forced a
    # synchronous device round trip every H steps, serializing the rounds
    seeds = np.asarray(jax.random.randint(kr, (n_rounds,), 0, 2**31 - 1,
                                          dtype=jnp.int32))

    def _extras(rounds_done: int) -> dict:
        return {"topology": topo,
                "samples_done": rounds_done * H * batch * n_dev}

    guard = PreemptionGuard.active() if stage_ckpt is not None else None
    preempt_state = None
    if guard is not None:
        # the snapshot is a fresh device buffer (slice), never donated —
        # save() host-gathers at signal time, so rounds stay async
        preempt_state = {"y": y0, "round": start_round}

        def _preempt_save():
            stage_ckpt.save("layout", {"y": preempt_state["y"]},
                            step=preempt_state["round"],
                            keep=max(1, ckpt_cfg.keep),
                            extra=_extras(preempt_state["round"]))

        guard.set_save_fn(_preempt_save)

    # per-shard round-time watchdogs: on a single-controller mesh every
    # shard observes the host-measured round time, so only an injected
    # (or runtime-reported) inflation differentiates them — which is
    # exactly what the straggler chaos tests feed through the callable
    # per-shard fault specs
    monitored = fault is not None
    watchdogs = [Watchdog() for _ in range(n_dev)] if monitored else []
    stragglers: list = []
    try:
        for r in range(start_round, n_rounds):
            t0 = time.time()
            y_rep = local_steps(
                y_rep, jnp.asarray(seeds[r:r + 1]), jnp.float32(r * H * dt),
                jnp.float32(dt), edge_sampler, neg_sampler)
            if monitored:
                jax.block_until_ready(y_rep)
                fault.fire("layout_round")
                round_dt = time.time() - t0
                dts = fire_per_shard(fault, "local_sgd_round", n_dev,
                                     stage="layout",
                                     payloads=[round_dt] * n_dev)
                for s, wd in enumerate(watchdogs):
                    if dts[s] is not None and wd.observe(r, float(dts[s])):
                        _, dtv, med = wd.stragglers[-1]
                        stragglers.append((s, r, dtv, med))
            if guard is not None:
                preempt_state["y"] = y_rep[0]
                preempt_state["round"] = r + 1
            if stage_ckpt is not None and (
                    (r + 1) % max(1, ckpt_cfg.every_chunks) == 0
                    or r + 1 >= n_rounds):
                stage_ckpt.save("layout", {"y": y_rep[0]}, step=r + 1,
                                keep=max(1, ckpt_cfg.keep),
                                extra=_extras(r + 1))
                if fault is not None:
                    fault.fire("layout_saved")
    finally:
        if guard is not None:
            guard.set_save_fn(None)
    if stragglers:
        worst = max(stragglers, key=lambda t: t[2])
        warnings.warn(
            f"local-SGD: shard {worst[0]} straggling — round {worst[1]} "
            f"took {worst[2]:.3f}s vs median {worst[3]:.3f}s "
            f"({len(stragglers)} flagged round(s); see "
            f"LayoutResult.stragglers)", RuntimeWarning, stacklevel=2)
    done = n_rounds - start_round
    # the replicas agree after the last round; the embedding leaves the
    # mesh for the default device, where the caller's own arrays live:
    # the one-device Pallas kernels that read it (transform, serving,
    # metrics) cannot be partitioned over a mesh
    y = jax.device_put(y_rep[0], jax.devices()[0])
    return LayoutResult(y=y, steps=done * H,
                        edge_samples=done * H * batch * n_dev,
                        stragglers=stragglers)


def run_layout(key, edge_sampler: EdgeSampler, neg_sampler: NodeSampler,
               n_nodes: int, cfg, *,
               callback: Optional[Callable] = None,
               y0=None, start_step: int = 0,
               on_chunk: Optional[Callable] = None,
               fault=None) -> LayoutResult:
    """Drive the layout for T = samples_per_node * N edge samples.

    Default path: ``layout_engine.layout_chunk`` — H =
    ``cfg.steps_per_dispatch`` scanned steps per device dispatch with a
    donated y buffer.  A ``callback`` (visual progress) or
    ``steps_per_dispatch <= 1`` requests the per-step Python loop, which
    produces the identical trajectory one host round trip per step.

    Resume: pass ``y0`` (e.g. a checkpointed layout) and ``start_step``;
    the schedule (key stream, t/T lr positions) continues exactly where
    step ``start_step`` would have run.  ``on_chunk(t, steps, y)`` fires
    after every dispatch on the scanned path with ``y`` synced — the
    checkpoint/watchdog/progress hook for chunked drivers.

    Robustness (scanned path; see README "Robustness"):

    * ``cfg.checkpoint`` — the layout self-checkpoints every
      ``every_chunks`` dispatches (atomic, keep-last-k, fingerprinted to
      this (key, cfg, N)); with no explicit ``y0`` it auto-resumes from
      the newest valid checkpoint, continuing the exact (key, lr) stream
      — a killed+resumed run is bitwise-equal to an uninterrupted one.
    * ``cfg.health`` — a jitted probe checks the embedding every
      ``check_every_chunks`` dispatches; divergence (non-finite entries
      or |y| past ``max_abs``) rolls back to the last healthy chunk with
      the lr scaled by ``lr_backoff`` (``DivergenceWarning``), raising
      ``LayoutDivergedError`` after ``max_rollbacks`` attempts.
    * degraded mode — a backend failure dispatching the first fused
      chunk demotes ``fused -> split`` for the run with one
      ``DegradedModeWarning`` instead of crashing the fit.
    * a :class:`~repro.runtime.fault_tolerance.Watchdog` times every
      blocked dispatch (from the ``lv.layout.dispatch`` span's start to
      the ``lv.layout.sync`` span's end, on ``perf_counter``; see
      ``runtime/spans.py``) and surfaces outliers in
      ``result.stragglers`` (chunks are only blocked-on when a
      hook/health/fault already forces the sync — a checkpoint-only run keeps the async pipeline:
      saves go through an off-thread
      :class:`~repro.checkpoint.largevis_state.AsyncStageWriter` fed
      on-device ``jnp.copy`` snapshots, and the watchdog times the
      interval between snapshot completions instead).
    * ``fault`` — a FaultInjector fired at ``layout_chunk`` (post-chunk
      payload = y) and ``layout_saved`` (post-checkpoint-commit) for the
      kill/chaos test matrices.
    """
    health = getattr(cfg, "health", None)
    stage_ckpt = _layout_stage_ckpt(key, n_nodes, cfg, edge_sampler)
    rho0_scale, rollbacks = 1.0, 0
    if stage_ckpt is not None and y0 is None and start_step == 0:
        loaded = stage_ckpt.load("layout")
        if loaded is not None:
            tree, saved_step, extra = loaded
            y0, start_step = tree["y"], saved_step
            rho0_scale = float(extra.get("rho0_scale", 1.0))
            rollbacks = int(extra.get("rollbacks", 0))

    ky, kr = jax.random.split(key)
    if y0 is None:
        y = (jax.random.normal(ky, (n_nodes, cfg.out_dim), jnp.float32)
             * cfg.init_scale)
    else:
        y = jnp.asarray(y0, jnp.float32)
    total = int(cfg.samples_per_node) * n_nodes
    batch = _collision_capped_batch(cfg.batch_size, n_nodes, total)
    steps = max(1, total // batch)
    start = min(int(start_step), steps)
    kwargs = _step_kwargs(edge_sampler, neg_sampler, n_nodes, cfg, batch)

    # 0 = unset: ask the autotuner for a tuned scan-chunk length (the
    # "layout_chunk" cell — results-neutral, see layout_engine.dispatch_steps)
    H = layout_engine.dispatch_steps(
        int(getattr(cfg, "steps_per_dispatch", 0)),
        n_nodes=n_nodes, batch=batch)
    watchdog = None
    if callback is None and H > 1:
        # block on every chunk only when something already needs the sync;
        # a checkpoint-only run keeps the async pipeline — saves go to an
        # off-thread writer fed on-device snapshots, so durability costs a
        # device memcpy per cadence instead of a pipeline stall per chunk
        monitored = (on_chunk is not None or health is not None
                     or fault is not None)
        watchdog = (Watchdog() if monitored or stage_ckpt is not None
                    else None)
        writer = None
        if stage_ckpt is not None and not monitored:
            from repro.checkpoint.largevis_state import AsyncStageWriter
            writer = AsyncStageWriter(stage_ckpt, watchdog=watchdog)
        ckpt_cfg = getattr(cfg, "checkpoint", None)
        last_good = (np.asarray(y), start) if health is not None else None
        t, chunk_i, first_chunk = start, 0, True
        # preemption: point the process-wide active guard (armed by
        # largevis() when checkpointing is on) at the newest completed
        # chunk — the snapshot is an on-device jnp.copy (no host sync,
        # never donated), host-gathered only if a signal actually lands
        guard = PreemptionGuard.active() if stage_ckpt is not None else None
        preempt_state = None
        if guard is not None:
            preempt_state = {"y": jnp.copy(y), "step": start,
                             "extra": {"rho0_scale": rho0_scale,
                                       "rollbacks": rollbacks}}

            def _preempt_save():
                stage_ckpt.save("layout", {"y": preempt_state["y"]},
                                step=preempt_state["step"],
                                keep=max(1, ckpt_cfg.keep),
                                extra=preempt_state["extra"])

            guard.set_save_fn(_preempt_save)
        noted = set()       # (chunk length, route) whose signature is noted
        # row tiles of y each step's kernel runs (the dispatch span's count)
        slabs = ops.edge_step_slabs(
            n_nodes, cfg.out_dim, batch, cfg.n_negatives,
            layout_step=(kwargs["layout_step"]
                         if cfg.prob_fn == "inv_quadratic" else "split"))

        def chunk(y, step_ids, t_fracs):
            args = (y, kr, step_ids, t_fracs)
            sig = (step_ids.shape[0], kwargs["layout_step"])
            if sig not in noted:
                noted.add(sig)
                spans.note("layout_chunk", layout_engine.layout_chunk,
                           *args, **kwargs)
            return layout_engine.layout_chunk(*args, **kwargs)

        try:
            while t < steps:
                h = min(H, steps - t)
                with spans.span("layout.dispatch", steps=h,
                                slabs=slabs) as dispatch:
                    step_ids = jnp.arange(t, t + h, dtype=jnp.int32)
                    # host-side t/steps (f64 rounded to f32) — bit-identical
                    # to the Python loop's jnp.float32(t / steps) schedule
                    t_fracs = jnp.asarray(np.arange(t, t + h) / steps,
                                          jnp.float32)
                    # traced: no recompile
                    kwargs["rho0"] = cfg.rho0 * rho0_scale
                    if first_chunk and kwargs["layout_step"] != "split":
                        # degraded-mode guard: donation invalidates y at
                        # dispatch, so snapshot once to make the retry safe
                        y_backup = np.asarray(y)
                        try:
                            y = chunk(y, step_ids, t_fracs)
                        except InjectedFault:
                            raise
                        except Exception as e:  # backend/compile failure
                            warnings.warn(DegradedModeWarning(
                                "layout_step", "fused", "split", e),
                                stacklevel=2)
                            kwargs["layout_step"] = "split"
                            slabs = 1
                            y = chunk(jnp.asarray(y_backup), step_ids,
                                      t_fracs)
                    else:
                        y = chunk(y, step_ids, t_fracs)
                first_chunk = False
                t += h
                chunk_i += 1
                if monitored:
                    with spans.span("layout.sync") as sync:
                        jax.block_until_ready(y)
                    watchdog.observe(t, (sync.t1 - dispatch.t0) / 1e9)
                if fault is not None:
                    y = fault.fire("layout_chunk", y)
                if health is not None and (
                        chunk_i % max(1, health.check_every_chunks) == 0
                        or t >= steps):
                    nf, mx = layout_health(y)
                    nf, mx = int(nf), float(mx)
                    if nf or mx > health.max_abs:
                        rollbacks += 1
                        if rollbacks > health.max_rollbacks:
                            raise LayoutDivergedError(
                                f"layout still diverging after "
                                f"{health.max_rollbacks} rollbacks "
                                f"(step {t}: nonfinite={nf}, "
                                f"max|y|={mx:.3g})")
                        rho0_scale *= health.lr_backoff
                        warnings.warn(DivergenceWarning(
                            t, last_good[1], nf, mx, rho0_scale),
                            stacklevel=2)
                        y, t = jnp.asarray(last_good[0]), last_good[1]
                        continue
                    last_good = (np.asarray(y), t)
                if stage_ckpt is not None and (
                        chunk_i % max(1, ckpt_cfg.every_chunks) == 0
                        or t >= steps):
                    extra = {"rho0_scale": rho0_scale,
                             "rollbacks": rollbacks}
                    keep = max(1, ckpt_cfg.keep)
                    if writer is not None:
                        writer.submit("layout", {"y": jnp.copy(y)}, step=t,
                                      keep=keep, extra=extra)
                    else:
                        stage_ckpt.save("layout", {"y": y}, step=t,
                                        keep=keep, extra=extra)
                        if fault is not None:
                            fault.fire("layout_saved")
                if guard is not None:
                    preempt_state["y"] = jnp.copy(y)
                    preempt_state["step"] = t
                    preempt_state["extra"] = {"rho0_scale": rho0_scale,
                                              "rollbacks": rollbacks}
                if on_chunk is not None:
                    on_chunk(t, steps, y)
        finally:
            if guard is not None:
                guard.set_save_fn(None)
            if writer is not None:
                writer.close()
    else:
        for t in range(start, steps):
            y = layout_step(y, jax.random.fold_in(kr, t),
                            jnp.float32(t / steps), **kwargs)
            if callback is not None and (t % max(1, steps // 20) == 0):
                callback(t, steps, y)
    stragglers = list(watchdog.stragglers) if watchdog is not None else []
    # surface stragglers only when the outlier is macroscopic — 3x a
    # sub-millisecond median is host jitter, not a sick device
    if stragglers and max(s[1] for s in stragglers) > 0.1:
        warnings.warn(
            f"layout: {len(stragglers)} straggler dispatch(es) — worst "
            f"{max(s[1] for s in stragglers):.3f}s vs median "
            f"{stragglers[-1][2]:.3f}s (see LayoutResult.stragglers)",
            RuntimeWarning, stacklevel=2)
    done = steps - start
    return LayoutResult(y=y, steps=done, edge_samples=done * batch,
                        rollbacks=rollbacks, rho0_scale=rho0_scale,
                        stragglers=stragglers)
