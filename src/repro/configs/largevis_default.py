"""LargeVis default hyper-parameters — the paper's own configuration (§4.3).

These are the defaults the paper reports as *stable across datasets*:
perplexity 50, K=150 neighbors, M=5 negatives, gamma=7, rho0=1.0,
f(x) = 1/(1+x^2), T proportional to N.

Implementation routing lives in one namespace, ``LargeVisConfig.routing``
(:class:`RoutingConfig`) — which kernel/builder backs each stage.  The
pre-PR-7 flat knobs (``knn_impl``, ``sampler_impl``, ``fused_step``,
``knn_distributed``) keep working as deprecated aliases: passing one
emits a ``DeprecationWarning`` and folds the value into ``routing``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Stage-checkpointed crash recovery for ``largevis()`` / ``fit()``.

    When set on ``LargeVisConfig.checkpoint``, every stage boundary of the
    pipeline — the KNN graph, the calibrated+symmetrized weights, the
    sampler pytrees, and the layout ``(y, step)`` every
    ``every_chunks`` dispatches — is persisted atomically (the
    ``checkpoint/`` machinery's write-then-rename-then-commit protocol)
    under ``directory``.  A killed fit re-run with the same ``(x, key,
    cfg)`` resumes from the last committed stage/chunk and produces a
    **bitwise-identical** final embedding (pinned in tests/test_resume.py;
    a config/key/data fingerprint guards against resuming someone else's
    directory — mismatches start fresh with a warning).
    """
    directory: str
    # layout save cadence, in steps_per_dispatch chunks.  A crash replays
    # at most every_chunks*steps_per_dispatch steps; the default trades a
    # few seconds of replay for keeping save overhead well under 5% even
    # when writer and compute share one core (every_chunks=1 — a save per
    # dispatch — is the chaos-test stress cadence, not a sane default)
    every_chunks: int = 4
    keep: int = 2             # keep-last-k layout checkpoints
    resume: bool = True       # False: checkpoint but never auto-resume


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Numerical-health guard + divergence rollback for the layout stage.

    When set on ``LargeVisConfig.health``, every ``check_every_chunks``
    dispatches a jitted probe reduces the embedding to (non-finite count,
    max |coordinate|).  A non-finite entry or a coordinate beyond
    ``max_abs`` is a divergence: the driver rolls the layout back to the
    last healthy chunk, scales the learning rate by ``lr_backoff``, and
    re-runs from there (one structured ``DivergenceWarning``).  More than
    ``max_rollbacks`` rollbacks raises ``LayoutDivergedError``.  The probe
    syncs the device once per check, so default runs (``health=None``)
    keep the fully-async dispatch pipeline.
    """
    check_every_chunks: int = 1
    max_abs: float = 1e6          # embedding-norm blowup bound
    lr_backoff: float = 0.5       # rho0 multiplier per rollback
    max_rollbacks: int = 3


@dataclasses.dataclass(frozen=True)
class RoutingConfig:
    """Implementation routing for every pipeline stage.

    Every knob accepts ``"auto"``; the full resolution table:

    ==============  ========================  ================================
    knob            values                    ``"auto"`` resolves to
    ==============  ========================  ================================
    ``knn``         auto | fused|pallas|ref   streaming distance->top-k
                                              (``kernels.ops.topk_sqdist``):
                                              Pallas kernel on TPU, the
                                              bit-identical streaming jnp
                                              oracle elsewhere
    ``sampler``     auto | device | host      alias-table builder at the
                                              graph->layout boundary:
                                              ``device`` (jitted prefix-sum
                                              construction); ``host`` is the
                                              numpy Vose oracle/debug path
    ``layout_step`` auto | fused | split      SGD edge-step body: the
                                              one-pass gather+grad+scatter
                                              Pallas kernel on TPU, its
                                              bitwise jnp oracle elsewhere;
                                              ``fused`` forces the kernel
                                              (interpret mode off TPU);
                                              ``split`` is the gather/grad/
                                              scatter path (also taken
                                              automatically for autodiff
                                              prob_fns and off CPU/TPU)
    ``knn_stage``   auto | ring | forest      stage-1 KNN under
                                              ``distributed=True``: ``ring``
                                              = the sharded distance ring
                                              (fixed memory, O(N^2 d/P)
                                              compute); ``forest`` = the
                                              paper's linear RP-forest +
                                              neighbor-exploring build
                                              (the fig6 scaling config)
    ``autotune``    auto | off|cache|sweep    kernel tile autotuner mode
                                              (``runtime.autotune``):
                                              ``auto`` leaves the AUTOTUNE
                                              env (default ``cache``) in
                                              charge; ``off`` pins every
                                              tile to the legacy hard-coded
                                              config (bitwise CI anchor);
                                              ``sweep`` measures cache
                                              misses and persists winners
    ==============  ========================  ================================
    """
    knn: str = "auto"
    sampler: str = "auto"
    layout_step: str = "auto"
    knn_stage: str = "auto"
    autotune: str = "auto"


class _ResolvedStr(str):
    """Marks a flat alias value that was derived from ``routing`` (not
    user-passed), so ``dataclasses.replace(cfg, routing=...)`` round trips
    know routing is authoritative and stay silent."""


class _ResolvedFlag(int):
    """Bool-valued counterpart of :class:`_ResolvedStr` (``bool`` is not
    subclassable; an int subclass keeps truthiness, ``==`` and hashing)."""


def _mark_resolved(v):
    return _ResolvedStr(v) if isinstance(v, str) else _ResolvedFlag(v)


# (deprecated flat field, routing key, routing-value -> flat-value,
#  flat-value -> routing-value)
_ALIASES = (
    ("knn_impl", "knn", lambda v: v, lambda o: o),
    ("sampler_impl", "sampler", lambda v: v, lambda o: o),
    ("fused_step", "layout_step", lambda v: v != "split",
     lambda o: "fused" if o else "split"),
    ("knn_distributed", "knn_stage", lambda v: v != "forest",
     lambda o: "ring" if o else "forest"),
)


@dataclasses.dataclass(frozen=True)
class LargeVisConfig:
    # --- KNN graph construction (paper §3.1, Algo 1) ---
    n_neighbors: int = 150          # K
    n_trees: int = 8                # NT random projection "trees" (tables)
    n_explore_iters: int = 1        # Iter; paper: 1-3 suffices
    tree_depth: int = 0             # 0 -> auto from N and leaf target
    leaf_target: int = 64           # target points per bucket
    window: int = 64                # sorted-window candidate half-width
    explore_sample: int = 0         # 0 -> auto (candidates per explore iter)
    rp_mode: str = "hash"           # "hash" (matmul, TPU-native) | "tree"
    perplexity: float = 50.0        # u in Eqn (1)
    perplexity_iters: int = 64      # bisection steps for sigma_i
    # --- distributed pipeline (knn_sharded.py / perplexity.py /
    #     sampler.py sharded drivers + local-SGD layout) ---
    distributed: bool = False       # run every stage on the 1-D "data" mesh
    data_shards: int = 0            # devices in the 1-D mesh (0 = all)
    # --- layout (paper §3.2) ---
    out_dim: int = 2                # s
    n_negatives: int = 5            # M
    gamma: float = 7.0
    rho0: float = 1.0               # initial lr; rho_t = rho0 * (1 - t/T)
    samples_per_node: int = 10_000  # T = samples_per_node * N edge samples
    prob_fn: str = "inv_quadratic"  # f(x)=1/(1+a x^2); see objective.py
    prob_a: float = 1.0
    grad_clip: float = 5.0          # reference-impl per-coordinate clip
    batch_size: int = 4096          # edge samples per device step (TPU adapt)
    steps_per_dispatch: int = 100   # scan-fused steps per device dispatch
    #   (core/layout_engine.py); <=1 falls back to the per-step Python loop
    #   (debug / visual-progress mode — ~dispatch-bound at small N)
    sync_every: int = 1             # H: local-SGD sync period (1 = sync SGD)
    init_scale: float = 1e-4        # initial layout ~ N(0, init_scale)
    neg_power: float = 0.75         # P_n(j) ∝ d_j^0.75
    # --- out-of-sample transform (core/transform.py) ---
    transform_steps: int = 48       # frozen-corpus SGD steps per query batch
    transform_rho0: float = 0.0     # initial transform lr (0 -> rho0)
    # --- robustness (crash recovery + numerical health; PR 8) ---
    checkpoint: Optional[CheckpointConfig] = None   # stage-checkpointed
    #   resume (None = no persistence, the historical behaviour)
    health: Optional[HealthConfig] = None           # divergence guard +
    #   rollback on the layout path (None = no per-chunk device sync)
    # --- implementation routing (one namespace; see RoutingConfig) ---
    routing: RoutingConfig = dataclasses.field(default_factory=RoutingConfig)
    # Deprecated flat aliases (pre-PR-7 names).  Passing one warns and
    # folds the value into ``routing``; after construction they always
    # hold the concrete routing-derived values, so legacy readers (and
    # ``dataclasses.replace`` round trips) keep working.
    knn_impl: Optional[str] = None            # -> routing.knn
    sampler_impl: Optional[str] = None        # -> routing.sampler
    fused_step: Optional[bool] = None         # -> routing.layout_step
    knn_distributed: Optional[bool] = None    # -> routing.knn_stage
    dtype: Any = jnp.float32
    seed: int = 0

    def __post_init__(self):
        routing = self.routing
        if routing is None:
            routing = RoutingConfig()
        for flat, key, from_routing, to_routing in _ALIASES:
            flat_val = getattr(self, flat)
            if flat_val is None:
                continue
            if from_routing(getattr(routing, key)) == flat_val:
                continue            # consistent (e.g. a replace() round trip)
            if isinstance(flat_val, (_ResolvedStr, _ResolvedFlag)):
                continue            # stale routing-derived value from a
                #                     replace(cfg, routing=...) — routing wins
            # an UNMARKED conflicting value was passed by the user in THIS
            # construction (including dataclasses.replace(cfg, fused_step=..)
            # on a config whose routing was folded earlier) — it wins, with
            # the deprecation warning; routing wins silently only over its
            # own stale derived values (the marked branch above)
            warnings.warn(
                f"LargeVisConfig({flat}=...) is deprecated; use "
                f"routing=RoutingConfig({key}={to_routing(flat_val)!r})",
                DeprecationWarning, stacklevel=3)
            routing = dataclasses.replace(
                routing, **{key: to_routing(flat_val)})
        object.__setattr__(self, "routing", routing)
        for flat, key, from_routing, _ in _ALIASES:
            object.__setattr__(
                self, flat,
                _mark_resolved(from_routing(getattr(routing, key))))


DEFAULT = LargeVisConfig()
