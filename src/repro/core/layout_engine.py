"""Scan-fused layout engine: H SGD steps per device dispatch (paper §3.2).

The layout stage is the paper's linear-time hot path, and a per-step Python
driver re-dispatches one jitted ``layout_step`` per SGD step — at the
collision-capped batch sizes (≤ N/2) that is thousands of host round trips,
so dispatch overhead dominates exactly the regime the paper optimizes.  This
module fuses the loop into the compiled program:

* :func:`sgd_edge_step` — the single-step body (alias edge/negative sampling
  + fused gradient + one scatter-add), shared by every driver so the scanned
  and per-step paths stay numerically identical.  Samplers enter as the
  :class:`~repro.core.sampler.EdgeSampler` / ``NodeSampler`` pytrees —
  one argument per sampler threaded through ``jit``/``scan``/``shard_map``,
  not six unpacked table arrays.  Samplers are duck-typed: anything with
  ``.sample(key, ...)`` works, so the per-shard samplers from
  ``sampler.build_samplers_sharded`` (a device's local ``EdgeSampler``
  slice, the two-level ``ShardedNodeSampler``) flow through the same
  step body unchanged — sharding lives in the drivers, not here.
* :func:`scan_layout_steps` — ``jax.lax.scan`` over the step body.  Used
  unjitted inside ``shard_map`` by the local-SGD drivers (replacing their
  hand-rolled ``fori_loop`` wiring) and jitted below for the single-device
  driver.
* :func:`layout_chunk` — the jitted, **y-donating** dispatch unit: one device
  round trip runs ``len(step_ids)`` steps.  Donation keeps peak memory at one
  (N, s) buffer instead of two.

Step identity is carried by ``step_ids`` (global step numbers, folded into
the PRNG key) and ``t_fracs`` (t/T learning-rate schedule positions), both
precomputed per chunk, so a scanned trajectory is step-for-step the same
stream of (key, lr) pairs the per-step Python loop produces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import objective
from repro.kernels import ops
from repro.runtime import spans

# static hyper-parameters of the step body (everything that changes the
# traced program rather than just its inputs)
STATIC_ARGNAMES = (
    "n_negatives",
    "n_nodes",
    "prob_fn",
    "a",
    "gamma",
    "clip",
    "batch",
    "layout_step",
)


def dispatch_steps(requested: int, *, n_nodes: int, batch: int) -> int:
    """Resolve the scan-chunk length (steps per device dispatch).

    ``requested`` (``cfg.steps_per_dispatch``) wins when positive; 0/None
    asks the autotuner for the "layout_chunk" cell — a cache/table-only
    tunable (no sweep builder: measuring it needs a full layout driver
    per candidate, which the fig6/table2 benches already do end to end).
    Chunking is results-neutral: the (key, lr) stream is precomputed per
    global step id, so any chunking yields the same trajectory.
    Returns 0 when neither source picks (drivers keep their own default).
    """
    if requested:
        return int(requested)
    from repro.runtime import autotune
    # off mode (and any miss) returns the sentinel 0 = "no opinion"
    cfg = autotune.get("layout_chunk", dict(n=n_nodes, b=batch),
                       dict(steps=0))
    return int(cfg["steps"])


def apply_edge_batch(
    y,
    i,
    j,
    negs,
    neg_mask,
    lr,
    *,
    prob_fn: str = "inv_quadratic",
    a: float = 1.0,
    gamma: float = 7.0,
    clip: float = 5.0,
    layout_step: str = "auto",
    n_frozen: int = 0,
):
    """Apply one pre-sampled edge batch to the (N, s) embedding.

    The update body shared by :func:`sgd_edge_step` (which samples the
    batch from the alias samplers) and the out-of-sample transform /
    serving paths (`core/transform.py`, which sample per-query neighbor
    edges) — one definition of the fused/split routing and of the
    canonical per-edge interleaved update order, so every consumer stays
    bitwise-consistent with the fused kernel.

    ``lr`` is a scalar or a (B,) per-edge vector; ``n_frozen`` masks
    updates to rows below that index to -0.0 (a bitwise no-op add) — the
    frozen-corpus transform mode.  ``layout_step`` is
    ``RoutingConfig.layout_step``: ``"fused"`` runs the fully-fused
    edge-step kernel (``kernels/largevis_step.py``; interpret mode off
    TPU), ``"auto"`` the kernel on TPU and its bitwise jnp oracle
    elsewhere (``ops.largevis_edge_step``), ``"split"`` the
    gather/grad/scatter path below — also taken for autodiff
    ``prob_fn``s and backends without the kernel
    (``ops.fused_step_supported``).  All three apply updates in the same
    canonical per-edge interleaved order, so their trajectories match
    bitwise.
    """
    if (
        layout_step != "split"
        and prob_fn == "inv_quadratic"
        and ops.fused_step_supported(y.shape[0], y.shape[1])
    ):
        return ops.largevis_edge_step(
            y, i, j, negs, neg_mask, lr, gamma=gamma, a=a, clip=clip,
            n_frozen=n_frozen, impl="fused" if layout_step == "fused"
            else "auto"
        )

    yi, yj, yneg = y[i], y[j], y[negs]
    if prob_fn == "inv_quadratic":
        gi, gj, gneg = ops.largevis_grads(
            yi, yj, yneg, neg_mask, gamma=gamma, a=a, clip=clip
        )
    else:
        gi, gj, gneg = objective.grads_autodiff(
            yi, yj, yneg, neg_mask, prob_fn=prob_fn, a=a, gamma=gamma, clip=clip
        )
    # single fused scatter-add (3 separate .at[].add calls triple the
    # y read/write traffic — §Perf hillclimb 3 iter 2), per-edge
    # interleaved [i_e, j_e, negs_e] so the duplicate-accumulation order
    # matches the fused kernel's sequential loop bitwise
    s = y.shape[1]
    idx = jnp.concatenate([i[:, None], j[:, None], negs], axis=1).reshape(-1)
    upd = jnp.concatenate([gi[:, None], gj[:, None], gneg], axis=1).reshape(-1, s)
    lr = jnp.asarray(lr, jnp.float32)
    if lr.ndim:                        # (B,) per-edge -> per update row
        lr = jnp.repeat(lr, 2 + negs.shape[1])[:, None]
    upd = -lr * upd
    if n_frozen:
        upd = jnp.where((idx >= n_frozen)[:, None], upd, jnp.float32(-0.0))
    return y.at[idx].add(upd)


def sgd_edge_step(
    y,
    key,
    t_frac,
    *,
    edge_sampler,
    neg_sampler,
    n_negatives: int,
    n_nodes: int,
    prob_fn: str = "inv_quadratic",
    a: float = 1.0,
    gamma: float = 7.0,
    clip: float = 5.0,
    rho0: float = 1.0,
    batch: int = 4096,
    layout_step: str = "auto",
):
    """One SGD step over a freshly sampled edge batch.  t_frac = t/T.

    ``edge_sampler`` / ``neg_sampler`` are the :class:`~repro.core.sampler`
    pytrees — one argument each instead of six unpacked table arrays, the
    same signature for every driver (the sampled index stream is bitwise
    identical to the unpacked form: ``EdgeSampler.sample`` is exactly the
    old ``sample_alias`` + two gathers).

    Unjitted on purpose: ``core.layout.layout_step`` wraps it for per-step
    dispatch, :func:`scan_layout_steps` scans it, and the shard_map local-SGD
    bodies inline it — one definition, three drivers.

    ``layout_step`` routes the update (see :func:`apply_edge_batch`):
    the fused edge-step kernel does gather + grad + scatter-accumulate
    in one pass with no (B, M, s) intermediates or (B*(2+M), s) concat
    buffer; ``"split"`` is the gather/grad/scatter path.
    """
    with spans.scope("layout.sample"):
        ke, kn, _ = jax.random.split(key, 3)
        i, j = edge_sampler.sample(ke, batch)
        negs = neg_sampler.sample(kn, (batch, n_negatives))
        # mask collisions: negative == source or target of the positive edge
        neg_mask = ((negs != i[:, None])
                    & (negs != j[:, None])).astype(jnp.float32)
        lr = rho0 * jnp.maximum(1.0 - t_frac, 1e-4)
    del n_nodes  # == y.shape[0] in every driver; apply_edge_batch re-derives
    with spans.scope("layout.update"):
        return apply_edge_batch(
            y, i, j, negs, neg_mask, lr, prob_fn=prob_fn, a=a, gamma=gamma,
            clip=clip, layout_step=layout_step)


def scan_layout_steps(y, base_key, step_ids, t_fracs, **kw):
    """Run ``len(step_ids)`` SGD steps as one ``lax.scan``.

    step k uses key ``fold_in(base_key, step_ids[k])`` and lr position
    ``t_fracs[k]`` — the same (key, lr) stream as a Python loop over
    ``sgd_edge_step``, so trajectories match the per-step driver.
    """

    def one(y, x):
        sid, tf = x
        with spans.scope("layout.sample"):
            key = jax.random.fold_in(base_key, sid)
        return sgd_edge_step(y, key, tf, **kw), None

    y, _ = jax.lax.scan(one, y, (step_ids, t_fracs))
    return y


@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=STATIC_ARGNAMES,
)
def layout_chunk(
    y,
    base_key,
    step_ids,
    t_fracs,
    *,
    edge_sampler,
    neg_sampler,
    n_negatives: int,
    n_nodes: int,
    prob_fn: str = "inv_quadratic",
    a: float = 1.0,
    gamma: float = 7.0,
    clip: float = 5.0,
    rho0: float = 1.0,
    batch: int = 4096,
    layout_step: str = "auto",
):
    """Jitted dispatch unit: ``len(step_ids)`` scanned steps, donated ``y``.

    The chunk length is static (it is a shape), so a driver using a fixed
    ``steps_per_dispatch`` plus one remainder chunk compiles at most twice.
    """
    return scan_layout_steps(
        y,
        base_key,
        step_ids,
        t_fracs,
        edge_sampler=edge_sampler,
        neg_sampler=neg_sampler,
        n_negatives=n_negatives,
        n_nodes=n_nodes,
        prob_fn=prob_fn,
        a=a,
        gamma=gamma,
        clip=clip,
        rho0=rho0,
        batch=batch,
        layout_step=layout_step,
    )
