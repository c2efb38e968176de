"""Mixture-of-Experts with sort-based token dispatch (MegaBlocks-style).

Dense one-hot dispatch tensors (T,E,C) blow up memory at production token
counts; instead tokens are argsorted by expert id and gathered into a padded
(E, capacity, d) buffer — linear memory, and the expert einsum batches over
the expert axis, which shards cleanly (EP) over the mesh.

Capacity overflow tokens are dropped (standard); the router aux loss is the
Switch-style load-balance term E * sum_e f_e * p_e.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import dense_init
from repro.runtime import sharding as shd
from jax import shard_map


def init_moe(key, cfg) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), scale=0.02),
        # expert arrays are (E, in, out): fan-in is axis 1, not axis 0
        "w_gate": dense_init(ks[1], (E, d, f), scale=1.0 / np.sqrt(d)),
        "w_up": dense_init(ks[2], (E, d, f), scale=1.0 / np.sqrt(d)),
        "w_down": dense_init(ks[3], (E, f, d), scale=1.0 / np.sqrt(f)),
    }


def capacity(n_tokens: int, n_experts: int, topk: int,
             factor: float = 1.25) -> int:
    c = int(np.ceil(n_tokens * topk * factor / n_experts))
    return max(8, int(np.ceil(c / 8)) * 8)


def moe_apply(params: dict, x: jax.Array, cfg,
              capacity_factor: float = 1.25):
    """x: (B,S,d) -> (y, aux_loss).

    Under an activation policy (distributed step), dispatch runs LOCALLY per
    DP shard via shard_map: data-dependent scatter/gather does not SPMD-
    partition (the global path materializes (E, C_global, d) — 40 GiB at a
    1M-token prefill), so each shard routes its own tokens and the expert
    einsum runs on model-axis weight slices with a psum combine.  The global
    path below remains for single-host execution and as the oracle the
    sharded path is tested against.
    """
    pol = shd.current_policy()
    if pol is not None and pol[1] is not None:
        return _moe_apply_sharded(params, x, cfg, capacity_factor, pol)
    return _moe_apply_global(params, x, cfg, capacity_factor)


def _moe_apply_sharded(params, x, cfg, capacity_factor, pol):
    mesh, dp, train = pol
    from jax.sharding import PartitionSpec as P
    fsdp = "data" if train else None

    def g(shape, spec):
        return shd._guard(mesh, shape, spec)

    r_spec = g(params["router"].shape, [fsdp, None])
    wg_spec = g(params["w_gate"].shape,
                [None if train else "data", fsdp, "model"])
    wd_spec = g(params["w_down"].shape,
                [None if train else "data", "model", fsdp])
    x_spec = P(dp, None, None)

    ep_axis = wg_spec[0]                # experts resident per-shard (EP)?
    if ep_axis is not None:
        # EP strategy (classic tradeoff): route TOKENS when their traffic
        # is below the resident weight stack (decode: ~MBs of slots vs
        # hundreds of MB of weights), otherwise gather WEIGHTS (prefill /
        # train: millions of tokens dwarf the weights — §Perf hillclimb 2
        # iter 2 fixed a 2.8x prefill regression from unconditional a2a).
        n_shards = 1
        for a in (ep_axis if isinstance(ep_axis, tuple) else (ep_axis,)):
            n_shards *= mesh.shape[a]
        B_, S_, d_ = x.shape
        t_loc = (B_ * S_) // max(
            1, (B_ * S_ if dp is None else
                int(np.prod([mesh.shape[a] for a in dp]))))
        c_loc = capacity(max(t_loc, 1), cfg.n_experts, cfg.topk_experts,
                         capacity_factor)
        token_bytes = 2 * cfg.n_experts * c_loc * d_ * 2
        weight_bytes = (3 * cfg.n_experts * d_ * cfg.d_ff * 2
                        // max(1, mesh.shape.get("model", 1)))
        if token_bytes >= weight_bytes:
            ep_axis = None              # fall back to weight gathering

    gather_ep = wg_spec[0] is not None and ep_axis is None

    def body(router, wg, wu, wd, xl):
        # FSDP gathers: reassemble full (E, d, ff_local) weight slices
        if r_spec[0] is not None:
            router = jax.lax.all_gather(router, r_spec[0], axis=0,
                                        tiled=True)
        if gather_ep:                   # weight-gather EP (token-heavy)
            wg = jax.lax.all_gather(wg, wg_spec[0], axis=0, tiled=True)
            wu = jax.lax.all_gather(wu, wg_spec[0], axis=0, tiled=True)
            wd = jax.lax.all_gather(wd, wd_spec[0], axis=0, tiled=True)
        if wg_spec[1] is not None:      # FSDP at training: gather d
            wg = jax.lax.all_gather(wg, wg_spec[1], axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, wg_spec[1], axis=1, tiled=True)
        if wd_spec[2] is not None:
            wd = jax.lax.all_gather(wd, wd_spec[2], axis=2, tiled=True)
        w = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        if ep_axis is None:
            # experts fully local (replicated, gathered, or ff-shard only)
            y, aux = _dispatch_and_compute(
                w, xl, cfg, capacity_factor, psum_axis="model")
        else:
            # true EP: all_to_all TOKEN slots to the shard holding their
            # expert (weights stay resident) — 2 small token buffers per
            # layer instead of the full expert stack (§Perf hillclimb 2)
            y, aux = _dispatch_ep_a2a(
                w, xl, cfg, capacity_factor, ep_axis=ep_axis,
                psum_axis="model")
        return y, jax.lax.pmean(aux, dp)

    return shard_map(
        body, mesh=mesh,
        in_specs=(r_spec, wg_spec, wg_spec, wd_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"],
      params["w_down"], x)


def _dispatch_ep_a2a(params, x, cfg, capacity_factor, *, ep_axis,
                     psum_axis):
    """Expert-parallel dispatch: local route -> all_to_all token slots to
    the expert's shard -> FFN on resident weights -> all_to_all back ->
    combine.  params weights are the LOCAL slices (E_local, d, ff_local)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.topk_experts
    dtype = x.dtype
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
    fe = jnp.mean(one_hot.sum(1), axis=0) / K
    aux = E * jnp.sum(fe * me)

    C = capacity(T, E, K, capacity_factor)
    e_flat = top_e.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T), K)
    w_flat = top_p.reshape(-1)
    order = jnp.argsort(e_flat)
    e_s, t_s = e_flat[order], t_flat[order]
    seg_starts = jnp.searchsorted(e_s, jnp.arange(E))
    pos = jnp.arange(T * K) - seg_starts[e_s]
    keep = pos < C
    dest = jnp.where(keep, e_s * C + pos, E * C)

    gathered = jnp.zeros((E * C + 1, d), dtype)
    gathered = gathered.at[dest].set(xf[t_s])
    g = gathered[:-1].reshape(E, C, d)                  # (E, C_local, d)

    # ship token slots to their expert's shard:
    # (E, C, d) -> (E_local, n_shards*C, d)
    ga = jax.lax.all_to_all(g, ep_axis, split_axis=0, concat_axis=1,
                            tiled=True)
    gate = jnp.einsum("ecd,edf->ecf", ga, params["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", ga, params["w_up"].astype(dtype))
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dtype))
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)                # back: (E, C_loc, d)

    out_flat = out.reshape(E * C, d)
    contrib = jnp.where(keep, w_flat[order], 0.0).astype(dtype)
    picked = jnp.where(keep[:, None],
                       out_flat[jnp.clip(dest, 0, E * C - 1)], 0.0)
    yf = jnp.zeros((T, d), dtype).at[t_s].add(picked * contrib[:, None])
    return yf.reshape(B, S, d), aux


def _moe_apply_global(params, x, cfg, capacity_factor):
    return _dispatch_and_compute(params, x, cfg, capacity_factor,
                                 psum_axis=None)


def _dispatch_and_compute(params, x, cfg, capacity_factor, *,
                          psum_axis=None):
    """Sort-based dispatch + expert FFN on (possibly local) tokens.

    psum_axis: mesh axis holding the ff shards of the expert weights
    (shard_map path) — w_down partial products are psum'd over it.
    """
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.topk_experts
    dtype = x.dtype
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ params["router"])        # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T,K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch): E * sum_e f_e * p_e ----
    me = jnp.mean(probs, axis=0)                                # (E,)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)       # (T,K,E)
    fe = jnp.mean(one_hot.sum(1), axis=0) / K
    aux = E * jnp.sum(fe * me)

    # ---- sort-based dispatch ----
    C = capacity(T, E, K, capacity_factor)
    e_flat = top_e.reshape(-1)                                  # (T*K,)
    t_flat = jnp.repeat(jnp.arange(T), K)
    w_flat = top_p.reshape(-1)
    order = jnp.argsort(e_flat)
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    # rank within expert segment
    seg_starts = jnp.searchsorted(e_s, jnp.arange(E))
    pos = jnp.arange(T * K) - seg_starts[e_s]
    keep = pos < C
    dest = jnp.where(keep, e_s * C + pos, E * C)                # drop slot

    gathered = jnp.zeros((E * C + 1, d), dtype)
    gathered = gathered.at[dest].set(xf[t_s])
    g = gathered[:-1].reshape(E, C, d)

    # ---- expert FFN, batched over E ----
    gate = jnp.einsum("ecd,edf->ecf", g, params["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", g, params["w_up"].astype(dtype))
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dtype))
    if psum_axis is not None:
        # shard_map path: ff was sharded over the model axis -> partial sums
        out = jax.lax.psum(out, psum_axis)

    # ---- combine back ----
    out_flat = out.reshape(E * C, d)
    contrib = jnp.where(keep, w_flat[order], 0.0).astype(dtype)
    picked = jnp.where(keep[:, None],
                       out_flat[jnp.clip(dest, 0, E * C - 1)], 0.0)
    yf = jnp.zeros((T, d), dtype).at[t_s].add(picked * contrib[:, None])
    return yf.reshape(B, S, d), aux


def moe_flops(cfg, n_tokens: int, capacity_factor: float = 1.25) -> float:
    C = capacity(n_tokens, cfg.n_experts, cfg.topk_experts, capacity_factor)
    per_expert = 2.0 * 3 * C * cfg.d_model * cfg.d_ff
    router = 2.0 * n_tokens * cfg.d_model * cfg.n_experts
    return per_expert * cfg.n_experts + router
