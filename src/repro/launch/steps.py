"""Step builders: sharded train / prefill / decode steps per (arch, shape).

Each builder returns (fn, in_shardings, out_shardings, arg_specs) ready for
``jax.jit(fn, in_shardings=..., out_shardings=...).lower(*arg_specs)`` —
the dry-run compiles exactly what the production launcher runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import input_specs
from repro.models import make_model, param_specs
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.runtime import sharding as sh
from jax import shard_map


def _out_tree_shardings(out_specs, mesh, *, global_batch: int):
    """Rule-based shardings for a (logits, cache)-style output pytree."""
    dp = sh.dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_first = global_batch % dp_size == 0 and global_batch >= dp_size

    def one(path, leaf):
        s = sh._path_str(path)
        shape = leaf.shape
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        if leaf.ndim == 2 and shape[-1] > 1024:          # logits (B, V)
            spec = [dp if batch_first else None, "model"]
            return NamedSharding(mesh, sh._guard(mesh, shape, spec))
        if s.endswith("encoder_out") or s.endswith("_scale") or any(
                s.endswith(t) for t in ("/k", "/v", "/ssm", "/conv", "/C",
                                        "/n", "/m", "/c", "/h")):
            return NamedSharding(
                mesh, sh._cache_pspec(s, shape, mesh, batch_first))
        spec = [dp if batch_first and shape[0] == global_batch else None]
        spec += [None] * (leaf.ndim - 1)
        return NamedSharding(mesh, sh._guard(mesh, shape, spec))

    return jax.tree_util.tree_map_with_path(one, out_specs)


def pick_microbatches(mesh, shape_cfg, *, tokens_budget: int = 8192) -> int:
    """Largest divisor of the per-device batch that brings per-microbatch
    tokens/device under budget (activation memory = one microbatch slice;
    grads accumulate in f32 across microbatches)."""
    dp_size = 1
    for a in sh.dp_axes(mesh):
        dp_size *= mesh.shape[a]
    per_dev_batch = max(1, shape_cfg.global_batch // dp_size)
    per_dev_tokens = per_dev_batch * shape_cfg.seq_len
    target = max(1, per_dev_tokens // tokens_budget)
    n = 1
    for cand in range(1, per_dev_batch + 1):
        if per_dev_batch % cand == 0 and cand <= target:
            n = cand
    return n


def make_train_step(cfg, mesh, shape_cfg, *, opt_cfg: AdamWConfig = None,
                    microbatches: int = 0):
    """Returns (train_step, arg_specs, in_shardings, out_shardings).

    Gradient accumulation over microbatches bounds activation memory: the
    assigned train shape (1M tokens/step global) is far beyond one
    microbatch per 16 GB chip.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    model = make_model(cfg)
    n_micro = microbatches or pick_microbatches(mesh, shape_cfg)

    def train_step(params, opt_state, batch):
        with sh.activation_policy(mesh, global_batch=shape_cfg.global_batch,
                                  train=True):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(model["loss"])(params,
                                                                batch)
            else:
                mb = jax.tree.map(
                    lambda a: sh.constrain_dim(
                        a.reshape((n_micro, a.shape[0] // n_micro)
                                  + a.shape[1:]), 1), batch)

                def micro_fn(carry, one):
                    gacc, lacc = carry
                    l, g = jax.value_and_grad(model["loss"])(params, one)
                    gacc = jax.tree.map(
                        lambda acc, gi: acc + gi.astype(jnp.float32),
                        gacc, g)
                    return (gacc, lacc + l), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (gsum, lsum), _ = jax.lax.scan(
                    micro_fn, (g0, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
                loss = lsum / n_micro
            params, opt_state, stats = adamw_update(opt_cfg, params, grads,
                                                    opt_state)
        return params, opt_state, loss

    p_specs = param_specs(cfg)
    o_specs = jax.eval_shape(adamw_init, p_specs)
    b_specs = input_specs(cfg, shape_cfg)

    p_shard = sh.params_shardings(p_specs, mesh, train=True)
    o_shard = {"m": sh.params_shardings(p_specs, mesh, train=True),
               "v": sh.params_shardings(p_specs, mesh, train=True),
               "step": NamedSharding(mesh, P())}
    b_shard = sh.batch_shardings(b_specs, mesh,
                                 global_batch=shape_cfg.global_batch)
    in_sh = (p_shard, o_shard, b_shard)
    out_sh = (p_shard, o_shard, NamedSharding(mesh, P()))
    return train_step, (p_specs, o_specs, b_specs), in_sh, out_sh


def make_prefill_step(cfg, mesh, shape_cfg):
    from repro.models.attention import kv_tp_repeat
    kv_rep = kv_tp_repeat(cfg, mesh.shape["model"])
    model = make_model(cfg, kv_repeat=kv_rep)

    def prefill_step(params, batch):
        with sh.activation_policy(mesh, global_batch=shape_cfg.global_batch):
            return model["prefill"](params, batch)

    p_specs = param_specs(cfg, inference=True)
    b_specs = input_specs(cfg, shape_cfg)
    p_shard = sh.params_shardings(p_specs, mesh, train=False)
    b_shard = sh.batch_shardings(b_specs, mesh,
                                 global_batch=shape_cfg.global_batch)
    out_specs = jax.eval_shape(prefill_step, p_specs, b_specs)
    out_sh = _out_tree_shardings(out_specs, mesh,
                                 global_batch=shape_cfg.global_batch)
    return prefill_step, (p_specs, b_specs), (p_shard, b_shard), out_sh


def make_decode_step(cfg, mesh, shape_cfg, *, kv_quant: bool = False):
    from repro.models.attention import kv_tp_repeat
    kv_rep = kv_tp_repeat(cfg, mesh.shape["model"])
    model = make_model(cfg, kv_repeat=kv_rep, kv_quant=kv_quant)

    def decode_step(params, batch):
        with sh.activation_policy(mesh, global_batch=shape_cfg.global_batch):
            return model["decode"](params, batch)

    p_specs = param_specs(cfg, inference=True)
    b_specs = input_specs(cfg, shape_cfg, kv_repeat=kv_rep,
                          kv_quant=kv_quant)
    p_shard = sh.params_shardings(p_specs, mesh, train=False)
    b_shard = sh.batch_shardings(b_specs, mesh,
                                 global_batch=shape_cfg.global_batch)
    out_specs = jax.eval_shape(decode_step, p_specs, b_specs)
    out_sh = _out_tree_shardings(out_specs, mesh,
                                 global_batch=shape_cfg.global_batch)
    return decode_step, (p_specs, b_specs), (p_shard, b_shard), out_sh


def make_step(cfg, mesh, shape_cfg):
    if shape_cfg.kind == "train":
        return make_train_step(cfg, mesh, shape_cfg)
    if shape_cfg.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape_cfg)
    return make_decode_step(cfg, mesh, shape_cfg)


# ---------------------------------------------------------------------------
# LargeVis layout step — the paper technique's own production cell
# ---------------------------------------------------------------------------

def make_largevis_step_local(mesh, *, n_nodes: int, n_edges: int,
                             batch: int, out_dim: int = 2,
                             n_negatives: int = 5, sync_every: int = 8,
                             layout_step: str = "auto"):
    """§Perf hillclimb 3: per-shard edge sampling + local-SGD sync.

    The v1 step shards the edge alias tables over DP and lets every device
    draw global indices — XLA materializes cross-shard table gathers (~2 GB
    per step).  The reference LargeVis gives each Hogwild thread its OWN
    sampling range, so the faithful distributed form is: each device holds
    a local alias table over its edge shard, samples locally (stratified
    sampling, proportional allocation), applies ``sync_every`` local update
    steps, and replicas merge with one delta-psum — the local-SGD analogue
    of the paper's async SGD (DESIGN.md §2).

    The H local steps are one scanned loop (``layout_engine``), the same
    body the single-device engine dispatches.  The wire format stays six
    flat table arrays (the dry-run lowering interface needs per-array
    shardings: edge tables shard over DP, node tables replicate); the
    body immediately reassembles them into the sampler pytrees the shared
    ``sgd_edge_step`` signature takes — each device's local
    ``EdgeSampler`` covers exactly its edge shard.
    """
    from repro.core.layout_engine import scan_layout_steps
    from repro.core.sampler import EdgeSampler, NodeSampler

    dp = sh.dp_axes(mesh)
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    b_loc = max(1, batch // n_shards)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct

    def step(y, seed, t_frac, edge_src, edge_dst, edge_thr, edge_alias,
             neg_thr, neg_alias):
        def body(y, seed, t_frac, esrc, edst, ethr, eali, nthr, nali):
            dev = jax.lax.axis_index(dp[-1])
            if len(dp) > 1:
                dev = dev + mesh.shape[dp[-1]] * jax.lax.axis_index(dp[0])
            y0 = y
            es = EdgeSampler(esrc, edst, ethr, eali, int(esrc.shape[0]))
            ns = NodeSampler(nthr, nali, n_nodes)
            base_key = jax.random.fold_in(jax.random.key(seed[0]), dev)
            step_ids = jnp.arange(sync_every, dtype=jnp.int32)
            y = scan_layout_steps(
                y, base_key, step_ids,
                jnp.broadcast_to(t_frac, (sync_every,)).astype(jnp.float32),
                edge_sampler=es, neg_sampler=ns, n_negatives=n_negatives,
                n_nodes=n_nodes, batch=b_loc, layout_step=layout_step)
            # merge replicas: Hogwild-sum of the deltas (one psum per H
            # steps) — every sampled edge's update lands at full lr, as
            # in the paper's async SGD; a mean would under-step the
            # schedule P-fold (see core/layout.make_local_sgd_fns)
            return y0 + jax.lax.psum(y - y0, dp)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P(dp), P(dp), P(dp), P(dp),
                      P(), P()),
            out_specs=P(), check_vma=False,
        )(y, seed, t_frac, edge_src, edge_dst, edge_thr, edge_alias,
          neg_thr, neg_alias)

    rep = NamedSharding(mesh, P())
    table = NamedSharding(mesh, sh._guard(mesh, (n_edges,), [dp]))
    arg_specs = (sds((n_nodes, out_dim), f32), sds((1,), i32), sds((), f32),
                 sds((n_edges,), i32), sds((n_edges,), i32),
                 sds((n_edges,), f32), sds((n_edges,), i32),
                 sds((n_nodes,), f32), sds((n_nodes,), i32))
    in_sh = (rep, rep, rep, table, table, table, table, rep, rep)
    return step, arg_specs, in_sh, rep


def make_largevis_step_sharded(mesh, *, n_nodes: int, n_edges: int,
                               batch: int, out_dim: int = 2,
                               n_negatives: int = 5, sync_every: int = 8,
                               layout_step: str = "auto"):
    """Local-SGD step over the *per-shard* sampler tables that
    ``sampler.build_samplers_sharded`` emits (PR 6 pipeline form).

    Unlike ``make_largevis_step_local`` — which slices one flat global
    alias table into slabs, leaving alias pointers that cross slab
    boundaries dangling — this builder's wire format is the stacked
    (P, E_loc) tables whose alias entries are LOCAL edge indices, so a
    device's slice is a self-contained alias table over exactly its
    edge shard (the reference implementation's per-thread sampling
    range).  Negatives sample *globally* through the two-level
    :class:`~repro.core.sampler.ShardedNodeSampler` (tiny replicated
    shard-selection table + stacked per-shard node tables), matching
    the paper's noise distribution P_n(j) ∝ deg(j)^0.75 over ALL nodes.

    Wire format: eleven flat arrays (per-array shardings for the
    dry-run lowering interface) — edge tables shard their leading (P,)
    axis over DP; neg + shard-selection tables replicate.
    """
    from repro.core.layout_engine import scan_layout_steps
    from repro.core.sampler import EdgeSampler, ShardedNodeSampler

    dp = sh.dp_axes(mesh)
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    if n_edges % n_shards:
        raise ValueError(f"n_edges={n_edges} not a multiple of the DP "
                         f"size {n_shards} (pad rows first)")
    if n_nodes < n_shards:
        # same constraint the elastic checkpoint restore enforces via its
        # topology tag (checkpoint/largevis_state.py): fewer rows than
        # shards cannot fill the contiguous-block layout
        raise ValueError(f"n_nodes={n_nodes} < DP size {n_shards}: rows "
                         f"cannot cover the mesh one block per device")
    e_loc = n_edges // n_shards
    n_loc = -(-n_nodes // n_shards)
    b_loc = max(1, batch // n_shards)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct

    def step(y, seed, t_frac, edge_src, edge_dst, edge_thr, edge_alias,
             neg_thr, neg_alias, neg_sthr, neg_sali):
        def body(y, seed, t_frac, esrc, edst, ethr, eali, nthr, nali,
                 nsthr, nsali):
            dev = jax.lax.axis_index(dp[-1])
            if len(dp) > 1:
                dev = dev + mesh.shape[dp[-1]] * jax.lax.axis_index(dp[0])
            y0 = y
            es = EdgeSampler(esrc[0], edst[0], ethr[0], eali[0], e_loc)
            ns = ShardedNodeSampler(nthr, nali, nsthr, nsali, n_shards,
                                    n_nodes)
            base_key = jax.random.fold_in(jax.random.key(seed[0]), dev)
            step_ids = jnp.arange(sync_every, dtype=jnp.int32)
            y = scan_layout_steps(
                y, base_key, step_ids,
                jnp.broadcast_to(t_frac, (sync_every,)).astype(jnp.float32),
                edge_sampler=es, neg_sampler=ns, n_negatives=n_negatives,
                n_nodes=n_nodes, batch=b_loc, layout_step=layout_step)
            # Hogwild-sum delta merge (see make_largevis_step_local)
            return y0 + jax.lax.psum(y - y0, dp)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P(dp, None), P(dp, None), P(dp, None),
                      P(dp, None), P(), P(), P(), P()),
            out_specs=P(), check_vma=False,
        )(y, seed, t_frac, edge_src, edge_dst, edge_thr, edge_alias,
          neg_thr, neg_alias, neg_sthr, neg_sali)

    rep = NamedSharding(mesh, P())
    table = NamedSharding(mesh, sh._guard(mesh, (n_shards, e_loc),
                                          [dp, None]))
    arg_specs = (sds((n_nodes, out_dim), f32), sds((1,), i32), sds((), f32),
                 sds((n_shards, e_loc), i32), sds((n_shards, e_loc), i32),
                 sds((n_shards, e_loc), f32), sds((n_shards, e_loc), i32),
                 sds((n_shards, n_loc), f32), sds((n_shards, n_loc), i32),
                 sds((n_shards,), f32), sds((n_shards,), i32))
    in_sh = (rep, rep, rep, table, table, table, table, rep, rep, rep, rep)
    return step, arg_specs, in_sh, rep


def make_largevis_transform_step(mesh, *, n_corpus: int, n_slots: int,
                                 k: int, out_dim: int = 2,
                                 n_negatives: int = 5, steps: int = 48,
                                 rho0: float = 1.0):
    """The projection server's lockstep "decode" as a launch-harness cell.

    One step of the continuous-batching projection engine
    (``launch/serve_projection.py``): every serving slot draws one
    positive edge from its own calibrated neighbor distribution plus M
    noise negatives and takes a fused edge step at its OWN schedule
    position (the kernel's per-edge (B,) lr mode), with the corpus rows
    of the resident ``[corpus; slots]`` embedding frozen via
    ``n_frozen`` masking.  Same 4-tuple contract as the LM builders;
    everything replicates (the working set is (N+S) x s f32 — tiny).

    Wire format: y_full (N+S, s), seed (1,), p_log (S, k), nn_idx
    (S, k), ages (S,) i32, active (S,) i32, neg_thr (N,), neg_alias (N,).
    """
    from repro.core.layout_engine import apply_edge_batch
    from repro.core.sampler import NodeSampler
    from repro.core.transform import sample_query_edges

    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct

    def step(y_full, seed, p_log, nn_idx, ages, active, neg_thr, neg_alias):
        ns = NodeSampler(neg_thr, neg_alias, n_corpus)
        key = jax.random.key(seed[0])
        i = n_corpus + jnp.arange(n_slots, dtype=i32)
        j, negs, neg_mask = sample_query_edges(
            key, p_log, nn_idx, ns, n_negatives)
        act = active.astype(bool)
        j = jnp.where(act, j, i)
        neg_mask = neg_mask * active.astype(f32)[:, None]
        lr = rho0 * jnp.maximum(1.0 - ages.astype(f32) / steps, 1e-4)
        return apply_edge_batch(y_full, i, j, negs, neg_mask, lr,
                                n_frozen=n_corpus)

    rep = NamedSharding(mesh, P())
    arg_specs = (sds((n_corpus + n_slots, out_dim), f32), sds((1,), i32),
                 sds((n_slots, k), f32), sds((n_slots, k), i32),
                 sds((n_slots,), i32), sds((n_slots,), i32),
                 sds((n_corpus,), f32), sds((n_corpus,), i32))
    in_sh = (rep,) * len(arg_specs)
    return step, arg_specs, in_sh, rep


def make_largevis_step(mesh, *, n_nodes: int, n_edges: int, batch: int,
                       out_dim: int = 2, n_negatives: int = 5):
    """Sharded layout step: edge batch over DP axes, embedding table
    replicated below 10M nodes (N x 2 f32 is tiny), grads combined by
    scatter-add.  Returns the same 4-tuple as the LM builders.  Flat
    table arrays on the wire (per-array shardings), sampler pytrees
    inside — same shared step signature as every other driver."""
    from repro.core.layout import layout_step
    from repro.core.sampler import EdgeSampler, NodeSampler

    dp = sh.dp_axes(mesh)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    args = {
        "y": sds((n_nodes, out_dim), f32),
        "edge_src": sds((n_edges,), i32),
        "edge_dst": sds((n_edges,), i32),
        "edge_thr": sds((n_edges,), f32),
        "edge_alias": sds((n_edges,), i32),
        "neg_thr": sds((n_nodes,), f32),
        "neg_alias": sds((n_nodes,), i32),
    }

    def step(y, seed, t_frac, edge_src, edge_dst, edge_thr, edge_alias,
             neg_thr, neg_alias):
        key = jax.random.key(seed[0])
        es = EdgeSampler(edge_src, edge_dst, edge_thr, edge_alias, n_edges)
        ns = NodeSampler(neg_thr, neg_alias, n_nodes)
        return layout_step(
            y, key, t_frac, edge_sampler=es, neg_sampler=ns,
            n_negatives=n_negatives, n_nodes=n_nodes, batch=batch)

    rep = NamedSharding(mesh, P())
    table = NamedSharding(mesh, sh._guard(mesh, (n_edges,), [dp]))
    node_t = NamedSharding(mesh, sh._guard(mesh, (n_nodes,), [dp]))
    arg_specs = (args["y"], sds((1,), i32), sds((), f32), args["edge_src"],
                 args["edge_dst"], args["edge_thr"], args["edge_alias"],
                 args["neg_thr"], args["neg_alias"])
    in_sh = (rep, rep, rep, table, table, table, table, node_t, node_t)
    out_sh = rep
    return step, arg_specs, in_sh, out_sh
