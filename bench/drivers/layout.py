"""Window driver: the layout's edge-sampling SGD, through ``run_layout``.

Set-up makes the corpus from the seed, builds the forest graph, the edge
weights and the alias samplers with the program's own stage functions
(the configuration's ``routing`` picks the samplers' builder), and draws
a starting embedding y0 from the seed.  It then makes one call of the
window's kind from y0 (that compiles the window's programs) and hands
the embedding it leaves to the window.

The window calls ``run_layout`` on the default schedule
(``samples_per_node`` edge samples per point, the chunked asynchronous
dispatch) again and again, each call with a key of its own and a
``start_step`` so that it runs the schedule's final stretch: the last
``chunks_per_call`` dispatches of a default fit and the shorter
dispatch that ends it, chunk for chunk as a fit from step 0 would make
them.  Per-step work does not depend on the position in the schedule.
The window closes at the first call that ends past ``--seconds``.

``correct``: the reference replays the window's last call, from the
embedding that call started from, with the same draws
(``bench/reference.layout_steps``), and the two embeddings are compared.
The draws read the program's alias tables, so the tables are first
compared with the distributions the reference works out from the
weights themselves (``bench/reference.table_tv``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference
from bench.common import Check, program_config

# limits of the numbers compared; see PERF.md for the readings behind them
LIMITS = {"y_err": 1e-3, "move_gap": 1e-3, "edge_tv": 1e-6,
          "node_tv": 1e-6, "edge_ends_bad": 0}


def build_samplers(idx, w, *, impl: str, power: float):
    """The program's edge and negative samplers of the graph (idx, w)."""
    from repro.core import sampler
    return (sampler.build_edge_sampler(idx, w, impl=impl),
            sampler.build_negative_sampler(idx, w, power=power, impl=impl))


def build_map(ctx, lv, x, key):
    """Forest graph, edge weights and samplers of the corpus."""
    from repro.core import knn, perplexity
    t0 = time.perf_counter()
    idx, dist = knn.build_knn_graph(x, key, lv)
    jax.block_until_ready((idx, dist))
    t1 = time.perf_counter()
    w = perplexity.edge_weights(idx, dist, lv.perplexity,
                                iters=lv.perplexity_iters)
    jax.block_until_ready(w)
    t2 = time.perf_counter()
    edge_s, neg_s = ctx.op("samplers", build_samplers)(
        idx, w, impl=lv.sampler_impl, power=lv.neg_power)
    jax.block_until_ready((edge_s.threshold, neg_s.threshold))
    t3 = time.perf_counter()
    ctx.log(f"setup knn_s={t1 - t0:.3f} weights_s={t2 - t1:.3f} "
            f"sampler_s={t3 - t2:.3f} sampler={lv.sampler_impl}")
    return idx, w, edge_s, neg_s


def schedule(lv, n: int) -> tuple[int, int, int]:
    """(steps of the whole schedule, batch, steps per dispatch), as
    ``run_layout`` sets them."""
    from repro.core import layout, layout_engine
    total = int(lv.samples_per_node) * n
    batch = layout._collision_capped_batch(lv.batch_size, n, total)
    steps = max(1, total // batch)
    h = layout_engine.dispatch_steps(int(lv.steps_per_dispatch), n_nodes=n,
                                     batch=batch)
    return steps, batch, max(1, h)


def call_steps(steps: int, h: int, chunks: int) -> int:
    """Steps of one window call: ``chunks`` whole dispatches and the
    shorter one that ends a fit from step 0 (none where h divides it)."""
    return min(steps, chunks * h + steps % h)


def setup(ctx) -> dict:
    from repro.core.layout import run_layout

    cfg, tr = ctx.cfg, ctx.traffic
    k_data, k_graph, k_y, k_warm, k_win = jax.random.split(
        data.seed_key(ctx.seed), 5)
    x, _ = data.corpus(cfg, k_data)
    lv = program_config(cfg)
    n = x.shape[0]
    idx, w, edge_s, neg_s = build_map(ctx, lv, x, k_graph)
    del x
    steps, batch, h = schedule(lv, n)
    per_call = call_steps(steps, h, int(tr["chunks_per_call"]))
    y0 = jax.random.normal(k_y, (n, lv.out_dim), jnp.float32) * float(
        tr["y0_scale"])
    state = {"lv": lv, "n": n, "idx": idx, "w": w, "edge_s": edge_s,
             "neg_s": neg_s, "steps": steps, "batch": batch,
             "start": steps - per_call,
             "layout": ctx.op("layout", run_layout), "k_win": k_win}
    t0 = time.perf_counter()
    res = state["layout"](k_warm, edge_s, neg_s, n, lv, y0=y0,
                          start_step=state["start"])
    state["y"] = jax.block_until_ready(res.y)
    ctx.log(f"setup call_steps={res.steps} per_dispatch={h} batch={batch} "
            f"steps={steps} first_call_s={time.perf_counter() - t0:.3f}")
    return state


def window(ctx, state) -> dict:
    lv, n = state["lv"], state["n"]
    y = state.pop("y")
    layout = state["layout"]
    calls = 0
    t0 = time.perf_counter()
    while True:
        key = jax.random.fold_in(state["k_win"], calls)
        # a call donates its input: keep a copy for the check of the last
        y_in = jnp.copy(y)
        with jax.profiler.TraceAnnotation("bench.layout_call"):
            res = layout(key, state["edge_s"], state["neg_s"], n, lv, y0=y,
                         start_step=state["start"])
            y = jax.block_until_ready(res.y)
        calls += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    done = calls * res.steps
    state["last"] = (key, y_in, y)
    return {"attempted": done, "failed": 0,
            "metrics": {"layout_samples_per_s":
                        done * state["batch"] / elapsed},
            "counts": {"steps": done, "calls": calls, "elapsed_s": elapsed,
                       "n": n, "s": lv.out_dim, "batch": state["batch"],
                       "negatives": lv.n_negatives}}


def tables(edge_s, neg_s) -> dict:
    return {"src": edge_s.src, "dst": edge_s.dst,
            "edge_threshold": edge_s.threshold, "edge_alias": edge_s.alias,
            "node_threshold": neg_s.threshold, "node_alias": neg_s.alias}


def replay(lv, key, y_in, tab: dict, *, start: int, steps: int, batch: int,
           dtype=jnp.float32):
    """The reference's embedding after ``run_layout(key, ...)``'s steps
    ``start``..``steps`` from ``y_in``, drawing from the tables ``tab``.
    ``run_layout`` splits its key in two and steps with the second."""
    step_ids = jnp.arange(start, steps, dtype=jnp.int32)
    t_fracs = jnp.asarray(np.arange(start, steps) / steps, jnp.float32)
    _, base = jax.random.split(key)
    return reference.layout_steps(
        y_in, base, step_ids, t_fracs, tab, batch=batch,
        negatives=lv.n_negatives, a=lv.prob_a, gamma=lv.gamma,
        clip=lv.grad_clip, rho0=lv.rho0, dtype=dtype)


def after(ctx, state, out) -> tuple[list, dict]:
    lv = state["lv"]
    edge_s, neg_s = state["edge_s"], state["neg_s"]
    tv = reference.table_tv(
        np.asarray(state.pop("idx")), np.asarray(state.pop("w")),
        {k: np.asarray(v) for k, v in tables(edge_s, neg_s).items()},
        power=lv.neg_power)
    ctx.log(f"alias tables against the weights: {tv}")
    key, y_in, y_prog = state.pop("last")
    y_ref = replay(lv, key, y_in, tables(edge_s, neg_s),
                   start=state["start"], steps=state["steps"],
                   batch=state["batch"])
    got = dict(reference.layout_compare(y_in, y_prog, y_ref),
               edge_tv=tv["edge_tv"], node_tv=tv["node_tv"],
               edge_ends_bad=tv["edge_ends_bad"])
    ctx.log(f"layout replay of the window's last call "
            f"({state['steps'] - state['start']} steps): {got}")
    return [Check(k, got[k], LIMITS[k]) for k in LIMITS], {}
