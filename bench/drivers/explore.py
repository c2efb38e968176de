"""Window driver: the neighbor-explore round over successive row blocks.

Set-up makes the corpus from the seed and builds the forest graph with
the program's ``build_knn_graph`` (its explore round off), then compiles
the rows entry of ``neighbor_explore`` at the block size.  The window
explores contiguous blocks of ``block_rows`` rows from a start drawn
from the seed, wrapping at N, each block written back into the graph the
next block reads, until the first block that ends past ``--seconds``.

Mix parameters (``bench/traffic/<name>.json``): ``block_rows``,
``recall_rows`` (rows of the window sampled for ``graph_recall``) and
``check_rows`` (rows of each checked block compared with the reference;
0 = all).

``correct``: the graph each of two blocks started from (the first and
the last of the window) is kept, and the reference explores the same
rows from it (``bench/reference.explore_rows``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference
from bench.common import Check, program_config

# limits of the numbers compared; see PERF.md for the readings behind them
LIMITS = {"id_miss": 5e-4, "dist_err": 1e-4, "bad_ids": 0}


def setup(ctx) -> dict:
    from repro.core import knn
    from repro.core.neighbor_explore import neighbor_explore

    cfg, tr = ctx.cfg, ctx.traffic
    key = data.seed_key(ctx.seed)
    k_data, k_graph, k_win = jax.random.split(key, 3)
    t0 = time.perf_counter()
    x, _ = data.corpus(cfg, k_data)
    lv = program_config(cfg)
    idx, dist = knn.build_knn_graph(x, k_graph, lv)
    jax.block_until_ready((idx, dist))
    ctx.log(f"setup forest_s={time.perf_counter() - t0:.3f}")
    n = x.shape[0]
    block = int(tr["block_rows"])
    start = int(jax.random.randint(k_win, (), 0, n))
    state = {"x": x, "idx": idx, "dist": dist, "block": block,
             "start": start, "sample": lv.explore_sample,
             "explore": ctx.op("explore", neighbor_explore)}
    # compile the window's one program (the output is thrown away)
    t0 = time.perf_counter()
    jax.block_until_ready(state["explore"](
        x, idx, dist, rows=block_rows(start, block, n),
        sample=state["sample"]))
    ctx.log(f"setup explore_compile_s={time.perf_counter() - t0:.3f}")
    return state


def block_rows(start: int, block: int, n: int) -> jax.Array:
    return jnp.asarray((start + np.arange(block)) % n, jnp.int32)


def window(ctx, state) -> dict:
    x, idx, dist = state["x"], state["idx"], state["dist"]
    n = x.shape[0]
    block, start = state["block"], state["start"]
    explore = state["explore"]
    kept = []
    b = 0
    t0 = time.perf_counter()
    while True:
        rows = block_rows(start + b * block, block, n)
        with jax.profiler.TraceAnnotation("bench.explore_block"):
            new_idx, new_dist = explore(x, idx, dist, rows=rows,
                                        sample=state["sample"])
            jax.block_until_ready((new_idx, new_dist))
        if b == 0:
            kept.append((rows, idx, dist, new_idx, new_dist))
        last = (rows, idx, dist, new_idx, new_dist)
        idx, dist = new_idx, new_dist
        b += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    if b > 1:
        kept.append(last)
    state.update(idx=idx, dist=dist, kept=kept, blocks=b)
    rows_done = b * block
    return {"attempted": rows_done, "failed": 0,
            "metrics": {"graph_points_per_s": rows_done / elapsed},
            "counts": {"calls": b, "rows": rows_done, "elapsed_s": elapsed,
                       "n": n, "k": idx.shape[1], "d": x.shape[1]}}


def after(ctx, state, out) -> tuple[list, dict]:
    x = state["x"]
    n = x.shape[0]
    rng = np.random.default_rng([ctx.seed, 7])
    explored = (state["start"] + np.arange(out["counts"]["rows"])) % n
    explored = np.unique(explored)
    sample = np.sort(rng.choice(explored, min(int(ctx.traffic["recall_rows"]),
                                              explored.shape[0]),
                                replace=False))
    rec = reference.recall(x, np.asarray(state["idx"][jnp.asarray(sample)]),
                           sample)
    ctx.log(f"graph_recall={rec!r} over {sample.shape[0]} sampled rows")
    state.pop("idx"), state.pop("dist")

    worst = {"id_miss": 0.0, "dist_err": 0.0, "bad_ids": 0}
    check_rows = int(ctx.traffic.get("check_rows", 0))
    for rows, idx_in, dist_in, idx_out, dist_out in state.pop("kept"):
        rows = np.asarray(rows)
        if check_rows and check_rows < rows.shape[0]:
            rows = np.sort(rng.choice(rows, check_rows, replace=False))
        at = jnp.asarray(rows)
        got = reference.explore_compare(
            x, idx_in, dist_in, rows, np.asarray(idx_out[at]),
            np.asarray(dist_out[at]))
        ctx.log(f"explore block rows {rows[0]}..: {got}")
        for name, v in got.items():
            worst[name] = max(worst[name], v)
    checks = [Check(name, worst[name], LIMITS[name]) for name in LIMITS]
    return checks, {"graph_recall": rec}
