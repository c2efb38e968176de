"""Smoke run of the LargeVis fit-and-serve path on a TPU.

    python chip_smoke.py              # one chip: fit, kernel parity, serve
    python chip_smoke.py --chips 4    # distributed fit vs the one-chip fit

One chip: the paper's 784-dim image corpus (``mnist_like``: N=70,000,
d=784, 10 classes, made from a seed) goes through ``largevis()`` with the
``LargeVisConfig`` defaults (K=150, perplexity 50, M=5, s=2, batch 4096,
8 trees, 1 full explore round) except ``samples_per_node`` (the layout's
edge samples per point), cut from 10,000 to 2,000 to keep the layout to
about a minute.  It checks the route the layout took (the Pallas
edge-step kernel, device alias tables, no degraded mode), KNN recall,
5-NN accuracy of the layout, the KNN and edge-step kernels against their
oracles on the chip, and a ``ProjectionEngine`` answering 256 held-out
queries with the fitted corpus left bitwise frozen.

``--chips 4``: ``largevis(distributed=True)`` on the same generator and
seed, compared with the one-device fit: weights against a one-device
calibration of the same graph, stage outputs spread over all four
devices, 5-NN accuracy, and held-out queries projected into the
distributed fit's map.  It runs a 10,000-point corpus: most of a
four-chip call at that size is compiling, and four chips cost four
times the chip time of one.

Stage times are printed as a smoke run, not a benchmark.  Without a TPU
the script exits nonzero before any phase.  The last line of a passing
run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src")]

N_CORPUS = 70_000
N_QUERY = 256
DIM = 784
N_CORPUS_4CHIP = 10_000
SAMPLES_PER_NODE = 2000
RECALL_FLOOR = 0.90
# 5-NN accuracy floor of the 2-D layout (chance is 0.1); see CHANGES.md
ACC_FLOOR = 0.90
TOPK_ROWS = 4096
# kernel-vs-oracle tolerances on the chip (f32 matmul passes on the MXU
# and in XLA need not match, so these are tolerances, not bitwise)
TOPK_MIN_OVERLAP = 0.99
TOPK_MAX_REL_ERR = 5e-3
STEP_MAX_ABS_ERR = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """A failed smoke check (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _data(n: int, d: int, seed: int):
    import jax

    from repro.data.synthetic import mnist_like
    return mnist_like(jax.random.key(seed), n, d, 10)


def _config(samples_per_node: int):
    from repro.configs.largevis_default import LargeVisConfig, RoutingConfig
    # tiles from the built-in defaults only: no per-user autotune cache
    return LargeVisConfig(samples_per_node=samples_per_node,
                          routing=RoutingConfig(autotune="off"))


def _fit(x, cfg, seed: int, label: str):
    import jax

    from repro.core.largevis import largevis
    t0 = time.time()
    res = largevis(x, jax.random.key(seed), cfg=cfg)
    jax.block_until_ready(res.y)
    wall = time.time() - t0
    stages = " ".join(f"{k}={v:.2f}" for k, v in res.timings.items())
    log(f"[smoke run, not a benchmark] {label} fit wall_s={wall:.2f} "
        f"{stages}")
    return res


def _layout_route(res, cfg) -> str:
    """Compiled text of one layout chunk exactly as ``run_layout`` calls
    it (same samplers, batch, chunk length)."""
    import jax
    import jax.numpy as jnp

    from repro.core import layout, layout_engine
    n = res.y.shape[0]
    total = cfg.samples_per_node * n
    batch = layout._collision_capped_batch(cfg.batch_size, n, total)
    h = min(cfg.steps_per_dispatch, max(1, total // batch))
    kw = layout._step_kwargs(res.edge_sampler, res.neg_sampler, n, cfg,
                             batch)
    return layout_engine.layout_chunk.lower(
        jnp.zeros_like(res.y), jax.random.key(0),
        jnp.arange(h, dtype=jnp.int32), jnp.zeros((h,), jnp.float32),
        **kw).compile().as_text()


def _topk_parity(x, rows: int, k: int):
    """Kernel vs streaming oracle on one (rows x N) call: id overlap and
    max distance error relative to the largest true distance."""
    import jax
    import numpy as np

    from repro.kernels import knn_topk, ref
    a = x[:rows]
    ki, kd = knn_topk.topk_sqdist(a, x, k, a_ids=None)
    with jax.default_matmul_precision("highest"):
        ri, rd = jax.jit(ref.topk_sqdist_ref, static_argnames=(
            "k", "bm", "bn", "lane"))(a, x, k, bm=256, bn=512, lane=128)
    ki, kd, ri, rd = (np.asarray(v) for v in (ki, kd, ri, rd))
    overlap = float(np.mean([len(np.intersect1d(p, q)) / k
                             for p, q in zip(ki, ri)]))
    rel = float(np.abs(kd - rd).max() / max(rd.max(), 1e-30))
    return overlap, rel


def _step_parity(res, cfg, seed: int):
    """Fused edge-step kernel vs ``ref.fused_edge_step_ref`` on one batch
    drawn from the fit's own samplers at the fitted layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import layout
    from repro.kernels import ops, ref
    from repro.kernels.largevis_step import fused_edge_step
    n, s = res.y.shape
    batch = layout._collision_capped_batch(cfg.batch_size, n)
    ke, kn = jax.random.split(jax.random.key(seed + 7))
    i, j = res.edge_sampler.sample(ke, batch)
    negs = res.neg_sampler.sample(kn, (batch, cfg.n_negatives))
    mask = ((negs != i[:, None]) & (negs != j[:, None])).astype(jnp.float32)
    kw = dict(gamma=cfg.gamma, a=cfg.prob_a, clip=cfg.grad_clip)
    got = fused_edge_step(res.y, i, j, negs, mask, 0.5,
                          y_tile=ops._fused_y_tile(n, s), **kw)
    want = jax.jit(ref.fused_edge_step_ref, static_argnames=(
        "gamma", "a", "clip"))(res.y, i, j, negs, mask, 0.5, **kw)
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max()), bool(np.array_equal(got, want))


def _serve(res, queries):
    """ProjectionEngine over the fitted model: every query answered,
    coordinates finite, corpus rows of the resident embedding frozen."""
    import numpy as np

    from repro.launch.serve_projection import ProjectionEngine, ProjectRequest
    q = np.asarray(queries)
    eng = ProjectionEngine(res, slots=q.shape[0])
    t0 = time.time()
    for r in range(q.shape[0]):
        eng.submit(ProjectRequest(rid=r, x=q[r]))
    steps = eng.run()
    wall = time.time() - t0
    n = res.y.shape[0]
    done = [r for r in eng.completed if r.error is None]
    check(len(done) == q.shape[0] and not eng.quarantined,
          f"served {len(done)}, quarantined {len(eng.quarantined)}")
    coords = np.stack([r.y for r in done])
    check(np.isfinite(coords).all(), "served coordinates finite")
    frozen = np.asarray(eng.y_full[:n]).view(np.uint32)
    check(np.array_equal(frozen, np.asarray(res.y).view(np.uint32)),
          "corpus rows bitwise frozen")
    log(f"[smoke run, not a benchmark] serve queries={q.shape[0]} "
        f"engine_steps={steps} wall_s={wall:.2f} corpus_frozen=bitwise")


def _alias_error(res) -> float:
    """Worst per-slot relative error of the device edge alias table's
    draw probabilities against the normalized weights."""
    import numpy as np

    from repro.core import sampler
    w = np.asarray(res.weights, np.float64).reshape(-1)
    p = np.maximum(w, 0.0) / np.maximum(w, 0.0).sum()
    m = sampler.edge_marginals(res.edge_sampler)
    live = p > 0
    return float(np.max(np.abs(m[live] - p[live]) / p[live]))


def run_one_chip(*, n: int = N_CORPUS, d: int = DIM, n_query: int = N_QUERY,
                 samples_per_node: int = SAMPLES_PER_NODE,
                 topk_rows: int = TOPK_ROWS, acc_floor: float = ACC_FLOOR,
                 recall_floor: float = RECALL_FLOOR, seed: int = 0) -> dict:
    """Fit, check the route and quality, check kernel parity, serve."""
    import jax
    import numpy as np

    from repro.core import metrics, sampler
    from repro.runtime import autotune
    x_all, labels = _data(n + n_query, d, seed)
    x, queries = x_all[:n], x_all[n:]
    labels = labels[:n]
    cfg = _config(samples_per_node)
    log(f"corpus n={n} d={d} K={cfg.n_neighbors} samples_per_node="
        f"{samples_per_node} explore_sample={cfg.explore_sample} (0 = full) "
        f"batch={cfg.batch_size}")

    res = _fit(x, cfg, seed, "one-device")
    log(f"autotune mode={autotune.mode()}")
    on_tpu = jax.default_backend() == "tpu"
    kernel = "tpu_custom_call" in _layout_route(res, cfg)
    impl = sampler._resolve_impl(cfg.sampler_impl)
    log(f"route: layout kernel compiled={kernel} sampler={impl}")
    check(kernel == on_tpu and impl == "device", f"route {kernel} {impl}")
    check(np.isfinite(np.asarray(res.y)).all(), "layout finite")

    recall = metrics.graph_recall(x, res.knn_idx, n_eval=200)
    acc = metrics.knn_classifier_accuracy(res.y, labels)
    log(f"knn recall={recall:.4f} (floor {recall_floor}) "
        f"5nn_accuracy={acc:.4f} (floor {acc_floor})")
    check(recall >= recall_floor and acc >= acc_floor,
          f"recall {recall}, accuracy {acc}")

    overlap, rel = _topk_parity(x, min(topk_rows, n), cfg.n_neighbors)
    log(f"parity topk_sqdist: id_overlap={overlap:.6f} "
        f"max_rel_dist_err={rel:.3e}")
    check(overlap >= TOPK_MIN_OVERLAP and rel <= TOPK_MAX_REL_ERR,
          f"topk overlap {overlap}, relative error {rel}")
    err, bitwise = _step_parity(res, cfg, seed)
    log(f"parity fused_edge_step: max_abs_err={err:.3e} bitwise={bitwise}")
    check(err <= STEP_MAX_ABS_ERR, f"edge step error {err}")

    _serve(res, queries)
    log(f"alias table worst per-slot relative marginal error="
        f"{_alias_error(res):.4e} (E={res.weights.size}, information only)")
    return dict(recall=recall, accuracy=acc)


def run_four_chips(*, n: int = N_CORPUS_4CHIP, d: int = DIM,
                   n_query: int = N_QUERY,
                   samples_per_node: int = SAMPLES_PER_NODE, seed: int = 0,
                   shards: int = 4) -> dict:
    """Distributed fit on ``shards`` devices vs the one-device fit."""
    import jax
    import numpy as np

    from repro.core import metrics, perplexity, transform
    x_all, labels = _data(n + n_query, d, seed)
    x, queries = x_all[:n], x_all[n:]
    labels = labels[:n]
    cfg = _config(samples_per_node)
    one = _fit(x, cfg, seed, "one-device")
    dist = _fit(x, dataclasses.replace(cfg, distributed=True,
                                       data_shards=shards), seed,
                f"distributed({shards})")
    for name in ("knn_idx", "knn_dist", "weights"):
        devs = getattr(dist, name).sharding.device_set
        log(f"distributed {name}: on {len(devs)} devices")
        check(len(devs) == shards, f"{name} on {devs}")
    # the sharded calibration + symmetrization against one device's, on
    # the distributed run's own graph
    dev0 = jax.devices()[0]
    w1 = perplexity.edge_weights(
        jax.device_put(dist.knn_idx, dev0),
        jax.device_put(dist.knn_dist, dev0), cfg.perplexity,
        iters=cfg.perplexity_iters)
    wd, w1 = np.asarray(dist.weights), np.asarray(w1)
    w_err = float(np.abs(wd - w1).max())
    w_bitwise = bool(np.array_equal(wd.view(np.uint32), w1.view(np.uint32)))
    log(f"weights sharded vs one device: bitwise={w_bitwise} "
        f"max_abs_err={w_err:.3e}")
    check(w_err <= 1e-6 * max(float(np.abs(w1).max()), 1e-30),
          f"sharded weights error {w_err}")
    # the embedding comes back on one device, where the one-device
    # Pallas kernels of the metric and of transform can read it
    y_devs = dist.y.sharding.device_set
    log(f"distributed y: on {len(y_devs)} device(s)")
    check(len(y_devs) == 1, f"distributed y on {y_devs}")
    check(np.isfinite(np.asarray(dist.y)).all(), "distributed layout finite")
    acc1 = metrics.knn_classifier_accuracy(one.y, labels)
    accd = metrics.knn_classifier_accuracy(dist.y, labels)
    log(f"5nn_accuracy one-device={acc1:.4f} distributed={accd:.4f}")
    check(abs(acc1 - accd) <= 0.05, f"accuracy {acc1} vs {accd}")
    yq, _ = transform.project(queries, x=dist.x, y=dist.y, cfg=cfg)
    check(yq.shape == (n_query, cfg.out_dim)
          and np.isfinite(np.asarray(yq)).all(), "projected queries")
    log(f"transform into the distributed map: {n_query} queries finite")
    return dict(accuracy=accd, weights_bitwise=w_bitwise)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed fit and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro.runtime import platform
    from repro.runtime.fault_tolerance import DegradedModeWarning
    log(f"compile cache: {platform.use_compile_cache()}")
    # a demoted route fails the run instead of passing it
    warnings.simplefilter("error", DegradedModeWarning)
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
