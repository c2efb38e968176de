"""The benchmark: one cell of ``BENCHMARK.json`` on the chip it runs on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The mix names the
window driver that reads it (``bench/drivers/<driver>.py``), and each
per-layer metric has a reader of its own (``bench/metrics/<name>.py``),
all found by name: a new cell, mix, configuration or metric is a new file
and a new entry, never an edit.

A run makes its corpus from ``--seed`` on the device, builds and warms up
what its window needs (that is ``setup_s``), measures for ``--seconds``,
and then checks what the window produced against a plain reference.
With ``--trace 1`` the window runs under the profiler and the line holds
the per-layer metrics read from the trace instead of the end-to-end
ones.  The last line of standard output is one JSON object; the numbers
compared for ``correct``, each with its limit, are the last lines of
standard error and the last key of that object.  Without an accelerator,
or with fewer chips than the cell asks for, the run prints no result and
exits with 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    mod_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str, e2e_of_cell: set[str]) -> bool:
    """Whether ``metric`` is reported in ``cell``: its ``workloads`` list,
    or, without one, every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_of_cell
    return True


@dataclasses.dataclass
class Context:
    """What a window driver is given: the cell's configuration and mix,
    the run's seed and length, and a log for earlier lines."""
    cell: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    ops: dict = dataclasses.field(default_factory=dict)
    log: object = log

    def op(self, name: str, default):
        """The program's callable ``name``, unless a test or the control
        put another in its place."""
        return self.ops.get(name, default)


def top(seconds: dict, n: int = 8) -> list:
    return sorted(seconds.items(), key=lambda kv: -kv[1])[:n]


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax, n_chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = []
    for dev in jax.devices()[:n_chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def setup_environment() -> str:
    """Environment every run shares; returns the compile cache directory.

    The committed tile table alone picks kernel tiles (an empty user
    cache, and the tuner never sweeps), and JAX's persistent compilation
    cache lives at a fixed path inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one."""
    os.environ["AUTOTUNE"] = "cache"
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(BENCH, "autotune_empty")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def run(cell: dict, cfg: dict, traffic: dict, *, seed: int, seconds: float,
        trace: bool, spec: dict, t_start: float, ops=None,
        trace_dir: str | None = None) -> dict:
    """One run of ``cell``; returns the result object.

    ``ops`` puts other callables in the place of the program's timed
    operations (by the names the window driver asks ``Context.op``
    for): the control and the tests that break the timed path use it.  The trace
    of a traced run goes to a temporary directory, removed after it is
    read, unless ``trace_dir`` keeps it."""
    import jax

    from bench import trace as trace_lib

    ctx = Context(cell=cell["name"], cfg=cfg, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, ops=dict(ops or {}))
    driver = load_module("drivers", traffic["driver"])
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s:.3f}")

    tmp = None
    if trace and trace_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        trace_dir = tmp.name
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            out = driver.window(ctx, state)
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            t0 = time.perf_counter()
            reduced = trace_lib.reduce_file(trace_dir)
            log(f"trace read in {time.perf_counter() - t0:.3f}s: "
                f"programs {top(reduced['programs'])} "
                f"kernels {top(reduced['kernels'])} "
                f"spans {reduced['spans']}")
    finally:
        if tmp is not None:
            tmp.cleanup()
    n_chips = int(cell.get("chips", 1))
    device = device_info(jax)
    device["memory_peak_bytes"] = memory_peak(jax, n_chips)
    checks, late_metrics = driver.after(ctx, state, out)
    del state

    e2e = [m for m in spec["end_to_end"] if applies(m, cell["name"], set())]
    e2e_names = {m["name"] for m in e2e}
    values = dict(out.get("metrics", {}), **late_metrics, setup_s=setup_s)
    metrics = {}
    result = {"correct": all(c.ok for c in checks),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        run_info = {"cfg": cfg, "traffic": traffic, "counts": out["counts"],
                    "peak": None}
        from bench import counts
        run_info["peak"] = counts.peaks(device["kind"]) if \
            device["platform"] != "cpu" else None
        for m in spec["per_layer"]:
            if not applies(m, cell["name"], e2e_names):
                continue
            value = load_module("metrics", m["name"]).read(reduced, run_info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name}={c.value!r} limit={c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the benchmark's modules import as ``bench.*`` from the checkout's
    # root; its own directory leaves the path (``trace.py`` would shadow
    # the standard library's module of that name)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    sys.path.insert(0, ROOT)

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    cfg = load_json(os.path.join(BENCH, "configs", f"{cell['config']}.json"))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     f"{cell['traffic']}.json"))
    cache = setup_environment()
    import jax
    backend = jax.default_backend()
    if backend not in ("tpu", "gpu"):
        log(f"no accelerator: JAX's backend is {backend!r}; nothing was run")
        return 2
    if len(jax.devices()) < int(cell["chips"]):
        log(f"{args.workload} needs {cell['chips']} chips, JAX finds "
            f"{len(jax.devices())}; nothing was run")
        return 2
    log(f"compile cache: {cache}")
    log(f"device: {device_info(jax)}")
    result = run(cell, cfg, traffic, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), spec=spec, t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
