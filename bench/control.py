"""Readings behind the limits of ``correct``: the program's, and the
control's, where the reference in the precision below the
configuration's (bfloat16 for its float32) takes the program's place.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 [--program]

Each seed is one run of the cell in this process (the compiled programs
are shared), with a short window; one JSON line per seed gives the
numbers compared.  The benchmark's own runs never run the control.
Without an accelerator it exits with 2, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def explore_control(x, idx, dist, *, rows, sample=0, **_):
    """One explore round over ``rows`` by the reference in bfloat16."""
    import jax.numpy as jnp
    import numpy as np

    from bench import reference
    del sample
    ids, ds = reference.explore_rows(x, idx, dist, np.asarray(rows),
                                     dtype=jnp.bfloat16)
    return idx.at[rows].set(ids), dist.at[rows].set(ds)


def layout_control(key, edge_s, neg_s, n, lv, *, y0, start_step):
    """``run_layout``'s final stretch by the reference in bfloat16."""
    import jax.numpy as jnp

    from bench.drivers import layout as layout_driver
    steps, batch, _ = layout_driver.schedule(lv, n)
    y = layout_driver.replay(lv, key, y0,
                             layout_driver.tables(edge_s, neg_s),
                             start=start_step, steps=steps, batch=batch,
                             dtype=jnp.bfloat16)
    return types.SimpleNamespace(y=y.astype(jnp.float32),
                                 steps=steps - start_step)


def samplers_control(idx, w, *, impl, power):
    """The program's samplers with their thresholds held in bfloat16."""
    import dataclasses

    import jax.numpy as jnp

    from bench.drivers import layout as layout_driver
    edge_s, neg_s = layout_driver.build_samplers(idx, w, impl=impl,
                                                 power=power)

    def bf16(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    return (dataclasses.replace(edge_s, threshold=bf16(edge_s.threshold)),
            dataclasses.replace(neg_s, threshold=bf16(neg_s.threshold)))


CONTROLS = {"explore": {"explore": explore_control},
            "layout": {"layout": layout_control,
                       "samplers": samplers_control}}



def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="read the program instead of the control")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    sys.path.insert(0, ROOT)
    from bench import run as bench_run
    spec = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = bench_run.load_json(
        os.path.join(BENCH, "configs", f"{cell['config']}.json"))
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    bench_run.setup_environment()
    import jax
    if jax.default_backend() not in ("tpu", "gpu"):
        print("no accelerator; nothing was run", file=sys.stderr)
        return 2
    ops = {} if args.program else CONTROLS[traffic["driver"]]
    for seed in args.seeds:
        res = bench_run.run(cell, cfg, traffic, seed=seed,
                            seconds=args.seconds, trace=False, spec=spec,
                            t_start=time.perf_counter(), ops=ops)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mode": "program" if args.program else "control",
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
