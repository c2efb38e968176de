"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo contract and saves
JSON artifacts under benchmarks/artifacts/.  ``--only fig2`` runs one
table.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Rows

TABLES = [
    ("fig2_knn_construction", "benchmarks.fig2_knn_construction"),
    ("fig3_neighbor_exploring", "benchmarks.fig3_neighbor_exploring"),
    ("fig4_prob_functions", "benchmarks.fig4_prob_functions"),
    ("fig5_knn_classifier", "benchmarks.fig5_knn_classifier"),
    ("table2_layout_time", "benchmarks.table2_layout_time"),
    ("table3_sampler_build", "benchmarks.table3_sampler_build"),
    ("fig6_scaling", "benchmarks.fig6_scaling"),
    ("fig7_sensitivity", "benchmarks.fig7_sensitivity"),
    ("serve_latency", "benchmarks.serve_latency"),
    ("autotune", "benchmarks.autotune_sweep"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    # declared-environment preset (flag hygiene) before any kernel compiles
    from repro.runtime import platform
    platform.apply_bench_preset()
    platform.use_compile_cache()
    import importlib
    t_all = time.time()
    failures = []
    for name, modpath in TABLES:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        rows = Rows(name)
        try:
            mod = importlib.import_module(modpath)
            mod.run(rows)
            rows.print_csv()
            rows.save()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"# {name} FAILED: {e!r}", file=sys.stderr)
    print(f"# total {time.time()-t_all:.1f}s", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
