"""Record the small traces that ``test_trace.py`` reads, on a chip.

    python bench/tests/record_traces.py [<directory>]

Runs each window driver at a tiny size (the sizes of ``test_cells.py``)
with the profiler on, and keeps each trace, gzipped, as
``<directory>/<driver>.xplane.pb.gz`` with the run's result beside it
(``<driver>.result.json``); the directory is ``bench/tests/data`` unless
named.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]
    from bench import run as bench_run
    bench_run.setup_environment()
    import jax
    if jax.default_backend() != "tpu":
        print("no TPU; nothing recorded", file=sys.stderr)
        return 2
    import test_cells
    spec = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    cells = {"explore": test_cells.explore_cell,
             "layout": test_cells.layout_cell}
    for name, cell in cells.items():
        with tempfile.TemporaryDirectory() as tmp:
            res = cell(spec, trace=True, trace_dir=tmp, seconds=0.2)
            path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
            with open(path, "rb") as f, gzip.open(
                    os.path.join(out, f"{name}.xplane.pb.gz"),
                    "wb") as g:
                shutil.copyfileobj(f, g)
        with open(os.path.join(out, f"{name}.result.json"), "w") as f:
            json.dump(res, f, indent=1)
        print(name, json.dumps(res)[:2000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
