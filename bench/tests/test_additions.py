"""A later PR adds a configuration, a traffic mix, a metric and a cell as
new files and entries, and edits nothing: this test does so in a copy of
the benchmark and runs the new cell through the harness."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from conftest import ROOT

RUN = textwrap.dedent("""
    import json, sys, time
    root = sys.argv[1]
    sys.path[:0] = [root, sys.argv[2]]
    from bench import run
    spec = run.load_json(root + "/BENCHMARK.json")
    cell = [w for w in spec["workloads"] if w["name"] == "tiny-extra"][0]
    cfg = run.load_json(root + "/bench/configs/tiny_extra.json")
    tr = run.load_json(root + "/bench/traffic/explore_tiny_extra.json")
    res = run.run(cell, cfg, tr, seed=5, seconds=0.2, trace=True,
                  spec=spec, t_start=time.perf_counter())
    print(json.dumps(res))
""")


def test_new_config_traffic_metric_and_cell_need_no_edit(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: (copy / "bench" / p).read_bytes()
              for p in ("run.py", "trace.py", "reference.py")}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    cfg = json.loads((copy / "bench/configs/mnist784.json").read_text())
    cfg.update(name="tiny_extra", N=1500, d=24)
    (copy / "bench/configs/tiny_extra.json").write_text(json.dumps(cfg))
    (copy / "bench/traffic/explore_tiny_extra.json").write_text(json.dumps(
        {"driver": "explore", "block_rows": 96, "recall_rows": 32,
         "check_rows": 0}))
    (copy / "bench/metrics/explore_blocks.py").write_text(textwrap.dedent('''
        def read(trace, run):
            span = trace["spans"].get("bench.explore_block")
            return float(span["count"]) if span else None
    '''))
    spec["configs"].append({"name": "tiny_extra", "source": "test",
                            "file": "bench/configs/tiny_extra.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-extra", "config": "tiny_extra",
                              "traffic": "explore_tiny_extra", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "mnist784-explore" in m["workloads"]:
            m["workloads"].append("tiny-extra")
    spec["per_layer"].append({"name": "explore_blocks", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "neighbor explore",
                              "moves": "graph_points_per_s",
                              "workloads": ["tiny-extra"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", RUN, str(copy), os.path.join(ROOT, "src")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"]
    assert res["metrics"]["explore_blocks"]["value"] >= 1
    for p, content in before.items():
        assert (copy / "bench" / p).read_bytes() == content
