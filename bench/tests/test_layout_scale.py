"""What the layout cells at scale (``wikiword100-layout``,
``wikidoc100-layout``) add to the benchmark: the readers of the alias
build's spans and of the edge-step kernel's time per slab of y, on
synthetic records; both cells through the harness on the CPU at a tiny
size, the new configuration from a copy of the benchmark whose files are
left as they are; and the float32 pairing fault that the tables check
must catch."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
import types

import jax
import jax.numpy as jnp
import pytest
from conftest import ROOT, load, tiny

from bench import run as bench_run
from bench import scopes
from bench.run import load_module

SECONDS = 1e9
RECORDS = {
    "sampler.edge": [(0, 5 * SECONDS, {"edges": 90}, 3),
                     (10 * SECONDS, 12 * SECONDS, {"edges": 90}, 0)],
    "sampler.node": [(12 * SECONDS, 12.5 * SECONDS, {"nodes": 9}, 0)],
    "layout.dispatch": [(0, 5, {"steps": 4, "slabs": 3}, 9),
                        (10, 11, {"steps": 4, "slabs": 3}, 0),
                        (20, 23, {"steps": 2, "slabs": 3}, 0)],
}
TRACE = {"n_chips": 1, "ops": {}, "kernels": {"fused_edge_step": 0.06},
         "program_kernels": {"jit_layout_chunk": 0.06}}
RUN = {"counts": {"steps": 6}}
TINY = dict(N=3000, d=16, samples_per_node=2000)


@pytest.fixture
def program(monkeypatch):
    def use(records):
        monkeypatch.setattr(scopes, "program_spans", lambda: None if records
                            is None else types.SimpleNamespace(
                                records=lambda name: list(
                                    records.get(name, ()))))
    use(RECORDS)
    return use


def read(name, trace=TRACE, run=RUN):
    return load_module("metrics", name).read(trace, run)


def test_build_s_adds_the_newest_edge_and_node_records(program):
    assert read("sampler.build_s") == pytest.approx(2.5)


def test_kernel_ms_per_slab_divides_by_steps_and_slabs(program):
    assert read("layout.kernel_ms_per_slab") == pytest.approx(
        1e3 * 0.06 / (6 * 3))


def test_slabs_that_differ_in_the_window_raise(program):
    recs = dict(RECORDS, **{"layout.dispatch": [
        (0, 1, {"steps": 4, "slabs": 3}, 0),
        (2, 3, {"steps": 2, "slabs": 1}, 0)]})
    program(recs)
    with pytest.raises(ValueError, match="slabs"):
        read("layout.kernel_ms_per_slab")


@pytest.mark.parametrize("name", ["sampler.build_s",
                                  "layout.kernel_ms_per_slab"])
def test_readers_are_silent_on_a_program_without_the_spans(program, name):
    """A program older than these spans (no ``slabs`` count, no sampler
    records), none at all, or a trace without a chip: nothing, and no
    error."""
    program({"layout.dispatch": [(0, 1, {"steps": 6}, 0)]})
    assert read(name) is None
    program(None)
    assert read(name) is None
    program(RECORDS)
    assert read(name, dict(TRACE, n_chips=0)) is None


def run_cell(spec, name, config, *, trace=False, ops=None):
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    tr = dict(load("traffic", cell["traffic"]), chunks_per_call=2)
    return bench_run.run(cell, tiny(config, **TINY), tr, seed=2**33 + 19,
                         seconds=0.3, trace=trace, spec=spec,
                         t_start=time.perf_counter(), ops=ops)


@pytest.mark.parametrize("name,config", [
    ("wikiword100-layout", "wikiword100"), ("wikidoc100-layout",
                                            "wikidoc100")])
def test_layout_cells_end_to_end(spec, name, config):
    res = run_cell(spec, name, config)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"layout_samples_per_s", "setup_s"}
    traced = run_cell(spec, name, config, trace=True)
    assert traced["correct"], traced["checks"]
    assert "setup_s" not in traced["metrics"]


RUN_COPY = textwrap.dedent("""
    import json, sys, time
    root = sys.argv[1]
    sys.path[:0] = [root, sys.argv[2]]
    from bench import run
    spec = run.load_json(root + "/BENCHMARK.json")
    cell = [w for w in spec["workloads"] if w["name"] == "wikidoc100-layout"][0]
    cfg = run.load_json(root + "/bench/configs/wikidoc100.json")
    tr = dict(run.load_json(root + "/bench/traffic/layout_tail.json"),
              chunks_per_call=2)
    res = run.run(cell, cfg, tr, seed=7, seconds=0.2, trace=True,
                  spec=spec, t_start=time.perf_counter())
    print(json.dumps(res))
""")


def test_wikidoc100_copy_runs_on_the_files_it_adds(tmp_path):
    """A checkout of the benchmark with this configuration cut to a tiny
    size runs the cell, traced, and leaves the harness's files as they
    were: the configuration, the cells and the readers are new files and
    entries only."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    harness = ("run.py", "trace.py", "reference.py", "scopes.py", "common.py",
               "drivers/layout.py", "traffic/layout_tail.json")
    before = {p: (copy / "bench" / p).read_bytes() for p in harness}
    path = copy / "bench/configs/wikidoc100.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **TINY)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", RUN_COPY, str(copy), os.path.join(ROOT, "src")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    for p, content in before.items():
        assert (copy / "bench" / p).read_bytes() == content


def _f32_pairing(p):
    """Vose's pairing by prefix sums in float32: the float arithmetic a
    TPU once paired in, whose prefix sums lose small slots' deficits past
    E ~ 1e5."""
    p = jnp.maximum(p.astype(jnp.float32).reshape(-1), 0.0)
    n = p.shape[0]
    scaled = p * (n / jnp.sum(p))
    small = scaled < 1.0
    m = jnp.sum(small.astype(jnp.int32))
    dest = jnp.where(small, jnp.cumsum(small) - 1, m + jnp.cumsum(~small) - 1)
    order = jnp.zeros(n, jnp.int32).at[dest].set(jnp.arange(n, dtype=jnp.int32))
    ss = scaled[order]
    pos = jnp.arange(n)
    is_small = pos < m
    d = jnp.where(is_small, 1.0 - ss, 0.0)
    e = jnp.where(is_small, 0.0, ss - 1.0)
    dd, se = jnp.cumsum(d), jnp.cumsum(e)
    tgt = jnp.clip(jnp.searchsorted(se, dd), m, n - 1)
    prev = se - e
    hi = jnp.searchsorted(dd, prev, side="right") - 1
    beta = jnp.clip(prev - jnp.where(hi >= 0, dd[jnp.clip(hi, 0, n - 1)],
                                     0.0), 0.0, 1.0)
    thr = jnp.where(is_small, ss, 1.0 - beta)
    ali = jnp.where(is_small, order[tgt], order[jnp.clip(pos - 1, m, n - 1)])
    return (jnp.zeros(n).at[order].set(thr),
            jnp.zeros(n, jnp.int32).at[order].set(ali))


def _f32_samplers(idx, w, *, impl, power):
    from repro.core import sampler
    del impl
    idx, w = jnp.asarray(idx), jnp.asarray(w, jnp.float32)
    n, k = idx.shape
    thr, ali = jax.jit(_f32_pairing)(w)
    deg = jnp.sum(w, 1).at[idx.reshape(-1)].add(w.reshape(-1))
    nthr, nali = jax.jit(_f32_pairing)(jnp.maximum(deg, 1e-12) ** power)
    return (sampler.EdgeSampler(jnp.repeat(jnp.arange(n, dtype=jnp.int32), k),
                                idx.reshape(-1), thr, ali, n * k),
            sampler.NodeSampler(nthr, nali, n))


def test_float32_pairing_tables_are_not_correct(spec):
    """Tables paired in float32 (E = 4.5e5 here) draw edges off their
    weights, which the tables check sees."""
    res = run_cell(spec, "wikidoc100-layout", "wikidoc100",
                   ops={"samplers": _f32_samplers})
    assert not res["correct"], res["checks"]
    assert res["checks"]["edge_tv"]["value"] > 1e-5
