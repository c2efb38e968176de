"""Each traffic mix end to end on the CPU at a tiny size, the faults that
``correct`` must catch, and the control that must come out not correct.

The command takes no size option, so the sizes are cut here.  These runs
skip the harness's look for a chip and drive the rest of a run.
"""
import copy
import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import load, tiny

from bench import control
from bench import run as bench_run
from bench.drivers import layout as layout_driver

EXPLORE = dict(N=2000, d=32)
# 20,074 steps of batch 1,000 (the batch is capped at N/2): the calls end
# in a 74-step dispatch, as a default fit of this size does
LAYOUT = dict(N=2000, d=16, samples_per_node=10_037)


def run_cell(spec, name, cfg, traffic, *, ops=None, seconds=0.5,
             trace=False, seed=2**33 + 11, trace_dir=None):
    cell = {w["name"]: w for w in spec["workloads"]}[name]
    return bench_run.run(cell, cfg, traffic, seed=seed, seconds=seconds,
                         trace=trace, spec=spec, t_start=time.perf_counter(),
                         ops=ops, trace_dir=trace_dir)


def explore_cell(spec, **kw):
    tr = dict(load("traffic", "explore_b512"), block_rows=128,
              recall_rows=64)
    return run_cell(spec, "mnist784-explore", tiny("mnist784", **EXPLORE),
                    tr, **kw)


def layout_cell(spec, **kw):
    tr = dict(load("traffic", "layout_tail"), chunks_per_call=2)
    return run_cell(spec, "mnist784-layout", tiny("mnist784", **LAYOUT), tr,
                    **kw)


def test_explore_mix_end_to_end(spec):
    res = explore_cell(spec)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert set(m) == {"graph_points_per_s", "graph_recall", "setup_s"}
    assert m["graph_points_per_s"]["value"] > 0
    assert 0.9 < m["graph_recall"]["value"] <= 1.0
    assert res["attempted"] >= 128 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_layout_mix_end_to_end(spec):
    res = layout_cell(spec)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"layout_samples_per_s", "setup_s"}
    assert res["attempted"] % 274 == 0 and res["attempted"] > 0
    assert res["checks"]["edge_tv"]["value"] < 1e-7


def test_traced_run_reports_per_layer_metrics_only(spec):
    """On the CPU no chip plane exists: the device metrics stay silent
    rather than read 0, and the line still says whether it is correct."""
    res = explore_cell(spec, trace=True)
    assert res["correct"]
    assert "graph_points_per_s" not in res["metrics"]
    assert "idle_share.graph" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


# ---------------------------------------------------------------------------
# faults planted in the timed path
# ---------------------------------------------------------------------------

def _explore_fault(kind):
    from repro.core.neighbor_explore import neighbor_explore

    def broken(x, idx, dist, *, rows, sample=0):
        if kind == "unchanged":
            return idx, dist
        if kind == "half":
            return neighbor_explore(x, idx, dist, rows=rows[: rows.shape[0] // 2],
                                    sample=sample)
        new_idx, new_dist = neighbor_explore(x, idx, dist, rows=rows,
                                             sample=sample)
        r = rows[0]
        other = (new_idx[r, 0] + 1) % x.shape[0]
        return new_idx.at[r, 0].set(other), new_dist
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_explore_fault_is_not_correct(spec, kind):
    res = explore_cell(spec, ops={"explore": _explore_fault(kind)})
    assert not res["correct"], res["checks"]


def _layout_fault(kind):
    from repro.core.layout import run_layout

    def broken(key, edge_s, neg_s, n, lv, *, y0, start_step):
        steps, batch, _ = layout_driver.schedule(lv, n)
        if kind == "unchanged":
            return types.SimpleNamespace(y=jnp.copy(y0),
                                         steps=steps - start_step)
        if kind == "half":
            # half of each batch left out, the rate doubled over the rest
            lv = dataclasses.replace(lv, batch_size=batch // 2,
                                     rho0=2 * lv.rho0,
                                     samples_per_node=lv.samples_per_node // 2)
            return run_layout(key, edge_s, neg_s, n, lv, y0=y0,
                              start_step=start_step)
        res = run_layout(key, edge_s, neg_s, n, lv, y0=y0,
                         start_step=start_step)
        res.y = res.y.at[7, 0].add(0.5)
        return res
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_layout_fault_is_not_correct(spec, kind):
    res = layout_cell(spec, ops={"layout": _layout_fault(kind)})
    assert not res["correct"], res["checks"]


def _f32_device_samplers(idx, w, *, impl, power):
    """The device alias build with the float32 pairing a TPU runs."""
    from repro.core import sampler
    del impl
    return (sampler._build_edge_sampler_device(idx, w, hi_dtype=jnp.float32),
            sampler._build_negative_sampler_device(idx, w, power=power,
                                                   hi_dtype=jnp.float32))


def test_layout_float32_pairing_tables_are_not_correct(spec):
    """The default builder on a TPU pairs in float32 (ROADMAP 2.1): its
    tables draw edges off their weights, which the tables check sees."""
    res = layout_cell(spec, ops={"samplers": _f32_device_samplers})
    assert not res["correct"], res["checks"]
    assert res["checks"]["edge_tv"]["value"] > 1e-5


# ---------------------------------------------------------------------------
# the control: the reference in bfloat16 in the program's place
# ---------------------------------------------------------------------------

def test_explore_control_is_not_correct(spec):
    res = explore_cell(spec, ops=control.CONTROLS["explore"])
    assert not res["correct"], res["checks"]
    assert res["checks"]["dist_err"]["value"] > res["checks"]["dist_err"]["limit"]


def test_layout_control_is_not_correct(spec):
    res = layout_cell(spec, ops=control.CONTROLS["layout"])
    assert not res["correct"], res["checks"]


def test_seed_gives_same_inputs():
    from bench import data
    cfg = tiny("mnist784", N=64, d=8)
    a = data.corpus(cfg, data.seed_key(2**33 + 3))[0]
    b = data.corpus(cfg, data.seed_key(2**33 + 3))[0]
    c = data.corpus(cfg, data.seed_key(3))[0]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert jax.random.key_data(data.seed_key(2**31 + 5)).shape == (2,)


def test_gaussian_mixture_corpus_from_the_seed():
    """The second generator a configuration may name: same seed, same
    corpus, in the clusters asked for."""
    from bench import data
    cfg = {"N": 300, "d": 8,
           "generator": {"name": "gaussian_mixture", "n_clusters": 4,
                         "sep": 7.0}}
    x, labels = data.corpus(cfg, data.seed_key(2**33 + 5))
    y, _ = data.corpus(cfg, data.seed_key(2**33 + 5))
    assert x.shape == (300, 8) and x.dtype == jnp.float32
    assert np.array_equal(np.asarray(x), np.asarray(y))
    assert set(np.unique(np.asarray(labels))) <= set(range(4))
