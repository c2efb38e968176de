"""Numerical health guard, degraded-mode routing, and public-API input
validation (PR 8 tentpole part 2 + satellites).

The health/rollback tests drive real divergence through the fault
injector's ``nan`` payload corruption at the ``layout_chunk`` site —
the probe, rollback, lr backoff, and give-up paths all execute on the
actual chunked driver, not on mocks.  Degraded-mode tests monkeypatch
the underlying builder/engine to raise, asserting the demotion happens
once, warns once, and still produces a healthy result.
"""
import dataclasses
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.largevis_default import HealthConfig, LargeVisConfig
from repro.core import sampler as sampler_lib
from repro.core.layout import layout_health, run_layout
from repro.runtime.fault_tolerance import (DegradedModeWarning,
                                           DivergenceWarning, FaultInjector,
                                           LayoutDivergedError, Watchdog)

KEY = jax.random.key(3)
N = 400
CFG = LargeVisConfig(n_neighbors=8, n_trees=2, n_explore_iters=1, window=16,
                     perplexity=6.0, samples_per_node=200, batch_size=128,
                     steps_per_dispatch=20)


@pytest.fixture(scope="module")
def samplers():
    rng = np.random.default_rng(0)
    idx = rng.integers(0, N, (N, 8)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (N, 8)).astype(np.float32)
    return (sampler_lib.build_edge_sampler(idx, w),
            sampler_lib.build_negative_sampler(idx, w))


# ---------------------------------------------------------------------------
# health probe + rollback
# ---------------------------------------------------------------------------

def test_layout_health_probe():
    y = jnp.asarray([[1.0, -2.0], [3.0, 4.0]])
    nf, mx = layout_health(y)
    assert int(nf) == 0 and float(mx) == 4.0
    y_bad = y.at[0, 1].set(jnp.nan).at[1, 0].set(jnp.inf)
    nf, mx = layout_health(y_bad)
    assert int(nf) == 2
    assert float(mx) == 4.0      # non-finite entries can't mask the max


def test_divergence_rolls_back_with_backoff(samplers):
    es, ns = samplers
    cfg = dataclasses.replace(CFG, health=HealthConfig(max_rollbacks=3))
    fi = FaultInjector({"layout_chunk": {1: "nan"}})
    with pytest.warns(DivergenceWarning) as wlog:
        r = run_layout(KEY, es, ns, N, cfg, fault=fi)
    assert len([w for w in wlog
                if issubclass(w.category, DivergenceWarning)]) == 1
    assert r.rollbacks == 1 and r.rho0_scale == 0.5
    assert bool(np.isfinite(np.asarray(r.y)).all())
    # the full sample budget still ran despite the replayed chunk
    assert r.steps * 128 == r.edge_samples


def test_norm_blowup_triggers_rollback(samplers):
    es, ns = samplers
    cfg = dataclasses.replace(CFG, health=HealthConfig(max_abs=1e3))

    def blowup(y):
        return y.at[0, 0].set(1e9)     # finite, but way past max_abs

    fi = FaultInjector({"layout_chunk": {2: blowup}})
    with pytest.warns(DivergenceWarning):
        r = run_layout(KEY, es, ns, N, cfg, fault=fi)
    assert r.rollbacks == 1
    assert float(np.abs(np.asarray(r.y)).max()) < 1e3


def test_persistent_divergence_raises(samplers):
    es, ns = samplers
    cfg = dataclasses.replace(CFG, health=HealthConfig(max_rollbacks=2))
    fi = FaultInjector({"layout_chunk": {i: "nan" for i in range(50)}})
    with pytest.raises(LayoutDivergedError):
        with pytest.warns(DivergenceWarning):
            run_layout(KEY, es, ns, N, cfg, fault=fi)


def test_healthy_run_unaffected_by_health_guard(samplers):
    """The guard must be observation-only on healthy runs: same bits as
    an unguarded run (the probe never perturbs the trajectory)."""
    es, ns = samplers
    r0 = run_layout(KEY, es, ns, N, CFG)
    cfg = dataclasses.replace(CFG, health=HealthConfig())
    r1 = run_layout(KEY, es, ns, N, cfg)
    assert np.array_equal(np.asarray(r0.y), np.asarray(r1.y))
    assert r1.rollbacks == 0 and r1.rho0_scale == 1.0


# ---------------------------------------------------------------------------
# degraded-mode routing
# ---------------------------------------------------------------------------

def test_fused_step_demotes_to_split_on_backend_failure(
        samplers, monkeypatch):
    """A fused-kernel failure on the first chunk demotes the run to the
    split path with ONE DegradedModeWarning; the result is the split
    path's bits (fused and split differ in op fusion, not semantics)."""
    from repro.core import layout_engine
    es, ns = samplers
    cfg = dataclasses.replace(CFG, fused_step=False)
    want = np.asarray(run_layout(KEY, es, ns, N, cfg).y)

    real_chunk = layout_engine.layout_chunk
    calls = {"n": 0}

    def flaky_chunk(y, kr, step_ids, t_fracs, **kw):
        calls["n"] += 1
        if kw.get("layout_step") != "split":
            raise RuntimeError("XLA fused kernel unavailable")
        return real_chunk(y, kr, step_ids, t_fracs, **kw)

    monkeypatch.setattr(layout_engine, "layout_chunk", flaky_chunk)
    cfg_fused = dataclasses.replace(CFG, fused_step=True)
    with pytest.warns(DegradedModeWarning) as wlog:
        r = run_layout(KEY, es, ns, N, cfg_fused,
                       fault=FaultInjector())    # monitored, inert plan
    assert len([w for w in wlog
                if issubclass(w.category, DegradedModeWarning)]) == 1
    assert np.array_equal(np.asarray(r.y), want)


def test_sampler_build_demotes_to_host(monkeypatch):
    """A device sampler-build failure falls back to the numpy Vose
    oracle (bitwise-identical tables — pinned in test_sampler) instead
    of killing the fit."""
    lv = sys.modules["repro.core.largevis"]
    rng = np.random.default_rng(1)
    idx = rng.integers(0, N, (N, 8)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (N, 8)).astype(np.float32)

    real_build = sampler_lib.build_edge_sampler

    def flaky(idx, w, impl="auto", **kw):
        if impl != "host":
            raise RuntimeError("device build exploded")
        return real_build(idx, w, impl=impl, **kw)

    monkeypatch.setattr(lv.sampler_lib, "build_edge_sampler", flaky)
    with pytest.warns(DegradedModeWarning, match="host"):
        res, _ = lv.layout_graph(jnp.asarray(idx), jnp.asarray(w), KEY,
                                 cfg=CFG)
    assert bool(np.isfinite(np.asarray(res.y)).all())


def test_host_impl_failure_is_not_masked(monkeypatch):
    """When the user explicitly routed sampler_impl='host', a failure
    there is real and must propagate, not demote in a loop."""
    lv = sys.modules["repro.core.largevis"]

    def always_boom(*a, **kw):
        raise RuntimeError("host build exploded")

    monkeypatch.setattr(lv.sampler_lib, "build_edge_sampler", always_boom)
    cfg = dataclasses.replace(CFG, sampler_impl="host")
    idx = np.zeros((16, 2), np.int32)
    w = np.ones((16, 2), np.float32)
    with pytest.raises(RuntimeError, match="host build exploded"):
        lv.layout_graph(jnp.asarray(idx), jnp.asarray(w), KEY, cfg=cfg)


# ---------------------------------------------------------------------------
# watchdog wiring
# ---------------------------------------------------------------------------

def test_watchdog_flags_straggler_dispatch(samplers):
    """run_layout observes every blocked dispatch; a straggler chunk
    lands in result.stragglers (injected via a slow callable fault)."""
    import time as _time
    es, ns = samplers
    cfg = dataclasses.replace(CFG, samples_per_node=600)

    def stall(y):
        _time.sleep(0.05)
        return y

    # the fault site runs inside the timed window of each dispatch
    fi = FaultInjector({"layout_chunk": {30: stall}})
    r = run_layout(KEY, es, ns, N, cfg, fault=fi)
    assert any(dt >= 0.05 for _, dt, _ in r.stragglers)


def test_watchdog_observe_math():
    dog = Watchdog(threshold=3.0)
    for i in range(20):
        assert not dog.observe(i, 0.01)
    assert dog.observe(99, 0.5)
    assert dog.stragglers[-1][0] == 99


# ---------------------------------------------------------------------------
# public-API input validation (one regression test per rejected case)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    from repro import LargeVis
    x = np.random.default_rng(0).normal(size=(N, 16)).astype(np.float32)
    return LargeVis(cfg=CFG).fit(x, KEY)


def test_fit_rejects_empty():
    from repro import LargeVis
    with pytest.raises(ValueError, match="empty"):
        LargeVis(cfg=CFG).fit(np.zeros((0, 8), np.float32))


def test_fit_rejects_wrong_rank():
    from repro import LargeVis
    with pytest.raises(ValueError, match="2-D"):
        LargeVis(cfg=CFG).fit(np.zeros((64,), np.float32))


def test_fit_rejects_nonfinite_rows():
    from repro import LargeVis
    x = np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
    x[7, 3] = np.inf
    with pytest.raises(ValueError, match=r"NaN/Inf.*\[7\]"):
        LargeVis(cfg=CFG).fit(x)


def test_fit_rejects_zero_features():
    from repro import LargeVis
    with pytest.raises(ValueError, match="0 features"):
        LargeVis(cfg=CFG).fit(np.zeros((16, 0), np.float32))


def test_transform_rejects_dim_mismatch(fitted):
    with pytest.raises(ValueError, match="fitted corpus"):
        fitted.transform(np.zeros((4, 7), np.float32))


def test_transform_rejects_empty(fitted):
    with pytest.raises(ValueError, match="empty"):
        fitted.transform(np.zeros((0, 16), np.float32))


def test_transform_rejects_nonfinite(fitted):
    q = np.zeros((3, 16), np.float32)
    q[1] = np.nan
    with pytest.raises(ValueError, match=r"NaN/Inf.*\[1\]"):
        fitted.transform(q)


def test_insert_rejects_dim_mismatch(fitted):
    with pytest.raises(ValueError, match="fitted corpus"):
        fitted.insert(np.zeros((4, 9), np.float32))


def test_insert_rejects_nonfinite(fitted):
    q = np.full((2, 16), np.nan, np.float32)
    with pytest.raises(ValueError, match="NaN/Inf"):
        fitted.insert(q)


def test_insert_empty_is_noop(fitted):
    """Empty insert stays a valid no-op (pre-PR-8 contract), returning
    a (0, s) block — not a ValueError."""
    out = fitted.insert(np.zeros((0, 16), np.float32))
    assert out.shape == (0, 2)
