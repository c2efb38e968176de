"""Scan-fused layout engine (core/layout_engine.py) vs the per-step loop.

Covers: trajectory equivalence (the scanned driver must reproduce the
per-step Python loop bitwise at a fixed seed, including remainder chunks),
buffer donation (the chunk must alias y in -> y out, no doubled peak
buffer), the tile-padded kernel entry, and end-to-end layout quality
through the default engine path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.largevis_default import LargeVisConfig
from repro.core import layout as layout_lib
from repro.core import layout_engine
from repro.core import metrics
from repro.core import sampler as sampler_lib
from repro.core.largevis import largevis
from repro.data.synthetic import gaussian_mixture
from repro.launch.mesh import make_mesh

KEY = jax.random.key(0)


@pytest.fixture(scope="module")
def small_graph():
    """Synthetic 600-node directed KNN graph + samplers (stepping fixture)."""
    rng = np.random.default_rng(3)
    n, k = 600, 8
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    es = sampler_lib.build_edge_sampler(idx, w)
    ns = sampler_lib.build_negative_sampler(idx, w)
    return n, es, ns


def _run(n, es, ns, *, steps_per_dispatch, spn=120):
    cfg = LargeVisConfig(samples_per_node=spn, batch_size=4096,
                         steps_per_dispatch=steps_per_dispatch)
    return layout_lib.run_layout(KEY, es, ns, n, cfg)


def test_scan_matches_loop_bitwise(small_graph):
    """Same seed -> the scanned engine reproduces the per-step Python loop
    exactly: same keys, same t/T schedule, same step body."""
    n, es, ns = small_graph
    r_loop = _run(n, es, ns, steps_per_dispatch=1)      # per-step driver
    r_scan = _run(n, es, ns, steps_per_dispatch=64)
    assert r_loop.steps == r_scan.steps
    assert r_loop.edge_samples == r_scan.edge_samples
    a, b = np.asarray(r_loop.y), np.asarray(r_scan.y)
    assert np.array_equal(a, b), float(np.abs(a - b).max())


def test_scan_remainder_chunks_match(small_graph):
    """A chunk size that does not divide the step count (prime H) exercises
    the remainder dispatch and must not change the trajectory."""
    n, es, ns = small_graph
    r_a = _run(n, es, ns, steps_per_dispatch=64)
    r_b = _run(n, es, ns, steps_per_dispatch=37)
    assert np.array_equal(np.asarray(r_a.y), np.asarray(r_b.y))


def test_chunk_donates_y_buffer(small_graph):
    """Donation must survive into the compiled executable: y aliases in->out
    (no doubled peak layout buffer) and the donated input is invalidated."""
    n, es, ns = small_graph
    cfg = LargeVisConfig()
    kwargs = layout_lib._step_kwargs(es, ns, n, cfg, 300)
    y0 = jax.random.normal(KEY, (n, 2), jnp.float32)
    step_ids = jnp.arange(8, dtype=jnp.int32)
    t_fracs = jnp.linspace(0.0, 0.1, 8).astype(jnp.float32)
    lowered = layout_engine.layout_chunk.lower(
        y0, KEY, step_ids, t_fracs, **kwargs)
    compiled = lowered.compile()
    assert "input_output_alias" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= y0.nbytes, ma.alias_size_in_bytes
    y1 = layout_engine.layout_chunk(y0, KEY, step_ids, t_fracs, **kwargs)
    assert y0.is_deleted()          # the buffer really was donated
    assert jnp.isfinite(y1).all()


def test_chunked_kernel_pads_odd_batches():
    """The grads kernel pads B % tile != 0 to whole edge blocks (the
    collision cap produces arbitrary odd batches inside the scan)."""
    from repro.kernels import ref
    from repro.kernels.largevis_grad import largevis_grads
    k1, k2, k3 = jax.random.split(KEY, 3)
    b, m, s = 37, 5, 2
    yi = jax.random.normal(k1, (b, s), jnp.float32)
    yj = jax.random.normal(k2, (b, s), jnp.float32)
    yn = jax.random.normal(k3, (b, m, s), jnp.float32)
    mask = (jax.random.uniform(k1, (b, m)) > 0.2).astype(jnp.float32)
    got = largevis_grads(yi, yj, yn, mask, tile=16, interpret=True)
    want = ref.largevis_grads_ref(yi, yj, yn, neg_mask=mask)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_local_sgd_scan_body_runs(small_graph):
    """make_local_sgd_fns now scans the shared step body; a single-device
    mesh round trip must run and keep the layout finite."""
    n, es, ns = small_graph
    mesh = make_mesh((1,), ("data",))
    cfg = LargeVisConfig(sync_every=4, samples_per_node=32, batch_size=256)
    res = layout_lib.run_layout_local_sgd(KEY, es, ns, n, cfg, mesh)
    assert jnp.isfinite(res.y).all()
    assert res.steps >= cfg.sync_every


def test_engine_layout_quality():
    """Paper C4 via the engine path: KNN-classifier accuracy on the
    2000-point fixture stays >= 0.95 (PR-1 recorded 0.96 on this cfg)."""
    x, labels = gaussian_mixture(KEY, 2000, 32, 8)
    cfg = LargeVisConfig(n_neighbors=15, n_trees=4, n_explore_iters=2,
                         window=32, perplexity=10.0, samples_per_node=2000,
                         batch_size=4096)
    assert cfg.steps_per_dispatch > 1   # default path = scan engine
    res = largevis(x, KEY, cfg=cfg)
    acc = metrics.knn_classifier_accuracy(res.y, labels, k=5)
    assert acc >= 0.95, acc
    assert jnp.isfinite(res.y).all()
