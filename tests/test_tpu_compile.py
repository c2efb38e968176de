"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler compiles for a described chip with no chip attached, so
these tests refuse, at no chip time, what the Pallas interpreter accepts
and Mosaic does not (unsupported lowerings, unaligned layouts, scoped VMEM
overruns).  They compile; nothing runs.  The topology is described inside
a fixture: only the worker that runs this file loads the TPU compiler.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import sampler, transform
from repro.kernels import knn_topk, ops
from repro.kernels.largevis_grad import largevis_grads
from repro.kernels.largevis_step import (DEFAULT_SCOPED_VMEM, ROWS,
                                        fused_edge_step, vmem_capacity,
                                        vmem_limit_bytes)

K, M, B = 150, 5, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topk_sqdist_brute_force_tile(spec):
    """One brute-force call of the 784-dim corpus: 4096 query rows
    against 70,000 points, K=150, the kernel's default tiles."""
    fn = functools.partial(knn_topk.topk_sqdist, k=K, interpret=False)
    txt = _compiled_text(lambda a, b: fn(a, b), spec((4096, 784)),
                         spec((70_000, 784)))
    assert "tpu_custom_call" in txt


def test_topk_sqdist_forest_window_tile(spec):
    """The forest fold's window block: 64 rows against their 192-row
    sorted neighborhood, seeded with the running state, deduplicated."""
    w = 64

    def fn(a, b, a_ids, b_ids, init_i, init_d):
        return knn_topk.topk_sqdist(
            a, b, K, a_ids=a_ids, b_ids=b_ids, init_ids=init_i,
            init_dists=init_d, dedup=True, bm=w, bn=3 * w, interpret=False)

    txt = _compiled_text(fn, spec((w, 784)), spec((3 * w, 784)),
                         spec((w,), jnp.int32), spec((3 * w,), jnp.int32),
                         spec((w, K), jnp.int32), spec((w, K)))
    assert "tpu_custom_call" in txt


def test_topk_sqdist_ring_step(spec):
    """One ring step of the 4-shard KNN at N=70k: a 17,500-row shard
    against the in-flight remote shard, bucket-masked by 8 trees' codes
    and seeded with the running state."""
    n_loc, trees = 17_500, 8

    def fn(a, b, a_ids, b_ids, ca, cb, init_i, init_d):
        return knn_topk.topk_sqdist(
            a, b, K, a_ids=a_ids, b_ids=b_ids, codes_a=ca, codes_b=cb,
            init_ids=init_i, init_dists=init_d, interpret=False)

    txt = _compiled_text(
        fn, spec((n_loc, 784)), spec((n_loc, 784)),
        spec((n_loc,), jnp.int32), spec((n_loc,), jnp.int32),
        spec((n_loc, trees), jnp.int32), spec((n_loc, trees), jnp.int32),
        spec((n_loc, K), jnp.int32), spec((n_loc, K)))
    assert "tpu_custom_call" in txt


def _edge_batch(spec, n, b=B):
    return (spec((n, 2)), spec((b,), jnp.int32), spec((b,), jnp.int32),
            spec((b, M), jnp.int32), spec((b, M)), spec(()))


def _budget_edge() -> int:
    """The largest N the VMEM budget leaves untiled at s=2."""
    return ops._fused_y_budget() // (4 * 2)


@pytest.mark.parametrize("n", [70_000, 1 << 20, 2_228_224, 2_837_395,
                               "budget_edge"])
def test_fused_edge_step_one_slab(spec, n):
    """Untiled: the whole planar embedding is one VMEM slab, up to the
    largest N the VMEM budget leaves untiled (3/4 of v5e's 128 MiB:
    12,582,912 rows at s=2).  2,228,224 rows is the wikidoc100
    configuration's N, 2,837,395 WikiDoc's published one."""
    n = _budget_edge() if n == "budget_edge" else n
    assert ops._fused_y_tile(n, 2) == 0
    fn = functools.partial(fused_edge_step, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, *_edge_batch(spec, n))


def test_fused_edge_step_tiled(spec):
    """N=16M at s=2 is past the budget: the kernel tiles y into slabs."""
    n = 16_000_000
    assert ops._fused_y_tile(_budget_edge() + 1, 2) > 0
    y_tile = ops._fused_y_tile(n, 2)
    assert 0 < y_tile < n
    fn = functools.partial(fused_edge_step, interpret=False, y_tile=y_tile)
    assert "tpu_custom_call" in _compiled_text(fn, *_edge_batch(spec, n))


def _scoped_vmem_asked(spec, n, s):
    """The scoped-VMEM size the edge step's lowering asks the compiler
    for, or None where it asks for none (the compiler's default)."""
    args = list(_edge_batch(spec, n))
    args[0] = spec((n, s))
    txt = jax.jit(functools.partial(fused_edge_step, interpret=False)).lower(
        *args).as_text()
    config = re.search(r'tpu_custom_call.*?backend_config = "(.*?)"', txt)
    config = json.loads(config.group(1).replace("\\22", '"'))
    scoped = config.get("scoped_memory_configs")
    return scoped[0]["size"] if scoped else None


@pytest.mark.parametrize("n,s", [(70_000, 2), (836_756, 2), (1 << 20, 2),
                                 (2_228_224, 2), (2_228_224, 3),
                                 ("budget_edge", 2)])
def test_fused_edge_step_vmem_limit(spec, n, s):
    """The kernel names no scoped-VMEM limit while its buffers fit the
    compiler's 16 MiB default, so the programs of those shapes are what
    they were; past it, it asks for the slab and the edge scratch, and
    never for more than the core's VMEM."""
    n = _budget_edge() if n == "budget_edge" else n
    rows = -(-n // ROWS) * ROWS
    buffers = 4 * (s * rows + (2 + M) * s * B)    # slab, edge scratch
    asked = _scoped_vmem_asked(spec, n, s)
    assert asked == vmem_limit_bytes(s, rows, M, B)
    if buffers + (1 << 20) <= DEFAULT_SCOPED_VMEM:
        assert asked is None
    else:
        assert buffers < asked <= vmem_capacity()


def test_vmem_limit_never_past_the_core():
    """A slab too large for the core asks for the core's VMEM, which the
    compiler then refuses, and never for more."""
    assert vmem_limit_bytes(2, 40_000_000, M, B) == vmem_capacity()
    assert vmem_limit_bytes(2, 1 << 20, M, B) is None


def test_fused_edge_step_frozen_serving_batch(spec):
    """The serving step: 256 slots appended to a frozen 70k corpus, with
    per-slot learning rates."""
    n, slots = 70_000, 256
    fn = functools.partial(fused_edge_step, interpret=False, n_frozen=n)
    args = list(_edge_batch(spec, n + slots, slots))
    args[-1] = spec((slots,))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_largevis_grads(spec):
    fn = functools.partial(largevis_grads, interpret=False)
    txt = _compiled_text(fn, spec((B, 2)), spec((B, 2)), spec((B, M, 2)),
                         spec((B, M)))
    assert "tpu_custom_call" in txt


def _wikidoc_rows() -> int:
    """N of the benchmark's ``wikidoc100`` configuration."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "configs", "wikidoc100.json")
    with open(path) as f:
        return json.load(f)["N"]


def _within_budget(fn, *args, **kw):
    """Compile an alias build; its temporaries stay within two flat (E,)
    arrays of 4 bytes beyond its outputs."""
    with jax.enable_x64(True):
        compiled = jax.jit(functools.partial(fn, **kw)).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    n, k = args[0].shape
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * 4 * n * k, f"{temp / 1e9:.2f} GB of temporaries"


@pytest.mark.parametrize("n", [836_756, "wikidoc100"])
def test_alias_build(spec, n):
    """The exact edge alias build at the layout cells' E: wikiword100's
    1.26e8 and wikidoc100's (64-bit integer sums, taken by the TPU)."""
    n = _wikidoc_rows() if n == "wikidoc100" else n
    _within_budget(sampler._build_edge_sampler_device,
                   spec((n, K), jnp.int32), spec((n, K)))


@pytest.mark.parametrize("n", [70_000, 836_756, "wikidoc100"])
def test_negative_sampler_build_70k(spec, n):
    """The noise-table build on the 70k x 150 graph and the layout cells'
    graphs: seconds.  With the graph flattened by ``reshape`` it took
    minutes to compile (the relayout of a 150-wide minor dimension; see
    ``core.flat``); scattered whole, the degrees held row-major copies of
    the graph."""
    n = _wikidoc_rows() if n == "wikidoc100" else n
    _within_budget(sampler._build_negative_sampler_device,
                   spec((n, K), jnp.int32), spec((n, K)), power=0.75)


@pytest.fixture(scope="module")
def fit_placements(topo):
    """Where a distributed fit's embedding could sit: on the first
    device (what ``run_layout_local_sgd`` returns), or left on the 4-chip
    mesh, replicated."""
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    return {"one_device": SingleDeviceSharding(topo.devices[0]),
            "mesh": NamedSharding(mesh, PartitionSpec())}


@pytest.mark.parametrize("placement", ["one_device", "mesh"])
def test_metric_distance_kernel_on_fit_placement(fit_placements, placement):
    """The 5-NN metric's distance kernel on a distributed fit's 70k
    embedding.  Left on the mesh it is refused: Mosaic kernels cannot be
    partitioned automatically, which is why the fit returns it on one
    device."""
    y = jax.ShapeDtypeStruct((70_000, 2), jnp.float32,
                             sharding=fit_placements[placement])
    fn = functools.partial(knn_topk.pairwise_sqdist, interpret=False)
    if placement == "mesh":
        with pytest.raises(NotImplementedError,
                           match="automatically partitioned"):
            _compiled_text(lambda y: fn(y[:1000], y[1000:]), y)
    else:
        assert "tpu_custom_call" in _compiled_text(
            lambda y: fn(y[:1000], y[1000:]), y)


def test_transform_into_distributed_fit(fit_placements, monkeypatch):
    """``transform``'s projection scan (the fused edge-step kernel, corpus
    rows frozen) over 256 queries appended to a 70k embedding placed as a
    distributed fit returns it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # compile, not interpret
    one = fit_placements["one_device"]
    n, q = 70_000, 256

    def at(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    neg = sampler.NodeSampler(threshold=at((n,)), alias=at((n,), jnp.int32),
                              n_nodes=n)
    txt = transform._project_scan.lower(
        at((n + q, 2)), jax.random.key(0), at((q, K)), at((q, K), jnp.int32),
        neg, n_negatives=M, steps=4, rho0=1.0, prob_fn="inv_quadratic",
        a=1.0, gamma=7.0, clip=5.0, layout_step="fused").compile().as_text()
    assert "tpu_custom_call" in txt
