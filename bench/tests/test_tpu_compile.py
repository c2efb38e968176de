"""Ahead-of-time compiles of each cell's window program for a TPU v5e, at
the cell's real shapes.

The TPU compiler compiles for a described chip with no chip attached, so
a program that does not fit the chip's memory, or a kernel that asks for
more VMEM than it may use, is refused here at no chip time.  Nothing
runs.  The topology is described inside a fixture: only the worker that
runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from conftest import load
from jax.sharding import SingleDeviceSharding

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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


@pytest.fixture
def on_tpu(monkeypatch):
    """The ops layer routes to the Pallas kernels as on the chip."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM, f"{total / 1e9:.2f} GB does not fit one chip"
    return total


@pytest.mark.parametrize("config,traffic", [("mnist784", "explore_b512"),
                                            ("wikiword100", "explore_b2048")])
def test_explore_window_program(spec, config, traffic):
    """The rows entry of neighbor_explore at the cell's N, d and block."""
    from repro.core.neighbor_explore import neighbor_explore
    cfg, tr = load("configs", config), load("traffic", traffic)
    n, d, k, b = cfg["N"], cfg["d"], cfg["n_neighbors"], tr["block_rows"]

    def window_call(x, idx, dist, rows):
        return neighbor_explore(x, idx, dist, rows=rows)

    compiled = jax.jit(window_call).lower(
        spec((n, d)), spec((n, k), jnp.int32), spec((n, k)),
        spec((b,), jnp.int32)).compile()
    _fits(compiled)


@pytest.mark.parametrize("config,traffic", [("mnist784", "layout_tail")])
def test_layout_window_programs(spec, on_tpu, config, traffic):
    """The scanned dispatches of a window call of run_layout (a whole one
    of 100 steps and the shorter one that ends the schedule, batch 4096)
    with the fused edge-step kernel, at the cell's N and E = N*K."""
    from bench.common import program_config
    from bench.drivers import layout as layout_driver
    from repro.core import layout_engine
    from repro.core.sampler import EdgeSampler, NodeSampler
    cfg = load("configs", config)
    lv = program_config(cfg)
    n, k = cfg["N"], cfg["n_neighbors"]
    e = n * k
    steps, batch, h = layout_driver.schedule(lv, n)
    assert (batch, h) == (4096, 100) and steps % h
    edge_s = EdgeSampler(spec((e,), jnp.int32), spec((e,), jnp.int32),
                         spec((e,)), spec((e,), jnp.int32), e)
    neg_s = NodeSampler(spec((n,)), spec((n,), jnp.int32), n)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=spec(()).sharding)
    for chunk in (h, steps % h):
        lowered = layout_engine.layout_chunk.lower(
            spec((n, cfg["out_dim"])), key, spec((chunk,), jnp.int32),
            spec((chunk,)), edge_sampler=edge_s, neg_sampler=neg_s,
            n_negatives=cfg["n_negatives"], n_nodes=n, gamma=cfg["gamma"],
            rho0=cfg["rho0"], batch=batch, layout_step="auto")
        compiled = lowered.compile()
        assert "tpu_custom_call" in compiled.as_text()
        _fits(compiled)
