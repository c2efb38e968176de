"""The program's own spans (``runtime/spans.py``): device scopes on the
explore round and the layout step, the scope table read from optimised
HLO (``launch/hlo_analysis.scope_table``), host span records and the
retrace counter.  CPU, small N."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.largevis_default import LargeVisConfig
from repro.core import layout as layout_lib
from repro.core import sampler as sampler_lib
from repro.core.neighbor_explore import _explore_rows_round, neighbor_explore
from repro.launch import hlo_analysis
from repro.runtime import spans

EXPLORE_SCOPES = {"lv.explore.reverse", "lv.explore.gather",
                  "lv.explore.merge", "lv.explore.writeback"}
LAYOUT_SCOPES = {"lv.layout.sample", "lv.layout.update"}

# A fusion whose root carries a scope, a copy XLA added (no op_name) that
# reads it, a while loop whose body reads only its parameter, and an
# instruction whose op_name names no lv. scope.
HLO = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sin.1 = f32[8]{0} sine(%param_0.1), metadata={op_name="jit(f)/lv.explore.gather/sin"}
}

%body.1 (arg_tuple.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg_tuple.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%arg_tuple.1), index=1
  %copy.3 = f32[8]{0} copy(%get-tuple-element.2)
  %add.1 = s32[] add(%get-tuple-element.1, %get-tuple-element.1), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%add.1, %copy.3)
}

%cond.1 (arg_tuple.2: (s32[], f32[8])) -> pred[] {
  %arg_tuple.2 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%arg_tuple.2), index=0
  %constant.1 = s32[] constant(4)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %constant.1), direction=LT
}

ENTRY %main.1 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %sine_fusion = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation
  %copy.1 = f32[8]{1,0} copy(%sine_fusion)
  %constant.2 = s32[] constant(0)
  %tuple.2 = (s32[], f32[8]{0}) tuple(%constant.2, %copy.1)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/lv.layout.sample/while"}
  ROOT %get-tuple-element.4 = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_scope_table_on_hand_written_hlo():
    table = hlo_analysis.scope_table(HLO)
    assert table == {
        "sine_fusion": "lv.explore.gather",   # its fused root's scope
        "copy.1": "lv.explore.gather",        # no op_name: its operand's
        "while.1": None,                      # container
        "copy.3": "lv.layout.sample",         # reads only the loop state
        "add.1": "",                          # op_name with no lv. scope
        "compare.1": "lv.layout.sample",      # no op_name, in the condition
    }
    # fused computations run as their fusion, not as operations
    assert "sin.1" not in table


def test_computation_blocks_with_tuple_parameters():
    blocks = hlo_analysis._computation_blocks(HLO)
    assert {"fused_computation", "body.1", "cond.1", "main.1"} <= set(blocks)
    assert any(ln.startswith("%copy.3") for ln in blocks["body.1"])


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(5)
    n, k, d = 301, 8, 6
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, k)), jnp.int32)
    dist = jnp.sum((x[idx] - x[:, None, :]) ** 2, axis=-1)
    return x, idx, dist


@pytest.fixture(scope="module")
def samplers():
    rng = np.random.default_rng(3)
    n, k = 600, 8
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    return (n, sampler_lib.build_edge_sampler(idx, w),
            sampler_lib.build_negative_sampler(idx, w))


def _leaves(table):
    return {k: v for k, v in table.items() if v is not None}


def test_explore_program_carries_every_explore_scope(graph):
    x, idx, dist = graph
    neighbor_explore(x, idx, dist, rows=jnp.arange(64, dtype=jnp.int32))
    table = spans.scope_table("explore_rows_round")
    leaves = _leaves(table)
    assert EXPLORE_SCOPES <= set(leaves.values())
    unscoped = [k for k, v in leaves.items() if v == ""]
    assert len(unscoped) <= 6, unscoped
    assert None in table.values()           # the tile loop is a container


def test_layout_program_carries_every_layout_scope(samplers):
    n, es, ns = samplers
    cfg = LargeVisConfig(samples_per_node=60, steps_per_dispatch=16)
    layout_lib.run_layout(jax.random.key(0), es, ns, n, cfg)
    leaves = _leaves(spans.scope_table("layout_chunk"))
    assert LAYOUT_SCOPES <= set(leaves.values())
    unscoped = [k for k, v in leaves.items() if v == ""]
    assert len(unscoped) <= 8, unscoped


def test_noted_signature_lowers_to_the_called_program(graph):
    """What ``scope_table`` compiles is the module the call ran."""
    x, idx, dist = graph
    rows = jnp.arange(48, dtype=jnp.int32)
    key = jax.random.key(4)
    static = dict(sample=0, tile=48, r_cap=8)
    spans.note("test_rows_round", _explore_rows_round, x, idx, dist, rows,
               key, **static)
    (fn, tree, leaves), = spans._signatures["test_rows_round"].values()
    args, kwargs = jax.tree.unflatten(tree, leaves)
    assert fn.lower(*args, **kwargs).as_text() == \
        _explore_rows_round.lower(x, idx, dist, rows, key, **static).as_text()


def test_explore_call_appends_one_record(graph):
    x, idx, dist = graph
    before = len(spans.records("explore.call"))
    neighbor_explore(x, idx, dist, rows=jnp.arange(40, dtype=jnp.int32))
    recs = spans.records("explore.call")
    assert len(recs) == before + 1
    t0, t1, counts, retraces = recs[-1]
    assert counts == {"rows": 40} and t1 > t0 and retraces >= 0


def test_run_layout_records_one_dispatch_per_chunk(samplers):
    n, es, ns = samplers
    cfg = LargeVisConfig(samples_per_node=70, steps_per_dispatch=16)
    before = len(spans.records("layout.dispatch"))
    res = layout_lib.run_layout(jax.random.key(1), es, ns, n, cfg)
    recs = spans.records("layout.dispatch")[before:]
    assert len(recs) == -(-res.steps // 16)
    assert sum(c["steps"] for _, _, c, _ in recs) == res.steps
    assert recs[-1][2]["steps"] == (res.steps % 16 or 16)


def test_sampler_spans_close_once_tables_are_ready(monkeypatch):
    """The device builders each add one record, ``edges=`` and
    ``nodes=``, and wait for their tables inside the span; the host
    route records nothing."""
    rng = np.random.default_rng(5)
    n, k = 300, 7
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    waits = []
    real = jax.block_until_ready

    def spy(x):
        waits.append((len(spans.records("sampler.edge")),
                      len(spans.records("sampler.node"))))
        return real(x)

    e0 = len(spans.records("sampler.edge"))
    n0 = len(spans.records("sampler.node"))
    monkeypatch.setattr(jax, "block_until_ready", spy)
    sampler_lib.build_edge_sampler(idx, w)
    sampler_lib.build_negative_sampler(idx, w)
    assert waits == [(e0, n0), (e0 + 1, n0)]
    (t0, t1, counts, _), = spans.records("sampler.edge")[e0:]
    assert counts == {"edges": n * k} and t1 > t0
    (t0, t1, counts, _), = spans.records("sampler.node")[n0:]
    assert counts == {"nodes": n} and t1 > t0
    sampler_lib.build_edge_sampler(idx, w, impl="host")
    sampler_lib.build_negative_sampler(idx, w, impl="host")
    assert len(spans.records("sampler.edge")) == e0 + 1
    assert len(spans.records("sampler.node")) == n0 + 1


def test_dispatch_counts_the_slabs_of_y(monkeypatch):
    """``slabs=`` on ``lv.layout.dispatch`` is the edge-step kernel's row
    tiles, ``ceil(N / y_tile)``, and 1 untiled or off the kernel; a run
    tiled into slabs gives the bits of one slab."""
    from repro.configs.largevis_default import RoutingConfig
    from repro.kernels import ops
    rng = np.random.default_rng(6)
    n, k = 5000, 4
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    w = rng.uniform(0.5, 1.5, (n, k)).astype(np.float32)
    es = sampler_lib.build_edge_sampler(idx, w)
    ns = sampler_lib.build_negative_sampler(idx, w)

    def run(layout_step):
        cfg = LargeVisConfig(samples_per_node=2, steps_per_dispatch=2,
                             routing=RoutingConfig(layout_step=layout_step))
        before = len(spans.records("layout.dispatch"))
        y = layout_lib.run_layout(jax.random.key(2), es, ns, n, cfg).y
        recs = spans.records("layout.dispatch")[before:]
        return y, {c["slabs"] for _, _, c, _ in recs}

    one, slabs = run("fused")
    assert slabs == {1}
    assert run("auto")[1] == {1}                 # the oracle on the CPU
    monkeypatch.setattr(ops, "_FUSED_MAX_Y_BYTES", 2048 * 2 * 4)
    assert ops._fused_y_tile(n, 2) == 2048
    tiled, slabs = run("fused")
    assert slabs == {3}
    assert np.array_equal(np.asarray(tiled), np.asarray(one))


def test_monitored_run_records_a_sync_per_dispatch(samplers):
    """The watchdog's blocked dispatch time is the dispatch span's start
    to the sync span's end."""
    n, es, ns = samplers
    cfg = LargeVisConfig(samples_per_node=70, steps_per_dispatch=16)
    seen = []
    d0 = len(spans.records("layout.dispatch"))
    s0 = len(spans.records("layout.sync"))
    res = layout_lib.run_layout(jax.random.key(1), es, ns, n, cfg,
                                on_chunk=lambda t, steps, y: seen.append(t))
    dispatches = spans.records("layout.dispatch")[d0:]
    syncs = spans.records("layout.sync")[s0:]
    assert len(syncs) == len(dispatches) == len(seen)
    for (a0, a1, _, _), (b0, b1, _, _) in zip(dispatches, syncs):
        assert a0 <= a1 <= b0 <= b1
    assert isinstance(res.stragglers, list)


def test_retraces_count_a_new_shape_and_not_a_repeat(graph):
    x, idx, dist = graph
    rows = jnp.arange(37, dtype=jnp.int32)
    neighbor_explore(x, idx, dist, rows=rows)
    first = spans.records("explore.call")[-1][3]
    neighbor_explore(x, idx, dist, rows=rows)
    again = spans.records("explore.call")[-1][3]
    assert first >= 1 and again == 0


def test_span_records_are_bounded():
    for i in range(spans.MAX_RECORDS + 5):
        with spans.span("test.bounded", i=i):
            pass
    recs = spans.records("test.bounded")
    assert len(recs) == spans.MAX_RECORDS
    assert recs[-1][2] == {"i": spans.MAX_RECORDS + 4}


PROGRAM = """
import jax, jax.numpy as jnp
from repro.runtime import spans

def plain():
    def f(x):
        return jnp.sin(x) * 2.0
    return jax.jit(f)

def scoped():
    def f(x):
        with spans.scope("explore.gather"):
            return jnp.sin(x) * 2.0
    return jax.jit(f)

x = jnp.arange(8.0)
plain()(x).block_until_ready()          # the cache's entry, no lv. metadata
g = scoped()
spans.note("f", g, x)
g(x).block_until_ready()                # the same HLO but its metadata
print(sorted(set(spans.scope_table("f").values())))
"""


def test_scope_table_survives_a_cache_entry_without_metadata(tmp_path):
    """JAX's persistent cache keys a program without its metadata, so a
    call can run an executable compiled from the same program with no
    scopes; the scope table still reads the program's own."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", PROGRAM], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "'lv.explore.gather'" in out.stdout.splitlines()[-1], out.stdout


def test_a_call_inside_another_trace_notes_nothing(graph):
    x, idx, dist = graph
    rows = jnp.arange(24, dtype=jnp.int32)
    before = dict(spans._signatures["explore_rows_round"])
    out = jax.jit(lambda x, i, d, r: neighbor_explore(x, i, d, rows=r))(
        x, idx, dist, rows)
    assert out[0].shape == idx.shape
    assert spans._signatures["explore_rows_round"] == before
