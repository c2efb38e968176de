"""The reduction of a profiler trace to what the metrics read: on a
small trace written out by hand in the shape of a TPU profile (host
spans, a chip plane with its ``XLA Modules`` and ``XLA Ops`` lines), and
on the traces recorded on a v5e in ``data/``."""
import gzip
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import trace

# One host thread with the window and two benchmark spans, one chip with
# two programs and four operations (one a Pallas kernel), times in ns:
#   window 1000..11000; span a 1000..5000; span b 6000..11000
#   ops: 500..1500 (clipped to 1000..1500), 1200..2000 (overlaps),
#        3000..4000 (the kernel), 7000..12000 (clipped to 7000..11000)
SYNTHETIC = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.a" } }
  event_metadata { key: 3 value { id: 3 name: "bench.b" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 3600000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1200000 duration_ps: 800000 }
    events { metadata_id: 5 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 7000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_layout_chunk(17)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_other(3)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %custom-call.3), kind=kLoop, calls=%fused_computation.1" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %param.1), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%fused_edge_step.7 = f32[2,8,128]{2,1,0} custom-call(s32[7,16]{1,0} %a, f32[2,8,128]{2,1,0} %b), custom_call_target=\\"tpu_custom_call\\", output_to_operand_aliasing={{}: (1, {})}" } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %custom-call.3), kind=kLoop, calls=%fused_computation.1" } }
}
"""
# As a TPU profile has them: device ops named by their HLO text, with no
# stat naming their program; a fusion that reads a custom call's result
# is no kernel.


@pytest.fixture(scope="module")
def synthetic():
    return trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_window_and_busy_union(synthetic):
    assert synthetic["window_s"] == pytest.approx(10e-6)
    # busy: 1000..2000 (two ops merged), 3000..4000, 7000..11000
    assert synthetic["busy_s"] == pytest.approx(6e-6)
    assert trace.idle_share(synthetic) == pytest.approx(40.0)


def test_programs_by_stable_name_and_clipped(synthetic):
    assert synthetic["programs"] == pytest.approx(
        {"jit_layout_chunk": 3.1e-6, "jit_other": 4e-6})


def test_ops_and_kernels(synthetic):
    assert synthetic["ops"] == pytest.approx(
        {"fusion.1": 4.5e-6, "fusion.2": 0.8e-6, "fused_edge_step.7": 1e-6})
    assert synthetic["kernels"] == pytest.approx({"fused_edge_step": 1e-6})
    assert synthetic["program_kernels"] == pytest.approx(
        {"jit_layout_chunk": 1e-6})
    assert synthetic["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_by_open_span(synthetic):
    # idle: 2000..3000 and 4000..5000 in span a, 5000..6000 in no span,
    # 6000..7000 in span b
    assert dict(synthetic["idle_gaps"]) == pytest.approx(
        {"bench.a": 2e-6, "window": 1e-6, "bench.b": 1e-6})
    assert synthetic["spans"]["bench.b"] == {"count": 1, "total_s": 5e-6}


def test_no_chip_plane_reads_nothing():
    red = trace.reduce_profile(ProfileData.from_text_proto(
        SYNTHETIC.split("planes {\n  id: 2")[0]))
    assert red["n_chips"] == 0 and trace.idle_share(red) is None


def test_program_and_kernel_lookups(synthetic):
    assert trace.program_seconds(synthetic, "layout_chunk") == \
        pytest.approx(3.1e-6)
    assert trace.program_seconds(synthetic, "other") == pytest.approx(4e-6)
    assert trace.kernel_seconds(synthetic, "layout_chunk") == \
        pytest.approx(1e-6)
    assert trace.kernel_seconds(synthetic) == pytest.approx(1e-6)


def test_a_name_missing_from_a_chip_trace_is_an_error(synthetic):
    with pytest.raises(LookupError, match="absent"):
        trace.program_seconds(synthetic, "absent")
    with pytest.raises(LookupError, match="prefill"):
        trace.kernel_seconds(synthetic, "prefill")


def test_a_trace_without_a_chip_reads_nothing():
    red = trace.reduce_profile(ProfileData.from_text_proto(
        SYNTHETIC.split("planes {\n  id: 2")[0]))
    assert trace.program_seconds(red, "layout_chunk") is None
    assert trace.kernel_seconds(red) is None


def test_readers_on_synthetic(synthetic):
    from bench import counts
    from bench.run import load_module
    run = {"peak": counts.peaks("TPU v5 lite"),
           "counts": {"steps": 1, "s": 2, "batch": 8, "negatives": 5}}
    share = load_module("metrics", "edge_step_roofline").read(synthetic, run)
    want = counts.edge_step_bytes(s=2, batch=8, negatives=5)
    assert share == pytest.approx(100 * want / 819e9 / 1e-6)
    assert load_module("metrics", "layout.nonkernel_ms_per_step").read(
        synthetic, run) == pytest.approx(1e3 * 2.1e-6)
    assert load_module("metrics", "idle_share.layout").read(
        synthetic, run) == pytest.approx(40.0)


# ---------------------------------------------------------------------------
# traces recorded on a v5e by ``record_traces.py`` (the sizes of
# ``test_cells.py``), with the result line each run printed
# ---------------------------------------------------------------------------

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the counts those runs gave the readers
RECORDED_COUNTS = {
    "explore": {"calls": 2, "rows": 256, "n": 2000, "k": 150, "d": 32},
    "layout": {"steps": 274, "s": 2, "batch": 1000, "negatives": 5},
}


def recorded(driver: str):
    with gzip.open(os.path.join(DATA, f"{driver}.xplane.pb.gz")) as f:
        red = trace.reduce_profile(ProfileData.from_serialized_xspace(
            f.read()))
    with open(os.path.join(DATA, f"{driver}.result.json")) as f:
        return red, json.load(f)


@pytest.mark.parametrize("driver", ["explore", "layout"])
def test_recorded_trace_reads_as_its_run_did(driver):
    from bench import counts
    from bench.run import load_module
    red, res = recorded(driver)
    assert red["n_chips"] == 1
    assert red["busy_s"] == pytest.approx(res["device"]["busy_s"])
    assert red["window_s"] == pytest.approx(res["device"]["window_s"])
    run = {"peak": counts.peaks(res["device"]["kind"]),
           "counts": RECORDED_COUNTS[driver]}
    for name, metric in res["metrics"].items():
        got = load_module("metrics", name).read(red, run)
        assert got == pytest.approx(metric["value"]), name


def test_recorded_traces_name_programs_and_kernels():
    red, _ = recorded("explore")
    assert trace.program_seconds(red, "explore_rows_round") > 0
    assert red["kernels"] == {}                 # explore has no Pallas kernel
    red, _ = recorded("layout")
    assert set(red["kernels"]) == {"fused_edge_step"}
    assert set(red["program_kernels"]) == {"jit_layout_chunk"}
    assert 0 < trace.kernel_seconds(red, "layout_chunk") < \
        trace.program_seconds(red, "layout_chunk")
