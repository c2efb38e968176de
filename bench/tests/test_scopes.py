"""The readers of the program's own spans (``bench/scopes.py`` and the
eight ``bench/metrics`` readers that use it): on a reduced trace written
by hand with a scope table and host records, and on traces recorded on a
v5e by ``record_scoped.py`` (``data/scoped``), with the tables and
records their runs read."""
import gzip
import json
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import scopes, trace
from bench.run import load_module

DEVICE = ("explore.reverse_ms_per_call", "explore.gather_ms_per_row",
          "explore.merge_ms_per_row", "layout.sample_ms_per_step")
HOST = ("explore.host_ms_per_call", "layout.host_ms_per_dispatch",
        "explore.retraces_in_window", "layout.retraces_in_window")

# two programs' tables; ops of another program share no name with them
TABLES = {
    "explore_rows_round": {
        "fusion.1": "lv.explore.reverse", "sort.2": "lv.explore.reverse",
        "fusion.3": "lv.explore.gather", "copy.4": "lv.explore.gather",
        "fusion.5": "lv.explore.merge", "scatter.6": "lv.explore.writeback",
        "while.7": None, "copy.8": ""},
    "layout_chunk": {
        "fusion.11": "lv.layout.sample", "fused_edge_step.12":
        "lv.layout.update", "while.13": None, "add.14": ""},
}
OPS = {"fusion.1": 0.2, "sort.2": 0.1, "fusion.3": 0.5, "copy.4": 0.1,
       "fusion.5": 0.3, "scatter.6": 0.01, "while.7": 0.9, "copy.8": 0.02,
       "fusion.11": 0.4, "fused_edge_step.12": 1.0, "while.13": 1.5,
       "add.14": 0.001, "other.99": 7.0}
# (t0_ns, t1_ns, counts, retraces); the first is set-up's warm-up call
RECORDS = {
    "explore.call": [(0, 9_000_000, {"rows": 8}, 40),
                     (10_000_000, 12_000_000, {"rows": 8}, 0),
                     (20_000_000, 24_000_000, {"rows": 8}, 1)],
    "layout.dispatch": [(0, 5_000_000, {"steps": 4}, 30),
                        (10_000_000, 11_000_000, {"steps": 4}, 0),
                        (20_000_000, 23_000_000, {"steps": 2}, 0)],
}
COUNTS = {"calls": 2, "rows": 16, "steps": 6}


def fake_spans(tables=TABLES, records=RECORDS):
    return types.SimpleNamespace(
        scope_table=lambda program: dict(tables.get(program, {})),
        records=lambda name: list(records.get(name, ())))


@pytest.fixture
def program(monkeypatch):
    """The program's spans module, as a stand-in the test fills."""
    def use(spans):
        monkeypatch.setattr(scopes, "program_spans", lambda: spans)
    use(fake_spans())
    return use


def reduced(ops=OPS, n_chips=1):
    return {"n_chips": n_chips, "ops": dict(ops)}


def read(name, trace_=None, counts=COUNTS):
    return load_module("metrics", name).read(
        reduced() if trace_ is None else trace_, {"counts": counts})


def test_scope_sums_leave_out_containers_and_other_programs(program):
    assert read("explore.reverse_ms_per_call") == pytest.approx(
        1e3 * 0.3 / 2)
    assert read("explore.gather_ms_per_row") == pytest.approx(
        1e3 * 0.6 / 16)
    assert read("explore.merge_ms_per_row") == pytest.approx(1e3 * 0.3 / 16)
    assert read("layout.sample_ms_per_step") == pytest.approx(1e3 * 0.4 / 6)


def test_host_readers_take_the_window_tail(program):
    assert read("explore.host_ms_per_call") == pytest.approx(3.0)
    assert read("explore.retraces_in_window") == 1
    # the last records whose steps sum to the window's 6: 4 + 2
    assert read("layout.host_ms_per_dispatch") == pytest.approx(2.0)
    assert read("layout.retraces_in_window") == 0


def test_unscoped_share_over_the_limit_raises(program):
    ops = dict(OPS, **{"copy.8": 0.07})         # 0.07 / 1.28 > 5%
    with pytest.raises(ValueError, match="outside every scope"):
        read("explore.merge_ms_per_row", reduced(ops))
    ops = dict(OPS, **{"copy.8": 0.05})         # 0.05 / 1.26 < 5%
    assert read("explore.merge_ms_per_row", reduced(ops)) > 0


def test_a_missing_scope_or_program_raises(program):
    tables = {"explore_rows_round": {
        k: v for k, v in TABLES["explore_rows_round"].items()
        if v != "lv.explore.merge"}}
    program(fake_spans(tables=tables))
    with pytest.raises(LookupError, match="lv.explore.merge"):
        read("explore.merge_ms_per_row")
    with pytest.raises(LookupError, match="layout_chunk"):
        read("layout.sample_ms_per_step")
    with pytest.raises(LookupError, match="no leaf operation"):
        read("explore.gather_ms_per_row", reduced({"other.99": 1.0}))


def test_too_few_records_raise(program):
    with pytest.raises(LookupError, match="5 calls"):
        read("explore.host_ms_per_call", counts=dict(COUNTS, calls=5))
    with pytest.raises(LookupError, match="5 steps"):
        read("layout.host_ms_per_dispatch", counts=dict(COUNTS, steps=5))
    with pytest.raises(LookupError, match="11 steps"):
        read("layout.retraces_in_window", counts=dict(COUNTS, steps=11))


@pytest.mark.parametrize("name", DEVICE + HOST)
def test_readers_are_silent_without_spans_or_chip(program, name):
    assert read(name, reduced(n_chips=0)) is None
    program(None)                    # a program with no spans module
    assert read(name) is None


def test_program_spans_is_the_programs_module():
    from repro.runtime import spans
    assert scopes.program_spans() is spans


# ---------------------------------------------------------------------------
# traces recorded on a v5e by ``record_scoped.py`` (the sizes of
# ``test_cells.py``), with the scope tables, records and counts beside them
# ---------------------------------------------------------------------------

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scoped")
CELL_PROGRAM = {"explore": "explore_rows_round", "layout": "layout_chunk"}


def load_json(name: str):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def recorded(driver: str):
    with gzip.open(os.path.join(DATA, f"{driver}.xplane.pb.gz")) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return profile, trace.reduce_profile(profile)


@pytest.fixture
def recorded_program(program):
    tables = {p: load_json(f"{p}.scopes.json") for p in CELL_PROGRAM.values()}
    records = {name: [tuple(r) for r in recs]
               for name, recs in load_json("spans.json").items()}
    program(fake_spans(tables=tables, records=records))
    return tables


@pytest.mark.parametrize("driver", ["explore", "layout"])
def test_recorded_trace_reads_as_its_run_did(recorded_program, driver):
    _, red = recorded(driver)
    res = load_json(f"{driver}.result.json")
    counts = load_json("counts.json")[driver]
    names = [n for n in DEVICE + HOST if n.split(".")[0] == driver]
    for name in names:
        assert name in res["metrics"], name
        got = read(name, red, counts)
        assert got == pytest.approx(res["metrics"][name]["value"]), name
    for name in HOST:
        if name.endswith("retraces_in_window") and name in res["metrics"]:
            assert res["metrics"][name]["value"] == 0


@pytest.mark.parametrize("driver", ["explore", "layout"])
def test_recorded_program_is_split_by_scope(recorded_program, driver):
    """Every scope of the program ran, and the leaf ops outside every
    scope stay under the readers' limit."""
    _, red = recorded(driver)
    table = recorded_program[CELL_PROGRAM[driver]]
    leaves = {n: s for n, s in table.items() if s is not None}
    for scope in set(leaves.values()) - {""}:
        assert scopes.scope_seconds(red, table, scope) > 0, scope
    ran = {n: red["ops"][n] for n in leaves if n in red["ops"]}
    unscoped = sum(v for n, v in ran.items() if not leaves[n])
    assert unscoped <= scopes.UNSCOPED_LIMIT * sum(ran.values())


@pytest.mark.parametrize("driver,span", [("explore", "lv.explore.call"),
                                         ("layout", "lv.layout.dispatch")])
def test_recorded_host_spans_reach_the_profiler_trace(driver, span):
    """The program's host spans are in the trace, on the host plane, one
    event per record the window made."""
    profile, _ = recorded(driver)
    counts = load_json("counts.json")[driver]
    events = [ev for plane in profile.planes
              if plane.name.startswith("/host") for line in plane.lines
              for ev in line.events if ev.name == span]
    want = counts["calls"] if driver == "explore" else \
        len(scopes.last_records(
            [tuple(r) for r in load_json("spans.json")["layout.dispatch"]],
            steps=counts["steps"]))
    assert len(events) == want
