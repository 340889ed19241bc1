"""The readers and metrics PR 26 adds, rehearsed on the CPU.

Every metric of PR 26 is reported only from a run that has the
device's trace (PR 25's rehearsal, ``test_rehearsal.py``, listed every
metric of the repository's file until PR 37 and asserts the exact set
a CPU run prints).  So the end-to-end rehearsal here runs the same
tiny cells with a benchmark that lists PR 26's metrics alone and hands
the readers a stand-in for the device's trace; what needs a real one
(``xplane_scopes``, ``idle_under``) is checked as pure functions on
hand-made events, and the protobuf reading on a profile of the CPU.
"""

import glob
import json
import os
import shutil

import pytest
from rehearsal_benchmarks import REPO, WHICH
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    CHIPBENCH,
    TINY_CELLS,
    TINY_CONFIGS,
    TINY_TRAFFIC,
    harness,
    last_line,
    run_cell,
    write_json,
)

NEW = {
    "solve": {
        "cpu": {"yaml.parse_s", "yaml.build_s"},
        "chip": {"engine.dispatch_ms", "kernel.f2v_us",
                 "kernel.aggregate_us", "kernel.v2f_us",
                 "kernel.update_us", "kernel.unscoped_us",
                 "device.idle.untraced.solve"}},
    "serve": {
        "cpu": {"serve.http_read_ms", "serve.yaml_parse_ms",
                "serve.yaml_build_ms"},
        "chip": {"device.idle.sched_empty.serve",
                 "device.idle.untraced.serve"}},
}
ALL_NEW = sorted(set().union(*(v for kind in NEW.values()
                               for v in kind.values())))


@pytest.fixture
def tracing_bench(tmp_path):
    """A BENCHMARK.json of the two tiny cells that lists, and whose
    data directory holds, the metrics of PR 26 alone."""
    data = tmp_path / "data"
    for name in ALL_NEW:
        os.makedirs(data / "metrics", exist_ok=True)
        shutil.copy(os.path.join(CHIPBENCH, "metrics", f"{name}.json"),
                    data / "metrics")
    for name, config in TINY_CONFIGS.items():
        write_json(str(data / "configs" / f"{name}.json"), config)
    for name, mix in TINY_TRAFFIC.items():
        write_json(str(data / "traffic" / f"{name}.json"), mix)
    bench_path = tmp_path / "BENCHMARK.json"
    write_json(str(bench_path), {
        "configs": [{"name": n, "file": f"data/configs/{n}.json"}
                    for n in TINY_CONFIGS],
        "workloads": [
            {"name": cell, "config": cell.split(".")[0],
             "traffic": cell.split(".")[1], "chips": 1}
            for cell in TINY_CELLS.values()],
        "end_to_end": [],
        "per_layer": [{"name": name} for name in ALL_NEW],
    })
    return str(bench_path)


@pytest.fixture
def stand_in_device_trace(harness, monkeypatch):
    """The readers see a capture that has a device trace (an empty
    one): what a chip run has and a CPU rehearsal has not."""
    real = harness.per_layer_metrics

    def with_stand_in(data_dir, kind, capture):
        capture.setdefault("device_trace", {"busy_s": 0.0, "ops": []})
        return real(data_dir, kind, capture)

    monkeypatch.setattr(harness, "per_layer_metrics", with_stand_in)
    return harness


@pytest.mark.parametrize("kind", ["solve", "serve"])
def test_the_span_and_ring_metrics_read_on_the_rehearsal_cells(
        stand_in_device_trace, tracing_bench, capsys, monkeypatch, kind):
    # Every span's self time, not the five largest: which five they
    # are depends on how busy the machine is.
    monkeypatch.setattr(stand_in_device_trace, "BREAKDOWN_ENTRIES", 10**6)
    assert run_cell(stand_in_device_trace, tracing_bench, kind, 1) == 0
    line, _ = last_line(capsys)
    assert line["correct"] is True
    # Those that need the profiler's file read nothing and are left
    # out; none raises.
    assert set(line["metrics"]) == NEW[kind]["cpu"]
    for name, metric in line["metrics"].items():
        assert metric["value"] > 0, name
    # The traced block's own spans reach the breakdown, each with the
    # time it took itself.
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert gaps and all(name.startswith("host:") for name in gaps)
    if kind == "solve":
        assert max(gaps.get("host:engine_call", 0),
                   gaps.get("host:jit_compile", 0)) > 0
    else:
        assert gaps["host:http_request"] > 0
        assert gaps["host:yaml_parse"] > 0


@pytest.mark.parametrize("kind", ["solve", "serve"])
def test_without_a_device_trace_no_new_metric_is_reported(
        harness, tracing_bench, capsys, kind):
    assert run_cell(harness, tracing_bench, kind, 1) == 0
    line, _ = last_line(capsys)
    assert line["metrics"] == {}


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("name", ALL_NEW)
def test_a_new_metric_is_of_one_kind_and_reads_nothing_from_nothing(
        benchmarks, which, name):
    """Which cells it lists is test_rehearsal_cells.py's one rule: only
    cells of its kind that report what it moves, possibly fewer."""
    from chipbench import run

    kind = next(k for k in NEW if name in NEW[k]["cpu"] | NEW[k]["chip"])
    with open(os.path.join(benchmarks[which][1], "metrics",
                           f"{name}.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["kinds"] == [kind]
    reader = run.module_by_name("readers", spec["reader"], name)
    # As on the parent commit, or off the chip: nothing, no error.
    assert reader.read({}, **spec["args"]) is None
    assert reader.read({"spans": "/nonexistent/spans.json",
                        "values": {"cycles": 10}}, **spec["args"]) is None


# --------------------------------------------------------------------- #
# ring


def test_ring_reads_the_last_span_of_each_name():
    from chipbench.readers import ring

    events = [
        {"name": "yaml_parse", "ph": "X", "dur": 10.0},
        {"name": "yaml_build", "ph": "X", "dur": 1.0},
        {"name": "yaml_parse", "ph": "i"},
        {"name": "yaml_parse", "ph": "X", "dur": 30.0},
    ]
    assert ring.last_durations(events, ["yaml_parse"]) == {
        "yaml_parse": 30.0}
    assert ring.last_durations(events, ["absent"]) == {}


@pytest.mark.parametrize("ring_on", [True, False])
def test_ring_reads_the_programs_own_ring(ring_on):
    from chipbench.readers import ring
    from pydcop_tpu.dcop.yamldcop import load_dcop_from_file
    from pydcop_tpu.observability.flight import FlightRecorder
    from pydcop_tpu.observability.trace import tracer

    previous = tracer.flight
    tracer.set_flight(FlightRecorder(events=16) if ring_on else None)
    try:
        load_dcop_from_file(os.path.join(
            REPO, "tests", "instances", "coloring_12_3.yml"))
        capture = {"device_trace": {"busy_s": 0.0}}
        both = ring.read(capture, ["yaml_parse", "yaml_build"], scale=1e-6)
        parse = ring.read(capture, ["yaml_parse"], scale=1e-6)
        absent = ring.read(capture, ["yaml_parse", "absent"])
    finally:
        tracer.set_flight(previous)
    if ring_on:
        assert 0 < parse < both
        assert absent is None
    else:
        assert both is None and parse is None


# --------------------------------------------------------------------- #
# xplane_scopes: hand-made events


LOOP = ("%while.3 = (f32[8,3]{1,0}) while((f32[8,3]{1,0}) %tuple.5), "
        "condition=%cond, body=%body")
F2V = "%fusion.48 = f32[8,3]{1,0} fusion(s32[16]{0} %g), kind=kLoop"
COPY = "%copy.96 = f32[8,3]{0,1} copy(f32[8,3]{1,0} %gte)"
SELECT = "%fusion.2 = s32[8]{0} fusion(f32[8,3]{1,0} %b), kind=kLoop"
OP_NAMES = {
    "jit_maxsum_solve(7)": {
        "fusion.48": "jit(maxsum_solve)/while/body/maxsum/f2v/reduce_min",
        "fusion.2": "jit(maxsum_solve)/maxsum/select/argmin",
        "while.3": "jit(maxsum_solve)/while",
    },
    # Another program whose instruction of the same name is another
    # phase: the enclosing module decides.
    "jit_other(9)": {"fusion.48": "jit(other)/maxsum/update/select_n"},
}


def test_scope_of_and_instruction_name():
    from chipbench.readers import xplane_scopes as xs

    assert xs.scope_of("jit(f)/while/body/maxsum/f2v/sub") == "f2v"
    assert xs.scope_of("jit(f)/vmap(maxsum/select)/reduce") == "select"
    # Innermost wins.
    assert xs.scope_of("maxsum/select/x/maxsum/aggregate/add") == \
        "aggregate"
    assert xs.scope_of("jit(f)/while/body/add") is None
    assert xs.scope_of(None) is None and xs.scope_of("") is None
    assert xs.instruction_name(F2V) == "fusion.48"
    assert xs.instruction_name("%copy-start.5 = (f32[3]) "
                               "copy-start(%x)") == "copy-start.5"


def test_scope_totals_are_self_times_grouped_by_scope_and_module():
    from chipbench.readers import xplane_scopes as xs

    modules = [("jit_maxsum_solve(7)", 0, 200),
               ("jit_other(9)", 300, 100)]
    ops = [
        (LOOP, 0, 150),         # nested while: self = 150 - 60 - 20
        (F2V, 10, 30), (F2V, 50, 30),
        (COPY, 100, 20),        # no op_name at all: unscoped
        (SELECT, 160, 10),
        (F2V, 310, 40),         # the other program's fusion.48
        (COPY, 500, 5),         # under no module's run: unscoped
    ]
    totals = xs.scope_totals(ops, modules, OP_NAMES)
    assert totals == {"f2v": 60, None: 70 + 20 + 5, "select": 10,
                      "update": 40}
    # The scopes add up to the device's busy time.
    from chipbench.readers import xplane

    assert sum(totals.values()) == xplane.busy_ns(ops)
    grouped = xs.by_module(ops, modules)
    assert [len(grouped[m]) for m in (
        "jit_maxsum_solve(7)", "jit_other(9)", None)] == [5, 1, 1]


# --------------------------------------------------------------------- #
# idle_under: hand-made events


def _annotated():
    """Device busy 100-200 and 600-700 inside two engine_call spans;
    block 0-1000 under two solves on one thread, a compile_graph
    inside the first, and a second thread's span over one gap."""
    ops = [("%a = f32[] add()", 100, 100), ("%b = f32[] add()", 600, 100)]
    annotations = [
        ("solve", 0, 450), ("compile_graph", 10, 60),
        ("engine_call", 80, 150),
        ("solve", 500, 500), ("engine_call", 560, 200),
        ("other_thread", 440, 80),   # covers the gap 450-500
    ]
    return ops, annotations


def test_idle_time_is_attributed_to_the_spans_open_over_it():
    from chipbench.readers import idle_under as iu

    ops, annotations = _annotated()
    # Idle: 0-100, 200-600, 700-1000 = 800 ns.
    value, why = iu.attribute(ops, annotations, "ms_per",
                              names=["engine_call", "jit_compile"],
                              per="solve")
    # Under engine_call: 80-100, 200-230, 560-600, 700-760 = 150 ns
    # over two solves.
    assert why is None and value == pytest.approx(75 / 1e6)
    value, _ = iu.attribute(ops, annotations, "share",
                            names=["compile_graph"])
    assert value == pytest.approx(100.0 * 60 / 800)
    # A gap under two annotations (two threads) counts once.
    value, _ = iu.attribute(ops, annotations, "share",
                            names=["solve", "other_thread"])
    assert value == pytest.approx(100.0)
    value, _ = iu.attribute(ops, annotations, "complement")
    assert value == pytest.approx(0.0)
    # Without the second thread the gap between the solves is under
    # no annotation.
    value, _ = iu.attribute(ops, annotations[:-1], "complement")
    assert value == pytest.approx(100.0 * 50 / 800)
    value, why = iu.attribute(ops, annotations, "ms_per",
                              names=["solve"], per="absent")
    assert value is None and "absent" in why


@pytest.mark.parametrize("case", ["misaligned", "no_annotation",
                                  "no_device_work", "pipelined"])
def test_the_clock_is_checked_before_it_is_trusted(case):
    from chipbench.readers import idle_under as iu

    ops, annotations = _annotated()
    if case == "misaligned":
        # The device's clock runs 300 ns ahead of the host's: its
        # busy time falls outside the dispatch spans.
        ops = [(name, start + 300, dur) for name, start, dur in ops]
        value, why = iu.attribute(ops, annotations, "complement")
        assert value is None and "clocks do not agree" in why
    elif case == "no_annotation":
        value, why = iu.attribute(ops, [], "complement")
        assert value is None and "no pydcop: annotation" in why
    elif case == "no_device_work":
        value, why = iu.attribute([], annotations, "complement")
        assert value is None and "no device operation" in why
    else:
        # A pipelined serve dispatch is launched inside the flush,
        # before its serve_dispatch span opens: the flush anchors it.
        annotations = [("sched_idle", 0, 90), ("sched_flush", 90, 200),
                       ("serve_dispatch", 180, 100),
                       ("sched_idle", 290, 710)]
        ops = [("%a = f32[] add()", 100, 100)]
        value, why = iu.attribute(ops, annotations, "share",
                                  names=["sched_idle"])
        assert why is None
        assert value == pytest.approx(100.0 * (90 + 710) / 900)


def test_interval_helpers():
    from chipbench.readers import idle_under as iu

    assert iu.merge([(5, 9), (0, 3), (2, 4), (7, 7)]) == [(0, 4), (5, 9)]
    assert iu.intersect([(0, 4), (5, 9)], [(3, 6), (8, 12)]) == [
        (3, 4), (5, 6), (8, 9)]
    assert iu.complement([(2, 3), (5, 20)], (0, 10)) == [(0, 2), (3, 5)]
    assert iu.complement([], (0, 10)) == [(0, 10)]
    assert iu.length([(0, 4), (5, 9)]) == 8


# --------------------------------------------------------------------- #
# xspace: the protobuf fields, hand-encoded and from a real profile


def _varint(value):
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_instruction_op_names_from_hand_encoded_protobuf():
    from chipbench.readers import xspace

    def instruction(name, op_name):
        meta = _field(2, op_name) if op_name else b""
        return _field(1, name) + _field(2, "fusion") + (
            _field(7, meta) if op_name else b"")

    computation = _field(1, "body") + _field(
        2, instruction("fusion.48", "jit(f)/while/body/maxsum/f2v/sub")
    ) + _field(2, instruction("copy.96", None))
    hlo_proto = _field(1, _field(1, "jit_f") + _field(3, computation))
    event_metadata = (_field(1, 7) + _field(2, "jit_f(7)")
                      + _field(5, _field(1, 1) + _field(6, hlo_proto)))
    metadata_plane = _field(2, xspace.METADATA_PLANE) + _field(
        4, _field(1, 7) + _field(2, event_metadata))
    other_plane = _field(2, "/host:CPU") + _field(
        4, _field(1, 1) + _field(2, _field(2, "ignored")))
    raw = _field(1, other_plane) + _field(1, metadata_plane)
    assert xspace.instruction_op_names(raw) == {
        "jit_f(7)": {"fusion.48": "jit(f)/while/body/maxsum/f2v/sub"}}
    assert list(xspace.fields(_field(3, 300) + _field(4, "ab"))) == [
        (3, 300), (4, b"ab")]


def test_a_real_profile_holds_the_scopes_and_the_annotations(tmp_path):
    """On the CPU there is no device plane, but the profiler stores
    the program's HLO and the bridge's annotations the same way."""
    import jax

    from chipbench.readers import xplane_scopes, xspace
    from pydcop_tpu import api
    from pydcop_tpu.observability.trace import tracer

    from chipbench import lib

    dcop = lib.generate({"family": "graph_coloring", "variables": 16,
                         "colors": 3, "graph": "grid", "soft": True}, 1)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path / "profile"),
                            profiler_options=options):
        tracer.enable()
        try:
            api.solve(dcop, "maxsum", max_cycles=20)
        finally:
            tracer.disable()
            tracer.clear()
    capture = {"spans": str(tmp_path / "spans.json")}
    path = xspace.profile_path(capture)
    assert path in glob.glob(str(tmp_path / "profile" / "**" / "*.pb"),
                             recursive=True)
    trace = xspace.load(path)
    assert trace["ops"] == [] and trace["modules"] == []
    names = {name for name, _, _ in trace["annotations"]}
    assert {"solve", "compile_graph"} <= names
    assert names & {"jit_compile", "engine_call"}
    modules = [m for m in trace["op_names"] if "maxsum_solve" in m]
    assert modules
    scopes = {xplane_scopes.scope_of(op_name) for module in modules
              for op_name in trace["op_names"][module].values()}
    assert {"f2v", "aggregate", "v2f", "update", "select"} <= scopes
    # No device plane: the device readers read nothing.
    assert xplane_scopes.read(dict(capture, values={"cycles": 20}),
                              scope="f2v", per="cycles") is None
    from chipbench.readers import idle_under

    assert idle_under.read(capture, "complement") is None
