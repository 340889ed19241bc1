"""Spans where the time is (ISSUE 26): the profiler bridge, the YAML
load, the HTTP front end, the scheduler's wait, what a first dispatch
did, and the superstep's named phases.

CPU only, no wall-clock assertions: names, nesting, identifiers and
counts.  Cases are parametrised by span name / layout so that each
counts.
"""

import contextlib
import glob
import json
import os
import re
import urllib.request

import jax
import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.dcop.yamldcop import dcop_yaml, load_dcop
from pydcop_tpu.observability.trace import (
    ANNOTATION_PREFIX,
    NOOP_SPAN,
    Tracer,
    tracer,
)

PHASES = ("maxsum/f2v", "maxsum/aggregate", "maxsum/v2f",
          "maxsum/update", "maxsum/select")
MAX_CYCLES = 30


def _ring(n: int, seed: int) -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"ring{n}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % n]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


@pytest.fixture
def file_session():
    """The process tracer under a file session, off again after."""
    tracer.enable()
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.clear()


def _children(events):
    by_parent = {}
    for e in events:
        by_parent.setdefault(e["parent"], []).append(e)
    return by_parent


def _descendants(events, root):
    by_parent, out, todo = _children(events), [], [root["id"]]
    while todo:
        for child in by_parent.get(todo.pop(), ()):
            out.append(child)
            todo.append(child["id"])
    return out


# ------------------------------------------------------------------ #
# (a) one clock: spans are annotations in the profiler's trace


BRIDGED = ("outer", "inner", "renamed_later")


@pytest.fixture(scope="module")
def bridged_profile(tmp_path_factory):
    """One ``jax.profiler.trace`` with the tracer on for its first
    half and off for its second; returns ``(annotations of the host
    planes by name, the tracer's events by name)``."""
    directory = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with jax.profiler.trace(directory, profiler_options=options):
        tracer.enable()
        try:
            with tracer.span("outer", "t"):
                with tracer.span("inner", "t"):
                    jax.numpy.ones(8).sum().block_until_ready()
                with tracer.span("renamed_later", "t") as span:
                    span.name = "another_name"
            # Retroactive: cannot be an annotation.
            tracer.complete("back_dated", "t", t0=0.0, t1=1.0)
        finally:
            tracer.disable()
        # Flight ring only: spans are made, annotations are not.
        assert tracer.active
        with tracer.span("ring_only", "t"):
            pass
    spans = {e["name"]: e for e in tracer.events()}
    tracer.clear()
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    annotations = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    annotations[e.name[len(ANNOTATION_PREFIX):]] = dict(
                        e.stats)
    return annotations, spans


@pytest.mark.parametrize("name", BRIDGED)
def test_a_span_under_a_file_session_is_a_profiler_annotation(
        bridged_profile, name):
    annotations, spans = bridged_profile
    # The annotation keeps the name the span was opened with; the
    # span_id ties it to the tracer's event whatever it was renamed.
    span = spans["another_name" if name == "renamed_later" else name]
    assert annotations[name]["span_id"] == span["id"]


@pytest.mark.parametrize("name", ["ring_only", "back_dated"])
def test_no_annotation_without_a_file_session_or_for_a_back_dated_span(
        bridged_profile, name):
    annotations, spans = bridged_profile
    assert name not in annotations
    # The back-dated span was recorded; the ring-only one went to the
    # ring, not to the (stopped) session.
    assert (name in spans) == (name == "back_dated")


def test_the_bridge_does_not_import_jax(monkeypatch):
    """A process that never touched JAX has no device trace to line
    up with, and the tracer must not be what imports it."""
    import sys

    from pydcop_tpu.observability import trace as trace_module

    monkeypatch.delitem(sys.modules, "jax")
    assert trace_module._open_annotation("x", 1) is None
    assert "jax" not in sys.modules


@pytest.mark.parametrize("nest", [False, True])
def test_complete_nests_under_the_open_span_only_when_asked(nest):
    t = Tracer()
    t.enable()
    with t.span("open", "t") as span:
        assert t.current_span_id() == span.span_id
        parent = t.current_span_id() if nest else 0
        t.complete("retro", "t", t0=1.0, t1=2.0, parent=parent)
    assert t.current_span_id() == 0
    retro = next(e for e in t.events() if e["name"] == "retro")
    assert retro["parent"] == (span.span_id if nest else 0)
    assert retro["dur"] == pytest.approx(1e6)


def test_the_noop_span_takes_and_drops_writes():
    NOOP_SPAN.args["k"] = 1
    NOOP_SPAN.name = "x"
    assert NOOP_SPAN.args == {} and NOOP_SPAN.name == ""


# ------------------------------------------------------------------ #
# the YAML load


@pytest.mark.parametrize("session", ["ring", "file", "off"])
def test_load_dcop_records_parse_and_build(session, monkeypatch):
    """``tracer.active`` sites: on the flight ring with tracing off,
    in the session when one is on, nothing when both are off."""
    from pydcop_tpu.observability.flight import FlightRecorder

    text = dcop_yaml(_ring(6, 1))
    recorder = FlightRecorder(events=64)
    previous = tracer.flight
    tracer.set_flight(None if session == "off" else recorder)
    if session == "file":
        tracer.enable()
    try:
        dcop = load_dcop(text)
    finally:
        if session == "file":
            tracer.disable()
        tracer.set_flight(previous)
    events = (tracer.events() if session == "file"
              else recorder.snapshot())
    tracer.clear()
    names = [e["name"] for e in events]
    if session == "off":
        assert names == [] and recorder.snapshot() == []
        return
    assert names == ["yaml_parse", "yaml_build"]
    parse, build = events
    assert parse["cat"] == build["cat"] == "dcop"
    assert parse["args"]["bytes"] == len(text)
    assert build["args"]["n_variables"] == len(dcop.variables) == 6
    assert build["args"]["n_constraints"] == len(dcop.constraints) == 6
    assert parse["ts"] + parse["dur"] <= build["ts"]


# ------------------------------------------------------------------ #
# (e) the HTTP front end, (f) the scheduler's wait


def _until(condition, tries=500):
    """A handler thread closes its ``http_request`` span after the
    caller has its reply: wait for it (bounded) before reading."""
    import time

    for _ in range(tries):
        if condition():
            return True
        time.sleep(0.01)
    return False


def _post(url, body):
    request = urllib.request.Request(
        url + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=120) as response:
        return response.status, json.loads(response.read())


@pytest.fixture(scope="module")
def served_requests():
    """Three ``POST /solve`` with ``wait: true``, one after the
    other, under a file session (the scheduler may still be inside
    the last one's flush when its reply arrives, so only the first
    two flushes are sure to be in the session); then one with only
    the flight ring.  Returns ``(session events, thread names,
    replies, ring events of the last request)``."""
    from pydcop_tpu import api
    from pydcop_tpu.observability.flight import FlightRecorder

    payloads = [dcop_yaml(_ring(7, seed)) for seed in (1, 2, 3, 4)]
    # These dispatches are not samples for the process-wide pack
    # planner fit, which other batteries of the same worker read.
    patch = pytest.MonkeyPatch()
    patch.setenv("PYDCOP_PACK_FIT", "0")
    handle = api.serve(port=0, batch_window_s=0.005, max_batch=4,
                       max_queue=16)
    previous = tracer.flight
    try:
        # Warm the program up first, so the traced requests take the
        # steady path (pipelined launch and collect).  Its handler
        # thread closes its ``http_request`` span after the caller has
        # the reply: wait for it, or a busy machine lets it close
        # inside the session, a fourth request there.
        warm_up = FlightRecorder(events=256)
        tracer.set_flight(warm_up)
        _post(handle.url, {"dcop": payloads[0], "wait": True,
                           "params": {"max_cycles": MAX_CYCLES}})
        assert _until(lambda: any(
            e["name"] == "http_request" for e in warm_up.snapshot()))
        tracer.enable()
        try:
            replies = [
                _post(handle.url, {"dcop": text, "wait": True,
                                   "params": {"max_cycles": MAX_CYCLES}})
                for text in payloads[:3]]
            assert _until(lambda: sum(
                e["name"] == "http_request"
                for e in tracer.events()) == 3), sorted(
                    (e["name"], e["tid"]) for e in tracer.events())
        finally:
            tracer.disable()
        events = tracer.events()
        names = tracer.thread_names()
        recorder = FlightRecorder(events=256)
        tracer.set_flight(recorder)
        _post(handle.url, {"dcop": payloads[3], "wait": True,
                           "params": {"max_cycles": MAX_CYCLES}})
        assert _until(lambda: any(
            e["name"] == "http_request" for e in recorder.snapshot()))
        ring = recorder.snapshot()
    finally:
        tracer.set_flight(previous)
        handle.stop()
        tracer.clear()
        patch.undo()
    return events, names, replies, ring


HTTP_DESCENDANTS = ("http_read", "yaml_parse", "yaml_build",
                    "serve_submit", "http_wait", "http_reply")


@pytest.mark.parametrize("name", HTTP_DESCENDANTS)
def test_every_part_of_a_request_is_under_its_http_request_span(
        served_requests, name):
    events, _, replies, _ = served_requests
    requests = [e for e in events if e["name"] == "http_request"]
    assert len(requests) == len(replies) == 3
    for request, (status, reply) in zip(requests, replies):
        assert status == 200 and reply["status"] == "FINISHED"
        # One request's spans share the reply's identifier.
        assert request["args"]["trace_id"] == reply["trace_id"]
        assert request["args"]["code"] == 200
        assert request["args"]["bytes"] > 0
        under = _descendants(events, request)
        found = [e for e in under if e["name"] == name]
        assert len(found) == 1, (name, [e["name"] for e in under])
        assert request["ts"] <= found[0]["ts"]
        assert (found[0]["ts"] + found[0]["dur"]
                <= request["ts"] + request["dur"])
        if name == "serve_submit":
            assert found[0]["args"]["trace_id"] == reply["trace_id"]


def test_a_request_leaves_three_more_spans_on_the_ring(
        served_requests):
    """With tracing off a request is on the flight ring as its
    ``tracer.active`` events only: 8 spans — the three this PR adds
    beside prep, submit, queue, dispatch and the engine's segment —
    and 3 lifecycle instants; none of the session-only spans."""
    *_, ring = served_requests
    # (The background compiler's own span may close meanwhile, on a
    # busy machine: it is no part of the request.)
    spans = sorted(e["name"] for e in ring if e["ph"] == "X"
                   and e["name"] != "speculative_compile")
    assert spans == sorted([
        "http_request", "yaml_parse", "yaml_build",
        "compile_graph", "serve_submit", "serve_queued",
        "serve_dispatch", "engine_segment"])
    assert sorted(e["name"] for e in ring if e["ph"] == "i") == [
        "serve_accepted", "serve_dispatched", "serve_finished"]


@pytest.mark.parametrize("name", ["sched_idle", "sched_collect",
                                  "sched_flush"])
def test_three_spans_tile_the_scheduler_thread_between_flushes(
        served_requests, name):
    events, thread_names, *_ = served_requests
    tid = next(t for t, label in thread_names.items()
               if label == "pydcop-serve-scheduler")
    roots = sorted((e for e in events
                    if e["tid"] == tid and e["parent"] == 0
                    and e["ph"] == "X" and e["name"].startswith("sched_")),
                   key=lambda e: e["ts"])
    sequence = [e["name"] for e in roots]
    # From the first flush to the second, in this order and nothing
    # else; the wait is ONE span however many polls it took.
    first = sequence.index("sched_flush")
    assert sequence[first:first + 4] == [
        "sched_flush", "sched_idle", "sched_collect", "sched_flush"]
    assert name in sequence[first:first + 4]
    for before, after in zip(roots, roots[1:]):
        assert before["ts"] + before["dur"] <= after["ts"]
    flushes = [e for e in roots if e["name"] == "sched_flush"]
    for flush in flushes:
        assert flush["args"]["n_requests"] == 1
        assert flush["args"]["n_chunks"] == 1
    # The dispatch runs inside the flush that planned it (the last
    # flush may have closed after the session: its span is dropped).
    dispatches = sorted((e for e in events
                         if e["name"] == "serve_dispatch"),
                        key=lambda e: e["ts"])[:2]
    assert len(dispatches) == 2 and all(
        e["parent"] in {f["id"] for f in flushes} for e in dispatches)


FLUSH_STAGES = ("sched_plan", "serve_launch", "serve_dispatch",
                "serve_decode")


@pytest.mark.parametrize("name", FLUSH_STAGES)
def test_four_stages_tile_a_flush(served_requests, name):
    """ISSUE 41: plan, the host's half of the launch, the wait for
    the device, the decode; in that order, one of each for a flush of
    one chunk, none overlapping, each with its thread's CPU time."""
    events, thread_names, *_ = served_requests
    tid = next(t for t, label in thread_names.items()
               if label == "pydcop-serve-scheduler")
    flushes = sorted((e for e in events if e["name"] == "sched_flush"),
                     key=lambda e: e["ts"])
    assert len(flushes) >= 2
    for flush in flushes[:2]:
        stages = sorted(
            (e for e in events if e["parent"] == flush["id"]
             and e["name"] != "gc_collect"),
            key=lambda e: e["ts"])
        assert [e["name"] for e in stages] == list(FLUSH_STAGES)
        assert all(e["tid"] == tid for e in stages)
        for before, after in zip(stages, stages[1:]):
            assert before["ts"] + before["dur"] <= after["ts"]
        assert flush["ts"] <= stages[0]["ts"]
        assert (stages[-1]["ts"] + stages[-1]["dur"]
                <= flush["ts"] + flush["dur"])
        stage = next(e for e in stages if e["name"] == name)
        assert 0 <= stage["tdur"] <= stage["dur"]
        if name == "sched_plan":
            assert stage["args"]["n_plans"] == 1
        elif name == "serve_launch":
            # The warm path: launched, not handed back to the
            # synchronous dispatch.
            assert stage["args"]["n_real"] == 1
            assert stage["args"]["packing"] == "structure"
            assert stage["args"]["pipelined"] is True
            assert stage["args"]["launched"] is True
        elif name == "serve_decode":
            assert stage["args"]["n_real"] == 1
            assert 0 < stage["args"]["cost_ms"] <= stage["dur"] / 1e3


def test_a_cold_flush_assembles_inside_its_dispatch(file_session):
    """The synchronous path (a program not compiled yet): the launch
    is handed back, and the assembly before ``timed_jit_call`` is the
    engine's ``batch_assemble`` under ``serve_dispatch``: one
    ``serve_launch`` a dispatch, whichever path it took."""
    from pydcop_tpu import api
    from pydcop_tpu.observability.trace import check_well_nested

    patch = pytest.MonkeyPatch()
    patch.setenv("PYDCOP_PACK_FIT", "0")
    try:
        with api.serve(port=0, batch_window_s=0.005, max_batch=4,
                       max_queue=16) as handle:
            rid = handle.service.submit(
                _ring(11, 7), params={"max_cycles": MAX_CYCLES})
            assert handle.service.result(
                rid, wait=120)["status"] == "FINISHED"
            assert _until(lambda: any(
                e["name"] == "sched_flush" for e in tracer.events()))
    finally:
        patch.undo()
    events = tracer.events()
    # (`serve_queued` is back-dated from another thread's clock read:
    # it lies across the scheduler's own spans by design.)
    check_well_nested(e for e in events if e["name"] != "serve_queued")
    (launch,) = [e for e in events if e["name"] == "serve_launch"]
    assert launch["args"]["pipelined"] is True
    assert launch["args"]["launched"] is False
    (dispatch,) = [e for e in events if e["name"] == "serve_dispatch"]
    (assembly,) = [e for e in events if e["name"] == "batch_assemble"]
    assert assembly["cat"] == "engine"
    assert assembly["parent"] == dispatch["id"]
    assert assembly["args"]["n_real"] == 1
    assert assembly["args"]["packing"] == "structure"
    assert launch["ts"] + launch["dur"] <= assembly["ts"]
    (flush,) = [e for e in events if e["name"] == "sched_flush"]
    under = {e["name"] for e in events if e["parent"] == flush["id"]}
    assert set(FLUSH_STAGES) <= under


def test_a_batch_solve_outside_the_service_names_no_serving_span(
        file_session):
    """``engine.batch.solve_maxsum_batch`` runs the same assembly with no
    service above it: its span is the engine's."""
    from pydcop_tpu.engine.batch import solve_maxsum_batch

    solve_maxsum_batch([_ring(9, 3), _ring(9, 4)],
                       max_cycles=MAX_CYCLES)
    events = tracer.events()
    assert [e["args"]["n_real"] for e in events
            if e["name"] == "batch_assemble"] == [2]
    assert not [e for e in events if e["cat"] == "serving"]


# ------------------------------------------------------------------ #
# (g) where solve's own time goes


SOLVE_CHILDREN = ("build_engine", "compile_graph", "engine_place",
                  "result_decode", "result_cost")


@pytest.fixture(scope="module")
def traced_solves():
    """Two ``api.solve`` of one problem under a file session (the
    second warm); returns the events."""
    from pydcop_tpu import api

    dcop = _ring(9, 4)
    tracer.enable()
    try:
        for _ in range(2):
            api.solve(dcop, "maxsum", max_cycles=MAX_CYCLES)
    finally:
        tracer.disable()
    events = tracer.events()
    tracer.clear()
    return events


@pytest.mark.parametrize("name", SOLVE_CHILDREN)
def test_the_children_of_a_solve_are_well_nested(traced_solves, name):
    from pydcop_tpu.observability.trace import check_well_nested

    check_well_nested(traced_solves)
    solves = [e for e in traced_solves if e["name"] == "solve"]
    assert len(solves) == 2
    by_id = {e["id"]: e for e in traced_solves}
    for solve in solves:
        under = _descendants(traced_solves, solve)
        (span,) = [e for e in under if e["name"] == name]
        parent = by_id[span["parent"]]
        expected = ("build_engine" if name in ("compile_graph",
                                               "engine_place")
                    else "solve")
        assert parent["name"] == expected
        assert parent["ts"] <= span["ts"]
        assert (span["ts"] + span["dur"]
                <= parent["ts"] + parent["dur"])
        assert 0 <= span["tdur"] <= span["dur"]
        if name == "build_engine":
            assert span["args"] == {"layout": "lane",
                                    "layout_source": "selected"}
        elif name == "engine_place":
            assert span["args"]["layout"] == "lane"
            assert span["args"]["bytes"] > 0
        elif name == "result_cost":
            assert span["args"] == {"n_constraints": 9,
                                    "n_variables": 9}
    # In the order the solve runs them.
    first = _descendants(traced_solves, solves[0])
    order = [e["name"] for e in sorted(first, key=lambda e: e["ts"])
             if e["name"] in SOLVE_CHILDREN]
    assert order == list(SOLVE_CHILDREN)


# ------------------------------------------------------------------ #
# (c) trace= does not change the program, (d) what a dispatch did


def test_a_traced_solve_is_the_untraced_solve(tmp_path):
    from pydcop_tpu import api
    from pydcop_tpu.observability.trace import load_trace_file

    dcop = _ring(9, 4)
    plain = api.solve(dcop, "maxsum", max_cycles=MAX_CYCLES)
    path = str(tmp_path / "trace.json")
    traced = api.solve(dcop, "maxsum", max_cycles=MAX_CYCLES, trace=path)
    for key in ("assignment", "cost", "cycles", "status"):
        assert traced[key] == plain[key], key
    names = {e["name"] for e in load_trace_file(path)}
    assert "solve" in names and "compile_graph" in names
    assert names & {"engine_call", "jit_compile"}
    assert not names & {"engine_segment", "chunk"}
    # What the first dispatch spent compiling is a part of its time,
    # from the counters, not a copy of it.
    assert 0 <= traced["compile_time"] < traced["time"]


@pytest.fixture
def disk_cache(tmp_path):
    """The persistent compile cache in the test's own directory; the
    process's cache settings are put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    from pydcop_tpu.engine import aotcache

    previous = {key: os.environ.pop(key, None)
                for key in ("JAX_COMPILATION_CACHE_DIR",
                            "PYDCOP_COMPILE_CACHE_DIR")}
    cache_dir = jax.config.jax_compilation_cache_dir
    with aotcache._lock:
        state = dict(aotcache._state)
    try:
        yield aotcache.enable_persistent_compile_cache(
            str(tmp_path / "jaxcache"))
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()
        with aotcache._lock:
            aotcache._state.update(state)
        for key, value in previous.items():
            if value is not None:
                os.environ[key] = value


@pytest.mark.parametrize("cache", ["empty", "warm"])
def test_the_dispatch_span_says_what_happened(
        disk_cache, file_session, cache):
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.engine.runner import reset_process_programs

    dcop = _ring(11, 5)
    cycles = MAX_CYCLES + 3  # a program no other test compiles
    if cache == "warm":
        build_engine(dcop, {}).run(max_cycles=cycles)
        file_session.clear()
        # A new process, as far as the engine's programs go: a new
        # engine alone would dispatch the program the first one ran.
        reset_process_programs()
    result = build_engine(dcop, {}).run(max_cycles=cycles)
    calls = [e for e in file_session.events()
             if e["name"] in ("jit_compile", "engine_call")]
    assert len(calls) == 1 and calls[0]["args"]["first"] is True
    stages = {e["name"] for e in _descendants(
        file_session.events(), calls[0])}
    assert {"jax_trace", "jax_lower"} <= stages
    assert result.metrics["cold_start"] is True
    if cache == "empty":
        assert calls[0]["name"] == "jit_compile"
        assert calls[0]["args"]["xla_compiles"] >= 1
        assert "xla_compile" in stages
        assert result.compile_time_s > 0
    else:
        assert calls[0]["name"] == "engine_call"
        assert calls[0]["args"]["cache_loads"] >= 1
        assert calls[0]["args"]["xla_compiles"] == 0
        assert "xla_cache_load" in stages
        assert "xla_compile" not in stages
        assert 0 < result.compile_time_s < result.time_s


@pytest.mark.parametrize("second", ["same_engine", "new_engine"])
def test_a_warm_call_compiled_nothing(file_session, second):
    """The program is the process's: a new engine over the same
    shapes and parameters (another problem: ``api.solve`` builds an
    engine per solve) dispatches it as the engine that ran it does,
    with no ``jax_trace`` / ``jax_lower`` / ``xla_cache_load`` /
    ``xla_compile`` under the call."""
    from pydcop_tpu.algorithms.maxsum import build_engine

    engine = build_engine(_ring(11, 6), {})
    engine.run(max_cycles=MAX_CYCLES)
    file_session.clear()
    if second == "new_engine":
        engine = build_engine(_ring(11, 7), {})
    warm = engine.run(max_cycles=MAX_CYCLES)
    assert warm.compile_time_s == 0.0
    assert warm.metrics["cold_start"] is False
    (call,) = [e for e in file_session.events()
               if e["name"] in ("jit_compile", "engine_call")]
    assert call["name"] == "engine_call" and "first" not in call["args"]
    assert _descendants(file_session.events(), call) == []


# ------------------------------------------------------------------ #
# (b) the superstep's phases are named, and only named


def _compiled_text(layout, dcop):
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.engine import batch
    from pydcop_tpu.engine.compile import compile_dcop

    if layout == "batched":
        graphs = [compile_dcop(_ring(8, seed), noise_level=0.01)[0]
                  for seed in (1, 2)]
        lowered = batch._batched_maxsum_solve.lower(
            batch.stack_graphs(graphs), max_cycles=MAX_CYCLES,
            damping=0.5, damp_vars=True, damp_factors=True,
            stability=0.1)
    else:
        engine = build_engine(dcop, {"layout": layout})
        lowered = engine._fn(MAX_CYCLES, True).lower(engine.graph)
    return lowered.compile().as_text()


@pytest.mark.parametrize("layout", ["edge", "lane", "batched"])
def test_the_compiled_program_carries_the_five_phases(layout):
    text = _compiled_text(layout, _ring(8, 1))
    op_names = re.findall(r'op_name="([^"]*)"', text)
    # Under vmap JAX spells a scope ``vmap(maxsum/f2v)``.
    found = {scope for name in op_names
             for scope in re.findall(r"maxsum/\w+", name)}
    assert found == set(PHASES)
    if layout != "batched":
        # The program has a name of its own in the trace.
        assert "jit(maxsum_solve)/" in "".join(op_names)


@pytest.mark.parametrize("layout", ["edge", "lane", "batched"])
def test_the_scopes_are_metadata_only(layout, monkeypatch):
    """Bit-equal results with ``jax.named_scope`` taken away: the
    parent commit's program, as far as the numbers go."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.engine import batch
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.engine.runner import reset_process_programs

    def solve():
        if layout == "batched":
            graphs = [compile_dcop(_ring(8, seed), noise_level=0.01)[0]
                      for seed in (1, 2)]
            # Not the module's jitted function: that would answer the
            # second call from its cache without tracing again.
            values, cycles, stable = jax.jit(
                batch._batched_maxsum_solve.__wrapped__,
                static_argnames=("max_cycles", "damping", "damp_vars",
                                 "damp_factors", "stability", "prune"),
            )(batch.stack_graphs(graphs), max_cycles=MAX_CYCLES,
              damping=0.5, damp_vars=True, damp_factors=True,
              stability=0.1)
            return np.asarray(values), np.asarray(cycles)
        # Nor the process's program, for the same reason.
        reset_process_programs()
        engine = build_engine(_ring(8, 1), {"layout": layout})
        state, values = engine._fn(MAX_CYCLES, False)(engine.graph)
        return (np.asarray(values),
                [np.asarray(m) for m in state.f2v + state.v2f])

    scoped = solve()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = solve()
    jax.tree_util.tree_map(np.testing.assert_array_equal, scoped, plain)
