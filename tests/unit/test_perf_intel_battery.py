"""Performance-intelligence battery: XLA cost attribution, live
telemetry endpoint, multi-process trace merge/diff, and the
observability hardening satellites.

Acceptance targets (ISSUE 5): a ``run_checkpointed`` solve on CPU
records per-segment XLA cost/memory-analysis metrics (or an explicit
``unavailable`` marker); ``/metrics`` scraped mid-run parses with a
growing cycle counter (the mid-run leg lives in tools/trace_demo.py,
the endpoint contract here); ``pydcop trace merge`` of two
concurrent-process traces yields one well-nested trace with distinct
lanes; histogram Prometheus output survives a promtool-style parser
including ``+Inf``/``le``/escaping; and the metrics registry + tracer
lose nothing under 8-thread concurrency.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from pydcop_tpu.observability.metrics import (
    CycleSnapshotter,
    Histogram,
    MetricsRegistry,
    registry as global_registry,
)
from pydcop_tpu.observability.profiler import (
    XlaCostProfiler,
    key_str,
    profiler,
)
from pydcop_tpu.observability.server import (
    TelemetryServer,
    health_verdict,
    set_health_provider,
)
from pydcop_tpu.observability.trace import (
    HEADER_KEY,
    TraceFileError,
    Tracer,
    check_well_nested,
    diff_trace_summaries,
    load_trace,
    load_trace_file,
    merge_traces,
    tracer,
    trace_header,
)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_observability():
    """Tracer off, profiler off+cleared, health provider cleared,
    registry inactive around every test.  The registry flag is
    NORMALIZED to False at setup (not just restored at teardown):
    a battery that ran earlier in the process and leaked
    ``active=True`` — any started-service crash simulation can —
    must not change what this battery's tests observe."""
    tracer.disable()
    tracer.clear()
    profiler.enabled = False
    profiler.clear()
    set_health_provider(None)
    was_active = global_registry.active
    global_registry.active = False
    yield
    tracer.disable()
    tracer.clear()
    profiler.enabled = False
    profiler.clear()
    set_health_provider(None)
    global_registry.active = was_active


def _tiny_engine(n_vars=6):
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import constraint_from_str
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.engine.runner import MaxSumEngine

    d = Domain("c", "", list(range(3)))
    dcop = DCOP("perfintel", objective="min")
    variables = [Variable(f"v{i}", d) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    for i in range(n_vars):
        j = (i + 1) % n_vars
        dcop.add_constraint(constraint_from_str(
            f"c{i}", f"3 if v{i} == v{j} else 0",
            [variables[i], variables[j]],
        ))
    graph, meta = compile_dcop(dcop, noise_level=0.01)
    return MaxSumEngine(graph, meta)


# ------------------------------------------------------------------ #
# XLA cost attribution


class TestXlaCostAttribution:
    def test_run_checkpointed_records_per_segment_cost(self):
        """The acceptance criterion: a CPU run_checkpointed solve
        carries measured flops/bytes/peak metrics per segment key."""
        profiler.enabled = True
        engine = _tiny_engine()
        res = engine.run_checkpointed(
            max_cycles=30, segment_cycles=10,
            stop_on_convergence=False)
        xla = res.metrics.get("xla_cost")
        assert xla, "no xla_cost in DeviceRunResult.metrics"
        seg_keys = [k for k in xla if k.startswith("('maxsum_segment'")]
        assert seg_keys
        for k in seg_keys:
            entry = xla[k]
            # CPU XLA supports cost analysis in this image; were it to
            # stop, the explicit marker is the accepted alternative.
            if entry["available"]:
                assert entry["flops"] > 0
                assert entry["bytes_accessed"] > 0
                assert entry["peak_bytes"] > 0
            else:
                assert entry["reason"]

    def test_flops_counted_per_loop_body_not_per_trip(self):
        """The roofline's measured override reads XLA flops as
        per-cycle numbers because XLA counts a while-loop body once;
        pin that invariant so a future XLA that scales by trip count
        fails HERE, not silently in a report."""
        profiler.enabled = True
        engine = _tiny_engine()
        for cycles in (8, 16):
            engine.run_checkpointed(
                max_cycles=cycles, segment_cycles=cycles,
                stop_on_convergence=False)
        entries = profiler.snapshot()
        flops = {
            k: v["flops"] for k, v in entries.items()
            if k.startswith("('maxsum_segment'") and v.get("available")
        }
        assert len(flops) == 2
        a, b = sorted(flops.values())
        assert a == pytest.approx(b, rel=0.01), (
            "XLA flops now scale with trip count; a per-cycle "
            "normalization must divide by cycles")

    def test_unavailable_marker_on_analysis_failure(self, monkeypatch):
        profiler.enabled = True
        global_registry.active = True
        monkeypatch.setattr(
            XlaCostProfiler, "_analyze",
            staticmethod(lambda fn, args: (_ for _ in ()).throw(
                RuntimeError("backend said no"))))
        engine = _tiny_engine()
        res = engine.run_checkpointed(
            max_cycles=10, segment_cycles=10,
            stop_on_convergence=False)
        entries = list(res.metrics["xla_cost"].values())
        assert entries
        assert all(e["available"] is False for e in entries)
        assert "backend said no" in entries[0]["reason"]
        assert global_registry.value(
            "pydcop_xla_analysis_unavailable_total") >= 1

    def test_disabled_profiler_records_nothing(self):
        engine = _tiny_engine()
        res = engine.run_checkpointed(
            max_cycles=10, segment_cycles=10,
            stop_on_convergence=False)
        assert "xla_cost" not in res.metrics
        assert profiler.snapshot() == {}

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("PYDCOP_XLA_PROFILE", "0")
        profiler.enabled = True
        assert profiler.enabled is False
        monkeypatch.setenv("PYDCOP_XLA_PROFILE", "1")
        profiler.enabled = False
        assert profiler.enabled is True

    def test_flops_counter_exported(self):
        profiler.enabled = True
        global_registry.active = True
        engine = _tiny_engine()
        engine.run_checkpointed(max_cycles=10, segment_cycles=10,
                                stop_on_convergence=False)
        metric = global_registry.get("pydcop_xla_flops_total")
        assert metric is not None
        assert sum(v for _, v in metric.samples()) > 0

    def test_registry_untouched_without_active(self):
        """profiler on + registry inactive: cost
        entries flow through DeviceRunResult only — no key-labeled
        series leak into the shared registry for a later solve's
        .prom dump."""
        global_registry.active = False
        profiler.enabled = True
        before = global_registry.get("pydcop_xla_flops_total")
        before_n = (sum(v for _, v in before.samples())
                    if before else 0.0)
        engine = _tiny_engine()
        res = engine.run_checkpointed(max_cycles=10, segment_cycles=10,
                                      stop_on_convergence=False)
        assert res.metrics["xla_cost"]  # entries still delivered
        after = global_registry.get("pydcop_xla_flops_total")
        after_n = (sum(v for _, v in after.samples())
                   if after else 0.0)
        assert after_n == before_n

    def test_first_dispatch_span_carries_cost(self):
        """The first dispatch of a program carries the measured cost.
        Its span says what happened inside it: the profiler's own AOT
        compile ran before the timed call, so the call itself may
        have compiled nothing (``engine_call``, ``xla_compiles`` 0)
        or compiled (``jit_compile``)."""
        profiler.enabled = True
        tracer.enable()
        engine = _tiny_engine()
        engine.run_checkpointed(max_cycles=10, segment_cycles=10,
                                stop_on_convergence=False)
        tracer.disable()
        firsts = [e for e in tracer.events()
                  if e["name"] in ("jit_compile", "engine_call")
                  and e["args"].get("first")]
        assert firsts
        for e in firsts:
            assert (e["name"] == "jit_compile") == (
                e["args"]["xla_compiles"] > 0)
        assert any("xla_cost" in e["args"] for e in firsts)

    def test_warm_cold_accounting_per_key(self):
        global_registry.active = True
        engine = _tiny_engine()
        engine.run_checkpointed(max_cycles=20, segment_cycles=10,
                                stop_on_convergence=False)
        calls = global_registry.get("pydcop_jit_calls_total")
        assert calls is not None
        cold = [(k, v) for k, v in calls.samples()
                if ("warmth", "cold") in k]
        warm = [(k, v) for k, v in calls.samples()
                if ("warmth", "warm") in k]
        assert cold and warm
        secs = global_registry.get("pydcop_jit_compile_seconds_total")
        assert sum(v for _, v in secs.samples()) > 0

    def test_dynamic_engine_records_cost(self):
        from pydcop_tpu.dcop.objects import Domain, Variable
        from pydcop_tpu.dcop.relations import constraint_from_str
        from pydcop_tpu.engine.dynamic import DynamicMaxSumEngine

        profiler.enabled = True
        d = Domain("c", "", [0, 1])
        x, y = Variable("x", d), Variable("y", d)
        c = constraint_from_str("c", "1 if x == y else 0", [x, y])
        engine = DynamicMaxSumEngine([x, y], [c])
        res = engine.run(max_cycles=10)
        assert "xla_cost" in res.metrics
        entry = list(res.metrics["xla_cost"].values())[0]
        assert entry["available"] in (True, False)

    def test_roofline_measured_override(self):
        from pydcop_tpu.engine.roofline import roofline_report

        engine = _tiny_engine()
        graph = engine.graph
        model = roofline_report(graph, 100.0, "cpu")
        assert model["cost_source"] == "model"
        assert "model_flops_per_cycle" not in model
        measured = roofline_report(
            graph, 100.0, "cpu",
            measured={"flops_per_cycle": 1234.0,
                      "bytes_per_cycle": 5678.0})
        assert measured["cost_source"] == "xla"
        assert measured["flops_per_cycle"] == 1234.0
        assert measured["bytes_per_cycle"] == 5678.0
        assert measured["model_flops_per_cycle"] == \
            model["flops_per_cycle"]
        # Empty/None measured: clean model fallback.
        assert roofline_report(graph, 100.0, "cpu", measured={})[
            "cost_source"] == "model"

    def test_compile_cache_metrics(self):
        from pydcop_tpu.dcop.dcop import DCOP
        from pydcop_tpu.dcop.objects import Domain, Variable
        from pydcop_tpu.dcop.relations import constraint_from_str
        from pydcop_tpu.engine.compile import compile_dcop

        global_registry.active = True
        d = Domain("c", "", [0, 1])
        dcop = DCOP("cachemetrics", objective="min")
        x, y = Variable("x", d), Variable("y", d)
        dcop.add_variable(x)
        dcop.add_variable(y)
        dcop.add_constraint(
            constraint_from_str("k", "x + y", [x, y]))
        counter = global_registry.counter("pydcop_compile_cache_total")
        before_hit = counter.value(outcome="hit")
        compile_dcop(dcop)
        compile_dcop(dcop)
        assert counter.value(outcome="hit") > before_hit

    def test_device_fn_profile_label_is_stable(self):
        from functools import partial

        from pydcop_tpu.engine.runner import _fn_label

        def run_solver(graph, max_cycles=10):
            return graph

        assert _fn_label(run_solver) == "run_solver"
        label = _fn_label(partial(run_solver, max_cycles=99))
        assert label == "run_solver"
        assert "0x" not in label  # never a repr with an address


# ------------------------------------------------------------------ #
# live telemetry endpoint


def _get(url, timeout=5):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


class TestTelemetryServer:
    def test_metrics_endpoint_serves_prometheus(self):
        reg = MetricsRegistry()
        reg.counter("t_total", "help me").inc(7, kind="x")
        with TelemetryServer(port=0, registry=reg) as srv:
            assert srv.port and srv.port > 0
            status, body = _get(f"{srv.url}/metrics")
        assert status == 200
        assert '# TYPE t_total counter' in body
        assert 't_total{kind="x"} 7' in body

    def test_port_zero_assigns_distinct_ports(self):
        with TelemetryServer(port=0) as a, TelemetryServer(port=0) as b:
            assert a.port != b.port

    def test_healthz_default_ok(self):
        with TelemetryServer(port=0) as srv:
            status, body = _get(f"{srv.url}/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "ok"

    def test_healthz_rolls_up_provider_statuses(self):
        set_health_provider(lambda: {
            "statuses": {"a1": "alive", "a2": "suspect"}})
        assert health_verdict()["status"] == "degraded"
        set_health_provider(lambda: {
            "statuses": {"a1": "dead"}})
        with TelemetryServer(port=0) as srv:
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"{srv.url}/healthz")
            assert err.value.code == 503
            assert json.loads(err.value.read())["status"] == "failing"

    def test_healthz_survives_broken_provider(self):
        set_health_provider(lambda: 1 / 0)
        verdict = health_verdict()
        assert verdict["status"] == "unknown"

    def test_unknown_path_404(self):
        with TelemetryServer(port=0) as srv:
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"{srv.url}/nope")
            assert err.value.code == 404

    def test_events_streams_cycle_snapshots(self):
        with TelemetryServer(port=0) as srv:
            # Private registry: the snapshotter must not advance the
            # process-global cycle counter other tests assert on.
            snapshotter = CycleSnapshotter(reg=MetricsRegistry())
            got = []

            def reader():
                req = urllib.request.urlopen(
                    f"{srv.url}/events", timeout=10)
                while len(got) < 2:
                    line = req.readline()
                    if line.startswith(b"data: "):
                        got.append(json.loads(line[6:]))

            thread = threading.Thread(target=reader, daemon=True)
            thread.start()
            deadline = time.time() + 5
            cycle = 0
            # Keep emitting until the reader has subscribed and seen
            # two events (subscription timing is not observable).
            while len(got) < 2 and time.time() < deadline:
                cycle += 10
                snapshotter(cycle, float(100 - cycle))
                time.sleep(0.05)
            thread.join(timeout=5)
        assert len(got) >= 2
        assert got[1]["cycle"] > got[0]["cycle"]
        assert "cost" in got[0]

    def test_observability_session_serves(self, tmp_path):
        from pydcop_tpu.observability import ObservabilitySession

        session = ObservabilitySession(serve_port=0).start()
        try:
            assert session.server is not None
            status, body = _get(f"{session.server.url}/metrics")
            assert status == 200
            # Serving implies the profiler + detail instrumentation.
            assert global_registry.active is True
            assert profiler.enabled is True
        finally:
            session.finish()
        assert session.server is None

    def test_session_start_failure_leaks_nothing(self):
        """A server bind failure out of start() must leave the
        process-wide tracer/registry/profiler flags untouched —
        api.solve's caller never gets a session, so finish() never
        runs."""
        from pydcop_tpu.observability import ObservabilitySession

        blocker = TelemetryServer(port=0).start()
        try:
            session = ObservabilitySession(
                trace_path="never.json", metrics_path="never.jsonl",
                serve_port=blocker.port)
            with pytest.raises(OSError):
                session.start()
        finally:
            blocker.stop()
        assert tracer.enabled is False
        assert global_registry.active is False
        assert profiler.enabled is False

    def test_thread_backend_serve_only_feeds_snapshotter(self):
        """serve_metrics without metrics_file on the thread backend
        still wires the orchestrator's CycleSnapshotter, so /metrics
        and /events have live cycle/cost data to serve."""
        from pydcop_tpu.dcop.dcop import DCOP
        from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
        from pydcop_tpu.dcop.relations import constraint_from_str
        from pydcop_tpu.api import solve

        d = Domain("c", "", ["R", "G", "B"])
        dcop = DCOP("serveonly", objective="min")
        variables = [Variable(f"v{i}", d) for i in range(3)]
        for v in variables:
            dcop.add_variable(v)
        for i in range(2):
            dcop.add_constraint(constraint_from_str(
                f"c{i}", f"10 if v{i} == v{i + 1} else 0",
                [variables[i], variables[i + 1]]))
        # oneagent distribution: one agent per computation node
        # (3 variables + 2 factors).
        dcop.add_agents([AgentDef(f"a{i}") for i in range(5)])
        before = global_registry.value("pydcop_cycles_total")
        res = solve(dcop, "amaxsum", backend="thread", timeout=4.0,
                    serve_metrics=0)
        assert res["assignment"]
        assert global_registry.value("pydcop_cycles_total") > before

    def test_cli_exposes_serve_metrics_knob(self):
        import argparse

        from pydcop_tpu.commands import solve as solve_cmd

        parser = argparse.ArgumentParser()
        parser.add_argument("--output", default=None)
        parser.add_argument("--timeout", type=float, default=None)
        sub = parser.add_subparsers()
        solve_cmd.set_parser(sub)
        args = parser.parse_args(
            ["solve", "-a", "maxsum", "--serve_metrics", "0", "f.yaml"])
        assert args.serve_metrics == 0


# ------------------------------------------------------------------ #
# multi-process trace aggregation


def _spawn_trace(path, span_name, fmt="chrome"):
    """Export a small trace from a REAL second process."""
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from pydcop_tpu.observability.trace import tracer\n"
        "import time\n"
        "tracer.enable()\n"
        f"with tracer.span({span_name!r}, 'proc'):\n"
        "    time.sleep(0.002)\n"
        "    with tracer.span('inner', 'proc'):\n"
        "        tracer.instant('mark', 'proc')\n"
        "tracer.disable()\n"
        f"tracer.export({str(path)!r}, {fmt!r})\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=REPO, timeout=120)


class TestTraceAggregation:
    def test_exports_carry_header(self, tmp_path):
        tracer.enable()
        with tracer.span("s", "t"):
            pass
        tracer.disable()
        chrome = tmp_path / "t.json"
        jsonl = tmp_path / "t.jsonl"
        tracer.export_chrome(str(chrome))
        tracer.export_jsonl(str(jsonl))
        for path in (chrome, jsonl):
            header, events = load_trace(str(path))
            assert header["pid"] == os.getpid()
            assert header["host"]
            assert header["anchor_unix_us"] > 0
            assert header["anchor_perf_us"] >= 0
            assert len(events) == 1
        # Raw JSONL: the header is line 1, and load_trace_file
        # excludes it from the event list.
        first = json.loads(
            jsonl.read_text().splitlines()[0])
        assert HEADER_KEY in first
        assert all("ph" in e for e in load_trace_file(str(jsonl)))

    def test_merge_two_process_traces(self, tmp_path):
        """Acceptance: merging two concurrent-process traces yields a
        single well-nested trace with distinct lanes."""
        mine = tmp_path / "local.json"
        other = tmp_path / "other.json"
        tracer.enable()
        with tracer.span("local_phase", "proc"):
            with tracer.span("inner", "proc"):
                pass
        tracer.disable()
        tracer.export_chrome(str(mine))
        _spawn_trace(other, "other_phase")
        out = tmp_path / "merged.json"
        info = merge_traces([str(mine), str(other)], str(out))
        assert info["files"] == 2
        assert info["anchored"] == 2
        header, events = load_trace(str(out))
        assert header["merged_from"][0]["clock_anchor"] is True
        check_well_nested(events)
        by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
        assert {"local_phase", "other_phase", "inner"} <= set(by_name)
        # Distinct lanes: the two processes' spans never share a tid.
        assert by_name["local_phase"]["tid"] != \
            by_name["other_phase"]["tid"]
        # Span ids are namespaced per file — no cross-process
        # collision even though both processes count from 1.
        ids = [e["args"]["span_id"] for e in events
               if e.get("ph") == "X"]
        assert len(ids) == len(set(ids))

    def test_merge_corrects_clock_offset(self, tmp_path):
        """Two synthetic traces whose perf epochs differ by an hour
        but whose anchors say they ran simultaneously must land
        interleaved, not an hour apart."""
        def write(path, perf_base_us, anchor_unix_us):
            header = dict(trace_header())
            header["anchor_perf_us"] = float(perf_base_us)
            header["anchor_unix_us"] = float(anchor_unix_us)
            rows = [{HEADER_KEY: header}]
            rows.append({"name": "work", "cat": "t", "ph": "X",
                         "ts": perf_base_us + 100.0, "dur": 50.0,
                         "id": 1, "parent": 0, "tid": 1, "args": {}})
            with open(path, "w", encoding="utf-8") as f:
                for row in rows:
                    f.write(json.dumps(row) + "\n")

        wall = 1.7e15  # some unix epoch in µs
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        write(a, perf_base_us=0.0, anchor_unix_us=wall)
        write(b, perf_base_us=3.6e9, anchor_unix_us=wall)  # +1h epoch
        out = tmp_path / "m.json"
        merge_traces([str(a), str(b)], str(out))
        _, events = load_trace(str(out))
        ts = sorted(float(e["ts"]) for e in events)
        # Aligned: both events at ~+100µs from their anchors.
        assert abs(ts[1] - ts[0]) < 1.0

    def test_merge_needs_two_files(self, tmp_path):
        with pytest.raises(TraceFileError):
            merge_traces(["only.json"], str(tmp_path / "o.json"))

    def test_merge_mixed_anchor_degrades_not_scatters(self, tmp_path):
        """An anchored trace merged with a headerless legacy one must
        NOT land decades apart (wall-rebased vs raw perf_counter):
        alignment degrades to per-file rebase and is flagged."""
        anchored = tmp_path / "new.json"
        tracer.enable()
        with tracer.span("modern", "t"):
            pass
        tracer.disable()
        tracer.export_chrome(str(anchored))
        legacy = tmp_path / "legacy.jsonl"
        legacy.write_text(json.dumps(
            {"name": "old", "cat": "t", "ph": "X", "ts": 5_000.0,
             "dur": 10.0, "id": 1, "parent": 0, "tid": 1,
             "args": {}}) + "\n")
        out = tmp_path / "mixed.json"
        info = merge_traces([str(anchored), str(legacy)], str(out))
        assert info["aligned"] is False
        assert info["anchored"] == 1
        # Both lanes start near 0 on the merged axis: the whole span
        # is bounded by real durations, not epoch deltas.
        assert info["span_us"] < 60e6
        _, events = load_trace(str(out))
        check_well_nested(events)

    def test_merge_labels_lanes_from_chrome_thread_names(
            self, tmp_path):
        mine = tmp_path / "a.json"
        other = tmp_path / "b.json"
        tracer.enable()
        with tracer.span("s", "t"):
            pass
        tracer.disable()
        tracer.export_chrome(str(mine))
        _spawn_trace(other, "s2")
        out = tmp_path / "m.json"
        merge_traces([str(mine), str(other)], str(out))
        # Lane labels carry host:pid + the ORIGINAL thread name
        # (recovered from the chrome thread_name metadata), not a
        # bare tid number.
        raw = json.loads(out.read_text())
        labels = [e["args"]["name"] for e in raw["traceEvents"]
                  if e.get("ph") == "M"
                  and e.get("name") == "thread_name"]
        assert len(labels) == 2
        assert any("MainThread" in l for l in labels), labels

    def test_merge_tolerates_foreign_string_ids(self, tmp_path):
        """Chrome traces from other tools (JAX profiler, async
        events) carry string ids like '0x42': merge must pass them
        through, not crash on int arithmetic."""
        mine = tmp_path / "own.json"
        tracer.enable()
        with tracer.span("own", "t"):
            pass
        tracer.disable()
        tracer.export_chrome(str(mine))
        foreign = tmp_path / "foreign.json"
        foreign.write_text(json.dumps({"traceEvents": [
            {"name": "ext", "cat": "x", "ph": "X", "ts": 1.0,
             "dur": 2.0, "tid": 7, "pid": 1, "id": "0x42",
             "args": {}}]}))
        out = tmp_path / "m.json"
        info = merge_traces([str(mine), str(foreign)], str(out))
        assert info["events"] == 2
        _, events = load_trace(str(out))
        assert {e["name"] for e in events} == {"own", "ext"}

    def test_non_trace_json_object_rejected(self, tmp_path):
        bogus = tmp_path / "bogus.json"
        bogus.write_text('{"foo": 1}')
        with pytest.raises(TraceFileError, match="not a trace"):
            load_trace_file(str(bogus))
        meta_only = tmp_path / "meta.json"
        meta_only.write_text(json.dumps({"traceEvents": [
            {"name": "thread_name", "ph": "M", "tid": 1,
             "args": {"name": "x"}}]}))
        with pytest.raises(TraceFileError, match="no trace events"):
            load_trace_file(str(meta_only))

    def test_load_trace_error_contract(self, tmp_path):
        missing = tmp_path / "missing.json"
        with pytest.raises(TraceFileError, match="cannot read"):
            load_trace_file(str(missing))
        empty = tmp_path / "empty.json"
        empty.write_text("")
        with pytest.raises(TraceFileError, match="empty"):
            load_trace_file(str(empty))
        trunc = tmp_path / "trunc.jsonl"
        trunc.write_text('{"name": "a", "ph": "i", "ts": 1}\n'
                         '{"name": "b", "ph"')
        with pytest.raises(TraceFileError, match="truncated"):
            load_trace_file(str(trunc))
        garbage = tmp_path / "garbage.json"
        garbage.write_text("this is not a trace\n")
        with pytest.raises(TraceFileError):
            load_trace_file(str(garbage))

    def test_diff_flags_regression(self):
        def spans(name, n, dur_us):
            return [{"name": name, "ph": "X", "ts": i * 1000.0,
                     "dur": dur_us, "tid": 1}
                    for i in range(n)]

        a = spans("hot", 10, 1000.0) + spans("steady", 5, 2000.0)
        b = spans("hot", 10, 2000.0) + spans("steady", 5, 2000.0)
        rows = diff_trace_summaries(a, b, threshold=0.25)
        by_name = {r["name"]: r for r in rows}
        assert by_name["hot"]["regressed"] is True
        assert by_name["steady"]["regressed"] is False
        assert by_name["hot"]["delta_total_ms"] == pytest.approx(10.0)
        assert by_name["hot"]["p50_ms_b"] == pytest.approx(2.0)
        # Noise floor: a huge relative delta under min_delta_ms never
        # flags.
        tiny_a = spans("tiny", 2, 1.0)
        tiny_b = spans("tiny", 2, 10.0)
        rows = diff_trace_summaries(tiny_a, tiny_b, threshold=0.25,
                                    min_delta_ms=1.0)
        assert rows[0]["regressed"] is False

    def test_diff_one_sided_names_stay_json_serializable(self):
        """A span name absent from the baseline has no defined
        relative growth: delta_rel must be None (json-valid), never
        float('inf') (json.dumps emits the non-JSON token Infinity),
        and the absolute floor alone gates its flag."""
        only_b = [{"name": "new_span", "ph": "X", "ts": 0.0,
                   "dur": 5000.0, "tid": 1}]
        rows = diff_trace_summaries([], only_b)
        assert rows[0]["delta_rel"] is None
        assert rows[0]["regressed"] is True  # 5 ms from nothing
        doc = json.dumps({"rows": rows})
        assert "Infinity" not in doc
        json.loads(doc)  # strict round-trip

    def test_trace_cli_summary_json_merge_diff(self, tmp_path,
                                               capsys):
        from pydcop_tpu.dcop_cli import main as cli_main

        t1 = tmp_path / "one.json"
        t2 = tmp_path / "two.json"
        tracer.enable()
        with tracer.span("phase", "cli"):
            pass
        tracer.disable()
        tracer.export_chrome(str(t1))
        _spawn_trace(t2, "phase")

        rc = cli_main(["trace", "summary", "--json", str(t1)])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip())
        assert doc["spans"] == 1
        assert doc["rows"][0]["name"] == "phase"

        out = tmp_path / "merged.json"
        rc = cli_main(["trace", "merge", str(out), str(t1), str(t2)])
        assert rc == 0
        capsys.readouterr()
        assert out.exists()

        rc = cli_main(["trace", "diff", "--json", str(t1), str(t2)])
        capsys.readouterr()
        assert rc in (0, 1)  # depends on measured durations

        # Clean error, not a traceback, on a truncated file.
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": [')
        rc = cli_main(["trace", "summary", str(bad)])
        assert rc == 2
        err = capsys.readouterr().err
        assert "pydcop trace:" in err
        assert "Traceback" not in err


# ------------------------------------------------------------------ #
# Prometheus exposition hardening (promtool-style line parser)


_METRIC_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})? "
    r"(?P<value>[+-]?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|Inf|NaN))$"
)
_LABEL = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(?:,|$)')


def _parse_exposition(text):
    """Strict promtool-style parse: returns {(name, labels): value};
    raises AssertionError on any malformed line, un-escaped label
    value, or histogram family violation."""
    samples = {}
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            assert "\n" not in line
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        match = _METRIC_LINE.match(line)
        assert match, f"malformed sample line: {line!r}"
        labels = {}
        raw = match.group("labels")
        if raw:
            consumed = "".join(
                m.group(0) for m in _LABEL.finditer(raw))
            assert consumed == raw, f"malformed labels: {raw!r}"
            for m in _LABEL.finditer(raw):
                value = (m.group(2)
                         .replace("\\\\", "\x00")
                         .replace('\\"', '"')
                         .replace("\\n", "\n")
                         .replace("\x00", "\\"))
                labels[m.group(1)] = value
        value = match.group("value")
        samples[(match.group("name"),
                 tuple(sorted(labels.items())))] = (
            float("inf") if value == "+Inf" else float(value))
    return samples, types


class TestPrometheusExposition:
    def test_histogram_inf_bucket_and_le_labels(self):
        reg = MetricsRegistry()
        hist = reg.histogram("lat_seconds", "latency",
                             buckets=(0.1, 1.0, 5.0))
        hist.observe(0.05, op="solve")
        hist.observe(2.0, op="solve")
        hist.observe(99.0, op="solve")
        samples, types = _parse_exposition(reg.to_prometheus())
        assert types["lat_seconds"] == "histogram"
        key = lambda le: ("lat_seconds_bucket",  # noqa: E731
                          (("le", le), ("op", "solve")))
        assert samples[key("0.1")] == 1
        assert samples[key("1")] == 1
        assert samples[key("5")] == 2
        assert samples[key("+Inf")] == 3  # every observation
        assert samples[("lat_seconds_count",
                        (("op", "solve"),))] == 3
        assert samples[("lat_seconds_sum",
                        (("op", "solve"),))] == pytest.approx(101.05)
        # Cumulative: each bucket >= all lower buckets.
        assert samples[key("0.1")] <= samples[key("1")] \
            <= samples[key("5")] <= samples[key("+Inf")]

    def test_label_escaping_backslash_newline_quote(self):
        reg = MetricsRegistry()
        nasty = 'back\\slash and\nnewline and "quote"'
        reg.counter("nasty_total", "n").inc(3, path=nasty)
        reg.histogram("nasty_seconds", "n",
                      buckets=(1.0,)).observe(0.5, path=nasty)
        text = reg.to_prometheus()
        # Raw control characters never appear inside a sample line.
        for line in text.splitlines():
            assert "\n" not in line
        samples, _ = _parse_exposition(text)
        assert samples[("nasty_total",
                        (("path", nasty),))] == 3
        assert samples[("nasty_seconds_bucket",
                        (("le", "1"), ("path", nasty)))] == 1

    def test_help_text_escaped(self):
        reg = MetricsRegistry()
        reg.counter("h_total", "line one\nline two \\ backslash")
        text = reg.to_prometheus()
        help_lines = [l for l in text.splitlines()
                      if l.startswith("# HELP h_total")]
        assert help_lines == [
            "# HELP h_total line one\\nline two \\\\ backslash"]
        _parse_exposition(text)  # still parses as a whole


# ------------------------------------------------------------------ #
# thread-safety battery


class TestThreadSafety:
    N_THREADS = 8
    N_OPS = 5000

    def test_concurrent_counter_inc_loses_nothing(self):
        reg = MetricsRegistry()
        counter = reg.counter("conc_total", "t")
        barrier = threading.Barrier(self.N_THREADS)

        def work(i):
            bound = counter.bind(worker=str(i % 2))
            barrier.wait()
            for _ in range(self.N_OPS):
                counter.inc()
                bound.inc()

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert counter.value() == self.N_THREADS * self.N_OPS
        assert (counter.value(worker="0") + counter.value(worker="1")
                == self.N_THREADS * self.N_OPS)

    def test_concurrent_histogram_observe(self):
        reg = MetricsRegistry()
        hist = reg.histogram("conc_seconds", "t", buckets=(0.5,))
        barrier = threading.Barrier(self.N_THREADS)

        def work():
            barrier.wait()
            for i in range(self.N_OPS):
                hist.observe(0.25 if i % 2 else 0.75)

        threads = [threading.Thread(target=work)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = self.N_THREADS * self.N_OPS
        assert hist.count() == total
        assert hist.sum() == pytest.approx(total * 0.5)

    def test_export_during_active_recording(self, tmp_path):
        """export_chrome while other threads record: the export is a
        consistent snapshot (valid JSON, well-formed events), no
        crash, and recording continues unhindered.  No recorder
        stops before the first export is read back: past its quota
        it waits on that event between spans (not a race against the
        clock), and each is BOUNDED (an unbounded spin would grow the
        buffers faster than the ever-larger exports can serialize
        them)."""
        t = Tracer()
        t.enable()
        recording = threading.Semaphore(0)
        exported = threading.Event()
        errors = []
        spans_per_thread = 2000
        cap = 10 * spans_per_thread
        counts = [0] * self.N_THREADS

        def recorder(i):
            try:
                n = 0
                while n < spans_per_thread or not (
                        n >= cap or exported.wait(0.001)):
                    with t.span(f"work{i}", "t", n=i):
                        t.instant("tick", "t")
                    n += 1
                    if n == 1:
                        recording.release()
                counts[i] = n
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=recorder, args=(i,))
                   for i in range(self.N_THREADS)]
        for th in threads:
            th.start()
        try:
            for _ in threads:
                assert recording.acquire(timeout=30)
            rounds = 0
            while rounds < 5 and (
                    rounds == 0
                    or any(th.is_alive() for th in threads)):
                path = tmp_path / f"live{rounds}.json"
                t.export_chrome(str(path))
                events = load_trace_file(str(path))
                for ev in events:
                    assert "name" in ev and "ts" in ev
                rounds += 1
                exported.set()
        finally:
            exported.set()
            for th in threads:
                th.join(timeout=60)
        assert not errors
        assert min(counts) >= spans_per_thread
        t.disable()
        # The buffers survived concurrent export: the final export
        # holds every span from every worker lane.
        final = tmp_path / "final.json"
        t.export_chrome(str(final))
        events = load_trace_file(str(final))
        spans = [e for e in events
                 if e["name"].startswith("work")]
        assert len(spans) == sum(counts)
