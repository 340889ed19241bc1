"""Observability subsystem battery: tracer, metrics registry, stats
shim, engine probe, and the end-to-end chaos-trace contract.

Acceptance targets (ISSUE 2): a chaos run's trace contains agent
step, message send/recv, injected fault drop, breaker trip and
checkpoint write spans and summarizes cleanly; metrics snapshots carry
a monotone cycle counter and a MaxSum cost-vs-cycle curve whose final
point equals the reported cost; Prometheus output is well-formed; and
disabled tracing adds no events and no per-call allocations.
"""

import json
import os
import re
import threading
import time

import pytest

from pydcop_tpu.observability.metrics import (
    CycleSnapshotter,
    MetricsRegistry,
)
from pydcop_tpu.observability.trace import (
    NOOP_SPAN,
    Tracer,
    check_well_nested,
    load_trace_file,
    summarize_spans,
    tracer,
)

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import constraint_from_str
from pydcop_tpu.distribution.objects import Distribution


# ------------------------------------------------------------------ #
# fixtures


def _coloring_dcop(n_vars=4, n_agents=5):
    d = Domain("colors", "", ["R", "G", "B"])
    dcop = DCOP("obs", objective="min")
    variables = [Variable(f"v{i}", d) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    for i in range(n_vars - 1):
        dcop.add_constraint(constraint_from_str(
            f"diff_{i}_{i + 1}",
            f"10 if v{i} == v{i + 1} else 0",
            [variables[i], variables[i + 1]],
        ))
    dcop.add_agents([
        AgentDef(f"a{i}", capacity=100, default_hosting_cost=i)
        for i in range(n_agents)
    ])
    return dcop


def _ring_dcop(n_vars=6):
    d = Domain("c", "", list(range(3)))
    dcop = DCOP("ring", objective="min")
    variables = [Variable(f"v{i}", d) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    edges = [(i, (i + 1) % n_vars) for i in range(n_vars)] + [(0, 3)]
    for i, j in edges:
        dcop.add_constraint(constraint_from_str(
            f"c_{i}_{j}", f"5 if v{i} == v{j} else 0",
            [variables[i], variables[j]],
        ))
    dcop.add_agents([AgentDef("a0")])
    return dcop


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the process tracer disabled."""
    tracer.disable()
    tracer.clear()
    yield
    tracer.disable()
    tracer.clear()


# ------------------------------------------------------------------ #
# tracer


class TestTracer:
    def test_span_nesting_and_parent_ids(self):
        t = Tracer()
        t.enable()
        with t.span("outer", "test", a=1):
            with t.span("inner", "test"):
                t.instant("point", "test", b=2)
        events = t.events()
        # Sorted by ts; spans are start-stamped (recorded on exit).
        assert [e["name"] for e in events] == [
            "outer", "inner", "point"]
        outer = next(e for e in events if e["name"] == "outer")
        inner = next(e for e in events if e["name"] == "inner")
        point = next(e for e in events if e["name"] == "point")
        assert outer["parent"] == 0
        assert inner["parent"] == outer["id"]
        assert point["parent"] == inner["id"]
        assert inner["ts"] >= outer["ts"]
        assert inner["dur"] <= outer["dur"]

    def test_enable_clears_previous_session(self):
        t = Tracer()
        t.enable()
        t.instant("old", "test")
        t.enable()
        t.instant("new", "test")
        assert [e["name"] for e in t.events()] == ["new"]

    def test_export_chrome_loads_and_nests(self, tmp_path):
        t = Tracer()
        t.enable()
        with t.span("a", "test"):
            with t.span("b", "test"):
                pass
            with t.span("c", "test"):
                pass
        path = str(tmp_path / "trace.json")
        t.export_chrome(path)
        data = json.load(open(path, encoding="utf-8"))
        names = {e["name"] for e in data["traceEvents"]}
        assert {"a", "b", "c", "thread_name"} <= names
        events = load_trace_file(path)
        check_well_nested(events)
        # Every exported event carries pid/tid and spans carry dur.
        for ev in events:
            assert "pid" in ev and "tid" in ev
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_export_jsonl(self, tmp_path):
        t = Tracer()
        t.enable()
        t.instant("x", "test", k="v")
        path = str(tmp_path / "trace.jsonl")
        t.export_jsonl(path)
        rows = [json.loads(line) for line in open(path)]
        # Line 1 is the process-identity/clock-anchor header (ISSUE 5
        # multi-process merge); events follow.
        assert "pydcop_trace_header" in rows[0]
        assert rows[1]["name"] == "x"
        assert rows[1]["args"] == {"k": "v"}
        assert "thread" in rows[1]
        # load_trace_file returns events only (header excluded).
        assert load_trace_file(path)[0]["name"] == "x"

    def test_multithreaded_buffers(self):
        t = Tracer()
        t.enable()

        def work(i):
            for _ in range(50):
                t.instant(f"ev{i}", "test")

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        events = t.events()
        assert len(events) == 200
        assert len({e["tid"] for e in events}) == 4

    def test_check_well_nested_rejects_overlap(self):
        events = [
            {"ph": "X", "name": "a", "ts": 0.0, "dur": 100.0, "tid": 1},
            {"ph": "X", "name": "b", "ts": 50.0, "dur": 100.0, "tid": 1},
        ]
        with pytest.raises(ValueError, match="overlaps"):
            check_well_nested(events)

    def test_summarize_spans(self):
        events = [
            {"ph": "X", "name": "a", "cat": "t", "ts": 0, "dur": 2000.0},
            {"ph": "X", "name": "a", "cat": "t", "ts": 0, "dur": 4000.0},
            {"ph": "i", "name": "b", "cat": "t", "ts": 0},
        ]
        rows = summarize_spans(events, top=5)
        assert rows[0]["name"] == "a"
        assert rows[0]["count"] == 2
        assert rows[0]["total_ms"] == pytest.approx(6.0)
        assert rows[0]["max_ms"] == pytest.approx(4.0)
        assert rows[1] == {"name": "b", "count": 1, "total_ms": 0.0,
                           "mean_ms": 0.0, "max_ms": 0.0}


class TestZeroOverheadWhenOff:
    """Disabled tracing must be one flag check: no events, no per-call
    span allocation (the shared NOOP singleton), instrumented hot
    sites short-circuit.

    Since PR 9 the always-on flight recorder keeps ``tracer.active``
    true (events flow to its ring even while file tracing is off), so
    the zero-overhead contract applies to the FULLY-off state: ring
    detached AND session disabled.  The fixture detaches the default
    ring for the duration; TestFlightRecorder covers the ring-attached
    behavior."""

    @pytest.fixture(autouse=True)
    def _detach_flight(self):
        saved = tracer.flight
        tracer.set_flight(None)
        yield
        tracer.set_flight(saved)

    def test_span_returns_shared_noop_singleton(self):
        assert not tracer.enabled
        s1 = tracer.span("x", "t")
        s2 = tracer.span("y", "t", arg=1)
        assert s1 is NOOP_SPAN and s2 is NOOP_SPAN

    def test_no_events_recorded_while_off(self):
        tracer.instant("x", "t", a=1)
        with tracer.span("y", "t"):
            pass
        assert tracer.events() == []

    def test_instrumented_runtime_sites_emit_nothing(self):
        from pydcop_tpu.infrastructure.communication import (
            InProcessCommunicationLayer,
            Messaging,
        )
        from pydcop_tpu.infrastructure.computations import Message

        messaging = Messaging("zoh", InProcessCommunicationLayer())
        messaging.register_computation("c")
        for _ in range(10):
            messaging.post_msg("x", "c", Message("algo", 1))
        assert tracer.events() == []

    @pytest.mark.parametrize("path", ["solve", "serve"])
    def test_the_solve_and_flush_sites_make_no_span(self, path,
                                                    monkeypatch):
        """ISSUE 41's sites (``build_engine``, ``engine_place``,
        ``result_decode``, ``result_cost``; ``sched_plan``,
        ``serve_launch``, ``serve_decode``; the collector's hook) with
        the tracer off: a solve and a served request make no ``_Span``,
        read no thread clock and record nothing."""
        from pydcop_tpu import api
        from pydcop_tpu.observability import trace as trace_mod

        made, reads = [], []
        real = trace_mod._Span.__init__

        def counting(self, *args, **kwargs):
            made.append(args[1])
            real(self, *args, **kwargs)

        class Clock:
            perf_counter = staticmethod(time.perf_counter)
            time = staticmethod(time.time)

            @staticmethod
            def thread_time_ns():
                reads.append(1)
                return time.thread_time_ns()

        monkeypatch.setattr(trace_mod._Span, "__init__", counting)
        monkeypatch.setattr(trace_mod, "time", Clock)
        assert not tracer.active
        dcop = _coloring_dcop()
        if path == "solve":
            result = api.solve(dcop, "maxsum", max_cycles=10)
            assert result["status"] in ("FINISHED", "TIMEOUT")
        else:
            with api.serve(port=0, batch_window_s=0.005, max_batch=2,
                           max_queue=8) as handle:
                # Cold, then warm: the synchronous and the pipelined
                # flush.
                for _ in range(2):
                    rid = handle.service.submit(
                        dcop, params={"max_cycles": 10})
                    answer = handle.service.result(rid, wait=120)
                    assert answer["status"] == "FINISHED"
        assert made == [] and reads == []
        assert tracer.events() == []

    def test_noop_span_reused_across_many_calls(self):
        # The identity check IS the zero-allocation assertion: every
        # disabled call returns the same singleton, so no span object
        # is ever allocated while off.
        spans = {id(tracer.span(f"s{i}", "t")) for i in range(100)}
        assert spans == {id(NOOP_SPAN)}


# ------------------------------------------------------------------ #
# metrics registry


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (-?[0-9.e+-]+|\+Inf)"
    # Optional OpenMetrics exemplar: ` # {trace_id="..."} value ts`
    # (bucket samples carry one once anything observed with an
    # exemplar — e.g. the serve plane's latency histogram).
    r"( # \{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\"\}"
    r" -?[0-9.e+-]+( [0-9.]+)?)?$"
)


class TestMetricsRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", "help")
        c.inc()
        c.inc(2.5, kind="x")
        assert c.value() == 1
        assert c.value(kind="x") == 2.5
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_and_bound_handles(self):
        reg = MetricsRegistry()
        g = reg.gauge("g", "")
        bound = g.bind(agent="a1")
        bound.set(3.0)
        bound.inc(1.0)
        assert g.value(agent="a1") == 4.0
        assert bound.value() == 4.0

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", "", buckets=(0.01, 1.0))
        h.observe(0.005)
        h.observe(0.5)
        h.observe(30.0)
        assert h.count() == 3
        assert h.sum() == pytest.approx(30.505)

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("m", "")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("m", "")

    def test_get_or_create_is_idempotent(self):
        reg = MetricsRegistry()
        assert reg.counter("c", "") is reg.counter("c", "")

    def test_prometheus_text_wellformed(self):
        reg = MetricsRegistry()
        reg.counter("msgs_total", "Messages").inc(
            3, type="value", direction="in")
        reg.gauge("depth", "Queue depth").set(7, agent="a1")
        h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0))
        h.observe(0.05, op="send")
        text = reg.to_prometheus()
        lines = text.strip().splitlines()
        families = set()
        for line in lines:
            if line.startswith("# HELP "):
                families.add(line.split()[2])
            elif line.startswith("# TYPE "):
                parts = line.split()
                assert parts[2] in families, "TYPE before HELP"
                assert parts[3] in ("counter", "gauge", "histogram")
            else:
                assert _PROM_SAMPLE.match(line), line
        assert {"msgs_total", "depth", "lat_seconds"} <= families
        assert 'lat_seconds_bucket{le="+Inf",op="send"} 1' in lines
        assert "lat_seconds_count" in text

    def test_snapshot_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c_total", "").inc(4)
        path = str(tmp_path / "m.jsonl")
        reg.write_snapshot(path, cycle=10)
        reg.write_snapshot(path, cycle=20)
        rows = [json.loads(line) for line in open(path)]
        assert [r["cycle"] for r in rows] == [10, 20]
        sample = rows[0]["metrics"]["c_total"]["samples"][0]
        assert sample == {"labels": {}, "value": 4}


class TestCycleSnapshotter:
    def test_monotone_counter_and_cadence(self, tmp_path):
        reg = MetricsRegistry()
        path = str(tmp_path / "m.jsonl")
        snap = CycleSnapshotter(path, every=5, reg=reg)
        snap(2)      # below cadence from 0? delta=2 -> first write
        snap(3)      # +1 < 5: skipped
        snap(1)      # regression: skipped (counter must stay monotone)
        snap(8)      # +6: written
        snap(8)      # no advance: skipped
        rows = [json.loads(line) for line in open(path)]
        assert [r["cycle"] for r in rows] == [2, 8]
        assert reg.value("pydcop_cycles_total") == 8
        assert reg.value("pydcop_cycle") == 8

    def test_cost_fn_called_only_on_snapshot(self, tmp_path):
        reg = MetricsRegistry()
        calls = []

        def cost():
            calls.append(1)
            return 42.0

        snap = CycleSnapshotter(str(tmp_path / "m.jsonl"), every=10,
                                reg=reg, cost_fn=cost)
        for cycle in range(1, 10):
            snap(cycle)
        assert calls == [1]  # only the first write (cycle 1) fired
        snap(11)
        assert len(calls) == 2
        assert reg.value("pydcop_cost") == 42.0


# ------------------------------------------------------------------ #
# stats shim (reference CSV parity + atomic swap regression)


class TestStatsShim:
    def test_forwards_rows_to_tracer(self, tmp_path):
        from pydcop_tpu.infrastructure import stats

        tracer.enable()
        try:
            path = str(tmp_path / "steps.csv")
            stats.set_stats_file(path)
            try:
                stats.trace_computation("v1", 0.02, 1, 3, 2, 4,
                                        value="R")
            finally:
                stats.set_stats_file(None)
        finally:
            tracer.disable()
        events = [e for e in tracer.events()
                  if e["name"] == "computation_step"]
        assert len(events) == 1
        assert events[0]["args"]["computation"] == "v1"
        assert events[0]["args"]["value"] == "R"
        # And the CSV row still landed (reference parity).
        lines = open(path).read().strip().splitlines()
        assert lines[1].split(",")[1] == "v1"

    def test_forwards_without_csv_file(self):
        from pydcop_tpu.infrastructure import stats

        tracer.enable()
        try:
            stats.trace_computation("v2", 0.01)
        finally:
            tracer.disable()
        assert [e["args"]["computation"] for e in tracer.events()
                if e["name"] == "computation_step"] == ["v2"]

    def test_failed_switch_keeps_previous_writer(self, tmp_path):
        """Regression: a failing open() mid-switch used to close the
        old file first and leave the globals half-cleared — callers
        believed tracing was on while every row vanished."""
        from pydcop_tpu.infrastructure import stats

        good = str(tmp_path / "good.csv")
        stats.set_stats_file(good)
        try:
            stats.trace_computation("before", 0.01)
            with pytest.raises(OSError):
                stats.set_stats_file(
                    str(tmp_path / "no_such_dir" / "bad.csv"))
            # Previous state intact: still enabled, still writing to
            # the original file.
            assert stats.tracing_enabled()
            stats.trace_computation("after", 0.01)
        finally:
            stats.set_stats_file(None)
        rows = open(good).read().strip().splitlines()
        assert [r.split(",")[1] for r in rows[1:]] == ["before",
                                                       "after"]

    def test_close_is_idempotent(self, tmp_path):
        from pydcop_tpu.infrastructure import stats

        stats.set_stats_file(str(tmp_path / "x.csv"))
        stats.close()
        stats.close()
        assert not stats.tracing_enabled()
        stats.trace_computation("v", 0.01)  # no-op, must not raise


# ------------------------------------------------------------------ #
# engine probe (device-mode cost/convergence telemetry)


class TestEngineProbe:
    def test_probed_solve_curve_matches_reported_cost(self, tmp_path):
        from pydcop_tpu.api import solve

        from pydcop_tpu.observability.metrics import registry

        metrics_file = str(tmp_path / "m.jsonl")
        trace_file = str(tmp_path / "t.json")
        # The cycle counter is process-global and monotone across
        # solves: assert this solve's DELTA, not an absolute value
        # that depends on what ran before in the process.
        cycles_before = registry.value("pydcop_cycles_total")
        res = solve(
            _ring_dcop(), "maxsum", backend="device", max_cycles=80,
            trace=trace_file, metrics_file=metrics_file,
            metrics_every=10,
        )
        curve = res["metrics"]["cost_curve"]
        assert curve, "probed solve produced no cost curve"
        cycles = [c for c, _ in curve]
        assert cycles == sorted(cycles)
        assert cycles[-1] == res["cycles"]
        # The acceptance contract: the curve's final point equals the
        # solver's reported cost.
        assert curve[-1][1] == pytest.approx(res["cost"])
        # JSONL snapshots: monotone cycle counter, parsable lines.
        rows = [json.loads(line) for line in open(metrics_file)]
        snap_cycles = [r["cycle"] for r in rows]
        assert snap_cycles == sorted(snap_cycles)
        total = rows[-1]["metrics"]["pydcop_cycles_total"]
        assert total["samples"][0]["value"] - cycles_before \
            == snap_cycles[-1]
        # Prometheus dump parses.
        prom = open(metrics_file + ".prom").read()
        assert "# HELP pydcop_cycles_total" in prom
        assert "# TYPE pydcop_cycles_total counter" in prom
        for line in prom.strip().splitlines():
            if not line.startswith("#"):
                assert _PROM_SAMPLE.match(line), line
        # Trace: engine chunks + segments present, well nested.
        events = load_trace_file(trace_file)
        names = {e["name"] for e in events}
        assert {"solve", "engine_segment", "chunk"} <= names
        check_well_nested(events)

    def test_probe_without_files_collects_points(self):
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.observability.engine_probe import EngineProbe
        from pydcop_tpu.observability.metrics import MetricsRegistry

        engine = build_engine(_ring_dcop(), {})
        probe = EngineProbe(engine, registry=MetricsRegistry())
        res = engine.run_checkpointed(
            max_cycles=40, segment_cycles=10, probe=probe)
        assert len(probe.chunks) == res.metrics["segments"]
        assert all(s >= 0 for _, _, _, s in probe.chunks)
        assert probe.cost_curve()[-1][0] == res.cycles


# ------------------------------------------------------------------ #
# agent metrics parity (registry-sourced totals)


class TestAgentMetricsParity:
    def test_totals_match_per_computation_dicts(self):
        from pydcop_tpu.algorithms import AlgorithmDef
        from pydcop_tpu.infrastructure.run import solve_with_agents

        algo = AlgorithmDef.build_with_default_param(
            "dsa", {"stop_cycle": 15}, mode="min")
        res = solve_with_agents(
            _coloring_dcop(), algo,
            distribution=Distribution({
                "a0": ["v0"], "a1": ["v1"], "a2": ["v2"],
                "a3": ["v3"], "a4": [],
            }),
            timeout=6,
        )
        agt_metrics = res["agt_metrics"]
        assert agt_metrics
        for name, metrics in agt_metrics.items():
            assert metrics["msg_count"] == sum(
                metrics["count_ext_msg"].values()), name
            assert metrics["msg_size"] == sum(
                metrics["size_ext_msg"].values()), name
            activity = metrics["activity"]
            assert activity["active_s"] >= 0
            assert activity["total_s"] >= activity["active_s"]
            assert metrics["activity_ratio"] == pytest.approx(
                activity["active_s"] / activity["total_s"], rel=1e-6)
        # Orchestrator end-metrics aggregate the same counters.
        assert res["msg_count"] == sum(
            m["msg_count"] for m in agt_metrics.values())
        assert res["msg_size"] == sum(
            m["msg_size"] for m in agt_metrics.values())


# ------------------------------------------------------------------ #
# end-to-end: a chaos run is fully reconstructable from one trace


class TestChaosTraceReconstruction:
    def test_one_trace_carries_all_required_span_kinds(self, tmp_path,
                                                       capsys):
        """Agent step, message send/recv, injected fault drop, breaker
        trip and checkpoint write all land in ONE tracing session, the
        exported Chrome trace validates, and ``pydcop trace summary``
        aggregates it without error."""
        from pydcop_tpu.api import solve
        from pydcop_tpu.infrastructure.run import solve_with_agents
        from pydcop_tpu.resilience.faults import FaultPlan
        from pydcop_tpu.resilience.retry import CircuitBreaker

        tracer.enable()
        try:
            # 1. Thread-mode chaos solve: agent steps, send/recv,
            # fault drops.
            solve_with_agents(
                _coloring_dcop(), "amaxsum",
                distribution=Distribution({
                    "a0": ["v0", "diff_0_1"], "a1": ["v1"],
                    "a2": ["v2", "diff_1_2"],
                    "a3": ["v3", "diff_2_3"], "a4": [],
                }),
                timeout=3,
                fault_plan=FaultPlan(seed=42, drop=0.3),
            )
            # 2. Device checkpointed solve: checkpoint_write spans.
            solve(
                _ring_dcop(), "maxsum", backend="device",
                max_cycles=30,
                checkpoint_dir=str(tmp_path / "ckpt"),
                checkpoint_every=10,
            )
            # 3. A destination failing repeatedly: breaker trip.
            breaker = CircuitBreaker(2, 1.0, name="a_dead")
            breaker.record_failure()
            breaker.record_failure()
        finally:
            tracer.disable()
        trace_file = str(tmp_path / "chaos.json")
        tracer.export_chrome(trace_file)
        events = load_trace_file(trace_file)
        names = {e["name"] for e in events}
        required = {"agent_step", "message_send", "message_recv",
                    "fault_drop", "breaker_trip", "checkpoint_write"}
        assert required <= names, f"missing: {required - names}"
        check_well_nested(events)
        # The summary command aggregates it without error.
        from pydcop_tpu.dcop_cli import main

        assert main(["trace", "summary", trace_file]) == 0
        out = capsys.readouterr().out
        assert "agent_step" in out
