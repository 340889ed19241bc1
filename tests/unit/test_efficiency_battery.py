"""Battery for the device-efficiency accounting plane (ISSUE 14):
ledger assembly + the components-sum-to-total invariant across solo,
binned, envelope-packed, lane-packed and session dispatch paths;
attainment math on synthetic cost entries; the tracker rollup
(per-backend / per-structure separation, waste by cause); the
``/profile`` endpoint and ``pydcop profile report --json`` schemas;
backend-label propagation into the metrics exposition; the dynamic
engine's deferred-edit batching (behavior-identical to per-action
application, incl. mid-batch recompile and the failed-batch
partial-apply contract); and the postmortem bundle's efficiency
section."""

import json

import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine import batch as engine_batch
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.engine.dynamic import DynamicMaxSumEngine
from pydcop_tpu.observability import efficiency
from pydcop_tpu.observability.efficiency import (
    EfficiencyTracker,
    attainment_from_cost,
    ledger_component_sum,
    make_ledger,
    resolved_backend,
    split_device_time,
)
from pydcop_tpu.observability.metrics import registry
from pydcop_tpu.serving.service import SolveService

MAX_CYCLES = 40
PARAMS = {"max_cycles": MAX_CYCLES}
LEDGER_TOL = 0.05


@pytest.fixture(autouse=True)
def _fresh_plane():
    registry.reset()
    efficiency.tracker.clear()
    yield
    registry.reset()
    efficiency.tracker.clear()


def _ring(n: int, seed: int, d: int = 3) -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", list(range(d)))
    dcop = DCOP(f"ring{n}_{seed}_{d}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n):
        table = rng.integers(0, 10, size=(d, d)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % n]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def _assert_ledger_sums(ledger, tol=LEDGER_TOL):
    assert isinstance(ledger, dict) and ledger.get("total_s", 0) > 0
    gap = abs(ledger_component_sum(ledger) - ledger["total_s"])
    assert gap <= tol * ledger["total_s"], ledger


# ------------------------------------------------------------------ #
# ledger helpers
# ------------------------------------------------------------------ #

class TestLedger:
    def test_make_ledger_sums_and_rounds(self):
        ledger = make_ledger(1.0, submit=0.1, queue=0.2, plan=0.05,
                             prep=0.05, compile=0.3, execute=0.25,
                             decode=0.05)
        assert ledger["total_s"] == 1.0
        assert abs(ledger_component_sum(ledger) - 1.0) < 1e-9
        assert abs(ledger["unaccounted_s"]) < 1e-9

    def test_unaccounted_is_honest_not_absorbed(self):
        ledger = make_ledger(1.0, execute=0.4)
        assert ledger["unaccounted_s"] == pytest.approx(0.6)

    def test_unknown_component_rejected(self):
        with pytest.raises(ValueError, match="unknown ledger"):
            make_ledger(1.0, warp=0.5)

    def test_negative_components_clamped(self):
        ledger = make_ledger(0.5, queue=-0.1, execute=0.5)
        assert ledger["queue_s"] == 0.0

    def test_split_device_time_cold_and_warm(self):
        # Cold: overlapping-fields convention (compile == time) —
        # the whole interval charges to compile, execute 0.
        cold = split_device_time(0.8, 0.8)
        assert cold == {"compile": 0.8, "execute": 0.0}
        warm = split_device_time(0.8, 0.0)
        assert warm == {"compile": 0.0, "execute": 0.8}
        assert sum(cold.values()) == sum(warm.values()) == 0.8


# ------------------------------------------------------------------ #
# attainment math on synthetic cost entries
# ------------------------------------------------------------------ #

class TestAttainment:
    def test_exact_numbers_against_env_peaks(self, monkeypatch):
        monkeypatch.setenv("PYDCOP_PEAK_FLOPS", "1e9")
        monkeypatch.setenv("PYDCOP_PEAK_BYTES_PER_S", "1e10")
        entry = {"available": True, "flops": 1e5,
                 "bytes_accessed": 2e5}
        att = attainment_from_cost(entry, cycles=100,
                                   execute_s=0.1, backend="cpu")
        # achieved flops/s = 1e5 * 100 / 0.1 = 1e8 -> 0.1 of peak.
        assert att["flop_attainment"] == pytest.approx(0.1)
        # achieved B/s = 2e5 * 100 / 0.1 = 2e8 -> 0.02 of peak.
        assert att["bandwidth_attainment"] == pytest.approx(0.02)
        # Roofline verdict: the binding (better-attained) resource.
        assert att["attainment"] == pytest.approx(0.1)
        assert att["peak_source"] == "env"

    @pytest.mark.parametrize("backend,kind,known", [
        ("tpu", "TPU v5 lite", True),
        ("tpu", "TPU v99 imaginary", False),
        ("tpu", None, False),       # this process runs on the CPU
        ("gpu", None, False),
        ("cpu", None, True),
    ])
    def test_backend_peaks_known_or_none(self, monkeypatch, backend,
                                         kind, known):
        """Chip peaks come from engine.roofline.TPU_PEAKS by
        device_kind; a device that is not in the table gets None —
        no attainment — never a default."""
        from pydcop_tpu.engine.roofline import TPU_PEAKS
        from pydcop_tpu.observability.efficiency import backend_peaks

        monkeypatch.delenv("PYDCOP_PEAK_FLOPS", raising=False)
        monkeypatch.delenv("PYDCOP_PEAK_BYTES_PER_S", raising=False)
        peaks = backend_peaks(backend, kind)
        if not known:
            assert peaks is None
            entry = {"available": True, "flops": 1e5,
                     "bytes_accessed": 2e5}
            if kind is None:
                assert attainment_from_cost(
                    entry, 100, 0.1, backend) is None
            return
        assert peaks["source"] == "default"
        if backend == "tpu":
            assert (peaks["flops_per_s"],
                    peaks["bytes_per_s"]) == TPU_PEAKS[kind]

    def test_unavailable_entry_is_none_not_zero(self):
        assert attainment_from_cost(
            {"available": False}, 10, 0.1, "cpu") is None
        assert attainment_from_cost(None, 10, 0.1, "cpu") is None

    def test_zero_execute_or_cycles_is_none(self):
        entry = {"available": True, "flops": 1e5}
        assert attainment_from_cost(entry, 0, 0.1, "cpu") is None
        assert attainment_from_cost(entry, 10, 0.0, "cpu") is None

    def test_useful_work_fraction_discounts_waste(self):
        tracker = EfficiencyTracker()
        tracker.enabled = True
        record = tracker.record_dispatch(
            key="k", structure="s", backend="cpu",
            time_s=0.1, compile_s=0.0, cycles=10,
            n_real=2, batch_size=4, pad_fraction=0.5,
            envelope_waste=0.2, packing="envelope",
            cost_entry={"available": True, "flops": 1e6})
        assert record["attainment"] is not None
        assert record["useful_work_fraction"] == pytest.approx(
            record["attainment"] * 0.5 * 0.8)

    def test_disabled_tracker_records_nothing(self):
        tracker = EfficiencyTracker()
        tracker.enabled = False
        assert tracker.record_dispatch(
            key="k", structure="s", backend="cpu", time_s=0.1,
            compile_s=0.0, cycles=10, n_real=1,
            batch_size=1) is None
        assert tracker.rollup()["structures"] == []


# ------------------------------------------------------------------ #
# tracker rollup
# ------------------------------------------------------------------ #

class TestRollup:
    def _tracker(self):
        tracker = EfficiencyTracker()
        tracker.enabled = True
        entry = {"available": True, "flops": 1e6,
                 "bytes_accessed": 1e6}
        # Two backends, two structures on cpu; devices separated.
        for backend, structure, execute in (
                ("cpu", "sA", 0.2), ("cpu", "sA", 0.2),
                ("cpu", "sB", 0.1), ("tpu", "sA", 0.01)):
            tracker.record_dispatch(
                key="k", structure=structure, backend=backend,
                time_s=execute, compile_s=0.0, cycles=50,
                n_real=1, batch_size=1, cost_entry=entry)
        return tracker

    def test_backends_never_share_a_rollup(self):
        roll = self._tracker().rollup()
        assert set(roll["backends"]) == {"cpu", "tpu"}
        assert roll["backends"]["cpu"]["dispatches"] == 3
        assert roll["backends"]["tpu"]["dispatches"] == 1
        # The tpu cell was recorded in a process that does not run
        # on a TPU, so its chip kind — hence its peak — is not known:
        # no attainment figure, never one borrowed from a default.
        assert roll["backends"]["cpu"]["attainment"] is not None
        assert "attainment" not in roll["backends"]["tpu"]

    def test_structures_ranked_by_device_time(self):
        roll = self._tracker().rollup()
        assert roll["structures"][0]["structure"] == "sA"
        assert roll["structures"][0]["backend"] == "cpu"
        assert roll["structures_total"] == 3

    def test_waste_by_cause_and_ledger_totals(self):
        tracker = self._tracker()
        tracker.record_jit("k", True, 0.5)
        tracker.record_ledger(make_ledger(
            1.0, queue=0.4, execute=0.6), backend="cpu")
        roll = tracker.rollup()
        assert roll["waste_by_cause"]["compile_s"] == \
            pytest.approx(0.5)
        assert roll["waste_by_cause"]["queue_s"] == \
            pytest.approx(0.4)
        assert roll["ledger"]["components_s"]["execute"] == \
            pytest.approx(0.6)
        assert roll["ledger"]["counts"] == {"request": 1}

    def test_pad_waste_charged_from_execute(self):
        tracker = EfficiencyTracker()
        tracker.enabled = True
        tracker.record_dispatch(
            key="k", structure="s", backend="cpu", time_s=1.0,
            compile_s=0.0, cycles=10, n_real=1, batch_size=2,
            pad_fraction=0.5)
        roll = tracker.rollup()
        assert roll["backends"]["cpu"]["pad_waste_s"] == \
            pytest.approx(0.5)

    def test_summary_is_compact_and_backend_labeled(self):
        summary = self._tracker().summary()
        assert summary["backend"] == resolved_backend()["backend"]
        assert "ledger_components_s" in summary
        assert "waste_by_cause" in summary


# ------------------------------------------------------------------ #
# ledger invariant across the real dispatch paths
# ------------------------------------------------------------------ #

class TestServiceLedgers:
    def _serve_burst(self, dcops, service_kw=None, params=None):
        service = SolveService(batch_window_s=0.05, max_batch=16,
                               **(service_kw or {})).start()
        try:
            ids = [service.submit(d, params=params or PARAMS)
                   for d in dcops]
            results = [service.result(i, wait=60) for i in ids]
        finally:
            service.stop()
        assert all(r is not None and r["status"] == "FINISHED"
                   for r in results), results
        return results

    def test_solo_and_binned_ledgers_sum(self):
        # 3 same-structure (one binned dispatch) + 1 other (solo).
        results = self._serve_burst(
            [_ring(6, s) for s in range(3)] + [_ring(10, 9)])
        for res in results:
            _assert_ledger_sums(res["ledger"])
        kinds = {res["batch"]["packing"] for res in results}
        assert "structure" in kinds

    def test_envelope_packed_ledgers_sum(self):
        # Distinct structures, prune=1 keeps them off the lane path,
        # zero modeled overhead forces the pack.
        results = self._serve_burst(
            [_ring(n, n) for n in (6, 9, 12)],
            service_kw={"envelope_overhead_ms": 1e6, "lane_pack": False},
            params={"max_cycles": MAX_CYCLES})
        for res in results:
            _assert_ledger_sums(res["ledger"])
        assert any(res["batch"]["packing"] == "envelope"
                   for res in results), [
                       r["batch"] for r in results]

    def test_lane_packed_ledgers_sum(self):
        results = self._serve_burst(
            [_ring(n, n) for n in (6, 9, 12)],
            service_kw={"envelope_overhead_ms": 1e6})
        for res in results:
            _assert_ledger_sums(res["ledger"])
        assert any(res["batch"]["packing"] == "lane"
                   for res in results), [
                       r["batch"] for r in results]

    def test_finished_requests_feed_the_rollup(self):
        self._serve_burst([_ring(6, s) for s in range(2)])
        roll = efficiency.tracker.rollup()
        assert roll["ledger"]["counts"].get("request", 0) >= 2
        assert roll["backends"], roll

    def test_session_segment_ledgers_sum(self):
        service = SolveService(batch_window_s=0.01).start()
        try:
            sess = service.sessions.open(
                _ring(8, 3), params={"max_cycles": 120,
                                     "segment_cycles": 30})
            out = service.sessions.apply_events(
                sess.id,
                [{"type": "change_factor", "name": "c0",
                  "variables": ["v0", "v1"],
                  "table": [[0, 5, 5], [5, 0, 5], [5, 5, 0]]}],
                wait=30.0)
            assert out["applied"] is True
            ledger = out["result"]["ledger"]
            _assert_ledger_sums(ledger)
            status = service.sessions.status(sess.id)
            _assert_ledger_sums(status["last"]["ledger"])
        finally:
            service.stop()
        assert efficiency.tracker.rollup()["ledger"]["counts"].get(
            "session", 0) >= 1

    def test_expired_request_still_carries_summing_ledger(self):
        service = SolveService(batch_window_s=0.01).start()
        try:
            req_id = service.submit(_ring(6, 0), params=PARAMS,
                                    deadline_s=1e-9)
            res = service.result(req_id, wait=30)
        finally:
            service.stop()
        if res is not None and res["status"] == "EXPIRED":
            _assert_ledger_sums(res["ledger"], tol=0.5)


# ------------------------------------------------------------------ #
# surfaces: /profile, /metrics labels, profile report
# ------------------------------------------------------------------ #

class TestSurfaces:
    def _burst(self):
        service = SolveService(batch_window_s=0.02).start()
        try:
            for rnd in range(2):  # warm round populates attainment
                ids = [service.submit(_ring(6, s), params=PARAMS)
                       for s in range(2)]
                for i in ids:
                    assert service.result(i, wait=60) is not None
            stats = service.stats()
        finally:
            service.stop()
        return stats

    def test_stats_efficiency_block(self):
        stats = self._burst()
        eff = stats["efficiency"]
        assert eff["backend"] == resolved_backend()["backend"]
        assert eff["useful_work_fraction"] is not None
        assert 0 < eff["useful_work_fraction"] <= 1.5
        assert eff["ledger_components_s"].get("execute", 0) > 0

    def test_metrics_exposition_is_backend_labeled(self):
        self._burst()
        text = registry.to_prometheus()
        backend = resolved_backend()["backend"]
        assert (f'pydcop_useful_work_fraction{{backend='
                f'"{backend}"}}') in text
        assert (f'pydcop_device_execute_seconds_total{{backend='
                f'"{backend}"') in text
        assert 'pydcop_request_ledger_seconds_total{' in text

    def test_profile_endpoint_schema(self):
        import urllib.request

        from pydcop_tpu.observability.server import TelemetryServer

        self._burst()
        server = TelemetryServer(port=0).start()
        try:
            with urllib.request.urlopen(
                    f"{server.url}/profile", timeout=30) as resp:
                doc = json.loads(resp.read())
        finally:
            server.stop()
        assert doc["backend"]["backend"] == \
            resolved_backend()["backend"]
        assert doc["structures"], doc
        assert set(doc["waste_by_cause"]) == {
            "padding_s", "envelope_s", "compile_s", "queue_s"}
        assert "components_s" in doc["ledger"]

    def test_profile_report_json_live(self):
        from pydcop_tpu.commands import profile as profile_cmd
        from pydcop_tpu.dcop_cli import make_parser

        self._burst()
        parser = make_parser()
        args = parser.parse_args(["profile", "report", "--json"])
        import io
        import sys as _sys

        out = io.StringIO()
        stdout, _sys.stdout = _sys.stdout, out
        try:
            rc = profile_cmd.run_report(args)
        finally:
            _sys.stdout = stdout
        assert rc == 0
        doc = json.loads(out.getvalue())
        assert doc["mode"] == ["self"]
        assert doc["live"]["backends"], doc

    def test_profile_report_trace_mode(self, tmp_path):
        from pydcop_tpu.commands.profile import trace_breakdown
        from pydcop_tpu.observability.trace import tracer

        tracer.enable()
        try:
            with tracer.span("serve_dispatch", "serving",
                             bin="v6d3habc"):
                with tracer.span("engine_segment", "engine"):
                    pass
            with tracer.span("jit_compile", "engine", key="k"):
                pass
        finally:
            tracer.disable()
        path = str(tmp_path / "trace.jsonl")
        tracer.export(path, "jsonl")
        doc = trace_breakdown([path])
        spans = {c["span"] for c in doc["components"]}
        assert {"serve_dispatch", "engine_segment",
                "jit_compile"} <= spans
        assert doc["structures"][0]["structure"] == "v6d3habc"


# ------------------------------------------------------------------ #
# deferred-edit batching (the PR-13 efficiency-note fix)
# ------------------------------------------------------------------ #

def _dyn_engine(n=8, seed=4, slack=0.5):
    dcop = _ring(n, seed)
    return DynamicMaxSumEngine(
        list(dcop.variables.values()),
        list(dcop.constraints.values()),
        noise_level=0.01, slack=slack)


def _apply_all(engine, actions, batched):
    from pydcop_tpu.engine.dynamic import apply_action

    import contextlib as _ctx

    ctx = engine.batch_edits() if batched else _ctx.nullcontext()
    errors = []
    with ctx:
        for a in actions:
            args = {k: v for k, v in a.items() if k != "type"}
            try:
                apply_action(engine, a["type"], args)
            except Exception as exc:  # noqa: BLE001
                errors.append(str(exc))
                break
    return errors


def _assert_engines_equal(a, b):
    np.testing.assert_array_equal(
        np.asarray(a.graph.var_costs), np.asarray(b.graph.var_costs))
    assert len(a.graph.buckets) == len(b.graph.buckets)
    for ba, bb in zip(a.graph.buckets, b.graph.buckets):
        np.testing.assert_array_equal(np.asarray(ba.costs),
                                      np.asarray(bb.costs))
        np.testing.assert_array_equal(np.asarray(ba.var_ids),
                                      np.asarray(bb.var_ids))
    assert a.slots == b.slots
    assert sorted(a.factors) == sorted(b.factors)
    if a._state is None or b._state is None:
        assert (a._state is None) == (b._state is None)
        return
    for leaf_a, leaf_b in zip(
            (*a._state.v2f, *a._state.f2v,
             *a._state.v2f_count, *a._state.f2v_count),
            (*b._state.v2f, *b._state.f2v,
             *b._state.v2f_count, *b._state.f2v_count)):
        np.testing.assert_array_equal(np.asarray(leaf_a),
                                      np.asarray(leaf_b))


MUTATION_LADDER = [
    {"type": "change_factor", "name": "c0",
     "variables": ["v0", "v1"],
     "table": [[0, 7, 7], [7, 0, 7], [7, 7, 0]]},
    {"type": "remove_factor", "name": "c3"},
    {"type": "add_factor", "name": "cX",
     "variables": ["v2", "v5"],
     "table": [[1, 2, 3], [4, 5, 6], [7, 8, 9]]},
    {"type": "change_factor", "name": "cX",
     "variables": ["v2", "v5"],
     "table": [[9, 8, 7], [6, 5, 4], [3, 2, 1]]},
    {"type": "remove_factor", "name": "c5"},
    {"type": "add_factor", "name": "cY",
     "variables": ["v6", "v7"],
     "table": [[0, 1, 0], [1, 0, 1], [0, 1, 0]]},
]


class TestBatchEdits:
    def test_batched_equals_sequential_cold(self):
        seq, bat = _dyn_engine(), _dyn_engine()
        assert not _apply_all(seq, MUTATION_LADDER, batched=False)
        assert not _apply_all(bat, MUTATION_LADDER, batched=True)
        _assert_engines_equal(seq, bat)

    def test_batched_equals_sequential_warm_state(self):
        seq, bat = _dyn_engine(), _dyn_engine()
        seq.run(max_cycles=30)
        bat.run(max_cycles=30)
        assert not _apply_all(seq, MUTATION_LADDER, batched=False)
        assert not _apply_all(bat, MUTATION_LADDER, batched=True)
        _assert_engines_equal(seq, bat)
        # And the post-event trajectories agree.
        ra = seq.run(max_cycles=60)
        rb = bat.run(max_cycles=60)
        assert ra.assignment == rb.assignment

    def test_recompile_mid_batch_matches_sequential(self):
        actions = MUTATION_LADDER[:2] + [
            {"type": "add_variable", "name": "w0",
             "domain": [0, 1, 2]},
            {"type": "add_factor", "name": "cW",
             "variables": ["w0", "v0"],
             "table": [[0, 2, 2], [2, 0, 2], [2, 2, 0]]},
        ] + MUTATION_LADDER[2:4]
        seq, bat = _dyn_engine(), _dyn_engine()
        seq.run(max_cycles=30)
        bat.run(max_cycles=30)
        assert not _apply_all(seq, actions, batched=False)
        assert not _apply_all(bat, actions, batched=True)
        _assert_engines_equal(seq, bat)

    def test_failed_batch_partial_apply_matches(self):
        actions = MUTATION_LADDER[:3] + [
            {"type": "remove_factor", "name": "nope"},  # semantic err
        ] + MUTATION_LADDER[4:]
        seq, bat = _dyn_engine(), _dyn_engine()
        seq.run(max_cycles=20)
        bat.run(max_cycles=20)
        err_a = _apply_all(seq, actions, batched=False)
        err_b = _apply_all(bat, actions, batched=True)
        assert err_a and err_b
        # Earlier actions STAND identically: the flush runs on the
        # early-error exit too.
        _assert_engines_equal(seq, bat)

    def test_slack_reuse_remove_then_add_same_row(self):
        actions = [
            {"type": "remove_factor", "name": "c1"},
            {"type": "add_factor", "name": "cZ",
             "variables": ["v1", "v4"],
             "table": [[5, 0, 0], [0, 5, 0], [0, 0, 5]]},
        ]
        seq, bat = _dyn_engine(slack=0.1), _dyn_engine(slack=0.1)
        assert not _apply_all(seq, actions, batched=False)
        assert not _apply_all(bat, actions, batched=True)
        _assert_engines_equal(seq, bat)

    def test_one_copy_per_touched_bucket_per_batch(self):
        engine = _dyn_engine()
        copies = [0]
        original = DynamicMaxSumEngine._materialize_bucket_rows

        def counting(self, costs, var_ids, rows):
            copies[0] += 1
            return original(self, costs, var_ids, rows)

        try:
            DynamicMaxSumEngine._materialize_bucket_rows = counting
            _apply_all(engine, MUTATION_LADDER, batched=True)
        finally:
            DynamicMaxSumEngine._materialize_bucket_rows = original
        # All six actions touch the single binary bucket: one
        # materialization, not six.
        assert copies[0] == 1

    def test_session_apply_event_batch_uses_batching(self):
        from pydcop_tpu.serving.sessions import apply_event_batch

        seq, bat = _dyn_engine(), _dyn_engine()
        seq.run(max_cycles=20)
        bat.run(max_cycles=20)
        _apply_all(seq, MUTATION_LADDER, batched=False)
        applied, _touched, error = apply_event_batch(
            bat, MUTATION_LADDER)
        assert error is None and len(applied) == len(MUTATION_LADDER)
        _assert_engines_equal(seq, bat)


# ------------------------------------------------------------------ #
# bundle sections
# ------------------------------------------------------------------ #

class TestBundleSections:
    def test_bundle_carries_efficiency_section(self, tmp_path):
        from pydcop_tpu.observability.flight import FlightRecorder

        efficiency.tracker.record_dispatch(
            key="k", structure="s", backend="cpu", time_s=0.1,
            compile_s=0.0, cycles=10, n_real=1, batch_size=1)
        doc = FlightRecorder(bundle_dir=str(tmp_path)).make_bundle(
            "test", {})
        assert "probe_log_tail" not in doc
        assert "probe_diagnostics" not in doc
        backend = doc["efficiency"]["backend"]
        assert backend == resolved_backend()
        assert set(backend) == {"backend", "device_kind", "n_devices"}
        assert doc["efficiency"]["structures"]


# ------------------------------------------------------------------ #
# real-dispatch attainment end-to-end
# ------------------------------------------------------------------ #

class TestRealDispatchAttainment:
    def test_warm_stacked_dispatch_attains(self):
        from pydcop_tpu.observability.profiler import profiler

        was = profiler.enabled
        profiler.enabled = True
        try:
            graph = compile_dcop(_ring(6, 1), noise_level=0.01)[0]
            engine_batch.run_stacked([graph, graph],
                                     max_cycles=MAX_CYCLES)
            _v, _c, warm = engine_batch.run_stacked(
                [graph, graph], max_cycles=MAX_CYCLES)
        finally:
            profiler.enabled = was
        record = warm.metrics["efficiency"]
        assert record["backend"] == resolved_backend()["backend"]
        assert record["compile_s"] == 0.0
        assert record["attainment"] is not None
        assert 0 < record["attainment"] <= 2.0
        assert record["useful_work_fraction"] == \
            pytest.approx(record["attainment"])

    def test_cold_dispatch_charges_compile_not_execute(self):
        graph = compile_dcop(_ring(7, 2), noise_level=0.01)[0]
        _v, _c, cold = engine_batch.run_stacked(
            [graph], max_cycles=MAX_CYCLES + 1)
        record = cold.metrics["efficiency"]
        assert record["compile_s"] > 0
        assert record["execute_s"] == 0.0
        assert record["attainment"] is None


# ------------------------------------------------------------------ #
# review-hardening regressions
# ------------------------------------------------------------------ #

class TestReviewRegressions:
    def test_restore_syncs_cycle_baseline(self, tmp_path):
        """A checkpoint-restored engine must not account every
        pre-checkpoint cycle to its first post-restore run — that
        inflated attainment by the whole restored history."""
        donor = _dyn_engine()
        donor.run(max_cycles=100)
        path = str(tmp_path / "ck.npz")
        donor.checkpoint(path)
        fresh = _dyn_engine()
        fresh.restore(path)
        assert fresh._cycles_recorded == \
            int(np.asarray(fresh._state.cycle))
        res = fresh.run(max_cycles=30)
        ran = fresh._cycles_recorded - int(
            np.asarray(donor._state.cycle))
        assert 0 <= ran <= 30 + 1, (ran, res.cycles)

    def test_peak_source_mixed_when_half_calibrated(self,
                                                    monkeypatch):
        from pydcop_tpu.observability.efficiency import backend_peaks

        monkeypatch.delenv("PYDCOP_PEAK_FLOPS", raising=False)
        monkeypatch.delenv("PYDCOP_PEAK_BYTES_PER_S", raising=False)
        assert backend_peaks("cpu")["source"] == "default"
        monkeypatch.setenv("PYDCOP_PEAK_FLOPS", "1e9")
        assert backend_peaks("cpu")["source"] == "mixed"
        monkeypatch.setenv("PYDCOP_PEAK_BYTES_PER_S", "1e10")
        assert backend_peaks("cpu")["source"] == "env"

    def test_terminal_ledger_post_dispatch_time_is_prep_not_queue(
            self):
        """A decode/dispatch failure after device work must not
        label the device seconds as queue wait."""
        import time as _time

        service = SolveService(batch_window_s=0.01)
        req = __import__(
            "pydcop_tpu.serving.service",
            fromlist=["SolveRequest"]).SolveRequest(
            id="x", dcop=None, graph=None, meta=None, params={},
            bin=None, t_submit=_time.perf_counter() - 1.0)
        req.t_enqueue = req.t_submit + 0.1
        req.t_dispatch = req.t_submit + 0.3
        ledger = service._terminal_ledger(req)
        assert ledger["queue_s"] == pytest.approx(0.2, abs=0.05)
        assert ledger["prep_s"] >= 0.6
        _assert_ledger_sums(ledger)

    def test_envelope_dispatch_label_is_the_envelope_shape(self):
        """Members of one envelope-packed dispatch share ONE
        structure cell (the padded shape), not the first member's
        pre-padding shape."""
        from pydcop_tpu.serving import binning

        g_small = compile_dcop(_ring(6, 1), noise_level=0.01)[0]
        g_big = compile_dcop(_ring(12, 2), noise_level=0.01)[0]
        env = binning.envelope_key(g_big)
        efficiency.tracker.clear()
        engine_batch.run_stacked([g_small, g_big],
                                 max_cycles=MAX_CYCLES,
                                 envelope=env)
        roll = efficiency.tracker.rollup()
        assert len(roll["structures"]) == 1
        label = roll["structures"][0]["structure"]
        assert label.startswith(f"v{env.v_env}d{env.d_env}")

    def test_malformed_table_fails_its_action_batch_scoped(self):
        """A bad cost table inside a deferred batch must fail at ITS
        action (the sequential contract), not at the flush — and the
        engines must still match afterwards."""
        actions = MUTATION_LADDER[:2] + [
            {"type": "change_factor", "name": "c0",
             "variables": ["v0", "v1"],
             # 5x5 table into a 3x3 domain: _render_row must raise.
             "table": [[1] * 5] * 5},
        ] + MUTATION_LADDER[2:3]
        seq, bat = _dyn_engine(), _dyn_engine()
        seq.run(max_cycles=20)
        bat.run(max_cycles=20)
        err_a = _apply_all(seq, actions, batched=False)
        err_b = _apply_all(bat, actions, batched=True)
        assert err_a and err_b
        _assert_engines_equal(seq, bat)
        assert bat._edit_session is None

    def test_flush_failure_clears_session_and_returns_batch_error(
            self, monkeypatch):
        """Even a flush-time failure must keep apply_event_batch's
        tuple contract AND leave the engine out of deferred mode —
        a stuck session would silently drop every later edit."""
        from pydcop_tpu.serving.sessions import apply_event_batch

        engine = _dyn_engine()

        def boom(self):
            if self._edit_session and self._edit_session["buckets"]:
                raise RuntimeError("synthetic flush failure")

        monkeypatch.setattr(DynamicMaxSumEngine,
                            "_flush_pending_edits", boom)
        applied, _touched, error = apply_event_batch(
            engine, MUTATION_LADDER[:1])
        assert error is not None and "flush" in error
        assert engine._edit_session is None
        monkeypatch.undo()
        # The engine still accepts (and materializes) edits.
        assert not _apply_all(engine, MUTATION_LADDER[:1],
                              batched=False)

    def test_dynamic_engine_outside_sessions_labels_dynamic(self):
        """A scenario replay / direct dynamic engine is NOT a
        session: its dispatches must not masquerade as session work
        in the rollup's request classes."""
        engine = _dyn_engine()
        engine.run(max_cycles=20)
        engine.run(max_cycles=20)
        classes = set()
        for row in efficiency.tracker.rollup()["structures"]:
            classes |= set(row["by_class"])
        assert classes == {"dynamic"}

    def test_disabled_plane_skips_metrics_entirely(self):
        was = efficiency.tracker.enabled
        efficiency.tracker.enabled = False
        try:
            graph = compile_dcop(_ring(6, 5), noise_level=0.01)[0]
            _v, _c, res = engine_batch.run_stacked(
                [graph], max_cycles=MAX_CYCLES)
        finally:
            efficiency.tracker.enabled = was
        assert "efficiency" not in res.metrics
        assert efficiency.tracker.rollup()["structures"] == []


# ------------------------------------------------------------------ #
# closed-loop hot path (ISSUE 18): pipelined flushes + speculation
# ------------------------------------------------------------------ #

class TestPipelinedFlush:
    """Ledger honesty and terminal ordering when dispatch k+1
    launches before dispatch k decodes (the pipelined flush path)."""

    def _pipelined_burst(self, dcops, service_kw=None):
        """Warm pass (compiles every program synchronously), then the
        measured burst on warm programs — only warm dispatches take
        the pipelined launch/collect path."""
        kw = dict({"pipeline": True}, **(service_kw or {}))
        service = SolveService(batch_window_s=0.05, max_batch=16,
                               **kw).start()
        completions = []
        orig_pub = service._publish_lifecycle

        def pub(event, req):
            if event == "finished":
                completions.append(req.id)
            return orig_pub(event, req)

        service._publish_lifecycle = pub
        try:
            ids = [service.submit(d, params=PARAMS) for d in dcops]
            warm = [service.result(i, wait=60) for i in ids]
            assert all(r["status"] == "FINISHED" for r in warm), warm
            completions.clear()
            ids = [service.submit(d, params=PARAMS) for d in dcops]
            results = [service.result(i, wait=60) for i in ids]
            assert all(r["status"] == "FINISHED"
                       for r in results), results
            reqs = {i: service._requests[i] for i in ids}
            stats = service.stats()
        finally:
            service.stop()
        return ids, results, reqs, stats, completions

    def test_multibin_pipelined_ledgers_and_ordering(self):
        # Two structures x 2 requests: two bins per flush, so the
        # second bin's device call launches while the first bin's
        # arrays are still in flight (scheduler pending depth 2).
        dcops = ([_ring(6, s) for s in range(2)]
                 + [_ring(9, s) for s in range(2)])
        ids, results, reqs, stats, completions = \
            self._pipelined_burst(dcops)
        assert stats["pipeline"]["enabled"]
        assert stats["pipeline"]["pipelined_dispatches"] >= 2, stats
        roll = efficiency.tracker.rollup()
        assert roll["pipeline"]["dispatches"] >= 2
        assert 0.0 <= roll["pipeline_overlap_fraction"] <= 1.0
        for res in results:
            # Sum-to-latency holds on the pipelined path, and decode
            # is attributed to the owning request (its own host
            # post-processing wall, never zeroed by the overlap).
            _assert_ledger_sums(res["ledger"])
            assert res["ledger"]["decode_s"] > 0.0, res["ledger"]
        # Terminal callbacks fire in pickup order: the order the
        # scheduler dispatched (t_dispatch), not decode-completion
        # races.
        pickup = sorted(ids, key=lambda i: reqs[i].t_dispatch)
        assert completions == pickup, (completions, pickup)

    def test_pipelined_envelope_and_lane_ledgers(self):
        mixed = [_ring(5, 0), _ring(6, 1), _ring(7, 2)]
        for kw in ({"envelope_overhead_ms": 1e6, "lane_pack": False},
                   {"envelope_overhead_ms": 1e6}):
            efficiency.tracker.clear()
            _ids, results, _reqs, stats, _comp = \
                self._pipelined_burst(mixed, service_kw=kw)
            assert stats["pipeline"]["pipelined_dispatches"] >= 1
            kinds = {r["batch"]["packing"] for r in results}
            assert kinds <= {"envelope", "lane"}, kinds
            for res in results:
                _assert_ledger_sums(res["ledger"])

    def test_no_pipeline_knob_stays_synchronous(self):
        dcops = [_ring(6, s) for s in range(2)]
        _ids, results, _reqs, stats, _comp = self._pipelined_burst(
            dcops, service_kw={"pipeline": False})
        assert stats["pipeline"]["pipelined_dispatches"] == 0
        for res in results:
            _assert_ledger_sums(res["ledger"])

    def test_pipelined_answers_equal_synchronous(self):
        """Four structure bins of two requests in one flush: the
        pipelined path answers every request as the synchronous path
        does, and only the pipelined service pipelined."""
        dcops = [_ring(n, s) for n in (17, 18, 19, 20)
                 for s in (0, 1)]
        answers = {}
        for pipeline in (False, True):
            _ids, results, _reqs, stats, _comp = self._pipelined_burst(
                dcops, service_kw={"pipeline": pipeline,
                                   "speculate": False})
            answers[pipeline] = [r["assignment"] for r in results]
            pipelined = stats["pipeline"]["pipelined_dispatches"]
            assert (pipelined > 0) == pipeline, stats
        assert answers[True] == answers[False]

    def test_stubbed_run_batch_never_pipelines(self):
        # A test double stubbing the device call IS the contract
        # under test for a pile of batteries: the pipelined path must
        # step aside for it.
        service = SolveService(batch_window_s=0.02, pipeline=True)
        calls = []
        orig = SolveService._run_batch

        def stub(reqs, params):
            calls.append(len(reqs))
            return orig(service, reqs, params)

        service._run_batch = stub
        service.start()
        try:
            i = service.submit(_ring(6, 0), params=PARAMS)
            r1 = service.result(i, wait=60)
            i = service.submit(_ring(6, 1), params=PARAMS)
            r2 = service.result(i, wait=60)
        finally:
            stats = service.stats()
            service.stop()
        assert r1["status"] == r2["status"] == "FINISHED"
        assert len(calls) == 2, calls
        assert stats["pipeline"]["pipelined_dispatches"] == 0


class TestSpeculativeCompiles:
    """Tentpole (b) discipline: background compiles never run on the
    device-owning scheduler thread, are compile-only (trace-span
    asserted), and a speculated program's first real dispatch counts
    as a hit."""

    def test_speculation_off_thread_and_hits(self):
        from pydcop_tpu.observability.trace import tracer
        from pydcop_tpu.serving import binning

        tracer.enable()
        service = SolveService(batch_window_s=0.05, max_batch=16,
                               pipeline=True, speculate=True).start()
        try:
            sched_ident = service._scheduler_ident
            assert sched_ident is not None
            # Phase 1: a recurring solo structure seeds the arrival
            # histogram; the speculator AOT-builds the bin rungs its
            # traffic will need (bs=2 among them).  The structure is
            # unique to this test (ring 11) — a key another battery
            # test already dispatched would be live-warm, which the
            # speculator rightly refuses to rebuild (and whose first
            # dispatch here would not be cold, so no hit either).
            for s in range(2):
                i = service.submit(_ring(11, s), params=PARAMS)
                assert service.result(i, wait=60)[
                    "status"] == "FINISHED"
            graph, _ = compile_dcop(_ring(11, 0), pad_to=1,
                                    aggregation="scatter")
            p = binning.normalize_params(PARAMS)
            prep = engine_batch._prepare_stacked(
                [graph, graph], p["max_cycles"], p["damping"],
                p["damping_nodes"], p["stability"],
                service.bin_sizes, False, None)
            expected = str(prep.key)
            import time as _time

            deadline = _time.time() + 120
            spec = service._speculator
            while (_time.time() < deadline
                   and expected not in spec.compiled_keys):
                _time.sleep(0.2)
            assert expected in spec.compiled_keys, spec.stats()
            # Phase 2: the predicted bin-of-2 arrives; its program is
            # cold in the jit cache but speculatively built — a hit.
            ids = [service.submit(_ring(11, s), params=PARAMS)
                   for s in (7, 8)]
            results = [service.result(i, wait=60) for i in ids]
            assert all(r["status"] == "FINISHED" for r in results)
            stats = service.stats()
            assert stats["speculation"]["enabled"]
            assert stats["speculation"][
                "speculative_compiles_total"] >= 1
            assert stats["speculation"]["hits"] >= 1, stats
            # Discipline: every compile ran off the scheduler thread.
            assert spec.records, "no compile records"
            for rec in spec.records:
                assert rec["thread_ident"] != sched_ident, rec
                assert rec["compile_only"], rec
            # Trace-span asserted too: speculative_compile spans
            # carry their thread and the compile-only flag, and none
            # ever ran on the dispatch-owning thread.
            spans = [e for e in tracer.events()
                     if e.get("name") == "speculative_compile"]
            assert spans, "no speculative_compile spans recorded"
            for ev in spans:
                assert ev["args"]["compile_only"] is True
                assert ev["args"]["thread"] != sched_ident
        finally:
            service.stop()
            tracer.disable()
            tracer.clear()
