"""Elastic-fleet battery (ISSUE 16): multi-host control plane, live
session migration, SLO-driven autoscaling, and weighted fair queuing.

- the WFQ fair scheduler: a tenant's flood advances only its OWN
  virtual-time tag, so a quiet tenant's next request overtakes the
  flood's tail; rejection is shaping (429 accounting), not failure;
- the autoscale policy as pure logic (synthetic replicas, no
  processes): up on p99 breach or deep queues under the ceiling,
  down only after a quiet streak above the floor, inert unless both
  ``slo_p99_ms`` and ``max_replicas`` are armed;
- journal compaction bounds recovery (the ISSUE-16 satellite): a
  rebased checkpoint drops the pre-checkpoint event tail, and the
  compacted file holds ONLY pending records — a dead replica's
  replacement replays pending work, not segment history;
- the migration rebase: a live engine's current problem serializes
  back to dcop yaml and rebuilds to the same cost (the zero-replay
  bundle's correctness core) and bundle validation rejects garbage;
- control-plane identity (``fleet_host_id``), ``--join`` wiring and
  CLI knobs, remote-join address validation;
- a REAL 2-replica/2-host fleet: SIGKILL the session-owning replica
  and (a) a submit that lands on the dead slot before the prober's
  verdict reroutes over ForwardNotSent to a survivor, (b) an open
  SSE stream through the router ends in a clean reconnectable EOF
  (never a hang), (c) the reconnect resumes the stream and acked
  event batches survive.
"""

import json
import os
import signal
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.dcop.yamldcop import dcop_yaml, load_dcop
from pydcop_tpu.engine.multihost import fleet_host_id
from pydcop_tpu.serving import journal as journal_mod
from pydcop_tpu.serving import migration
from pydcop_tpu.serving.router import (
    UP,
    FairScheduler,
    FleetRouter,
    Replica,
)

SESSION_PARAMS = {"noise": 0.01, "stability": 0.001,
                  "max_cycles": 500}


def _path_dcop(n: int, seed: int) -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"elastic_{n}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n - 1):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def _req(url, method="GET", payload=None, timeout=60):
    data = (json.dumps(payload).encode()
            if payload is not None else None)
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


# ------------------------------------------------------------------ #
# weighted fair queuing


class TestFairScheduler:
    def test_quiet_tenant_overtakes_a_flood(self):
        """Tenant A floods the single slot; tenant B's lone request
        must be admitted right behind the in-flight one — ahead of
        the flood's tail — because B's tag starts at the current
        virtual time while A's tags kept advancing."""
        fair = FairScheduler(fair_share=1)
        assert fair.acquire("A", up=1)     # occupies the only slot
        order = []
        lock = threading.Lock()

        def worker(tenant):
            assert fair.acquire(tenant, up=1, timeout=30)
            with lock:
                order.append(tenant)
            fair.release()

        flood = [threading.Thread(target=worker, args=("A",))
                 for _ in range(4)]
        for t in flood:
            t.start()
        deadline = time.monotonic() + 10
        while fair.stats()["queued"] < 4 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        late = threading.Thread(target=worker, args=("B",))
        late.start()
        while fair.stats()["queued"] < 5 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        fair.release()                     # open the floodgate
        for t in flood + [late]:
            t.join(timeout=30)
        assert len(order) == 5
        # B overtook at least three of A's four queued requests.
        assert order.index("B") <= 1, order

    def test_rejection_is_shaping_not_failure(self):
        fair = FairScheduler(fair_share=1)
        assert fair.acquire("A", up=1)
        assert fair.acquire("B", up=1, timeout=0.05) is False
        stats = fair.stats()
        assert stats["rejected"] == 1
        assert stats["admitted"] == 1
        fair.release()
        assert fair.stats()["active"] == 0

    def test_capacity_scales_with_live_replicas(self):
        fair = FairScheduler(fair_share=2)
        for _ in range(4):                 # up=2 → cap 4
            assert fair.acquire("A", up=2, timeout=0.5)
        assert fair.acquire("A", up=2, timeout=0.05) is False
        assert fair.acquire("A", up=3, timeout=0.5)   # cap now 6


# ------------------------------------------------------------------ #
# autoscale policy (synthetic replicas, no processes)


def _policy_router(n=2, **kw) -> FleetRouter:
    router = FleetRouter(replicas=max(n, 1), **kw)
    for k in range(n):
        replica = Replica(k, None, f"/dev/null-{k}",
                          host_id=f"host{k % max(router.hosts, 1)}")
        replica.status = UP
        replica.port = 1
        router.replicas.append(replica)
    return router


class TestAutoscalePolicy:
    def test_inert_unless_armed(self):
        router = _policy_router(2, slo_p99_ms=100)   # no ceiling
        for _ in range(50):
            router.record_latency(1000.0)
        assert router.autoscale_decision() is None

    def test_scales_up_on_p99_breach(self):
        router = _policy_router(2, slo_p99_ms=100, min_replicas=2,
                                max_replicas=4)
        for _ in range(50):
            router.record_latency(250.0)
        assert router.autoscale_decision() == "up"

    def test_scales_up_on_deep_queues_without_latency(self):
        router = _policy_router(2, slo_p99_ms=100, min_replicas=2,
                                max_replicas=4)
        for r in router.replicas:
            r.queue_depth = 10             # >> 2 * live
        assert router.rolling_p99() is None
        assert router.autoscale_decision() == "up"

    def test_respects_the_ceiling(self):
        router = _policy_router(4, slo_p99_ms=100, max_replicas=4)
        for _ in range(50):
            router.record_latency(250.0)
        assert router.autoscale_decision() is None

    def test_scales_down_only_after_quiet_streak(self):
        router = _policy_router(3, slo_p99_ms=100, min_replicas=2,
                                max_replicas=4,
                                scale_down_quiet_checks=3)
        for _ in range(50):
            router.record_latency(10.0)    # far under slo/2
        assert router.autoscale_decision() is None
        assert router.autoscale_decision() is None
        assert router.autoscale_decision() == "down"

    def test_breach_resets_the_quiet_streak(self):
        router = _policy_router(3, slo_p99_ms=100, min_replicas=2,
                                max_replicas=4,
                                scale_down_quiet_checks=2)
        for _ in range(50):
            router.record_latency(10.0)
        assert router.autoscale_decision() is None   # quiet 1/2
        for _ in range(100):
            router.record_latency(250.0)
        assert router.autoscale_decision() == "up"   # streak reset
        # Flush the whole rolling window (deque maxlen): while any
        # breach sample is still inside it, p99 stays breached and
        # "up" remains the CORRECT verdict.
        for _ in range(600):
            router.record_latency(10.0)
        assert router.autoscale_decision() is None   # quiet 1/2 again

    def test_respects_the_floor(self):
        router = _policy_router(2, slo_p99_ms=100, min_replicas=2,
                                max_replicas=4,
                                scale_down_quiet_checks=1)
        for _ in range(50):
            router.record_latency(10.0)
        assert router.autoscale_decision() is None


# ------------------------------------------------------------------ #
# journal compaction bounds recovery (ISSUE 16 satellite)


class TestCompactionBoundsRecovery:
    def _fill(self, journal_dir, rebased):
        jn = journal_mod
        jn.append_record(journal_dir, jn.accepted_record(
            "r-done", "dcop: a", {"max_cycles": 10}))
        jn.append_record(journal_dir, jn.completed_record(
            "r-done", "FINISHED"))
        jn.append_record(journal_dir, jn.accepted_record(
            "r-pending", "dcop: b", {"max_cycles": 10}))
        jn.append_record(journal_dir, jn.session_open_record(
            "s1", "dcop: base", {"max_cycles": 10}))
        for seq in range(1, 6):
            jn.append_record(journal_dir, jn.session_event_record(
                "s1", seq, [{"type": "noop", "n": seq}]))
        jn.append_record(journal_dir, jn.session_ckpt_record(
            "s1", 3, "/tmp/ck.npz", cycle=7,
            dcop="dcop: rebased" if rebased else None))

    def test_rebased_ckpt_drops_the_pre_checkpoint_tail(self,
                                                        tmp_path):
        jd = str(tmp_path)
        self._fill(jd, rebased=True)
        pending, sessions, _results = journal_mod.compact_journal(jd)
        assert [r["id"] for r in pending] == ["r-pending"]
        (sess,) = sessions
        assert [r["seq"] for r in sess["events"]] == [4, 5]
        assert sess["ckpt"]["dcop"] == "dcop: rebased"

    def test_plain_ckpt_keeps_every_event(self, tmp_path):
        jd = str(tmp_path)
        self._fill(jd, rebased=False)
        _pending, sessions, _results = journal_mod.compact_journal(jd)
        (sess,) = sessions
        assert [r["seq"] for r in sess["events"]] == [1, 2, 3, 4, 5]

    def test_compacted_file_holds_only_pending_records(self,
                                                       tmp_path):
        """THE recovery-time bound: re-scanning the compacted file
        must visit exactly the pending request + the session's
        post-checkpoint replay set — no completed pairs, no
        pre-checkpoint events, no closed sessions."""
        jd = str(tmp_path)
        self._fill(jd, rebased=True)
        journal_mod.append_record(jd, journal_mod.session_open_record(
            "s-closed", "dcop: c", {}))
        journal_mod.append_record(
            jd, journal_mod.session_close_record(
                "s-closed", "MIGRATED"))
        journal_mod.compact_journal(jd)
        records, _bytes, torn = journal_mod.scan_journal(
            os.path.join(jd, journal_mod.JOURNAL_FILE))
        assert not torn
        kinds = sorted((r["kind"], r.get("seq", 0)) for r in records)
        assert kinds == [
            (journal_mod.ACCEPTED, 0),
            (journal_mod.SESSION_CKPT, 3),
            (journal_mod.SESSION_EVENT, 4),
            (journal_mod.SESSION_EVENT, 5),
            (journal_mod.SESSION_OPEN, 0),
        ]
        assert all(r["id"] != "s-closed" for r in records)
        # Idempotent: compacting the compacted file changes nothing.
        pending2, sessions2, _results2 = journal_mod.compact_journal(jd)
        assert [r["id"] for r in pending2] == ["r-pending"]
        assert [r["seq"] for r in sessions2[0]["events"]] == [4, 5]


# ------------------------------------------------------------------ #
# crash-durable results: a 202 whose solve FINISHED moments before
# the kill must still resolve to its 200 on the replacement process


class TestDurableResults:
    def test_completed_with_result_survives_compaction(self,
                                                       tmp_path):
        jd = str(tmp_path)
        jn = journal_mod
        jn.append_record(jd, jn.accepted_record("r1", "dcop: a", {}))
        jn.append_record(jd, jn.completed_record(
            "r1", "FINISHED",
            result={"id": "r1", "status": "FINISHED", "cost": 3.0}))
        jn.append_record(jd, jn.accepted_record("r2", "dcop: b", {}))
        # Payload-less tombstone (pre-ISSUE-16 journals): dropped.
        jn.append_record(jd, jn.accepted_record("r3", "dcop: c", {}))
        jn.append_record(jd, jn.completed_record("r3", "FINISHED"))
        pending, _sessions, results = jn.compact_journal(jd)
        assert [r["id"] for r in pending] == ["r2"]
        assert [r["id"] for r in results] == ["r1"]
        recs, _bytes, torn = jn.scan_journal(
            os.path.join(jd, jn.JOURNAL_FILE))
        assert not torn
        assert sorted((r["kind"], r["id"]) for r in recs) == [
            (jn.ACCEPTED, "r2"), (jn.COMPLETED, "r1")]

    def test_retention_keeps_the_newest_tail(self, tmp_path):
        jd = str(tmp_path)
        jn = journal_mod
        for i in range(jn.COMPLETED_KEEP + 40):
            jn.append_record(jd, jn.completed_record(
                f"x{i}", "FINISHED", result={"id": f"x{i}"}))
        _p, _s, results = jn.compact_journal(jd)
        assert len(results) == jn.COMPLETED_KEEP
        assert results[0]["id"] == "x40"
        assert results[-1]["id"] == f"x{jn.COMPLETED_KEEP + 39}"

    def test_recovered_service_serves_the_predecessors_outcome(
            self, tmp_path):
        """Kill-equivalent crash AFTER a solve finished: the
        replacement's /result-path lookups (result/status/trace_id)
        answer from the journal, and the outcome equals the
        predecessor's."""
        from pydcop_tpu.observability.profiler import profiler
        from pydcop_tpu.serving.service import SolveService

        d = str(tmp_path)
        was_profiling = profiler.enabled
        svc = SolveService(journal_dir=d).start()
        rid = svc.submit(load_dcop(dcop_yaml(_path_dcop(8, 11))),
                         params={"max_cycles": 30})
        res = svc.result(rid, wait=120)
        assert res is not None and res["status"] == "FINISHED"
        # SIGKILL-equivalent: no drain, no close record — just stop
        # the scheduler thread and slam the journal handle shut.
        svc._scheduler._stop.set()
        svc._journal.close()

        svc2 = SolveService(journal_dir=d, recover=True).start()
        try:
            got = svc2.result(rid)
            assert got is not None
            assert got["status"] == "FINISHED"
            assert got["cost"] == res["cost"]
            assert got["assignment"] == res["assignment"]
            assert svc2.status(rid) == "FINISHED"
            assert svc2.trace_id(rid) == res["trace_id"]
            with pytest.raises(KeyError):
                svc2.result("never-acked")
        finally:
            svc2.stop(drain=False)
            # The killed service never gave the profiler back, and
            # the rest of this worker's tests would run profiled.
            profiler.enabled = was_profiling


# ------------------------------------------------------------------ #
# migration rebase + bundle validation


class TestMigrationBundle:
    def test_rebase_roundtrips_to_the_same_cost(self):
        from pydcop_tpu.engine.dynamic import build_dynamic_engine
        from pydcop_tpu.serving.sessions import apply_event_batch

        rng = np.random.default_rng(5)
        dcop = _path_dcop(8, 5)
        engine = build_dynamic_engine(dcop, dict(SESSION_PARAMS))
        engine.run(max_cycles=500)
        batch = [{"type": "change_factor", "name": "c3",
                  "table": rng.integers(0, 10, size=(3, 3))
                  .astype(float).tolist()}]
        _a, _t, err = apply_event_batch(engine, batch)
        assert err is None
        res = engine.run(max_cycles=500)
        cost = engine.cost(res.assignment)

        rebased = migration.engine_dcop_yaml(engine)
        clone = build_dynamic_engine(load_dcop(rebased),
                                     dict(SESSION_PARAMS))
        res2 = clone.run(max_cycles=500)
        assert clone.cost(res2.assignment) == cost

    def test_bundle_roundtrips_fields(self):
        bundle = migration.build_bundle(
            "s1", "t1", "dcop: x", True, {"max_cycles": 10},
            seq=4, cycle=9,
            events=[{"seq": 4, "events": []}],
            npz_bytes=b"\x00\x01", ckpt_seq=3)
        blob = json.loads(json.dumps(bundle))   # wire round-trip
        assert blob["session_id"] == "s1"
        assert blob["rebased"] is True
        assert blob["seq"] == 4 and blob["ckpt_seq"] == 3
        assert migration._bundle_npz_bytes(blob) == b"\x00\x01"

    def test_install_rejects_garbage(self):
        with pytest.raises(ValueError):
            migration.install_bundle(None, {"version": 99})
        with pytest.raises(ValueError):
            migration.install_bundle(
                None, {"version": migration.BUNDLE_VERSION,
                       "session_id": ""})


# ------------------------------------------------------------------ #
# control-plane identity, join wiring, CLI knobs


class TestControlPlane:
    def test_fleet_host_id_env_override(self, monkeypatch):
        monkeypatch.setenv("PYDCOP_HOST_ID", "rack7")
        assert fleet_host_id() == "rack7"
        monkeypatch.delenv("PYDCOP_HOST_ID")
        assert fleet_host_id() == socket.gethostname()

    def test_register_remote_rejects_bad_address(self):
        router = _policy_router(1)
        with pytest.raises(ValueError):
            router.register_remote("not-an-address")

    def test_join_excludes_local_fleet(self):
        from pydcop_tpu import api

        with pytest.raises(ValueError):
            api.serve(replicas=2, join="http://127.0.0.1:1/")

    def test_elastic_cli_knobs_parse(self):
        import argparse

        from pydcop_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        serve_cmd.set_parser(sub)
        args = parser.parse_args(
            ["serve", "--hosts", "2", "--join",
             "http://127.0.0.1:9", "--host_id", "hostX",
             "--slo_p99_ms", "250", "--min_replicas", "2",
             "--max_replicas", "6"])
        assert args.hosts == 2
        assert args.join == "http://127.0.0.1:9"
        assert args.host_id == "hostX"
        assert args.slo_p99_ms == 250.0
        assert args.min_replicas == 2
        assert args.max_replicas == 6

    def test_cli_rejects_join_with_local_fleet(self):
        import argparse

        from pydcop_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        serve_cmd.set_parser(sub)
        args = parser.parse_args(
            ["serve", "--join", "http://127.0.0.1:9",
             "--replicas", "2"])
        assert serve_cmd.run_cmd(args) == 2

    def test_announce_join_retries_then_gives_up(self):
        from pydcop_tpu import api

        # Nothing listens on this port: every attempt fails and the
        # worker stays standalone instead of crashing.
        assert api._announce_join(
            "http://127.0.0.1:9", "http://127.0.0.1:8",
            host_id="h") is False


# ------------------------------------------------------------------ #
# the real fleet: host kill under an open SSE stream


class TestFleetKillEndToEnd:
    def test_forward_retry_sse_eof_and_event_survival(self,
                                                      tmp_path):
        """One fleet, three ISSUE-16 satellites:

        (a) a request that picks the just-killed replica before the
            prober's verdict reroutes (ForwardNotSent) to a survivor
            instead of failing;
        (c) the SSE stream proxied through the router for a session
            owned by the victim ends with a clean EOF within the
            probe window — never a hang — and a reconnect resumes
            the stream on the new owner;
        plus the durability core: the acked event batch survives the
        kill (the next PATCH lands as seq 2).
        """
        from pydcop_tpu import api

        # A wide heartbeat keeps the just-killed replica in the
        # candidate set for ~a beat: the window in which a submit can
        # actually pick the dead slot and exercise the
        # ForwardNotSent reroute (satellite a).
        handle = api.serve(port=0, replicas=2, hosts=2,
                           batch_window_s=0.05, max_batch=8,
                           heartbeat_s=1.5,
                           journal_dir=str(tmp_path / "jnl"))
        try:
            url = handle.url
            router = handle.router
            assert {r.host_id for r in router.replicas} \
                == {"host0", "host1"}

            rng = np.random.default_rng(2)
            dcop = _path_dcop(10, 1707)
            status, body = _req(
                url + "/session", "POST",
                {"dcop": dcop_yaml(dcop),
                 "params": SESSION_PARAMS})
            assert status == 201, body
            sid = body["session_id"]
            batches = [
                [{"type": "change_factor",
                  "name": f"c{int(rng.integers(9))}",
                  "table": rng.integers(0, 10, size=(3, 3))
                  .astype(float).tolist()}]
                for _ in range(2)
            ]
            status, ack = _req(
                url + f"/session/{sid}/events", "PATCH",
                {"events": batches[0], "wait": True,
                 "timeout": 30.0})
            assert status == 200 and ack["seq"] == 1, ack

            # Open the SSE stream THROUGH the router before the kill.
            stream = urllib.request.urlopen(
                url + f"/session/{sid}/events", timeout=30)
            assert stream.status == 200

            victim = router.pinned(sid, router._session_pins)
            assert victim is not None
            os.kill(victim.proc.pid, signal.SIGKILL)

            # (a) ForwardNotSent reroute: async submits fired in the
            # window between the SIGKILL and the prober's verdict.
            # Distinct structures rendezvous ~evenly across both
            # slots, so some pick the dead one — its refused connect
            # must reroute to the survivor (202 to the client, never
            # a failure), not surface an error.
            acked = []
            for s in range(200):
                if router.reroutes >= 1 or victim.status != UP:
                    break
                solo = _path_dcop(6 + (s % 12), 40 + s)
                status, body = _req(
                    url + "/solve", "POST",
                    {"dcop": dcop_yaml(solo),
                     "params": {"max_cycles": 60}})
                assert status == 202, (s, status, body)
                acked.append(body["id"])
            assert router.reroutes >= 1, \
                (router.reroutes, victim.status, len(acked))
            # The fleet keeps serving end-to-end through the death.
            status, body = _req(
                url + "/solve", "POST",
                {"dcop": dcop_yaml(_path_dcop(12, 77)),
                 "wait": True, "timeout": 60,
                 "params": {"max_cycles": 60}})
            assert status == 200 \
                and body["status"] == "FINISHED", body

            # (c) clean reconnectable EOF, not a hang: the proxy
            # breaks the relay once the prober declares the owner
            # dead (read timeout max(8*hb, 3) + verdict ~8 beats).
            t0 = time.monotonic()
            while True:
                chunk = stream.read(65536)
                if not chunk:
                    break
                assert time.monotonic() - t0 < 30, \
                    "SSE stream hung past the probe window"
            stream.close()
            assert time.monotonic() - t0 < 30

            # Reconnect resumes: the session moved (adopted by the
            # survivor or replayed by the restart); the stream must
            # come back 200 and the acked batch must still be there.
            deadline = time.monotonic() + 120
            reconnected = False
            while time.monotonic() < deadline and not reconnected:
                try:
                    s2 = urllib.request.urlopen(
                        url + f"/session/{sid}/events", timeout=10)
                    if s2.status == 200:
                        reconnected = True
                        s2.close()
                except (urllib.error.HTTPError, OSError):
                    time.sleep(0.2)
            assert reconnected, "SSE reconnect never succeeded"

            deadline = time.monotonic() + 120
            while True:
                status, ack2 = _req(
                    url + f"/session/{sid}/events", "PATCH",
                    {"events": batches[1], "wait": True,
                     "timeout": 30.0})
                if status == 200:
                    break
                assert status in (409, 503), (status, ack2)
                assert time.monotonic() < deadline, (status, ack2)
                time.sleep(0.2)
            assert ack2["seq"] == 2, ack2
            status, final = _req(url + f"/session/{sid}", "DELETE")
            assert status == 200, final
        finally:
            handle.stop()


# ------------------------------------------------------------------ #
# worker admin surface validation (no fleet needed)


class TestAdminSurface:
    def test_admin_endpoint_validation(self):
        from pydcop_tpu import api

        handle = api.serve(port=0, batch_window_s=0.02)
        try:
            url = handle.url
            status, body = _req(url + "/admin/export_session",
                                "POST", {"session_id": "nope"})
            assert status == 404, body
            status, body = _req(url + "/admin/export_session",
                                "POST", {})
            assert status == 400, body
            status, body = _req(url + "/admin/no_such_op",
                                "POST", {})
            assert status == 404, body
            status, body = _req(url + "/admin/import_session",
                                "POST", {"version": 99})
            assert status == 400, body
        finally:
            handle.stop()
