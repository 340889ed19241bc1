"""Chaos soak: a seeded scenario matrix asserting global invariants.

This gate proves the runtime *heals* — every scenario injects a
distinct failure combination (message drop + duplicate + delay,
network partition with healing, silent agent kill, engine guard
trips, checkpoint corruption, serve-process crash with journal
replay, poison requests in a batched bin, device loss mid-sharded-
solve) and asserts the system-wide invariants that define
"self-healing":

- **valid assignment** — every variable ends with a value from its
  domain (a migrated computation kept working; nothing was lost);
- **monotone cycle counter** — progress never runs backwards in the
  observable record (trace ``engine_segment`` spans may rewind ONLY
  across an explicit ``recovery_rollback``);
- **no orphaned computations** — a killed agent's computations are
  re-hosted, not dropped (their variables still carry values);
- **health verdicts consistent with the kill schedule** — every
  injected kill is reported ``agent_dead`` within the configured miss
  bound, and scenarios with message faults but NO kill produce zero
  death verdicts (suspicion is allowed: that is the phi-accrual
  detector doing its job on a lossy link).

Every scenario is a pure function of the seed (fault decisions are
seeded per edge+index, heartbeat bounds are schedule-free, guard trips
are cycle-keyed), so a red run REPLAYS: the failure report prints the
scenario name, the seed and the trace file to hand to
``pydcop trace summary``.

Usage::

    python tools/chaos_soak.py                 # full matrix
    python tools/chaos_soak.py --quick         # make-test gate (~20 s)
    python tools/chaos_soak.py --scenarios 6   # first N scenarios
    python tools/chaos_soak.py --seed 7 --only kill_detected

``make chaos-soak`` runs the full matrix; ``make test`` wires the
``--quick`` device-side gate (fixed seed, ~20 s): engine guard
recovery, checkpoint corruption, guard purity, journal crash replay,
poison-bin bisection, shard-loss repartition, and the anomaly
postmortem (a guard trip with file tracing off must leave a
flight-recorder bundle whose tail holds the triggering instant).
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The shard-trip scenario needs a multi-device mesh: force the
# 8-virtual-device CPU platform (same recipe as the root conftest)
# unless the caller already chose a device count.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from pydcop_tpu.algorithms import AlgorithmDef  # noqa: E402
from pydcop_tpu.dcop.dcop import DCOP  # noqa: E402
from pydcop_tpu.dcop.objects import (  # noqa: E402
    AgentDef,
    Domain,
    Variable,
)
from pydcop_tpu.dcop.relations import constraint_from_str  # noqa: E402
from pydcop_tpu.distribution.objects import Distribution  # noqa: E402

DEFAULT_SEED = int(os.environ.get("PYDCOP_CHAOS_SEED", "42"))


# ------------------------------------------------------------------ #
# fixtures


def coloring_dcop(n_agents=5, n_vars=4):
    """3-colorable chain: fault-free optimum cost is 0."""
    d = Domain("colors", "", ["R", "G", "B"])
    dcop = DCOP("soak", objective="min")
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


def variable_distribution():
    return Distribution({
        "a0": ["v0"], "a1": ["v1"], "a2": ["v2"], "a3": ["v3"],
        "a4": [],
    })


def ring_dcop(n_vars=6):
    d = Domain("c", "", list(range(3)))
    dcop = DCOP("soak_ring", objective="min")
    variables = [Variable(f"v{i}", d) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    edges = [(i, (i + 1) % n_vars) for i in range(n_vars)] + [(0, 3)]
    for i, j in edges:
        dcop.add_constraint(constraint_from_str(
            f"c{i}_{j}", f"10 if v{i} == v{j} else 0",
            [variables[i], variables[j]],
        ))
    return dcop


# ------------------------------------------------------------------ #
# invariants


def assert_valid_assignment(dcop, assignment):
    """Every variable valued, every value in its domain."""
    for name, variable in dcop.variables.items():
        assert name in assignment, f"variable {name} has NO value " \
            "(orphaned computation?)"
        value = assignment[name]
        assert value in list(variable.domain), \
            f"variable {name} = {value!r} outside its domain"


def assert_health_consistent(health, killed):
    """Dead verdicts == the injected kill schedule, exactly."""
    dead = set(health["dead"])
    assert dead == set(killed), (
        f"health verdicts inconsistent with kill schedule: "
        f"dead={sorted(dead)} killed={sorted(killed)}"
    )


def assert_monotone_segments(trace_path):
    """Engine segment cycles never rewind except across an explicit
    recovery rollback — the monotone-progress invariant."""
    from pydcop_tpu.observability.trace import load_trace_file

    events = sorted(
        (e for e in load_trace_file(trace_path)
         if e.get("name") in ("engine_segment", "recovery_rollback")),
        key=lambda e: e["ts"],
    )
    last_cycle = -1
    for ev in events:
        if ev["name"] == "recovery_rollback":
            last_cycle = -1  # an announced rewind resets the floor
            continue
        start = int(ev.get("args", {}).get("from_cycle", 0))
        assert start >= last_cycle, (
            f"cycle counter rewound without a rollback: segment from "
            f"cycle {start} after cycle {last_cycle}"
        )
        last_cycle = start
    return events


# ------------------------------------------------------------------ #
# scenarios — each returns a dict of observations, raises on failure


def _thread_chaos(seed, trace, *, plan, health=True, algo=None,
                  timeout=20):
    from pydcop_tpu.infrastructure.run import solve_with_agents
    from pydcop_tpu.observability import ObservabilitySession
    from pydcop_tpu.resilience.health import HealthConfig

    dcop = coloring_dcop()
    algo = algo or AlgorithmDef.build_with_default_param(
        "adsa", {"stop_cycle": 40, "period": 0.05}, mode="min")
    config = HealthConfig() if health else None
    with ObservabilitySession(trace, "chrome"):
        res = solve_with_agents(
            dcop, algo, distribution=variable_distribution(),
            timeout=timeout, fault_plan=plan, health_config=config,
        )
    assert_valid_assignment(dcop, res["assignment"])
    assert res.get("cycles", 0) > 0, "no cycle ever completed"
    return res


def scenario_kill_detected(seed, trace):
    """Silent kill mid-run: the heartbeat monitor (not the injector)
    must detect the death and the repair path must migrate the
    victim's computation."""
    from pydcop_tpu.resilience.faults import CrashEvent, FaultPlan

    res = _thread_chaos(seed, trace, plan=FaultPlan(
        seed=seed, crashes=(CrashEvent("a1", 5),), replicas=2,
    ), timeout=45)
    assert res["killed_agents"] == ["a1"]
    assert_health_consistent(res["health"], ["a1"])
    assert res["status"] == "FINISHED", f"run ended {res['status']}"
    assert res["cost"] == 0, f"non-optimal cost {res['cost']}"
    return {"dead": res["health"]["dead"], "cost": res["cost"]}


def scenario_drop_dup_delay(seed, trace):
    """Lossy-but-alive links: drop+dup+delay with NO kill must
    converge to the fault-free cost with ZERO death verdicts
    (suspicion allowed — that is the detector's designed response)."""
    from pydcop_tpu.resilience.faults import FaultPlan

    res = _thread_chaos(seed, trace, plan=FaultPlan(
        seed=seed, drop=0.10, duplicate=0.05, delay=0.05,
        delay_time=0.02,
    ))
    stats = res["fault_stats"]
    assert stats["dropped"] > 0, "no fault injected — not a chaos run"
    assert_health_consistent(res["health"], [])
    assert res["cost"] == 0, f"non-optimal cost {res['cost']}"
    return {"fault_stats": stats,
            "suspects": [v for v in res["health"]["verdicts"]
                         if v["status"] == "suspect"]}


def scenario_delay_only_no_death(seed, trace):
    """Pure delay (30%): heartbeats arrive late, never never-again —
    zero death verdicts."""
    from pydcop_tpu.resilience.faults import FaultPlan

    res = _thread_chaos(seed, trace, plan=FaultPlan(
        seed=seed, delay=0.30, delay_time=0.05,
    ))
    assert_health_consistent(res["health"], [])
    assert res["cost"] == 0, f"non-optimal cost {res['cost']}"
    return {"verdicts": len(res["health"]["verdicts"])}


def scenario_partition_heal(seed, trace):
    """A partition splits the chain mid-problem, then HEALS (per-edge
    index bound): the run must reconverge to the fault-free cost after
    the heal — the assertion PR-1's permanent partitions could never
    make."""
    from pydcop_tpu.resilience.faults import FaultPlan

    res = _thread_chaos(seed, trace, plan=FaultPlan(
        seed=seed,
        partitions=(frozenset({"a0", "a1"}),
                    frozenset({"a2", "a3", "a4"})),
        partition_heal_index=8,
    ), timeout=30)
    assert res["fault_stats"]["partitioned"] > 0, \
        "partition never blocked a message"
    assert_health_consistent(res["health"], [])
    assert res["cost"] == 0, (
        f"no reconvergence after partition heal: cost {res['cost']}")
    return {"partitioned": res["fault_stats"]["partitioned"]}


def scenario_drop_plus_kill(seed, trace):
    """Combined loss + silent kill: detection and repair under a lossy
    network."""
    from pydcop_tpu.resilience.faults import CrashEvent, FaultPlan

    res = _thread_chaos(seed, trace, plan=FaultPlan(
        seed=seed, drop=0.10, crashes=(CrashEvent("a2", 5),),
        replicas=2,
    ), timeout=45)
    assert res["killed_agents"] == ["a2"]
    assert_health_consistent(res["health"], ["a2"])
    assert res["status"] == "FINISHED", f"run ended {res['status']}"
    assert res["cost"] == 0, f"non-optimal cost {res['cost']}"
    return {"dead": res["health"]["dead"]}


def scenario_guard_trip_device(seed, trace):
    """Injected guard trip on a device solve: rollback + recovery must
    appear in the exported trace, the cycle counter may only rewind
    across the rollback, and the healed run still converges to a valid
    assignment."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.observability import ObservabilitySession
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    dcop = ring_dcop()
    with ObservabilitySession(trace, "chrome"):
        res = build_engine(dcop, {}).run_checkpointed(
            max_cycles=120, segment_cycles=7,
            recovery=RecoveryPolicy(trip_cycles=(14,),
                                    noise_seed=seed),
        )
    assert res.metrics["guard_trips"] == 1
    assert res.metrics["recovery_attempts"] == 1
    assert res.converged, "recovered run failed to converge"
    assert_valid_assignment(dcop, res.assignment)
    events = assert_monotone_segments(trace)
    names = {e["name"] for e in events}
    assert "recovery_rollback" in names, \
        "recovery span missing from exported trace"
    return {"trace_events": len(events),
            "actions": res.metrics["recovery_actions"]}


def scenario_guard_noop_device(seed, trace):
    """Guard armed, nothing injected: the guarded trajectory must be
    bit-identical to the unguarded one (guards are pure reads)."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    dcop = ring_dcop()
    ref = build_engine(dcop, {}).run_checkpointed(
        max_cycles=120, segment_cycles=7)
    res = build_engine(dcop, {}).run_checkpointed(
        max_cycles=120, segment_cycles=7, recovery=RecoveryPolicy())
    assert res.metrics["guard_trips"] == 0
    assert res.assignment == ref.assignment, \
        "guarded run diverged from unguarded with no faults"
    assert res.cycles == ref.cycles
    assert_valid_assignment(dcop, res.assignment)
    return {"cycles": res.cycles}


def scenario_decimation_guard_trip(seed, trace):
    """Guard trip mid-decimation (ISSUE 10): the rollback must restore
    the CLAMP SET together with the snapshot — resuming the
    rolled-back messages under a stale (newer) active-edge mask would
    silently solve a different problem.  Asserted: the trip and the
    clamp-set rollback both happened, per-segment decimated counts are
    monotone EXCEPT exactly across the rollback (the decimation
    analogue of the monotone-cycle invariant), the healed run still
    fixes every variable and ends with a valid assignment."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.engine.runner import DecimationPlan
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    dcop = ring_dcop()

    class FixedCountProbe:
        """Records the engine's decimated count per validated
        segment (on_segment fires only for validated states)."""

        def __init__(self, decim_run_ref):
            self.counts = []
            self._ref = decim_run_ref

        def on_segment(self, state, values, run_s, compile_s):
            self.counts.append(int(self._ref[0].fixed.sum())
                               if self._ref[0] is not None else 0)

    engine = build_engine(dcop, {})
    # Reach into the run via a mutable ref the probe reads: the
    # engine constructs its _DecimationRun internally.
    ref = [None]
    orig_run = engine.run_checkpointed

    def run_with_ref(**kw):
        import pydcop_tpu.engine.runner as runner_mod

        orig_cls = runner_mod._DecimationRun

        class Capturing(orig_cls):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                ref[0] = self

        runner_mod._DecimationRun = Capturing
        try:
            return orig_run(**kw)
        finally:
            runner_mod._DecimationRun = orig_cls

    probe = FixedCountProbe(ref)
    res = run_with_ref(
        max_cycles=400, segment_cycles=10,
        decimation=DecimationPlan(frac_per_round=0.25,
                                  cycles_per_round=10),
        recovery=RecoveryPolicy(trip_cycles=(25,), noise_seed=seed),
        probe=probe,
    )
    assert res.metrics["guard_trips"] == 1
    assert res.metrics["recovery_attempts"] == 1
    assert res.metrics["decimation_rollbacks"] == 1, \
        "guard trip did not roll the clamp set back with the snapshot"
    assert res.metrics["decimated_vars"] == len(dcop.variables), \
        "healed decimated run left variables unclamped"
    assert res.metrics["decimated_fraction"] == 1.0
    assert res.metrics["active_edges"] == 0
    assert_valid_assignment(dcop, res.assignment)
    # Monotone-decimation invariant: the validated per-segment counts
    # never decrease (a decrease would mean a stale mask leaked past
    # a rollback into a validated segment).
    counts = probe.counts
    assert all(b >= a for a, b in zip(counts, counts[1:])), \
        f"validated decimated counts ran backwards: {counts}"
    return {"decimated": res.metrics["decimated_vars"],
            "rounds": res.metrics["decimation_rounds"],
            "segment_counts": counts}


def scenario_checkpoint_corruption(seed, trace):
    """Torn-write simulation: truncate the newest snapshot mid-file;
    resume must fall back to the previous VALID snapshot and still
    reproduce the uninterrupted run; retention keeps exactly N."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.resilience.checkpoint import (
        CheckpointManager,
        resume_from_checkpoint,
    )

    dcop = ring_dcop()
    ref = build_engine(dcop, {}).run(max_cycles=120)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        manager = CheckpointManager(ckpt_dir, every=5, keep=2)
        build_engine(dcop, {}).run_checkpointed(
            max_cycles=120, manager=manager, max_segments=3)
        on_disk = manager.checkpoints()
        assert len(on_disk) == 2, (
            f"retention kept {len(on_disk)} snapshots, wanted "
            f"exactly 2")
        newest = on_disk[-1][1]
        with open(newest, "r+b") as f:
            f.truncate(os.path.getsize(newest) // 2)
        res = resume_from_checkpoint(
            build_engine(dcop, {}), manager, max_cycles=120)
        assert res.metrics["resumed_from_cycle"] == on_disk[-2][0], \
            "resume did not fall back to the previous valid snapshot"
        assert res.assignment == ref.assignment
        assert res.cycles == ref.cycles
        assert_valid_assignment(dcop, res.assignment)
        return {"resumed_from": res.metrics["resumed_from_cycle"]}


def _serve_instance(n_vars, seed):
    """Ring coloring with seeded random tables; carries an agent so
    it survives the journal's dcop_yaml round-trip."""
    import numpy as np

    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"soak_srv_{n_vars}_{seed}", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n_vars):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % n_vars]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def scenario_serve_journal_replay(seed, trace):
    """Crash-equivalent journal (accepted records, one pre-crash
    completion, a torn tail) + a ``recover=True`` service start:
    exactly the unfinished requests replay through the normal queue
    and complete — zero acknowledged requests lost — and the replay
    is announced in the trace (``serve_replay`` span)."""
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.observability import ObservabilitySession
    from pydcop_tpu.serving.journal import (
        RequestJournal,
        accepted_record,
        completed_record,
    )
    from pydcop_tpu.serving.service import SolveService

    params = {"max_cycles": 40}
    with tempfile.TemporaryDirectory() as journal_dir:
        jnl = RequestJournal(journal_dir)
        dcops = {}
        for i in range(5):
            rid = f"crash{i}"
            dcops[rid] = _serve_instance(8, seed * 100 + i)
            jnl.append(accepted_record(
                rid, dcop_yaml(dcops[rid]), params))
        jnl.append(completed_record("crash0", "FINISHED"))
        jnl.close()
        with open(jnl.path, "ab") as f:
            f.write(b"\x00\x00\x00\x20torn-mid-append")  # kill -9
        svc = SolveService(journal_dir=journal_dir, recover=True,
                           batch_window_s=0.05, max_batch=8)
        with ObservabilitySession(trace, "chrome"):
            svc.start()
            try:
                for rid in ("crash1", "crash2", "crash3", "crash4"):
                    result = svc.result(rid, wait=60.0)
                    assert result is not None \
                        and result["status"] == "FINISHED", \
                        f"replayed request {rid} lost after crash"
                    assert_valid_assignment(dcops[rid],
                                            result["assignment"])
                assert svc.replayed == 4, \
                    f"replayed {svc.replayed}, wanted exactly 4 " \
                    "(the pre-crash completion must not resurrect)"
                try:
                    svc.result("crash0")
                    raise AssertionError(
                        "completed-before-crash request resurrected")
                except KeyError:
                    pass
            finally:
                svc.stop(drain=False)
        from pydcop_tpu.observability.trace import load_trace_file

        names = {e["name"] for e in load_trace_file(trace)}
        assert "serve_replay" in names, \
            "serve_replay span missing from exported trace"
        return {"replayed": 4, "torn_tail": "truncated"}


def scenario_session_replay(seed, trace):
    """Crash-equivalent SESSION journal (ISSUE 13): an open record,
    3 acked event batches and a torn tail, no close — a
    ``recover=True`` start must rebuild the session's engine, apply
    every journaled batch, re-converge to EXACTLY the uninterrupted
    replay's final cost, announce the replay in the trace
    (``session_replay`` span), and a close must retire the session
    so a second recovery has nothing to resurrect."""
    import numpy as np

    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.engine.dynamic import build_dynamic_engine
    from pydcop_tpu.observability import ObservabilitySession
    from pydcop_tpu.serving.sessions import apply_event_batch
    from pydcop_tpu.serving.journal import (
        RequestJournal,
        session_event_record,
        session_open_record,
    )
    from pydcop_tpu.serving.service import SolveService

    rng = np.random.default_rng(seed)
    params = {"noise": 0.01, "stability": 0.001,
              "max_cycles": 500, "segment_cycles": 100}
    # Path topology: max-sum is exact there, so cost equality with
    # the uninterrupted run is a hard assertion, not a tolerance.
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"soak_sess_{seed}", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(10)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(9):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    batches = [
        [{"type": "change_factor", "name": f"c{int(rng.integers(9))}",
          "table": rng.integers(0, 10, size=(3, 3))
          .astype(float).tolist()}]
        for _ in range(3)
    ]
    # Uninterrupted reference through the same engine machinery.
    ref = build_dynamic_engine(dcop, params)
    ref.run(max_cycles=params["max_cycles"])
    for batch in batches:
        _applied, _touched, error = apply_event_batch(ref, batch)
        assert error is None, f"reference batch failed: {error}"
        ref.run(max_cycles=params["max_cycles"])
    expected = ref.cost(
        ref.run(max_cycles=params["max_cycles"]).assignment)

    with tempfile.TemporaryDirectory() as journal_dir:
        jnl = RequestJournal(journal_dir)
        jnl.append(session_open_record(
            "crash_sess", dcop_yaml(dcop), params))
        for i, batch in enumerate(batches):
            jnl.append(session_event_record("crash_sess", i + 1,
                                            batch))
        jnl.close()
        with open(jnl.path, "ab") as f:
            f.write(b"\x00\x00\x00\x20torn-mid-append")  # kill -9
        svc = SolveService(journal_dir=journal_dir, recover=True,
                           batch_window_s=0.05, max_batch=8)
        with ObservabilitySession(trace, "chrome"):
            svc.start()
            try:
                status = svc.sessions.status("crash_sess")
                assert status["seq"] == 3 \
                    and status["applied_seq"] == 3, \
                    f"acked batches lost in replay: {status}"
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    status = svc.sessions.status("crash_sess")
                    last = status["last"]
                    if last and last.get("converged"):
                        break
                    time.sleep(0.05)
                final = svc.sessions.close("crash_sess")
                assert final["cost"] == expected, \
                    f"recovered session cost {final['cost']} != " \
                    f"uninterrupted {expected}"
            finally:
                svc.stop(drain=False)
        svc2 = SolveService(journal_dir=journal_dir, recover=True,
                            batch_window_s=0.05)
        svc2.start()
        try:
            try:
                svc2.sessions.status("crash_sess")
                raise AssertionError(
                    "closed session resurrected on second recovery")
            except KeyError:
                pass
        finally:
            svc2.stop(drain=False)
    from pydcop_tpu.observability.trace import load_trace_file

    names = {e["name"] for e in load_trace_file(trace)}
    assert "session_replay" in names, \
        "session_replay span missing from exported trace"
    return {"replayed_batches": 3, "final_cost": expected}


def scenario_serve_poison_bin(seed, trace):
    """One poison request in a bin of 6: the failed dispatch BISECTS
    — the poison request fails alone, every bin-mate succeeds, the
    retries are accounted, and the breaker never opens."""
    from pydcop_tpu.serving.service import SolveService

    svc = SolveService(batch_window_s=0.3, max_batch=8)
    svc.start()
    real = svc._run_batch
    poison = set()

    def poisoned(reqs, params):
        if any(r.id in poison for r in reqs):
            raise RuntimeError("poison request in batch")
        return real(reqs, params)

    svc._run_batch = poisoned
    try:
        rids = [svc.submit(_serve_instance(8, seed * 10 + i),
                           params={"max_cycles": 40})
                for i in range(6)]
        poison.add(rids[seed % 6])
        statuses = {}
        for rid in rids:
            result = svc.result(rid, wait=60.0)
            assert result is not None, f"request {rid} hung"
            statuses[rid] = result["status"]
        assert statuses[rids[seed % 6]] == "ERROR", \
            "poison request must fail"
        mates = [r for r in rids if r != rids[seed % 6]]
        assert all(statuses[r] == "FINISHED" for r in mates), (
            "bin-mates of the poison request failed too: "
            f"{statuses}")
        assert svc.dispatch_retries > 0, \
            "bisection never retried (wholesale failure?)"
        assert svc.admission.breaker.state != "open", \
            "isolated poison failure opened the breaker"
        return {"retries": svc.dispatch_retries,
                "isolated": rids[seed % 6]}
    finally:
        svc.stop(drain=False)


def scenario_shard_trip_repartition(seed, trace):
    """Injected device loss mid-sharded-solve: rollback +
    re-partition onto the survivors, with the SAME final assignment
    and cost as the untripped run, the repartition visible in the
    trace, and the cycle counter monotone except across the
    announced rollback."""
    import numpy as np

    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.observability import ObservabilitySession
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    rng = np.random.default_rng(seed)
    d = Domain("d", "", [0, 1, 2])
    dcop = DCOP("soak_shard", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(20)]
    for v in vs:
        dcop.add_variable(v)
    seen, k = set(), 0
    while k < 30:
        i, j = rng.choice(20, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[key[0]], vs[key[1]]],
            rng.integers(0, 10, size=(3, 3)), name=f"c{k}"))
        k += 1
    ref = build_engine(dcop, {}, shards=2).run_checkpointed(
        max_cycles=60, segment_cycles=10)
    with ObservabilitySession(trace, "chrome"):
        res = build_engine(dcop, {}, shards=2).run_checkpointed(
            max_cycles=60, segment_cycles=10,
            recovery=RecoveryPolicy(trip_shard=((20, seed % 2),)))
    assert res.assignment == ref.assignment, \
        "repartitioned recovery diverged from the untripped solve"
    assert_valid_assignment(dcop, res.assignment)
    m = res.metrics
    assert m["shard_losses"] == 1 and m["repartitions"] == 1
    assert m["recovery_attempts"] == 0, \
        "a device loss must not consume the numerics restart budget"
    assert m["shard_recovery_s"] > 0
    events = assert_monotone_segments(trace)
    rollbacks = [e for e in events
                 if e["name"] == "recovery_rollback"]
    assert any(e["args"].get("action") == "repartition"
               for e in rollbacks), \
        "repartition rollback missing from exported trace"
    return {"lost_shard": seed % 2,
            "shard_recovery_s": m["shard_recovery_s"]}


def scenario_replica_kill(seed, trace):
    """ISSUE 15: SIGKILL one of two fleet replicas mid-burst.  Every
    202-acked request must complete through the router — the survivors
    keep serving while the dead replica's journal segment is handed to
    its restarted replacement and replayed — zero acknowledged
    requests lost, and the fleet SIGTERM-drains clean (every worker
    exit 0)."""
    import json
    import signal as signal_mod
    import urllib.error
    import urllib.request

    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    journal_dir = tempfile.mkdtemp(prefix="soak_fleet_")
    handle = api.serve(port=0, replicas=2, batch_window_s=0.25,
                       max_batch=8, journal_dir=journal_dir,
                       heartbeat_s=0.15)
    try:
        url = handle.url

        def post(payload):
            req = urllib.request.Request(
                url + "/solve", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as err:
                return err.code, json.loads(err.read())

        acked, dcops = [], {}
        for i in range(10):
            dcop = _serve_instance(10, seed * 1000 + i)
            status, body = post({"dcop": dcop_yaml(dcop),
                                 "params": {"max_cycles": 150}})
            assert status == 202, f"burst request {i}: {status}"
            acked.append(body["id"])
            dcops[body["id"]] = dcop
        # Mid-burst: batch windows still open on both replicas.
        victim = handle.router.replicas[seed % 2]
        os.kill(victim.proc.pid, signal_mod.SIGKILL)

        # The survivors must keep admitting DURING the recovery.
        extra = _serve_instance(10, seed * 1000 + 99)
        status, body = post({"dcop": dcop_yaml(extra),
                             "params": {"max_cycles": 150}})
        assert status in (200, 202, 503), \
            f"router wedged during replica death ({status})"
        if status == 202:
            acked.append(body["id"])
            dcops[body["id"]] = extra

        done = {}
        deadline = time.monotonic() + 120
        while len(done) < len(acked) \
                and time.monotonic() < deadline:
            for rid in acked:
                if rid in done:
                    continue
                try:
                    with urllib.request.urlopen(
                            url + f"/result/{rid}",
                            timeout=10) as resp:
                        if resp.status == 200:
                            done[rid] = json.loads(resp.read())
                except (urllib.error.HTTPError, OSError):
                    pass
            time.sleep(0.1)
        lost = sorted(set(acked) - set(done))
        assert not lost, \
            f"{len(lost)} acked request(s) lost to the SIGKILL: " \
            f"{lost}"
        assert all(r["status"] == "FINISHED"
                   for r in done.values()), \
            {k: v["status"] for k, v in done.items()
             if v["status"] != "FINISHED"}
        for rid in acked[:2]:
            assert_valid_assignment(dcops[rid],
                                    done[rid]["assignment"])
        assert victim.restarts == 1, \
            f"victim restarted {victim.restarts} times, wanted 1"
        stats = handle.router.stats()
        assert stats["deaths"] == 1 and stats["up"] == 2
    finally:
        summary = handle.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    exits = [w["exit"] for w in summary["workers"]]
    assert exits == [0, 0], \
        f"fleet SIGTERM drain not clean: exits {exits}"
    return {"acked": len(acked), "completed": len(done),
            "victim": victim.index,
            "deaths": stats["deaths"]}


def _session_chaos_problem(seed):
    """Path-topology dynamic session problem + event batches +
    uninterrupted reference cost.  Path topology: max-sum is exact
    there, so cost equality across a migration/kill is a hard
    assertion, not a tolerance (same recipe as session_replay)."""
    import numpy as np

    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.engine.dynamic import build_dynamic_engine
    from pydcop_tpu.serving.sessions import apply_event_batch

    rng = np.random.default_rng(seed)
    params = {"noise": 0.01, "stability": 0.001, "max_cycles": 500}
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"soak_mig_{seed}", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(10)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(9):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    batches = [
        [{"type": "change_factor",
          "name": f"c{int(rng.integers(9))}",
          "table": rng.integers(0, 10, size=(3, 3))
          .astype(float).tolist()}]
        for _ in range(5)
    ]
    ref = build_dynamic_engine(dcop, params)
    ref.run(max_cycles=params["max_cycles"])
    for batch in batches:
        _applied, _touched, error = apply_event_batch(ref, batch)
        assert error is None, f"reference batch failed: {error}"
        ref.run(max_cycles=params["max_cycles"])
    expected = ref.cost(
        ref.run(max_cycles=params["max_cycles"]).assignment)
    return dcop, params, batches, expected


def _fleet_request(url, method="GET", payload=None, timeout=60):
    import json
    import urllib.error
    import urllib.request

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


def _patch_until_acked(url, sid, batch, deadline_s=90):
    """PATCH with the elastic-fleet client contract: 409 means the
    session is frozen MIGRATING (retry lands on the new owner through
    the repointed pin), 503 means the owner is being
    recovered/adopted.  Both resolve; anything else is a failure."""
    deadline = time.monotonic() + deadline_s
    while True:
        status, out = _fleet_request(
            url + f"/session/{sid}/events", "PATCH",
            {"events": batch, "wait": True, "timeout": 30.0})
        if status == 200:
            return out
        assert status in (409, 503), \
            f"PATCH failed non-retryably: {status} {out}"
        assert time.monotonic() < deadline, \
            f"PATCH never recovered: last {status} {out}"
        time.sleep(0.2)


def scenario_session_migrate(seed, trace):
    """ISSUE 16 live migration under PATCH traffic: a warm session is
    migrated between replicas (operator ``POST /admin/migrate``)
    while a client keeps streaming event batches.  Every acked batch
    must survive the move — the final cost equals the uninterrupted
    single-engine run on integer tables (hard equality, path
    topology) — and the router pin must point at the new owner."""
    import threading

    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    dcop, params, batches, expected = _session_chaos_problem(seed)
    journal_dir = tempfile.mkdtemp(prefix="soak_mig_")
    handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                       journal_dir=journal_dir, heartbeat_s=0.15)
    try:
        url = handle.url
        status, body = _fleet_request(
            url + "/session", "POST",
            {"dcop": dcop_yaml(dcop), "params": params})
        assert status == 201, f"open failed: {status} {body}"
        sid = body["session_id"]
        _patch_until_acked(url, sid, batches[0])
        _patch_until_acked(url, sid, batches[1])
        source = handle.router.pinned(
            sid, handle.router._session_pins)

        migrate_result = {}

        def _migrate():
            migrate_result["reply"] = _fleet_request(
                url + "/admin/migrate", "POST",
                {"session_id": sid}, timeout=120)

        mover = threading.Thread(target=_migrate, daemon=True)
        mover.start()
        # Live PATCH traffic DURING the move: the freeze window 409s,
        # the retry lands on whichever side owns the session.
        for batch in batches[2:]:
            _patch_until_acked(url, sid, batch)
        mover.join(timeout=120)
        assert not mover.is_alive(), "/admin/migrate hung"
        status, out = migrate_result["reply"]
        assert status == 200, f"migrate failed: {status} {out}"
        target = handle.router.pinned(
            sid, handle.router._session_pins)
        assert target.index != source.index, \
            "router pin did not move with the session"

        st = {}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            _code, st = _fleet_request(url + f"/session/{sid}")
            last = st.get("last")
            if last and last.get("converged"):
                break
            time.sleep(0.05)
        assert st.get("applied_seq") == len(batches), \
            f"acked batches lost across migration: {st}"
        status, final = _fleet_request(url + f"/session/{sid}",
                                       "DELETE")
        assert status == 200, f"close failed: {status} {final}"
        assert final["cost"] == expected, \
            f"migrated session cost {final['cost']} != " \
            f"uninterrupted {expected}"
        stats = handle.router.stats()
        assert stats["migrations"] == 1, stats["migrations"]
    finally:
        handle.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return {"final_cost": expected,
            "from": source.index, "to": target.index}


def scenario_host_kill(seed, trace):
    """ISSUE 16 host death: a 4-replica fleet striped over 2
    simulated hosts loses ALL of host0's replicas (SIGKILL) mid-burst
    with a warm session pinned somewhere.  Zero acked solve requests
    lost (journal replay through the restarted slots), zero acked
    session events lost (the session is adopted by a survivor if its
    owner died), and the fleet heals back to 4 up."""
    import signal as signal_mod

    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    dcop, params, batches, expected = _session_chaos_problem(seed)
    journal_dir = tempfile.mkdtemp(prefix="soak_hostkill_")
    handle = api.serve(port=0, replicas=4, hosts=2,
                       batch_window_s=0.25, max_batch=8,
                       journal_dir=journal_dir, heartbeat_s=0.15)
    try:
        url = handle.url
        status, body = _fleet_request(
            url + "/session", "POST",
            {"dcop": dcop_yaml(dcop), "params": params})
        assert status == 201, f"open failed: {status} {body}"
        sid = body["session_id"]
        _patch_until_acked(url, sid, batches[0])
        _patch_until_acked(url, sid, batches[1])

        acked = []
        for i in range(10):
            inst = _serve_instance(10, seed * 1000 + i)
            status, body = _fleet_request(
                url + "/solve",
                "POST", {"dcop": dcop_yaml(inst),
                         "params": {"max_cycles": 150}})
            assert status == 202, f"burst request {i}: {status}"
            acked.append(body["id"])

        # Mid-burst: kill EVERY replica of host0 at once.
        victims = [r for r in handle.router.replicas
                   if r.host_id == "host0"]
        assert len(victims) == 2, \
            [r.host_id for r in handle.router.replicas]
        for victim in victims:
            os.kill(victim.proc.pid, signal_mod.SIGKILL)

        done = {}
        deadline = time.monotonic() + 180
        while len(done) < len(acked) \
                and time.monotonic() < deadline:
            for rid in acked:
                if rid in done:
                    continue
                code, out = _fleet_request(
                    url + f"/result/{rid}", timeout=10)
                if code == 200:
                    done[rid] = out
            time.sleep(0.1)
        lost = sorted(set(acked) - set(done))
        assert not lost, \
            f"{len(lost)} acked request(s) lost to the host kill: " \
            f"{lost}"
        assert all(r["status"] == "FINISHED"
                   for r in done.values()), \
            {k: v["status"] for k, v in done.items()
             if v["status"] != "FINISHED"}

        # Every acked session event survived — through adoption when
        # the owner died with its host, in place otherwise.
        _patch_until_acked(url, sid, batches[2], deadline_s=180)
        _code, st = _fleet_request(url + f"/session/{sid}")
        assert st.get("seq") == 3 and st.get("applied_seq") == 3, \
            f"acked session events lost: {st}"
        status, final = _fleet_request(url + f"/session/{sid}",
                                       "DELETE")
        assert status == 200, f"close failed: {status} {final}"

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if handle.router.up_count() == 4:
                break
            time.sleep(0.1)
        stats = handle.router.stats()
        assert stats["up"] == 4, \
            f"fleet never healed: {stats['up']}/4 up"
        assert stats["deaths"] == 2, stats["deaths"]
    finally:
        handle.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return {"acked": len(acked), "completed": len(done),
            "deaths": stats["deaths"],
            "session_events": st["applied_seq"]}


def scenario_fleet_partition_heal(seed, trace):
    """ISSUE 19 split-brain: a remote-joined replica owning a warm
    session is PARTITIONED (netfault blackhole) mid-PATCH-burst, the
    router declares it dead and ADOPTS the session onto a survivor
    (epoch bump), the partition heals — and the healed original is
    FENCED at the revival probe: its stale copy rejects direct writes
    with a structured 409, the surviving copy holds every acked
    batch, and the final cost equals the uninterrupted run (hard
    equality, path topology)."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    dcop, params, batches, expected = _session_chaos_problem(seed)
    journal_dir = tempfile.mkdtemp(prefix="soak_fpart_")
    remote_journal = tempfile.mkdtemp(prefix="soak_fpart_remote_")
    handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                       journal_dir=journal_dir, heartbeat_s=0.15)
    remote = api.serve(port=0, batch_window_s=0.05,
                       journal_dir=remote_journal)
    try:
        url = handle.url
        router = handle.router
        status, body = _fleet_request(
            url + "/session", "POST",
            {"dcop": dcop_yaml(dcop), "params": params})
        assert status == 201, f"open failed: {status} {body}"
        sid = body["session_id"]
        _patch_until_acked(url, sid, batches[0])

        remote_idx = router.register_remote(
            remote.url, host_id="hostB",
            journal_dir=remote_journal)["index"]
        status, out = _fleet_request(
            url + "/admin/migrate", "POST",
            {"session_id": sid, "target": remote_idx}, timeout=120)
        assert status == 200, f"migrate to remote failed: " \
                              f"{status} {out}"
        assert router.session_epoch(sid) == 2
        _patch_until_acked(url, sid, batches[1])
        _code, st = _fleet_request(remote.url + f"/session/{sid}")
        assert st.get("epoch") == 2, \
            f"migrated-in copy lost its epoch: {st}"

        # Sever router->remote.  The prober's verdict fires adoption
        # (the remote announced a reachable journal segment); PATCH
        # traffic sheds 503-with-retry until the pin repoints.
        netfault.install("link=*>hostB,blackhole=1,hold_s=0.05")
        _patch_until_acked(url, sid, batches[2], deadline_s=120)
        _patch_until_acked(url, sid, batches[3], deadline_s=120)
        survivor = router.pinned(sid, router._session_pins)
        assert survivor.index != remote_idx, \
            "session was not adopted off the partitioned replica"
        assert router.session_epoch(sid) >= 3
        injected = netfault.counters()
        assert injected.get("blackhole", 0) > 0, injected

        # Heal.  The revival probe must fence the stale copy BEFORE
        # any client byte can reach it.
        netfault.clear()
        deadline = time.monotonic() + 60
        fenced_st = {}
        while time.monotonic() < deadline:
            _code, fenced_st = _fleet_request(
                remote.url + f"/session/{sid}")
            if fenced_st.get("status") == "FENCED":
                break
            time.sleep(0.1)
        assert fenced_st.get("status") == "FENCED", \
            f"healed replica was not fenced: {fenced_st}"

        # Direct stale write to the healed original: structured 409.
        status, out = _fleet_request(
            remote.url + f"/session/{sid}/events", "PATCH",
            {"events": batches[4], "epoch": 2})
        assert status == 409 and out.get("stale_epoch") is True, \
            f"stale write not fenced: {status} {out}"
        assert out.get("session_epoch", 0) >= 2, out

        # The router-facing session keeps serving: last batch lands
        # on the survivor, nothing acked was lost or double-applied.
        _patch_until_acked(url, sid, batches[4])
        _code, st = _fleet_request(url + f"/session/{sid}")
        assert st.get("seq") == len(batches) \
            and st.get("applied_seq") == len(batches), \
            f"acked events lost/doubled across the partition: {st}"
        status, final = _fleet_request(url + f"/session/{sid}",
                                       "DELETE")
        assert status == 200, f"close failed: {status} {final}"
        assert final["cost"] == expected, \
            f"post-partition cost {final['cost']} != " \
            f"uninterrupted {expected}"
        stats = router.stats()
        assert stats["adopted_sessions"] >= 1, stats
    finally:
        netfault.clear()
        handle.stop()
        remote.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
        shutil.rmtree(remote_journal, ignore_errors=True)
    return {"final_cost": expected,
            "epoch": router.session_epoch(sid),
            "injected": injected}


def scenario_fleet_gray_failure(seed, trace):
    """ISSUE 19 gray failure: a replica whose link turns SLOW (500 ms
    injected delay, under the probe timeout) must be reported as a
    degraded/gray link on /healthz — and must NOT be declared dead
    (latency-aware probe scoring beats binary liveness).  Clearing
    the fault returns the fleet to ok."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                       heartbeat_s=0.2)
    try:
        url = handle.url
        router = handle.router
        deaths0 = router.stats()["deaths"]
        netfault.install("link=router>replica-1,delay_ms=500")
        gray = {}
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            _code, hz = _fleet_request(url + "/healthz", timeout=10)
            links = (hz.get("fleet") or {}).get("links") or []
            gray = next((l for l in links
                         if l.get("verdict") == "gray"), {})
            if hz.get("status") == "degraded" and gray:
                break
            time.sleep(0.1)
        assert gray, f"slow link never went gray: {hz}"
        assert gray["replica"] == 1, gray
        assert hz.get("status") == "degraded", hz
        assert (hz["fleet"].get("netfault_injected") or {}) \
            .get("delay", 0) > 0, hz
        assert router.stats()["deaths"] == deaths0, \
            "gray (slow-but-alive) replica was falsely killed"

        # Slow is not dead: a solve routed to the gray replica still
        # completes (the injected delay rides the forward too).
        inst = _serve_instance(8, seed)
        status, body = _fleet_request(
            url + "/solve", "POST",
            {"dcop": dcop_yaml(inst), "params": {"max_cycles": 80}})
        assert status == 202, f"solve under gray: {status} {body}"
        deadline = time.monotonic() + 60
        code, out = 0, {}
        while time.monotonic() < deadline:
            code, out = _fleet_request(
                url + f"/result/{body['id']}", timeout=10)
            if code == 200:
                break
            time.sleep(0.1)
        assert code == 200 and out["status"] == "FINISHED", \
            f"solve lost under gray link: {code} {out}"

        netfault.clear()
        deadline = time.monotonic() + 30
        hz = {}
        while time.monotonic() < deadline:
            _code, hz = _fleet_request(url + "/healthz", timeout=10)
            if hz.get("status") == "ok":
                break
            time.sleep(0.1)
        assert hz.get("status") == "ok", \
            f"fleet never recovered from gray: {hz}"
        assert router.stats()["deaths"] == deaths0
    finally:
        netfault.clear()
        handle.stop()
    return {"gray_probe_ms": gray.get("probe_ms"),
            "deaths": deaths0}


def scenario_fleet_retry_idempotent(seed, trace):
    """ISSUE 19 ambiguous-failure retry: the response to a forwarded
    /solve is LOST after the worker executed it (netfault
    lose_response).  The router's deadline-bounded retry redelivers
    to the SAME pinned replica; the worker dedupes on the
    router-minted id — the client sees one 202 and one result,
    exactly one execution, retries within the deadline budget."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    journal_dir = tempfile.mkdtemp(prefix="soak_fretry_")
    handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                       journal_dir=journal_dir, heartbeat_s=0.15)
    try:
        url = handle.url
        router = handle.router
        netfault.install(
            f"seed={seed};link=router>replica-*,path=/solve,"
            "lose_response=1.0,times=1")
        inst = _serve_instance(10, seed)
        t0 = time.monotonic()
        status, body = _fleet_request(
            url + "/solve", "POST",
            {"dcop": dcop_yaml(inst),
             "params": {"max_cycles": 120}, "deadline_s": 30.0})
        elapsed = time.monotonic() - t0
        assert status == 202, \
            f"solve not retried through lost response: " \
            f"{status} {body}"
        assert elapsed < 30.0, \
            f"retry blew the deadline budget: {elapsed:.1f}s"
        injected = netfault.counters()
        assert injected.get("lose_response", 0) == 1, injected

        deadline = time.monotonic() + 60
        code, out = 0, {}
        while time.monotonic() < deadline:
            code, out = _fleet_request(
                url + f"/result/{body['id']}", timeout=10)
            if code == 200:
                break
            time.sleep(0.1)
        assert code == 200 and out["status"] == "FINISHED", \
            f"result lost: {code} {out}"

        assert router.stats()["retries"] >= 1, router.stats()
        # Exactly one execution: the redelivery hit the worker's
        # dedupe table, not the solve queue.
        replica = router.pinned(body["id"])
        _code, wstats = _fleet_request(
            f"http://{replica.host}:{replica.port}/stats",
            timeout=10)
        assert wstats.get("deduped", 0) >= 1, wstats
    finally:
        netfault.clear()
        handle.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return {"retries": router.stats()["retries"],
            "deduped": wstats.get("deduped"),
            "elapsed_s": round(elapsed, 2)}


def scenario_forensics_under_faults(seed, trace):
    """ISSUE 20 forensics gate: a request whose response is LOST
    after execution (netfault lose_response) must be fully
    reconstructable from telemetry ALONE — ``GET
    /fleet/forensics/<id>`` shows one well-nested causal tree with
    the route pick, the retry hop, the dedupe hit on redelivery, and
    exactly ONE execute (``serve_dispatch``) span.  No log grepping,
    no worker /stats: the trace plane itself proves idempotency."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    journal_dir = tempfile.mkdtemp(prefix="soak_forensics_")
    handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                       journal_dir=journal_dir, heartbeat_s=0.15)
    try:
        url = handle.url
        netfault.install(
            f"seed={seed};link=router>replica-*,path=/solve,"
            "lose_response=1.0,times=1")
        inst = _serve_instance(10, seed)
        status, body = _fleet_request(
            url + "/solve", "POST",
            {"dcop": dcop_yaml(inst),
             "params": {"max_cycles": 120}, "deadline_s": 30.0})
        assert status == 202, \
            f"solve not retried through lost response: " \
            f"{status} {body}"
        rid = body["id"]
        deadline = time.monotonic() + 60
        code, out = 0, {}
        while time.monotonic() < deadline:
            code, out = _fleet_request(
                url + f"/result/{rid}", timeout=10)
            if code == 200:
                break
            time.sleep(0.1)
        assert code == 200 and out["status"] == "FINISHED", \
            f"result lost: {code} {out}"

        # Span shipping is async (bounded batches on a flush
        # interval): give the worker's shipper a few flushes before
        # judging the merged tree.
        def _nodes(roots):
            for node in roots:
                yield node
                yield from _nodes(node["children"])

        names, doc = set(), {}
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            code, doc = _fleet_request(
                url + f"/fleet/forensics/{rid}", timeout=10)
            if code == 200:
                names = set(doc["names"])
                if {"router_retry", "serve_dedupe",
                        "serve_dispatch"} <= names:
                    break
            time.sleep(0.25)
        assert code == 200, f"forensics unavailable: {code} {doc}"
        assert doc["well_nested"], \
            f"forensics tree not well-nested: {sorted(names)}"
        assert "router_route_pick" in names, sorted(names)
        assert "router_retry" in names, \
            f"retry hop missing from the tree: {sorted(names)}"
        assert "netfault_injected" in names, \
            f"injected fault missing from the tree: {sorted(names)}"
        assert "serve_dedupe" in names, \
            f"dedupe hit missing from the tree: {sorted(names)}"
        flat = list(_nodes(doc["tree"]))
        executes = [n for n in flat
                    if n["name"] == "serve_dispatch"
                    and n["ph"] == "X"]
        assert len(executes) == 1, (
            f"forensics shows {len(executes)} executions of {rid} "
            "(idempotent forwarding demands exactly one)")
        retries = [n for n in flat if n["name"] == "router_retry"]
    finally:
        netfault.clear()
        handle.stop()
        shutil.rmtree(journal_dir, ignore_errors=True)
    return {"spans": doc["spans"], "instants": doc["instants"],
            "lanes": doc["lanes"], "retry_hops": len(retries),
            "well_nested": doc["well_nested"]}


def scenario_anomaly_postmortem(seed, trace):
    """ISSUE 9 anomaly path: an injected guard trip, with file
    tracing OFF and only the always-on flight recorder attached,
    must leave a postmortem bundle on disk whose event tail contains
    the triggering instant plus pre-anomaly engine context — the
    black box works precisely when nobody was tracing."""
    import glob
    import json

    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.observability.flight import FlightRecorder
    from pydcop_tpu.observability.trace import tracer
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    bundle_dir = tempfile.mkdtemp(prefix="soak_bundles_")
    prev = tracer.flight
    tracer.set_flight(FlightRecorder(events=512,
                                     bundle_dir=bundle_dir))
    try:
        assert not tracer.enabled, \
            "scenario requires file tracing OFF (black-box mode)"
        dcop = ring_dcop()
        res = build_engine(dcop, {}).run_checkpointed(
            max_cycles=120, segment_cycles=7,
            recovery=RecoveryPolicy(trip_cycles=(14,),
                                    noise_seed=seed))
    finally:
        tracer.set_flight(prev)
    assert res.metrics["guard_trips"] == 1
    assert res.converged and res.assignment
    assert_valid_assignment(dcop, res.assignment)
    bundles = glob.glob(
        os.path.join(bundle_dir, "bundle_guard_trip_*.json"))
    assert len(bundles) == 1, \
        f"expected exactly one guard-trip bundle, found {bundles}"
    with open(bundles[0], encoding="utf-8") as f:
        doc = json.load(f)
    tail_names = [e["name"] for e in doc["events"]]
    anomalies = [e for e in doc["events"] if e["name"] == "anomaly"]
    assert anomalies, \
        f"triggering instant missing from bundle tail: {tail_names}"
    assert anomalies[-1]["args"]["kind"] == "guard_trip"
    assert anomalies[-1]["args"]["cycle"] == 14
    assert "engine_segment" in tail_names, \
        "pre-anomaly engine context missing from the ring tail"
    for section in ("metrics", "healthz", "env",
                    "efficiency"):
        assert section in doc, f"bundle missing {section} section"
    return {"bundle": bundles[0],
            "tail_events": len(doc["events"])}


# Quick-gate ordering: the first 6 cover every failure class (kill
# detection, engine recovery, partition healing, lossy links,
# checkpoint corruption, guard purity).
SCENARIOS = [
    ("kill_detected", scenario_kill_detected),
    ("guard_trip_device", scenario_guard_trip_device),
    ("partition_heal", scenario_partition_heal),
    ("drop_dup_delay", scenario_drop_dup_delay),
    ("checkpoint_corruption", scenario_checkpoint_corruption),
    ("guard_noop_device", scenario_guard_noop_device),
    ("delay_only_no_death", scenario_delay_only_no_death),
    ("drop_plus_kill", scenario_drop_plus_kill),
    ("serve_journal_replay", scenario_serve_journal_replay),
    ("session_replay", scenario_session_replay),
    ("serve_poison_bin", scenario_serve_poison_bin),
    ("replica_kill", scenario_replica_kill),
    ("session_migrate", scenario_session_migrate),
    ("host_kill", scenario_host_kill),
    ("fleet_partition_heal", scenario_fleet_partition_heal),
    ("fleet_gray_failure", scenario_fleet_gray_failure),
    ("fleet_retry_idempotent", scenario_fleet_retry_idempotent),
    ("forensics_under_faults", scenario_forensics_under_faults),
    ("shard_trip_repartition", scenario_shard_trip_repartition),
    ("anomaly_postmortem", scenario_anomaly_postmortem),
    ("decimation_guard_trip", scenario_decimation_guard_trip),
]

# The `make test` gate (--quick): the DEVICE-SIDE failure classes —
# engine guard recovery, checkpoint corruption, guard purity, plus
# the three ISSUE-8 classes (journal crash replay, poison-bin
# bisection, shard-loss repartition) — chosen to finish in ~20 s.
# The thread-runtime scenarios (kills, partitions, lossy links) stay
# in the full matrix (`make chaos-soak`); their invariants also run
# in `make test` through tests/unit/test_resilience_battery.py and
# test_selfheal_battery.py.
QUICK_GATE = [
    "guard_trip_device",
    "checkpoint_corruption",
    "guard_noop_device",
    "serve_journal_replay",
    "session_replay",
    "serve_poison_bin",
    "replica_kill",
    "session_migrate",
    "host_kill",
    "fleet_partition_heal",
    "fleet_gray_failure",
    "fleet_retry_idempotent",
    "forensics_under_faults",
    "shard_trip_repartition",
    "anomaly_postmortem",
    "decimation_guard_trip",
]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--scenarios", type=int, default=0,
                        help="run only the first N scenarios "
                             "(0 = full matrix)")
    parser.add_argument("--quick", action="store_true",
                        help="the `make test` gate: the device-side "
                             "scenario subset (~20 s)")
    parser.add_argument("--seed", type=int, default=DEFAULT_SEED)
    parser.add_argument("--only", default=None,
                        help="run a single scenario by name (replay)")
    parser.add_argument("--out", default=None,
                        help="directory for per-scenario trace files "
                             "(default: a temp dir)")
    args = parser.parse_args(argv)

    selected = SCENARIOS
    if args.only:
        selected = [s for s in SCENARIOS if s[0] == args.only]
        if not selected:
            names = ", ".join(name for name, _ in SCENARIOS)
            print(f"unknown scenario {args.only!r}; have: {names}")
            return 2
    elif args.quick:
        selected = [s for s in SCENARIOS if s[0] in QUICK_GATE]
    elif args.scenarios:
        selected = SCENARIOS[:args.scenarios]

    out_dir = args.out or tempfile.mkdtemp(prefix="chaos_soak_")
    os.makedirs(out_dir, exist_ok=True)
    print(f"chaos soak: {len(selected)} scenario(s), "
          f"seed={args.seed}, traces in {out_dir}")
    failures = 0
    t_total = time.perf_counter()
    for name, fn in selected:
        trace = os.path.join(out_dir, f"{name}.trace.json")
        t0 = time.perf_counter()
        try:
            obs = fn(args.seed, trace)
        except Exception as e:
            failures += 1
            print(f"FAIL  {name} ({time.perf_counter() - t0:.1f}s): "
                  f"{e}")
            print(f"      replay: python tools/chaos_soak.py "
                  f"--seed {args.seed} --only {name} "
                  f"--out {out_dir}")
            print(f"      trace:  {trace}  "
                  f"(pydcop trace summary {trace})")
            continue
        print(f"ok    {name} ({time.perf_counter() - t0:.1f}s) {obs}")
    status = "FAIL" if failures else "PASS"
    print(f"chaos soak {status}: {len(selected) - failures}/"
          f"{len(selected)} scenarios in "
          f"{time.perf_counter() - t_total:.1f}s")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
