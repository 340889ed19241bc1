"""Shard-loss recovery battery (ISSUE 8): a device lost mid-sharded-
solve trips the guarded segment, which rolls back to the last
validated snapshot, RE-PARTITIONS the factor graph onto the surviving
mesh, remaps the snapshot onto the new layout and resumes.

Asserted here:

- **repartition-recovery parity** (the acceptance criterion): a
  sharded solve with an injected shard trip finishes with the same
  assignment and cost as the untripped run — on integer cost tables
  the f32 message sums are exact, so parity is exact even though the
  surviving mesh reassociates reductions;
- a solve survives a SEQUENCE of losses (4 -> 3 -> 2 shards) and
  every loss is accounted (``repartitions``, ``lost_shards``,
  ``shard_recovery_s``, ``shard_losses``);
- shard losses do not consume the escalation-ladder restart budget
  (``recovery_attempts`` stays 0) — a numerics intervention makes no
  sense for a dead device;
- losing the LAST device raises :class:`RecoveryExhausted` carrying
  the partial trajectory (last validated snapshot's assignment);
- the guard trip and the repartition rollback are visible in the
  exported trace (``guard_trip`` kind=shard_loss,
  ``recovery_rollback`` action=repartition);
- the failure modes fail loudly: ``trip_shard`` on an engine without
  the repartition hook, malformed trip entries, out-of-range shard
  indices.

Runs on the repo-wide 8-virtual-device CPU platform (root
conftest.py).
"""

import numpy as np
import pytest

import jax

from pydcop_tpu.algorithms.maxsum import build_engine
from pydcop_tpu.api import solve
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.resilience.recovery import (
    NoSurvivingDevices,
    RecoveryExhausted,
    RecoveryPolicy,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)

MAX_CYCLES = 60
SEGMENT = 10


def _loopy_dcop(n_vars=24, n_edges=36, d=3, seed=0) -> DCOP:
    """Random loopy binary DCOP with INTEGER tables: f32 sums of
    integer costs are exact, so tripped-vs-untripped parity is
    bit-exact despite the repartition's reduction reorder."""
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP("loopy", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    seen = set()
    k = 0
    while k < n_edges:
        i, j = rng.choice(n_vars, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        m = rng.integers(0, 10, size=(d, d))
        dcop.add_constraint(
            NAryMatrixRelation([vs[key[0]], vs[key[1]]], m,
                               name=f"c{k}"))
        k += 1
    dcop.add_agents([AgentDef("a0")])
    return dcop


def _run(dcop, shards, recovery=None):
    return build_engine(dcop, {}, shards=shards).run_checkpointed(
        max_cycles=MAX_CYCLES, segment_cycles=SEGMENT,
        recovery=recovery)


class TestShardTripParity:
    def test_single_trip_same_assignment_and_cost(self):
        dcop = _loopy_dcop()
        ref = _run(dcop, shards=4)
        res = _run(dcop, shards=4,
                   recovery=RecoveryPolicy(trip_shard=((20, 1),)))
        assert res.assignment == ref.assignment, \
            "repartitioned recovery diverged from the untripped run"
        m = res.metrics
        assert m["shard_losses"] == 1
        assert m["repartitions"] == 1
        assert m["lost_shards"] == [1]
        assert m["shard_recovery_s"] > 0
        assert m["n_shards"] == 3, "metrics must reflect the final mesh"
        assert m["guard_violations"][0]["kind"] == "shard_loss"
        assert m["guard_violations"][0]["shard"] == 1

    def test_trip_does_not_consume_restart_budget(self):
        """max_restarts=0 would exhaust on the FIRST ladder trip;
        a shard loss must sail through it untouched."""
        dcop = _loopy_dcop(seed=1)
        res = _run(dcop, shards=4, recovery=RecoveryPolicy(
            max_restarts=0, trip_shard=((20, 2),)))
        assert res.metrics["shard_losses"] == 1
        assert res.metrics["recovery_attempts"] == 0
        assert res.metrics["recovery_actions"] == ["repartition"]

    def test_loss_sequence_survives_and_accounts(self):
        """4 -> 3 -> 2 shards: the second trip's shard index applies
        to the ALREADY-SHRUNK mesh; parity still holds."""
        dcop = _loopy_dcop(seed=2)
        ref = _run(dcop, shards=4)
        res = _run(dcop, shards=4, recovery=RecoveryPolicy(
            trip_shard=((10, 3), (30, 0))))
        assert res.assignment == ref.assignment
        m = res.metrics
        assert m["shard_losses"] == 2
        assert m["repartitions"] == 2
        assert m["lost_shards"] == [3, 0]
        assert m["n_shards"] == 2

    def test_cost_parity_via_api_solve(self):
        """The same path through api.solve(shards=..., recovery=...):
        identical cost and assignment to the untripped solve."""
        dcop = _loopy_dcop(seed=3)
        ref = solve(dcop, "maxsum", max_cycles=MAX_CYCLES, shards=2)
        res = solve(dcop, "maxsum", max_cycles=MAX_CYCLES, shards=2,
                    recovery=RecoveryPolicy(trip_shard=((15, 0),)))
        assert res["assignment"] == ref["assignment"]
        assert res["cost"] == ref["cost"]
        assert res["metrics"]["shard_losses"] == 1


class TestShardTripTrace:
    def test_trip_and_repartition_visible_in_trace(self, tmp_path):
        from pydcop_tpu.observability.trace import (
            load_trace_file,
            tracer,
        )

        trace_path = str(tmp_path / "shardloss.trace.json")
        tracer.enable()
        try:
            _run(_loopy_dcop(seed=4), shards=4,
                 recovery=RecoveryPolicy(trip_shard=((20, 1),)))
        finally:
            tracer.disable()
            tracer.export(trace_path, "chrome")
        events = load_trace_file(trace_path)
        trips = [e for e in events if e["name"] == "guard_trip"]
        assert any(e["args"].get("kind") == "shard_loss"
                   and e["args"].get("shard") == 1 for e in trips)
        rollbacks = [e for e in events
                     if e["name"] == "recovery_rollback"]
        assert any(e["args"].get("action") == "repartition"
                   and e["args"].get("lost_shard") == 1
                   for e in rollbacks)


class TestShardTripExhaustion:
    def test_last_device_loss_exhausts_with_partial(self):
        """2 -> 1 -> nothing: the second loss leaves an empty mesh;
        RecoveryExhausted must carry the last snapshot's partial
        trajectory instead of crashing bare."""
        dcop = _loopy_dcop(seed=5)
        with pytest.raises(RecoveryExhausted) as err:
            _run(dcop, shards=2, recovery=RecoveryPolicy(
                trip_shard=((10, 1), (11, 0))))
        exc = err.value
        assert "no surviving devices" in str(exc)
        assert exc.partial["assignment"] is not None
        assert set(exc.partial["assignment"]) == \
            {f"v{i}" for i in range(24)}
        assert [v.kind for v in exc.violations] == \
            ["shard_loss", "shard_loss"]
        assert isinstance(exc.__cause__, NoSurvivingDevices)

    def test_unsharded_engine_rejects_trip_shard(self):
        """trip_shard needs the repartition hook: a single-device
        engine must fail loudly, not ignore the injection."""
        dcop = _loopy_dcop(seed=6)
        with pytest.raises(ValueError, match="repartition_after_loss"):
            build_engine(dcop, {}).run_checkpointed(
                max_cycles=MAX_CYCLES, segment_cycles=SEGMENT,
                recovery=RecoveryPolicy(trip_shard=((10, 0),)))

    def test_out_of_range_shard_rejected(self):
        dcop = _loopy_dcop(seed=7)
        with pytest.raises(ValueError, match="out of range"):
            _run(dcop, shards=2,
                 recovery=RecoveryPolicy(trip_shard=((10, 5),)))

    def test_malformed_trip_entry_rejected_at_policy(self):
        with pytest.raises(ValueError, match="cycle, shard"):
            RecoveryPolicy(trip_shard=((10,),))


class TestRepartitionStateRemap:
    def test_remap_preserves_messages_exactly(self):
        """The remap is a pure relabeling: gathering the remapped
        state back to global real-factor row order must reproduce the
        original snapshot's messages bit-for-bit (only the halo is
        recomputed, against the new layout's boundary set)."""
        from pydcop_tpu.engine.partition import partition_compiled
        from pydcop_tpu.engine.runner import ShardedMaxSumEngine

        dcop = _loopy_dcop(seed=8)
        engine = build_engine(dcop, {}, shards=4)
        assert isinstance(engine, ShardedMaxSumEngine)
        # Run a few cycles so messages are non-trivial.
        engine.run(max_cycles=8)
        state = engine.init_state()
        (state, _), _, _ = engine._call(
            *engine._segment(8, False), engine.graph, state)
        snap = jax.tree_util.tree_map(lambda x: x, state)
        new_state = engine.repartition_after_loss(2, snap)
        assert engine.mesh.size == 3
        assert engine.partition.n_shards == 3
        # Every bucket's per-factor message rows survive the
        # relabeling: compare global gatherings old vs new.
        old_part = partition_compiled(engine._source_graph, 4)
        from pydcop_tpu.engine.sharding import _factor_row_maps

        old_maps = _factor_row_maps(engine._source_graph, old_part)
        new_maps = _factor_row_maps(engine._source_graph,
                                    engine.partition)

        def gather(blocked, maps, i):
            blocked = np.asarray(jax.device_get(blocked))
            rows, per_shard = maps[i]
            out = np.zeros((rows.shape[0],) + blocked.shape[2:],
                           blocked.dtype)
            for s, sel in enumerate(per_shard):
                out[sel] = blocked[s, :sel.shape[0]]
            return out

        for i in range(len(engine._source_graph.buckets)):
            np.testing.assert_array_equal(
                gather(snap.f2v[i], old_maps, i),
                gather(new_state.f2v[i], new_maps, i),
                err_msg=f"f2v bucket {i} corrupted by remap")
            np.testing.assert_array_equal(
                gather(snap.v2f[i], old_maps, i),
                gather(new_state.v2f[i], new_maps, i),
                err_msg=f"v2f bucket {i} corrupted by remap")
        assert int(new_state.cycle) == int(snap.cycle)
