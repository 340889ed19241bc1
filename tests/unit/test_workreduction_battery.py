"""Work-reduction battery (ISSUE 10): branch-and-bound message
pruning, segmented decimation, and the whole-algorithm portfolio racer.

The contracts pinned here:

- **Pruning never changes values.**  On integer cost tables the pruned
  trajectory is BIT-IDENTICAL to the dense one — every state leaf,
  not just the assignment — across all aggregation strategies and
  under ``shards=N`` (the per-shard local reductions prune with a
  globally-agreed phase predicate).
- **Traced solves stop at the fixpoint** like untraced ones (the
  pre-PR-10 trace paid full ``max_cycles`` after convergence), with
  the cost curve's tail holding the final value.
- **Decimation is anytime-sane** on graph coloring: the final cost is
  within tolerance of the best intermediate and of the colorable
  optimum, every variable ends clamped, and ``active_edges`` reports
  the shrunk work set.
- **Checkpoint/resume mid-decimation equals uninterrupted** — the
  clamp set travels with the snapshot (DecimationState).
- **The portfolio racer caches by structure**: hit/replay with no
  re-race (also through ``api.solve(algo="auto")`` — the acceptance
  assertion), invalid cache entries re-measure, different shapes
  never share a decision.
"""

import json
import os
import tempfile
from functools import partial

import jax
import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.ops import maxsum as maxsum_ops


def loopy_dcop(n=40, d=16, seed=0, density=1.8, spread=40):
    """Loopy coloring with INTEGER tables and a domain large enough
    to engage pruning (compile.PRUNE_MIN_DOMAIN): equality penalty
    per edge, integer unary costs via a unary matrix relation — the
    bit-identity instance family."""
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP(f"wr{n}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    eye = np.eye(d)
    seen, k = set(), 0
    while k < int(n * density):
        i, j = rng.choice(n, 2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(
            NAryMatrixRelation([vs[i], vs[j]], eye, f"c{k}"))
        k += 1
    # Integer unary costs as unary matrix relations (keeps the
    # instance's tables integral end to end).
    for i, v in enumerate(vs):
        u = rng.integers(0, spread, size=(d,)).astype(float)
        dcop.add_constraint(NAryMatrixRelation([v], u, f"u{i}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def coloring_dcop(n=36, seed=1, density=1.6):
    """3-colorable-ish loopy coloring (no unaries) — the decimation
    quality instance."""
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"col{n}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    eq = np.eye(3)
    seen, k = set(), 0
    while k < int(n * density):
        i, j = rng.choice(n, 2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(
            NAryMatrixRelation([vs[i], vs[j]], eq, f"c{k}"))
        k += 1
    dcop.add_agents([AgentDef("a0")])
    return dcop


def _leaves_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


class TestPruningBitIdentity:
    @pytest.mark.parametrize("aggregation",
                             ["scatter", "sorted", "ell", "boundary"])
    def test_identical_across_aggregations(self, aggregation):
        dcop = loopy_dcop()
        graph, _meta = compile_dcop(
            dcop, noise_level=0.0, aggregation=aggregation,
            use_cache=False)
        g = jax.device_put(graph)
        runs = {}
        for prune in (False, True):
            fn = jax.jit(partial(
                maxsum_ops.run_maxsum, max_cycles=120,
                stop_on_convergence=False, prune=prune))
            runs[prune] = jax.block_until_ready(fn(g))
        assert _leaves_equal(runs[False], runs[True]), (
            f"pruned trajectory diverged from dense under "
            f"aggregation={aggregation}")

    def test_identical_with_engine_and_noise(self):
        """The real engine path (tie-break noise on): engine-level
        prune=True produces the identical solve."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = loopy_dcop(seed=3)
        results = {}
        for prune in (False, True):
            res = build_engine(
                dcop, {"prune": prune}).run(
                    max_cycles=150, stop_on_convergence=False)
            results[prune] = res
        assert results[False].assignment == results[True].assignment
        assert results[False].cycles == results[True].cycles
        assert results[False].converged == results[True].converged

    def test_identical_under_shards(self):
        """Partitioned engine: per-shard pruned reductions with the
        global phase predicate stay bit-identical to dense."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = loopy_dcop(n=48, seed=5)
        engines = {
            prune: build_engine(dcop, {"prune": prune,
                                       "noise": 0.0}, shards=4)
            for prune in (False, True)
        }
        states = {}
        for prune, eng in engines.items():
            st, values = eng._ops.run_maxsum(
                eng.graph, 100, stop_on_convergence=False,
                prune=eng.prune)
            states[prune] = (st, values)
        assert np.array_equal(np.asarray(states[False][1]),
                              np.asarray(states[True][1]))
        assert _leaves_equal(states[False][0], states[True][0])

    def test_segmented_equals_plain_with_prune(self):
        """The segmented runner's pruned segments reproduce the
        one-program pruned solve (the checkpointing contract holds
        with pruning on)."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = loopy_dcop(seed=7)
        plain = build_engine(dcop, {"prune": True}).run(
            max_cycles=140)
        seg = build_engine(dcop, {"prune": True}).run_checkpointed(
            max_cycles=140, segment_cycles=20)
        assert plain.assignment == seg.assignment
        assert plain.cycles == seg.cycles


class TestTraceEarlyExit:
    def test_traced_and_untraced_cycles_agree(self):
        """The PR-10 satellite: run_maxsum_trace used to ignore
        stop_on_convergence, paying full max_cycles after the
        fixpoint."""
        dcop = loopy_dcop(seed=2)
        graph, _meta = compile_dcop(dcop, noise_level=0.0,
                                    use_cache=False)
        g = jax.device_put(graph)
        st_run, v_run = jax.block_until_ready(jax.jit(partial(
            maxsum_ops.run_maxsum, max_cycles=400))(g))
        st_tr, v_tr, costs = jax.block_until_ready(jax.jit(partial(
            maxsum_ops.run_maxsum_trace, max_cycles=400))(g))
        assert int(st_tr.cycle) == int(st_run.cycle)
        assert int(st_run.cycle) < 400, \
            "instance never converged; the agreement check is vacuous"
        assert np.array_equal(np.asarray(v_tr), np.asarray(v_run))
        # The tail of the curve holds the final cost.
        costs = np.asarray(costs)
        conv = int(st_tr.cycle)
        assert np.all(costs[conv:] == costs[conv - 1])

    def test_engine_run_trace_agrees_with_run(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = loopy_dcop(seed=4)
        eng_a = build_engine(dcop, {})
        eng_b = build_engine(dcop, {})
        run = eng_a.run(max_cycles=400)
        trace = eng_b.run_trace(max_cycles=400)
        assert trace.cycles == run.cycles
        assert trace.assignment == run.assignment
        assert len(trace.metrics["cost_trace"]) == 400


class TestDecimation:
    def test_anytime_and_final_cost_on_coloring(self):
        """Decimated coloring: final cost within tolerance of the
        best intermediate (anytime sanity) and of the colorable
        optimum; every variable clamped; active_edges shrinks to 0."""
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.runner import DecimationPlan

        dcop = coloring_dcop()

        segment_costs = []

        class CostProbe:
            def on_segment(self, state, values, run_s, compile_s):
                vals = np.asarray(jax.device_get(values))
                asg = {f"v{i}": int(vals[i])
                       for i in range(len(vals))}
                segment_costs.append(dcop.solution_cost(asg)[0])

        eng = build_engine(dcop, {})
        res = eng.run_checkpointed(
            max_cycles=1500, segment_cycles=25,
            decimation=DecimationPlan(frac_per_round=0.2,
                                      cycles_per_round=25),
            probe=CostProbe(),
        )
        final_cost, violations = dcop.solution_cost(res.assignment)
        assert res.converged
        assert res.metrics["decimated_vars"] == len(dcop.variables)
        assert res.metrics["decimated_fraction"] == 1.0
        assert res.metrics["active_edges"] == 0
        assert res.metrics["decimation_rounds"] >= 2
        # Anytime sanity: the run never ends worse than its best
        # validated intermediate (one conflict of slack for the last
        # clamp round).
        assert final_cost <= min(segment_costs) + 1
        # Quality: a sparse loopy coloring is (near-)colorable.
        assert final_cost <= 2

    def test_resume_mid_decimation_equals_uninterrupted(self):
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.runner import DecimationPlan
        from pydcop_tpu.resilience.checkpoint import (
            CheckpointManager,
            resume_from_checkpoint,
        )

        dcop = coloring_dcop(seed=6)
        plan = DecimationPlan(frac_per_round=0.25,
                              cycles_per_round=20)
        full = build_engine(dcop, {}).run_checkpointed(
            max_cycles=1500, segment_cycles=20, decimation=plan)
        with tempfile.TemporaryDirectory() as td:
            manager = CheckpointManager(td, every=20, keep=50)
            part = build_engine(dcop, {}).run_checkpointed(
                max_cycles=1500, segment_cycles=20, decimation=plan,
                manager=manager, max_segments=3)
            assert part.metrics["interrupted"]
            assert 0 < part.metrics["decimated_vars"] \
                < len(dcop.variables)
            resumed = resume_from_checkpoint(
                build_engine(dcop, {}), manager, max_cycles=1500,
                segment_cycles=20, decimation=plan)
            assert resumed.metrics["resumed_from_cycle"] > 0
        assert resumed.assignment == full.assignment
        assert resumed.cycles == full.cycles
        assert resumed.metrics["decimated_vars"] \
            == full.metrics["decimated_vars"]

    def test_guard_trip_on_first_segment_rolls_back_cleanly(self):
        """A trip on the VERY FIRST segment must roll the clamp set
        back to the (empty) initial snapshot, not crash unpacking a
        never-retained one (regression: the initial recovery retain
        used to skip the decimation bookkeeping)."""
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.runner import DecimationPlan
        from pydcop_tpu.resilience.recovery import RecoveryPolicy

        dcop = coloring_dcop(seed=11)
        res = build_engine(dcop, {}).run_checkpointed(
            max_cycles=900, segment_cycles=15,
            decimation=DecimationPlan(frac_per_round=0.25,
                                      cycles_per_round=15),
            recovery=RecoveryPolicy(trip_cycles=(1,)))
        assert res.metrics["guard_trips"] == 1
        assert res.metrics["decimation_rollbacks"] == 1
        assert res.metrics["decimated_vars"] == len(dcop.variables)

    def test_decimation_rejected_on_sharded_and_lane(self):
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.runner import DecimationPlan

        dcop = coloring_dcop(seed=8)
        eng = build_engine(dcop, {}, shards=2)
        with pytest.raises(ValueError, match="decimation"):
            eng.run_checkpointed(
                max_cycles=100, decimation=DecimationPlan())

    def test_resume_without_plan_refused(self):
        """A DecimationState snapshot must not silently resume as a
        plain run (the clamp set would be dropped)."""
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.runner import (
            DecimationState,
            MaxSumEngine,
        )

        dcop = coloring_dcop(seed=9)
        eng = build_engine(dcop, {})
        assert isinstance(eng, MaxSumEngine)
        fake = DecimationState(
            solver=eng.init_state(),
            fixed=np.zeros(len(dcop.variables), bool),
            var_costs=np.asarray(
                jax.device_get(eng.graph.var_costs)),
        )
        with pytest.raises(ValueError, match="clamp set"):
            eng.run_checkpointed(max_cycles=50, initial_state=fake)


class TestPortfolio:
    def _graph(self, n=30, seed=0):
        dcop = coloring_dcop(n=n, seed=seed)
        graph, _ = compile_dcop(dcop, noise_level=0.01,
                                use_cache=False)
        return dcop, graph

    def test_measure_then_replay(self):
        from pydcop_tpu.engine.autotune import (
            PORTFOLIO_CANDIDATES,
            autotune_portfolio,
            dpop_portfolio_runner,
        )

        dcop = coloring_dcop(n=30, seed=0)
        graph, meta = compile_dcop(dcop, noise_level=0.01,
                                   use_cache=False)
        with tempfile.TemporaryDirectory() as td:
            cache = os.path.join(td, "tune.json")
            dpop_runner = dpop_portfolio_runner(dcop, graph, meta)
            info = autotune_portfolio(
                graph, race_cycles=30, cache_file=cache,
                extra_runners={"dpop": dpop_runner})
            assert info["algo"] in PORTFOLIO_CANDIDATES
            assert info["portfolio_source"] == "measured"
            timed = [n for n, t in
                     info["portfolio_timings_ms"].items()
                     if t is not None]
            # "dpop" only races when the structure is width-feasible
            # (runner is None past the gate) — every unconditional
            # candidate must have been timed either way.
            expected = set(PORTFOLIO_CANDIDATES) - (
                set() if dpop_runner is not None else {"dpop"})
            assert set(timed) == expected
            assert info["portfolio_target_cost"] is not None
            replay = autotune_portfolio(
                graph, race_cycles=30, cache_file=cache)
            assert replay["portfolio_source"] == "cache"
            assert replay["algo"] == info["algo"]

    def test_invalid_cache_entry_remeasures(self):
        from pydcop_tpu.engine.autotune import (
            autotune_portfolio,
            graph_shape_key,
            portfolio_key,
        )

        _dcop, graph = self._graph(seed=1)
        with tempfile.TemporaryDirectory() as td:
            cache = os.path.join(td, "tune.json")
            key = portfolio_key(graph_shape_key(graph))
            with open(cache, "w") as f:
                json.dump({key: {"algo": "not-a-kernel"}}, f)
            info = autotune_portfolio(
                graph, race_cycles=30, cache_file=cache)
            assert info["portfolio_source"] == "measured"

    def test_different_shape_different_key(self):
        from pydcop_tpu.engine.autotune import graph_shape_key

        _d1, g1 = self._graph(n=30, seed=0)
        _d2, g2 = self._graph(n=32, seed=0)
        assert graph_shape_key(g1) != graph_shape_key(g2)

    def test_api_auto_replays_on_second_solve(self, monkeypatch):
        """The ISSUE 10 acceptance: api.solve(algo='auto') picks a
        cached portfolio decision on the second same-structure solve
        — no re-race."""
        from pydcop_tpu.api import solve

        def ring_instance(table_seed):
            """Fixed topology (same structure signature), seeded
            random tables (a different problem instance)."""
            rng = np.random.default_rng(table_seed)
            dom = Domain("c", "", [0, 1, 2])
            dcop = DCOP(f"ring{table_seed}", objective="min")
            vs = [Variable(f"v{i}", dom) for i in range(24)]
            for v in vs:
                dcop.add_variable(v)
            edges = [(i, (i + 1) % 24) for i in range(24)]
            edges += [(i, (i + 12) % 24) for i in range(0, 24, 3)]
            for k, (i, j) in enumerate(edges):
                dcop.add_constraint(NAryMatrixRelation(
                    [vs[i], vs[j]],
                    rng.integers(0, 10, (3, 3)).astype(float),
                    f"c{k}"))
            dcop.add_agents([AgentDef("a0")])
            return dcop

        with tempfile.TemporaryDirectory() as td:
            monkeypatch.setenv(
                "PYDCOP_AGG_AUTOTUNE_CACHE",
                os.path.join(td, "tune.json"))
            first = solve(ring_instance(2), "auto", max_cycles=120)
            second = solve(ring_instance(3), "auto", max_cycles=120)
        assert first["metrics"]["portfolio"][
            "portfolio_source"] == "measured"
        assert second["metrics"]["portfolio"][
            "portfolio_source"] == "cache"
        assert second["metrics"]["portfolio"]["algo"] \
            == first["metrics"]["portfolio"]["algo"]
        assert first["status"] == "FINISHED" or first["cost"] >= 0

    def test_auto_rejected_off_device(self):
        from pydcop_tpu.api import solve

        with pytest.raises(ValueError, match="auto"):
            solve(coloring_dcop(n=12, seed=4), "auto",
                  backend="thread")


class TestServingConsumption:
    def test_prune_auto_resolves_from_portfolio_cache(self,
                                                      monkeypatch):
        """The serving dispatch path consumes the racer's cached
        decision: prune='auto' resolves to the pruned program when
        maxsum_prune won, and the batched answer still equals the
        solo solve (pruning never changes values)."""
        from pydcop_tpu.api import solve
        from pydcop_tpu.engine.autotune import _store_cache
        from pydcop_tpu.engine.autotune import (
            graph_shape_key,
            portfolio_key,
        )
        from pydcop_tpu.serving.service import SolveService

        dcop = coloring_dcop(n=24, seed=5)
        with tempfile.TemporaryDirectory() as td:
            cache = os.path.join(td, "tune.json")
            monkeypatch.setenv("PYDCOP_AGG_AUTOTUNE_CACHE", cache)
            graph, _ = compile_dcop(dcop, noise_level=0.01)
            _store_cache(cache, {
                portfolio_key(graph_shape_key(graph)): {
                    "algo": "maxsum_prune"}})
            service = SolveService(batch_window_s=0.005,
                                   max_batch=8).start()
            try:
                rid = service.submit(
                    dcop, params={"max_cycles": 60,
                                  "prune": "auto"})
                res = service.result(rid, wait=60)
                assert res["status"] == "FINISHED"
                assert service.stats()["portfolio_resolved"] == 1
            finally:
                service.stop(drain=False)
            solo = solve(dcop, "maxsum", max_cycles=60,
                         algo_params={"layout": "edge"})
            assert res["assignment"] == solo["assignment"]

    def test_prune_param_rides_the_bin_key(self):
        from pydcop_tpu.serving import binning

        dcop = coloring_dcop(n=18, seed=6)
        graph, _ = compile_dcop(dcop, noise_level=0.01)
        k0 = binning.bin_key(
            graph, binning.normalize_params({"prune": 0}))
        k1 = binning.bin_key(
            graph, binning.normalize_params({"prune": 1}))
        assert k0 != k1

    def test_bad_prune_param_rejected(self):
        from pydcop_tpu.serving import binning

        with pytest.raises(ValueError, match="prune"):
            binning.normalize_params({"prune": "sometimes"})
