"""Engine + MaxSum kernel tests.

The oracle for kernel semantics is a naive dict-based reimplementation of
the reference's message updates (factor_costs_for_var maxsum.py:382,
costs_for_factor :623) evaluated on tiny graphs.
"""

import itertools

import jax
import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable, VariableWithCostFunc
from pydcop_tpu.dcop.relations import constraint_from_str
from pydcop_tpu.engine.compile import BIG, compile_dcop, compile_factor_graph
from pydcop_tpu.engine.runner import MaxSumEngine
from pydcop_tpu.engine.sharding import make_mesh
from pydcop_tpu.ops import maxsum as ops


def _tiny_dcop():
    d = Domain("colors", "", ["R", "G"])
    v1 = VariableWithCostFunc("v1", d, "-0.1 if v1 == 'R' else 0.1")
    v2 = VariableWithCostFunc("v2", d, "-0.1 if v2 == 'G' else 0.1")
    v3 = VariableWithCostFunc("v3", d, "-0.1 if v3 == 'G' else 0.1")
    c1 = constraint_from_str("c1", "1 if v1 == v2 else 0", [v1, v2])
    c2 = constraint_from_str("c2", "1 if v2 == v3 else 0", [v2, v3])
    dcop = DCOP("tiny")
    dcop.add_constraint(c1)
    dcop.add_constraint(c2)
    return dcop


class TestCompile:
    def test_shapes_and_padding(self):
        dcop = _tiny_dcop()
        graph, meta = compile_dcop(dcop)
        assert graph.n_vars == 3
        assert graph.dmax == 2
        assert len(graph.buckets) == 1  # all arity-2
        b = graph.buckets[0]
        assert b.costs.shape == (2, 2, 2)
        assert b.var_ids.shape == (2, 2)
        assert meta.factor_names == ("c1", "c2")

    def test_mixed_arity_buckets(self):
        d = Domain("d", "", [0, 1, 2])
        x, y, z = (Variable(n, d) for n in "xyz")
        c1 = constraint_from_str("c1", "x + y", [x, y])
        c2 = constraint_from_str("c2", "x * y * z", [x, y, z])
        c3 = constraint_from_str("c3", "z", [z])
        graph, meta = compile_factor_graph([x, y, z], [c1, c2, c3])
        arities = sorted(b.arity for b in graph.buckets)
        assert arities == [1, 2, 3]

    def test_domain_padding_big(self):
        d2 = Domain("d2", "", [0, 1])
        d3 = Domain("d3", "", [0, 1, 2])
        x, y = Variable("x", d2), Variable("y", d3)
        c = constraint_from_str("c", "x + y", [x, y])
        graph, _ = compile_factor_graph([x, y], [c])
        costs = graph.buckets[0].costs
        # x axis padded at index 2:
        assert np.all(costs[0, 2, :] == BIG)
        assert costs[0, 1, 2] == 3  # valid corner

    def test_row_padding(self):
        dcop = _tiny_dcop()
        graph, meta = compile_dcop(dcop, pad_to=8)
        b = graph.buckets[0]
        assert b.costs.shape[0] == 8
        assert np.all(b.var_ids[2:] == graph.n_vars)  # sentinel
        assert np.all(b.costs[2:] == 0)
        assert meta.bucket_sizes == (2,)

    def test_max_mode_negates(self):
        d = Domain("d", "", [0, 1])
        x = Variable("x", d)
        c = constraint_from_str("c", "x * 5", [x])
        dcop = DCOP("t", objective="max")
        dcop.add_constraint(c)
        graph, meta = compile_dcop(dcop)
        assert graph.buckets[0].costs[0, 1] == -5
        assert meta.mode == "max"

    def test_zero_ary_folded(self):
        d = Domain("d", "", [0, 1])
        x = Variable("x", d)
        from pydcop_tpu.dcop.relations import ZeroAryRelation

        c = constraint_from_str("c", "x", [x])
        z = ZeroAryRelation("z", 7.0)
        graph, meta = compile_factor_graph([x], [c, z])
        assert meta.constant_cost == 7.0
        assert len(graph.buckets) == 1


def _naive_factor_msg(table, in_msgs, target_pos):
    """Reference semantics: min over other vars' assignments of
    table + sum of their incoming messages (maxsum.py:382)."""
    arity = table.ndim
    dom = table.shape
    out = []
    for d in range(dom[target_pos]):
        best = np.inf
        ranges = [range(dom[q]) if q != target_pos else [d]
                  for q in range(arity)]
        for idx in itertools.product(*ranges):
            val = table[idx]
            for q in range(arity):
                if q != target_pos:
                    val += in_msgs[q][idx[q]]
            best = min(best, val)
        out.append(best)
    return np.array(out)


class TestKernelsVsNaive:
    def test_factor_to_var_matches_naive(self):
        rng = np.random.default_rng(0)
        d = Domain("d", "", [0, 1, 2])
        x, y, z = (Variable(n, d) for n in "xyz")
        c = constraint_from_str("c", "x*9 + y*3 + z", [x, y, z])
        graph, _ = compile_factor_graph([x, y, z], [c])
        msgs = rng.normal(size=(1, 3, 3)).astype(np.float32)
        f2v = ops.factor_to_var(graph, (msgs,))
        table = np.asarray(graph.buckets[0].costs[0])
        for p in range(3):
            expected = _naive_factor_msg(
                table, [msgs[0, q] for q in range(3)], p
            )
            np.testing.assert_allclose(
                np.asarray(f2v[0][0, p]), expected, rtol=1e-5
            )

    def test_var_to_factor_normalization(self):
        """v2f = var_cost + sum(other factors) - mean(sum other factors)
        (reference maxsum.py:623-674)."""
        dcop = _tiny_dcop()
        graph, meta = compile_dcop(dcop)
        rng = np.random.default_rng(1)
        f2v = (rng.normal(size=(2, 2, 2)).astype(np.float32),)
        beliefs, sums = ops.aggregate_beliefs(graph, f2v)
        v2f = ops.var_to_factor(graph, f2v, beliefs, sums)

        # Check message v2 -> c1 (factor 0, position 1 holds v2).
        i_v2 = meta.var_names.index("v2")
        assert graph.buckets[0].var_ids[0, 1] == i_v2
        # v2 receives from c1 (slot [0,1]) and c2 (slot [1,0]).
        assert graph.buckets[0].var_ids[1, 0] == i_v2
        other = np.asarray(f2v[0][1, 0])           # from c2
        var_cost = np.array([0.1, -0.1])           # v2 costs
        expected = var_cost + other - other.mean()
        np.testing.assert_allclose(
            np.asarray(v2f[0][0, 1]), expected, rtol=1e-5
        )

    def test_select_values_tie_breaks_first(self):
        d = Domain("d", "", [0, 1])
        x = Variable("x", d)
        c = constraint_from_str("c", "x * 0", [x])
        graph, _ = compile_factor_graph([x], [c])
        beliefs, _ = ops.aggregate_beliefs(
            graph, (np.zeros((1, 1, 2), np.float32),)
        )
        vals = ops.select_values(graph, beliefs)
        assert int(vals[0]) == 0


class TestEndToEnd:
    def test_tiny_coloring_optimal(self):
        dcop = _tiny_dcop()
        graph, meta = compile_dcop(dcop)
        engine = MaxSumEngine(graph, meta)
        res = engine.run(max_cycles=100)
        assert res.converged
        cost, violations = dcop.solution_cost(res.assignment)
        assert violations == 0
        assert cost == pytest.approx(-0.1)

    def test_max_mode(self):
        d = Domain("d", "", [0, 1, 2])
        x, y = Variable("x", d), Variable("y", d)
        c = constraint_from_str("c", "x + y", [x, y])
        dcop = DCOP("t", objective="max")
        dcop.add_constraint(c)
        graph, meta = compile_dcop(dcop)
        res = MaxSumEngine(graph, meta).run(max_cycles=50)
        assert res.assignment == {"x": 2, "y": 2}

    def test_fixed_cycles_no_convergence_stop(self):
        dcop = _tiny_dcop()
        graph, meta = compile_dcop(dcop)
        engine = MaxSumEngine(graph, meta)
        res = engine.run(max_cycles=7, stop_on_convergence=False)
        assert res.cycles == 7

    def test_sharded_equals_unsharded(self):
        """8-device virtual CPU mesh must give identical results."""
        assert len(jax.devices()) >= 8, "conftest must force 8 devices"
        d = Domain("d", "", list(range(4)))
        rng = np.random.default_rng(7)
        variables = [Variable(f"v{i}", d) for i in range(12)]
        constraints = []
        for k in range(20):
            i, j = rng.choice(12, size=2, replace=False)
            constraints.append(constraint_from_str(
                f"c{k}", f"abs(v{i} - v{j}) * {rng.integers(1, 4)}",
                variables))
        dcop = DCOP("rand")
        for c in constraints:
            dcop.add_constraint(c)

        graph1, meta1 = compile_dcop(dcop)
        res1 = MaxSumEngine(graph1, meta1).run(max_cycles=60)

        mesh = make_mesh(8)
        graph8, meta8 = compile_dcop(dcop, pad_to=8)
        res8 = MaxSumEngine(graph8, meta8, mesh=mesh).run(max_cycles=60)

        assert res1.assignment == res8.assignment
        assert res1.cycles == res8.cycles


class TestTimingConvention:
    """DeviceRunResult timing contract on the whole-solve path
    (engine/runner.py docstring): a first call has cold_start=True
    and its compile_time_s is what XLA spent compiling (from JAX's
    counters), a part of time_s and never a copy of it; warm calls
    have compile_time_s=0.  "First" is first in the process, by
    any engine: the programs are the process's, and every test
    starts with none of them warm (tests/conftest.py)."""

    def _engine(self):
        from pydcop_tpu.dcop.objects import Domain, Variable
        from pydcop_tpu.dcop.relations import constraint_from_str
        from pydcop_tpu.engine.compile import compile_factor_graph
        from pydcop_tpu.engine.runner import MaxSumEngine

        d = Domain("d", "", [0, 1, 2])
        vs = [Variable(f"v{i}", d) for i in range(4)]
        cs = [constraint_from_str(f"c{i}", f"v{i} + v{i+1}",
                                  [vs[i], vs[i + 1]]) for i in range(3)]
        graph, meta = compile_factor_graph(vs, cs)
        return MaxSumEngine(graph, meta)

    def test_cold_then_warm(self):
        engine = self._engine()
        cold = engine.run(max_cycles=5, stop_on_convergence=False)
        assert cold.metrics["cold_start"] is True
        # Tracing, lowering and the run are in time_s alone.
        assert 0 < cold.compile_time_s < cold.time_s
        warm = engine.run(max_cycles=5, stop_on_convergence=False)
        assert warm.metrics["cold_start"] is False
        assert warm.compile_time_s == 0.0
        assert 0 < warm.time_s < cold.time_s

    def test_distinct_keys_are_cold_again(self):
        engine = self._engine()
        engine.run(max_cycles=5, stop_on_convergence=False)
        other = engine.run(max_cycles=7, stop_on_convergence=False)
        assert other.metrics["cold_start"] is True

    def test_trace_has_own_key(self):
        engine = self._engine()
        engine.run(max_cycles=5, stop_on_convergence=False)
        tr = engine.run_trace(max_cycles=5)
        assert tr.metrics["cold_start"] is True
        tr2 = engine.run_trace(max_cycles=5)
        assert tr2.metrics["cold_start"] is False
