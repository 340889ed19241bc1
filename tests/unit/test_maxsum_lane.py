"""Lane-major MaxSum (ops/maxsum_lane.py) parity vs the edge-major
kernels — the CPU bit-parity contract behind the ``layout="lane"``
algo param.

Parity tiers (module docstring of maxsum_lane explains why they
differ):

- factor update and variable update are elementwise/tiny-D ops in
  identical order across layouts → BIT-equal given equal inputs;
- variable aggregation sums each variable's incoming edges in a
  different order (edge-major flattens (factor, position), lane-major
  (position, factor)) → bit-equal whenever each variable has at most
  one incoming edge, float-tolerance otherwise;
- whole trajectories → identical selected assignments and cycle
  counts on well-separated instances (seeded), messages to float
  tolerance.
"""

import numpy as np
import pytest

import jax

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation, constraint_from_str
from pydcop_tpu.engine.compile import compile_dcop, compile_factor_graph
from pydcop_tpu.engine.runner import MaxSumEngine
from pydcop_tpu.ops import maxsum as edge_ops
from pydcop_tpu.ops import maxsum_lane as lane_ops


def _random_dcop(n_vars=12, n_edges=18, d=3, seed=0, ternary=False):
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP("rand", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    seen = set()
    k = 0
    while k < n_edges:
        i, j = rng.choice(n_vars, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        table = rng.integers(0, 10, size=(d, d)).astype(np.float64)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], table, f"c{k}"))
        k += 1
    if ternary:
        i, j, l = rng.choice(n_vars, size=3, replace=False)
        table = rng.integers(0, 10, size=(d, d, d)).astype(np.float64)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j], variables[l]], table, "t0"))
    return dcop


def _lane_to_edge_msgs(msgs):
    """[D, a, F] -> [F, a, D] for comparisons."""
    return tuple(np.transpose(np.asarray(m), (2, 1, 0)) for m in msgs)


def _edge_to_lane_msgs(msgs):
    return tuple(np.transpose(np.asarray(m), (2, 1, 0)) for m in msgs)


class TestRelayout:
    def test_to_lane_graph_shapes(self):
        graph, _ = compile_dcop(_random_dcop(ternary=True))
        lane = lane_ops.to_lane_graph(graph)
        assert lane.var_costs.shape == graph.var_costs.shape[::-1]
        assert lane.n_vars == graph.n_vars
        assert lane.dmax == graph.dmax
        for eb, lb in zip(graph.buckets, lane.buckets):
            assert lb.arity == eb.arity
            assert lb.n_factors == eb.n_factors
            assert lb.var_ids.shape == eb.var_ids.shape[::-1]
            np.testing.assert_array_equal(
                np.asarray(lb.var_ids), np.asarray(eb.var_ids).T)
            np.testing.assert_array_equal(
                np.moveaxis(np.asarray(lb.costs), -1, 0),
                np.asarray(eb.costs))

    def test_lane_requires_scatter(self):
        graph, meta = compile_dcop(_random_dcop(), aggregation="sorted")
        with pytest.raises(ValueError, match="scatter"):
            MaxSumEngine(graph, meta, layout="lane")

    def test_lane_is_single_device(self):
        graph, meta = compile_dcop(_random_dcop(), pad_to=8)
        with pytest.raises(ValueError, match="single-device"):
            MaxSumEngine(graph, meta, layout="lane", n_devices=8)

    def test_bad_layout_rejected(self):
        graph, meta = compile_dcop(_random_dcop())
        with pytest.raises(ValueError, match="layout"):
            MaxSumEngine(graph, meta, layout="columns")


class TestOpParity:
    """Single-op comparisons on equal inputs."""

    def _graphs(self, **kw):
        graph, _ = compile_dcop(_random_dcop(**kw), noise_level=0.01)
        return graph, lane_ops.to_lane_graph(graph)

    def _random_msgs(self, graph, seed=1):
        rng = np.random.default_rng(seed)
        d = graph.var_costs.shape[1]
        return tuple(
            rng.random(b.var_ids.shape + (d,)).astype(np.float32)
            for b in graph.buckets
        )

    def test_factor_update_bit_equal(self):
        graph, lane = self._graphs(ternary=True)
        v2f = self._random_msgs(graph)
        edge_out = edge_ops.factor_to_var(graph, v2f)
        lane_out = lane_ops.factor_to_var(lane, _edge_to_lane_msgs(v2f))
        for e, l in zip(edge_out, _lane_to_edge_msgs(lane_out)):
            np.testing.assert_array_equal(np.asarray(e), l)

    def test_var_update_bit_equal(self):
        graph, lane = self._graphs(ternary=True)
        f2v = self._random_msgs(graph, seed=2)
        beliefs, sums = edge_ops.aggregate_beliefs(graph, f2v)
        edge_out = edge_ops.var_to_factor(graph, f2v, beliefs, sums)
        lane_out = lane_ops.var_to_factor(
            lane, _edge_to_lane_msgs(f2v),
            np.asarray(beliefs).T, np.asarray(sums).T)
        for e, l in zip(edge_out, _lane_to_edge_msgs(lane_out)):
            np.testing.assert_array_equal(np.asarray(e), l)

    def test_aggregation_bit_equal_single_edge_vars(self):
        """A matching: every variable has exactly one incoming edge, so
        the per-variable sum has one term and reassociation cannot
        differ — the layouts must agree bitwise."""
        d = Domain("d", "", [0, 1, 2])
        variables = [Variable(f"v{i}", d) for i in range(8)]
        cons = [
            constraint_from_str(
                f"c{i}", f"v{2*i} + 2 * v{2*i+1}",
                [variables[2 * i], variables[2 * i + 1]])
            for i in range(4)
        ]
        graph, _ = compile_factor_graph(variables, cons)
        lane = lane_ops.to_lane_graph(graph)
        f2v = self._random_msgs(graph, seed=3)
        eb, es = edge_ops.aggregate_beliefs(graph, f2v)
        lb, ls = lane_ops.aggregate_beliefs(
            lane, _edge_to_lane_msgs(f2v))
        np.testing.assert_array_equal(np.asarray(eb), np.asarray(lb).T)
        np.testing.assert_array_equal(np.asarray(es), np.asarray(ls).T)

    def test_aggregation_close_general(self):
        graph, lane = self._graphs(ternary=True)
        f2v = self._random_msgs(graph, seed=4)
        eb, _ = edge_ops.aggregate_beliefs(graph, f2v)
        lb, _ = lane_ops.aggregate_beliefs(
            lane, _edge_to_lane_msgs(f2v))
        np.testing.assert_allclose(
            np.asarray(eb), np.asarray(lb).T, rtol=1e-6, atol=1e-5)

    def test_select_values_match(self):
        graph, lane = self._graphs()
        rng = np.random.default_rng(5)
        beliefs = rng.random(graph.var_costs.shape).astype(np.float32)
        e = edge_ops.select_values(graph, beliefs)
        l = lane_ops.select_values(lane, beliefs.T)
        np.testing.assert_array_equal(np.asarray(e), np.asarray(l))

    def test_assignment_cost_bit_equal(self):
        graph, lane = self._graphs(ternary=True)
        rng = np.random.default_rng(6)
        values = rng.integers(0, 3, size=graph.n_vars).astype(np.int32)
        e = edge_ops.assignment_constraint_cost(graph, values)
        l = lane_ops.assignment_constraint_cost(lane, values)
        assert float(e) == float(l)


class TestRunParity:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("stop", [True, False])
    def test_whole_run(self, seed, stop):
        dcop = _random_dcop(seed=seed, ternary=(seed == 2))
        graph, _ = compile_dcop(dcop, noise_level=0.01)
        lane = lane_ops.to_lane_graph(graph)
        es, ev = jax.jit(
            lambda g: edge_ops.run_maxsum(
                g, 60, stop_on_convergence=stop))(graph)
        ls, lv = jax.jit(
            lambda g: lane_ops.run_maxsum(
                g, 60, stop_on_convergence=stop))(lane)
        assert int(es.cycle) == int(ls.cycle)
        assert bool(es.stable) == bool(ls.stable)
        np.testing.assert_array_equal(
            np.asarray(ev), np.asarray(lv))
        for e, l in zip(es.f2v, _lane_to_edge_msgs(ls.f2v)):
            np.testing.assert_allclose(
                np.asarray(e), l, rtol=1e-5, atol=1e-4)
        for e, l in zip(es.v2f, _lane_to_edge_msgs(ls.v2f)):
            np.testing.assert_allclose(
                np.asarray(e), l, rtol=1e-5, atol=1e-4)

    def test_trace_parity(self):
        dcop = _random_dcop(seed=7)
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        lane = lane_ops.to_lane_graph(graph)
        base = meta.var_base_costs
        _, ev, ec = jax.jit(lambda g: edge_ops.run_maxsum_trace(
            g, 25, var_base_costs=base))(graph)
        _, lv, lc = jax.jit(lambda g: lane_ops.run_maxsum_trace(
            g, 25, var_base_costs=base))(lane)
        np.testing.assert_array_equal(np.asarray(ev), np.asarray(lv))
        np.testing.assert_allclose(
            np.asarray(ec), np.asarray(lc), rtol=1e-6, atol=1e-4)


class TestEngineLayout:
    def test_engine_lane_matches_edge(self):
        dcop = _random_dcop(seed=9)
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        edge_res = MaxSumEngine(graph, meta).run(max_cycles=50)
        lane_res = MaxSumEngine(graph, meta, layout="lane").run(
            max_cycles=50)
        assert lane_res.assignment == edge_res.assignment
        assert lane_res.cycles == edge_res.cycles
        assert lane_res.converged == edge_res.converged

    def test_engine_lane_trace(self):
        dcop = _random_dcop(seed=10)
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        edge_res = MaxSumEngine(graph, meta).run_trace(max_cycles=20)
        lane_res = MaxSumEngine(graph, meta, layout="lane").run_trace(
            max_cycles=20)
        np.testing.assert_allclose(
            lane_res.metrics["cost_trace"],
            edge_res.metrics["cost_trace"], rtol=1e-6, atol=1e-4)

    def test_engine_lane_rejects_decimation(self):
        graph, meta = compile_dcop(_random_dcop())
        eng = MaxSumEngine(graph, meta, layout="lane")
        with pytest.raises(ValueError, match="edge"):
            eng.run_decimated(max_cycles=10)

    def test_solve_with_layout_param(self):
        from pydcop_tpu.api import solve

        dcop = _random_dcop(seed=11)
        edge = solve(dcop, "maxsum", backend="device", max_cycles=40,
                     algo_params={"layout": "edge"})
        lane = solve(dcop, "maxsum", backend="device", max_cycles=40,
                     algo_params={"layout": "lane"})
        assert lane.assignment == edge.assignment


class TestLargerInstanceLayout:
    """300 variables, 450 factors: what the layouts' A/B ran at its
    smallest size."""

    def test_lane_agrees_with_edge(self):
        graph, _ = compile_dcop(
            _random_dcop(n_vars=300, n_edges=450, seed=7),
            noise_level=0.01)
        _, ev = jax.jit(lambda g: edge_ops.run_maxsum(
            g, 10, stop_on_convergence=False))(graph)
        _, lv = jax.jit(lambda g: lane_ops.run_maxsum(
            g, 10, stop_on_convergence=False))(
                lane_ops.to_lane_graph(graph))
        agree = np.mean(np.asarray(ev) == np.asarray(lv))
        assert agree > 0.99

    @pytest.mark.parametrize("aggregation", ["sorted", "ell"])
    def test_lane_rejects_a_sort_based_aggregation(self, aggregation):
        graph, meta = compile_dcop(
            _random_dcop(n_vars=300, n_edges=450, seed=7),
            aggregation=aggregation)
        with pytest.raises(ValueError, match="scatter"):
            MaxSumEngine(graph, meta, layout="lane")
