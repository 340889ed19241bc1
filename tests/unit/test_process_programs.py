"""The solo MaxSum engine's programs belong to the process.

``MaxSumEngine``'s whole-solve, segment and cost-trace programs are
process-level jitted functions whose solver parameters are static
arguments (engine/runner.py ``_process_program``), and their warmth
is a process-level fact keyed on program + statics + the placed
graph's arrays.  So ``api.solve``, which builds an engine per solve,
traces, lowers and loads a program once per process and shape, not
once per solve.  What must hold:

- a second engine over the same shapes and parameters is warm, and
  anything JAX keys the program on makes a call "first" exactly once;
- the numbers are those of the per-engine ``jax.jit(partial(...))``
  this replaced, bit for bit, and so is the HLO;
- ``reset_process_programs`` is a new process as far as these go
  (tests/conftest.py calls it before every test);
- ``ShardedMaxSumEngine``, whose ops bake in a mesh, keeps its
  programs and its warmth to itself.
"""

from functools import partial

import jax
import numpy as np
import pytest

from pydcop_tpu.algorithms.maxsum import build_engine
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine import aotcache, runner
from pydcop_tpu.engine.compile import compile_factor_graph
from pydcop_tpu.engine.runner import (
    MaxSumEngine,
    ShardedMaxSumEngine,
    reset_process_programs,
)
from pydcop_tpu.observability.metrics import registry
from pydcop_tpu.observability.profiler import profiler
from pydcop_tpu.ops import maxsum as maxsum_ops
from pydcop_tpu.ops import maxsum_lane as lane_ops

CYCLES = 17


def ring(n: int, seed: int, chords: int = 0) -> DCOP:
    """``n`` variables of 3 values on a ring with ``chords`` extra
    edges: ``n + chords`` binary factors with seeded tables."""
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"ring{n}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    pairs = [(k, (k + 1) % n) for k in range(n)]
    pairs += [(k, (k + n // 2) % n) for k in range(chords)]
    for i, (a, b) in enumerate(pairs):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(
            NAryMatrixRelation([vs[a], vs[b]], table, f"c{i}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def solve(seed=1, params=None, cycles=CYCLES, chords=0, **kwargs):
    """One ``api.solve``'s worth: a new engine, one run."""
    return build_engine(
        ring(13, seed, chords), params or {}, **kwargs).run(
            max_cycles=cycles)


def is_first(result) -> bool:
    first = result.metrics["cold_start"]
    assert (result.compile_time_s > 0) == first
    return first


def test_a_second_engine_over_the_same_shapes_is_warm():
    before = aotcache.counters()
    assert is_first(solve(seed=1))
    after_first = aotcache.counters()
    assert after_first["compiles"] + after_first["hits"] \
        > before["compiles"] + before["hits"]
    for seed in (2, 3, 4):
        result = solve(seed=seed)  # another problem, the same shapes
        assert result.metrics["cold_start"] is False
        assert result.compile_time_s == 0.0
    # Nothing was compiled or read from the disk cache again.
    assert aotcache.counters() == after_first
    assert len(runner._process_programs) == 1


VARIANTS = {
    "factor_count": dict(chords=2),
    "max_cycles": dict(cycles=CYCLES + 1),
    "damping": dict(params={"damping": 0.7}),
    "damping_nodes": dict(params={"damping_nodes": "vars"}),
    "stability": dict(params={"stability": 0.0}),
    "layout_lane": dict(params={"layout": "lane"}),
    "aggregation_sorted": dict(params={"aggregation": "sorted"}),
    "replicated_mesh": dict(n_devices=2),
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_what_jax_keys_the_program_on_is_first_once(variant):
    """Each is another compiled program, so under the warm base
    program its first call is "first" (it does compile), and its
    second, by a new engine, is warm."""
    assert is_first(solve())
    assert is_first(solve(seed=2, **VARIANTS[variant]))
    assert not is_first(solve(seed=3, **VARIANTS[variant]))
    assert not is_first(solve(seed=4))


def _segments(donate: bool, seed: int):
    dcop = ring(13, seed)
    graph, meta = compile_factor_graph(
        list(dcop.variables.values()), list(dcop.constraints.values()))
    return MaxSumEngine(graph, meta, donate=donate).run_checkpointed(
        max_cycles=12, segment_cycles=6, stop_on_convergence=False)


def test_donated_and_undonated_segments_are_each_first_once():
    results = {}
    for donate in (True, False):
        first = _segments(donate, seed=1)
        assert first.metrics["cold_start"] is True
        again = _segments(donate, seed=1)
        assert again.metrics["cold_start"] is False
        assert again.compile_time_s == 0.0
        results[donate] = again
    # Two executables of one function; donation moves no number.
    assert len(runner._process_programs) == 2
    assert results[True].assignment == results[False].assignment
    assert results[True].cycles == results[False].cycles == 12


def test_the_cost_trace_takes_the_base_costs_as_an_argument():
    """Two problems of one shape with different variable costs run
    one program, each with its own costs in the curve."""
    from pydcop_tpu.dcop.objects import VariableWithCostDict

    def engine(unary):
        dom = Domain("c", "", [0, 1, 2])
        vs = [VariableWithCostDict(f"v{i}", dom,
                                   {0: unary * i, 1: 0.5, 2: 1.0})
              for i in range(5)]
        cs = [NAryMatrixRelation(
            [vs[i], vs[i + 1]], np.eye(3) * 4.0, f"c{i}")
            for i in range(4)]
        graph, meta = compile_factor_graph(vs, cs)
        return MaxSumEngine(graph, meta), vs, cs

    traces = []
    for unary, first in ((0.25, True), (2.0, False)):
        eng, vs, cs = engine(unary)
        res = eng.run_trace(max_cycles=9, stop_on_convergence=False)
        assert res.metrics["cold_start"] is first
        cost = sum(c(**{v.name: res.assignment[v.name]
                        for v in c.dimensions}) for c in cs)
        cost += sum(v.cost_for_val(res.assignment[v.name]) for v in vs)
        assert res.metrics["cost_trace"][-1] == pytest.approx(cost)
        traces.append(res.metrics["cost_trace"])
    assert not np.array_equal(traces[0], traces[1])


def test_reset_makes_the_next_call_first_again():
    assert is_first(solve())
    assert not is_first(solve(seed=2))
    reset_process_programs()
    assert not runner._process_programs and not runner._process_warm
    assert is_first(solve(seed=3))
    assert not is_first(solve(seed=4))


def test_the_calls_are_counted_by_warmth_per_process():
    was = registry.active
    registry.active = True
    try:
        def calls(warmth):
            counter = registry.get("pydcop_jit_calls_total")
            return sum(v for k, v in counter.samples()
                       if ("warmth", warmth) in k) if counter else 0

        cold, warm = calls("cold"), calls("warm")
        for seed in (1, 2, 3):
            solve(seed=seed)
        assert calls("cold") - cold == 1
        assert calls("warm") - warm == 2
    finally:
        registry.active = was


def test_a_profiler_turned_on_later_still_gets_the_programs_cost():
    solve()
    assert not profiler.enabled
    profiler.enabled = True
    try:
        result = solve(seed=2)
        assert result.metrics["cold_start"] is False
        (entry,) = result.metrics["xla_cost"].values()
        assert entry["available"] is False or entry["flops"] > 0
    finally:
        profiler.enabled = False
        profiler.clear()


# ------------------------------------------------------------------ #
# the numbers, and the HLO, are the per-engine jit's


def _problems():
    """The host-compile problems of test_chip_compile.py that hold
    variables (its oracle's inputs), built by its own functions."""
    from tests.unit.test_chip_compile import PROBLEMS

    return {name: make for name, make in PROBLEMS.items()
            if name != "no_variables"}


PROBLEM_NAMES = (
    "colouring_1000", "serve_grid_100", "secp_arity_1_to_4",
    "mixed_domains", "mixed_domains_max", "costed_variables",
    "costed_variables_max", "shared_signatures")


def _parents_solve(engine, ops, max_cycles):
    """``MaxSumEngine._fn`` at the parent commit: a fresh
    ``jax.jit`` of a fresh ``partial`` per engine."""
    fn = partial(
        ops.run_maxsum, max_cycles=max_cycles, damping=engine.damping,
        damp_vars=engine.damp_vars, damp_factors=engine.damp_factors,
        stability=engine.stability, stop_on_convergence=True,
        prune=engine.prune)
    fn.__name__ = "maxsum_solve"
    return jax.jit(fn)


@pytest.mark.parametrize("layout", ["edge", "lane"])
@pytest.mark.parametrize("problem", PROBLEM_NAMES)
def test_bit_equal_to_a_fresh_jit_of_the_parents_partial(
        problem, layout):
    assert set(_problems()) == set(PROBLEM_NAMES)
    variables, constraints, mode = _problems()[problem]()
    graph, meta = compile_factor_graph(
        variables, constraints, mode=mode, noise_level=0.01)
    engine = MaxSumEngine(graph, meta, layout=layout)
    ops = lane_ops if layout == "lane" else maxsum_ops
    ours = engine._fn(25, True)
    theirs = _parents_solve(engine, ops, 25)
    assert ours.lower(engine.graph).as_text() \
        == theirs.lower(engine.graph).as_text()
    state, values = ours(engine.graph)
    state0, values0 = theirs(engine.graph)
    np.testing.assert_array_equal(values, values0)
    assert int(state.cycle) == int(state0.cycle)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state, state0)
    result = engine.run(max_cycles=25)
    assert result.assignment == meta.assignment_from_indices(
        np.asarray(values0))
    assert result.cycles == int(state0.cycle)


# ------------------------------------------------------------------ #
# the partitioned engine keeps its programs to itself


def test_the_sharded_engine_keeps_per_engine_programs():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (forced host) devices")
    dcop = ring(16, 1, chords=4)
    engine = build_engine(dcop, {}, shards=4)
    assert isinstance(engine, ShardedMaxSumEngine)
    assert engine._warm is not runner._process_warm
    assert engine.run(max_cycles=CYCLES).metrics["cold_start"] is True
    assert engine.run(max_cycles=CYCLES).metrics["cold_start"] is False
    # Its programs bake in its mesh: another engine shares nothing.
    other = build_engine(ring(16, 2, chords=4), {}, shards=4)
    assert other.run(max_cycles=CYCLES).metrics["cold_start"] is True
    assert not runner._process_programs and not runner._process_warm
    assert engine._jitted and engine._warm

    state = engine.init_state()
    key, fn = engine._segment(4, False)
    (state, _), _, _ = engine._call(key, fn, engine.graph, state)
    engine.repartition_after_loss(1, state)
    # The old programs ran on the dead mesh: all dropped.
    assert engine.mesh.size == 3
    assert not engine._jitted and not engine._warm
    after = engine.run(max_cycles=CYCLES)
    assert after.metrics["cold_start"] is True
    unsharded = build_engine(dcop, {}).run(max_cycles=CYCLES)
    assert after.assignment == unsharded.assignment
