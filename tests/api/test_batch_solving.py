"""Batched multi-instance device solving tests: one vmapped XLA
program must produce bit-identical results to solving each instance
separately, and reject shape-mismatched batches."""

import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine.batch import solve_maxsum_batch
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.engine.runner import MaxSumEngine


def _instance(n: int, seed: int, objective: str = "min") -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"b{n}_{seed}_{objective}", objective=objective)
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    # Same topology across seeds (ring + fixed chords), different
    # random cost tables: identical compiled shapes.
    edges = [(i, (i + 1) % n) for i in range(n)]
    edges += [(i, (i + n // 2) % n) for i in range(0, n, 3)]
    for k, (i, j) in enumerate(edges):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(
            NAryMatrixRelation([vs[i], vs[j]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def test_batch_matches_individual_solves():
    dcops = [_instance(24, seed) for seed in range(6)]
    batch = solve_maxsum_batch(dcops, max_cycles=80)
    for dcop, res in zip(dcops, batch):
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        solo = MaxSumEngine(graph, meta).run(
            max_cycles=80, stop_on_convergence=False)
        assert res["assignment"] == solo.assignment
        assert res["cycles"] == 80


def test_batch_rejects_shape_mismatch():
    a = _instance(24, 0)
    b = _instance(30, 1)
    with pytest.raises(ValueError, match="identical compiled shapes"):
        solve_maxsum_batch([a, b])


def test_batch_amortizes_launch_overhead():
    """The whole batch runs in one program: 8 instances are one
    dispatch, where solving them one by one is 8 (each of them warm:
    an engine per instance re-jits nothing, the solo programs are the
    process's as the batched one is).  Counted, not timed: what a
    launch costs is the chip's to say (chipbench)."""
    from pydcop_tpu.observability.trace import tracer

    def dispatches(solve):
        tracer.enable()
        try:
            solve()
            return [e for e in tracer.events()
                    if e["name"] in ("engine_call", "jit_compile")]
        finally:
            tracer.disable()
            tracer.clear()

    dcops = [_instance(40, seed) for seed in range(8)]
    solve_maxsum_batch(dcops, max_cycles=60)  # warm the jit cache
    batched = dispatches(
        lambda: solve_maxsum_batch(dcops, max_cycles=60))

    def one_by_one():
        for dcop in dcops:
            g, m = compile_dcop(dcop, noise_level=0.01)
            MaxSumEngine(g, m).run(
                max_cycles=60, stop_on_convergence=False)

    one_by_one()  # warm
    sequential = dispatches(one_by_one)
    assert len(batched) == 1 and len(sequential) == 8
    assert not any("first" in e["args"] for e in batched + sequential)

def test_batch_handles_max_objective():
    """objective=max problems negate at compile time; the batched path
    must decode the maximizing assignment — checked against an
    independent host-side evaluation, not the engine's own cost."""
    dcops = [_instance(12, seed, objective="max") for seed in range(3)]
    batch = solve_maxsum_batch(dcops, max_cycles=80)
    rng = np.random.default_rng(99)
    for dcop, res in zip(dcops, batch):
        # Same assignment as the solo engine (sign handling agrees).
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        solo = MaxSumEngine(graph, meta).run(
            max_cycles=80, stop_on_convergence=False)
        assert res["assignment"] == solo.assignment
        # Independent check: the reported cost is the raw table sum of
        # the assignment (not accidentally negated)...
        raw = sum(
            float(c(*(res["assignment"][v.name]
                      for v in c.dimensions)))
            for c in dcop.constraints.values()
        )
        assert res["cost"] == raw
        # ...and the solver actually MAXIMIZED: it beats random
        # assignments comfortably.
        rand = {
            v: int(rng.integers(0, 3)) for v in dcop.variables
        }
        rand_cost, _ = dcop.solution_cost(rand)
        assert res["cost"] > rand_cost
