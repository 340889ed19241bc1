"""Semantics freezes for the flagship device kernels (maxsum
superstep, dsa, mgm, dpop sweep).

Each live kernel must produce the exact seeded trajectory of a FROZEN
copy of itself (golden_*.py, the reference implementations these tests
compare against), so an "optimization" cannot silently change
semantics.  How fast a kernel runs is read on the chip
(``chipbench/``, ``kernel.superstep_us``), not raced here on a CPU.
"""

from functools import partial

import jax
import numpy as np
import pytest

from tests.unit import golden_dpop_r5 as golden_dpop
from tests.unit import golden_localsearch_r5 as golden_ls
from tests.unit import golden_maxsum_kernel as golden

N_VARS = 2_000
N_COLORS = 3
CYCLES = 100


@pytest.fixture(scope="module")
def problem():
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.engine.compile import compile_dcop

    rng = np.random.default_rng(11)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP("perf_gc", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(N_VARS)]
    for v in variables:
        dcop.add_variable(v)
    eq = np.eye(N_COLORS, dtype=np.float64)
    seen = set()
    for k in range(int(N_VARS * 1.5)):
        i, j = rng.choice(N_VARS, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], eq, f"c{k}"))
    graph, meta = compile_dcop(dcop, noise_level=0.01)
    return jax.device_put(graph)


def test_superstep_semantics_frozen(problem):
    from pydcop_tpu.ops import maxsum as ops

    live = jax.jit(partial(
        ops.run_maxsum, max_cycles=CYCLES, stop_on_convergence=False))
    gold = jax.jit(partial(golden.run_maxsum, max_cycles=CYCLES))
    s_live, v_live = live(problem)
    s_gold, v_gold = gold(problem)
    assert (np.asarray(v_live) == np.asarray(v_gold)).all()
    assert bool(s_live.stable) == bool(s_gold.stable)
    np.testing.assert_array_equal(
        np.asarray(s_live.f2v[0]), np.asarray(s_gold.f2v[0]))


# ---- dsa / mgm kernel gates (VERDICT r4 next #5) ---------------------- #


@pytest.fixture(scope="module")
def hypergraph_problem():
    """Same random coloring, compiled WITHOUT noise: the local-search
    kernels' trajectories must be exactly reproducible from the seed."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.engine.compile import compile_dcop

    rng = np.random.default_rng(17)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP("perf_ls", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(N_VARS)]
    for v in variables:
        dcop.add_variable(v)
    eq = np.eye(N_COLORS, dtype=np.float64)
    seen = set()
    for k in range(int(N_VARS * 1.5)):
        i, j = rng.choice(N_VARS, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], eq, f"c{k}"))
    graph, meta = compile_dcop(dcop)
    return jax.device_put(graph)


def test_dsa_kernel_semantics_frozen(hypergraph_problem):
    from pydcop_tpu.ops import dsa as ops

    for variant in ("A", "B", "C"):
        v_live, c_live, _ = jax.jit(partial(
            ops.run_dsa, max_cycles=CYCLES, variant=variant, seed=3
        ))(hypergraph_problem)
        v_gold, c_gold, _ = jax.jit(partial(
            golden_ls.run_dsa, max_cycles=CYCLES, variant=variant,
            seed=3,
        ))(hypergraph_problem)
        np.testing.assert_array_equal(
            np.asarray(v_live), np.asarray(v_gold),
            err_msg=f"dsa variant {variant} trajectory changed",
        )
        assert float(c_live) == float(c_gold)


def test_mgm_kernel_semantics_frozen(hypergraph_problem):
    from pydcop_tpu.ops import mgm as ops

    n = int(hypergraph_problem.var_costs.shape[0])
    ranks = jax.numpy.arange(n, dtype=jax.numpy.float32)
    for break_mode in ("lexic", "random"):
        v_live, c_live, _ = jax.jit(partial(
            ops.run_mgm, max_cycles=CYCLES, lexic_ranks=ranks,
            break_mode=break_mode, seed=3,
        ))(hypergraph_problem)
        v_gold, c_gold, _ = jax.jit(partial(
            golden_ls.run_mgm, max_cycles=CYCLES, lexic_ranks=ranks,
            break_mode=break_mode, seed=3,
        ))(hypergraph_problem)
        np.testing.assert_array_equal(
            np.asarray(v_live), np.asarray(v_gold),
            err_msg=f"mgm break_mode {break_mode} trajectory changed",
        )
        assert float(c_live) == float(c_gold)


# ---- dpop sweep gate (VERDICT r4 next #5) ----------------------------- #


@pytest.fixture(scope="module")
def dpop_tree():
    """A 1500-variable random tree-ish coloring whose pseudo-tree the
    level-batched sweep must solve fast (host-driven, so the race times
    the full compile_tree + UTIL + VALUE pipeline end to end)."""
    from pydcop_tpu.computations_graph.pseudotree import (
        build_computation_graph,
    )
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(23)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP("perf_dpop", objective="min")
    n = 1_500
    variables = [Variable(f"v{i}", dom) for i in range(n)]
    for v in variables:
        dcop.add_variable(v)
    for i in range(1, n):
        p = int(rng.integers(0, i))
        dcop.add_constraint(NAryMatrixRelation(
            [variables[p], variables[i]],
            rng.random((N_COLORS, N_COLORS)).round(3), f"c{i}"))
    return build_computation_graph(dcop)


def test_dpop_sweep_semantics_frozen(dpop_tree):
    from pydcop_tpu.ops import dpop as ops

    live, _stats = ops.solve_sweep(dpop_tree)
    gold = golden_dpop.solve_sweep(dpop_tree)
    assert live == gold
