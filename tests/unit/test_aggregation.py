"""Aggregation-strategy equivalence (ops/maxsum.aggregate_beliefs).

The scatter path is the parity default; sorted/boundary are the
HBM-regime options (engine/compile.build_aggregation_arrays).  All
three compute the same per-variable sums up to float reassociation, and
full solves must select the same assignment on a well-separated
problem.
"""

import jax
import numpy as np
import pytest
from functools import partial

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.ops import maxsum as ops


def _coloring(n_vars=300, seed=5):
    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", [0, 1, 2])
    dcop = DCOP("agg_gc", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    eq = np.eye(3, dtype=np.float64)
    seen = set()
    for k in range(int(n_vars * 1.5)):
        i, j = rng.choice(n_vars, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], eq, f"c{k}"))
    return dcop


@pytest.mark.parametrize("strategy", ["sorted", "boundary", "ell"])
def test_aggregate_matches_scatter(strategy):
    dcop = _coloring()
    g_sc, _ = compile_dcop(dcop, noise_level=0.01)
    g_st, _ = compile_dcop(dcop, noise_level=0.01,
                           aggregation=strategy)
    state = ops.init_state(g_sc)
    # a few real supersteps so messages are non-trivial
    step = jax.jit(partial(
        ops.superstep, damping=0.5, damp_vars=True, damp_factors=True,
        stability=0.1))
    for _ in range(3):
        state = step(state, g_sc)
    b_sc, s_sc = ops.aggregate_beliefs(g_sc, state.f2v)
    b_st, s_st = ops.aggregate_beliefs(g_st, state.f2v)
    np.testing.assert_allclose(
        np.asarray(s_sc), np.asarray(s_st), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(b_sc), np.asarray(b_st), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", ["sorted", "ell"])
def test_full_solve_same_assignment(strategy):
    from pydcop_tpu.api import solve

    dcop = _coloring(n_vars=150, seed=9)
    # The strategies are edge-major arrays: their scatter twin too.
    base = solve(dcop, "maxsum", max_cycles=60,
                 algo_params={"layout": "edge"})
    alt = solve(dcop, "maxsum", max_cycles=60,
                algo_params={"aggregation": strategy})
    assert alt["cost"] == base["cost"]
    assert alt["assignment"] == base["assignment"]


@pytest.mark.parametrize(
    "algo", ["dsa", "adsa", "mgm", "dba", "gdba", "mgm2", "mixeddsa"])
def test_local_search_ell_bit_parity(algo):
    """With integer constraint costs, the ell sums are exact, so the
    local-search trajectory (and final assignment) must be
    bit-identical to the scatter path for every algorithm exposing
    the param."""
    from pydcop_tpu.api import solve

    dcop = _coloring(n_vars=120, seed=7)
    base = solve(dcop, algo, max_cycles=40, algo_params={"seed": 3})
    alt = solve(dcop, algo, max_cycles=40,
                algo_params={"seed": 3, "aggregation": "ell"})
    assert alt["cost"] == base["cost"]
    assert alt["assignment"] == base["assignment"]


@pytest.mark.parametrize("strategy", ["sorted", "ell"])
def test_non_scatter_aggregation_rejected_on_mesh(strategy):
    """shard_graph drops the agg_* arrays, so a non-scatter strategy
    on a mesh would silently measure scatter — build_engine must
    refuse loudly instead."""
    from pydcop_tpu.api import solve

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device backend")
    dcop = _coloring(n_vars=64, seed=2)
    with pytest.raises(ValueError, match="single-device"):
        solve(dcop, "maxsum", max_cycles=5, n_devices=2,
              algo_params={"aggregation": strategy})


def test_ell_lists_cover_every_real_edge_once():
    """Structural invariant behind the dense-gather path: every real
    edge index appears in exactly one variable's list, every dummy
    slot holds E, and the sentinel row is all-dummy."""
    dcop = _coloring(n_vars=80, seed=4)
    graph, _ = compile_dcop(dcop, aggregation="ell")
    seg = np.concatenate(
        [b.var_ids.reshape(-1) for b in graph.buckets])
    n_edges = seg.size
    ell = np.asarray(graph.agg_ell)
    assert ell.shape[0] == graph.var_costs.shape[0]
    assert (ell[-1] == n_edges).all()          # sentinel row: dummies
    real_entries = ell[ell < n_edges]
    # Each real edge appears exactly once, in its own variable's row.
    assert sorted(real_entries.tolist()) == list(range(n_edges))
    rows, _ = np.nonzero(ell < n_edges)
    np.testing.assert_array_equal(
        seg[real_entries], rows.astype(seg.dtype))


def test_ell_max_degree_matches_k():
    dcop = _coloring(n_vars=80, seed=4)
    graph, _ = compile_dcop(dcop, aggregation="ell")
    seg = np.concatenate(
        [b.var_ids.reshape(-1) for b in graph.buckets])
    counts = np.bincount(seg, minlength=graph.var_costs.shape[0])
    assert graph.agg_ell.shape[1] == counts[:-1].max()


def test_boundary_not_a_solve_option():
    """'boundary' is experiment-only (f32 prefix-sum cancellation at
    scale — ops/maxsum.aggregate_beliefs docstring); the maxsum param
    validator must reject it."""
    from pydcop_tpu.api import solve

    dcop = _coloring(n_vars=20, seed=3)
    with pytest.raises(Exception, match="aggregation"):
        solve(dcop, "maxsum", max_cycles=5,
              algo_params={"aggregation": "boundary"})


def test_sharded_graph_drops_sort_arrays():
    from pydcop_tpu.engine.sharding import make_mesh, shard_graph

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device backend")
    dcop = _coloring(n_vars=64, seed=2)
    mesh = make_mesh(2)
    graph, _ = compile_dcop(dcop, pad_to=2, aggregation="sorted")
    assert graph.agg_perm is not None
    sharded = shard_graph(graph, mesh)
    assert sharded.agg_perm is None  # scatter path on meshes


def test_decimation_composes_with_ell():
    """run_decimated clamps var_costs rows via graph._replace, which
    must preserve the ell lists — the decimated rounds aggregate
    through them."""
    from pydcop_tpu.api import solve

    dcop = _coloring(n_vars=60, seed=5)
    base = solve(dcop, "maxsum", max_cycles=120,
                 algo_params={"decimation": 10})
    alt = solve(dcop, "maxsum", max_cycles=120,
                algo_params={"decimation": 10, "aggregation": "ell"})
    assert alt["cost"] == base["cost"]
    assert alt["assignment"] == base["assignment"]


def test_ell_hub_guard():
    """A power-law hub makes K = max degree explode the [V+1, K]
    lists; the builder must refuse with guidance instead of OOMing
    (exercised via a synthetic bucket so no giant graph is built)."""
    import numpy as np

    from pydcop_tpu.engine.compile import (
        FactorBucket,
        build_aggregation_arrays,
    )

    n_vars = 2_000_000
    # 600k binary factors all touching variable 0 (the hub).
    ids = np.zeros((600_000, 2), np.int32)
    ids[:, 1] = np.arange(600_000) % (n_vars - 1) + 1
    bucket = FactorBucket(np.zeros((600_000, 2, 2), np.float32), ids)
    with pytest.raises(ValueError, match="hub"):
        build_aggregation_arrays((bucket,), n_vars + 1, "ell")


def test_unknown_aggregation_rejected():
    dcop = _coloring(n_vars=10, seed=1)
    with pytest.raises(ValueError):
        compile_dcop(dcop, aggregation="nope")
