"""Seeded device-vs-thread quality parity for every local-search
algorithm (VERDICT weak #8) and the mgm2 statistical equivalence check
(VERDICT weak #5).

Local search is stochastic and the two runtimes draw their randomness
differently (jax PRNG on device, python random in agent mode), so the
assertions are quality-level, not bit-level:

- on an easy instance with a known optimum, both backends must find a
  feasible (violation-free / low-cost) solution;
- across a batch of seeded random instances, the device kernel's mean
  final cost must be within a band of the thread runtime's mean
  (statistical solution-quality equivalence — the device kernels may
  diverge from the reference protocol in documented scheduling details
  but must not be systematically worse).
"""

import numpy as np
import pytest

from pydcop_tpu.api import solve
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.dcop.yamldcop import load_dcop_from_file

from fixtures_paths import local

FIXTURE = local("coloring_chain.yaml")
LOCAL_SEARCH = ["dsa", "mgm", "mgm2", "dba", "gdba", "mixeddsa"]


# Both runtimes must color the 4-chain properly; costs then span
# [-0.6, 0.6] depending on which preference-tie the run lands on (the
# device kernels fold unary preferences in, agent mode is unary-blind
# like the reference, so only feasibility is runtime-invariant).
def _acceptable(res) -> bool:
    a = res["assignment"]
    proper = all(
        a[left] != a[right]
        for left, right in [("w1", "w2"), ("w2", "w3"), ("w3", "w4")]
    )
    return proper and -0.6 - 1e-6 <= res["cost"] <= 0.6 + 1e-6


def _random_coloring(n_vars: int, n_colors: int, seed: int,
                     n_agents: int = 4, density: float = 1.8) -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", list(range(n_colors)))
    dcop = DCOP(f"gc{n_vars}_{seed}", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    eq = np.eye(n_colors, dtype=np.float64)
    seen, k = set(), 0
    while k < int(n_vars * density):
        i, j = rng.choice(n_vars, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], eq, f"c{k}"))
        k += 1
    dcop.add_agents(
        [AgentDef(f"a{i}", capacity=10_000) for i in range(n_agents)])
    return dcop


def _pack_distribution(dcop, algo):
    """Round-robin Distribution over the dcop's agents (capacity-free
    packing for parity runs)."""
    from pydcop_tpu.algorithms import load_algorithm_module
    from pydcop_tpu.computations_graph import load_graph_module
    from pydcop_tpu.distribution.objects import Distribution

    module = load_algorithm_module(algo)
    cg = load_graph_module(
        module.GRAPH_TYPE).build_computation_graph(dcop)
    agents = sorted(dcop.agents)
    mapping = {a: [] for a in agents}
    for i, node in enumerate(cg.nodes):
        mapping[agents[i % len(agents)]].append(node.name)
    return Distribution(mapping)


@pytest.mark.parametrize("algo", ["dsa", "mgm", "mgm2", "mixeddsa"])
def test_device_and_thread_both_feasible_on_fixture(algo):
    d1 = load_dcop_from_file(FIXTURE)
    r_dev = solve(d1, algo, backend="device", max_cycles=100)
    assert _acceptable(r_dev), f"device {algo}: {r_dev['cost']}"
    d2 = load_dcop_from_file(FIXTURE)
    r_thr = solve(d2, algo, backend="thread", timeout=4)
    assert _acceptable(r_thr), f"thread {algo}: {r_thr['cost']}"


def _hard_csp(n_vars=8, seed=0):
    """Ring coloring with hard (10000) difference constraints — the
    problem class dba/gdba target (violation count, reference dba.py
    'CSP-flavored')."""
    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", [0, 1, 2])
    dcop = DCOP(f"csp{n_vars}", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    hard = 10000.0 * np.eye(3)
    for i in range(n_vars):
        j = (i + 1) % n_vars
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], hard, f"c{i}"))
    dcop.add_agents(
        [AgentDef(f"a{i}", capacity=10_000) for i in range(4)])
    return dcop


@pytest.mark.parametrize("algo", ["dba", "gdba"])
def test_breakout_solves_csp_on_both_backends(algo):
    d1 = _hard_csp()
    r_dev = solve(d1, algo, backend="device", max_cycles=300)
    assert r_dev["cost"] == 0, f"device {algo}: {r_dev['cost']}"
    d2 = _hard_csp()
    r_thr = solve(
        d2, algo, backend="thread", timeout=6,
        distribution=_pack_distribution(d2, algo),
    )
    assert r_thr["cost"] == 0, f"thread {algo}: {r_thr['cost']}"


class TestMgm2StatisticalEquivalence:
    """Device mgm2 diverges from the reference protocol in partner
    selection and shared-gain accounting (documented, ops/mgm2.py);
    this pins the consequence: solution quality must be statistically
    equivalent to the agent-mode protocol."""

    SEEDS = [0, 1, 2, 3]
    N_VARS, N_COLORS = 24, 3

    def _run(self, backend, seed):
        dcop = _random_coloring(self.N_VARS, self.N_COLORS, seed)
        if backend == "thread":
            res = solve(
                dcop, "mgm2", backend="thread", timeout=6,
                distribution=_pack_distribution(dcop, "mgm2"),
                algo_params={"stop_cycle": 60},
            )
        else:
            res = solve(dcop, "mgm2", backend="device", max_cycles=60)
        return float(res["cost"])

    def test_mean_quality_within_band(self):
        dev = [self._run("device", s) for s in self.SEEDS]
        thr = [self._run("thread", s) for s in self.SEEDS]
        mean_dev, mean_thr = np.mean(dev), np.mean(thr)
        n_constraints = int(self.N_VARS * 1.8)
        # Equivalence band: 10% of the constraint count (each conflict
        # costs 1).  Catches any systematic quality regression while
        # tolerating per-seed local-optimum noise.
        assert abs(mean_dev - mean_thr) <= 0.10 * n_constraints, (
            f"device {dev} vs thread {thr}"
        )


@pytest.mark.parametrize("algo", ["dsa", "mgm"])
def test_seeded_random_instances_quality(algo):
    """Device local search on seeded 30-var instances ends close to the
    thread runtime's quality (mean gap <= 10% of constraints)."""
    dev, thr = [], []
    for seed in (0, 1):
        dcop = _random_coloring(30, 3, seed)
        r_dev = solve(dcop, algo, backend="device", max_cycles=80)
        dev.append(float(r_dev["cost"]))
        dcop2 = _random_coloring(30, 3, seed)
        r_thr = solve(
            dcop2, algo, backend="thread", timeout=5,
            distribution=_pack_distribution(dcop2, algo),
            algo_params={"stop_cycle": 80},
        )
        thr.append(float(r_thr["cost"]))
    assert abs(np.mean(dev) - np.mean(thr)) <= 0.10 * 30 * 1.8, (
        f"device {dev} vs thread {thr}"
    )

def test_maxsum_device_equals_thread_once_frozen():
    """Where the BSP trajectory freezes (send-suppression quiets
    every edge: a sparse colouring, 1.5 edges a variable) the device
    engine and the threaded agent runtime end
    on the IDENTICAL assignment, hence the same cost.  The thread
    runtime stops on its timeout, so it is given longer until it has
    run at least the cycles the device needed to converge: the
    comparison is made once both are frozen, whatever the host's
    speed."""
    for timeout in (4, 8, 16, 32):
        dcop = _random_coloring(60, 3, seed=3, n_agents=8,
                                density=1.5)
        r_thr = solve(
            dcop, "maxsum", backend="thread", timeout=timeout,
            distribution=_pack_distribution(dcop, "maxsum"))
        r_dev = solve(dcop, "maxsum", backend="device",
                      max_cycles=max(int(r_thr["cycles"]), 50))
        if r_dev["status"] == "FINISHED":
            break
    assert r_dev["status"] == "FINISHED", (
        f"the thread runtime ran {r_thr['cycles']} cycles in "
        f"{timeout} s, fewer than the device needs to converge")
    assert r_thr["assignment"] == r_dev["assignment"]
    assert r_thr["cost"] == r_dev["cost"]
