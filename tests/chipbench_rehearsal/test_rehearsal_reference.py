"""The plain references, rehearsed on the CPU: ``min_sum`` on factors
of any arity against brute force on a hypertree (min and max), and
against the binary reference it grew from, kept here word for word as
the oracle; ``exact`` against brute force on loopy problems of arity 1
to 3 (min and max), and its refusal of a table too wide."""

import itertools

import numpy as np
import pytest
from test_rehearsal import _brute_force, _random_problem


def _best(dcop):
    """The optimum by brute force, in the problem's own sense."""
    names = list(dcop.variables)
    pick = max if dcop.objective == "max" else min
    return pick(
        dcop.solution_cost(dict(zip(names, values)))[0]
        for values in itertools.product(
            *(dcop.variables[n].domain.values for n in names)))


def _loopy(seed, objective):
    """Seven variables of three values; factors of arity 1, 2 and 3
    that close cycles, and a variable with no factor."""
    rng = np.random.default_rng(seed)
    scopes = [(0,), (3,), (0, 1), (1, 2), (2, 0), (2, 3, 4), (4, 5),
              (5, 3), (1, 4, 5), (6, 0)]
    dcop = _random_problem(rng, scopes, 3)
    dcop.objective = objective
    return dcop


@pytest.mark.parametrize("objective", ["min", "max"])
@pytest.mark.parametrize("seed", [1, 2, 3000000001])
def test_exact_finds_the_optimum_of_a_loopy_problem(seed, objective):
    from chipbench.references import exact

    dcop = _loopy(seed, objective)
    assignment, cost = exact.solve(dcop, cycles=30, seed=seed)
    assert cost == pytest.approx(_best(dcop), abs=1e-9)
    assert dcop.solution_cost(assignment)[0] == pytest.approx(cost,
                                                             abs=1e-9)
    # The same answer every time, whatever the seed.
    assert exact.solve(dcop, cycles=1, seed=seed + 1) == (assignment, cost)


def test_exact_refuses_a_clique_too_wide_before_it_builds_a_table():
    """27 variables of two values that all meet: eliminating the first
    would build 2**27 entries, twice the cap."""
    from chipbench.references import exact

    dcop = _random_problem(np.random.default_rng(1), list(
        itertools.combinations(range(27), 2)), 2)
    with pytest.raises(ValueError, match=r"table of 134217728 entries "
                                         r"over 27 variables"):
        exact.solve(dcop, 0, 0)


def test_reference_finds_the_optimum_of_a_hypertree_of_arity_2_3_and_4():
    """Nine variables of three values; factors of arity 2, 3 and 4
    that meet in one variable at most and close no cycle."""
    from chipbench.references import min_sum as reference

    scopes = [(0, 1, 2, 3), (3, 4, 5), (5, 6), (2, 7, 8)]
    dcop = _random_problem(np.random.default_rng(11), scopes, 3)
    assert sorted(reference.tables(dcop)[3]) == [2, 3, 4]
    assignment, cost = reference.solve(dcop, cycles=30, seed=1)
    assert cost == pytest.approx(_brute_force(dcop))
    assert dcop.solution_cost(assignment)[0] == pytest.approx(cost)


def test_min_sum_finds_the_optimum_of_a_max_hypertree():
    """The hypertree below, its utility maximised: max-sum, that is
    min-sum on the negated tables, and the utility returned."""
    from chipbench.references import min_sum

    scopes = [(0, 1, 2, 3), (3, 4, 5), (5, 6), (2, 7, 8)]
    dcop = _random_problem(np.random.default_rng(11), scopes, 3)
    dcop.objective = "max"
    assignment, utility = min_sum.solve(dcop, cycles=30, seed=1)
    assert utility == pytest.approx(_best(dcop))
    assert dcop.solution_cost(assignment)[0] == pytest.approx(utility)
    # Not the least utility, which a min-sum that ignored the
    # objective would find.
    assert utility > _brute_force(dcop)


def _binary_min_sum(unary, index, costs, cycles, seed=0):
    """The binary reference as it stood before factors of any arity
    (PR 25 to PR 29), kept word for word as the oracle: the three
    accepted cells' reference costs were computed by it."""
    DAMPING, NOISE = 0.5, 1e-3
    rng = np.random.default_rng(seed)
    unary = unary + NOISE * rng.random(unary.shape)
    n_factors, size = len(index), unary.shape[1]
    to_factor = np.zeros((n_factors, 2, size))
    to_var = np.zeros((n_factors, 2, size))
    beliefs = unary
    for _ in range(cycles):
        new = np.stack([
            (costs + to_factor[:, 1, None, :]).min(axis=2),
            (costs + to_factor[:, 0, :, None]).min(axis=1)], axis=1)
        to_var = DAMPING * to_var + (1 - DAMPING) * new
        beliefs = unary.copy()
        np.add.at(beliefs, index, to_var)
        new = beliefs[index] - to_var
        new -= new.mean(axis=2, keepdims=True)
        to_factor = DAMPING * to_factor + (1 - DAMPING) * new
    return beliefs.argmin(axis=1)


@pytest.mark.parametrize("seed", [1, 2, 3000000001])
def test_on_binary_factors_the_reference_is_the_binary_one_bit_for_bit(seed):
    """Loopy problems, where a last digit's difference in a message
    would grow over the cycles: the values chosen and the cost are
    equal, not close."""
    from chipbench.references import min_sum as reference

    rng = np.random.default_rng(seed)
    scopes = set()
    while len(scopes) < 90:
        i, j = rng.choice(40, 2, replace=False)
        scopes.add((int(i), int(j)))
    dcop = _random_problem(rng, sorted(scopes), 4)
    names, values, unary, factors = reference.tables(dcop)
    assert list(factors) == [2]
    index, costs = factors[2]
    oracle = _binary_min_sum(unary, index, costs, 60, seed)
    assert (reference.min_sum(unary, factors, 60, seed) == oracle).all()
    oracle_cost = float(unary[np.arange(len(names)), oracle].sum())
    oracle_cost += float(costs[np.arange(len(index)), oracle[index[:, 0]],
                               oracle[index[:, 1]]].sum())
    assignment, cost = reference.solve(dcop, 60, seed)
    assert cost == oracle_cost
    assert assignment == {n: values[i][oracle[i]]
                          for i, n in enumerate(names)}


def test_the_reference_needs_one_domain_size():
    from chipbench.references import min_sum as reference
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    dcop = _random_problem(np.random.default_rng(1), [(0, 1)], 3)
    other = Variable("w", Domain("e", "e", ["a", "b"]))
    dcop.add_constraint(NAryMatrixRelation(
        [dcop.variables["v0"], other], np.zeros((3, 2)), "cw"))
    with pytest.raises(ValueError, match="one domain size"):
        reference.tables(dcop)
