"""The plain reference: synchronous min-sum on binary factors, numpy.

Independent of ``pydcop_tpu``: it reads the problem only by calling
each constraint on every pair of values and each variable's
``cost_for_val``.  Damping 0.5 on both message directions,
mean-normalised variable-to-factor messages, a small seeded
tie-breaking noise on the unary costs, a fixed cycle budget, decoded
by the argmin of the beliefs.  On a tree it is exact.
"""

import numpy as np

DAMPING = 0.5
NOISE = 1e-3


def tables(dcop):
    """``(names, values, unary[V, D], index[F, 2], costs[F, D, D])`` of
    a problem whose constraints have arity 1 or 2 and whose variables
    share one domain size."""
    variables = list(dcop.variables.values())
    names = [v.name for v in variables]
    position = {name: i for i, name in enumerate(names)}
    values = [list(v.domain.values) for v in variables]
    size = len(values[0])
    if any(len(vals) != size for vals in values):
        raise ValueError("the reference needs one domain size")
    unary = np.array([[v.cost_for_val(val) for val in vals]
                      for v, vals in zip(variables, values)], dtype=float)
    index, costs = [], []
    for c in dcop.constraints.values():
        dims = [v.name for v in c.dimensions]
        if len(dims) == 1:
            i = position[dims[0]]
            unary[i] += [c(**{dims[0]: val}) for val in values[i]]
        elif len(dims) == 2:
            i, j = position[dims[0]], position[dims[1]]
            index.append((i, j))
            costs.append([[c(**{dims[0]: a, dims[1]: b})
                           for b in values[j]] for a in values[i]])
        else:
            raise ValueError(f"constraint {c.name} has arity {len(dims)}")
    return (names, values, unary,
            np.array(index, dtype=np.int64).reshape(-1, 2),
            np.array(costs, dtype=float).reshape(-1, size, size))


def min_sum(unary, index, costs, cycles, seed=0):
    """The value index of every variable after ``cycles`` cycles."""
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


def solve(dcop, cycles, seed=0):
    """``(assignment, cost)``: the reference's answer and what it
    costs, summed from the same tables."""
    names, values, unary, index, costs = tables(dcop)
    choice = min_sum(unary, index, costs, cycles, seed)
    cost = float(unary[np.arange(len(names)), choice].sum())
    if len(index):
        cost += float(costs[np.arange(len(index)), choice[index[:, 0]],
                            choice[index[:, 1]]].sum())
    return {n: values[i][choice[i]] for i, n in enumerate(names)}, cost
