"""The plain reference: synchronous min-sum on factors of any arity,
numpy.

Independent of ``pydcop_tpu``: it reads the problem only by calling
each constraint on every tuple of values and each variable's
``cost_for_val``.  Damping 0.5 on both message directions,
mean-normalised variable-to-factor messages, a small seeded
tie-breaking noise on the unary costs, a fixed cycle budget, decoded
by the argmin of the beliefs.  On a tree it is exact.

Factors are grouped by arity, one ``(index[F, k], costs[F, D, ..., D])``
each, and every step visits the groups in ascending arity.  For a
problem of binary factors there is one group, and the floating-point
operations and their order are those of the binary reference this
grew from (held by a test that keeps that one as its oracle).
"""

import itertools

import numpy as np

DAMPING = 0.5
NOISE = 1e-3


def tables(dcop):
    """``(names, values, unary[V, D], factors)`` of a problem whose
    variables share one domain size.  ``factors`` maps each arity k of
    2 and over to ``(index[F, k], costs[F, D, ..., D])``, in ascending
    arity; constraints of arity 1 are folded into ``unary``."""
    variables = list(dcop.variables.values())
    names = [v.name for v in variables]
    position = {name: i for i, name in enumerate(names)}
    values = [list(v.domain.values) for v in variables]
    size = len(values[0])
    if any(len(vals) != size for vals in values):
        raise ValueError("the reference needs one domain size")
    unary = np.array([[v.cost_for_val(val) for val in vals]
                      for v, vals in zip(variables, values)], dtype=float)
    index, costs = {}, {}
    for c in dcop.constraints.values():
        dims = [v.name for v in c.dimensions]
        scope = [position[d] for d in dims]
        if len(dims) == 1:
            unary[scope[0]] += [c(**{dims[0]: val})
                                for val in values[scope[0]]]
            continue
        index.setdefault(len(dims), []).append(scope)
        costs.setdefault(len(dims), []).append([
            c(**dict(zip(dims, chosen)))
            for chosen in itertools.product(*(values[i] for i in scope))])
    factors = {
        k: (np.array(index[k], dtype=np.int64).reshape(-1, k),
            np.array(costs[k], dtype=float).reshape((-1,) + (size,) * k))
        for k in sorted(index)}
    return names, values, unary, factors


def _to_variables(costs, to_factor):
    """What each factor of one arity sends to each of its positions:
    the min, over the other positions' values, of its costs plus what
    those positions sent it.  ``[F, k, D]``."""
    arity = costs.ndim - 1
    out = []
    for p in range(arity):
        total = costs
        for q in range(arity):
            if q != p:
                shape = [len(costs)] + [1] * arity
                shape[q + 1] = -1
                total = total + to_factor[:, q].reshape(shape)
        others = tuple(q + 1 for q in range(arity) if q != p)
        out.append(total.min(axis=others))
    return np.stack(out, axis=1)


def min_sum(unary, factors, cycles, seed=0):
    """The value index of every variable after ``cycles`` cycles."""
    rng = np.random.default_rng(seed)
    unary = unary + NOISE * rng.random(unary.shape)
    size = unary.shape[1]
    to_factor = {k: np.zeros((len(index), k, size))
                 for k, (index, _) in factors.items()}
    to_var = {k: np.zeros((len(index), k, size))
              for k, (index, _) in factors.items()}
    beliefs = unary
    for _ in range(cycles):
        for k, (_, costs) in factors.items():
            to_var[k] = (DAMPING * to_var[k] + (1 - DAMPING)
                         * _to_variables(costs, to_factor[k]))
        beliefs = unary.copy()
        for k, (index, _) in factors.items():
            np.add.at(beliefs, index, to_var[k])
        for k, (index, _) in factors.items():
            new = beliefs[index] - to_var[k]
            new -= new.mean(axis=2, keepdims=True)
            to_factor[k] = DAMPING * to_factor[k] + (1 - DAMPING) * new
    return beliefs.argmin(axis=1)


def solve(dcop, cycles, seed=0):
    """``(assignment, cost)``: the reference's answer and what it
    costs, summed from the same tables."""
    names, values, unary, factors = tables(dcop)
    choice = min_sum(unary, factors, cycles, seed)
    cost = float(unary[np.arange(len(names)), choice].sum())
    for k, (index, costs) in factors.items():
        chosen = tuple(choice[index[:, p]] for p in range(k))
        cost += float(costs[(np.arange(len(index)),) + chosen].sum())
    return {n: values[i][choice[i]] for i, n in enumerate(names)}, cost
