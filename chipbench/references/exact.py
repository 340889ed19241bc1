"""The plain reference ``exact``: bucket (variable) elimination, numpy.

Independent of ``pydcop_tpu``, as ``min_sum`` is: it reads the problem
only by calling each constraint on every tuple of values and each
variable's ``cost_for_val``.  Variables are eliminated in a
min-degree order computed from the scopes (of equal degrees, the
variable first in ``dcop.variables``).  Eliminating a variable adds
every table that holds it and keeps, for each assignment of the
others, its best value (the first of equals) and that total.  The
answer is decoded in the reverse order, and its cost (on a problem
whose ``objective`` is ``max``, its utility) is summed from the same
tables.  ``cycles`` and ``seed`` are not read: the answer is an
optimum, and the same one every time.

Where the elimination would build a table of more than ``MAX_TABLE``
entries (2**26, 512 MiB of float64), ``solve`` raises ``ValueError``
naming the largest before it builds any: too wide a problem is a
verdict, not a hang.
"""

import heapq
import itertools
import math

import numpy as np

MAX_TABLE = 2 ** 26


def tables(dcop):
    """``(names, values, factors)``: every variable's name and values,
    and one ``(scope, costs)`` per variable (its ``cost_for_val``) and
    per constraint, ``scope`` the variables' positions and ``costs``
    an array with one axis per position of the scope, in its order."""
    variables = list(dcop.variables.values())
    names = [v.name for v in variables]
    position = {name: i for i, name in enumerate(names)}
    values = [list(v.domain.values) for v in variables]
    factors = [((i,), np.array([v.cost_for_val(val) for val in values[i]],
                               dtype=float))
               for i, v in enumerate(variables)]
    for c in dcop.constraints.values():
        dims = [v.name for v in c.dimensions]
        scope = tuple(position[d] for d in dims)
        costs = [c(**dict(zip(dims, chosen)))
                 for chosen in itertools.product(*(values[i] for i in scope))]
        factors.append((scope, np.array(costs, dtype=float).reshape(
            [len(values[i]) for i in scope])))
    return names, values, factors


def elimination_order(sizes, scopes):
    """The positions in min-degree order.  Raises ``ValueError`` where
    eliminating some variable would build a table of more than
    ``MAX_TABLE`` entries, naming the largest."""
    neighbours = [set() for _ in sizes]
    for scope in scopes:
        for i in scope:
            neighbours[i].update(scope)
    for i, around in enumerate(neighbours):
        around.discard(i)
    heap = [(len(around), i) for i, around in enumerate(neighbours)]
    heapq.heapify(heap)
    done, order, largest = [False] * len(sizes), [], (0, None, 0)
    while heap:
        degree, i = heapq.heappop(heap)
        if done[i] or degree != len(neighbours[i]):
            continue
        done[i] = True
        order.append(i)
        entries = sizes[i] * math.prod(sizes[j] for j in neighbours[i])
        largest = max(largest, (entries, i, degree + 1))
        for j in neighbours[i]:
            neighbours[j].discard(i)
            neighbours[j].update(neighbours[i] - {j})
            heapq.heappush(heap, (len(neighbours[j]), j))
    entries, i, width = largest
    if entries > MAX_TABLE:
        raise ValueError(
            f"exact: eliminating variable {i} would build a table of "
            f"{entries} entries over {width} variables, more than "
            f"MAX_TABLE = {MAX_TABLE}")
    return order


def _expand(scope, costs, target):
    """``costs`` with its axes in ``target``'s order, and an axis of
    length 1 for each variable of ``target`` that ``scope`` lacks."""
    at = {v: a for a, v in enumerate(target)}
    axes = sorted(range(len(scope)), key=lambda a: at[scope[a]])
    shape = [1] * len(target)
    for a in axes:
        shape[at[scope[a]]] = costs.shape[a]
    return costs.transpose(axes).reshape(shape)


def eliminate(sizes, factors):
    """The value index of every variable in an assignment of least
    total cost over ``factors``."""
    order = elimination_order(sizes, [scope for scope, _ in factors])
    alive = dict(enumerate(factors))
    holding = [set() for _ in sizes]
    for f, (scope, _) in alive.items():
        for i in scope:
            holding[i].add(f)
    decoded = []
    for i in order:
        bucket = []
        for f in sorted(holding[i]):
            bucket.append(alive.pop(f))
            for j in bucket[-1][0]:
                holding[j].discard(f)
        others = tuple(sorted({j for scope, _ in bucket for j in scope}
                              - {i}))
        total = sum(_expand(scope, costs, others + (i,))
                    for scope, costs in bucket)
        best = total.argmin(axis=-1)
        decoded.append((i, others, best))
        if others:
            message = np.take_along_axis(total, best[..., None], -1)[..., 0]
            f = len(factors) + len(decoded)
            alive[f] = (others, message)
            for j in others:
                holding[j].add(f)
    choice = [0] * len(sizes)
    for i, others, best in reversed(decoded):
        choice[i] = int(best[tuple(choice[j] for j in others)])
    return choice


def solve(dcop, cycles=None, seed=None):
    """``(assignment, cost)``: an optimal assignment and its cost (on a
    max problem, its utility), summed from the same tables."""
    names, values, factors = tables(dcop)
    sign = -1.0 if dcop.objective == "max" else 1.0
    choice = eliminate([len(v) for v in values],
                       [(scope, sign * costs) for scope, costs in factors])
    cost = sum(float(costs[tuple(choice[i] for i in scope)])
               for scope, costs in factors)
    return {n: values[i][choice[i]] for i, n in enumerate(names)}, cost
