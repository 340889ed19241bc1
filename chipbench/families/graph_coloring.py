"""``pydcop generate graph_coloring``.

Spec keys: ``variables``, ``colors``, ``graph`` (``random`` with
``p_edge``, or ``grid``), ``soft`` and, for a random graph,
``constraints``: the edges are cut or filled to exactly that many, so
that every seed has the same shapes and finds the same compiled
program.
"""

import math

import numpy as np

from chipbench.lib import BenchFailure

SMALL_CONSTRAINTS = 1500


def generate(spec, seed):
    """The instance the command builds, as a DCOP object (the
    function behind the command)."""
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    dcop = generate_graph_coloring(
        spec["variables"], spec["colors"], spec["graph"],
        soft=spec.get("soft", False), p_edge=spec.get("p_edge"),
        allow_subgraph=True, noagents=True, seed=seed)
    if "constraints" in spec:
        fix_constraint_count(dcop, spec["constraints"], seed)
    return dcop


def fix_constraint_count(dcop, count, seed):
    """Drop seeded-random constraints, or add copies of the (one)
    hard table between seeded-random pairs that share none yet."""
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    names = list(dcop.constraints)
    surplus = len(names) - count
    if surplus > 0:
        for i in rng.choice(len(names), surplus, replace=False):
            del dcop.constraints[names[i]]
        return
    table = np.asarray(dcop.constraints[names[0]].matrix)
    if any(not np.array_equal(c.matrix, table)
           for c in dcop.constraints.values()):
        raise BenchFailure("constraints can be added only where all "
                           "share one table (not to a soft instance)")
    variables = list(dcop.variables.values())
    taken = {frozenset(v.name for v in c.dimensions)
             for c in dcop.constraints.values()}
    while len(dcop.constraints) < count:
        i, j = sorted(rng.choice(len(variables), 2, replace=False))
        pair = frozenset((variables[i].name, variables[j].name))
        if pair not in taken:
            taken.add(pair)
            dcop.add_constraint(NAryMatrixRelation(
                [variables[i], variables[j]], table.copy(),
                f"c{len(dcop.constraints)}x"))


def _mean_edges(spec):
    """What ``-p`` gives on average: p * V * (V - 1) / 2."""
    return spec["p_edge"] * spec["variables"] * (spec["variables"] - 1) / 2


def _constraints(spec):
    if "constraints" in spec:
        return spec["constraints"]
    if spec["graph"] == "grid":
        side = math.isqrt(spec["variables"])
        return 2 * side * (side - 1)
    raise ValueError(
        f"a {spec['graph']} graph's edge count varies by seed: the "
        "spec has to fix `constraints`")


def shapes(spec):
    return {"variables": spec["variables"], "domain": spec["colors"],
            "factors_by_arity": {2: _constraints(spec)}}


def small(spec):
    """At most ``SMALL_CONSTRAINTS`` constraints at the same mean
    degree: a random graph's variables divided and its density
    multiplied by one whole number, its count restated as the mean of
    that density; a grid's side cut."""
    scale = -(-_constraints(spec) // SMALL_CONSTRAINTS)
    if scale == 1:
        return spec
    if spec["graph"] == "grid":
        side = (1 + math.isqrt(1 + 2 * SMALL_CONSTRAINTS)) // 2
        return dict(spec, variables=side * side)
    out = dict(spec, variables=spec["variables"] // scale,
               p_edge=spec["p_edge"] * scale)
    out["constraints"] = round(_mean_edges(out))
    return out


def check(spec):
    """``constraints`` is the mean of what ``-p`` gives, to the
    nearest whole number or the one beside it."""
    if "constraints" in spec and abs(
            _mean_edges(spec) - spec["constraints"]) >= 2:
        raise ValueError(
            f"constraints {spec['constraints']} is not what p_edge "
            f"{spec['p_edge']} gives on average over "
            f"{spec['variables']} variables ({_mean_edges(spec):.1f})")
