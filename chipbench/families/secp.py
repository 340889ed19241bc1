"""``pydcop generate secp``: smart-lighting scenes.

Spec keys: ``lights``, ``models``, ``rules``, ``max_model_size``,
``max_rule_size`` (the command's arguments) and ``factors_by_arity``
(``{"1": n, "2": n, ...}``).  Every light and model is a variable of
one domain of 5 levels.  A light has a cost factor of arity 1
(``c_l*``); a model is tied to 2..``max_model_size`` lights by a hard
constraint of arity 3 and over (``c_m*``); a rule sets targets for
1..``max_rule_size`` variables (``r_*``).  How many factors of each
arity a seed draws varies, and the program compiles one program per
set of counts, so the surplus of each arity is dropped (seeded) down
to the count the spec states: every seed then has the same shapes.
Only models' and rules' constraints are dropped, never a light's
cost and never a variable: a model whose constraint went stays as a
free variable.
"""

import math

import numpy as np

from chipbench.lib import BenchFailure

LEVELS = 5
SMALL_CONSTRAINTS = 1500
# How far under the mean of the draws a stated count has to lie, in
# standard deviations, so that no seed falls short of it.
SIGMAS = 5


def generate(spec, seed):
    from pydcop_tpu.generators.secp import generate_secp

    dcop = generate_secp(
        spec["lights"], spec["models"], spec["rules"],
        max_model_size=spec["max_model_size"],
        max_rule_size=spec["max_rule_size"], seed=seed)
    by_arity = {}
    for name, c in dcop.constraints.items():
        by_arity.setdefault(len(c.dimensions), []).append(name)
    asked = {int(k): n for k, n in spec["factors_by_arity"].items()}
    rng = np.random.default_rng(seed)
    for arity in sorted(by_arity):
        names = by_arity[arity]
        if arity not in asked:
            raise BenchFailure(
                f"{len(names)} factors of arity {arity} drawn, and the "
                "spec's factors_by_arity states no count for it")
        free = [n for n in names if not n.startswith("c_l")]
        surplus = len(names) - asked[arity]
        if not 0 <= surplus <= len(free):
            raise BenchFailure(
                f"arity {arity}: {len(names)} factors drawn, "
                f"{len(names) - len(free)} of them lights' costs, which "
                f"stay; {asked[arity]} asked (seed {seed})")
        for i in rng.choice(len(free), surplus, replace=False):
            del dcop.constraints[free[i]]
    return dcop


def shapes(spec):
    return {"variables": spec["lights"] + spec["models"],
            "domain": LEVELS,
            "factors_by_arity": {int(k): n for k, n
                                 in spec["factors_by_arity"].items() if n}}


def draws(spec):
    """``{arity: (mean, standard deviation)}`` of what a seed draws: a
    model's arity is even over 3..``max_model_size`` + 1, a rule's over
    1..``max_rule_size``, and arity 1 also holds every light's cost."""
    model = range(3, max(3, spec["max_model_size"] + 1) + 1)
    rule = range(1, spec["max_rule_size"] + 1)
    out = {}
    for arity in sorted(set(model) | set(rule)):
        mean, variance = (spec["lights"] if arity == 1 else 0), 0.0
        for count, arities in ((spec["models"], model),
                               (spec["rules"], rule)):
            if arity in arities:
                p = 1 / len(arities)
                mean += count * p
                variance += count * p * (1 - p)
        out[arity] = (mean, math.sqrt(variance))
    return out


def small(spec):
    """At most ``SMALL_CONSTRAINTS`` constraints in the same ratio of
    lights, models and rules, each count restated as what every seed
    reaches at that size."""
    total = sum(spec["factors_by_arity"].values())
    scale = -(-total // SMALL_CONSTRAINTS)
    if scale == 1:
        return spec
    out = dict(spec, **{key: spec[key] // scale
                        for key in ("lights", "models", "rules")})
    out["factors_by_arity"] = {
        str(arity): math.floor(mean - SIGMAS * sd)
        for arity, (mean, sd) in draws(out).items()}
    return out


def check(spec):
    """Every stated count lies ``SIGMAS`` standard deviations or more
    under the mean of the draws, and arity 1 keeps every light."""
    drawn = draws(spec)
    for key, count in spec["factors_by_arity"].items():
        mean, sd = drawn.get(int(key), (0, 0.0))
        if count > mean - SIGMAS * sd:
            raise ValueError(
                f"arity {key}: {count} stated; the draws give "
                f"{mean:.1f} on average (sd {sd:.1f}), so some seed "
                f"falls short of more than {mean - SIGMAS * sd:.1f}")
    if spec["factors_by_arity"].get("1", 0) < spec["lights"]:
        raise ValueError(
            f"arity 1: {spec['factors_by_arity'].get('1', 0)} stated, "
            f"under the {spec['lights']} lights' costs, which stay")
