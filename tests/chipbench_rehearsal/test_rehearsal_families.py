"""Every family of ``chipbench/families/`` and what the configuration
files ask of one, rehearsed on the CPU: the shapes a spec states are
the shapes every seed gives, ``small`` keeps the family's rule at a
size a test holds, ``check`` refuses counts the draws do not give."""

import glob
import os

import pytest
from rehearsal_benchmarks import (
    config_files,
    per_benchmark,
    solve_config_files,
)
from test_rehearsal import CHIPBENCH, FAMILY_SPECS
from test_rehearsal_cells import _config


@pytest.mark.parametrize("name", sorted(FAMILY_SPECS))
def test_every_seed_gives_the_shapes_its_family_states(name):
    """Every seed's instance has the counts the spec states (so that
    it finds the same compiled program), no two constraints of a
    colouring share a pair, and one seed gives one instance."""
    from chipbench import lib

    spec = FAMILY_SPECS[name]
    stated = lib.family_of(spec).shapes(spec)
    for seed in (1, 2, 3000000001):
        dcop = lib.generate(spec, seed)
        assert lib.shapes(dcop) == stated
        scopes = [tuple(v.name for v in c.dimensions)
                  for c in dcop.constraints.values()]
        if spec["family"] == "graph_coloring":
            assert len({frozenset(s) for s in scopes}) == len(scopes)
        again = lib.generate(spec, seed)
        assert list(again.constraints) == list(dcop.constraints)
        assert scopes == [tuple(v.name for v in c.dimensions)
                          for c in again.constraints.values()]


@pytest.mark.parametrize("which,filename", per_benchmark(config_files))
def test_a_configuration_names_a_family_that_is_a_file(
        benchmarks, which, filename):
    """In the benchmark's own ``families/``, or in ``chipbench``'s,
    where a rehearsal's copy adds none of that name."""
    data = benchmarks[which][1]
    family = _config(filename, data)["generator"]["family"]
    assert any(os.path.isfile(os.path.join(root, "families", f"{family}.py"))
               for root in (data, CHIPBENCH))


@pytest.mark.parametrize("which,filename",
                         per_benchmark(solve_config_files))
def test_a_solve_configuration_states_its_parameters_and_how_it_ends(
        benchmarks, which, filename):
    config = _config(filename, benchmarks[which][1])
    assert isinstance(config["algo_params"], dict)
    if config["ends"] is not None:
        assert set(config["ends"]) == {"status", "cycles"}
        assert config["ends"]["cycles"] <= config["max_cycles"]
        # A solve that spends its budget is a TIMEOUT, and no other is.
        assert (config["ends"]["status"] == "TIMEOUT") == (
            config["ends"]["cycles"] == config["max_cycles"])


# --------------------------------------------------------------------- #
# every family is what the README says one is


FAMILY_FILES = sorted(
    os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(CHIPBENCH, "families", "*.py"))
    if not os.path.basename(p).startswith("_"))


@pytest.mark.parametrize("name", FAMILY_FILES)
def test_a_family_has_the_four_functions_the_readme_names(name):
    from chipbench import lib

    family = lib.module_by_name("families", name, "family")
    for function in ("generate", "shapes", "small", "check"):
        assert callable(getattr(family, function)), function


# The sizing run of PR 30: 10 000 factors in the 20 : 3 : 10 of
# docs/cli.md's `pydcop generate secp`, each count 5 standard
# deviations under the mean of its draws.
SECP_10K = {
    "family": "secp", "lights": 6380, "models": 957, "rules": 3190,
    "max_model_size": 3, "max_rule_size": 3,
    "factors_by_arity": {"1": 7282, "2": 930, "3": 1387, "4": 401}}
LARGE_SPECS = {
    "graph_coloring-random": {
        "family": "graph_coloring", "variables": 20000, "colors": 3,
        "graph": "random", "p_edge": 0.0002, "constraints": 39998},
    "graph_coloring-grid": {
        "family": "graph_coloring", "variables": 10000, "colors": 3,
        "graph": "grid", "soft": True},
    "secp": SECP_10K,
}


@pytest.mark.parametrize("name", sorted(LARGE_SPECS))
def test_small_keeps_the_family_and_its_rule_at_a_size_a_test_holds(name):
    from chipbench import lib

    spec = LARGE_SPECS[name]
    family = lib.family_of(spec)
    family.check(spec)
    small = family.small(spec)
    assert small["family"] == spec["family"]
    shapes = family.shapes(small)
    assert 500 < sum(shapes["factors_by_arity"].values()) <= 1500
    assert shapes["domain"] == family.shapes(spec)["domain"]
    family.check(small)
    assert family.small(small) == small
    for seed in (5, 3000000017):
        assert lib.shapes(lib.generate(small, seed)) == shapes


def test_secp_keeps_every_light_and_variable_and_drops_by_the_seed():
    from chipbench import lib
    from pydcop_tpu.generators.secp import generate_secp

    spec = FAMILY_SPECS["secp"]
    for seed in (1, 2, 3000000001):
        dcop = lib.generate(spec, seed)
        drawn = generate_secp(56, 17, 28, max_model_size=3,
                              max_rule_size=3, seed=seed)
        assert list(dcop.variables) == list(drawn.variables)
        assert len(dcop.variables) == 56 + 17
        assert {f"c_l{i}" for i in range(56)} <= set(dcop.constraints)
        # What is left is what was drawn, in its order, less a surplus.
        assert [n for n in drawn.constraints if n in dcop.constraints] == (
            list(dcop.constraints))
        assert len(drawn.constraints) > len(dcop.constraints) == 81


@pytest.mark.parametrize("arity,count,said", [
    ("4", 30, "arity 4: "), ("1", 55, "arity 1: "),
])
def test_secp_fails_where_a_count_cannot_be_had(arity, count, said):
    """More factors of an arity than the seed drew, or fewer of arity
    1 than there are lights, whose costs stay."""
    from chipbench import lib

    spec = dict(FAMILY_SPECS["secp"], factors_by_arity=dict(
        FAMILY_SPECS["secp"]["factors_by_arity"], **{arity: count}))
    with pytest.raises(lib.BenchFailure) as failure:
        lib.generate(spec, 1)
    assert said in str(failure.value)
    assert f"{count} asked" in str(failure.value)
    assert "drawn" in str(failure.value)


def test_secp_fails_where_an_arity_drawn_has_no_stated_count():
    from chipbench import lib

    counts = dict(FAMILY_SPECS["secp"]["factors_by_arity"])
    del counts["4"]
    with pytest.raises(lib.BenchFailure, match="states no count"):
        lib.generate(dict(FAMILY_SPECS["secp"], factors_by_arity=counts), 1)


def test_secp_check_refuses_a_count_that_some_seed_falls_short_of():
    from chipbench.families import secp

    secp.check(SECP_10K)
    drawn = secp.draws(SECP_10K)
    assert drawn[1][0] == 6380 + 3190 / 3
    assert drawn[3][0] == 957 / 2 + 3190 / 3
    assert drawn[4][0] == 957 / 2
    for arity, (mean, sd) in drawn.items():
        counts = dict(SECP_10K["factors_by_arity"],
                      **{str(arity): int(mean - 2 * sd)})
        with pytest.raises(ValueError, match=f"arity {arity}: "):
            secp.check(dict(SECP_10K, factors_by_arity=counts))
    with pytest.raises(ValueError, match="lights' costs"):
        secp.check(dict(SECP_10K, factors_by_arity=dict(
            SECP_10K["factors_by_arity"], **{"1": 6000})))


def test_graph_coloring_check_refuses_a_count_its_density_does_not_give():
    from chipbench.families import graph_coloring

    spec = LARGE_SPECS["graph_coloring-random"]
    graph_coloring.check(spec)
    with pytest.raises(ValueError, match="on average"):
        graph_coloring.check(dict(spec, constraints=39990))
    with pytest.raises(ValueError, match="varies by seed"):
        graph_coloring.shapes({k: v for k, v in spec.items()
                               if k != "constraints"})
