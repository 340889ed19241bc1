"""A tiny cell of another family, built from data files alone,
rehearsed on the CPU.

SECP (smart lighting): factors of arity 1 to 4 side by side, and a
solver's parameter the configuration has to state.  The cell goes
through ``run.main`` with the rehearsal's own ``--bench``: sound,
with the parameters left at pyDCOP's defaults, and with a fault
planted in the program that touches only factors of arity 3 and over."""

import pytest
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    LAST_LINE_KEYS,
    harness,
    last_line,
)
from test_rehearsal_cells import _write_bench

# Another family, as data alone.  One broken model constraint costs
# 10 000 and a sound answer 170-290 here, so the tolerance holds the
# program to the reference's cost to well within one such constraint.
# With pyDCOP's default `stability` (0.1) MaxSum stops at cycle 16-123
# with up to two of them broken: the configuration has to state 0.
SECP = {
    "name": "tiny_secp", "kind": "solve",
    "generator": {"family": "secp", "lights": 56, "models": 17,
                  "rules": 28, "max_model_size": 3, "max_rule_size": 3,
                  "factors_by_arity": {"1": 62, "2": 7, "3": 15, "4": 6}},
    "algo": "maxsum", "algo_params": {"stability": 0}, "max_cycles": 200,
    "ends": {"status": "TIMEOUT", "cycles": 200}, "cli_solve": True,
    "reference": "min_sum", "cost_tolerance": 0.5}
SECP_CELL = "tiny_secp.resolve"


@pytest.fixture
def secp_bench(tmp_path):
    """The tiny SECP cell as its configuration states it, and the
    same with the solver's parameters left at pyDCOP's defaults."""
    return _write_bench(
        tmp_path, [SECP_CELL, "tiny_secp_default.resolve"],
        {"tiny_secp": SECP,
         "tiny_secp_default": dict(SECP, name="tiny_secp_default",
                                   algo_params={})})


def run_secp(run, bench, trace, seed, cell=SECP_CELL):
    return run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "1", "--trace", str(trace),
                     "--bench", bench])


# Seeds on which the sound program and the plain reference agree to
# the digit (sandbox, PR 30: 1, 2, 3, 4, 5, 8, 2147483659 and
# 3000000001 do; on 7 both leave one model constraint broken).
@pytest.mark.parametrize("trace,seed", [(0, 1), (0, 3000000001), (1, 3)])
def test_a_secp_cell_is_correct_and_costs_what_the_reference_does(
        harness, secp_bench, capsys, trace, seed):
    assert run_secp(harness, secp_bench, trace, seed) == 0
    line, notes = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    setup = next(n["setup"] for n in notes if "setup" in n)
    assert (setup["variables"], setup["constraints"]) == (73, 90)
    assert setup["cli_solve_s"] > 0
    # The CLI solve, given `-p stability:0`, is among the answers.
    window = next(n["window"] for n in notes if "window" in n)
    assert line["attempted"] == window["solves"] + 1
    assert (window["status"], window["cycles"]) == ("TIMEOUT", 200)
    cost, limit = line["compared"]["cost"]
    assert cost == pytest.approx(setup["reference_cost"], abs=1e-6)
    assert limit == pytest.approx(1.5 * setup["reference_cost"])
    assert limit < setup["reference_cost"] + 10000
    assert line["compared"]["cycles"] == [200, 200]
    if trace:
        assert line["metrics"]["solve.cost_ratio"]["value"] == (
            pytest.approx(1.0, abs=1e-6))
        assert "yaml.load_s" in line["metrics"]


@pytest.mark.parametrize("seed,costs_more", [(1, True), (2, False)])
def test_with_the_default_parameters_a_secp_cell_is_not_correct(
        harness, secp_bench, capsys, seed, costs_more):
    """pyDCOP's default ``stability`` stops MaxSum early (sandbox, PR
    30: ``FINISHED`` at cycle 121 with one model constraint broken on
    seed 1, at 116 with the reference's cost on seed 2).  The fault
    names how the solve ended; where the cost is sound, that is all
    that tells the two configurations apart."""
    assert run_secp(harness, secp_bench, 0, seed,
                    "tiny_secp_default.resolve") == 0
    line, notes = last_line(capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 2
    faults = [n["fault"] for n in notes if "fault" in n]
    assert faults[0].startswith("pydcop solve: ")
    cycles, stated = line["compared"]["cycles"]
    assert cycles < stated == 200
    cost, limit = line["compared"]["cost"]
    if costs_more:
        assert cost > limit + 9000
        assert all("worse than the reference" in f for f in faults)
    else:
        assert cost <= limit
        assert all(f.endswith(f"ended FINISHED at cycle {cycles}; the "
                              "configuration states TIMEOUT at 200")
                   for f in faults)


def test_factors_of_arity_3_and_over_silenced_make_a_secp_cell_not_correct(
        harness, secp_bench, capsys, monkeypatch):
    """The fault planted in the program, in both layouts: what a
    factor of arity 3 or 4 sends its variables is zeroed, so the
    models' hard constraints are not heard.  Lights' costs, rules of
    one and two variables and the solver's budget are untouched: only
    the comparison with a reference that takes every arity sees it."""
    import jax.numpy as jnp

    from pydcop_tpu.ops import maxsum, maxsum_lane

    silenced = []

    def deaf_to_high_arity(honest):
        def factor_to_var(graph, v2f, *args, **kwargs):
            out = honest(graph, v2f, *args, **kwargs)
            silenced.extend(m.shape[1] for m in out if m.shape[1] >= 3)
            return tuple(jnp.zeros_like(m) if m.shape[1] >= 3 else m
                         for m in out)
        return factor_to_var

    for module in (maxsum, maxsum_lane):
        monkeypatch.setattr(module, "factor_to_var",
                            deaf_to_high_arity(module.factor_to_var))
    assert run_secp(harness, secp_bench, 0, 1) == 0
    assert {3, 4} <= set(silenced)
    line, notes = last_line(capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 2
    faults = [n["fault"] for n in notes if "fault" in n]
    assert faults and all("worse than the reference" in f for f in faults)
    cost, limit = line["compared"]["cost"]
    assert cost > limit + 9000
    assert line["compared"]["cost_minus_host"] == [0.0, 0]
    assert line["compared"]["cycles"] == [200, 200]


def test_a_seed_whose_shapes_are_not_the_familys_gives_no_result(
        harness, secp_bench, capsys, monkeypatch):
    """Every seed has to find the same compiled program: an instance
    that has not the shapes its family states for the spec is a
    failure of the run, not an answer at fault."""
    from chipbench.families import secp

    honest = secp.shapes
    monkeypatch.setattr(secp, "shapes", lambda spec: dict(
        honest(spec), variables=honest(spec)["variables"] + 1))
    assert run_secp(harness, secp_bench, 0, 1) == 1
    captured = capsys.readouterr()
    assert "the configuration's family states" in captured.err
    assert not any("correct" in x for x in captured.out.splitlines())
