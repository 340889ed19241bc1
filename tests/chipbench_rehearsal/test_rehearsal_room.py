"""Room for the two kinds of cell that the harness could not take as
new files and entries only, tried by adding each to a copy of the
benchmark (``rehearsal_benchmarks.py``) and running it through
``run.main``:

- ``max_exact``: a max-objective deployment (a PEAV meeting day from
  a family file of the copy's own) solved by DPOP and judged by the
  plain ``exact`` reference; ``compared`` holds the negated utility
  beside the negated floor, and a spoiled answer is caught;
- ``shards4``: a colouring whose solves run over four shards, in a
  cell of four chips (the CPU's forced host devices), with
  ``--shards 4`` given to the timed ``pydcop solve`` too.

Each copy is compared with the repository as the driver's
``benchmark_edited`` does, and ``run.py`` refuses a configuration that
does not fit its cell's chips before anything runs.
"""

import json
import os
import shutil

import numpy as np
import pytest
from rehearsal_benchmarks import (
    ADDITIONS,
    CHIPBENCH,
    COPIED,
    MAX_CELL,
    MAX_CONFIG,
    SHARDS_CELL,
    SHARDS_CONFIG,
    benchmark,
)
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    LAST_LINE_KEYS,
    _random_problem,
    harness,
    last_line,
    unread,
)
from test_rehearsal_fourth_cell import (
    altered_files,
    altered_values,
    files_under,
)

SEED = 3000000001


def run_cell(run, benchmarks, which, cell, trace=0, seed=SEED):
    return run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "1", "--trace", str(trace), "--bench",
                     benchmarks[which][0]])


# --------------------------------------------------------------------- #
# entries and new files only


@pytest.mark.parametrize("which", ["max_exact", "shards4"])
def test_the_copy_alters_no_file_and_no_value_that_was_there(
        benchmarks, which):
    path, data = benchmarks[which]
    assert altered_files(CHIPBENCH, data) == []
    copied = set()
    for sub in COPIED:
        copied |= {f"{sub}/{name}" for name in
                   files_under(os.path.join(CHIPBENCH, sub))}
    assert files_under(data) - copied == set(ADDITIONS[which]["files"])
    assert set(os.listdir(os.path.dirname(path))) == {
        "BENCHMARK.json", "chipbench"}
    with open(path, encoding="utf-8") as f:
        written = json.load(f)
    assert written == benchmark(which)
    real = benchmark("real")
    assert altered_values(real, written) == []
    for section in ("configs", "workloads"):
        assert len(written[section]) == len(real[section]) + 1
    assert len(written["per_layer"]) == len(real["per_layer"])
    # No file of that name in chipbench: the copy's own is what runs.
    for relative in ADDITIONS[which]["files"]:
        assert not os.path.exists(os.path.join(CHIPBENCH, relative))


# --------------------------------------------------------------------- #
# a max objective, an exact algorithm, an exact reference


@pytest.mark.parametrize("trace", [0, 1])
def test_a_max_objective_dpop_cell_is_correct_against_the_exact_reference(
        harness, benchmarks, capsys, trace):
    assert run_cell(harness, benchmarks, "max_exact", MAX_CELL, trace) == 0
    line, notes = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True
    assert line["attempted"] > 1 and line["failed"] == 0
    setup = next(n["setup"] for n in notes if "setup" in n)
    assert setup["cli_solve_s"] > 0
    assert (setup["variables"], setup["constraints"]) == (12, 20)
    utility = setup["reference_cost"]
    assert utility > 0
    # Sound where value <= limit: the negated utility beside the
    # negated floor, utility - 1e-9 * |utility|.
    value, limit = line["compared"]["cost"]
    assert value == pytest.approx(-utility, abs=1e-9 * utility)
    assert limit == -utility + MAX_CONFIG["cost_tolerance"] * utility
    assert value <= limit
    assert "cycles" not in line["compared"]
    if trace:
        assert line["metrics"]["solve.cost_ratio"]["value"] == (
            pytest.approx(1.0, abs=1e-9))
        listed = {m["name"] for m in benchmark("max_exact")["per_layer"]
                  if MAX_CELL in m["workloads"]}
        assert set(line["metrics"]) == listed
        assert unread(notes) == []
    else:
        assert set(line["metrics"]) == {
            "setup_s", "cli_solve_s", "solve_p50_s"}


def test_check_answer_holds_a_utility_to_the_floor_below_the_reference():
    """On a max problem the answer is sound where its utility is at
    least the reference's less the tolerance's share of it;
    ``compared.cost`` holds both negated, so that "value <= limit"
    reads the same in both directions, and ``worst`` ranks the lowest
    utility worst."""
    from chipbench import lib

    dcop = _random_problem(np.random.default_rng(3), [(0, 1), (1, 2)], 3)
    dcop.objective = "max"
    assignment = {name: "x0" for name in dcop.variables}
    utility, violations = dcop.solution_cost(assignment)
    answer = {"assignment": assignment, "cost": utility,
              "violations": violations}

    def check(reference, tolerance):
        return lib.check_answer(dcop, answer, reference, tolerance)

    assert check(utility, 0.0) == (None, {
        "cost": [-utility, -utility], "unassigned": [0, 0],
        "cost_minus_host": [0.0, 0], "violations_minus_host": [0, 0]})
    # Above the reference is sound, whatever the tolerance.
    assert check(utility / 2, 0.0)[0] is None
    said, compared = check(utility * 2, 0.25)
    assert said == (f"utility {utility} is worse than the reference's "
                    f"{utility * 2} by more than 0.25 of it")
    assert compared["cost"] == [-utility, pytest.approx(-1.5 * utility)]
    assert check(utility * 2, 0.5)[0] is None
    checked = [check(utility * 1.5, 1.0), check(utility, 1.0)]
    assert lib.worst(checked) == checked[0][1]
    assert lib.cost_ratio(dcop, utility, utility * 1.5) == (
        pytest.approx(1 + 0.5 / 1.5))
    # A utility below 0 (PEAV's penalties): worse is still above 1,
    # and an answer of utility 0 gives a number, not a division by 0.
    assert lib.cost_ratio(dcop, -20.0, -10.0) == 2.0
    assert lib.cost_ratio(dcop, -5.0, -10.0) == 0.5
    assert lib.cost_ratio(dcop, 0.0, 4.0) == 2.0
    assert lib.cost_ratio(dcop, 4.0, 0.0) is None
    assert lib.mean_cost_ratio([(dcop, 0.0, 4.0), (dcop, 3.0, 4.0)]) == (
        1.625)
    assert lib.mean_cost_ratio([(dcop, 0.0, 4.0), (dcop, 4.0, 0.0)]) is None
    dcop.objective = "min"
    assert lib.cost_ratio(dcop, utility, utility * 1.5) == (
        pytest.approx(1 / 1.5))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_spoiled_max_answer_makes_correct_false(
        harness, benchmarks, capsys, monkeypatch, trace):
    """Every solve (the CLI's too) answers with every meeting unheld:
    a whole, honestly priced assignment of utility 0, below the
    optimum.  Only the comparison with the reference can see it; the
    traced run reads its cost ratio, 2, and still prints its line."""
    from pydcop_tpu import api

    honest = api.solve

    def unheld(dcop, *args, **kwargs):
        result = honest(dcop, *args, **kwargs)
        result["assignment"] = {name: 0 for name in dcop.variables}
        result["cost"], result["violations"] = dcop.solution_cost(
            result["assignment"])
        return result

    monkeypatch.setattr(api, "solve", unheld)
    assert run_cell(harness, benchmarks, "max_exact", MAX_CELL, trace) == 0
    line, notes = last_line(capsys)
    assert line["correct"] is False
    if trace:
        assert line["metrics"]["solve.cost_ratio"]["value"] == 2.0
    assert line["failed"] == line["attempted"] >= 1
    value, limit = line["compared"]["cost"]
    assert value == 0 and value > limit
    assert line["compared"]["cost_minus_host"] == [0.0, 0]
    faults = [n["fault"] for n in notes if "fault" in n]
    assert faults and all("utility 0.0 is worse than the reference's"
                          in f for f in faults)


# --------------------------------------------------------------------- #
# four shards on four chips


def test_a_four_chip_cell_solves_over_four_shards(
        harness, benchmarks, capsys, monkeypatch):
    from chipbench import lib

    said = []
    honest = lib.pydcop

    def pydcop(*args):
        said.append(args)
        return honest(*args)

    monkeypatch.setattr(lib, "pydcop", pydcop)
    assert run_cell(harness, benchmarks, "shards4", SHARDS_CELL) == 0
    line, notes = last_line(capsys, compared_of="solve")
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] >= 4
    window = next(n["window"] for n in notes if "window" in n)
    assert window["n_shards"] == SHARDS_CONFIG["shards"] == 4
    assert line["compared"]["cycles"] == [30, 30]
    (args,) = said
    at = args.index("--shards")
    assert args[at + 1] == "4" and "--n_devices" not in args


def _edited(benchmarks, tmp_path, chips=None, **changes):
    """The ``shards4`` copy with the configuration's keys changed
    (None drops one) and the cell's chips set."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.dirname(benchmarks["shards4"][0]), root)
    config_path = root / "chipbench" / "configs" / (
        f"{SHARDS_CONFIG['name']}.json")
    config = json.loads(config_path.read_text())
    for key, value in changes.items():
        if value is None:
            config.pop(key)
        else:
            config[key] = value
    config_path.write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if chips is not None:
        next(w for w in bench["workloads"]
             if w["name"] == SHARDS_CELL)["chips"] = chips
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root / "BENCHMARK.json")


@pytest.mark.parametrize("chips,changes,said", [
    (None, {"shards": 8},
     "configuration 'gc_small_shards4' states shards 8; cell "
     "'gc_small_shards4' has 4 chip(s)"),
    (2, {}, "states shards 4; cell 'gc_small_shards4' has 2 chip(s)"),
    (1, {"shards": None, "n_devices": 4}, "states n_devices 4; cell "
     "'gc_small_shards4' has 1 chip(s)"),
    (None, {"shards": None},
     "cell 'gc_small_shards4' takes 4 chips; configuration "
     "'gc_small_shards4' states neither shards nor n_devices"),
    (None, {"shards": 2},
     "configuration 'gc_small_shards4' states shards 2; cell "
     "'gc_small_shards4' takes 4 chips, so 2 would stay idle"),
    (None, {"shards": None, "n_devices": 1},
     "states n_devices 1; cell 'gc_small_shards4' takes 4 chips, so 3 "
     "would stay idle"),
    (None, {"n_devices": 4}, "shards and n_devices are exclusive"),
], ids=["shards_over_chips", "chips_under_shards", "n_devices_over_chips",
        "four_chips_one_device", "shards_under_chips",
        "n_devices_under_chips", "both"])
def test_a_configuration_that_does_not_fit_its_cells_chips_fails_first(
        harness, benchmarks, tmp_path, capsys, monkeypatch, chips, changes,
        said):
    def reached(chips):
        raise AssertionError("the run went on to the device")

    monkeypatch.setattr(harness, "device_info", reached)
    bench = _edited(benchmarks, tmp_path, chips, **changes)
    assert harness.main(["--workload", SHARDS_CELL, "--seed", "1",
                         "--seconds", "1", "--bench", bench]) == 1
    captured = capsys.readouterr()
    assert said in captured.err
    assert not any("correct" in x for x in captured.out.splitlines())


def test_a_configuration_that_names_no_reference_fails(
        harness, benchmarks, tmp_path, capsys):
    """There is no default reference, as there is no default family."""
    bench = _edited(benchmarks, tmp_path, reference=None)
    assert harness.main(["--workload", SHARDS_CELL, "--seed", "1",
                         "--seconds", "1", "--bench", bench]) == 1
    captured = capsys.readouterr()
    assert "states no `reference`" in captured.err
    assert os.path.join(CHIPBENCH, "references") in captured.err
    assert not any("correct" in x for x in captured.out.splitlines())
