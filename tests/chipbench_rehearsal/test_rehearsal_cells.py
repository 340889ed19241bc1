"""A solve cell that makes no CLI solve, the rule for a per-layer
metric's cells, and the configuration files, rehearsed on the CPU.

``test_rehearsal.py``'s tiny solve cell makes the timed ``pydcop
solve`` in set-up, as ``gc10k_maxsum`` does.  A configuration with
``cli_solve: false`` does not: its cell reports ``setup_s`` and
``solve_p50_s`` alone, loads no YAML and so reads no ``yaml.*``
metric.  The first half drives such a cell, sound and broken, through
``run.main``; the second half holds ``BENCHMARK.json``'s lists and
every file in ``chipbench/configs/`` to what the README says they
are.  No test here names a real cell, a real
configuration or a count of either: each expectation is read from the
data, so a later PR's cell, metric, configuration or family is held to
the same rules without an edit here.  That is tried: every such check
runs on the repository's benchmark and on a copy of it to which a
fourth cell was added as entries and new files only
(``rehearsal_benchmarks.py``).
"""

import json
import os
import shutil

import pytest
from rehearsal_benchmarks import (
    WHICH,
    benchmark,
    config_files,
    per_benchmark,
    per_layer_names,
    solve_config_files,
)
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    CHIPBENCH,
    LAST_LINE_KEYS,
    TINY_CONFIGS,
    TINY_TRAFFIC,
    harness,
    last_line,
    write_json,
)

CELL = "tiny_solve_nocli.resolve"
NOCLI = dict(
    TINY_CONFIGS["tiny_solve"], name="tiny_solve_nocli",
    generator={"family": "graph_coloring", "variables": 60, "colors": 3,
               "graph": "random", "p_edge": 0.05, "constraints": 90},
    cli_solve=False)
# What chipbench/README.md's "a configuration" row names.
README_KEYS = {"name", "kind", "source", "reduced", "assumed",
               "guarantees", "cost_tolerance", "why"}
RUNNER_KEYS = {
    "solve": {"generator", "algo", "algo_params", "max_cycles", "ends",
              "cli_solve"},
    "serve": {"generator", "pool", "params", "service"},
}


def _config(filename, data=CHIPBENCH):
    with open(os.path.join(data, "configs", filename),
              encoding="utf-8") as f:
        return json.load(f)


def _config_of(bench, data=CHIPBENCH):
    """Each cell's configuration file, by the cell's name."""
    files = {c["name"]: os.path.basename(c["file"])
             for c in bench["configs"]}
    return {w["name"]: _config(files[w["config"]], data)
            for w in bench["workloads"]}


def cells_the_rule_gives(bench, moves, kinds, data=CHIPBENCH):
    """chipbench/README.md, "a cell": the cells whose configuration is
    of one of ``kinds`` and which report the end-to-end metric
    ``moves`` (every cell, where that metric lists none)."""
    config_of = _config_of(bench, data)
    moved = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return [w["name"] for w in bench["workloads"]
            if config_of[w["name"]]["kind"] in kinds
            and w["name"] in moved.get("workloads", [w["name"]])]


def _write_bench(tmp_path, cli_solve_cells, configs=None):
    """A BENCHMARK.json of the tiny solve cell with a CLI solve and
    the one without (or of ``configs``), beside a copy of
    ``chipbench/metrics``; the real file's per-layer lists, with every
    cell in each solve metric but those that move ``cli_solve_s``,
    which only the cells that make the CLI solve are in."""
    data = tmp_path / "data"
    shutil.copytree(os.path.join(CHIPBENCH, "metrics"), data / "metrics")
    configs = configs or {"tiny_solve": TINY_CONFIGS["tiny_solve"],
                          "tiny_solve_nocli": NOCLI}
    for name, config in configs.items():
        write_json(str(data / "configs" / f"{name}.json"), config)
    write_json(str(data / "traffic" / "resolve.json"),
               TINY_TRAFFIC["resolve"])
    real = benchmark("real")
    both = [f"{n}.resolve" for n in configs]
    bench_path = tmp_path / "BENCHMARK.json"
    write_json(str(bench_path), {
        "configs": [{"name": n, "file": f"data/configs/{n}.json"}
                    for n in configs],
        "workloads": [{"name": f"{n}.resolve", "config": n,
                       "traffic": "resolve", "chips": 1}
                      for n in configs],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "cli_solve_s", "unit": "s",
             "workloads": cli_solve_cells},
            {"name": "solve_p50_s", "unit": "s", "workloads": both}],
        "per_layer": [
            {"name": m["name"],
             "workloads": (cli_solve_cells
                           if m["moves"] == "cli_solve_s" else both)}
            for m in real["per_layer"] if m["moves"] in (
                "cli_solve_s", "solve_p50_s")],
    })
    return str(bench_path)


@pytest.fixture
def bench(tmp_path):
    return _write_bench(tmp_path, ["tiny_solve.resolve"])


def run_nocli(run, bench, trace, seed=3000000001):
    return run.main(["--workload", CELL, "--seed", str(seed),
                     "--seconds", "1", "--trace", str(trace),
                     "--bench", bench])


# --------------------------------------------------------------------- #
# a solve cell without the CLI solve, end to end


def test_untraced_it_prints_setup_and_solve_p50_alone(
        harness, bench, capsys):
    assert run_nocli(harness, bench, 0) == 0
    line, notes = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "solve_p50_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0
    setup = next(n["setup"] for n in notes if "setup" in n)
    assert setup["cli_solve_s"] is None
    assert (setup["variables"], setup["constraints"]) == (60, 90)
    # Every answer is the window's: no "pydcop solve" among them.
    window = next(n["window"] for n in notes if "window" in n)
    assert line["attempted"] == window["solves"]


def test_traced_it_prints_no_yaml_metric_and_is_correct(
        harness, bench, capsys):
    assert run_nocli(harness, bench, 1) == 0
    line, _ = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    # What the cell with the CLI solve prints on a CPU, less the load.
    assert set(line["metrics"]) == {
        "hostcompile.ms", "engine.ms", "entry.self_ms",
        "xla.compiles.solve", "solve.cost_ratio"}
    assert not any(name.startswith("yaml.") for name in line["metrics"])
    names = {name for name, _ in line["breakdown"]["idle_gaps"]}
    assert "host:yaml_parse" not in names


def test_listed_under_cli_solve_s_it_fails_and_prints_no_result(
        harness, tmp_path, capsys):
    bench = _write_bench(tmp_path, ["tiny_solve.resolve", CELL])
    assert run_nocli(harness, bench, 0) == 1
    captured = capsys.readouterr()
    assert f"cell {CELL!r} reports no cli_solve_s" in captured.err
    assert not any("correct" in x for x in captured.out.splitlines())


# --------------------------------------------------------------------- #
# the timed path broken underneath: `correct` comes out false


def _altered_answer(fault):
    """``api.solve`` with the answer altered where it is produced; the
    rest of the run is the harness's own."""
    from pydcop_tpu import api

    honest = api.solve

    def solve(dcop, *args, **kwargs):
        result = honest(dcop, *args, **kwargs)
        if fault == "one_value_altered":
            # One variable takes its neighbour's colour after the cost
            # was summed: the assignment changes, the reported cost
            # does not.
            first = next(iter(dcop.constraints.values()))
            a, b = (v.name for v in first.dimensions)
            assert result["assignment"][a] != result["assignment"][b]
            result["assignment"][b] = result["assignment"][a]
        else:
            assert fault == "a_variable_left_out"
            del result["assignment"][max(result["assignment"])]
        return result

    return solve


@pytest.mark.parametrize("fault,said", [
    ("one_value_altered", "reported cost"),
    ("a_variable_left_out", "covers 59/60"),
])
def test_an_altered_answer_makes_correct_false(
        harness, bench, capsys, monkeypatch, fault, said):
    from pydcop_tpu import api

    monkeypatch.setattr(api, "solve", _altered_answer(fault))
    assert run_nocli(harness, bench, 0) == 0
    line, notes = last_line(capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert any(said in n.get("fault", "") for n in notes)


def test_a_superstep_that_leaves_its_state_unchanged_makes_correct_false(
        harness, bench, capsys, monkeypatch):
    """The fault planted in the program's superstep, in both layouts:
    it counts its cycle and sends what was sent before, so the solver
    spends its budget, decodes the messages it began with and reports
    that colouring's true cost.  Only the comparison with the plain
    reference can see it."""
    from pydcop_tpu.ops import maxsum, maxsum_lane

    planted = []

    def unchanged(honest):
        def superstep(state, graph, **kwargs):
            planted.append(honest.__module__)
            return state._replace(
                cycle=honest(state, graph, **kwargs).cycle)
        return superstep

    for module in (maxsum, maxsum_lane):
        monkeypatch.setattr(module, "superstep",
                            unchanged(module.superstep))
    assert run_nocli(harness, bench, 0) == 0
    assert planted
    line, notes = last_line(capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    faults = [n["fault"] for n in notes if "fault" in n]
    assert faults and all("worse than the reference" in f for f in faults)
    # The exact checks had nothing to say: the answer is whole and
    # honestly costed.
    assert not any("reported" in f or "covers" in f for f in faults)
    window = next(n["window"] for n in notes if "window" in n)
    assert window["cycles"] == NOCLI["max_cycles"]


# --------------------------------------------------------------------- #
# BENCHMARK.json's lists follow from the data


@pytest.mark.parametrize("which", WHICH)
def test_the_cells_of_cli_solve_s_are_those_whose_configuration_says_so(
        benchmarks, which):
    bench = benchmark(which)
    config_of = _config_of(bench, benchmarks[which][1])
    cli = next(m for m in bench["end_to_end"]
               if m["name"] == "cli_solve_s")
    assert set(cli["workloads"]) == {
        name for name, config in config_of.items()
        if config["kind"] == "solve" and config["cli_solve"]}
    # Every solve cell reports the warm solve, whatever else it does.
    p50 = next(m for m in bench["end_to_end"]
               if m["name"] == "solve_p50_s")
    assert set(p50["workloads"]) == {
        name for name, config in config_of.items()
        if config["kind"] == "solve"}


@pytest.mark.parametrize("which,name", per_benchmark(per_layer_names))
def test_a_per_layer_metric_lists_only_cells_the_rule_allows(
        benchmarks, which, name):
    """chipbench/README.md, "a cell": a per-layer metric lists only
    cells of its kind that report the end-to-end metric it moves.  It
    may list fewer (a metric that finds something to read in some of
    them only), never another.  No test asks for all of them."""
    bench, data = benchmark(which), benchmarks[which][1]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    with open(os.path.join(data, "metrics", f"{name}.json"),
              encoding="utf-8") as f:
        kinds = json.load(f)["kinds"]
    allowed = cells_the_rule_gives(bench, entry["moves"], kinds, data)
    assert entry["workloads"], name
    assert set(entry["workloads"]) <= set(allowed)
    assert len(set(entry["workloads"])) == len(entry["workloads"])


@pytest.mark.parametrize("which", WHICH)
def test_every_cell_is_in_some_per_layer_metric_of_each_metric_it_reports(
        which):
    """A cell that no per-layer metric follows for an end-to-end metric
    (``setup_s`` apart) would move unexplained."""
    bench = benchmark(which)
    for cell in bench["workloads"]:
        for metric in bench["end_to_end"]:
            if metric["name"] == "setup_s" or cell["name"] not in \
                    metric.get("workloads", [cell["name"]]):
                continue
            assert any(cell["name"] in m["workloads"]
                       and m["moves"] == metric["name"]
                       for m in bench["per_layer"]), (
                cell["name"], metric["name"])


# --------------------------------------------------------------------- #
# every configuration file is what the README says one is


@pytest.mark.parametrize("which", WHICH)
def test_there_is_a_file_for_each_configuration_and_no_other(
        benchmarks, which):
    listed = {os.path.basename(c["file"])
              for c in benchmark(which)["configs"]}
    assert listed == set(os.listdir(os.path.join(benchmarks[which][1],
                                                 "configs")))
    assert listed == set(config_files(which))


@pytest.mark.parametrize("which,filename", per_benchmark(config_files))
def test_a_configuration_file_has_the_keys_the_readme_names(
        benchmarks, which, filename):
    config = _config(filename, benchmarks[which][1])
    assert README_KEYS <= set(config), README_KEYS - set(config)
    assert config["kind"] in RUNNER_KEYS
    missing = RUNNER_KEYS[config["kind"]] - set(config)
    assert not missing, missing
    assert os.path.isfile(os.path.join(
        CHIPBENCH, "runners", f"{config['kind']}.py"))
    assert isinstance(config["assumed"], dict) and config["assumed"]
    assert isinstance(config["guarantees"], list) and config["guarantees"]
    assert config["cost_tolerance"] > 0
    assert config["why"].strip()


@pytest.mark.parametrize("which,filename", per_benchmark(config_files))
def test_a_configuration_file_agrees_with_its_benchmark_entry(
        benchmarks, which, filename):
    config = _config(filename, benchmarks[which][1])
    assert config["name"] + ".json" == filename
    entry = next(c for c in benchmark(which)["configs"]
                 if c["name"] == config["name"])
    assert entry["file"] == f"chipbench/configs/{filename}"
    assert config["source"] == entry["source"]
    assert 1 <= len(config["source"]) <= 200
    assert "\n" not in config["source"] and "\t" not in config["source"]
    assert config["reduced"] == entry["reduced"]


@pytest.mark.parametrize("which", WHICH)
def test_no_two_configurations_share_a_source(benchmarks, which):
    sources = [_config(f, benchmarks[which][1])["source"]
               for f in config_files(which)]
    assert len(set(sources)) == len(sources)


@pytest.mark.parametrize("which,filename",
                         per_benchmark(solve_config_files))
def test_a_solve_configuration_fixes_the_count_its_density_gives(
        benchmarks, which, filename):
    """The counts the file fixes follow from the parameters its
    ``source`` names, by its family's own rule, and every seed's
    instance has the shapes the family states (tried on the same
    family at a size a test can hold)."""
    from chipbench import lib

    config = _config(filename, benchmarks[which][1])
    assert config["kind"] == "solve"
    generator = config["generator"]
    family = lib.family_of(generator)
    family.check(generator)
    small = family.small(generator)
    assert sum(family.shapes(small)["factors_by_arity"].values()) <= 1500
    assert small["family"] == generator["family"]
    family.check(small)
    assert lib.shapes(lib.generate(small, 4100000007)) == family.shapes(small)
