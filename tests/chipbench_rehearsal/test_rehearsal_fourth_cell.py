"""``chipbench/README.md``'s promise, "nothing here is edited to add a
cell", tried by adding one.

``rehearsal_benchmarks.py`` builds a copy of the repository's
``BENCHMARK.json`` and data directories and adds what the README's
table says a PR adds, and nothing else: a configuration of another
family that makes no CLI solve, a cell of it under a mix that is there
already, a metric file that reads a program span, and the list
entries.  The checks of the lists run on that copy from
``test_rehearsal.py``, ``test_rehearsal_cells.py``,
``test_rehearsal_families.py`` and ``test_rehearsal_tracing.py``
(their ``which`` cases).  Here the new cell goes through ``run.main``,
untraced and traced, and the copy is compared with the repository the
way the driver's ``benchmark_edited`` compares a PR with its parent: no
file that was there differs, no value of ``BENCHMARK.json`` that was
there is changed, moved or gone.
"""

import copy
import filecmp
import json
import os
import shutil

import pytest
from rehearsal_benchmarks import (
    ADDED_FILES,
    CELL,
    CHIPBENCH,
    CONFIG,
    COPIED,
    METRIC,
    benchmark,
)
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    LAST_LINE_KEYS,
    harness,
    last_line,
    unread,
)
from test_rehearsal_cells import cells_the_rule_gives
from test_rehearsal_secp import SECP

SEED = 3000000001


def listed_for_the_cell(source):
    """The per-layer metrics the fourth-cell benchmark lists for the
    new cell whose ``source`` is (``True``) or is not the device's
    trace."""
    return {m["name"] for m in benchmark("fourth_cell")["per_layer"]
            if CELL in m["workloads"]
            and (m["source"] == "device_trace") == source}


def run_fourth(run, benchmarks, trace):
    return run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                     "1", "--trace", str(trace), "--bench",
                     benchmarks["fourth_cell"][0]])


# --------------------------------------------------------------------- #
# the new cell, through run.main


def test_the_addition_is_what_the_readme_says_a_pr_adds():
    bench = benchmark("fourth_cell")
    # Another family than any cell has, and no CLI solve.
    assert CONFIG["generator"]["family"] == SECP["generator"]["family"]
    assert CONFIG["algo_params"] == SECP["algo_params"]
    assert CONFIG["cli_solve"] is False
    # The mix was there already.
    assert os.path.isfile(os.path.join(
        CHIPBENCH, "traffic",
        f"{bench['workloads'][-1]['traffic']}.json"))
    # The new metric lists fewer cells than the one rule allows, and
    # no test minds.
    allowed = cells_the_rule_gives(benchmark("real"), METRIC["moves"],
                                   METRIC["kinds"])
    assert allowed and CELL not in allowed
    assert bench["per_layer"][-1]["workloads"] == [CELL]
    assert METRIC["reader"] == "spans" and METRIC["source"] == "program_span"


def test_untraced_the_fourth_cell_reports_setup_and_solve_p50(
        harness, benchmarks, capsys):
    assert run_fourth(harness, benchmarks, 0) == 0
    line, notes = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "solve_p50_s"}
    setup = next(n["setup"] for n in notes if "setup" in n)
    assert setup["cli_solve_s"] is None
    assert (setup["variables"], setup["constraints"]) == (230, 240)
    cost, limit = line["compared"]["cost"]
    assert cost == pytest.approx(setup["reference_cost"], abs=1e-6)
    assert limit < setup["reference_cost"] + 10000
    assert line["compared"]["cycles"] == [200, 200]
    # An untraced run reads no per-layer metric and notes none unread.
    assert not any("listed_and_read_nothing" in n for n in notes)


def test_traced_on_a_cpu_it_prints_what_spans_and_counters_give(
        harness, benchmarks, capsys):
    assert run_fourth(harness, benchmarks, 1) == 0
    line, notes = last_line(capsys)
    assert set(line) == LAST_LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    # The new metric needs no gate: test_rehearsal.py's benchmark does
    # not list it, so it may read on any traced run.
    assert set(line["metrics"]) == listed_for_the_cell(False)
    assert METRIC["name"] in line["metrics"]
    assert line["metrics"][METRIC["name"]]["value"] > 0
    assert not any(name.startswith("yaml.") for name in line["metrics"])
    # The run names what it listed and could not read: off the chip,
    # the device's metrics and no other.
    assert set(unread(notes)) == listed_for_the_cell(True)
    assert listed_for_the_cell(True) >= {
        "kernel.superstep_us", "maxsum_superstep_roofline",
        "device.idle.solve", "engine.dispatch_ms"}


def test_with_a_stand_in_device_trace_it_prints_no_metric_not_listed(
        harness, benchmarks, capsys, monkeypatch, tmp_path):
    """test_rehearsal_tracing.py's stand-in opens the gate of PR 26's
    span and ring metrics.  The files of the metrics read from the
    device's trace itself are left out of what is evaluated: a
    stand-in has no busy time to give them."""
    from pydcop_tpu.dcop.yamldcop import load_dcop_from_file
    from test_rehearsal_tracing import NEW

    data = benchmarks["fourth_cell"][1]
    host_only = tmp_path / "metrics"
    host_only.mkdir()
    for name in os.listdir(os.path.join(data, "metrics")):
        with open(os.path.join(data, "metrics", name),
                  encoding="utf-8") as f:
            spec = json.load(f)
        if spec["source"] != "device_trace":
            (host_only / name).write_text(json.dumps(spec))
    real = harness.per_layer_metrics
    found = {}

    def with_stand_in(data_dir, kind, capture):
        capture.setdefault("device_trace", {"busy_s": 0.0, "ops": []})
        found.update(real(str(tmp_path), kind, capture))
        return found

    monkeypatch.setattr(harness, "per_layer_metrics", with_stand_in)
    # The flight ring holds a YAML load, as it does on the chip after
    # gc10k_maxsum's: the ring metrics find it, and this cell, which
    # does not report cli_solve_s, does not list them.
    load_dcop_from_file(os.path.join(
        os.path.dirname(CHIPBENCH), "tests", "instances",
        "coloring_12_3.yml"))
    assert run_fourth(harness, benchmarks, 1) == 0
    line, notes = last_line(capsys)
    assert line["correct"] is True
    assert NEW["solve"]["cpu"] <= set(found)
    assert set(line["metrics"]) == listed_for_the_cell(False)
    assert not NEW["solve"]["cpu"] & set(line["metrics"])
    assert set(unread(notes)) == listed_for_the_cell(True)


def test_every_reader_of_the_copy_reads_nothing_from_nothing(benchmarks):
    from chipbench import run

    data = benchmarks["fourth_cell"][1]
    assert run.per_layer_metrics(data, "solve", {}) == {}
    assert run.per_layer_metrics(data, "serve", {}) == {}


# --------------------------------------------------------------------- #
# entries and new files only: the driver's `benchmark_edited`, here


def files_under(directory):
    return {os.path.relpath(os.path.join(root, name), directory)
            for root, _, names in os.walk(directory) for name in names}


def altered_files(repo_data, copy_data):
    """Files of the repository's data directories that the copy has
    not, or has with other bytes."""
    out = []
    for sub in COPIED:
        names = sorted(files_under(os.path.join(repo_data, sub)))
        _, differing, missing = filecmp.cmpfiles(
            os.path.join(repo_data, sub), os.path.join(copy_data, sub),
            names, shallow=False)
        out += [f"{sub}/{name}" for name in differing + missing]
    return out


def altered_values(old, new, where="BENCHMARK.json"):
    """Where ``new`` changes, moves or drops a value of ``old``: a
    dict keeps its keys and gains none, a list keeps its items in
    their places and may gain more at its end, anything else is
    equal."""
    if isinstance(old, dict) and isinstance(new, dict):
        out = [f"{where}: key {key!r} added or gone"
               for key in sorted(set(old) ^ set(new))]
        for key in old.keys() & new.keys():
            out += altered_values(old[key], new[key], f"{where}.{key}")
        return out
    if isinstance(old, list) and isinstance(new, list):
        out = ([f"{where}: {len(old) - len(new)} item(s) gone"]
               if len(new) < len(old) else [])
        for i, (a, b) in enumerate(zip(old, new)):
            out += altered_values(a, b, f"{where}[{i}]")
        return out
    return [] if old == new and type(old) is type(new) else [
        f"{where}: {old!r} became {new!r}"]


def test_the_copy_alters_no_file_and_no_value_that_was_there(benchmarks):
    """After the runs above went through it."""
    path, data = benchmarks["fourth_cell"]
    assert altered_files(CHIPBENCH, data) == []
    added = set()
    for sub in COPIED:
        added |= {f"{sub}/{name}" for name in
                  files_under(os.path.join(data, sub))
                  - files_under(os.path.join(CHIPBENCH, sub))}
    assert added == set(ADDED_FILES)
    assert set(os.listdir(os.path.dirname(path))) == {
        "BENCHMARK.json", "chipbench"}
    with open(path, encoding="utf-8") as f:
        written = json.load(f)
    assert written == benchmark("fourth_cell")
    assert altered_values(benchmark("real"), written) == []
    # Something was added, in every list a new cell touches.
    real = benchmark("real")
    for section in ("configs", "workloads", "per_layer"):
        assert len(written[section]) == len(real[section]) + 1


def _edit(bench, path, value=None, drop=False):
    """``bench`` with the value at ``path`` replaced or dropped."""
    node = bench
    for key in path[:-1]:
        node = node[key]
    if drop:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return bench


@pytest.mark.parametrize("edit,said", [
    (lambda b: _edit(b, ["end_to_end", 0, "bound"], 0.5),
     "end_to_end[0].bound: 0.25 became 0.5"),
    (lambda b: _edit(b, ["run_seconds"], 30.0), "run_seconds: 30 became"),
    (lambda b: _edit(b, ["per_layer", 3], drop=True), "item(s) gone"),
    (lambda b: _edit(b, ["workloads", 0, "why"], drop=True),
     "workloads[0]: key 'why' added or gone"),
    (lambda b: _edit(b, ["workloads", 0, "note"], "x"),
     "workloads[0]: key 'note' added or gone"),
    (lambda b: _edit(b, ["end_to_end", 3, "workloads"],
                     ["serve_grid100_c1", "serve_grid100_c8"]),
     "end_to_end[3].workloads[0]: 'serve_grid100_c8' became"),
    (lambda b: b["per_layer"].insert(0, dict(b["per_layer"][-1])) or b,
     "per_layer[0].name: "),
], ids=["bound", "type", "entry_gone", "key_gone", "key_added",
        "list_reordered", "entry_put_first"])
def test_an_edit_of_what_was_there_is_seen(edit, said):
    """The comparison fails where it should: each of these is what
    ``benchmark_edited`` refuses a PR for."""
    real = benchmark("real")
    assert altered_values(real, copy.deepcopy(real)) == []
    found = altered_values(real, edit(benchmark("fourth_cell")))
    assert any(said in line for line in found), found


def test_a_changed_or_missing_file_is_seen(benchmarks, tmp_path):
    data = str(tmp_path / "chipbench")
    shutil.copytree(benchmarks["fourth_cell"][1], data)
    assert altered_files(CHIPBENCH, data) == []
    mix = os.path.join(data, "traffic", "one_caller_resolve.json")
    with open(mix, encoding="utf-8") as f:
        changed = dict(json.load(f), traced_solves=2)
    with open(mix, "w", encoding="utf-8") as f:
        json.dump(changed, f)
    os.remove(os.path.join(data, "metrics", "engine.ms.json"))
    assert altered_files(CHIPBENCH, data) == [
        "traffic/one_caller_resolve.json", "metrics/engine.ms.json"]
