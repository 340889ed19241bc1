"""The benchmarks that the "lists follow from the data" checks are held
against: the repository's own, and a copy of it to which a fourth cell
was added the way ``chipbench/README.md``'s table says a PR adds one.

The copy is ``BENCHMARK.json`` and ``chipbench/{configs,traffic,
metrics}`` in a temporary directory, plus ONLY a configuration file of
another family (SECP, ``cli_solve: false``), one metric file that reads
a program span, and the list entries that name them.  Nothing that came
from the repository is edited: ``test_rehearsal_fourth_cell.py``
compares the two, as the driver's ``benchmark_edited`` does.  No test
module is imported here, so every test module may import this one.
"""

import copy
import functools
import glob
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIPBENCH = os.path.join(REPO, "chipbench")
COPIED = ("configs", "traffic", "metrics")
WHICH = ("real", "fourth_cell")

CELL = "secp_small_maxsum"
# The 20 : 3 : 10 of docs/cli.md's `pydcop generate secp` at the least
# size whose counts, 5 standard deviations under the mean of the draws
# (families/secp.py `check`), are all above 0.  (The tiny SECP of
# test_rehearsal_secp.py states counts that seven seeds reach, not
# every seed: `check` refuses it, so it cannot be a configuration.)
CONFIG = {
    "name": "secp_small", "kind": "solve",
    "source": "docs/cli.md: pydcop generate secp --lights 20 --models 3 "
              "--rules 10, ten of them: --lights 200 --models 30 "
              "--rules 100 --max_model_size 3 --max_rule_size 3",
    "generator": {"family": "secp", "lights": 200, "models": 30,
                  "rules": 100, "max_model_size": 3, "max_rule_size": 3,
                  "factors_by_arity": {"1": 209, "2": 9, "3": 21, "4": 1}},
    "algo": "maxsum", "algo_params": {"stability": 0}, "max_cycles": 200,
    "ends": {"status": "TIMEOUT", "cycles": 200}, "cli_solve": False,
    # One broken model constraint costs 10 000, a sound answer 130-240
    # (sandbox, PR 37: seeds 1-5, 3000000001 and 4100000007 equal the
    # reference to the digit).
    "cost_tolerance": 0.5,
    "reduced": [],
    "assumed": {"factors_by_arity": "each count 5 sd under the mean of "
                                    "its draws, so every seed reaches it",
                "algo_params": "stability 0: pyDCOP's default 0.1 stops "
                               "MaxSum before its budget on SECP"},
    "guarantees": ["every solve returns an assignment over all variables "
                   "whose reported cost and violations equal "
                   "dcop.solution_cost on the host"],
    "why": "a second family beside the colourings: factors of arity 1 "
           "to 4 over one domain of 5, hard model constraints"}
CONFIG_ENTRY = {
    "name": CONFIG["name"], "source": CONFIG["source"],
    "file": f"chipbench/configs/{CONFIG['name']}.json",
    "reduced": CONFIG["reduced"], "why": CONFIG["why"]}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG["name"],
    "traffic": "one_caller_resolve", "chips": 1,
    "why": "230 vars, 240 factors of arity 1-4, MaxSum 200 cycles, one "
           "caller re-solving back to back; no CLI solve, no YAML"}
# A span metric as a later PR adds one: read by `spans`, not gated on
# a device trace, and listed for the new cell alone (fewer cells than
# the rule allows: gc10k_maxsum is of its kind and reports solve_p50_s).
METRIC = {
    "name": "engine.call_median_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "engine", "moves": "solve_p50_s",
    "kinds": ["solve"], "reader": "spans",
    "args": {"names": ["jit_compile", "engine_call"], "stat": "total",
             "reduce": "median", "scale": 0.001},
    "what": "median engine/runner.py timed_jit_call span of the traced "
            "solves"}
METRIC_ENTRY = dict(
    {key: METRIC[key] for key in ("name", "unit", "better", "source",
                                  "layer", "moves")},
    workloads=[CELL])
ADDED_FILES = {
    f"configs/{CONFIG['name']}.json": CONFIG,
    f"metrics/{METRIC['name']}.json": METRIC}


def _read(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(which):
    """The BENCHMARK.json of ``which``, loaded (the caller's own copy).
    The fourth cell's is the repository's with entries appended: the
    configuration, the cell, the cell under ``solve_p50_s`` (it makes
    no CLI solve) and under every per-layer metric of kind ``solve``
    that moves it, and the new metric."""
    return copy.deepcopy(_benchmark(which))


@functools.lru_cache(maxsize=None)
def _benchmark(which):
    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    if which == "real":
        return bench
    bench["configs"].append(CONFIG_ENTRY)
    bench["workloads"].append(CELL_ENTRY)
    for metric in bench["end_to_end"]:
        if metric["name"] == "solve_p50_s":
            metric["workloads"].append(CELL)
    for metric in bench["per_layer"]:
        spec = _read(os.path.join(CHIPBENCH, "metrics",
                                  f"{metric['name']}.json"))
        if metric["moves"] == "solve_p50_s" and "solve" in spec["kinds"]:
            metric["workloads"].append(CELL)
    bench["per_layer"].append(METRIC_ENTRY)
    return bench


def per_layer_names(which):
    return [m["name"] for m in benchmark(which)["per_layer"]]


def config_files(which):
    """The names of the files in the benchmark's ``configs/``."""
    names = [os.path.basename(p) for p in glob.glob(
        os.path.join(CHIPBENCH, "configs", "*.json"))]
    if which == "fourth_cell":
        names.append(f"{CONFIG['name']}.json")
    return sorted(names)


def solve_config_files(which):
    """Those of ``config_files`` of kind ``solve``; known before the
    copy is built, to parametrise over."""
    def kind(name):
        return (ADDED_FILES.get(f"configs/{name}") or _read(os.path.join(
            CHIPBENCH, "configs", name)))["kind"]

    return [name for name in config_files(which) if kind(name) == "solve"]


def per_benchmark(names_of):
    """``[(which, name)]`` over both benchmarks, to parametrise over."""
    return [(which, name) for which in WHICH for name in names_of(which)]


def build(root):
    """Write the fourth cell's benchmark under ``root``; returns
    ``(path of its BENCHMARK.json, its data directory)``."""
    data = os.path.join(root, "chipbench")
    for sub in COPIED:
        shutil.copytree(os.path.join(CHIPBENCH, sub),
                        os.path.join(data, sub))
    for relative, content in ADDED_FILES.items():
        with open(os.path.join(data, relative), "w",
                  encoding="utf-8") as f:
            json.dump(content, f, indent=1)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(benchmark("fourth_cell"), f, indent=1)
    return path, data
