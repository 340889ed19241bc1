"""The benchmarks that the "lists follow from the data" checks are held
against: the repository's own, and copies of it to which a cell was
added the way ``chipbench/README.md``'s table says a PR adds one.

A copy is ``BENCHMARK.json`` and ``chipbench/{configs,traffic,
metrics}`` in a temporary directory, plus ONLY the files its cell
brings and the list entries that name them:

- ``fourth_cell``: a configuration of another family (SECP,
  ``cli_solve: false``) and one metric file that reads a program span;
- ``max_exact``: a configuration of a max-objective deployment (a
  PEAV meeting day) solved by DPOP and judged by the plain ``exact``
  reference, and its family, a file that ``chipbench/families/`` does
  not have;
- ``shards4``: a colouring whose solves run partitioned over four
  devices (``shards: 4``), in a cell of four chips.

Nothing that came from the repository is edited:
``test_rehearsal_fourth_cell.py`` and ``test_rehearsal_room.py``
compare each copy with it, as the driver's ``benchmark_edited`` does.
No test module is imported here, so every test module may import this
one.
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
    "reference": "min_sum", "cost_tolerance": 0.5,
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

# A max-objective deployment checked against an exact reference.  DPOP
# is exact, so the tolerance covers the order of a float sum alone:
# PEAV's (u1 + u2) / (n - 1) entries are not dyadic, and two optimal
# assignments, or two sums of one, can differ in the last bits
# (sandbox: DPOP and `exact` 1.4e-14 apart on seed 4100000007, equal
# on 1, 2, 3, 17, 23 and 3000000001).
MAX_CELL = "meetings_small_dpop"
MAX_CONFIG = {
    "name": "meetings_small", "kind": "solve",
    "source": "pydcop generate meetings --slots_count 4 --events_count 6 "
              "--resources_count 5 --max_resources_event 3 (PEAV model, "
              "objective max)",
    "generator": {"family": "meetings_peav", "slots": 4, "events": 6,
                  "resources": 5, "max_resources_event": 3,
                  "variables": 12, "factors_by_arity": {"1": 1, "2": 19}},
    "algo": "dpop", "algo_params": {}, "max_cycles": 100, "ends": None,
    "cli_solve": True, "reference": "exact", "cost_tolerance": 1e-9,
    "reduced": [],
    "assumed": {
        "factors_by_arity": "12 variables, 1 unary and 19 binary "
                            "factors: the counts 4% of the draws have; "
                            "each seed's first draw that has them",
        "ends": "null: DPOP ends FINISHED after a number of steps that "
                "follows the pseudo-tree, which differs by seed"},
    "guarantees": ["every solve returns an optimal assignment over all "
                   "variables whose reported utility and violations "
                   "equal dcop.solution_cost on the host"],
    "cost_gap": "float summation order only, never a heuristic's slack",
    "why": "a max-objective deployment, an exact algorithm and an exact "
           "reference: UTIL and VALUE sweeps over one day's meetings"}
MAX_FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "added_families", "meetings_peav.py")

# A solve that runs partitioned over four devices.
SHARDS_CELL = "gc_small_shards4"
SHARDS_CONFIG = {
    "name": "gc_small_shards4", "kind": "solve",
    "source": "pydcop generate graph_coloring -v 60 -c 3 -g random "
              "-p 0.05, solved by pydcop solve -a maxsum --shards 4",
    "generator": {"family": "graph_coloring", "variables": 60,
                  "colors": 3, "graph": "random", "p_edge": 0.05,
                  "soft": False, "constraints": 89},
    "algo": "maxsum", "algo_params": {}, "max_cycles": 30,
    "ends": {"status": "TIMEOUT", "cycles": 30}, "cli_solve": True,
    "shards": 4, "reference": "min_sum", "cost_tolerance": 3.0,
    "reduced": [],
    "assumed": {"constraints": "89, the mean -p 0.05 gives over 60 "
                               "variables (88.5)",
                "shards": "4: the partitioned engine, one shard a chip"},
    "guarantees": ["every solve returns an assignment over all variables "
                   "whose reported cost and violations equal "
                   "dcop.solution_cost on the host"],
    "why": "the partitioned engine: a min-edge-cut partition over four "
           "chips and the halo exchanged every superstep"}


def _entries(config, cell, chips, why):
    """The ``configs`` and ``workloads`` entries of a configuration and
    its one cell under the mix ``one_caller_resolve``."""
    return ({"name": config["name"], "source": config["source"],
             "file": f"chipbench/configs/{config['name']}.json",
             "reduced": config["reduced"], "why": config["why"]},
            {"name": cell, "config": config["name"],
             "traffic": "one_caller_resolve", "chips": chips, "why": why})


# What each copy adds: its files (a dict is written as JSON, a path is
# copied), its configuration and cell, the end-to-end metrics the cell
# reports, the per-layer metrics that list it (None: every one of kind
# solve that moves solve_p50_s), and a new per-layer metric or None.
ADDITIONS = {
    "fourth_cell": {
        "files": ADDED_FILES, "entries": (CONFIG_ENTRY, CELL_ENTRY),
        "reports": ["solve_p50_s"], "per_layer": None,
        "metric": METRIC_ENTRY},
    "max_exact": {
        "files": {f"configs/{MAX_CONFIG['name']}.json": MAX_CONFIG,
                  "families/meetings_peav.py": MAX_FAMILY},
        "entries": _entries(
            MAX_CONFIG, MAX_CELL, 1,
            "12 PEAV variables of 5 slots, DPOP, one caller re-solving "
            "back to back after one pydcop solve: exact algorithm on a "
            "max objective"),
        "reports": ["cli_solve_s", "solve_p50_s"],
        "per_layer": ["yaml.load_s", "solve.cost_ratio",
                      "xla.compiles.solve"],
        "metric": None},
    "shards4": {
        "files": {f"configs/{SHARDS_CONFIG['name']}.json": SHARDS_CONFIG},
        "entries": _entries(
            SHARDS_CONFIG, SHARDS_CELL, 4,
            "60 vars, 89 hard binary factors, MaxSum 30 cycles over four "
            "shards, one caller re-solving: partition and halo exchange"),
        "reports": ["cli_solve_s", "solve_p50_s"],
        "per_layer": ["yaml.load_s", "solve.cost_ratio",
                      "xla.compiles.solve"],
        "metric": None},
}
WHICH = ("real",) + tuple(ADDITIONS)


def _read(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(which):
    """The BENCHMARK.json of ``which``, loaded (the caller's own copy).
    A copy's is the repository's with entries appended: the
    configuration, the cell, the cell under each end-to-end metric it
    reports and under the per-layer metrics that follow it (the fourth
    cell: every one of kind ``solve`` that moves ``solve_p50_s``; it
    makes no CLI solve), and the new metric where there is one."""
    return copy.deepcopy(_benchmark(which))


@functools.lru_cache(maxsize=None)
def _benchmark(which):
    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    if which == "real":
        return bench
    added = ADDITIONS[which]
    config_entry, cell_entry = added["entries"]
    cell = cell_entry["name"]
    bench["configs"].append(config_entry)
    bench["workloads"].append(cell_entry)
    for metric in bench["end_to_end"]:
        if metric["name"] in added["reports"]:
            metric["workloads"].append(cell)
    for metric in bench["per_layer"]:
        spec = _read(os.path.join(CHIPBENCH, "metrics",
                                  f"{metric['name']}.json"))
        if (metric["name"] in added["per_layer"]
                if added["per_layer"] is not None else
                metric["moves"] == "solve_p50_s" and "solve" in spec["kinds"]):
            metric["workloads"].append(cell)
    if added["metric"] is not None:
        bench["per_layer"].append(added["metric"])
    return bench


def per_layer_names(which):
    return [m["name"] for m in benchmark(which)["per_layer"]]


def added_files(which):
    """``{path under the copy's chipbench/: content}`` of ``which``."""
    return {} if which == "real" else ADDITIONS[which]["files"]


def config_files(which):
    """The names of the files in the benchmark's ``configs/``."""
    names = [os.path.basename(p) for p in glob.glob(
        os.path.join(CHIPBENCH, "configs", "*.json"))]
    names += [os.path.basename(relative) for relative in added_files(which)
              if relative.startswith("configs/")]
    return sorted(names)


def solve_config_files(which):
    """Those of ``config_files`` of kind ``solve``; known before the
    copy is built, to parametrise over."""
    def kind(name):
        return (added_files(which).get(f"configs/{name}") or _read(
            os.path.join(CHIPBENCH, "configs", name)))["kind"]

    return [name for name in config_files(which) if kind(name) == "solve"]


def per_benchmark(names_of):
    """``[(which, name)]`` over every benchmark, to parametrise over."""
    return [(which, name) for which in WHICH for name in names_of(which)]


def build(root, which):
    """Write the benchmark of ``which`` under ``root``; returns
    ``(path of its BENCHMARK.json, its data directory)``."""
    data = os.path.join(root, "chipbench")
    for sub in COPIED:
        shutil.copytree(os.path.join(CHIPBENCH, sub),
                        os.path.join(data, sub))
    for relative, content in added_files(which).items():
        path = os.path.join(data, relative)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if isinstance(content, dict):
            with open(path, "w", encoding="utf-8") as f:
                json.dump(content, f, indent=1)
        else:
            shutil.copyfile(content, path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(benchmark(which), f, indent=1)
    return path, data
