"""Kind ``solve``: one caller that solves one large problem again and
again through ``api.solve``, after one timed ``pydcop solve`` of the
same problem from its YAML file.

Configuration keys: ``generator`` (its ``family`` names a module of
``chipbench/families/``; the rest are that family's arguments),
``algo``, ``algo_params`` (a dict, given to ``api.solve`` and as one
``-p name:value`` each to ``pydcop solve``), ``max_cycles``, ``ends``
(``{"status", "cycles"}``: how every solve has to end, or null),
``cli_solve`` (whether set-up makes the timed CLI solve),
``reference`` (a module of ``chipbench/references/``),
``cost_tolerance``, and optionally one of ``shards`` and ``n_devices``
(where the solves run: given to ``api.solve`` and as ``--shards N`` /
``--n_devices N`` to ``pydcop solve``; ``run.py`` holds them to the
cell's chips).  Traffic keys: ``warmup_solves``, ``traced_solves``.
"""

import json
import os
import statistics
import time

from chipbench import lib


def cli_solve(cell, path):
    """One ``pydcop solve`` of the YAML file, in this process, timed
    from the command to its result file: what the CLI's user waits
    for once the compiled program is in the cache."""
    result_path = os.path.join(cell.workdir, "result.json")
    t0 = time.perf_counter()
    params = [arg for name, value in cell.config["algo_params"].items()
              for arg in ("-p", f"{name}:{value}")]
    params += [arg for key, value in lib.placement(cell.config).items()
               for arg in (f"--{key}", str(value))]
    lib.pydcop("--output", result_path, "solve", "-a",
               cell.config["algo"], *params, "-c",
               str(cell.config["max_cycles"]), path)
    wall = time.perf_counter() - t0
    with open(result_path, encoding="utf-8") as f:
        result = json.load(f)
    if result["platform"] != cell.device["platform"]:
        raise lib.BenchFailure(
            f"pydcop solve ran on {result['platform']}")
    return wall, {"assignment": result["assignment"],
                  "cost": result["cost"],
                  "violations": result["violation"],
                  "status": result["status"], "cycles": result["cycle"]}


def run(cell):
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml, load_dcop_from_file
    from pydcop_tpu.engine import aotcache

    config, traffic = cell.config, cell.traffic
    algo, cycles = config["algo"], config["max_cycles"]
    reference = lib.reference_of(config, cell.data_dir)

    def solve():
        t0 = time.perf_counter()
        res = api.solve(dcop, algo, max_cycles=cycles,
                        algo_params=config["algo_params"],
                        **lib.placement(config))
        return time.perf_counter() - t0, res

    # ---- set-up ------------------------------------------------------
    t0 = time.perf_counter()
    dcop = lib.generate(config["generator"], cell.seed, cell.data_dir)
    generate_s = time.perf_counter() - t0
    stated = lib.family_of(config["generator"], cell.data_dir).shapes(
        config["generator"])
    if lib.shapes(dcop) != stated:
        raise lib.BenchFailure(
            f"seed {cell.seed} gave the shapes {lib.shapes(dcop)}; the "
            f"configuration's family states {stated}")
    # The first solve compiles, or loads the program from the disk
    # cache; the rest of the run finds it in the process.
    warm = [solve()[0] for _ in range(traffic["warmup_solves"])]
    answers, end_to_end, yaml_path = [], {}, None
    if config["cli_solve"]:
        yaml_path = os.path.join(cell.workdir, "instance.yaml")
        with open(yaml_path, "w", encoding="utf-8") as f:
            f.write(dcop_yaml(dcop))
        end_to_end["cli_solve_s"], answer = cli_solve(cell, yaml_path)
        answers.append(("pydcop solve", answer))
    t0 = time.perf_counter()
    _, reference_cost = reference.solve(dcop, cycles, cell.seed)
    lib.note(setup={"generate_s": generate_s, "warmup_solves_s": warm,
                    "cli_solve_s": end_to_end.get("cli_solve_s"),
                    "reference_s": time.perf_counter() - t0,
                    "reference_cost": reference_cost,
                    "variables": len(dcop.variables),
                    "constraints": len(dcop.constraints)})

    # ---- the window --------------------------------------------------
    counters_before = aotcache.counters()
    walls = []
    deadline = cell.start_window() + cell.seconds
    while time.perf_counter() < deadline:
        wall, res = solve()
        walls.append(wall)
        answers.append((f"api.solve #{len(walls)}", {
            key: res[key] for key in ("assignment", "cost", "violations",
                                      "status", "cycles")}))
    end_to_end["solve_p50_s"] = statistics.median(walls)
    counters = aotcache.counters()
    lib.note(window={"solves": len(walls), "min_s": min(walls),
                     "cache_hits": counters["hits"] - counters_before["hits"],
                     "cache_misses": (counters["misses"]
                                      - counters_before["misses"]),
                     "p50_s": end_to_end["solve_p50_s"],
                     "max_s": max(walls), "cycles": res["cycles"],
                     "status": res["status"], "cost": res["cost"],
                     "n_shards": res.get("metrics", {}).get("n_shards")})

    # ---- the traced block --------------------------------------------
    capture = None
    if cell.trace:
        capture = {"counters_before": counters_before,
                   "shapes": stated, "values": dict(end_to_end)}
        traced_cycles = 0
        with lib.traced_block(cell, capture):
            for _ in range(traffic["traced_solves"]):
                traced_cycles += solve()[1]["cycles"]
        capture["counters_after"] = aotcache.counters()
        capture["values"]["cycles"] = traced_cycles
        if yaml_path is not None:
            t0 = time.perf_counter()
            load_dcop_from_file([yaml_path])
            capture["values"]["yaml_load_s"] = time.perf_counter() - t0

    # ---- the checks, after the window --------------------------------
    checked = [lib.check_answer(dcop, answer, reference_cost,
                                config["cost_tolerance"], config["ends"])
               for _, answer in answers]
    faults = [f"{what}: {fault}" for (what, _), (fault, _)
              in zip(answers, checked) if fault]
    for fault in faults[:5]:
        lib.note(fault=fault)
    if capture is not None:
        capture["values"]["cost_ratio"] = lib.mean_cost_ratio(
            (dcop, a["cost"], reference_cost) for _, a in answers)
    return {"correct": not faults, "attempted": len(answers),
            "failed": len(faults), "end_to_end": end_to_end,
            "capture": capture, "compared": lib.worst(checked)}
