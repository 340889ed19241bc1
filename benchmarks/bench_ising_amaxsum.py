"""Ising A-MaxSum benchmark — BASELINE config #2: 32x32 (1,024-var)
random Ising grid with binary + unary factors, solved with
amaxsum + damping 0.7 on the device engine, against this repo's own
threaded agent runtime running the true asynchronous amaxsum
computations on the same instance.

Device amaxsum is the lockstep engine (an async firing schedule has no
device meaning — algorithms/amaxsum.py docstring), so beyond speed this
bench records both final costs: the documented claim that lockstep and
async schedules land in the same cost band on Ising grids.

The device leg builds ONE engine and times the second run, so the
cycles/s value is steady-state execution (warm jit cache), and
speedup_wall compares compile-free device wall clock against the
thread runtime's wall clock.

Run: python benchmarks/bench_ising_amaxsum.py [rows]
Prints one JSON line.
"""

import json
import sys
import time

ROWS = 32
DEVICE_CYCLES = 300
THREAD_TIMEOUT_S = 20.0
THREAD_AGENTS = 8


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else ROWS
    from pydcop_tpu.algorithms import AlgorithmDef, load_algorithm_module
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.computations_graph import load_graph_module
    from pydcop_tpu.distribution.objects import Distribution
    from pydcop_tpu.generators.ising import generate_ising
    from pydcop_tpu.infrastructure.run import run_local_thread_dcop

    dcop, _, _ = generate_ising(rows, no_agents=True, seed=11)
    module = load_algorithm_module("amaxsum")

    # Device leg: ONE engine so the timed run hits the warm jit cache
    # (solve_on_device builds a fresh engine per call — every call
    # would be a cold start).
    algo_def = AlgorithmDef.build_with_default_param(
        "amaxsum", mode="min", params={"damping": 0.7})
    engine = build_engine(dcop, algo_def.params)
    engine.run(max_cycles=DEVICE_CYCLES, stop_on_convergence=False)
    t0 = time.perf_counter()
    res = engine.run(max_cycles=DEVICE_CYCLES, stop_on_convergence=False)
    device_wall = time.perf_counter() - t0
    device_cost, _ = dcop.solution_cost(res.assignment)
    device_cps = res.cycles / res.time_s if res.time_s > 0 else 0.0

    # Thread leg: true async amaxsum computations on agent threads.
    from pydcop_tpu.dcop.objects import AgentDef

    dcop.add_agents(
        [AgentDef(f"a{i}") for i in range(THREAD_AGENTS)])
    cg = load_graph_module(
        module.GRAPH_TYPE).build_computation_graph(dcop)
    agents = sorted(dcop.agents)
    mapping = {a: [] for a in agents}
    for i, node in enumerate(cg.nodes):
        mapping[agents[i % len(agents)]].append(node.name)
    orch = run_local_thread_dcop(
        algo_def, cg, Distribution(mapping), dcop)
    try:
        if not orch.wait_ready(30):
            raise RuntimeError("agents not ready")
        orch.deploy_computations()
        t0 = time.perf_counter()
        orch.run(timeout=THREAD_TIMEOUT_S)
        thread_wall = time.perf_counter() - t0
        orch.stop_agents(10)
        metrics = orch.end_metrics()
        # end_metrics already filters the assignment and guards the
        # not-all-reported case; None -> NaN keeps the JSON line alive.
        thread_cost = (
            float(metrics["cost"]) if metrics["cost"] is not None
            else float("nan")
        )
    finally:
        orch.stop_agents(5)
        orch.stop()

    print(json.dumps({
        "metric": "ising_amaxsum_cycles_per_sec",
        "value": round(device_cps, 2),
        "unit": "cycles/s",
        "n_vars": rows * rows,
        "damping": 0.7,
        "device_cost": round(device_cost, 3),
        "device_wall_s": round(device_wall, 3),
        "thread_cost_async": round(thread_cost, 3),
        "thread_wall_s": round(thread_wall, 2),
        "speedup_wall": (
            round(thread_wall / device_wall, 1)
            if device_wall > 0 else None
        ),
    }))


if __name__ == "__main__":
    main()
