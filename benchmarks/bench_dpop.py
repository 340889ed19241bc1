"""DPOP bench: level-batched jitted sweep vs per-node numpy sweep.

Config #3 of BASELINE.md (tree-structured DCOP, total solve time).
Prints one JSON line per problem size with both engines' times and the
(identical) optimal cost.

Run: python benchmarks/bench_dpop.py  (runs on whatever platform JAX
resolves; the result line names it).
"""

import json
import sys
import time

import numpy as np


def make_tree_dcop(n, d, seed=0):
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("c", "", list(range(d)))
    dcop = DCOP("bench", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for i in range(1, n):
        p = rng.integers(0, i)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[p], vs[i]], rng.random((d, d)), f"c{i}"
        ))
    return dcop


def main():
    from pydcop_tpu.algorithms import AlgorithmDef
    from pydcop_tpu.algorithms.dpop import solve_on_device

    for n, d in ((3000, 3), (10000, 8)):
        dcop = make_tree_dcop(n, d)
        jit_algo = AlgorithmDef.build_with_default_param(
            "dpop", {"engine": "jit"}, mode="min"
        )
        np_algo = AlgorithmDef.build_with_default_param(
            "dpop", {"engine": "numpy"}, mode="min"
        )
        # Warm the kernel cache so the timed run is compile-free.
        solve_on_device(dcop, jit_algo)
        t0 = time.perf_counter()
        r_jit = solve_on_device(dcop, jit_algo)
        t1 = time.perf_counter()
        r_np = solve_on_device(dcop, np_algo)
        t2 = time.perf_counter()
        assert abs(
            r_jit.metrics["device_cost"] - r_np.metrics["device_cost"]
        ) < 1e-2, "cost parity violated"
        print(json.dumps({
            "metric": f"dpop_solve_time_{n}var_d{d}",
            "value": round(t1 - t0, 4),
            "unit": "s",
            "vs_baseline": round((t2 - t1) / (t1 - t0), 2),
            "baseline": "per-node numpy sweep",
            "numpy_s": round(t2 - t1, 4),
            "cost": round(r_jit.metrics["device_cost"], 3),
            "kernel_calls": r_jit.metrics["kernel_calls"],
        }))


if __name__ == "__main__":
    main()
