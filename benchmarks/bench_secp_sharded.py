"""SECP sharded benchmark — BASELINE config #5: smart-lighting-style
factor population (default 100k binary rule factors over 4k lights,
domain 5) compiled, sharded over every available device, solved with
the MaxSum engine; reports iters/s, per-device memory, and final cost.

On a real multi-chip TPU slice the mesh rides ICI; under
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu it
exercises the identical sharded program on the virtual mesh (what
tests/api/test_secp_sharded_scale.py asserts bit-parity for).

Run: python benchmarks/bench_secp_sharded.py [n_rules]
Prints one JSON line.
"""

import json
import sys
import time

import numpy as np

N_LIGHTS = 4_000
N_RULES = 100_000
D = 5
CYCLES = 50


def build_arrays(n_lights, n_rules, seed=0):
    """SECP rule tables as device-ready arrays (building 100k Python
    constraint objects adds minutes of host time for no benchmark
    signal; the structure matches the generator's rule factors:
    |li - ti| + |lj - tj| over light pairs)."""
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, n_lights, size=(n_rules, 2)).astype(np.int32)
    # No self-loop factors (the generator pairs DISTINCT lights,
    # rng.choice replace=False): resample the second slot on collision.
    loops = pairs[:, 0] == pairs[:, 1]
    while loops.any():
        pairs[loops, 1] = rng.integers(
            0, n_lights, size=int(loops.sum()))
        loops = pairs[:, 0] == pairs[:, 1]
    ti = rng.integers(0, D, size=n_rules)
    tj = rng.integers(0, D, size=n_rules)
    grid = np.arange(D)
    tables = (
        np.abs(grid[None, :, None] - ti[:, None, None])
        + np.abs(grid[None, None, :] - tj[:, None, None])
    ).astype(np.float32)
    return pairs, tables


def main():
    n_rules = int(sys.argv[1]) if len(sys.argv) > 1 else N_RULES
    import jax

    from pydcop_tpu.engine.compile import (
        BIG,
        CompiledFactorGraph,
        FactorBucket,
    )
    from pydcop_tpu.engine.sharding import make_mesh, shard_graph
    from pydcop_tpu.ops import maxsum as ops

    n_devices = len(jax.devices())
    pairs, tables = build_arrays(N_LIGHTS, n_rules)
    # Pad rows to divide the mesh (sentinel var id = N_LIGHTS).
    pad = (-n_rules) % max(n_devices, 1)
    if pad:
        pairs = np.concatenate(
            [pairs, np.full((pad, 2), N_LIGHTS, np.int32)])
        tables = np.concatenate(
            [tables, np.zeros((pad, D, D), np.float32)])
    var_costs = np.full((N_LIGHTS + 1, D), BIG, np.float32)
    var_costs[:-1] = np.random.default_rng(1).random(
        (N_LIGHTS, D)) * 0.01
    var_valid = np.zeros((N_LIGHTS + 1, D), bool)
    var_valid[:-1] = True
    graph = CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid,
        buckets=(FactorBucket(tables, pairs),),
    )

    bucket_bytes = sum(
        b.costs.nbytes + b.var_ids.nbytes for b in graph.buckets)
    replicated = graph.var_costs.nbytes + graph.var_valid.nbytes
    per_device_mb = (bucket_bytes / n_devices + replicated) / 1e6

    if n_devices > 1:
        mesh = make_mesh(n_devices)
        graph = shard_graph(graph, mesh)
    else:
        graph = jax.device_put(graph)

    from functools import partial

    from pydcop_tpu.engine.timing import timed_call

    # timed_call closes each window on a host fetch of the result
    # (engine/timing.py).
    fn = jax.jit(partial(ops.run_maxsum, max_cycles=CYCLES,
                         stop_on_convergence=False))
    _, compile_s = timed_call(fn, graph)
    (state, values), elapsed = timed_call(fn, graph)

    final_cost = float(ops.assignment_constraint_cost(graph, values))
    print(json.dumps({
        "metric": "secp_sharded_cycles_per_sec",
        "value": round(int(state.cycle) / elapsed, 2),
        "unit": "cycles/s",
        "n_rules": n_rules,
        "n_lights": N_LIGHTS,
        "n_devices": n_devices,
        "backend": jax.devices()[0].platform,
        "per_device_mb": round(per_device_mb, 1),
        "compile_s": round(compile_s, 2),
        "final_cost": round(final_cost, 1),
    }))


if __name__ == "__main__":
    main()
