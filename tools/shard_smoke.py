"""Shard-smoke gate: the partitioned engine's claims, on CPU.

Part of ``make test`` (like ``make chaos`` / ``make serve-smoke``):
quick, deterministic checks that the sharded superstep actually is
what ISSUE 7 says it is —

1. **Cut quality**: the min-edge-cut partitioner on a ~2k-variable
   locally-connected loopy graph (a 45x45 grid coloring) lands
   ``edge_cut_fraction`` < 0.3 over 8 shards with balance within the
   cap (measured ~0.02 here — grids partition well; the 0.3 bound is
   the acceptance criterion's regime marker).
2. **Communication accounting**: the per-superstep halo exchange
   volume (``[B, D]`` boundary buffer) is STRICTLY below the
   replicated path's dense ``[V+1, D]`` all-reduce volume.
3. **Parity**: the 8-shard solve produces the identical assignment
   (and therefore identical host-evaluated cost) as the unsharded
   single-device engine at the same cycle budget.
4. **Auto-padding regression**: ``shard_graph`` on a bucket whose row
   count is NOT divisible by the mesh size pads instead of raising.

Runs under 8 forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), the same
recipe CI parity tests use, so the gate needs no accelerator.

Run:  python tools/shard_smoke.py      (exit 0 = all claims hold)
"""

import os
import sys
import time

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

N_SHARDS = 8
GRID_SIDE = 45          # 2025 variables, 3960 factors — loopy
MAX_CYCLES = 80


def fail(msg: str) -> "None":
    print(f"shard_smoke: FAIL — {msg}")
    sys.exit(1)


def main() -> int:
    t0 = time.perf_counter()
    import jax

    if len(jax.devices()) < N_SHARDS:
        fail(f"only {len(jax.devices())} devices (forced-host flag "
             "not honored?)")

    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.engine.runner import (
        MaxSumEngine,
        ShardedMaxSumEngine,
    )
    from pydcop_tpu.engine.sharding import make_mesh, shard_graph
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    # `pydcop generate graph_coloring -g grid --soft`: random integer
    # tables on a 4-neighbor grid.
    dcop = generate_graph_coloring(
        GRID_SIDE * GRID_SIDE, 3, "grid", soft=True, noagents=True,
        seed=0)
    graph, meta = compile_dcop(dcop, noise_level=0.01)

    single = MaxSumEngine(graph, meta)
    res1 = single.run(max_cycles=MAX_CYCLES, stop_on_convergence=False)

    sharded = ShardedMaxSumEngine(graph, meta, n_shards=N_SHARDS)
    m = sharded.extra_metrics
    cut = m["edge_cut_fraction"]
    if not cut < 0.3:
        fail(f"edge_cut_fraction {cut:.3f} >= 0.3 on a grid — the "
             "partitioner regressed")
    halo = m["halo_exchange_elems_per_superstep"]
    repl = m["replicated_allreduce_elems_per_superstep"]
    if not halo < repl:
        fail(f"halo exchange volume {halo} not below the replicated "
             f"all-reduce volume {repl}")
    res8 = sharded.run(max_cycles=MAX_CYCLES, stop_on_convergence=False)
    if res8.assignment != res1.assignment:
        diff = sum(res8.assignment[k] != res1.assignment[k]
                   for k in res1.assignment)
        fail(f"sharded assignment diverged on {diff}/"
             f"{len(res1.assignment)} variables")
    cost1, _ = dcop.solution_cost(res1.assignment)
    cost8, _ = dcop.solution_cost(res8.assignment)
    if cost1 != cost8:
        fail(f"sharded cost {cost8} != unsharded {cost1}")

    # Auto-padding regression: 1001 binary factors do not divide 8.
    from pydcop_tpu.engine.compile import compile_factor_graph

    sub = list(dcop.constraints.values())[:1001]
    g_odd, _ = compile_factor_graph(
        list(dcop.variables.values()), sub)
    mesh = make_mesh(N_SHARDS)
    placed = shard_graph(g_odd, mesh)
    rows = placed.buckets[0].costs.shape[0]
    if rows % N_SHARDS:
        fail(f"shard_graph left {rows} rows, not a multiple of "
             f"{N_SHARDS}")

    print(
        f"shard_smoke: OK — {GRID_SIDE * GRID_SIDE} vars / "
        f"{len(dcop.constraints)} factors over {N_SHARDS} shards: "
        f"edge_cut={cut:.3f}, halo {halo} elems/superstep vs "
        f"replicated {repl} ({halo / repl:.1%}), bit-parity at "
        f"{MAX_CYCLES} cycles (cost {cost8}), autopad {rows} rows "
        f"[{time.perf_counter() - t0:.1f}s]"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
