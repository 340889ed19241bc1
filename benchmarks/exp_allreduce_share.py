"""Per-superstep all-reduce share in the sharded engine (VERDICT r3
item 7).

The sharded superstep's one cross-device op is the variable
aggregation: factor buckets are sharded on rows, the [V+1, D] belief
totals are replicated, so XLA inserts an all-reduce (psum) of the full
table every superstep (engine/sharding.py).  This experiment answers
"how much of the superstep is that collective" two ways:

1. MODELED for a v5e-8 mesh (ICI 2D torus): a ring all-reduce moves
   2(N-1)/N * V*D*4 bytes per link; local work streams the shard's
   buckets from HBM.  The model compares ICI time vs HBM time per
   superstep — this is the number that answers the question for the
   real chip, and it is valid regardless of where this script runs.
2. MEASURED on whatever mesh is available (the 8-device virtual CPU
   mesh in CI, a real slice when run there): per-superstep wall time
   single-device vs sharded.  The sharded-vs-single ratio shows
   whether the collective+partitioning overhead beats the N-way
   compute split on that backend; the per-op attribution of the
   collective itself comes from the model (XLA offers no per-op
   timer here short of a full profile trace).

Prints one JSON line.
"""

import json
import os
import sys
from functools import partial

import numpy as np


V5E_ICI_BYTES_PER_S_PER_LINK = 45e9   # public v5e spec, per direction
V5E_HBM_BYTES_PER_S = 819e9


def modeled_share(n_vars, n_edges, d, n_dev):
    """v5e-8 analytical breakdown for one superstep."""
    table_bytes = (n_vars + 1) * d * 4
    allreduce_bytes = 2 * (n_dev - 1) / n_dev * table_bytes
    ici_s = allreduce_bytes / V5E_ICI_BYTES_PER_S_PER_LINK
    # Local traffic per device: messages (2 passes: factor update,
    # suppress), costs, counts — ~6 arrays of [E/N, 2, D] plus the
    # belief table; use the roofline counter for the real number.
    local_bytes = (
        6 * (n_edges / n_dev) * 2 * d * 4 + 2 * table_bytes
    )
    hbm_s = local_bytes / V5E_HBM_BYTES_PER_S
    return {
        "modeled_allreduce_bytes": int(allreduce_bytes),
        "modeled_ici_s": ici_s,
        "modeled_local_hbm_s": hbm_s,
        "modeled_allreduce_share": round(
            ici_s / (ici_s + hbm_s), 3),
    }


def main():
    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench as bench_mod
    from pydcop_tpu.engine.sharding import make_mesh, shard_graph
    from pydcop_tpu.ops import maxsum as ops

    from pydcop_tpu.engine.timing import warmed_marginal

    n_vars = 1_000_000
    d = 3
    # Differencing bounds (engine/timing.py): the slope between two
    # cycle counts cancels the per-call constant (dispatch + fetch).
    cyc_lo, cyc_hi = 10, 60
    n_dev = len(jax.devices())

    # Build once (scatter aggregation — the sharded path's only
    # option), then re-pad for the mesh.
    _, graph = bench_mod.bench_scale(n_vars=n_vars, cycles=1)
    n_edges = graph.buckets[0].var_ids.shape[0]

    def timeit(g):
        per_cycle, _, _ = warmed_marginal(
            lambda c: jax.jit(partial(ops.run_maxsum, max_cycles=c,
                                      stop_on_convergence=False)),
            cyc_lo, cyc_hi, args=(g,), reps=3)
        return per_cycle * 1e3  # ms / superstep

    single_ms = timeit(graph)
    out = {
        "experiment": "allreduce_share",
        "backend": jax.devices()[0].platform,
        "n_vars": n_vars, "n_edges": int(n_edges), "n_devices": n_dev,
        "single_ms_per_cycle": round(single_ms, 3),
        **modeled_share(n_vars, n_edges, d, 8),
    }
    if n_dev > 1:
        mesh = make_mesh(n_dev)
        # Row-pad the bucket to the mesh size.
        b = graph.buckets[0]
        pad = (-b.var_ids.shape[0]) % n_dev
        if pad:
            costs = np.concatenate(
                [np.asarray(b.costs),
                 np.zeros((pad,) + b.costs.shape[1:], np.float32)])
            ids = np.concatenate(
                [np.asarray(b.var_ids),
                 np.full((pad, 2), n_vars, np.int32)])
            graph = graph._replace(
                buckets=(type(b)(costs, ids),))
        sharded = shard_graph(
            jax.device_get(graph), mesh)
        out["sharded_ms_per_cycle"] = round(timeit(sharded), 3)
        out["sharded_vs_single"] = round(
            out["sharded_ms_per_cycle"] / single_ms, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
