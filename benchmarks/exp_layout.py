"""Layout A/B for the MaxSum superstep at scale: edge-major (current
engine default, messages [F, arity, D]) vs lane-major (factors on the
TPU lane axis, messages [D, arity, F] — ops/maxsum_lane.py), plus the
edge-major "sorted" aggregation for a third column.

Motivation: past the size that fits fast memory the superstep is
expected to be scatter/layout-bound, and lane-major puts the factor
axis, not the 3-8 wide domain axis, on the 128 lanes (not measured on
the present chip — PERF.md).  This harness measures the FULL
superstep per layout on the synthetic 3-coloring scale problem
(bench.bench_scale) at 10k / 100k / 1M vars, so the number that
decides the scale path's default is end-to-end, not op-level.

Run on the target backend:  python benchmarks/exp_layout.py
Prints one JSON line per size: ms/cycle per configuration + the
selected-assignment agreement between layouts at that size (the
layouts reassociate the per-variable float sums, so trajectories can
split on near-ties; agreement is reported, not asserted — the
bit-level contract is tests/unit/test_maxsum_lane.py).
"""

import json
import sys
import time

import numpy as np


def main():
    import os

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench as bench_mod

    # Compile frugality: every (config, size, scan-length) is a
    # distinct XLA program (about a minute of compile at 100k vars).
    # Only the rows of the decision this harness feeds are kept:
    # edge-major vs lane-major past the size that fits fast memory.
    configs = [
        ("edge_scatter", {"aggregation": "scatter", "layout": "edge"}),
        ("lane", {"aggregation": "scatter", "layout": "lane"}),
    ]
    for n_vars in (100_000, 1_000_000):
        cycles = 200 if n_vars <= 100_000 else 50
        out = {"n_vars": n_vars, "cycles": cycles,
               "backend": jax.devices()[0].platform}
        values = {}
        for name, kw in configs:
            t0 = time.perf_counter()
            cps, graph, vals = bench_mod.bench_scale(
                n_vars=n_vars, cycles=cycles, return_values=True, **kw)
            out[f"{name}_ms_per_cycle"] = (
                round(1e3 / cps, 4) if cps else None)
            out[f"{name}_total_s"] = round(time.perf_counter() - t0, 1)
            # Agreement column reuses the timed run's own assignment —
            # no extra solve in the scarce on-chip window.
            values[name] = vals
            del graph
        if "edge_scatter" in values and "lane" in values:
            agree = float(np.mean(
                values["edge_scatter"] == values["lane"]))
            out["lane_vs_edge_assignment_agreement"] = round(agree, 4)
        print(json.dumps(out))
        sys.stdout.flush()


if __name__ == "__main__":
    main()
