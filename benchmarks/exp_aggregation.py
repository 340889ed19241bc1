"""Aggregation-strategy experiment for the MaxSum superstep's variable
aggregation — the suspect past the size that fits fast memory (the
scatter-add and the tiny-minor-dim gathers; not measured on the
present chip — PERF.md).

Four strategies, identical math (up to float reassociation):

- scatter:   jax.ops.segment_sum on unsorted edge ids (current engine,
             ops/maxsum.aggregate_beliefs).
- sorted:    segment_sum on compile-time-sorted ids with
             indices_are_sorted=True (static permutation; the gather of
             messages into sorted order happens per cycle).
- boundary:  compile-time edge sort + cumsum along edges + per-variable
             boundary gathers — no scatter at all.
- ell:       compile-time per-variable edge lists padded to the max
             degree; dense gather + K-way sum — no scatter, no sort
             (TPU scatter-add serializes row updates; this is the
             vectorizable shape).

Run on the target backend:  python benchmarks/exp_aggregation.py
Prints one JSON line per size with ms/iteration for each strategy; use
it to decide whether the engine's aggregation is worth rewriting for
the HBM-bound regime (keep the engine unchanged until the winner is
measured on real hardware).
"""

import json
import os
import sys
import time
from functools import partial

import numpy as np


def build(n_vars, n_edges, d, seed=0):
    rng = np.random.default_rng(seed)
    seg = rng.integers(0, n_vars, size=n_edges).astype(np.int32)
    msgs = rng.random((n_edges, d)).astype(np.float32)
    perm = np.argsort(seg, kind="stable").astype(np.int32)
    sorted_seg = seg[perm]
    # Boundary offsets: starts[v] .. ends[v] index into the sorted
    # edge order (searchsorted on the static sorted ids).
    starts = np.searchsorted(sorted_seg, np.arange(n_vars),
                             side="left").astype(np.int32)
    ends = np.searchsorted(sorted_seg, np.arange(n_vars),
                           side="right").astype(np.int32)
    # ELL: per-variable edge lists padded to the max degree; dummy
    # slots hold n_edges (the kernel clips the index and masks the
    # contribution to zero).
    k_max = max(int((ends - starts).max()), 1)
    ell = np.full((n_vars, k_max), n_edges, np.int32)
    k_pos = np.arange(n_edges) - starts[sorted_seg]
    ell[sorted_seg, k_pos] = perm
    return seg, msgs, perm, sorted_seg, starts, ends, ell


def main():
    import jax
    import jax.numpy as jnp

    from pydcop_tpu.engine.timing import warmed_marginal

    d = 3
    # Differencing over the scan length (engine/timing.py): the
    # slope between two scan lengths cancels the per-call constant
    # (dispatch + fetch), which would otherwise dominate the small
    # sizes and make the A/B columns identical.
    IT_LO, IT_HI = 20, 120

    def timeit(make_fn, *args):
        per_iter, _, out = warmed_marginal(
            lambda n: jax.jit(make_fn(n)), IT_LO, IT_HI,
            args=args, reps=3)
        return per_iter * 1e3, out             # ms per iteration

    # Compile frugality: each distinct XLA program is compiled once
    # per (size, strategy, scan length), so the grid is kept to the
    # decision rows.  The 1M op-level row is dropped — 100k is
    # already past fast memory and the engine-level leg below
    # measures 1M end to end.
    for n_vars in (10_000, 100_000):
        n_edges = n_vars * 3
        seg, msgs, perm, sorted_seg, starts, ends, ell = build(
            n_vars, n_edges, d)

        def make_scatter(iters):
            def run(msgs, seg):
                def step(m, _):
                    s = jax.ops.segment_sum(
                        m, seg, num_segments=n_vars)
                    # feed result back so iterations can't collapse
                    return m + 1e-9 * s[seg], None
                m, _ = jax.lax.scan(step, msgs, None, length=iters)
                return jax.ops.segment_sum(m, seg, num_segments=n_vars)
            return run

        def make_sorted(iters):
            def run(msgs, seg_s, perm):
                def agg(m):
                    return jax.ops.segment_sum(
                        m[perm], seg_s, num_segments=n_vars,
                        indices_are_sorted=True)
                def step(m, _):
                    s = agg(m)
                    return m + 1e-9 * s[seg], None
                m, _ = jax.lax.scan(step, msgs, None, length=iters)
                return agg(m)
            return run

        def make_boundary(iters):
            def run(msgs, perm, starts, ends):
                def agg(m):
                    cum = jnp.cumsum(m[perm], axis=0)
                    cz = jnp.concatenate(
                        [jnp.zeros((1, d), jnp.float32), cum], axis=0)
                    return cz[ends] - cz[starts]
                def step(m, _):
                    s = agg(m)
                    return m + 1e-9 * s[seg], None
                m, _ = jax.lax.scan(step, msgs, None, length=iters)
                return agg(m)
            return run

        def make_ell(iters):
            def run(msgs, ell):
                def agg(m):
                    # clip + mask, not a zero-row append: appending
                    # copies the whole message array per iteration.
                    safe = jnp.minimum(ell, n_edges - 1)
                    mask = (ell < n_edges)[..., None]
                    return jnp.sum(
                        jnp.where(mask, m[safe], 0.0), axis=1)
                def step(m, _):
                    s = agg(m)
                    return m + 1e-9 * s[seg], None
                m, _ = jax.lax.scan(step, msgs, None, length=iters)
                return agg(m)
            return run

        t_sc, ref = timeit(make_scatter, jnp.asarray(msgs),
                           jnp.asarray(seg))
        t_so, out_so = timeit(make_sorted, jnp.asarray(msgs),
                              jnp.asarray(sorted_seg),
                              jnp.asarray(perm))
        t_bo, out_bo = timeit(make_boundary, jnp.asarray(msgs),
                              jnp.asarray(perm), jnp.asarray(starts),
                              jnp.asarray(ends))
        t_el, out_el = timeit(make_ell, jnp.asarray(msgs),
                              jnp.asarray(ell))
        err_so = float(jnp.max(jnp.abs(ref - out_so)))
        err_bo = float(jnp.max(jnp.abs(ref - out_bo)))
        err_el = float(jnp.max(jnp.abs(ref - out_el)))
        print(json.dumps({
            "n_vars": n_vars, "n_edges": n_edges,
            "backend": jax.devices()[0].platform,
            "scatter_ms": round(t_sc, 4),
            "sorted_ms": round(t_so, 4),
            "boundary_ms": round(t_bo, 4),
            "ell_ms": round(t_el, 4),
            "sorted_err": err_so, "boundary_err": err_bo,
            "ell_err": err_el,
        }))
        sys.stdout.flush()

    # Engine-level decision leg: the FULL superstep (run_maxsum) per
    # strategy on the 1M-var synthetic coloring — this is the number
    # that decides the headline bench's aggregation choice (the
    # op-level loops above attribute it).  NOTE: "boundary" here is a
    # throughput measurement only — its f32 prefix sum cancels at this
    # edge count (see ops/maxsum.aggregate_beliefs), so even if it
    # wins on speed it needs a numerics redesign before promotion to
    # the solve path.
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench as bench_mod

    # "boundary" is excluded from the engine leg (numerically
    # disqualified for solves — f32 prefix-sum cancellation, see
    # ops/maxsum.aggregate_beliefs) and "sorted" was measured ~=
    # scatter on-chip at the op level; each strategy costs two big
    # remote compiles, so spend them on the two candidates that could
    # actually become the scale-path default: the current scatter and
    # the dense-gather ell.
    for strategy in ("scatter", "ell"):
        t0 = time.perf_counter()
        cps, graph = bench_mod.bench_scale(
            n_vars=1_000_000, cycles=50, aggregation=strategy)
        print(json.dumps({
            "engine_1m_vars": strategy,
            "backend": jax.devices()[0].platform,
            "cycles_per_s": round(cps, 2),
            "ms_per_cycle": round(1e3 / cps, 3) if cps else None,
            "total_s": round(time.perf_counter() - t0, 1),
        }))
        sys.stdout.flush()
        del graph


if __name__ == "__main__":
    main()
