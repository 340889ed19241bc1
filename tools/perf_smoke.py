"""Perf-smoke gate: the hot-path overhaul's measurable claims, on CPU.

Part of ``make test`` (like ``make chaos`` / ``make trace-demo``):
quick, deterministic checks that the compile fast paths actually stay
fast and the autotuner only makes valid choices —

1. **Vectorized compile**: compiling a 10k-binary-factor
   expression-constraint instance with the vectorized+memoized table
   evaluation must be >= 3x faster than the per-factor per-assignment
   reference loop (ISSUE 3 acceptance; measured ~5x on this box).
2. **Structure cache**: recompiling a same-structured problem must
   hit the layout cache — layout/agg-array construction skipped
   entirely (counter-asserted) and the warm compile faster than the
   cold one.
3. **Autotuner**: ``aggregation='auto'`` must pick one of the four
   named strategies (never "boundary" — numerics), record timings,
   and replay its decision from the JSON shape cache.
4. **Flight-recorder overhead** (ISSUE 9 acceptance): the always-on
   ring must cost <= 5% on the segmented-run benchmark — recorder
   attached vs detached on the same warmed engine, min-of-N runs
   (events reach the ring only at segment boundaries; the jitted
   loop itself is untouched).
5. **Efficiency-plane overhead** (ISSUE 14 acceptance): the device-
   efficiency accounting plane (per-dispatch attainment records,
   jit accounting) must cost <= 5% on the serving-shaped batched
   dispatch — tracker on vs off, PAIRWISE interleaved so CPU
   frequency drift and concurrent-load flake cannot masquerade as
   plane overhead.
6. **Cross-edge consistency** (ISSUE 17 acceptance): on a seeded
   low-width graph with soft-dominated domain values, the CEC
   preprocessing pass must either speed the warmed UTIL sweep by
   >= 1.2x or gain >= 1 effective width rung (one domain factor off
   the largest UTIL hypercube) — CEC-on vs CEC-off PAIRWISE
   interleaved — while the returned assignment stays bit-identical.
7. **Pipelined flushes** (ISSUE 18 acceptance): on a seeded 4-bin
   flush, the pipelined scheduler (launch k+1 while k's arrays are
   in flight) must return BIT-IDENTICAL assignments to the
   synchronous path and never cost more than 2% over it; where the
   box has a second core to overlap on (>= 2 CPUs) it must also be
   >= 1.15x faster — on/off PAIRWISE interleaved, min-of-N.

Run:  python tools/perf_smoke.py      (exit 0 = all claims hold)
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from pydcop_tpu.dcop.objects import Domain, Variable  # noqa: E402
from pydcop_tpu.dcop.relations import (  # noqa: E402
    NAryMatrixRelation,
    constraint_from_str,
)
from pydcop_tpu.engine.compile import (  # noqa: E402
    AGGREGATIONS,
    compile_cache,
    compile_factor_graph,
)

N_VARS = 2_000
N_FACTORS = 10_000
MIN_SPEEDUP = 3.0


def build_instance(n_vars=N_VARS, n_factors=N_FACTORS, penalty=10):
    """10k binary *expression* constraints (the acceptance instance):
    per-edge intentional constraints exactly as the YAML/generator
    path produces them."""
    rng = np.random.default_rng(7)
    d = Domain("colors", "", [0, 1, 2])
    vs = [Variable(f"x{i}", d) for i in range(n_vars)]
    pairs = rng.integers(0, n_vars, size=(n_factors, 2))
    loop = pairs[:, 0] == pairs[:, 1]
    pairs[loop, 1] = (pairs[loop, 0] + 1) % n_vars
    cons = [
        constraint_from_str(
            f"c{i}", f"{penalty} if x{a} == x{b} else 0",
            [vs[a], vs[b]])
        for i, (a, b) in enumerate(pairs)
    ]
    return vs, cons


def check_vectorized_compile() -> dict:
    best = 0.0
    t_old = t_new = None
    for _ in range(2):  # one retry damps a noisy neighbor
        vs, cons = build_instance()
        t0 = time.perf_counter()
        g_old, _ = compile_factor_graph(
            vs, cons, vectorize=False, use_cache=False)
        t_old = time.perf_counter() - t0
        vs, cons = build_instance()  # fresh: no per-instance caches
        t0 = time.perf_counter()
        g_new, _ = compile_factor_graph(
            vs, cons, vectorize=True, use_cache=False)
        t_new = time.perf_counter() - t0
        for b_old, b_new in zip(g_old.buckets, g_new.buckets):
            np.testing.assert_array_equal(b_old.costs, b_new.costs)
        best = max(best, t_old / t_new)
        if best >= MIN_SPEEDUP:
            break
    assert best >= MIN_SPEEDUP, (
        f"vectorized compile only {best:.2f}x faster than the "
        f"per-factor loop (need >= {MIN_SPEEDUP}x): "
        f"{t_old * 1e3:.0f}ms -> {t_new * 1e3:.0f}ms")
    return {"per_factor_ms": round(t_old * 1e3, 1),
            "vectorized_ms": round(t_new * 1e3, 1),
            "speedup": round(best, 2)}


def build_matrix_instance(n_vars=4_000, n_factors=20_000, seed=0):
    """Extensional (table) constraints: table evaluation is nearly
    free here, so compile time is layout-weighted — the instance that
    makes the structure-cache's layout skip show up on the clock."""
    rng = np.random.default_rng(7)
    d = Domain("colors", "", [0, 1, 2])
    vs = [Variable(f"x{i}", d) for i in range(n_vars)]
    pairs = rng.integers(0, n_vars, size=(n_factors, 2))
    loop = pairs[:, 0] == pairs[:, 1]
    pairs[loop, 1] = (pairs[loop, 0] + 1) % n_vars
    tables = [np.random.default_rng(seed + i).random((3, 3))
              for i in range(4)]
    cons = [
        NAryMatrixRelation([vs[a], vs[b]], tables[i % 4], f"m{i}")
        for i, (a, b) in enumerate(pairs)
    ]
    return vs, cons


def check_structure_cache() -> dict:
    # Interleaved cold/warm pairs (each pair adjacent in time, so a
    # noisy neighbor hits both sides) + min-of-N: the warm compile
    # does strictly less work, so min-vs-min is the honest compare.
    t_cold, t_warm = [], []
    for i in range(3):
        compile_cache.clear()
        vs, cons = build_matrix_instance(seed=10 * i)
        t0 = time.perf_counter()
        compile_factor_graph(vs, cons, aggregation="ell")
        t_cold.append(time.perf_counter() - t0)
        stats = compile_cache.stats()
        assert stats == {"hits": 0, "misses": 1, "layout_builds": 1,
                         "entries": 1}, stats
        # Same structure, new cost tables (the serving pattern): the
        # hit must skip layout construction entirely.
        vs, cons = build_matrix_instance(seed=10 * i + 5)
        t0 = time.perf_counter()
        compile_factor_graph(vs, cons, aggregation="ell")
        t_warm.append(time.perf_counter() - t0)
        stats = compile_cache.stats()
        assert stats["hits"] == 1, stats
        assert stats["layout_builds"] == 1, (
            f"layout rebuilt on a structure-cache hit: {stats}")
    assert min(t_warm) < min(t_cold), (
        f"cached compile not faster: cold {min(t_cold) * 1e3:.0f}ms "
        f"vs warm {min(t_warm) * 1e3:.0f}ms")
    return {"cold_ms": round(min(t_cold) * 1e3, 1),
            "warm_ms": round(min(t_warm) * 1e3, 1),
            "stats": stats}


def check_autotuner() -> dict:
    from pydcop_tpu.engine.autotune import autotune_aggregation

    vs, cons = build_instance(n_vars=300, n_factors=900)
    graph, _ = compile_factor_graph(vs, cons, use_cache=False)
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "tune.json")
        info = autotune_aggregation(graph, cache_file=cache)
        assert info["aggregation"] in AGGREGATIONS, info
        assert info["aggregation"] != "boundary", (
            "autotuner selected the numerics-disqualified strategy")
        assert info["aggregation_source"] == "measured", info
        timed = [s for s, t in info["aggregation_timings_ms"].items()
                 if t is not None]
        assert {"scatter", "sorted", "ell"} <= set(timed), info
        replay = autotune_aggregation(graph, cache_file=cache)
        assert replay["aggregation_source"] == "cache", replay
        assert replay["aggregation"] == info["aggregation"]
    return {"choice": info["aggregation"],
            "timings_ms": info["aggregation_timings_ms"]}


# ------------------------------------------------------------------ #
# ISSUE 10 work-reduction gates: branch-and-bound pruning and
# decimation, both on ONE large-domain loopy instance — an effective
# 2-coloring embedded in D=128 domains (two near-zero unary slots
# shared by every variable, the rest expensive): plain MaxSum
# oscillates on the frustrated loops (the decimation regime) while the
# big unary spread keeps the per-factor survivor sets tiny (the
# pruning regime).

PRUNE_MIN_SPEEDUP = 1.3
DECIM_MAX_FRACTION = 0.70
WR_N_VARS = 200
WR_DOMAIN = 128
WR_EDGE_FACTOR = 1.6
WR_BUDGET_CYCLES = 300


def build_workreduction_graph(seed=3, noise=0.01):
    """Direct-array build (compile would dominate the gate) of the
    gate instance + a minimal meta for the engine: integer unary
    costs in [32, 400) except two zero slots, equality penalty 1,
    deterministic tie-break noise like engine.compile applies."""
    from pydcop_tpu.engine.compile import (
        BIG,
        CompiledFactorGraph,
        FactorBucket,
        FactorGraphMeta,
    )

    rng = np.random.default_rng(seed)
    v, d = WR_N_VARS, WR_DOMAIN
    f = int(v * WR_EDGE_FACTOR)
    var_ids = rng.integers(0, v, size=(f, 2)).astype(np.int32)
    loop = var_ids[:, 0] == var_ids[:, 1]
    var_ids[loop, 1] = (var_ids[loop, 0] + 1) % v
    costs = np.ascontiguousarray(np.broadcast_to(
        np.eye(d, dtype=np.float32), (f, d, d))).copy()
    var_costs = np.full((v + 1, d), BIG, np.float32)
    unary = rng.integers(32, 400, size=(v, d)).astype(np.float32)
    unary[:, 0] = 0.0
    unary[:, 1] = 0.0
    base = unary.copy()
    var_costs[:-1] = unary + (
        noise * rng.random((v, d))).astype(np.float32)
    var_valid = np.zeros((v + 1, d), bool)
    var_valid[:-1] = True
    graph = CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid,
        buckets=(FactorBucket(costs, var_ids),))
    meta = FactorGraphMeta(
        var_names=tuple(f"v{i}" for i in range(v)),
        domains=tuple(tuple(range(d)) for _ in range(v)),
        factor_names=tuple(f"c{i}" for i in range(f)),
        bucket_sizes=(f,), mode="min", var_base_costs=base)
    return graph, meta


def _constraint_cost(graph, values: np.ndarray) -> float:
    ids = np.asarray(graph.buckets[0].var_ids)
    return float(np.sum(values[ids[:, 0]] == values[ids[:, 1]]))


def check_pruning() -> dict:
    """Branch-and-bound pruning: >= 1.3x superstep throughput on the
    fixed-budget (serving-shaped) run AND a bit-identical trajectory —
    every state leaf equal, not just the assignment."""
    from functools import partial

    import jax

    from pydcop_tpu.ops import maxsum as ops

    graph, _meta = build_workreduction_graph()
    g = jax.device_put(graph)
    fns = {
        prune: jax.jit(partial(
            ops.run_maxsum, max_cycles=WR_BUDGET_CYCLES,
            stop_on_convergence=False, prune=prune))
        for prune in (False, True)
    }
    outs = {p: jax.block_until_ready(fn(g)) for p, fn in fns.items()}
    for (ld, lp) in zip(jax.tree_util.tree_leaves(outs[False]),
                        jax.tree_util.tree_leaves(outs[True])):
        assert np.array_equal(np.asarray(ld), np.asarray(lp)), \
            "pruned trajectory diverged from dense (bit-parity)"

    best = 0.0
    t_d = t_p = None
    for _ in range(3):  # best-of-N attempts damp a noisy neighbor
        d_times, p_times = [], []
        for _rep in range(3):  # interleaved: equal noise exposure
            t0 = time.perf_counter()
            jax.block_until_ready(fns[False](g))
            d_times.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fns[True](g))
            p_times.append(time.perf_counter() - t0)
        t_d, t_p = min(d_times), min(p_times)
        best = max(best, t_d / t_p)
        if best >= PRUNE_MIN_SPEEDUP:
            break
    assert best >= PRUNE_MIN_SPEEDUP, (
        f"pruning only {best:.2f}x over the dense superstep (need >= "
        f"{PRUNE_MIN_SPEEDUP}x): dense {t_d * 1e3:.0f}ms -> pruned "
        f"{t_p * 1e3:.0f}ms")
    return {"dense_ms": round(t_d * 1e3, 1),
            "pruned_ms": round(t_p * 1e3, 1),
            "speedup": round(best, 2)}


def check_decimation() -> dict:
    """Decimation: reach the reference cost in <= 70% of the baseline
    wall time on the same graph.  Reference = the decimated run's
    final constraint cost; baseline = plain MaxSum's wall to first
    reach it, censored at the full fixed budget when it never does
    (the anytime-comparison convention: the loser is charged the
    budget it actually burned)."""
    from functools import partial

    import jax

    from pydcop_tpu.engine.runner import DecimationPlan, MaxSumEngine
    from pydcop_tpu.ops import maxsum as ops

    graph, meta = build_workreduction_graph()
    g = jax.device_put(graph)
    plan = DecimationPlan(frac_per_round=0.2, cycles_per_round=25)

    def decim_engine():
        return MaxSumEngine(graph, meta, prune=True)

    def decim_run(engine):
        t0 = time.perf_counter()
        res = engine.run_checkpointed(
            max_cycles=4 * WR_BUDGET_CYCLES,
            segment_cycles=plan.cycles_per_round,
            decimation=plan)
        return time.perf_counter() - t0, res

    engine = decim_engine()
    decim_run(engine)  # warm every jitted round + the margin fn
    ratio = float("inf")
    decim_s = base_s = ref = None
    plain_curve = None
    fn = jax.jit(partial(
        ops.run_maxsum, max_cycles=WR_BUDGET_CYCLES,
        stop_on_convergence=False))
    jax.block_until_ready(fn(g))  # warm the baseline program
    trace_fn = jax.jit(partial(
        ops.run_maxsum_trace, max_cycles=WR_BUDGET_CYCLES,
        stop_on_convergence=False))
    _st, _vv, plain_curve = jax.device_get(
        jax.block_until_ready(trace_fn(g)))
    plain_curve = np.asarray(plain_curve)
    for _ in range(3):
        d_s, res = decim_run(engine)
        values = np.array(
            [res.assignment[n] for n in meta.var_names])
        ref = _constraint_cost(graph, values)
        assert res.metrics["decimated_vars"] == WR_N_VARS
        # Plain wall to the reference cost, censored at the budget.
        budget_times = []
        for _rep in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(g))
            budget_times.append(time.perf_counter() - t0)
        budget_s = min(budget_times)
        below = np.nonzero(plain_curve <= ref)[0]
        frac = ((int(below[0]) + 1) / WR_BUDGET_CYCLES
                if below.size else 1.0)
        base_s = budget_s * frac
        decim_s = d_s
        ratio = min(ratio, decim_s / base_s)
        if ratio <= DECIM_MAX_FRACTION:
            break
    assert ratio <= DECIM_MAX_FRACTION, (
        f"decimation took {ratio:.0%} of the baseline wall to the "
        f"reference cost (budget {DECIM_MAX_FRACTION:.0%}): decim "
        f"{decim_s * 1e3:.0f}ms vs baseline {base_s * 1e3:.0f}ms "
        f"(ref cost {ref})")
    return {"decim_ms": round(decim_s * 1e3, 1),
            "baseline_ms": round(base_s * 1e3, 1),
            "fraction": round(ratio, 3),
            "ref_cost": ref,
            "plain_best_cost": float(plain_curve.min())}


MAX_FLIGHT_OVERHEAD = 1.05  # on/off runtime ratio (<= 5%)


def check_flight_overhead() -> dict:
    """The ISSUE 9 perf gate: an attached flight ring may cost at
    most 5% on the segmented-run benchmark.  Ring appends happen only
    at segment boundaries (the jitted loop never sees the recorder),
    so the measured ratio is noise-dominated — min-of-N per side,
    best-of-3 attempts, exactly like the compile checks above."""
    from pydcop_tpu.algorithms.maxsum import build_engine
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.observability.flight import FlightRecorder
    from pydcop_tpu.observability.trace import tracer

    rng = np.random.default_rng(7)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP("flight_bench", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(12)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(12):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % 12]],
            rng.integers(0, 10, size=(3, 3)).astype(float),
            f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    engine = build_engine(dcop, {})
    kw = dict(max_cycles=600, segment_cycles=5,
              stop_on_convergence=False)
    prev = tracer.flight
    tracer.set_flight(None)

    def timed() -> float:
        t0 = time.perf_counter()
        engine.run_checkpointed(**kw)
        return time.perf_counter() - t0

    try:
        timed()  # warm the jit cache once, outside the clock
        ratio = float("inf")
        t_off = t_on = None
        # Bundle dir never written on the happy path: ring only.
        ring = FlightRecorder(events=2048)
        for _ in range(4):
            offs, ons = [], []
            # Interleave off/on runs pairwise: a phase of all-off
            # followed by a phase of all-on lets CPU frequency drift
            # masquerade as recorder overhead; alternating gives both
            # sides the same noise exposure, min-of-N filters upward
            # excursions.
            for _rep in range(5):
                tracer.set_flight(None)
                offs.append(timed())
                tracer.set_flight(ring)
                ons.append(timed())
            tracer.set_flight(None)
            t_off, t_on = min(offs), min(ons)
            ratio = min(ratio, t_on / t_off)
            if ratio <= MAX_FLIGHT_OVERHEAD:
                break
    finally:
        tracer.set_flight(prev)
    assert ratio <= MAX_FLIGHT_OVERHEAD, (
        f"flight recorder costs {(ratio - 1) * 100:.1f}% on the "
        f"segmented run (budget {(MAX_FLIGHT_OVERHEAD - 1) * 100:.0f}"
        f"%): off {t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    return {"off_ms": round(t_off * 1e3, 1),
            "on_ms": round(t_on * 1e3, 1),
            "overhead": round(ratio - 1, 4)}


MAX_EFFICIENCY_OVERHEAD = 1.05  # on/off runtime ratio (<= 5%)


def check_efficiency_overhead() -> dict:
    """The ISSUE 14 perf gate: the efficiency accounting plane
    (observability/efficiency.py — per-dispatch attainment records +
    jit accounting) may cost at most 5% on the serving-shaped batched
    dispatch.  Recording is one lock + dict arithmetic per DISPATCH
    (milliseconds of device work), so the measured ratio is
    noise-dominated: off/on runs interleave PAIRWISE (a phase of
    all-off followed by all-on lets CPU frequency drift masquerade as
    plane overhead — the PR-9 methodology), min-of-N per side,
    best-of-attempts."""
    import jax

    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.engine import batch as engine_batch
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.observability.efficiency import tracker
    from pydcop_tpu.observability.metrics import registry

    rng = np.random.default_rng(11)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP("eff_bench", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(16)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(16):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % 16]],
            rng.integers(0, 10, size=(3, 3)).astype(float),
            f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    graph, _meta = compile_dcop(dcop)
    graphs = [graph] * 4
    kw = dict(max_cycles=200, pad_to_bins=(4,))

    def timed() -> float:
        t0 = time.perf_counter()
        for _ in range(4):
            engine_batch.run_stacked(graphs, **kw)
        return time.perf_counter() - t0

    was_enabled = tracker.enabled
    was_active = registry.active
    registry.active = True  # the serving posture: export paths live
    try:
        tracker.enabled = True
        timed()  # warm the jit cache once, outside the clock
        jax.block_until_ready(jax.numpy.zeros(()))
        ratio = float("inf")
        t_off = t_on = None
        for _ in range(4):
            offs, ons = [], []
            for _rep in range(5):
                tracker.enabled = False
                offs.append(timed())
                tracker.enabled = True
                ons.append(timed())
            t_off, t_on = min(offs), min(ons)
            ratio = min(ratio, t_on / t_off)
            if ratio <= MAX_EFFICIENCY_OVERHEAD:
                break
    finally:
        tracker.enabled = was_enabled
        registry.active = was_active
    assert ratio <= MAX_EFFICIENCY_OVERHEAD, (
        f"efficiency plane costs {(ratio - 1) * 100:.1f}% on the "
        f"batched dispatch (budget "
        f"{(MAX_EFFICIENCY_OVERHEAD - 1) * 100:.0f}%): off "
        f"{t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    return {"off_ms": round(t_off * 1e3, 1),
            "on_ms": round(t_on * 1e3, 1),
            "overhead": round(ratio - 1, 4)}


MAX_NETFAULT_OVERHEAD = 1.02  # on/off runtime ratio (<= 2%)


def check_netfault_overhead() -> dict:
    """The ISSUE 19 perf gate: with a fault plan installed but no
    clause matching the live links, the seam's per-call plan scan may
    cost at most 2% on the serving hop — and that hop is the
    dedupe-enabled one (a caller-supplied ``request_id`` on every
    POST, the idempotent-forwarding wire shape, answered by the
    worker's early dedupe lookup).  Both sides route through
    ``netfault.exchange``; the off side has no plan (the production
    default: one ``plan()`` read), the on side scans clauses and a
    partition that match nothing.  Per-hop work is a socket round
    trip plus a dict hit, so the ratio is noise-dominated: pairwise-
    interleaved off/on reps, min-of-N per side, best-of-attempts —
    the PR-9 methodology."""
    from urllib.parse import urlsplit

    from pydcop_tpu import api
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    rng = np.random.default_rng(19)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP("netfault_bench", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(4)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(3):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]],
            rng.integers(0, 10, size=(3, 3)).astype(float),
            f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    body = json.dumps({
        "dcop": dcop_yaml(dcop),
        "params": {"max_cycles": 50},
        "request_id": "perf-netfault",
    }).encode()
    # Clauses/partition that match nothing on the measured link: the
    # scan runs in full on every hop, injects nothing.
    inactive = netfault.FaultPlan.parse(
        "seed=5;link=*>replica-*,drop=1.0,delay_ms=5;"
        "link=*>*,path=/no-such-endpoint,blackhole=1;"
        "partition=ghost-a/ghost-b")

    handle = api.serve(port=0)
    try:
        parts = urlsplit(handle.url)
        host, port = parts.hostname, parts.port

        def hop() -> None:
            status, _ctype, _payload = netfault.exchange(
                "perf-client", "worker-perf", host, port,
                "POST", "/solve", body=body, timeout=30.0)
            assert status == 202, f"solve hop answered {status}"

        def timed() -> float:
            t0 = time.perf_counter()
            for _ in range(40):
                hop()
            return time.perf_counter() - t0

        netfault.clear()
        hop()   # first delivery executes; every later hop dedupes
        timed()  # warm the server/socket path, outside the clock
        ratio = float("inf")
        t_off = t_on = None
        for _ in range(4):
            offs, ons = [], []
            for _rep in range(5):
                netfault.clear()
                offs.append(timed())
                netfault.install(inactive)
                ons.append(timed())
            netfault.clear()
            t_off, t_on = min(offs), min(ons)
            ratio = min(ratio, t_on / t_off)
            if ratio <= MAX_NETFAULT_OVERHEAD:
                break
        assert inactive.injected() == {}, (
            f"'inactive' plan injected faults: {inactive.injected()}")
    finally:
        netfault.clear()
        handle.stop()
    assert ratio <= MAX_NETFAULT_OVERHEAD, (
        f"inactive netfault plan costs {(ratio - 1) * 100:.1f}% on "
        f"the dedupe-enabled serving hop (budget "
        f"{(MAX_NETFAULT_OVERHEAD - 1) * 100:.0f}%): off "
        f"{t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    return {"off_ms": round(t_off * 1e3, 1),
            "on_ms": round(t_on * 1e3, 1),
            "overhead": round(ratio - 1, 4)}


MAX_FLEETTRACE_OVERHEAD = 1.02  # on/off runtime ratio (<= 2%)


def check_fleettrace_overhead() -> dict:
    """The ISSUE 20 perf gate: fleet tracing ON (context minting,
    header stamping, route-pick/retry instants, the flight tap and
    span shipping on both sides) may cost at most 2% on the routed
    serving hop versus ``PYDCOP_FLEET_TRACE=0``.  The toggle is
    ``FleetRouter.set_fleet_trace`` — the same env-knob flip + worker
    config push an operator gets — so both sides of the pair run the
    honest production path.  Noise discipline is the PR-9 methodology:
    pairwise-interleaved off/on batches, min-of-N per side,
    best-of-attempts, early exit once the budget holds.

    Rider invariant: with tracing ON the pooled ``/fleet/profile``
    ledger must still sum — telemetry that breaks the efficiency
    accounting is worse than no telemetry."""
    from urllib.parse import urlsplit

    from pydcop_tpu import api
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault

    rng = np.random.default_rng(20)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP("fleettrace_bench", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(4)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(3):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]],
            rng.integers(0, 10, size=(3, 3)).astype(float),
            f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    body = json.dumps({
        "dcop": dcop_yaml(dcop),
        "params": {"max_cycles": 50},
        "wait": True,
    }).encode()

    handle = api.serve(port=0, replicas=2, batch_window_s=0.01,
                       heartbeat_s=0.25)
    try:
        router = handle.router
        parts = urlsplit(handle.url)
        host, port = parts.hostname, parts.port

        def hop() -> None:
            status, _ctype, _payload = netfault.exchange(
                "perf-client", "router", host, port,
                "POST", "/solve", body=body, timeout=60.0)
            assert status in (200, 202), \
                f"routed solve hop answered {status}"

        def timed() -> float:
            t0 = time.perf_counter()
            for _ in range(20):
                hop()
            return time.perf_counter() - t0

        router.set_fleet_trace(True)
        hop()    # compile the structure on first delivery
        timed()  # warm the routed socket path, outside the clock
        ratio = float("inf")
        t_off = t_on = None
        for _ in range(4):
            offs, ons = [], []
            for _rep in range(4):
                router.set_fleet_trace(False)
                offs.append(timed())
                router.set_fleet_trace(True)
                ons.append(timed())
            t_off, t_on = min(offs), min(ons)
            ratio = min(ratio, t_on / t_off)
            if ratio <= MAX_FLEETTRACE_OVERHEAD:
                break

        status, _ctype, payload = netfault.exchange(
            "perf-client", "router", host, port,
            "GET", "/fleet/profile", timeout=30.0)
        assert status == 200, f"/fleet/profile answered {status}"
        ledger = json.loads(payload)["ledger"]
        total = max(float(ledger.get("total_s") or 0.0), 1e-9)
        unacct = abs(float(ledger.get("unaccounted_abs_s") or 0.0))
        assert unacct <= 0.05 * total, (
            f"pooled ledger no longer sums with tracing on: "
            f"|unaccounted| {unacct:.4f}s > 5% of {total:.4f}s")
    finally:
        handle.stop()
    assert ratio <= MAX_FLEETTRACE_OVERHEAD, (
        f"fleet tracing costs {(ratio - 1) * 100:.1f}% on the routed "
        f"serving hop (budget "
        f"{(MAX_FLEETTRACE_OVERHEAD - 1) * 100:.0f}%): off "
        f"{t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    return {"off_ms": round(t_off * 1e3, 1),
            "on_ms": round(t_on * 1e3, 1),
            "overhead": round(ratio - 1, 4),
            "ledger_unaccounted_s": round(unacct, 4)}


CEC_MIN_SPEEDUP = 1.2
CEC_N_VARS = 60
CEC_DOMAIN = 8


def build_cec_graph(seed=17, n=CEC_N_VARS, d=CEC_DOMAIN):
    """Seeded low-width instance where CEC provably bites: a banded
    chain whose factor tables carry a +10 offset on the upper half of
    every domain (``m[a][b] = base + off[a] + off[b]``), so those
    values are soft-dominated from every context and the consistency
    pass halves each hypercube axis."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable

    rng = np.random.default_rng(seed)
    dom = Domain("c", "", list(range(d)))
    dcop = DCOP("cec_bench", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    off = np.where(np.arange(d) < d // 2, 0.0, 10.0)
    k = 0
    for i in range(1, n):
        for j in (i - 1, i - 2):
            if j < 0:
                continue
            table = (rng.random((d, d))
                     + off[:, None] + off[None, :])
            dcop.add_constraint(
                NAryMatrixRelation([vs[j], vs[i]], table, f"c{k}"))
            k += 1
    dcop.add_agents([AgentDef("a0")])
    return dcop


def check_cec() -> dict:
    """The ISSUE 17 perf gate: CEC preprocessing must pay for itself
    on the UTIL sweep.  Both engines are warmed (compiles and the
    one-shot dominance pass land outside the clock — serving and the
    portfolio race reuse cached survivors the same way), then CEC-off
    and CEC-on sweeps interleave PAIRWISE (the PR-9 methodology),
    min-of-N per side.  Pass = >= 1.2x sweep throughput OR >= 1
    effective width rung gained; bit-identical assignment always."""
    import math

    from pydcop_tpu.computations_graph import pseudotree as pt
    from pydcop_tpu.engine.dpop import DpopEngine
    from pydcop_tpu.ops.dpop import cec_survivors, tree_stats

    dcop = build_cec_graph()
    tree = pt.build_computation_graph(dcop)
    survivors, meta = cec_survivors(tree, "min")
    assert meta["pruned"] > 0, (
        "CEC pruned nothing on the dominated-value instance "
        f"({meta})")
    raw = tree_stats(tree)["max_elements"]
    shrunk = tree_stats(tree, survivors)["max_elements"]
    # One rung = one domain factor off the largest hypercube: the
    # width-ceiling currency (a problem one rung smaller admits one
    # more separator variable at the same element cap).
    rungs = (math.log(raw / shrunk, CEC_DOMAIN) if shrunk else 0.0)

    on = DpopEngine(tree, mode="min", cec=True)
    off = DpopEngine(tree, mode="min", cec=False)
    res_on = on.run()    # warm: compiles + survivor cache
    res_off = off.run()
    assert res_on.assignment == res_off.assignment, (
        "CEC-on assignment diverged from CEC-off")
    ratio = 0.0
    t_on = t_off = None
    for _ in range(3):  # best-of-attempts damps a noisy neighbor
        offs, ons = [], []
        for _rep in range(4):  # pairwise interleaved
            t0 = time.perf_counter()
            off.run()
            offs.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            on.run()
            ons.append(time.perf_counter() - t0)
        t_off, t_on = min(offs), min(ons)
        ratio = max(ratio, t_off / t_on)
        if ratio >= CEC_MIN_SPEEDUP:
            break
    assert ratio >= CEC_MIN_SPEEDUP or rungs >= 1.0, (
        f"CEC gained only {ratio:.2f}x sweep throughput (need >= "
        f"{CEC_MIN_SPEEDUP}x) and {rungs:.2f} width rungs (need >= "
        f"1): off {t_off * 1e3:.1f}ms -> on {t_on * 1e3:.1f}ms, "
        f"max_elements {raw} -> {shrunk}")
    return {"off_ms": round(t_off * 1e3, 2),
            "on_ms": round(t_on * 1e3, 2),
            "speedup": round(ratio, 2),
            "pruned_values": meta["pruned"],
            "max_elements_raw": raw,
            "max_elements_cec": shrunk,
            "width_rungs_gained": round(rungs, 2)}


PIPELINE_MIN_SPEEDUP = 1.15       # hard gate only with >= 2 CPUs
PIPELINE_MAX_DISABLED_OVERHEAD = 1.02  # on/off wall ratio, always


def check_pipelining() -> dict:
    """The ISSUE 18 perf gate: the pipelined flush (scheduler
    launches bin k+1's device call while bin k's arrays are still in
    flight, decode drained in pickup order) must give BIT-IDENTICAL
    assignments to the synchronous path, cost <= 2% when the overlap
    cannot help, and — where a second core exists to overlap decode
    with execute — run the seeded 4-bin flush >= 1.15x faster.
    On/off runs interleave PAIRWISE (the PR-9 methodology), min-of-N
    per side, best-of-attempts."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef
    from pydcop_tpu.serving.service import SolveService

    def ring(n, seed, d=3):
        rng = np.random.default_rng(seed)
        dom = Domain("c", "", list(range(d)))
        dcop = DCOP(f"pipe_ring{n}_{seed}", objective="min")
        vs = [Variable(f"v{i}", dom) for i in range(n)]
        for v in vs:
            dcop.add_variable(v)
        for k in range(n):
            table = rng.integers(0, 10, size=(d, d)).astype(float)
            dcop.add_constraint(NAryMatrixRelation(
                [vs[k], vs[(k + 1) % n]], table, f"c{k}"))
        dcop.add_agents([AgentDef("a0")])
        return dcop

    # Four structure bins, two requests each: one flush, four
    # pipelined device dispatches.  Cycle count high enough that
    # device work dominates the fixed batch window on both sides.
    dcops = [ring(n, seed)
             for n in (17, 18, 19, 20) for seed in (0, 1)]
    params = {"max_cycles": 2000}

    def burst(service):
        t0 = time.perf_counter()
        ids = [service.submit(d, params=params) for d in dcops]
        res = [service.result(i, wait=120) for i in ids]
        wall = time.perf_counter() - t0
        assert all(r["status"] == "FINISHED" for r in res), res
        return wall, [tuple(sorted(r["assignment"].items()))
                      for r in res]

    on = SolveService(batch_window_s=0.04, max_batch=16,
                      pipeline=True, speculate=False).start()
    off = SolveService(batch_window_s=0.04, max_batch=16,
                       pipeline=False, speculate=False).start()
    try:
        # Warm pass on each side: compiles land outside the clock
        # (the jit cache is process-wide, so one side's warmup warms
        # both — run both anyway so either order is safe).
        _, baseline = burst(off)
        _, warm_on = burst(on)
        assert warm_on == baseline, (
            "pipelined flush diverged from synchronous assignments")
        assert on.pipelined_dispatches > 0, (
            "pipeline=True service never actually pipelined")
        assert off.pipelined_dispatches == 0, (
            "pipeline=False service pipelined anyway")
        overhead = float("inf")
        speedup = 0.0
        t_off = t_on = None
        multicore = (os.cpu_count() or 1) >= 2
        for _ in range(4):  # best-of-attempts damps noisy neighbors
            offs, ons = [], []
            for _rep in range(3):  # pairwise interleaved
                wall, got = burst(off)
                assert got == baseline
                offs.append(wall)
                wall, got = burst(on)
                assert got == baseline
                ons.append(wall)
            t_off, t_on = min(offs), min(ons)
            overhead = min(overhead, t_on / t_off)
            speedup = max(speedup, t_off / t_on)
            if overhead <= PIPELINE_MAX_DISABLED_OVERHEAD and (
                    speedup >= PIPELINE_MIN_SPEEDUP
                    or not multicore):
                break
    finally:
        on.stop()
        off.stop()
    assert overhead <= PIPELINE_MAX_DISABLED_OVERHEAD, (
        f"pipelined flush costs {(overhead - 1) * 100:.1f}% over the "
        f"synchronous path (budget "
        f"{(PIPELINE_MAX_DISABLED_OVERHEAD - 1) * 100:.0f}%): off "
        f"{t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    if multicore:
        # One core cannot overlap decode with execute — the speedup
        # claim is only falsifiable with a second one.
        assert speedup >= PIPELINE_MIN_SPEEDUP, (
            f"pipelined flush gained only {speedup:.2f}x (need >= "
            f"{PIPELINE_MIN_SPEEDUP}x on a multicore box): off "
            f"{t_off * 1e3:.0f}ms -> on {t_on * 1e3:.0f}ms")
    return {"off_ms": round(t_off * 1e3, 1),
            "on_ms": round(t_on * 1e3, 1),
            "speedup": round(speedup, 3),
            "speedup_gated": multicore}


def main() -> int:
    results = {}
    for name, check in (
        ("vectorized_compile", check_vectorized_compile),
        ("structure_cache", check_structure_cache),
        ("autotuner", check_autotuner),
        ("pruning", check_pruning),
        ("decimation", check_decimation),
        ("flight_overhead", check_flight_overhead),
        ("efficiency_overhead", check_efficiency_overhead),
        ("netfault_overhead", check_netfault_overhead),
        ("fleettrace_overhead", check_fleettrace_overhead),
        ("cec", check_cec),
        ("pipelining", check_pipelining),
    ):
        try:
            results[name] = check()
        except AssertionError as e:
            print(f"perf-smoke: {name} FAILED: {e}")
            return 1
    print("perf-smoke: all checks passed")
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
