"""North-star benchmark: MaxSum on 10k-variable graph coloring
(BASELINE.json config #4/#1 scale), device engine vs this repo's OWN
threaded agent runtime on the same problem — the comparison the
reference architecture implies (pydcop/infrastructure/run.py:145
run_local_thread_dcop hosts every computation on an agent thread; the
hot loop is factor_costs_for_var maxsum.py:382 + costs_for_factor :623).

Prints ONE json line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

extra keys: backend ("tpu"/"cpu"), baseline_cycles_per_s, cost-parity
evidence (device vs thread cost on a converged mid-size run), and a
modeled roofline (flops/bytes per superstep, achieved GFLOP/s, MFU vs
v5e bf16 peak, HBM utilization — see pydcop_tpu/engine/roofline.py for
the counting rules and why HBM util is the meaningful number).

Both paths share one problem builder and the same seeded tie-breaking
noise (_stable_noise), so costs are directly comparable.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

N_VARS = 10_000
N_COLORS = 3
DEVICE_CYCLES = 200
SCALE_N_VARS = 1_000_000     # HBM-bound leg (TPU only)
SCALE_CYCLES = 50
THREAD_TIMEOUT_S = 30.0
THREAD_AGENTS = 8
PARITY_VARS = 60
PARITY_SEED = 3
PARITY_TIMEOUT_S = 8.0
# Matched-cycle quality tolerance at 10k vars, as a fraction of the
# constraint count: thread mode stops on wall clock with computations at
# slightly skewed cycles, so mid-descent costs can differ by a few
# cycles' worth of improvement.
QUALITY_TOL_FRAC = 0.025

def build_dcop(n_vars: int, seed: int = 0):
    """n_vars-variable 3-coloring: cost-1 equality penalty per edge,
    ~1.5 edges/var (the round-1 bench problem, now as a real DCOP so
    the agent runtime can solve the identical instance)."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP(f"gc_{n_vars}", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in variables:
        dcop.add_variable(v)
    eq = np.eye(N_COLORS, dtype=np.float64)
    seen = set()
    k = 0
    for _ in range(int(n_vars * 1.5)):
        i, j = rng.choice(n_vars, size=2, replace=False)
        key = (min(i, j), max(i, j))
        if key in seen:
            continue
        seen.add(key)
        dcop.add_constraint(NAryMatrixRelation(
            [variables[i], variables[j]], eq, f"c{k}"))
        k += 1
    dcop.add_agents([AgentDef(f"a{a}") for a in range(THREAD_AGENTS)])
    return dcop


def build_grid_dcop(side: int, seed: int = 0):
    """``side x side`` 4-neighbor grid coloring with random integer
    tables — the locally-connected instance the SHARDED leg measures.
    Random graphs are expanders (no partitioner cuts them well); real
    DCOP deployments (sensor nets, smart grids, meeting graphs) are
    spatially local, and a grid is the canonical local topology:
    a BFS-grown min-edge-cut partition lands a single-digit-percent
    cut, which is the regime where halo exchange beats the
    replicated all-reduce."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP(f"grid_{side}", objective="min")
    variables = [Variable(f"v{i}", dom) for i in range(side * side)]
    for v in variables:
        dcop.add_variable(v)
    k = 0
    for r in range(side):
        for c in range(side):
            i = r * side + c
            for rr, cc in ((r + 1, c), (r, c + 1)):
                if rr < side and cc < side:
                    j = rr * side + cc
                    table = rng.integers(
                        0, 10, size=(N_COLORS, N_COLORS))
                    dcop.add_constraint(NAryMatrixRelation(
                        [variables[i], variables[j]],
                        table.astype(np.float64), f"c{k}"))
                    k += 1
    dcop.add_agents([AgentDef("a0")])
    return dcop


def bench_device(dcop, max_cycles: int, timed: bool = True):
    """Compile + run the device engine; returns (cycles/s, result,
    engine).  With timed=True a warmup run precedes the timed run so
    the number is steady-state execution, not compilation."""
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.engine.runner import MaxSumEngine

    graph, meta = compile_dcop(dcop, noise_level=0.01)
    engine = MaxSumEngine(graph, meta)
    if timed:
        engine.run(max_cycles=max_cycles, stop_on_convergence=False)
    res = engine.run(max_cycles=max_cycles, stop_on_convergence=False)
    cps = res.cycles / res.time_s if res.time_s > 0 else 0.0
    return cps, res, engine


def bench_thread(dcop, timeout: float):
    """The repo's own threaded agent runtime on the same DCOP: one
    orchestrator + THREAD_AGENTS OrchestratedAgent threads, in-process
    transport, computations round-robined over agents.  Returns
    (cycles/s, completed cycles, cost at stop, assignment)."""
    from pydcop_tpu.algorithms import AlgorithmDef, load_algorithm_module
    from pydcop_tpu.computations_graph import load_graph_module
    from pydcop_tpu.distribution.objects import Distribution
    from pydcop_tpu.infrastructure.run import run_local_thread_dcop

    algo_def = AlgorithmDef.build_with_default_param("maxsum", mode="min")
    module = load_algorithm_module("maxsum")
    cg = load_graph_module(module.GRAPH_TYPE).build_computation_graph(dcop)
    agents = sorted(dcop.agents)
    mapping = {a: [] for a in agents}
    for i, node in enumerate(cg.nodes):
        mapping[agents[i % len(agents)]].append(node.name)
    dist = Distribution(mapping)

    orch = run_local_thread_dcop(algo_def, cg, dist, dcop)
    try:
        if not orch.wait_ready(30):
            raise RuntimeError("agents not ready")
        orch.deploy_computations()
        t0 = time.perf_counter()
        orch.run(timeout=timeout)
        elapsed = time.perf_counter() - t0
        orch.stop_agents(10)
        metrics = orch.end_metrics()
        cycles = int(metrics["cycle"])
        cost = float(metrics["cost"]) if metrics["cost"] is not None \
            else float("nan")
        assignment = {
            k: v for k, v in metrics["assignment"].items()
            if k in dcop.variables
        }
        return cycles / elapsed, cycles, cost, assignment
    finally:
        orch.stop_agents(5)
        orch.stop()


def exact_parity():
    """Semantic-equivalence leg of the north-star claim: on a problem
    the BSP trajectory freezes on (send-suppression quiets every edge),
    the device engine and the threaded agent runtime must produce the
    IDENTICAL assignment, hence identical cost.  Larger loopy instances
    oscillate within the stability band and the thread runtime stops on
    wall clock mid-oscillation, so exactness is asserted here and a
    matched-cycle quality bound is asserted at full scale."""
    dcop = build_dcop(PARITY_VARS, seed=PARITY_SEED)
    _, thread_cycles, thread_cost, thread_asg = bench_thread(
        dcop, PARITY_TIMEOUT_S)
    _, res, _ = bench_device(
        dcop, max_cycles=max(thread_cycles, 50), timed=False)
    device_cost, _ = dcop.solution_cost(res.assignment)
    differing = [
        v for v in thread_asg if thread_asg[v] != res.assignment[v]
    ]
    if differing or device_cost != thread_cost:
        print(
            f"bench: EXACT PARITY FAILED device={device_cost} "
            f"thread={thread_cost} differing_vars={len(differing)}",
            file=sys.stderr,
        )
        sys.exit(1)
    return device_cost, thread_cost


def bench_scale(n_vars: int = SCALE_N_VARS, edge_factor: float = 1.5,
                cycles: int = SCALE_CYCLES, aggregation: str = "scatter",
                layout: str = "edge", return_values: bool = False,
                detail: bool = False):
    """HBM-bound scale leg: a synthetic 1M-variable / 1.5M-factor
    3-coloring whose ~190 MB working set cannot stay VMEM-resident, so
    the measured rate reflects real HBM streaming (the 10k north-star
    problem fits in VMEM and proves nothing about bandwidth).  Arrays
    are built directly (building 1.5M Python constraint objects would
    dominate the bench); the superstep math is identical.

    ``aggregation`` selects the variable-aggregation strategy
    (engine/compile.build_aggregation_arrays); the headline leg runs
    the strategy benchmarks/exp_aggregation.py measured fastest on the
    target backend.  ``layout="lane"`` runs the lane-major superstep
    (ops/maxsum_lane.py; scatter aggregation only) — the layout A/B is
    benchmarks/exp_layout.py.

    Timing is the MARGINAL per-cycle rate via two-point differencing
    (engine/timing.py): the per-call constant (dispatch + sync +
    fetch) would otherwise be reported as if it were HBM streaming
    time.  With ``cycles < 10`` (parity-only test runs) a single
    fully-synced call is timed instead.

    Returns (cycles/s, graph), or (cycles/s, graph, values) with
    ``return_values=True`` (a full ``cycles``-run's selected assignment
    as numpy — exp_layout's agreement column), or with ``detail=True``
    a trailing dict {sec_per_cycle, fixed_overhead_s}.  With the
    default edge layout the graph feeds roofline accounting; a lane
    graph does NOT (the roofline counters unpack edge-major shapes
    positionally and would count garbage — they reject LaneGraph) and
    is returned for value-parity runs only.
    """
    from functools import partial

    import jax

    from pydcop_tpu.engine.compile import (
        BIG,
        CompiledFactorGraph,
        FactorBucket,
        build_aggregation_arrays,
    )
    from pydcop_tpu.engine.timing import (
        sync,
        timed_call,
        warmed_marginal,
    )
    from pydcop_tpu.ops import maxsum as ops

    if n_vars < 2:
        raise ValueError("bench_scale needs n_vars >= 2")
    rng = np.random.default_rng(7)
    n_factors = int(n_vars * edge_factor)
    var_ids = rng.integers(
        0, n_vars, size=(n_factors, 2)).astype(np.int32)
    # Redraw self-loops (v1 == v2) so the instance is a well-formed
    # coloring problem and the cost semantics stay meaningful.
    loop = var_ids[:, 0] == var_ids[:, 1]
    while loop.any():
        var_ids[loop, 1] = rng.integers(
            0, n_vars, size=int(loop.sum())).astype(np.int32)
        loop = var_ids[:, 0] == var_ids[:, 1]
    eq = np.eye(N_COLORS, dtype=np.float32)
    costs = np.ascontiguousarray(
        np.broadcast_to(eq, (n_factors, N_COLORS, N_COLORS)))
    var_costs = np.full((n_vars + 1, N_COLORS), BIG, np.float32)
    var_costs[:-1] = rng.random((n_vars, N_COLORS)) * 0.01
    var_valid = np.zeros((n_vars + 1, N_COLORS), bool)
    var_valid[:-1] = True
    buckets = (FactorBucket(costs, var_ids),)
    perm, sorted_seg, starts, ends, ell = build_aggregation_arrays(
        buckets, n_vars + 1, aggregation)
    graph = CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid, buckets=buckets,
        agg_perm=perm, agg_sorted_seg=sorted_seg,
        agg_starts=starts, agg_ends=ends, agg_ell=ell,
    )
    if layout == "lane":
        if aggregation != "scatter":
            raise ValueError("layout='lane' requires scatter "
                             "aggregation")
        from pydcop_tpu.ops import maxsum_lane as lane_ops

        graph = jax.device_put(lane_ops.to_lane_graph(graph))
        run = lane_ops.run_maxsum
    else:
        graph = jax.device_put(graph)
        run = ops.run_maxsum

    def jitted(c):
        return jax.jit(partial(run, max_cycles=c,
                               stop_on_convergence=False))

    if cycles >= 10:
        lo = max(1, cycles // 5)
        sec_per_cycle, fixed, (state, values) = warmed_marginal(
            jitted, lo, cycles, args=(graph,), reps=3)
        cps = 1.0 / sec_per_cycle if sec_per_cycle > 0 else 0.0
    else:
        # Parity-only runs (tests): a single fully-synced call, warmed
        # so compile time stays out of the window.
        fn = jitted(cycles)
        sync(fn(graph))
        (state, values), elapsed = timed_call(fn, graph)
        sec_per_cycle = elapsed / int(state.cycle)
        fixed = 0.0
        cps = int(state.cycle) / elapsed
    info = {"sec_per_cycle": sec_per_cycle, "fixed_overhead_s": fixed}
    # The flags COMPOSE (ADVICE r5: return_values used to shadow
    # detail and silently drop the timing dict): values come before
    # info, so every single-flag caller keeps its 3-tuple shape and
    # both-flags callers get (cps, graph, values, info).
    out = [cps, graph]
    if return_values:
        out.append(np.asarray(jax.device_get(values)))
    if detail:
        out.append(info)
    return tuple(out) if len(out) > 2 else (cps, graph)


# Sharded-superstep leg: the partitioned engine (min-edge-cut
# partition + shard_map halo exchange, engine/sharding.py) on a
# locally-connected grid.  On a multi-chip TPU host the mesh is the
# real device list; otherwise the leg runs in a CHILD process with
# JAX_PLATFORMS=cpu and
# XLA_FLAGS=--xla_force_host_platform_device_count=8 (the backend
# reads the flag when it starts, so the forced mesh cannot be
# conjured in-process) — the same recipe CI parity tests use.
SHARDED_SIDE = 64            # 64x64 grid = 4096 vars, 8064 factors
SHARDED_SHARDS = 8
SHARDED_CYCLES = 100
SHARDED_CHILD_TIMEOUT_S = 600
SCALE_SMOKE_N_VARS = 50_000  # CPU smoke of the 1M-var scale leg
SCALE_SMOKE_CYCLES = 12


def bench_sharded(n_shards: int = SHARDED_SHARDS):
    """Steady-state cycles/s of the partitioned engine on the grid
    instance, plus the partition/communication evidence: cut
    fraction, halo-vs-replicated exchange volume.  Caller guarantees
    >= n_shards devices exist (real or forced-host)."""
    from pydcop_tpu.algorithms.maxsum import build_engine

    dcop = build_grid_dcop(SHARDED_SIDE)
    engine = build_engine(dcop, {"noise": 0.01}, shards=n_shards)
    engine.run(max_cycles=SHARDED_CYCLES, stop_on_convergence=False)
    res = engine.run(
        max_cycles=SHARDED_CYCLES, stop_on_convergence=False)
    cps = res.cycles / res.time_s if res.time_s > 0 else 0.0
    m = res.metrics
    out = {
        "maxsum_cycles_per_sec_sharded": round(cps, 2),
        "sharded_n_vars": SHARDED_SIDE * SHARDED_SIDE,
        "sharded_n_shards": n_shards,
        "sharded_edge_cut_fraction": round(
            m["edge_cut_fraction"], 4),
        "sharded_halo_elems": m[
            "halo_exchange_elems_per_superstep"],
        "sharded_replicated_elems": m[
            "replicated_allreduce_elems_per_superstep"],
        "sharded_balance": round(m["balance"], 3),
    }
    # Shard-loss recovery latency (ISSUE 8): inject a device loss on
    # a FRESH engine for the same instance and report the engine's
    # repartition + state-remap wall time — the time a mid-solve
    # device failure costs on this backend before compute resumes.
    try:
        from pydcop_tpu.resilience.recovery import RecoveryPolicy

        rec_res = build_engine(
            dcop, {"noise": 0.01}, shards=n_shards,
        ).run_checkpointed(
            max_cycles=30, segment_cycles=10,
            stop_on_convergence=False,
            recovery=RecoveryPolicy(trip_shard=((10, 1),)))
        out["shard_recovery_s"] = \
            rec_res.metrics["shard_recovery_s"]
    except Exception as exc:  # noqa: BLE001 — auxiliary sub-leg
        print(f"bench: shard-recovery leg failed ({exc}); "
              "continuing", file=sys.stderr)
        out["shard_recovery_s"] = None
        out["shard_recovery_error"] = \
            f"{type(exc).__name__}: {exc}"[:200]
    return out


def _bench_sharded_forced():
    """CPU path: run bench_sharded in a child with 8 forced host
    devices (the flag must be set before jax imports).  Returns the
    sharded keys, or a None-valued entry with the error — the
    sharded leg never kills the headline line."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{SHARDED_SHARDS}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYDCOP_BENCH_SHARDED_CHILD"] = "1"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            timeout=SHARDED_CHILD_TIMEOUT_S, stdout=subprocess.PIPE,
            text=True,
        )
    except subprocess.TimeoutExpired:
        return {"maxsum_cycles_per_sec_sharded": None,
                "sharded_error": "child timeout"}
    for line in (proc.stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if "maxsum_cycles_per_sec_sharded" in parsed:
                parsed["sharded_backend"] = "cpu"
                parsed["sharded_forced_host_devices"] = SHARDED_SHARDS
                return parsed
    return {"maxsum_cycles_per_sec_sharded": None,
            "sharded_error": f"child rc={proc.returncode}, "
                             "no result line"}


# Time-to-target-cost leg (ISSUE 10): the headline number of the
# work-reduction stack.  A loopy LARGE-DOMAIN coloring (the regime
# branch-and-bound pruning targets) is traced once to find the
# reference cost — the final (converged-and-frozen) cost of the
# fixed-budget run itself, deterministic for the fixed seed; the
# timed quantity is a warmed PRUNED run of the full TTC_CYCLES budget
# (the serving dispatch shape: batched dispatches never early-exit,
# so the budget wall IS the time the answer takes).  This changes
# what the bench optimizes from cycles/sec to wall-clock to a known
# solution quality — judged by tools/bench_sentinel.py as a
# lower-is-better family per backend.
TTC_N_VARS = 240
TTC_DOMAIN = 128
TTC_EDGE_FACTOR = 1.5
TTC_CYCLES = 160
TTC_UNARY_SPREAD = 400


def build_ttc_graph(seed: int = 11):
    """Loopy D=TTC_DOMAIN coloring with integer unary costs, built
    directly as arrays (same recipe as bench_scale): equality penalty
    1 per edge, unary integers in [0, TTC_UNARY_SPREAD) — integer
    tables keep the pruned trajectory bit-identical to dense
    (ops/maxsum)."""
    from pydcop_tpu.engine.compile import (
        BIG,
        CompiledFactorGraph,
        FactorBucket,
    )

    rng = np.random.default_rng(seed)
    n_factors = int(TTC_N_VARS * TTC_EDGE_FACTOR)
    var_ids = rng.integers(
        0, TTC_N_VARS, size=(n_factors, 2)).astype(np.int32)
    loop = var_ids[:, 0] == var_ids[:, 1]
    var_ids[loop, 1] = (var_ids[loop, 0] + 1) % TTC_N_VARS
    eye = np.eye(TTC_DOMAIN, dtype=np.float32)
    costs = np.ascontiguousarray(np.broadcast_to(
        eye, (n_factors, TTC_DOMAIN, TTC_DOMAIN))).copy()
    var_costs = np.full((TTC_N_VARS + 1, TTC_DOMAIN), BIG, np.float32)
    var_costs[:-1] = rng.integers(
        0, TTC_UNARY_SPREAD,
        size=(TTC_N_VARS, TTC_DOMAIN)).astype(np.float32)
    var_valid = np.zeros((TTC_N_VARS + 1, TTC_DOMAIN), bool)
    var_valid[:-1] = True
    return CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid,
        buckets=(FactorBucket(costs, var_ids),))


def bench_time_to_cost():
    """{maxsum_time_to_cost_ms, ...}: wall-clock to the reference cost
    under the SERVING dispatch shape — a fixed ``TTC_CYCLES`` budget
    with no convergence stop (batched dispatches never early-exit:
    engine/batch.run_stacked), so the request's time-to-answer IS the
    full-budget wall and the reference cost is the budget run's final
    (converged-and-frozen) cost.  The pruned trajectory is
    bit-identical to dense, so the ratio against ``ttc_dense_ms``
    isolates the per-cycle work reduction: after the transient the
    survivor sets collapse and most of the budget runs the compacted
    kernel.  Never kills the headline line (caller wraps)."""
    from functools import partial

    import jax

    from pydcop_tpu.engine.timing import sync, timed_call
    from pydcop_tpu.ops import maxsum as ops

    graph = jax.device_put(build_ttc_graph())
    trace_fn = jax.jit(partial(
        ops.run_maxsum_trace, max_cycles=TTC_CYCLES,
        stop_on_convergence=False))
    _state, _values, costs = sync(trace_fn(graph))
    costs = np.asarray(costs)
    ref = float(costs[-1])
    below = np.nonzero(costs <= ref)[0]
    cycles_to_ref = int(below[0]) + 1 if below.size else TTC_CYCLES

    def timed_run(prune: bool) -> float:
        fn = jax.jit(partial(
            ops.run_maxsum, max_cycles=TTC_CYCLES,
            stop_on_convergence=False, prune=prune))
        sync(fn(graph))  # compile + warm
        best = float("inf")
        for _ in range(3):
            _out, elapsed = timed_call(fn, graph)
            best = min(best, elapsed)
        return best

    pruned_s = timed_run(True)
    dense_s = timed_run(False)
    return {
        "maxsum_time_to_cost_ms": round(pruned_s * 1e3, 2),
        "ttc_dense_ms": round(dense_s * 1e3, 2),
        "ttc_ref_cost": ref,
        "ttc_cycles": cycles_to_ref,
        "ttc_n_vars": TTC_N_VARS,
        "ttc_domain": TTC_DOMAIN,
    }


# Serving-throughput leg: closed-loop clients firing small random
# coloring DCOPs at the solve service (pydcop_tpu/serving).  Small
# problems + several structures is the multi-tenant traffic shape the
# service exists for; the number that matters is sustained
# problems/sec with per-request latency percentiles.
SERVE_N_VARS = (24, 30)         # two structure bins
SERVE_POOL_PER_STRUCT = 6       # distinct instances per structure
SERVE_CLIENTS = 4
SERVE_DURATION_S = 4.0
SERVE_MAX_CYCLES = 60


def bench_serving():
    """Sustained service throughput: SERVE_CLIENTS closed-loop client
    threads submit-and-wait random coloring DCOPs for
    SERVE_DURATION_S.  Returns {serve_problems_per_sec, serve_p50_ms,
    serve_p99_ms, serve_batched_fraction} (None values when the
    service completed nothing — never crashes the bench)."""
    import threading

    from pydcop_tpu.serving.service import SolveService

    pool = {
        n: [build_dcop_small(n, seed) for seed in
            range(SERVE_POOL_PER_STRUCT)]
        for n in SERVE_N_VARS
    }
    service = SolveService(max_queue=512, batch_window_s=0.005,
                           max_batch=16).start()
    try:
        params = {"max_cycles": SERVE_MAX_CYCLES}
        # Warm: one dispatch per structure compiles the batched
        # programs so the timed window measures steady state.
        for dcops in pool.values():
            rid = service.submit(dcops[0], params=params)
            service.result(rid, wait=60)
        latencies = []
        completed = [0]
        lock = threading.Lock()
        t_end = time.perf_counter() + SERVE_DURATION_S

        def client(idx):
            n = SERVE_N_VARS[idx % len(SERVE_N_VARS)]
            i = 0
            while time.perf_counter() < t_end:
                dcop = pool[n][i % SERVE_POOL_PER_STRUCT]
                i += 1
                t0 = time.perf_counter()
                rid = service.submit(dcop, params=params)
                res = service.result(rid, wait=60)
                t1 = time.perf_counter()
                if res is not None and res["status"] == "FINISHED":
                    with lock:
                        latencies.append(t1 - t0)
                        completed[0] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(SERVE_CLIENTS)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=SERVE_DURATION_S + 120)
        elapsed = time.perf_counter() - t_start
        stats = service.stats()
    finally:
        service.stop(drain=False)
    if not latencies or elapsed <= 0:
        return {"serve_problems_per_sec": None}
    lat_ms = np.asarray(latencies) * 1e3
    p99_exemplar = (stats.get("latency_exemplars") or {}).get("p99")
    return {
        "serve_problems_per_sec": round(completed[0] / elapsed, 2),
        "serve_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "serve_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "serve_requests": completed[0],
        "serve_batched_fraction": round(
            stats["batched_dispatches"] / stats["dispatches"], 3)
            if stats["dispatches"] else None,
        # The p99 bucket's exemplar: a flagged regression in the
        # sentinel points at a concrete request trace to open.
        "exemplar_trace_id": (p99_exemplar or {}).get("trace_id"),
        # The efficiency plane's verdict on the leg (ISSUE 14):
        # backend-honest attainment + useful-work fraction + the
        # where-the-time-went component sums, so a throughput number
        # always ships with the evidence of HOW the device time was
        # spent.  Detail key — the sentinel ignores it.
        "serve_efficiency": stats.get("efficiency"),
    }


# Crash-recovery replay leg (ISSUE 8): how long a --recover start
# takes to scan + compact the journal and push REPLAY_N acknowledged
# requests back through the queue — the downtime a serve-process
# crash adds before the service answers again.
REPLAY_N = 8
REPLAY_N_VARS = 24
REPLAY_MAX_CYCLES = 60


def bench_recovery_replay():
    """Time a journal crash-recovery start: REPLAY_N accepted-but-
    unfinished records on disk, ``SolveService(recover=True).start()``
    timed (scan, torn-tail handling, compaction, re-compile, enqueue
    — everything between process start and the queue being live
    again).  Returns {serve_recovery_replay_s, serve_recovery_replayed}
    (None-valued on failure — never kills the headline line)."""
    import shutil
    import tempfile

    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving.journal import (
        RequestJournal,
        accepted_record,
    )
    from pydcop_tpu.serving.service import SolveService

    journal_dir = tempfile.mkdtemp(prefix="bench_replay_")
    try:
        jnl = RequestJournal(journal_dir)
        for i in range(REPLAY_N):
            jnl.append(accepted_record(
                f"r{i}", dcop_yaml(build_dcop_small(REPLAY_N_VARS, i)),
                {"max_cycles": REPLAY_MAX_CYCLES}))
        jnl.close()
        service = SolveService(journal_dir=journal_dir, recover=True,
                               batch_window_s=0.005, max_batch=16)
        t0 = time.perf_counter()
        service.start()
        replay_s = time.perf_counter() - t0
        try:
            for i in range(REPLAY_N):
                service.result(f"r{i}", wait=120)
        finally:
            service.stop(drain=False)
        return {
            "serve_recovery_replay_s": round(replay_s, 4),
            "serve_recovery_replayed": REPLAY_N,
        }
    finally:
        shutil.rmtree(journal_dir, ignore_errors=True)


# Stateful-session leg (ISSUE 13): the dynamic-DCOP serving workload.
# A warm DynamicMaxSumEngine absorbs a seeded change_factor stream;
# per event we time wall-clock until the warm trajectory RECOVERS the
# cost a cold re-solve of the mutated problem reaches, against that
# cold re-solve itself ON THE SAME COMPILED PROGRAM (state reset, not
# a rebuilt engine — isolating the warm-start message benefit from
# compile-cache effects, which would flatter warm for free).
SESSION_N_VARS = 48
SESSION_EVENTS = 16
SESSION_MAX_CYCLES = 400
SESSION_SEGMENT_CYCLES = 25


def bench_sessions():
    """Warm vs cold after scenario events.  Emits
    ``session_time_to_recovered_cost_ms`` (median over the event
    stream, LOWER is better — sentinel family ``session_recovery``),
    ``session_events_per_sec`` (sustained apply+re-converge rate —
    family ``session_events``), the cold-baseline median and the
    warm/cold speedup.  None-valued on failure — never kills the
    headline line."""
    from pydcop_tpu.engine.dynamic import build_dynamic_engine

    rng = np.random.default_rng(1306)
    base = build_dcop_small(SESSION_N_VARS, 0)
    params = {"noise": 0.0}
    warm = build_dynamic_engine(base, params)
    cold = build_dynamic_engine(base, params)
    # Converge the initial problem, then run one throwaway
    # SEGMENT-sized call: max_cycles is part of the superstep
    # program's jit key, so the timed warm loop (segment-sized runs)
    # and the timed cold runs (full-budget runs) each need their
    # program compiled HERE or the first timed event pays a compile.
    warm.run(max_cycles=SESSION_MAX_CYCLES)
    warm.run(max_cycles=SESSION_SEGMENT_CYCLES)
    cold.run(max_cycles=SESSION_MAX_CYCLES)
    names = sorted(warm.factors)
    warm_ms, cold_ms, matched = [], [], 0
    warm_wall = 0.0
    for _ in range(SESSION_EVENTS):
        name = names[int(rng.integers(len(names)))]
        scope = warm.factors[name].dimensions
        table = rng.integers(
            0, 10, size=tuple(len(v.domain) for v in scope)
        ).astype(float)
        from pydcop_tpu.dcop.relations import NAryMatrixRelation

        # Cold baseline: same edit, messages thrown away.
        cold.change_factor(
            name, NAryMatrixRelation(list(scope), table, name))
        cold._state = None
        t0 = time.perf_counter()
        cres = cold.run(max_cycles=SESSION_MAX_CYCLES)
        cold_s = time.perf_counter() - t0
        cold_cost = cold.cost(cres.assignment)
        # Warm path: apply + re-converge from the pre-event fixpoint,
        # in anytime segments, until the cold-solve cost is recovered
        # (or the warm fixpoint is reached — a warm run may settle at
        # a different local optimum).
        t0 = time.perf_counter()
        warm.change_factor(
            name, NAryMatrixRelation(list(scope), table, name))
        recovered_cost = None
        for _seg in range(
                SESSION_MAX_CYCLES // SESSION_SEGMENT_CYCLES + 1):
            wres = warm.run(max_cycles=SESSION_SEGMENT_CYCLES)
            recovered_cost = warm.cost(wres.assignment)
            if recovered_cost <= cold_cost + 1e-9 or wres.converged:
                break
        warm_s = time.perf_counter() - t0
        warm_wall += warm_s
        warm_ms.append(warm_s * 1e3)
        cold_ms.append(cold_s * 1e3)
        if recovered_cost is not None \
                and recovered_cost <= cold_cost + 1e-9:
            matched += 1
    warm_med = float(np.median(warm_ms))
    cold_med = float(np.median(cold_ms))
    return {
        "session_time_to_recovered_cost_ms": round(warm_med, 3),
        "session_cold_resolve_ms": round(cold_med, 3),
        "session_warm_speedup": (round(cold_med / warm_med, 2)
                                 if warm_med > 0 else None),
        "session_events_per_sec": (
            round(SESSION_EVENTS / warm_wall, 2)
            if warm_wall > 0 else None),
        "session_events": SESSION_EVENTS,
        # Fraction of events where warm re-converged to a cost at
        # least as good as the cold re-solve — the quality guard on
        # the speed claim.
        "session_cost_match_fraction": round(
            matched / SESSION_EVENTS, 3),
    }


def build_dcop_small(n_vars: int, seed: int):
    """Ring + chord coloring with random cost tables — the serving
    bench's per-request problem (same topology per n_vars, so same
    structure bin; different tables per seed)."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP(f"serve_{n_vars}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    edges = [(i, (i + 1) % n_vars) for i in range(n_vars)]
    edges += [(i, (i + n_vars // 2) % n_vars)
              for i in range(0, n_vars, 3)]
    for k, (i, j) in enumerate(edges):
        table = rng.integers(0, 10, size=(N_COLORS, N_COLORS))
        dcop.add_constraint(NAryMatrixRelation(
            [vs[i], vs[j]], table.astype(float), f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


# Mixed-structure serving leg (ISSUE 11): zipf-distributed DISTINCT
# topologies — the production-shaped traffic on which pure structure
# binning degenerates to batch-size-1.  The leg runs the same seeded
# request stream twice, envelope packing ON and OFF, so the JSON line
# carries both the envelope throughput and the no-envelope baseline it
# must beat.
SERVE_MIXED_STRUCTS = 24
SERVE_MIXED_CLIENTS = 8
SERVE_MIXED_DURATION_S = 4.0
SERVE_MIXED_WINDOW_S = 0.005
SERVE_MIXED_MAX_CYCLES = 60
SERVE_MIXED_ZIPF_A = 1.05


def build_dcop_mixed(struct_idx: int, seed: int):
    """One of SERVE_MIXED_STRUCTS structurally DISTINCT small
    colorings: the ring size (``14 + 3*struct_idx`` — distinct per
    index, which alone guarantees distinct structure signatures) plus
    ``struct_idx % 4`` half-way chords, so the edge count varies too;
    different seeds only change cost tables."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    n_vars = 14 + 3 * struct_idx
    dom = Domain("colors", "color", list(range(N_COLORS)))
    dcop = DCOP(f"mix{struct_idx}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    edges = [(i, (i + 1) % n_vars) for i in range(n_vars)]
    edges += [(i, (i + n_vars // 2) % n_vars)
              for i in range(struct_idx % 4)]
    seen = set()
    for k, (i, j) in enumerate(edges):
        if i == j or (min(i, j), max(i, j)) in seen:
            continue
        seen.add((min(i, j), max(i, j)))
        table = rng.integers(0, 10, size=(N_COLORS, N_COLORS))
        dcop.add_constraint(NAryMatrixRelation(
            [vs[i], vs[j]], table.astype(float), f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def bench_serving_mixed():
    """Sustained throughput under zipf-diverse structures, envelope
    packing ON vs OFF on the same seeded stream.  Emits
    ``serve_mixed_problems_per_sec`` (the sentinel family) +
    latency percentiles + ``serve_mixed_batched_fraction`` (requests
    that shared a device dispatch — ~0 without envelopes on this
    traffic) and the no-envelope baseline keys.  Also emits
    ``serve_overlap_fraction`` (ISSUE 18): the measured-window
    fraction of device execute wall the pipelined scheduler hid
    decode work under."""
    import threading

    from pydcop_tpu.observability.efficiency import (
        tracker as efficiency_tracker,
    )
    from pydcop_tpu.serving.service import SolveService

    # Structure frequencies: zipf over ranks, so a couple of
    # structures dominate and a long tail stays rare — the worst case
    # for pure structure binning (the tail never coalesces).
    ranks = np.arange(1, SERVE_MIXED_STRUCTS + 1, dtype=float)
    probs = ranks ** -SERVE_MIXED_ZIPF_A
    probs /= probs.sum()
    pool = {
        s: [build_dcop_mixed(s, seed) for seed in range(4)]
        for s in range(SERVE_MIXED_STRUCTS)
    }

    def run_once(envelope_packing: bool,
                 duration_s: float = SERVE_MIXED_DURATION_S,
                 pipeline: bool = True):
        service = SolveService(
            max_queue=512, batch_window_s=SERVE_MIXED_WINDOW_S,
            max_batch=16, pipeline=pipeline, speculate=False,
            envelope_packing=envelope_packing).start()
        try:
            params = {"max_cycles": SERVE_MIXED_MAX_CYCLES}
            # Warm pass 1: one request per structure, submit-and-WAIT
            # so each dispatches solo — compiles the layouts and the
            # per-structure solo programs (what leftover singleton
            # groups and the whole no-envelope run reuse; submitted
            # together they would coalesce into one packed dispatch
            # and leave every solo program cold).
            for s in range(SERVE_MIXED_STRUCTS):
                service.result(
                    service.submit(pool[s][0], params=params),
                    wait=60)
            # Warm pass 1b: exact-tier bin programs — same-structure
            # pairs for every structure, plus bin-4 for the zipf head
            # (the sizes structure collisions actually produce).
            for s in range(SERVE_MIXED_STRUCTS):
                for size in ((2, 4) if s < 6 else (2,)):
                    burst = [service.submit(pool[s][i % 4],
                                            params=params)
                             for i in range(size)]
                    for rid in burst:
                        service.result(rid, wait=60)
            # Warm pass 2: concurrent mixed bursts of several sizes —
            # compiles the packed-union programs on the rungs real
            # group compositions land on (binning.UNION_LADDER bounds
            # these; v and row rungs correlate, so a spread of burst
            # sizes covers the set).  Exact-tier bin programs warm
            # organically in the discardable pre-runs below — the jit
            # cache is process-global, so without identical warm
            # treatment whichever measured run went first would eat
            # every compile and the comparison would be ordering
            # noise, not packing.
            for size in (2, 3, 5, 8, 12, SERVE_MIXED_STRUCTS):
                burst = [service.submit(pool[s % SERVE_MIXED_STRUCTS]
                                        [1], params=params)
                         for s in range(size)]
                for rid in burst:
                    service.result(rid, wait=60)
            stats0 = service.stats()
            # Window-scoped efficiency ledger (ISSUE 18): the warm
            # passes above dispatch and decode too, so the overlap
            # fraction must come from a tracker cleared at the
            # measured window's start, not the service-lifetime
            # /stats ratio.
            efficiency_tracker.clear()
            latencies = []
            completed = [0]
            lock = threading.Lock()
            t_end = time.perf_counter() + duration_s

            def client(idx):
                rng = np.random.default_rng(1000 + idx)
                i = 0
                while time.perf_counter() < t_end:
                    s = int(rng.choice(SERVE_MIXED_STRUCTS, p=probs))
                    dcop = pool[s][i % 4]
                    i += 1
                    t0 = time.perf_counter()
                    rid = service.submit(dcop, params=params)
                    res = service.result(rid, wait=60)
                    t1 = time.perf_counter()
                    if res is not None and res["status"] == "FINISHED":
                        with lock:
                            latencies.append(t1 - t0)
                            completed[0] += 1

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(SERVE_MIXED_CLIENTS)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=duration_s + 120)
            elapsed = time.perf_counter() - t_start
            stats = service.stats()
            rollup = efficiency_tracker.rollup()
        finally:
            service.stop(drain=False)
        if not latencies or elapsed <= 0:
            return None
        lat_ms = np.asarray(latencies) * 1e3
        # Window-only ledger deltas: the warm passes batched too and
        # must not inflate the fraction.
        batched = (stats["batched_requests"]
                   - stats0["batched_requests"])
        return {
            "pps": round(completed[0] / elapsed, 2),
            "p50": round(float(np.percentile(lat_ms, 50)), 2),
            "p99": round(float(np.percentile(lat_ms, 99)), 2),
            "requests": completed[0],
            # Fraction of completed requests that SHARED their device
            # dispatch — the number that collapses on this traffic
            # without the envelope tier.
            "batched_fraction": round(
                min(batched / completed[0], 1.0), 3)
                if completed[0] else None,
            "envelope_dispatches": (stats["envelope_dispatches"]
                                    - stats0["envelope_dispatches"]),
            "lane_dispatches": (stats["lane_dispatches"]
                                - stats0["lane_dispatches"]),
            # Measured-window decode/dispatch overlap: fraction of
            # device execute wall the pipelined scheduler hid decode
            # work under (0.0 with --no_pipeline).
            "overlap_fraction": rollup.get(
                "pipeline_overlap_fraction"),
            "pipelined_dispatches": (rollup.get("pipeline") or
                                     {}).get("dispatches", 0),
        }

    # Discardable pre-runs (1 s each): the jit caches and process
    # state are GLOBAL, so whichever measured run went first would
    # eat every residual compile and donate its warmth to the other.
    # After one short pass per configuration both measured runs see
    # the same fully-warmed process.
    run_once(True, duration_s=2.0)
    run_once(False, duration_s=2.0)
    on = run_once(True)
    off = run_once(False)
    if on is None:
        return {"serve_mixed_problems_per_sec": None}
    out = {
        "serve_mixed_problems_per_sec": on["pps"],
        "serve_mixed_p50_ms": on["p50"],
        "serve_mixed_p99_ms": on["p99"],
        "serve_mixed_requests": on["requests"],
        "serve_mixed_batched_fraction": on["batched_fraction"],
        "serve_mixed_envelope_dispatches": on["envelope_dispatches"],
        "serve_mixed_lane_dispatches": on["lane_dispatches"],
        # Sentinel family ``serve_overlap`` (ISSUE 18): measured-
        # window pipelined decode/dispatch overlap fraction.
        "serve_overlap_fraction": on["overlap_fraction"],
        "serve_overlap_pipelined_dispatches":
            on["pipelined_dispatches"],
    }
    if off is not None:
        out["serve_mixed_baseline_problems_per_sec"] = off["pps"]
        out["serve_mixed_baseline_batched_fraction"] = \
            off["batched_fraction"]
    return out


# Fleet-serving leg (ISSUE 15): aggregate problems/sec through the
# replicated serve plane — REAL worker subprocesses behind the
# structure-affinity router — at replicas=1/2/4 on the same seeded
# mixed-structure stream, plus the affinity-vs-round-robin A/B at
# replicas=2.  replicas=1 also runs THROUGH the router so every leg
# pays the same wire overhead and the speedup isolates replication.
FLEET_STRUCTS = (20, 24, 28, 32)
FLEET_POOL_PER_STRUCT = 4
FLEET_MAX_CYCLES = 60
FLEET_DURATION_S = 4.0
FLEET_WARM_S = 3.0
FLEET_REPLICA_COUNTS = (1, 2, 4)
# One FIXED closed-loop client pool across every replica count — the
# acceptance's "same stream": with clients scaled per replica the r1
# leg is latency-bound (clients/latency), not capacity-bound, and the
# speedup would measure the client pool, not the fleet.
FLEET_CLIENTS = 12


def _fleet_post(url, payload, timeout=60):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url + "/solve", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


def bench_serving_fleet():
    """Closed-loop clients against a real fleet.  Emits
    ``fleet_problems_per_sec_r<N>`` per replica count (the sentinel
    family ``serving_fleet`` judges the r2 value),
    ``fleet_speedup_r2`` (r2/r1 on the same stream),
    ``fleet_affinity_hit_fraction`` and the round-robin A/B
    (``fleet_rr_problems_per_sec`` / ``fleet_affinity_gain``) —
    affinity must BEAT round-robin for the routing complexity to pay
    its way.  None-valued on failure — never kills the headline."""
    import threading

    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving.router import FleetRouter, RouterFrontEnd

    pool = {
        n: [dcop_yaml(build_dcop_small(n, seed))
            for seed in range(FLEET_POOL_PER_STRUCT)]
        for n in FLEET_STRUCTS
    }
    params = {"max_cycles": FLEET_MAX_CYCLES}
    worker_args = ["--batch_window", "0.005", "--max_batch", "16",
                   "--max_queue", "512",
                   "--cycles", str(FLEET_MAX_CYCLES)]

    def run_leg(replicas: int, affinity: str):
        router = FleetRouter(replicas=replicas,
                             worker_args=worker_args,
                             affinity=affinity).start()
        front = RouterFrontEnd(router, port=0).start()
        url = front.url
        try:
            completed = [0]
            latencies = []
            lock = threading.Lock()
            state = {"t_end": 0.0}

            def client(idx, record):
                rng = np.random.default_rng(7000 + idx)
                i = 0
                while time.perf_counter() < state["t_end"]:
                    n = FLEET_STRUCTS[int(rng.integers(
                        len(FLEET_STRUCTS)))]
                    payload = pool[n][i % FLEET_POOL_PER_STRUCT]
                    i += 1
                    t0 = time.perf_counter()
                    status, body = _fleet_post(url, {
                        "dcop": payload, "wait": True,
                        "timeout": 60, "params": params})
                    t1 = time.perf_counter()
                    if record and status == 200 \
                            and body.get("status") == "FINISHED":
                        with lock:
                            latencies.append(t1 - t0)
                            completed[0] += 1

            def drive(duration, record):
                state["t_end"] = time.perf_counter() + duration
                threads = [
                    threading.Thread(target=client,
                                     args=(i, record))
                    for i in range(FLEET_CLIENTS)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=duration + 120)

            drive(FLEET_WARM_S, record=False)   # compile warm-up
            t_start = time.perf_counter()
            drive(FLEET_DURATION_S, record=True)
            elapsed = time.perf_counter() - t_start
            stats = router.stats()
        finally:
            front.stop()
            router.stop(drain=False)
        if not completed[0] or elapsed <= 0:
            return None
        lat_ms = np.asarray(latencies) * 1e3
        return {
            "pps": round(completed[0] / elapsed, 2),
            "p50": round(float(np.percentile(lat_ms, 50)), 2),
            "p99": round(float(np.percentile(lat_ms, 99)), 2),
            "requests": completed[0],
            "affinity_hit_fraction": stats["affinity_hit_fraction"],
        }

    out = {}
    by_replicas = {}
    for replicas in FLEET_REPLICA_COUNTS:
        leg = run_leg(replicas, "structure")
        by_replicas[replicas] = leg
        if leg is None:
            out[f"fleet_problems_per_sec_r{replicas}"] = None
            continue
        out[f"fleet_problems_per_sec_r{replicas}"] = leg["pps"]
        if replicas == 2:
            out["fleet_p50_ms"] = leg["p50"]
            out["fleet_p99_ms"] = leg["p99"]
            out["fleet_requests"] = leg["requests"]
            out["fleet_affinity_hit_fraction"] = \
                leg["affinity_hit_fraction"]
    r1, r2 = by_replicas.get(1), by_replicas.get(2)
    if r1 and r2:
        out["fleet_speedup_r2"] = round(r2["pps"] / r1["pps"], 3)
    rr = run_leg(2, "round_robin")
    if rr and r2:
        out["fleet_rr_problems_per_sec"] = rr["pps"]
        out["fleet_affinity_gain"] = round(r2["pps"] / rr["pps"], 3)
    # Fleet-trace side-channel (ISSUE 20): the SAME r2 leg with the
    # trace plane off (workers inherit the env knob).  The r2 leg
    # above ran with tracing ON (the default), so off/on is the
    # plane's whole cost — context minting, header stamping, span
    # shipping, collector ingest.  The perf-smoke pairwise gate
    # enforces <= 2%; this emits the longer-horizon number for the
    # sentinel history.
    from pydcop_tpu.observability import fleettrace

    prev = os.environ.get(fleettrace.ENV_KNOB)
    os.environ[fleettrace.ENV_KNOB] = "0"
    try:
        off = run_leg(2, "structure")
    finally:
        if prev is None:
            os.environ.pop(fleettrace.ENV_KNOB, None)
        else:
            os.environ[fleettrace.ENV_KNOB] = prev
    if off and r2:
        out["fleet_trace_off_problems_per_sec"] = off["pps"]
        out["fleet_trace_overhead"] = round(
            off["pps"] / r2["pps"], 3)
    return out


# Partition-tolerant fleet leg (ISSUE 19): the SAME closed-loop
# 2-replica stream run clean and then under a seeded 1%-drop /
# 20ms-delay netfault plan on the router->replica /solve links
# (liveness probes spared via the path= scope, so the leg measures
# retry absorption, not false death verdicts).  Every request
# carries a deadline_s; the router's idempotent retry must absorb
# every injected fault — zero acked requests lost, zero retry
# budgets exhausted — or the leg fails.  Sentinel family
# "fleet_faulted" (the faulted problems/sec: its own family, NOT
# compared against the clean serving_fleet numbers).
FLEET_FAULT_SPEC = ("seed=19;link=router>replica-*,path=/solve,"
                    "drop=0.01,delay_ms=20")
FLEET_FAULT_DEADLINE_S = 30.0


def bench_serving_fleet_faulted():
    """Closed-loop clients against a 2-replica fleet with seeded
    drop+delay on the solve links.  Emits
    ``fleet_faulted_problems_per_sec`` (the sentinel value),
    ``fleet_faulted_clean_problems_per_sec`` /
    ``fleet_faulted_throughput_fraction`` (the same stream with the
    plan cleared, same process, for the overhead read),
    ``fleet_faulted_retries`` and the two MUST-be-zero ledgers
    ``fleet_faulted_lost_acked`` / ``fleet_faulted_budget_exceeded``.
    None-valued on failure — never kills the headline."""
    import threading
    import urllib.error
    import urllib.request

    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving import netfault
    from pydcop_tpu.serving.router import FleetRouter, RouterFrontEnd

    pool = {
        n: [dcop_yaml(build_dcop_small(n, seed))
            for seed in range(FLEET_POOL_PER_STRUCT)]
        for n in FLEET_STRUCTS
    }
    params = {"max_cycles": FLEET_MAX_CYCLES}
    worker_args = ["--batch_window", "0.005", "--max_batch", "16",
                   "--max_queue", "512",
                   "--cycles", str(FLEET_MAX_CYCLES)]

    def poll_result(url, rid, deadline):
        while time.perf_counter() < deadline:
            try:
                with urllib.request.urlopen(
                        url + f"/result/{rid}", timeout=10) as resp:
                    body = json.loads(resp.read())
            except urllib.error.HTTPError as err:
                body = json.loads(err.read())
            except OSError:
                time.sleep(0.2)
                continue
            if body.get("status") in ("FINISHED", "ERROR"):
                return body.get("status") == "FINISHED"
            time.sleep(0.2)
        return False

    def run_leg(faulted: bool):
        router = FleetRouter(replicas=2, worker_args=worker_args,
                             affinity="structure").start()
        front = RouterFrontEnd(router, port=0).start()
        url = front.url
        try:
            completed = [0]
            acked_pending = []
            lock = threading.Lock()
            state = {"t_end": 0.0}

            def client(idx, record):
                rng = np.random.default_rng(9000 + idx)
                i = 0
                while time.perf_counter() < state["t_end"]:
                    n = FLEET_STRUCTS[int(rng.integers(
                        len(FLEET_STRUCTS)))]
                    payload = pool[n][i % FLEET_POOL_PER_STRUCT]
                    i += 1
                    status, body = _fleet_post(url, {
                        "dcop": payload, "wait": True,
                        "timeout": 60, "params": params,
                        "deadline_s": FLEET_FAULT_DEADLINE_S})
                    if not record:
                        continue
                    if status == 200 \
                            and body.get("status") == "FINISHED":
                        with lock:
                            completed[0] += 1
                    elif status in (200, 202) and body.get("id"):
                        # Acked but not finished in the wait window:
                        # the zero-loss ledger must resolve it.
                        with lock:
                            acked_pending.append(body["id"])

            def drive(duration, record):
                state["t_end"] = time.perf_counter() + duration
                threads = [
                    threading.Thread(target=client,
                                     args=(i, record))
                    for i in range(FLEET_CLIENTS)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=duration + 120)

            drive(FLEET_WARM_S, record=False)   # clean warm-up
            if faulted:
                netfault.install(FLEET_FAULT_SPEC)
            t_start = time.perf_counter()
            drive(FLEET_DURATION_S, record=True)
            elapsed = time.perf_counter() - t_start
            injected = netfault.counters()
            netfault.clear()
            stats = router.stats()
            # Resolve every acked-but-pending id AFTER the faults are
            # cleared: an ack the fleet cannot honor is a lost
            # request, whatever the link did.
            lost = 0
            poll_deadline = time.perf_counter() + 60.0
            for rid in acked_pending:
                done = poll_result(url, rid, poll_deadline)
                if done:
                    completed[0] += 1
                else:
                    lost += 1
        finally:
            netfault.clear()
            front.stop()
            router.stop(drain=False)
        if not completed[0] or elapsed <= 0:
            return None
        return {
            "pps": round(completed[0] / elapsed, 2),
            "requests": completed[0],
            "lost": lost,
            "retries": stats.get("retries", 0),
            "budget_exceeded": stats.get("retry_budget_exceeded", 0),
            "injected": injected,
        }

    clean = run_leg(faulted=False)
    faulted = run_leg(faulted=True)
    if faulted is None:
        return {"fleet_faulted_problems_per_sec": None,
                "fleet_faulted_error":
                    "faulted leg produced no completions"}
    if faulted["lost"]:
        raise RuntimeError(
            f"{faulted['lost']} acked request(s) lost under the "
            f"injected fault plan (retries {faulted['retries']})")
    if faulted["budget_exceeded"]:
        raise RuntimeError(
            f"{faulted['budget_exceeded']} retry budget(s) exhausted "
            f"under a {FLEET_FAULT_DEADLINE_S:.0f}s deadline")
    out = {
        "fleet_faulted_problems_per_sec": faulted["pps"],
        "fleet_faulted_requests": faulted["requests"],
        "fleet_faulted_lost_acked": faulted["lost"],
        "fleet_faulted_retries": faulted["retries"],
        "fleet_faulted_budget_exceeded": faulted["budget_exceeded"],
        "fleet_faulted_injected_drop":
            faulted["injected"].get("drop", 0),
        "fleet_faulted_injected_delay":
            faulted["injected"].get("delay", 0),
    }
    if clean:
        out["fleet_faulted_clean_problems_per_sec"] = clean["pps"]
        out["fleet_faulted_throughput_fraction"] = round(
            faulted["pps"] / clean["pps"], 3)
    return out


# Elastic-fleet leg (ISSUE 16): one two-host fleet (socket-distinct
# replica processes striped over simulated host identities) driven
# through four phases — baseline throughput, live session migration
# with hard cost parity, a 4x closed-loop traffic step against the
# SLO autoscaler, and a host kill mid-burst that must lose zero
# acknowledged requests or session events.  Sentinel family
# "fleet_elastic" (the baseline problems/sec).
FLEET_ELASTIC_N_VARS = 24
FLEET_ELASTIC_POOL = 4
FLEET_ELASTIC_MAX_CYCLES = 60
FLEET_ELASTIC_BASE_CLIENTS = 3
FLEET_ELASTIC_STEP_CLIENTS = 12      # the 4x traffic step
FLEET_ELASTIC_WARM_S = 2.0
FLEET_ELASTIC_PHASE_S = 4.0
FLEET_ELASTIC_SETTLE_S = 5.0         # autoscale reaction window
FLEET_ELASTIC_BURST = 12
# Session params through the router: admission validates them, so
# only solver keys (no session-only knobs like segment_cycles).
FLEET_ELASTIC_SESSION_PARAMS = {
    "noise": 0.01, "stability": 0.001, "max_cycles": 500}


def _fleet_req(url, method="GET", payload=None, timeout=60):
    import urllib.error
    import urllib.request

    data = (json.dumps(payload).encode()
            if payload is not None else None)
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


def _elastic_session_problem(seed: int, n_batches: int):
    """A 10-variable integer-table path problem, its event batches,
    and the UNINTERRUPTED reference cost (warm engine, every batch
    applied in-process) — migration parity is judged by hard
    equality against this."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.engine.dynamic import build_dynamic_engine
    from pydcop_tpu.serving.sessions import apply_event_batch

    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"elastic{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(10)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(9):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]],
            rng.integers(0, 10, size=(3, 3)).astype(float), f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    batches = [
        [{"type": "change_factor",
          "name": f"c{int(rng.integers(9))}",
          "table": rng.integers(0, 10, size=(3, 3))
                      .astype(float).tolist()}]
        for _ in range(n_batches)
    ]
    params = dict(FLEET_ELASTIC_SESSION_PARAMS)
    ref = build_dynamic_engine(dcop, params)
    ref.run(max_cycles=params["max_cycles"])
    for batch in batches:
        _asg, _trace, err = apply_event_batch(ref, batch)
        if err is not None:
            raise RuntimeError(f"reference event failed: {err}")
        ref.run(max_cycles=params["max_cycles"])
    expected = ref.cost(
        ref.run(max_cycles=params["max_cycles"]).assignment)
    return dcop_yaml(dcop), batches, expected


def _elastic_patch_acked(url, sid, batch, deadline_s=120.0):
    """PATCH until the batch is acked: 409 (frozen mid-migration)
    and 503 (owner recovering) are the fleet saying retry."""
    deadline = time.perf_counter() + deadline_s
    while True:
        status, out = _fleet_req(
            url + f"/session/{sid}/events", "PATCH",
            {"events": batch, "wait": True, "timeout": 30.0})
        if status == 200:
            return out
        if status not in (409, 503) \
                or time.perf_counter() > deadline:
            raise RuntimeError(f"PATCH not acked: {status} {out}")
        time.sleep(0.2)


def _elastic_close_session(url, sid, deadline_s=120.0):
    deadline = time.perf_counter() + deadline_s
    while time.perf_counter() < deadline:
        status, st = _fleet_req(url + f"/session/{sid}")
        if status == 200:
            last = st.get("last")
            if last and last.get("converged"):
                break
        time.sleep(0.05)
    status, final = _fleet_req(url + f"/session/{sid}", "DELETE")
    if status != 200:
        raise RuntimeError(f"session close failed: {status} {final}")
    return final


def bench_fleet_elastic():
    """Elastic two-host fleet under churn.  Emits
    ``fleet_elastic_problems_per_sec`` (baseline closed-loop
    throughput — the sentinel value), migration cost parity
    (``fleet_elastic_migrate_cost_ok``), the 4x-step p99 ratio vs
    baseline with autoscaler reaction
    (``fleet_elastic_p99_ratio`` / ``fleet_elastic_scale_ups``), and
    the host-kill ledger (``fleet_elastic_lost`` — MUST be 0,
    ``fleet_elastic_session_events_ok``).  None-valued on failure —
    never kills the headline."""
    import shutil
    import tempfile
    import threading

    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving.router import FleetRouter, RouterFrontEnd

    pool = [dcop_yaml(build_dcop_small(FLEET_ELASTIC_N_VARS, seed))
            for seed in range(FLEET_ELASTIC_POOL)]
    params = {"max_cycles": FLEET_ELASTIC_MAX_CYCLES}
    worker_args = ["--batch_window", "0.005", "--max_batch", "16",
                   "--max_queue", "512",
                   "--cycles", str(FLEET_ELASTIC_MAX_CYCLES)]
    journal_dir = tempfile.mkdtemp(prefix="bench_elastic_jnl_")
    cache_dir = _leg_cache_dir("bench_fleet_elastic", empty=False)
    router = FleetRouter(
        replicas=2, worker_args=worker_args,
        journal_dir=journal_dir, compile_cache_dir=cache_dir,
        hosts=2, min_replicas=2, max_replicas=4,
        autoscale_interval_s=1.0, heartbeat_s=0.2).start()
    front = RouterFrontEnd(router, port=0).start()
    url = front.url
    out = {}
    try:
        lock = threading.Lock()
        state = {"t_end": 0.0}

        def drive(n_clients, duration, record):
            completed = [0]
            latencies = []

            def client(idx):
                rng = np.random.default_rng(8100 + idx)
                while time.perf_counter() < state["t_end"]:
                    payload = pool[int(rng.integers(len(pool)))]
                    t0 = time.perf_counter()
                    status, body = _fleet_post(url, {
                        "dcop": payload, "wait": True,
                        "timeout": 60, "params": params})
                    t1 = time.perf_counter()
                    if record and status == 200 \
                            and body.get("status") == "FINISHED":
                        with lock:
                            latencies.append(t1 - t0)
                            completed[0] += 1

            state["t_end"] = time.perf_counter() + duration
            t_start = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=duration + 120)
            elapsed = time.perf_counter() - t_start
            if not record or not completed[0] or elapsed <= 0:
                return None
            lat_ms = np.asarray(latencies) * 1e3
            return {
                "pps": round(completed[0] / elapsed, 2),
                "p50": round(float(np.percentile(lat_ms, 50)), 2),
                "p99": round(float(np.percentile(lat_ms, 99)), 2),
                "requests": completed[0],
            }

        # Phase A — baseline throughput/latency on the 2-host floor.
        drive(FLEET_ELASTIC_BASE_CLIENTS, FLEET_ELASTIC_WARM_S,
              record=False)
        base = drive(FLEET_ELASTIC_BASE_CLIENTS,
                     FLEET_ELASTIC_PHASE_S, record=True)
        if base is None:
            return {"fleet_elastic_problems_per_sec": None,
                    "fleet_elastic_error":
                        "baseline produced no completions"}
        out["fleet_elastic_problems_per_sec"] = base["pps"]
        out["fleet_elastic_p50_ms"] = base["p50"]
        out["fleet_elastic_p99_ms"] = base["p99"]
        out["fleet_elastic_requests"] = base["requests"]

        # Phase B — live migration with hard cost parity: the
        # migrated session must finish at EXACTLY the uninterrupted
        # reference cost on integer tables.
        yaml_a, batches_a, expected_a = \
            _elastic_session_problem(4201, 4)
        status, body = _fleet_req(
            url + "/session", "POST",
            {"dcop": yaml_a,
             "params": FLEET_ELASTIC_SESSION_PARAMS})
        if status != 201:
            raise RuntimeError(
                f"session open failed: {status} {body}")
        sid = body["session_id"]
        for batch in batches_a[:2]:
            _elastic_patch_acked(url, sid, batch)
        src = router.pinned(sid, router._session_pins)
        status, body = _fleet_req(url + "/admin/migrate", "POST",
                                  {"session_id": sid})
        dst = router.pinned(sid, router._session_pins)
        moved = (status == 200 and src is not None
                 and dst is not None and dst.index != src.index)
        for batch in batches_a[2:]:
            _elastic_patch_acked(url, sid, batch)
        final = _elastic_close_session(url, sid)
        out["fleet_elastic_migrate_cost_ok"] = bool(
            moved and final.get("cost") == expected_a)
        out["fleet_elastic_migrations"] = router.migrations

        # Phase C — 4x traffic step against the autoscaler.  The SLO
        # is pegged to the measured baseline (armed only now, so the
        # baseline itself ran on the fixed floor), the settle window
        # gives the control loop time to spawn, and the recorded
        # window judges the post-reaction p99.
        router.slo_p99_ms = max(1.5 * base["p99"], 25.0)
        out["fleet_elastic_slo_p99_ms"] = round(
            router.slo_p99_ms, 2)
        drive(FLEET_ELASTIC_STEP_CLIENTS, FLEET_ELASTIC_SETTLE_S,
              record=False)
        step = drive(FLEET_ELASTIC_STEP_CLIENTS,
                     FLEET_ELASTIC_PHASE_S, record=True)
        out["fleet_elastic_scale_ups"] = router.scale_ups
        out["fleet_elastic_replicas_after_step"] = router.up_count()
        if step is not None:
            out["fleet_elastic_step_p99_ms"] = step["p99"]
            ratio = (step["p99"] / base["p99"]
                     if base["p99"] > 0 else None)
            out["fleet_elastic_p99_ratio"] = (
                round(ratio, 3) if ratio is not None else None)
            out["fleet_elastic_p99_within_2x"] = bool(
                ratio is not None and ratio <= 2.0)
        # Freeze the fleet size for the kill phase: a concurrent
        # scale-down would blur whose journal replays what.
        router.slo_p99_ms = None

        # Phase D — host kill mid-burst.  Every 202 and every acked
        # event batch is a durability promise; killing the host that
        # owns the warm session (both its replica processes) must
        # lose none of them.
        yaml_b, batches_b, expected_b = \
            _elastic_session_problem(4301, 3)
        status, body = _fleet_req(
            url + "/session", "POST",
            {"dcop": yaml_b,
             "params": FLEET_ELASTIC_SESSION_PARAMS})
        if status != 201:
            raise RuntimeError(
                f"session open failed: {status} {body}")
        sid_b = body["session_id"]
        for batch in batches_b[:2]:
            _elastic_patch_acked(url, sid_b, batch)
        pinned = router.pinned(sid_b, router._session_pins)
        victim_host = pinned.host_id if pinned else "host0"
        acked = []
        for k in range(FLEET_ELASTIC_BURST):
            status, body = _fleet_post(url, {
                "dcop": pool[k % len(pool)], "params": params})
            if status == 202:
                acked.append(body["id"])
        t_kill = time.perf_counter()
        victims = [r for r in router.replicas
                   if r.host_id == victim_host and r.managed
                   and not r.retired and r.proc is not None
                   and r.proc.poll() is None]
        for r in victims:
            r.proc.kill()
        out["fleet_elastic_burst_acked"] = len(acked)
        out["fleet_elastic_host_killed"] = len(victims)
        remaining = set(acked)
        deadline = time.perf_counter() + 180.0
        while remaining and time.perf_counter() < deadline:
            for rid in list(remaining):
                status, body = _fleet_req(url + f"/result/{rid}")
                if status == 200 \
                        and body.get("status") == "FINISHED":
                    remaining.discard(rid)
            if remaining:
                time.sleep(0.25)
        out["fleet_elastic_lost"] = len(remaining)
        out["fleet_elastic_kill_recover_s"] = round(
            time.perf_counter() - t_kill, 2)
        # The acked events survived iff the next batch lands as seq 3
        # and the session still converges to the reference cost.
        ack3 = _elastic_patch_acked(url, sid_b, batches_b[2],
                                    deadline_s=180.0)
        final_b = _elastic_close_session(url, sid_b,
                                         deadline_s=180.0)
        out["fleet_elastic_session_events_ok"] = bool(
            ack3.get("seq") == 3
            and final_b.get("cost") == expected_b)
        out["fleet_elastic_deaths"] = router.deaths
        return out
    finally:
        front.stop()
        router.stop(drain=False)
        shutil.rmtree(journal_dir, ignore_errors=True)


# Cold-start leg (ISSUE 15): time-to-first-result of a FRESH serve
# worker on a known structure, empty disk cache vs warm.  The warm
# process must serve its first same-structure request with the jit
# compile collapsed to the cache-retrieval wall (``compile`` ≈ 0 in
# its PR-14 request ledger) — the fleet's replicas and restarts live
# or die on this.  Workers run with PYDCOP_XLA_PROFILE=0 so the
# profiler's untimed throwaway AOT compile cannot seed the disk cache
# mid-dispatch and blur the A/B.  The instance is deliberately the
# COMPILE-HEAVIEST serving shape we have — domain 8, mixed
# binary/ternary buckets, branch-and-bound pruning enabled (the
# pruned program roughly triples XLA's work on this family) — because
# the leg exists to measure compile avoidance, not solve speed.
COLD_START_N_VARS = 48
COLD_START_TERNARY = 8
COLD_START_DOMAIN = 8
COLD_START_MAX_CYCLES = 200


def _leg_cache_dir(name: str, empty: bool) -> str:
    """A leg's own compile cache: a fixed sub-directory of the
    resolved cache (engine/aotcache.resolve_cache_dir) — the path is
    part of every entry's key, so it never moves.  ``empty`` for a leg
    that must start cold."""
    import shutil

    from pydcop_tpu.engine.aotcache import resolve_cache_dir

    path = os.path.join(resolve_cache_dir()[0], name)
    if empty:
        shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


def build_cold_start_dcop(seed: int = 3):
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    d = COLD_START_DOMAIN
    dom = Domain("c", "", list(range(d)))
    dcop = DCOP("coldstart", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(COLD_START_N_VARS)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(COLD_START_N_VARS):
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % COLD_START_N_VARS]],
            rng.integers(0, 10, size=(d, d)).astype(float), f"c{k}"))
    for k in range(COLD_START_TERNARY):
        i, j, l = rng.choice(COLD_START_N_VARS, size=3,
                             replace=False)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[i], vs[j], vs[l]],
            rng.integers(0, 10, size=(d, d, d)).astype(float),
            f"t{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def bench_serve_cold_start():
    """Two fresh serve subprocesses against one cache directory:
    round 1 compiles (and populates the cache), round 2 must
    deserialize.  Emits ``serve_cold_start_warm_s`` (warm
    time-to-first-result — the ``serve_cold_start`` sentinel family,
    LOWER is better), the cold baseline, and both request ledgers'
    ``compile`` components.  None-valued on failure."""
    import shutil
    import signal as signal_mod
    import subprocess as sp
    import tempfile
    import urllib.request

    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    from pydcop_tpu.engine.aotcache import JAX_ENV_DIR

    cache_dir = _leg_cache_dir("bench_serve_cold_start", empty=True)
    run_dir = tempfile.mkdtemp(prefix="bench_cold_")
    payload = dcop_yaml(build_cold_start_dcop())
    request_params = {"max_cycles": COLD_START_MAX_CYCLES,
                      "prune": 1}

    def one_round(tag):
        port_file = os.path.join(run_dir, f"{tag}.port")
        env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
            "JAX_PLATFORMS", "cpu"), PYDCOP_XLA_PROFILE="0")
        if JAX_ENV_DIR in env:
            # JAX's own setting wins in the worker: point it at the
            # leg's emptied sub-directory, or round 1 is not cold.
            env[JAX_ENV_DIR] = cache_dir
        log = open(os.path.join(run_dir, f"{tag}.log"), "wb")
        proc = sp.Popen(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli", "serve",
             "--port", "0", "--port_file", port_file,
             "--compile_cache_dir", cache_dir,
             "--batch_window", "0.005",
             "--cycles", str(COLD_START_MAX_CYCLES)],
            env=env, stdout=log, stderr=log)
        log.close()
        try:
            deadline = time.monotonic() + 120
            port = None
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"cold-start worker died (exit "
                        f"{proc.returncode})")
                try:
                    with open(port_file, encoding="utf-8") as f:
                        port = int(f.read().strip())
                    break
                except (OSError, ValueError):
                    time.sleep(0.05)
            if port is None:
                raise RuntimeError("cold-start worker never listened")
            url = f"http://127.0.0.1:{port}"
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(url + "/healthz",
                                                timeout=2):
                        break
                except OSError:
                    time.sleep(0.05)
            t0 = time.perf_counter()
            status, body = _fleet_post(url, {
                "dcop": payload, "wait": True, "timeout": 120,
                "params": request_params}, timeout=150)
            ttfr = time.perf_counter() - t0
            if status != 200 or body.get("status") != "FINISHED":
                raise RuntimeError(
                    f"cold-start request failed ({status})")
            ledger = body.get("ledger") or {}
            return {
                "ttfr_s": round(ttfr, 4),
                "compile_s": round(
                    float(ledger.get("compile_s", 0.0)), 4),
                "execute_s": round(
                    float(ledger.get("execute_s", 0.0)), 4),
            }
        finally:
            if proc.poll() is None:
                proc.send_signal(signal_mod.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except sp.TimeoutExpired:
                    proc.kill()

    try:
        cold = one_round("cold")
        warm = one_round("warm")
        return {
            "serve_cold_start_warm_s": warm["ttfr_s"],
            "serve_cold_start_cold_s": cold["ttfr_s"],
            "serve_cold_start_warm_compile_s": warm["compile_s"],
            "serve_cold_start_cold_compile_s": cold["compile_s"],
            "serve_cold_start_speedup": round(
                cold["ttfr_s"] / warm["ttfr_s"], 3)
                if warm["ttfr_s"] else None,
        }
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


DPOP_EXACT_N = 300
DPOP_EXACT_D = 8
DPOP_EXACT_REPS = 5


def build_dpop_exact_dcop(n: int = DPOP_EXACT_N,
                          d: int = DPOP_EXACT_D, seed: int = 1709):
    """Width-bounded exact-inference instance: a random spanning tree
    (induced width stays small) over a mid-sized domain, seeded so
    every round solves the same problem."""
    import numpy as np

    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("c", "", list(range(d)))
    dcop = DCOP("dpop_exact", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for i in range(1, n):
        p = int(rng.integers(max(0, i - 3), i))
        dcop.add_constraint(NAryMatrixRelation(
            [vs[p], vs[i]], rng.random((d, d)), f"c{i}"))
    # Short-range cross edges push the induced width past 1 so the
    # UTIL sweep carries real separators, while the bounded bandwidth
    # keeps the hypercubes far under the element cap.
    for k in range(5, n, 5):
        lo = max(0, k - 4)
        q = int(rng.integers(lo, k))
        dcop.add_constraint(NAryMatrixRelation(
            [vs[q], vs[k]], rng.random((d, d)), f"x{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def bench_dpop_exact():
    """Exact-inference leg: warmed, best-of-N wall time for a full
    DPOP sweep (UTIL up + VALUE down, CEC on) on the width-bounded
    seeded instance — sentinel family ``dpop_exact`` (ms, LOWER is
    better).  The warm-up run eats every signature-bucket compile, so
    the measured reps are the serving-steady-state cost of an exact
    answer."""
    from pydcop_tpu.computations_graph import pseudotree as pt
    from pydcop_tpu.engine.dpop import DpopEngine
    from pydcop_tpu.ops.dpop import tree_stats

    dcop = build_dpop_exact_dcop()
    tree = pt.build_computation_graph(dcop)
    stats = tree_stats(tree)
    engine = DpopEngine(tree, mode="min", cec=True)
    warm = engine.run()   # compiles + caches CEC survivors
    best = None
    for _ in range(DPOP_EXACT_REPS):
        t0 = time.perf_counter()
        res = engine.run()
        elapsed = time.perf_counter() - t0
        best = elapsed if best is None else min(best, elapsed)
    cost, violations = dcop.solution_cost(res.assignment)
    if violations:
        raise RuntimeError("exact sweep produced violations")
    return {
        "dpop_exact_ms": round(best * 1000.0, 3),
        "dpop_exact_cold_ms": round(warm.time_s * 1000.0, 3),
        "dpop_exact_induced_width": stats["induced_width"],
        "dpop_exact_levels": stats["levels"],
        "dpop_exact_cec_pruned": res.metrics.get("cec_pruned"),
        "dpop_exact_cost": round(float(cost), 4),
    }


def run_bench():
    import jax

    from pydcop_tpu.engine.roofline import roofline_report
    from pydcop_tpu.observability.profiler import profiler

    # XLA cost attribution for the roofline: the engine's cold
    # dispatch captures measured flops/bytes per compiled program
    # (PYDCOP_XLA_PROFILE=0 vetoes — the capture adds one AOT compile
    # per program).
    profiler.enabled = True
    dev = jax.devices()[0]
    platform = dev.platform
    device_kind = getattr(dev, "device_kind", None)
    parity_device_cost, parity_thread_cost = exact_parity()

    dcop = build_dcop(N_VARS)
    device_cps, res, engine = bench_device(dcop, DEVICE_CYCLES)
    thread_cps, thread_cycles, thread_cost, _asg = bench_thread(
        dcop, THREAD_TIMEOUT_S)
    if thread_cycles <= 0 or thread_cps <= 0:
        # Degenerate baseline (no full BSP cycle within the timeout):
        # still emit the JSON line rather than dying on a divide.
        out = {
            "metric": "maxsum_cycles_per_sec_10kvar_graphcoloring",
            "value": round(device_cps, 2),
            "unit": "cycles/s",
            "vs_baseline": None,
            "backend": platform,
            "host_cpus": os.cpu_count(),
            "baseline_cycles_completed": thread_cycles,
            "note": "threaded baseline completed no full cycle in "
                    f"{THREAD_TIMEOUT_S}s",
        }
        print(json.dumps(out))
        return

    # Cost-vs-cycle trace on the device: the quality check is one-sided
    # (fail only if the device is WORSE than the thread runtime at the
    # matched cycle count, beyond skew tolerance), and the trace gives
    # the north-star number — wall-clock to reach the thread runtime's
    # final cost.
    trace_res = engine.run_trace(max_cycles=thread_cycles)
    trace = trace_res.metrics["cost_trace"]
    quality_cost = float(trace[thread_cycles - 1])
    n_constraints = len(dcop.constraints)
    if quality_cost - thread_cost > QUALITY_TOL_FRAC * n_constraints:
        print(
            f"bench: QUALITY CHECK FAILED device@{thread_cycles}="
            f"{quality_cost} thread={thread_cost} "
            f"tol={QUALITY_TOL_FRAC * n_constraints}", file=sys.stderr,
        )
        sys.exit(1)
    # First cycle at which the device matches the thread's final cost.
    below = np.nonzero(trace <= thread_cost)[0]
    cycles_to_cost = int(below[0]) + 1 if below.size else None
    time_to_cost = (
        cycles_to_cost / device_cps if cycles_to_cost else None
    )
    thread_elapsed = thread_cycles / thread_cps
    speedup_equal_cost = (
        round(thread_elapsed / time_to_cost, 1)
        if time_to_cost else None
    )

    # Marginal per-cycle rate: the end-to-end device_cps above
    # includes the per-call constant (dispatch + sync + fetch), which
    # at 200 cycles can dominate a 10k-var superstep.  Differencing
    # two cycle counts cancels it (engine/timing.py); this is the rate
    # utilization claims are based on.  TPU only: multi-thousand-cycle
    # CPU runs would add minutes and say nothing about the chip.
    marginal_cps = None
    fixed_latency = None
    if platform == "tpu":
        from pydcop_tpu.engine.timing import warmed_marginal

        # Adaptive ladder: start with a short delta and escalate 10x
        # only while the measured slope keeps the next rung's program
        # under ~45 s, until the delta is long enough (0.5 s) to
        # dominate host-clock jitter.
        lo, hi = 200, 2_200
        while True:
            sec_per_cycle, fixed_latency, _ = warmed_marginal(
                lambda c: engine._fn(c, False), lo, hi,
                args=(engine.graph,), reps=3)
            delta_s = sec_per_cycle * (hi - lo)
            next_hi = hi * 10
            if (delta_s >= 0.5 or next_hi > 3_000_000
                    or sec_per_cycle * next_hi > 45):
                break
            hi = next_hi
        marginal_cps = (
            1.0 / sec_per_cycle if sec_per_cycle > 0 else None)

    # Measured (XLA-reported) per-cycle cost when the backend offered
    # one: the headline program is one while-loop whose body is a
    # superstep, and XLA's cost analysis counts a loop body ONCE
    # (trip-count-independent — verified in the perf-intel battery),
    # so the reported flops/bytes ARE per-cycle numbers.
    # bench_device's engine compiles exactly one program, so take the
    # sole entry rather than reverse-engineering the jit cache key
    # (whose format belongs to the engine).
    xla_entries = list((res.metrics.get("xla_cost") or {}).values())
    xla_entry = xla_entries[0] if len(xla_entries) == 1 else {}
    measured = None
    if xla_entry.get("available"):
        measured = {
            "flops_per_cycle": xla_entry.get("flops"),
            "bytes_per_cycle": xla_entry.get("bytes_accessed"),
        }
    roofline = roofline_report(
        engine.graph, marginal_cps or device_cps, platform, device_kind,
        measured=measured)
    roofline["roofline_rate_basis"] = (
        "marginal" if marginal_cps else "end_to_end")
    if xla_entry.get("peak_bytes"):
        roofline["xla_peak_bytes"] = xla_entry["peak_bytes"]
    # HBM-bound scale leg: TPU only — on an explicit CPU run it
    # would add minutes and say nothing about HBM streaming.
    if platform == "tpu":
        scale_cps, scale_graph, scale_info = bench_scale(detail=True)
        scale_keys = {
            "scale_n_vars": SCALE_N_VARS,
            "scale_fixed_latency_s": round(
                scale_info["fixed_overhead_s"], 3),
        }
        if scale_cps > 0:
            scale_roofline = roofline_report(
                scale_graph, scale_cps, platform, device_kind)
            scale_keys.update({
                "scale_cycles_per_s": round(scale_cps, 2),
                "scale_ms_per_cycle": round(
                    scale_info["sec_per_cycle"] * 1e3, 4),
                "scale_hbm_util": scale_roofline["hbm_util"],
                "scale_achieved_gbps": scale_roofline["achieved_gbps"],
                "scale_vmem_resident": scale_roofline["vmem_resident"],
                "scale_hbm_util_exceeds_peak": scale_roofline[
                    "hbm_util_exceeds_peak"],
            })
        else:
            # Jitter-floored slope: no rate claim (matches the
            # headline leg's None convention) rather than a 0.0 that
            # reads as a dead chip.
            scale_keys.update({
                "scale_cycles_per_s": None,
                "scale_timing_below_jitter": True,
            })
        del scale_graph
    else:
        # CPU smoke of the same scale-leg code path (shrunk from 1M
        # vars so it adds seconds, not minutes): the array
        # builder, aggregation layout and marginal-timing ladder all
        # execute before a TPU ever runs them at full scale.  No HBM
        # claim is made — the keys are namespaced "smoke".
        try:
            smoke_cps, _smoke_graph, smoke_info = bench_scale(
                n_vars=SCALE_SMOKE_N_VARS, cycles=SCALE_SMOKE_CYCLES,
                detail=True)
            scale_keys = {
                "scale_smoke_n_vars": SCALE_SMOKE_N_VARS,
                "scale_smoke_cycles_per_s": round(smoke_cps, 2),
                "scale_smoke_ms_per_cycle": round(
                    smoke_info["sec_per_cycle"] * 1e3, 4),
            }
            del _smoke_graph
        except Exception as exc:  # noqa: BLE001 — auxiliary leg
            print(f"bench: scale smoke failed ({exc}); continuing",
                  file=sys.stderr)
            scale_keys = {
                "scale_smoke_cycles_per_s": None,
                "scale_smoke_error":
                    f"{type(exc).__name__}: {exc}"[:200],
            }
    # Time-to-target-cost leg (both backends — the work-reduction
    # stack's headline; sentinel family "time_to_cost", lower is
    # better).  Never kills the headline line.
    try:
        ttc_keys = bench_time_to_cost()
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: time-to-cost leg failed ({exc}); continuing",
              file=sys.stderr)
        ttc_keys = {"maxsum_time_to_cost_ms": None,
                    "ttc_error": f"{type(exc).__name__}: {exc}"[:200]}
    # Serving-throughput leg (both backends: the request plane exists
    # on an explicit CPU run too, and its trajectory is what the
    # sentinel tracks per backend).  Never kills the headline line.
    try:
        serve_keys = bench_serving()
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: serving leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys = {"serve_problems_per_sec": None,
                      "serve_error": f"{type(exc).__name__}: {exc}"[:200]}
    # Mixed-structure serving leg (ISSUE 11): zipf-diverse topologies,
    # envelope packing vs the no-envelope baseline on the same stream;
    # sentinel family "serve_mixed".  Never kills the headline line.
    try:
        serve_keys.update(bench_serving_mixed())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: mixed serving leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "serve_mixed_problems_per_sec": None,
            "serve_mixed_error":
                f"{type(exc).__name__}: {exc}"[:200]})
    # Crash-recovery replay leg: journal scan + replay downtime —
    # the sentinel tracks it per backend like any other metric, so a
    # change that slows recovery is a tracked regression.
    try:
        serve_keys.update(bench_recovery_replay())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: recovery-replay leg failed ({exc}); "
              "continuing", file=sys.stderr)
        serve_keys.update({
            "serve_recovery_replay_s": None,
            "serve_recovery_error":
                f"{type(exc).__name__}: {exc}"[:200],
        })
    # Fleet-serving leg (ISSUE 15): aggregate problems/sec through
    # the replicated router at replicas=1/2/4 on the same seeded
    # stream + the affinity-vs-round-robin A/B — sentinel family
    # "serving_fleet" (the r2 value).  Never kills the headline.
    try:
        serve_keys.update(bench_serving_fleet())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: fleet leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "fleet_problems_per_sec_r2": None,
            "fleet_error": f"{type(exc).__name__}: {exc}"[:200],
        })
    # Partition-tolerant fleet leg (ISSUE 19): the same closed-loop
    # stream under a seeded 1%-drop/20ms-delay plan on the solve
    # links, zero-acked-loss + deadline-budget ledgers — sentinel
    # family "fleet_faulted" (its own family, never compared against
    # the clean fleet numbers).  Never kills the headline.
    try:
        serve_keys.update(bench_serving_fleet_faulted())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: faulted-fleet leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "fleet_faulted_problems_per_sec": None,
            "fleet_faulted_error":
                f"{type(exc).__name__}: {exc}"[:200],
        })
    # Elastic-fleet leg (ISSUE 16): two-host fleet under churn —
    # baseline throughput, live-migration cost parity, a 4x traffic
    # step against the SLO autoscaler, and a host kill mid-burst
    # with a zero-acked-loss ledger — sentinel family
    # "fleet_elastic".  Never kills the headline.
    try:
        serve_keys.update(bench_fleet_elastic())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: elastic-fleet leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "fleet_elastic_problems_per_sec": None,
            "fleet_elastic_error":
                f"{type(exc).__name__}: {exc}"[:200],
        })
    # Cold-start leg (ISSUE 15): fresh-worker time-to-first-result,
    # warm disk compile cache vs empty — sentinel family
    # "serve_cold_start" (warm TTFR, lower is better).
    try:
        serve_keys.update(bench_serve_cold_start())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: cold-start leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "serve_cold_start_warm_s": None,
            "serve_cold_start_error":
                f"{type(exc).__name__}: {exc}"[:200],
        })
    # Stateful-session leg (ISSUE 13): warm time-to-recovered-cost
    # after scenario events vs a cold re-solve on the same compiled
    # program, plus sustained events/sec — sentinel families
    # "session_recovery" (lower is better) and "session_events".
    try:
        serve_keys.update(bench_sessions())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: session leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "session_time_to_recovered_cost_ms": None,
            "session_events_per_sec": None,
            "session_error": f"{type(exc).__name__}: {exc}"[:200],
        })
    # Exact-inference leg (ISSUE 17): warmed best-of-N full DPOP
    # sweep on the width-bounded seeded instance — sentinel family
    # "dpop_exact" (lower is better).
    try:
        serve_keys.update(bench_dpop_exact())
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: dpop exact leg failed ({exc}); continuing",
              file=sys.stderr)
        serve_keys.update({
            "dpop_exact_ms": None,
            "dpop_exact_error": f"{type(exc).__name__}: {exc}"[:200],
        })
    # Sharded-superstep leg: real mesh on a multi-chip TPU host,
    # forced-host-device child otherwise (``sharded_backend`` names
    # which).
    try:
        if platform == "tpu" and len(jax.devices()) >= 2:
            shard_keys = bench_sharded(
                min(SHARDED_SHARDS, len(jax.devices())))
            shard_keys["sharded_backend"] = "tpu"
        else:
            shard_keys = _bench_sharded_forced()
    except Exception as exc:  # noqa: BLE001 — auxiliary leg
        print(f"bench: sharded leg failed ({exc}); continuing",
              file=sys.stderr)
        shard_keys = {
            "maxsum_cycles_per_sec_sharded": None,
            "sharded_error": f"{type(exc).__name__}: {exc}"[:200],
        }
    out = {
        "metric": "maxsum_cycles_per_sec_10kvar_graphcoloring",
        "value": round(device_cps, 2),
        "unit": "cycles/s",
        "vs_baseline": round(device_cps / thread_cps, 1),
        "backend": platform,
        # Host hardware class: CPU rates scale with the core
        # count of the bench box, so the sentinel keys CPU baselines on
        # it (a 1-core round must not be judged against an 8-core
        # history — same refusal the backend split already applies).
        "host_cpus": os.cpu_count(),
        "device_kind": device_kind,
        "baseline": "own threaded agent runtime "
                    f"({THREAD_AGENTS} agent threads, same problem)",
        "baseline_cycles_per_s": round(thread_cps, 3),
        "baseline_cycles_completed": thread_cycles,
        "parity_cost_device": round(parity_device_cost, 4),
        "parity_cost_thread": round(parity_thread_cost, 4),
        "quality_cost_device_matched_cycles": round(quality_cost, 2),
        "quality_cost_thread": round(thread_cost, 2),
        "device_cycles_to_thread_cost": cycles_to_cost,
        "device_seconds_to_thread_cost": (
            round(time_to_cost, 4) if time_to_cost else None
        ),
        "speedup_at_equal_cost": speedup_equal_cost,
        "marginal_cycles_per_s": (
            round(marginal_cps, 1) if marginal_cps else None
        ),
        "fixed_latency_s": (
            round(fixed_latency, 4) if fixed_latency is not None
            else None
        ),
        **roofline,
        **scale_keys,
        **ttc_keys,
        **serve_keys,
        **shard_keys,
    }
    print(json.dumps(out))


def main():
    if os.environ.get("PYDCOP_BENCH_SHARDED_CHILD"):
        # Forced-host-device child of the sharded leg: one JSON line
        # with the sharded keys, nothing else on stdout.
        print(json.dumps(bench_sharded()))
        return
    from pydcop_tpu.engine.aotcache import (
        enable_persistent_compile_cache,
    )

    enable_persistent_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # A benchmark that finds no chip fails; it never falls back.
        # Tests and tools/*_smoke.py ask for the CPU explicitly.
        print(f"bench: JAX resolved platform {platform!r}, not a TPU; "
              "set JAX_PLATFORMS=cpu to run the CPU path on purpose",
              file=sys.stderr)
        sys.exit(2)
    run_bench()


if __name__ == "__main__":
    main()
