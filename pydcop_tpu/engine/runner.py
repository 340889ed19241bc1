"""The BSP engine runner: jit-compiles and executes algorithm loops.

This replaces the reference's orchestrator + thread-per-agent runtime
(pydcop/infrastructure/run.py:145 run_local_thread_dcop) for on-device
execution: the whole solve — message updates, damping, convergence test,
value selection — is one XLA program; the host only launches it and reads
back the result.
"""

import sys
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pydcop_tpu.engine import aotcache
from pydcop_tpu.engine.compile import (
    BIG,
    CompiledFactorGraph,
    FactorGraphMeta,
)
from pydcop_tpu.engine.sharding import make_mesh, shard_graph
from pydcop_tpu.engine.timing import sync
from pydcop_tpu.observability.efficiency import (
    tracker as efficiency_tracker,
)
from pydcop_tpu.observability.metrics import registry as metrics_registry
from pydcop_tpu.observability.profiler import key_str, profiler
from pydcop_tpu.observability.trace import NOOP_SPAN, tracer
from pydcop_tpu.ops import maxsum as maxsum_ops
from pydcop_tpu.ops import maxsum_lane as lane_ops


@dataclass(frozen=True)
class DecimationPlan:
    """Segmented decimation policy (Improving Max-Sum through
    Decimation, arXiv:1706.02209): at every segment boundary — where
    the host already syncs for guards/probes, so the jitted loop gains
    ZERO new syncs — variables whose belief margin (gap between best
    and second-best value) clears ``margin`` are CLAMPED to their
    selected value (unary costs overwritten with BIG everywhere else,
    the one-hot-constant-message form the kernels already respect),
    shrinking the set of edges still doing useful work round by round.

    ``margin``: threshold a variable's margin must exceed to clamp
    (0 = pure top-fraction selection, the classic decimation schedule).
    ``frac_per_round``: cap on the fraction of ALL variables newly
    clamped per boundary.  ``force_progress``: clamp the top-margin
    free variable even when none clears the threshold — guarantees the
    classic schedule terminates with everything fixed; threshold mode
    (margin > 0) leaves it False so only genuinely confident variables
    ever clamp.  ``cycles_per_round``: segment length used when the
    caller does not impose one (checkpoint cadence wins when present).
    """

    margin: float = 0.0
    frac_per_round: float = 0.1
    force_progress: bool = True
    cycles_per_round: int = 60


class DecimationState(NamedTuple):
    """Checkpoint payload of a decimated run: solver state + the clamp
    bookkeeping that must travel with it.  A snapshot missing the
    clamp set would resume message passing against un-clamped unary
    costs — a silently different problem; bundling them makes
    resume-mid-decimation reproduce the uninterrupted run (asserted
    in tests/unit/test_workreduction_battery.py)."""

    solver: Any           # MaxSumState
    fixed: Any            # [V] bool — clamped variables
    var_costs: Any        # [V+1, D] f32 — current (clamped) table


@dataclass
class DeviceRunResult:
    """Result of an on-device solve.

    Timing convention: ``time_s`` is the total wall time of the
    engine call, INCLUDING any tracing and jit compile that happened
    inside it.  On the whole-solve path (``MaxSumEngine.run``)
    ``compile_time_s`` is the part of it XLA spent compiling, or
    loading executables from the disk cache, from JAX's own counters
    (``timed_jit_call``'s ``report``): 0 when neither happened.  The
    other engines still report a first call's compile portion when
    it was separately measurable, else ``time_s`` itself.  Either
    way the two fields overlap — never sum them — and
    ``metrics['cold_start']`` is True on the first call of a
    program: for the whole-solve, segment and cost-trace programs of
    a single-device or replicated-mesh MaxSum engine, the first call
    in the process with those parameters and shapes, whichever
    engine makes it (the programs are the process's; see
    ``_process_program``).  Callers that need steady-state execution
    time (benchmarks) make an identical call first; the warm call
    has ``compile_time_s == 0``."""

    assignment: Dict[str, Any]
    cycles: int
    converged: bool
    time_s: float
    compile_time_s: float
    metrics: Dict[str, Any] = field(default_factory=dict)


# What a warm dispatch did besides run (``timed_jit_call``'s report).
_WARM_DISPATCH = {"first": False, "xla_compiles": 0, "cache_loads": 0,
                  "compile_s": 0.0}


def timed_jit_call(warm: set, key, fn, *args,
                   report: Optional[Dict[str, Any]] = None,
                   span_args: Optional[Dict[str, Any]] = None):
    """Execute a cached-jit function, splitting compile from run time.

    Plain jit dispatch, NOT ``fn.lower(...).compile()``: the AOT
    path recompiles on every call (lower/compile bypasses the jit
    cache), and it freezes input placements, which breaks feeding
    device-resident state back in on mesh runs.  The first call per
    ``key`` includes trace+compile and reports the whole elapsed
    interval as BOTH compile and run time (the DeviceRunResult
    overlapping-fields convention; compile dominates) — unless its
    executables all came off the disk cache, when compile is the
    retrieval wall; warm calls report (0, elapsed).

    ``warm`` is the set of whoever owns ``fn``: the process's for a
    process-level program (``_process_warm`` here, engine/batch.py's
    ``_warm``), whose key then carries everything JAX keys the
    program on — statics and the arrays' shapes — so that "first" is
    the first dispatch of that program in the process by any engine;
    an engine's own set for a program the engine built.

    What the first call really did comes from JAX's own counters
    (engine/aotcache.dispatch_compile) and names the span:
    ``jit_compile`` only when XLA compiled during the call, else
    ``engine_call`` (the first solve of a process whose program came
    from the disk cache did not compile), with ``first`` /
    ``cache_loads`` / ``xla_compiles`` in the args of a first call.
    ``report``, when given, is filled with the same and with
    ``compile_s``, the seconds XLA compiled or loaded (0 when neither
    happened): the whole-solve path reports that as its compile time.
    ``span_args`` are further args of the span (a MaxSum engine's
    ``layout``).

    Completion is forced with engine.timing.sync (a host fetch of
    the smallest output — see the timing module docstring).

    Returns (out, compile_s, run_s).
    """
    first = key not in warm
    # Cost attribution happens BEFORE the timer: the profiler's
    # throwaway AOT compile must never pollute the measured interval,
    # and it must run before the dispatch below donates ``args``'
    # buffers (the profiler only reads avals, but they come from the
    # live arrays).
    entry = None
    before = None
    # A process-level program may have been warmed before the profiler
    # was turned on: its cost is captured on the first dispatch the
    # profiler sees.
    if profiler.enabled and (first or profiler.get(key) is None):
        entry = profiler.capture(key, fn, args)
    if first:
        aotcache.install_listeners()
        before = aotcache.counters(thread=True)
    did = _WARM_DISPATCH
    t0 = time.perf_counter()
    # First dispatches record on ``tracer.active`` (a recompile storm
    # is exactly the signal a flight-recorder postmortem needs); warm
    # dispatches only under a file session — in flight-only mode the
    # enclosing engine_segment span already marks every segment, and
    # the redundant per-segment event would eat the ring.
    span = NOOP_SPAN
    if tracer.enabled or (first and tracer.active):
        span = tracer.span("engine_call", "engine", key=str(key),
                           **(span_args or {}))
    with span:
        out = sync(fn(*args))
        elapsed = time.perf_counter() - t0
        if first:
            did = {"first": True, **aotcache.dispatch_compile(
                elapsed, before, aotcache.counters(thread=True))}
            if did["xla_compiles"]:
                span.name = "jit_compile"
            span.args.update(first=True,
                             cache_loads=did["cache_loads"],
                             xla_compiles=did["xla_compiles"])
            if entry is not None:
                span.args["xla_cost"] = {
                    k: v for k, v in entry.items() if k != "capture_s"
                }
    if report is not None:
        report.update(did)
    if metrics_registry.active:
        _account_jit_call(str(key), first, elapsed)
    if not first:
        efficiency_tracker.record_jit(str(key), first, elapsed)
        return out, 0.0, elapsed
    warm.add(key)
    # Every executable came off the disk cache: the first interval
    # holds trace + retrieval + first run, with zero XLA compile —
    # charge only the retrieval wall to ``compile`` so the cold-start
    # ledger says what actually happened.  Otherwise the whole
    # interval stands.
    disk_compile = (did["compile_s"] if did["cache_loads"]
                    and not did["xla_compiles"] else None)
    # Efficiency plane (observability/efficiency.py): global
    # cold/warm dispatch accounting — the compile column of
    # waste-by-cause, covering every engine that routes through this
    # one chokepoint.
    efficiency_tracker.record_jit(str(key), first, elapsed,
                                  compile_s=disk_compile)
    return out, (elapsed if disk_compile is None else disk_compile), \
        elapsed


def launch_jit_call(warm: set, key, fn, *args):
    """Async-launch a WARM cached-jit dispatch without forcing
    completion (JAX async dispatch: the call returns device futures
    almost immediately while the backend executes).  The pipelined
    serving path uses this to issue dispatch k+1 while dispatch k's
    results are still in flight; :func:`finish_jit_call` later forces
    completion and performs exactly the accounting a warm
    :func:`timed_jit_call` would have.

    Only valid for warm keys: a cold launch would hide trace+compile
    inside an unattributed wait (and the profiler/aotcache cold-call
    bookkeeping lives on the synchronous path).  Callers gate on
    warmth and fall back to ``timed_jit_call`` when cold.
    """
    if key not in warm:
        raise RuntimeError(
            f"launch_jit_call on cold key {key!r}: cold dispatches "
            "must go through timed_jit_call")
    return fn(*args)


def finish_jit_call(key, out, t_launch: float):
    """Force completion of a launched warm dispatch and account it.

    ``t_launch`` is the perf_counter the caller took just before
    :func:`launch_jit_call`; the elapsed interval is the honest device
    wall of the dispatch — launch, execution (possibly overlapped with
    host work on other dispatches) and the residual completion wait.
    Returns ``(out, run_s)``; the warm-call compile time is 0 by
    definition."""
    out = sync(out)
    elapsed = time.perf_counter() - t_launch
    if metrics_registry.active:
        _account_jit_call(str(key), False, elapsed)
    efficiency_tracker.record_jit(str(key), False, elapsed)
    return out, elapsed


def _account_jit_call(skey: str, first: bool, elapsed: float):
    """Per-cache-key compile/dispatch accounting (registry.active
    only — the key label is unbounded across engines, so this is
    opt-in detail): warm-vs-cold call counts plus cold wall seconds,
    the queryable form of "did this run recompile, and what did it
    cost"."""
    metrics_registry.counter(
        "pydcop_jit_calls_total",
        "Engine jit dispatches by cache key and warmth",
    ).inc(key=skey, warmth="cold" if first else "warm")
    if first:
        metrics_registry.counter(
            "pydcop_jit_compile_seconds_total",
            "Wall seconds of cold engine dispatches (trace+compile+"
            "first run) by cache key",
        ).inc(elapsed, key=skey)


def _fn_label(fn) -> str:
    """Stable, low-cardinality name for a solve fn: partials (every
    one-shot algorithm wraps its runner in one) resolve to the
    wrapped function's name — never repr(), whose embedded addresses
    and array dumps would mint a fresh metric label per solve."""
    name = getattr(fn, "__name__", None)
    if name:
        return name
    inner = getattr(fn, "func", None)  # functools.partial
    return getattr(inner, "__name__", None) or type(fn).__name__


def _jit_program(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` as a program called ``name``.  The solver
    programs are ``functools.partial`` objects of an ops function
    (over nothing for a process-level program, whose parameters are
    static arguments; over an engine's parameters for a per-engine
    one), which have no name of their own, so JAX called every one
    of them ``jit__unknown``: in the device trace's ``XLA Modules``
    line, in the HLO the profiler stores, in compile logs.  The name
    is also part of the
    persistent compile cache's key, and HLO metadata is not: a
    change to a program that alters only its metadata (a
    ``jax.named_scope``) has to rename it here, or a shared cache
    directory goes on serving the older executable, whose trace
    lacks the new names."""
    fn.__name__ = name
    return jax.jit(fn, **jit_kwargs)


def shape_signature(graph) -> tuple:
    """What of a graph decides which compiled program runs it: the
    dtype and shape of every array, ``None`` where an optional
    aggregation array is absent.  Edge-major, lane-major and stacked
    graphs alike (shared with engine/batch.py, whose warm keys it
    also makes)."""
    return tuple(
        None if x is None
        else f"{x.dtype.name}[{','.join(map(str, x.shape))}]"
        for x in jax.tree_util.tree_leaves(
            graph, is_leaf=lambda x: x is None))


# The solver parameters a whole-solve, segment or cost-trace program
# is specialised on, besides its cycle count: static arguments, so
# JAX's own cache keys a program on them and on the avals and
# shardings of the graph it is handed.
_SOLVER_STATICS = ("damping", "damp_vars", "damp_factors", "stability",
                   "stop_on_convergence", "prune")

# The programs of the two ops modules (ops/maxsum.py,
# ops/maxsum_lane.py) belong to the process, as engine/batch.py's
# ``_batched_maxsum_solve`` does: nothing in them is an engine's own,
# so a second engine over the same shapes and parameters dispatches
# the program the first one traced, without tracing, lowering or
# reading the disk cache again.  ``_process_warm`` is their
# ``timed_jit_call`` warm set, of MaxSumEngine._program's keys.
_process_programs: Dict[Any, Any] = {}
_process_warm: set = set()


def _process_program(name: str, fn, cycles_arg: str,
                     donate_argnums: tuple = ()):
    """The process's jitted ``fn`` (a function of an ops module) as
    program ``name``, built on first use."""
    key = (name, fn, donate_argnums)
    program = _process_programs.get(key)
    if program is None:
        program = _process_programs.setdefault(key, _jit_program(
            name, partial(fn),
            static_argnames=(cycles_arg,) + _SOLVER_STATICS,
            donate_argnums=donate_argnums))
    return program


def reset_process_programs():
    """Forget the process-level programs and their warmth (what
    ``jax.clear_caches()`` is to JAX): the next solve of any shape
    traces, lowers and compiles or loads from the disk cache again,
    as in a new process.  For tests, and for a long-lived process
    that wants the memory of programs it no longer runs back;
    otherwise a program is kept as long as JAX's jit cache keeps
    it."""
    _process_programs.clear()
    _process_warm.clear()


class _BoundProgram:
    """A process-level program with an engine's parameters bound:
    called and lowered with the arrays alone, as a per-engine
    ``jax.jit(partial(...))`` is."""

    __slots__ = ("program", "statics")

    def __init__(self, program, statics: Dict[str, Any]):
        self.program = program
        self.statics = statics

    def __call__(self, *args, **kwargs):
        return self.program(*args, **kwargs, **self.statics)

    def lower(self, *args, **kwargs):
        return self.program.lower(*args, **kwargs, **self.statics)


class _DecimationRun:
    """Host-side clamp bookkeeping for ONE decimated
    ``run_checkpointed`` call: the fixed-variable mask, the clamped
    unary table, and their rollback snapshot.  All mutation happens at
    segment boundaries on the host; the jitted loop only ever sees a
    fresh (replaced) graph, so decimation adds zero syncs inside it.
    """

    def __init__(self, engine, plan: DecimationPlan,
                 initial: Optional[DecimationState] = None):
        self.engine = engine
        self.plan = plan
        self.n_vars = len(engine.meta.var_names)
        if initial is not None:
            self.fixed = np.asarray(
                jax.device_get(initial.fixed)).astype(bool).copy()
            self.var_costs = np.asarray(
                jax.device_get(initial.var_costs)).copy()
        else:
            self.fixed = np.zeros(self.n_vars, dtype=bool)
            self.var_costs = np.asarray(
                jax.device_get(engine.graph.var_costs)).copy()
        self.rounds = 0
        self.rollbacks = 0
        self._snap = None

    def put(self, arr: np.ndarray):
        """Place a replacement var_costs table like the original: a
        replicated-mesh engine needs the replicated sharding spec, a
        single-device engine a plain device_put."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self.engine.mesh
        if mesh is not None and mesh.size > 1:
            return jax.device_put(
                arr, NamedSharding(mesh, PartitionSpec()))
        return jax.device_put(arr)

    def clamp(self, graph, state, values, margin):
        """Select-and-clamp at one segment boundary.  Returns
        ``(newly_clamped, graph, state)`` — a nonzero clamp count
        replaces the graph's unary table and clears the convergence
        flag (the clamped problem is a new problem; the warm-started
        messages adapt)."""
        margin = np.asarray(jax.device_get(margin))
        vals = np.asarray(jax.device_get(values))
        free = np.nonzero(~self.fixed)[0]
        if free.size == 0:
            return 0, graph, state
        cap = max(1, int(self.plan.frac_per_round * self.n_vars))
        if self.plan.margin > 0:
            eligible = free[margin[free] > self.plan.margin]
        else:
            eligible = free
        order = eligible[np.argsort(-margin[eligible], kind="stable")]
        chosen = order[:cap]
        if chosen.size == 0 and self.plan.force_progress:
            chosen = free[
                np.argsort(-margin[free], kind="stable")[:1]]
        if chosen.size == 0:
            return 0, graph, state
        d = self.var_costs.shape[1]
        for i in chosen:
            keep = int(vals[i])
            row = np.full((d,), BIG, self.var_costs.dtype)
            row[keep] = self.var_costs[i, keep]
            self.var_costs[i] = row
            self.fixed[i] = True
        self.rounds += 1
        graph = graph._replace(
            var_costs=self.put(self.var_costs.copy()))
        state = state._replace(stable=jnp.asarray(False))
        return int(chosen.size), graph, state

    def retain(self, graph):
        """Snapshot the clamp set alongside the recovery run's state
        snapshot: a later rollback must restore BOTH, or the replayed
        segment would run against a clamp set from its future."""
        self._snap = (self.fixed.copy(), self.var_costs.copy(), graph)

    def rollback(self):
        """Restore the clamp set retained with the last validated
        snapshot; returns the graph to continue with."""
        fixed, var_costs, graph = self._snap
        self.fixed = fixed.copy()
        self.var_costs = var_costs.copy()
        self.rollbacks += 1
        return graph

    def snapshot_payload(self, solver_state) -> DecimationState:
        """Checkpoint payload: solver state + the CURRENT clamp set
        (called after the boundary's clamping, so a resume replays
        exactly the uninterrupted sequence)."""
        return DecimationState(
            solver=solver_state,
            fixed=self.fixed.copy(),
            var_costs=self.var_costs.copy(),
        )

    def active_edges(self, graph) -> int:
        """Edge slots whose variable is still free — the per-round
        shrinking work set the metrics report."""
        n = 0
        for b in graph.buckets:
            ids = np.asarray(b.var_ids).reshape(-1)
            real = ids < self.n_vars
            n += int(np.sum(
                real & ~self.fixed[np.minimum(ids, self.n_vars - 1)]))
        return n

    def metrics(self, graph) -> Dict[str, Any]:
        return {
            "decimated_vars": int(self.fixed.sum()),
            "decimated_fraction": (
                float(self.fixed.sum()) / self.n_vars
                if self.n_vars else 0.0),
            "active_edges": self.active_edges(graph),
            "decimation_rounds": self.rounds,
            "decimation_rollbacks": self.rollbacks,
        }


def decimation_template(engine, solver_template) -> DecimationState:
    """Checkpoint restore template of a decimated run (resilience/
    checkpoint.load_state restores into this structure/placement)."""
    n_vars = len(engine.meta.var_names)
    return DecimationState(
        solver=solver_template,
        fixed=np.zeros(n_vars, dtype=bool),
        var_costs=np.asarray(
            jax.device_get(engine.graph.var_costs)).copy(),
    )


def _place_graph(graph: CompiledFactorGraph, mesh,
                 n_devices: Optional[int]):
    """Put the graph on device(s): sharded over a mesh when requested,
    else whole on the default device.  Returns (graph, mesh)."""
    if mesh is None and n_devices is not None and n_devices > 1:
        available = len(jax.devices())
        if n_devices > available:
            raise ValueError(
                f"Requested {n_devices} devices but only {available} "
                "available"
            )
        mesh = make_mesh(n_devices)
    if mesh is not None and mesh.size > 1:
        return shard_graph(graph, mesh), mesh
    return jax.device_put(graph), mesh


def run_device_fn(graph: CompiledFactorGraph, meta: FactorGraphMeta,
                  fn, mesh=None, n_devices: Optional[int] = None,
                  finished: bool = False,
                  warmup: bool = False) -> DeviceRunResult:
    """Jit + run a whole-solve function ``fn(graph) -> (values, cost,
    cycles)`` and package the result (shared by the local-search and
    sweep algorithms).

    One-shot cached-jit dispatch (not ``lower().compile()`` — see
    timed_jit_call).  By default a cold call (fresh
    jit), so per the DeviceRunResult convention time_s and
    compile_time_s both carry the whole wall time and cycles_per_s is a
    lower bound.  With ``warmup=True`` the jitted fn is executed once
    untimed first, so the timed call is steady-state: compile_time_s
    is 0 per the warm-call convention (the warmup wall time, compile +
    one discarded execution, lands in metrics['warmup_time_s']) and
    cycles_per_s is the true run-only rate (use for benchmarking
    one-shot algorithms)."""
    graph, mesh = _place_graph(graph, mesh, n_devices)
    jitted = jax.jit(fn)
    xla_entry = None
    xla_key = None
    if profiler.enabled:
        xla_key = ("device_fn", _fn_label(fn))
        xla_entry = profiler.capture(xla_key, jitted, (graph,))
    compile_s = 0.0
    if warmup:
        t0 = time.perf_counter()
        sync(jitted(graph))
        compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    if tracer.active:
        with tracer.span("device_solve", "engine",
                         warmed=warmup):
            out = sync(jitted(graph))
    else:
        out = sync(jitted(graph))
    t1 = time.perf_counter()
    values, cost, cycles = jax.device_get(out)
    values = np.asarray(values)
    assignment = meta.assignment_from_indices(values)
    sign = 1.0 if meta.mode == "min" else -1.0
    metrics = {
        "device_cost": sign * float(cost) + meta.constant_cost,
        "cycles_per_s": (
            int(cycles) / (t1 - t0) if t1 > t0 else 0.0
        ),
        "cold_start": not warmup,
    }
    if warmup:
        metrics["warmup_time_s"] = compile_s
    if xla_entry is not None:
        metrics["xla_cost"] = {key_str(xla_key): xla_entry}
    return DeviceRunResult(
        assignment=assignment,
        cycles=int(cycles),
        converged=finished,
        time_s=t1 - t0,
        compile_time_s=0.0 if warmup else t1 - t0,
        metrics=metrics,
    )


class MaxSumEngine:
    """Runs MaxSum supersteps on a compiled factor graph.

    Parameters mirror the reference algo_params (maxsum.py:212-220):
    damping (0.5), damping_nodes (vars/factors/both/none), stability
    (0.1).  `noise` is applied at compile time (engine.compile).
    """

    def __init__(self, graph: CompiledFactorGraph, meta: FactorGraphMeta,
                 damping: float = 0.5, damping_nodes: str = "both",
                 stability: float = 0.1,
                 mesh=None, n_devices: Optional[int] = None,
                 layout: str = "edge", donate: bool = True,
                 prune: bool = False):
        if layout not in ("edge", "lane"):
            raise ValueError(
                f"layout must be 'edge' or 'lane', got {layout!r}")
        if prune and layout == "lane":
            raise ValueError(
                "prune=True gathers rows of the edge-major cost "
                "hypercubes; run with layout='edge'")
        self.meta = meta
        self.layout = layout
        if layout == "lane":
            # Lane-major ([D, arity, F], factors on the TPU lane axis
            # — see ops/maxsum_lane.py).  Single-device: shard_graph's
            # row sharding and the sort-based aggregations are
            # edge-major concepts.
            if (mesh is not None and mesh.size > 1) or (
                    n_devices is not None and n_devices > 1):
                raise ValueError(
                    "layout='lane' is single-device; use the default "
                    "edge layout for mesh runs")
            if graph.agg_perm is not None or graph.agg_ell is not None:
                raise ValueError(
                    "layout='lane' uses its own scatter aggregation; "
                    "compile with aggregation='scatter'")
            maxsum_ops.refuse_pallas("layout='lane'")
        # The host's share of putting the graph where it runs (the
        # lane conversion is numpy): its own span under a file
        # session, beside ``compile_graph`` under ``build_engine``.
        traced = tracer.enabled
        with (tracer.span("engine_place", "engine", layout=layout)
              if traced else NOOP_SPAN) as span:
            if layout == "lane":
                self.graph = jax.device_put(
                    lane_ops.to_lane_graph(graph))
                self.mesh = None
            else:
                self.graph, self.mesh = _place_graph(
                    graph, mesh, n_devices)
            if traced:
                span.args["bytes"] = sum(
                    int(leaf.nbytes)
                    for leaf in jax.tree_util.tree_leaves(self.graph))
        if self.mesh is not None and self.mesh.size > 1:
            maxsum_ops.refuse_pallas("n_devices= mesh runs")
        self._ops = lane_ops if layout == "lane" else maxsum_ops
        self._init_solver_state(damping, damping_nodes, stability,
                                donate, prune)

    def _init_solver_state(self, damping: float, damping_nodes: str,
                           stability: float, donate: bool,
                           prune: bool = False):
        """Solver-parameter and runtime-bookkeeping tail shared by
        every engine initializer (ShardedMaxSumEngine builds its own
        graph/ops head, then calls this — one place to grow when the
        runner gains per-engine attributes)."""
        self.damping = damping
        self.damp_vars = damping_nodes in ("vars", "both")
        self.damp_factors = damping_nodes in ("factors", "both")
        self.stability = stability
        # Branch-and-bound message pruning (ops/maxsum.prune_tables).
        # Pruning changes wall-clock, never values.
        self.prune = prune
        # Donate the state argument of the segment program: XLA then
        # writes each segment's output state into the input buffers
        # instead of allocating fresh ones — zero steady-state
        # allocations across a checkpointed/dynamic run.  Donation
        # only changes WHERE outputs land, never their values (the
        # tier-1 battery pins the bit-identical trajectory);
        # ``donate=False`` keeps input states alive for callers that
        # re-run from one (the A/B tests do).
        self.donate = donate
        # Per-engine annotations (e.g. the aggregation autotuner's
        # decision) merged into every DeviceRunResult.metrics: the
        # message layout that ran, and whether a caller named it
        # ("param") or algorithms/maxsum.select_layout chose it
        # ("selected": build_engine overwrites the source).
        self.extra_metrics: Dict[str, Any] = {
            "layout": self.layout, "layout_source": "param"}
        # Extra args stamped onto every engine_segment span (the
        # partitioned engine tags its shard count here so trace
        # tooling can tell sharded segments apart).
        self._segment_span_args: Dict[str, Any] = {}
        # The programs that close over this engine (guard, margin,
        # decimation rounds) and, where ``_ops`` is not an ops module
        # (ShardOps bakes in its mesh), the solver programs too.
        self._jitted: Dict[Any, Any] = {}
        # What the code can observe decides: the solver programs of
        # an ops module are the process's (``_process_program``), and
        # so is their warmth; the rest of their key is then what else
        # JAX keys them on — layout, mesh, the placed graph's arrays.
        if self._ops in (maxsum_ops, lane_ops):
            self._warm = _process_warm
            mesh = self.mesh
            self._placed = (
                self.layout,
                None if mesh is None
                else tuple(d.id for d in mesh.devices.flat),
                shape_signature(self.graph))
        else:
            self._warm = set()
            self._placed = ()

    def _call(self, key, fn, *args, report=None):
        """See timed_jit_call (module level, shared with the dynamic
        engine).  While the profiler is enabled, every compiled
        program's measured cost/memory analysis (or its explicit
        unavailable marker) is folded into ``extra_metrics`` so each
        DeviceRunResult carries ``metrics['xla_cost']`` keyed by cache
        key.  The fold happens once per engine and key (a program may
        be warm in the process when this engine first runs it); later
        dispatches skip the profiler lock."""
        out = timed_jit_call(self._warm, key, fn, *args, report=report,
                             span_args={"layout": self.layout})
        if profiler.enabled:
            skey = key_str(key)
            if skey not in self.extra_metrics.get("xla_cost", ()):
                entry = profiler.get(key)
                if entry is not None:
                    self.extra_metrics.setdefault(
                        "xla_cost", {})[skey] = entry
        return out

    def init_state(self):
        """Fresh solver state for this engine's placed graph — also the
        checkpoint *template*: resilience/checkpoint.py restores
        snapshots into this exact pytree structure (shapes, dtypes,
        device placement)."""
        return self._ops.init_state(self.graph)

    def _program(self, name: str, fn, stop_on_convergence: bool,
                 donate_argnums: tuple = (), **cycles):
        """``(key, program)`` of one solver program: ``fn`` (of
        ``_ops``) specialised on its cycle count (``max_cycles=`` or
        ``extra_cycles=``) and this engine's parameters, called with
        the arrays alone.  ``key`` is its ``timed_jit_call`` key.
        The parameters are read now, not at construction: after a
        recovery damping bump (resilience/recovery.py) the next
        segment is another key and another program, never the one
        specialised on the old damping."""
        statics = dict(
            cycles,
            damping=self.damping,
            damp_vars=self.damp_vars,
            damp_factors=self.damp_factors,
            stability=self.stability,
            stop_on_convergence=stop_on_convergence,
            prune=self.prune,
        )
        key = (name, tuple(sorted(statics.items())),
               donate_argnums) + self._placed
        if self._warm is _process_warm:
            (cycles_arg,) = cycles
            return key, _BoundProgram(
                _process_program(name, fn, cycles_arg, donate_argnums),
                statics)
        if key not in self._jitted:
            self._jitted[key] = _jit_program(
                name, partial(fn, **statics),
                donate_argnums=donate_argnums)
        return key, self._jitted[key]

    def _segment(self, extra_cycles: int, stop_on_convergence: bool):
        """``(key, program)`` of ``run_maxsum_from`` for one K-cycle
        segment (the checkpointed loop re-enters the solve with
        device state, the warm-start primitive dynamic DCOPs already
        use).  With ``donate=True`` (default) the state argument is
        donated, so every segment reuses the previous segment's
        buffers in place — the donated input is dead after the call;
        the loop only ever touches the returned state."""
        return self._program(
            "maxsum_segment", self._ops.run_maxsum_from,
            stop_on_convergence,
            donate_argnums=(1,) if self.donate else (),
            extra_cycles=extra_cycles)

    def _segment_fn(self, extra_cycles: int, stop_on_convergence: bool):
        return self._segment(extra_cycles, stop_on_convergence)[1]

    def _guard_fn(self, with_cost: bool = True):
        """Cached-jit segment-boundary guard: NaN/Inf scan over every
        floating-point state leaf, plus (``with_cost``) the constraint
        cost of the selected assignment — computed ON DEVICE so the
        verdict rides the segment boundary's existing host fetch (no
        syncs enter the jitted loop).  ``with_cost=False`` (the
        default-policy case: divergence guard disabled) skips the cost
        evaluation entirely instead of computing a value nobody reads.
        Pure reads either way: running the guard can never change the
        trajectory (the no-trip bit-identity the battery pins)."""
        key = ("guard", with_cost)
        if key not in self._jitted:
            ops = self._ops

            def guard(graph, state, values):
                finite = jnp.asarray(True)
                for leaf in jax.tree_util.tree_leaves(state):
                    if jnp.issubdtype(leaf.dtype, jnp.inexact):
                        finite = finite & jnp.all(jnp.isfinite(leaf))
                cost = (
                    ops.assignment_constraint_cost(graph, values)
                    if with_cost else jnp.asarray(0.0)
                )
                return finite, cost

            self._jitted[key] = jax.jit(guard)
        return self._jitted[key]

    def run_checkpointed(self, max_cycles: int = 1000, *,
                         manager=None,
                         checkpoint_dir: Optional[str] = None,
                         segment_cycles: Optional[int] = None,
                         stop_on_convergence: bool = True,
                         initial_state=None,
                         max_segments: Optional[int] = None,
                         probe=None,
                         checkpoint_async: bool = True,
                         recovery=None,
                         decimation: Optional[DecimationPlan] = None,
                         ) -> "DeviceRunResult":
        """The solve loop chunked into K-cycle segments with a state
        snapshot between segments — the preemption-survival entry point
        (resilience/checkpoint.py owns the format and the resume side).

        Because each segment re-enters ``run_maxsum_from`` with the
        exact device state the previous one produced, the segmented
        trajectory is the same superstep sequence as :meth:`run`'s
        single XLA program: same assignment, cost and cycle count
        (asserted in the tier-1 resilience battery).

        Steady-state host cost per segment is one scalar fetch (the
        data-dependent cycle counter): with ``checkpoint_async=True``
        (default) the snapshot's device→host copy and atomic NPZ
        write run on a background writer thread
        (resilience.checkpoint.AsyncCheckpointWriter) and overlap the
        NEXT segment's device compute, and with the engine's
        ``donate=True`` each segment reuses the previous state's
        buffers in place (the writer gets a device-side copy so
        donation can never invalidate an in-flight snapshot).
        ``checkpoint_async=False`` restores the synchronous
        fetch-then-write between segments.

        ``manager`` (a resilience.checkpoint.CheckpointManager) or
        ``checkpoint_dir`` enables snapshots; with neither this is just
        a segmented run (still useful to bound time-to-interrupt).
        ``initial_state`` resumes from a restored snapshot (with
        ``donate=True`` the passed state is consumed by the first
        segment — reload it for any later reuse);
        ``max_segments`` stops early after that many segments — the
        test harness's deterministic stand-in for a preemption.
        All snapshots are flushed to disk before this returns,
        whichever mode wrote them.

        ``probe`` (an observability.engine_probe.EngineProbe) receives
        ``on_segment(state, values, run_s, compile_s)`` after every
        segment — the chunk boundary is the only place a host already
        waits, so the probe's cost/convergence points cost no extra
        syncs inside the jitted loop.

        ``recovery`` (a resilience.recovery.RecoveryPolicy) arms the
        segment-boundary GUARD: each segment's end state is validated
        on device (NaN/Inf scan + optional cost-divergence window) and
        a tripped guard rolls back to the last valid in-memory
        snapshot and re-runs under the policy's escalation ladder
        (reseeded tie-break noise -> damping bump -> RecoveryExhausted
        carrying the partial trajectory), bounded by its restart
        budget.  Only VALIDATED states are checkpointed or fed to the
        probe; with no trips the guarded trajectory is bit-identical
        to the unguarded one (guards are pure reads — tier-1
        asserted).

        ``decimation`` (a :class:`DecimationPlan`) turns the segmented
        loop into the decimated solve: at every boundary — the host is
        already synced there — variables whose belief margin clears
        the plan's threshold are clamped to their selected value and
        the graph's unary table replaced (the jitted loop gains zero
        syncs; the clamped problem warm-starts from the surviving
        messages).  The clamp set rides every snapshot
        (:class:`DecimationState`) and every recovery retain, so a
        resume or a guard-trip rollback restores messages AND clamp
        set together — never a stale active-edge mask.  Metrics gain
        ``decimated_vars`` / ``decimated_fraction`` / ``active_edges``
        / ``decimation_rounds`` / ``decimation_rollbacks``.
        """
        from pydcop_tpu.resilience.checkpoint import (
            AsyncCheckpointWriter,
            CheckpointManager,
        )

        if decimation is not None and self._ops is not maxsum_ops:
            raise ValueError(
                "decimation clamps the edge-major var_costs table; "
                "run the unsharded edge-layout engine (no shards=, "
                "layout='edge')")
        if manager is None and checkpoint_dir is not None:
            manager = CheckpointManager(
                checkpoint_dir, every=segment_cycles or 100
            )
        every = segment_cycles or (
            manager.every if manager is not None else 100
        )
        graph = self.graph
        decim = None
        if decimation is not None:
            initial_decim = (
                initial_state
                if isinstance(initial_state, DecimationState) else None
            )
            decim = _DecimationRun(self, decimation, initial_decim)
            if initial_decim is not None:
                graph = graph._replace(
                    var_costs=decim.put(decim.var_costs.copy()))
                initial_state = initial_decim.solver
        elif isinstance(initial_state, DecimationState):
            raise ValueError(
                "initial_state carries a decimation clamp set but no "
                "decimation plan was passed — resuming it without one "
                "would silently solve a different problem")
        state = (
            initial_state if initial_state is not None
            else self.init_state()
        )
        rec = None
        if recovery is not None:
            from pydcop_tpu.resilience.recovery import RecoveryRun

            rec = RecoveryRun(recovery, self)
            # The starting state is the first rollback target: a trip
            # on the very first segment restarts from here — the
            # decimation clamp set must be retained alongside it, or
            # that first-segment rollback would unpack an empty
            # snapshot.
            rec.retain(state, None)
            if decim is not None:
                decim.retain(graph)
        writer = None
        if manager is not None and checkpoint_async:
            writer = AsyncCheckpointWriter(manager)
        t0 = time.perf_counter()
        compile_s = 0.0
        segments = 0
        checkpoints = 0
        interrupted = False
        values = None
        try:
            while True:
                cycle = int(state.cycle)
                if values is not None and (
                    cycle >= max_cycles
                    or (stop_on_convergence and bool(state.stable))
                    # Every variable clamped: the decimated solve is
                    # complete by definition — the clamped unary rows
                    # (BIG off the kept value) push message magnitudes
                    # to the BIG scale where the relative stability
                    # test may never settle, so waiting for it would
                    # burn the whole cycle budget for nothing.
                    or (decim is not None and bool(decim.fixed.all()))
                ):
                    break
                # A resume at/past the cycle budget still needs the
                # value selection: a zero-extra segment computes it
                # without stepping.
                extra = min(every, max(max_cycles - cycle, 0))
                seg_key, fn = self._segment(extra, stop_on_convergence)
                if tracer.active:
                    with tracer.span("engine_segment", "engine",
                                     segment=segments,
                                     from_cycle=cycle,
                                     extra_cycles=extra,
                                     **self._segment_span_args):
                        (state, values), c_s, run_s = self._call(
                            seg_key, fn, graph, state,
                        )
                else:
                    (state, values), c_s, run_s = self._call(
                        seg_key, fn, graph, state,
                    )
                compile_s += c_s
                segments += 1
                if rec is not None:
                    finite, g_cost = jax.device_get(
                        self._guard_fn(
                            recovery.divergence_window > 0
                        )(graph, state, values))
                    violation = rec.check(
                        int(state.cycle), bool(finite), float(g_cost))
                    if violation is not None:
                        # Tripped: the segment's output never reaches
                        # the probe or a checkpoint.  rollback raises
                        # RecoveryExhausted past the restart budget.
                        state, values = rec.rollback(violation)
                        if decim is not None:
                            # The clamp set travels with the snapshot:
                            # resuming the rolled-back messages under
                            # a newer (stale-in-time) active-edge mask
                            # would solve a different problem than the
                            # one the snapshot was validated for.
                            graph = decim.rollback()
                        else:
                            # A shard-loss rollback rebuilt the
                            # engine's graph on the surviving mesh
                            # (repartition_after_loss): re-read it.
                            graph = self.graph
                        if max_segments is not None \
                                and segments >= max_segments:
                            interrupted = True
                            break
                        continue
                    rec.retain(state, values)
                    if decim is not None:
                        decim.retain(graph)
                if probe is not None:
                    probe.on_segment(state, values, run_s, c_s)
                if decim is not None:
                    # Clamp BEFORE the checkpoint: the snapshot then
                    # carries the post-clamp set, and a resume replays
                    # exactly the uninterrupted boundary sequence
                    # (next segment first, next clamp after it).
                    margin = self._margin_fn()(graph, state)
                    newly, graph, state = decim.clamp(
                        graph, state, values, margin)
                    if newly and tracer.active:
                        tracer.instant(
                            "decimation_clamp", "engine",
                            newly_clamped=newly,
                            decimated_vars=int(decim.fixed.sum()),
                            cycle=int(state.cycle))
                if manager is not None:
                    if writer is not None:
                        snap = state
                        if self.donate:
                            # The next segment donates ``state``'s
                            # buffers; the writer must fetch from a
                            # copy that outlives the donation.  The
                            # copy is a device-side program — it
                            # overlaps, no host sync.  The recovery
                            # run already retained exactly that copy
                            # (both sides only read it), so reuse it
                            # rather than paying a second one.  A
                            # decimated run copies fresh instead: the
                            # retained copy predates this boundary's
                            # clamp (stable flag reset).
                            snap = (
                                rec.snapshot_state
                                if rec is not None and decim is None
                                else jax.tree_util.tree_map(
                                    jnp.copy, state)
                            )
                        # snap.cycle, not state.cycle: the original
                        # scalar is donated along with the rest of
                        # the state on the next dispatch.
                        if decim is not None:
                            writer.submit(
                                decim.snapshot_payload(snap),
                                snap.cycle)
                        else:
                            writer.submit(snap, snap.cycle)
                    else:
                        payload = (
                            decim.snapshot_payload(state)
                            if decim is not None else state
                        )
                        manager.save(payload, int(state.cycle))
                    checkpoints += 1
                if max_segments is not None \
                        and segments >= max_segments:
                    interrupted = True
                    break
        finally:
            if writer is not None:
                try:
                    writer.close()
                except Exception:
                    # Don't mask an in-flight engine error with a
                    # checkpoint-write error; with a clean loop exit
                    # the write failure IS the error.
                    if sys.exc_info()[0] is None:
                        raise
        if values is None:
            # Reachable when a guard trip on the very first segment
            # meets a max_segments break: the rollback restored the
            # initial snapshot, which carries no selected values yet.
            # A zero-extra segment computes the selection without
            # stepping (the same trick the resume-at-budget path
            # uses).
            seg_key, fn = self._segment(0, stop_on_convergence)
            (state, values), c_s, _ = self._call(
                seg_key, fn, graph, state)
            compile_s += c_s
        total = time.perf_counter() - t0
        values_host, cycle, stable = jax.device_get(
            (values, state.cycle, state.stable)
        )
        values_host = np.asarray(values_host)
        cycle, stable = int(cycle), bool(stable)
        if decim is not None and decim.fixed.all() and not interrupted:
            # Fully decimated = solved: every variable carries its
            # clamped value (legacy run_decimated convention).
            stable = True
        steady = max(total - compile_s, 0.0)
        return DeviceRunResult(
            assignment=self.meta.assignment_from_indices(values_host),
            cycles=cycle,
            converged=stable,
            time_s=total,
            compile_time_s=compile_s,
            metrics={
                **self.extra_metrics,
                "segments": segments,
                "segment_cycles": every,
                "checkpoints_written": checkpoints,
                "checkpoint_async": writer is not None,
                "interrupted": interrupted,
                "cycles_per_s": cycle / steady if steady > 0 else 0.0,
                "cold_start": compile_s > 0,
                **(rec.metrics() if rec is not None else {}),
                **(decim.metrics(graph) if decim is not None else {}),
            },
        )

    def _margin_fn(self):
        """Cached-jit belief-margin evaluation ([V] gap between best
        and second-best value) — the decimation confidence signal,
        computed on device and fetched at the segment boundary the
        host is already syncing on."""
        key = ("decim_margin",)
        if key not in self._jitted:
            def margin_of(graph, state):
                beliefs, _ = maxsum_ops.aggregate_beliefs(
                    graph, state.f2v)
                masked = jnp.where(
                    graph.var_valid, beliefs, jnp.inf)[:-1]
                best2 = jnp.sort(masked, axis=1)[:, :2]
                return best2[:, 1] - best2[:, 0]

            self._jitted[key] = jax.jit(margin_of)
        return self._jitted[key]

    def _solve(self, max_cycles: int, stop_on_convergence: bool):
        """``(key, program)`` of the whole solve, ``run_maxsum``."""
        return self._program(
            "maxsum_solve", self._ops.run_maxsum, stop_on_convergence,
            max_cycles=max_cycles)

    def _fn(self, max_cycles: int, stop_on_convergence: bool):
        return self._solve(max_cycles, stop_on_convergence)[1]

    def run_trace(self, max_cycles: int,
                  stop_on_convergence: bool = True
                  ) -> "DeviceRunResult":
        """Run recording the constraint cost of the selected
        assignment after every cycle (metrics['cost_trace'], numpy
        [max_cycles]) — the curve behind time-to-equal-cost claims.
        Default ``stop_on_convergence`` matches
        :meth:`run`: the loop exits at the fixpoint, the cycle count
        agrees with an untraced solve, and the curve's tail holds the
        final cost (still a valid anytime record at full length)."""
        key, fn = self._program(
            "maxsum_cost_trace", self._ops.run_maxsum_trace,
            stop_on_convergence, max_cycles=max_cycles)
        # An argument of the program, not a constant closed over: the
        # program is then the same for every problem of these shapes.
        base = self.meta.var_base_costs
        if base is not None:
            base = jnp.asarray(base)
        (state, values, costs), compile_s, run_s = self._call(
            key + shape_signature(base),
            partial(fn, var_base_costs=base), self.graph)
        values, cycle, stable, costs = jax.device_get(
            (values, state.cycle, state.stable, costs)
        )
        values = np.asarray(values)
        sign = 1.0 if self.meta.mode == "min" else -1.0
        return DeviceRunResult(
            assignment=self.meta.assignment_from_indices(values),
            cycles=int(cycle),
            converged=bool(stable),
            time_s=run_s,
            compile_time_s=compile_s,
            metrics={
                **self.extra_metrics,
                "cost_trace": sign * np.asarray(costs)
                + self.meta.constant_cost,
                "cold_start": compile_s > 0,
            },
        )

    def run_decimated(self, max_cycles: int = 1000,
                      frac: float = 0.1,
                      cycles_per_round: int = 60) -> DeviceRunResult:
        """MaxSum with decimation (Improving Max-Sum through Decimation,
        arXiv:1706.02209): alternate message passing with fixing the
        most *confident* variables — those with the largest belief
        margin between their best and second-best value — by clamping
        their unary costs, then warm-restarting the messages.  On loopy
        graphs this breaks the oscillations that keep plain MaxSum away
        from good assignments, at the price of a handful of
        host-driven rounds (each round is still one XLA program).

        ``frac`` of all variables (at least 1, capped to the remaining
        free set) is fixed per round; runs until every variable is
        fixed or ``max_cycles`` total cycles are spent.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        if self.layout != "edge":
            raise ValueError(
                "decimation clamps rows of the edge-major var_costs "
                "table; run with layout='edge'")
        n_vars = len(self.meta.var_names)
        dmax = self.graph.var_costs.shape[1]
        var_costs = np.asarray(self.graph.var_costs).copy()
        fixed = np.zeros(n_vars, dtype=bool)
        graph = self.graph
        state = maxsum_ops.init_state(graph)

        compile_s = 0.0

        def _call_round(extra, g, s):
            """Run one compiled round via cached-jit dispatch (see
            timed_jit_call for why never AOT lower/compile).  The
            first call per round length is timed as compile — it
            includes one execution, but compile dominates, the same
            approximation every engine entry point uses."""
            nonlocal compile_s
            key = ("decim", extra)
            first_call = key not in self._jitted
            if first_call:
                def _round(g, s, _extra=extra):
                    s, values = maxsum_ops.run_maxsum_from(
                        g, s, _extra,
                        damping=self.damping,
                        damp_vars=self.damp_vars,
                        damp_factors=self.damp_factors,
                        stability=self.stability,
                        stop_on_convergence=True,
                    )
                    beliefs, _ = maxsum_ops.aggregate_beliefs(g, s.f2v)
                    masked = jnp.where(
                        g.var_valid, beliefs, jnp.inf)[:-1]
                    best2 = jnp.sort(masked, axis=1)[:, :2]
                    margin = best2[:, 1] - best2[:, 0]
                    return s, values, margin

                self._jitted[key] = jax.jit(_round)
            tc = time.perf_counter()
            out = self._jitted[key](g, s)
            if first_call:
                sync(out)
                compile_s += time.perf_counter() - tc
            return out

        def _put(arr):
            if self.mesh is not None and self.mesh.size > 1:
                return jax.device_put(
                    arr, NamedSharding(self.mesh, PartitionSpec()))
            return jax.device_put(arr)

        t0 = time.perf_counter()
        values = None
        while True:
            # Never overshoot the caller's cycle budget: the final
            # round runs only the remainder (at most one extra compile
            # for the non-standard round length).
            remaining = max_cycles - int(state.cycle)
            if remaining <= 0 and values is not None:
                break
            extra = min(cycles_per_round, max(remaining, 1))
            state, values, margin = _call_round(extra, graph, state)
            if bool(np.all(fixed)) or \
                    int(state.cycle) >= max_cycles:
                break
            margin = np.asarray(margin)
            vals_host = np.asarray(values)
            free = np.nonzero(~fixed)[0]
            if free.size == 0:
                break
            k = max(1, int(frac * n_vars))
            chosen = free[np.argsort(-margin[free])[:k]]
            for i in chosen:
                keep = int(vals_host[i])
                clamp = np.full(dmax, BIG, np.float32)
                clamp[keep] = var_costs[i, keep]
                var_costs[i] = clamp
                fixed[i] = True
            graph = graph._replace(var_costs=_put(var_costs.copy()))
            # Clamped costs changed the problem: clear convergence so
            # the warm-started messages adapt.
            state = state._replace(stable=jnp.asarray(False))
        sync(values)
        total = time.perf_counter() - t0
        # DeviceRunResult convention: time_s = total wall including
        # compiles; steady-state rate uses the compile-free remainder.
        steady = max(total - compile_s, 0.0)
        values = np.asarray(jax.device_get(values))
        cycle = int(state.cycle)
        return DeviceRunResult(
            assignment=self.meta.assignment_from_indices(values),
            cycles=cycle,
            converged=bool(np.all(fixed)),
            time_s=total,
            compile_time_s=compile_s,
            metrics={
                **self.extra_metrics,
                "decimated_vars": int(fixed.sum()),
                "cycles_per_s": cycle / steady if steady > 0 else 0.0,
                "cold_start": compile_s > 0,
            },
        )

    def run(self, max_cycles: int = 1000,
            stop_on_convergence: bool = True) -> DeviceRunResult:
        """Steady-state ``time_s`` requires a prior call, by any
        engine of this process, with the same parameters over a graph
        of the same shapes: a first call's ``time_s`` holds trace +
        compile (or cache load) + run.  Its ``compile_time_s`` is the
        seconds XLA compiled or loaded the program from the disk
        cache, from the counters (0 when neither happened), not a
        copy of ``time_s``."""
        key, fn = self._solve(max_cycles, stop_on_convergence)
        did: Dict[str, Any] = {}
        (state, values), _, run_s = self._call(
            key, fn, self.graph, report=did)
        with (tracer.span("result_decode", "engine")
              if tracer.enabled else NOOP_SPAN):
            return self._decode(state, values, run_s, did)

    def _decode(self, state, values, run_s: float,
                did: Dict[str, Any]) -> DeviceRunResult:
        """The host's tail of :meth:`run`, after the dispatch has
        returned: the outputs fetched, the assignment by name, the
        metrics."""
        # One host transfer for all three outputs.
        values, cycle, stable = jax.device_get(
            (values, state.cycle, state.stable)
        )
        values = np.asarray(values)
        cycle, stable = int(cycle), bool(stable)
        assignment = self.meta.assignment_from_indices(values)
        n_msgs = sum(
            int(np.prod(b.var_ids.shape)) for b in self.graph.buckets
        )
        return DeviceRunResult(
            assignment=assignment,
            cycles=cycle,
            converged=stable,
            time_s=run_s,
            compile_time_s=did["compile_s"],
            metrics={
                **self.extra_metrics,
                "msg_count": 2 * n_msgs * cycle,
                "cycles_per_s": cycle / run_s if run_s > 0 else 0.0,
                "cold_start": did["first"],
            },
        )


class ShardedMaxSumEngine(MaxSumEngine):
    """MaxSum on a PARTITIONED factor graph: each shard owns a local
    slice of the variable tables and the messages of its own factors;
    the per-superstep cross-shard traffic is the compacted ``[B, D]``
    halo buffer (B = cut-edge endpoint count) instead of the
    replicated path's dense ``[V+1, D]`` all-reduce — O(cut·D), not
    O(V·D) (engine/sharding.py: build_partitioned_graph + ShardOps;
    engine/partition.py: the min-edge-cut partitioner).

    Everything above the kernel — segmented runs, checkpointing,
    recovery guards, probes — is inherited from MaxSumEngine through
    the ``_ops`` seam: ShardOps exposes the ops.maxsum call surface
    (init_state / run_maxsum / run_maxsum_from / run_maxsum_trace /
    assignment_constraint_cost) over the sharded state, and the
    returned ``values`` are already reassembled to global order.

    ``metrics`` on every result carry the partition statistics
    (``edge_cut_fraction``, ``halo_vars_per_shard``, ``balance``) and
    the communication accounting
    (``halo_exchange_elems_per_superstep`` vs
    ``replicated_allreduce_elems_per_superstep``)."""

    def __init__(self, graph: CompiledFactorGraph,
                 meta: FactorGraphMeta, *,
                 n_shards: Optional[int] = None, mesh=None,
                 partition=None,
                 damping: float = 0.5, damping_nodes: str = "both",
                 stability: float = 0.1, donate: bool = True,
                 prune: bool = False):
        from pydcop_tpu.engine.partition import partition_compiled
        from pydcop_tpu.engine.sharding import (
            ShardOps,
            build_partitioned_graph,
        )

        if mesh is None:
            mesh = make_mesh(n_shards)
        if mesh.size < 2:
            raise ValueError(
                "partitioned sharding needs a mesh of >= 2 devices; "
                "run unsharded (or force host devices with XLA_FLAGS="
                "--xla_force_host_platform_device_count=N for CPU "
                "testing)")
        maxsum_ops.refuse_pallas("shards= mesh runs")
        if partition is None:
            partition = partition_compiled(graph, mesh.size)
        self.meta = meta
        # Edge-major messages (the probe's layout contract); the
        # partitioning is orthogonal to the layout.
        self.layout = "edge"
        self.mesh = mesh
        self.partition = partition
        # Kept for shard-loss recovery: re-partitioning onto a
        # surviving mesh rebuilds the per-shard layout from the
        # ORIGINAL compiled graph (repartition_after_loss).
        self._source_graph = graph
        self.graph, part_metrics = build_partitioned_graph(
            graph, partition, mesh)
        self._ops = ShardOps(mesh, len(meta.var_names))
        self._init_solver_state(damping, damping_nodes, stability,
                                donate, prune)
        self.extra_metrics.update(part_metrics)
        self._segment_span_args["shards"] = mesh.size

    def _call(self, key, fn, *args, report=None):
        out = super()._call(key, fn, *args, report=report)
        if tracer.active:
            # One instant per shard with its static partition stats:
            # the honest per-shard facts a single-program dispatch
            # can report (per-shard wall time does not exist — the
            # mesh runs one XLA program).  Trace merge routes
            # shard-tagged events onto distinct lanes.
            owned = self.extra_metrics.get(
                "owned_vars_per_shard", [])
            halo = self.extra_metrics.get(
                "halo_vars_per_shard", [])
            for s in range(self.mesh.size):
                tracer.instant(
                    "shard_segment", "engine", shard=s,
                    owned_vars=owned[s] if s < len(owned) else None,
                    halo_vars=halo[s] if s < len(halo) else None,
                    key=str(key),
                )
        return out

    def repartition_after_loss(self, lost_shard: int,
                               snapshot_state):
        """Shard-loss recovery: rebuild this engine on the surviving
        mesh and remap a validated snapshot onto the new layout.

        Called by the recovery run (resilience/recovery.py) when a
        ``shard_loss`` guard trips.  The sequence: (1) a fresh 1-D
        mesh over the surviving devices, (2) a re-partition of the
        ORIGINAL compiled graph onto it — memoized by structure key +
        shard count (engine/partition.partition_cache), so a repeated
        loss pattern re-partitions from cache, (3) the per-shard
        layout rebuilt, (4) the snapshot's messages remapped onto the
        new factor→shard packing with the halo buffer recomputed
        on-device (engine/sharding.remap_partitioned_state), and
        (5) every cached jit/warm entry dropped — the old programs
        baked in the dead mesh.  Returns the remapped state to resume
        from; raises :class:`~pydcop_tpu.resilience.recovery.
        NoSurvivingDevices` when the mesh would be empty.

        The repartition + remap wall time lands in
        ``extra_metrics['shard_recovery_s']`` (the bench's
        per-backend recovery-time series).
        """
        from jax.sharding import Mesh

        from pydcop_tpu.engine.partition import partition_compiled
        from pydcop_tpu.engine.sharding import (
            SHARD_AXIS,
            ShardOps,
            build_partitioned_graph,
            remap_partitioned_state,
        )
        from pydcop_tpu.resilience.recovery import NoSurvivingDevices

        t0 = time.perf_counter()
        devices = list(self.mesh.devices.flat)
        if not 0 <= lost_shard < len(devices):
            raise ValueError(
                f"lost shard {lost_shard} out of range for a mesh "
                f"of {len(devices)}")
        survivors = [d for i, d in enumerate(devices)
                     if i != lost_shard]
        if not survivors:
            raise NoSurvivingDevices(
                f"shard {lost_shard} was the last device")
        new_mesh = Mesh(np.array(survivors), (SHARD_AXIS,))
        new_part = partition_compiled(self._source_graph,
                                      new_mesh.size)
        new_graph, part_metrics = build_partitioned_graph(
            self._source_graph, new_part, new_mesh)
        new_ops = ShardOps(new_mesh, len(self.meta.var_names))
        state = remap_partitioned_state(
            self._source_graph, self.partition, new_part,
            snapshot_state, new_graph, new_ops)
        self.mesh = new_mesh
        self.partition = new_part
        self.graph = new_graph
        self._ops = new_ops
        # Stale compiled programs reference the dead mesh; the next
        # segment call recompiles against the survivors.
        self._jitted.clear()
        self._warm.clear()
        self.extra_metrics.update(part_metrics)
        self.extra_metrics["repartitions"] = (
            self.extra_metrics.get("repartitions", 0) + 1)
        self.extra_metrics.setdefault(
            "lost_shards", []).append(int(lost_shard))
        self.extra_metrics["shard_recovery_s"] = round(
            time.perf_counter() - t0, 4)
        self._segment_span_args["shards"] = new_mesh.size
        return state

    def run_decimated(self, *args, **kwargs):
        raise ValueError(
            "decimation clamps rows of the single-device var_costs "
            "table; run without shards= (or use the replicated "
            "n_devices= path)")
