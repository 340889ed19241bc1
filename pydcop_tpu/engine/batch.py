"""Batched multi-instance solving: many DCOPs in ONE XLA program.

A capability the reference architecture cannot express: its benchmark
sweeps (`pydcop batch`) run one subprocess per instance
(pydcop/commands/batch.py), paying process + solve overhead per run.
On device, same-shaped compiled graphs stack into batched arrays and
`jax.vmap` turns the whole MaxSum solve into a single program over the
instance axis — N problems cost barely more than one (the MXU/VPU work
batches; the host launches once).

Shape contract: every instance must compile to identical array shapes
(same variable count, same dmax, same bucket layout) — exactly what
seeded generator sweeps produce (same config, different seeds or cost
tables).  A shape mismatch raises instead of silently padding, so the
caller controls the batching granularity.

This module is ALSO the serving hot path (pydcop_tpu/serving/): the
request scheduler stacks same-structure-bin requests and dispatches
them through :func:`run_stacked`.  Two serving-driven extensions:

- **Padding to bin sizes.** A jitted batched program re-traces per
  batch size, so a scheduler dispatching raw batch sizes 3, 5, 7, 6 …
  would compile a fresh program per straggler count.  ``pad_to_bins``
  rounds the stack up to a fixed ladder of sizes (duplicating the
  last instance; padded lanes are computed and discarded), bounding
  the number of compiled programs per structure to ``len(bins)``.

- **Honest padding accounting.** Padded lanes are wasted device work,
  so every padded dispatch reports ``pad_fraction`` (padded lanes /
  batch size) in ``DeviceRunResult.metrics`` — the serving
  batch-occupancy telemetry reads it instead of guessing.

Two heterogeneous-structure extensions (ISSUE 11) relax the
same-shape contract for the serving tier WITHOUT giving up
bit-identical per-request results:

- **Shape-envelope stacking.** :func:`pad_graph_to_envelope` mask-pads
  a compiled graph up to a shape envelope (serving/binning.Envelope):
  extra domain slots get ``BIG`` cost and ``var_valid=False`` (the
  compile-time domain-padding discipline), extra variable rows are
  dead invalid rows, and extra bucket rows are zero-cost rows pointing
  at the sentinel variable (the PR-7 autopad pattern) — every kernel
  already masks all three, so a padded graph's real variables see
  bit-identical messages.  :func:`stack_to_envelope` pads a
  *different*-structure group to one envelope and stacks it for a
  single vmapped dispatch; ``run_stacked(envelope=...)`` reports
  honest per-lane ``envelope_waste`` next to ``pad_fraction``.

- **Lane packing.** :func:`run_lane_packed` routes a tiny-domain group
  through ops/maxsum_lane instead: the graphs are concatenated into
  one disjoint-union factor graph (factors on the lane axis, no
  per-member shape padding at all — the only mask waste is the shared
  domain rung), solved as one program, and sliced back per member.
"""

import contextlib
import functools
import time
from typing import (
    Any,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import (
    BIG,
    CompiledFactorGraph,
    FactorBucket,
    FactorGraphMeta,
    compile_dcop,
)
from pydcop_tpu.engine.runner import (
    DeviceRunResult,
    finish_jit_call,
    launch_jit_call,
    shape_signature as _shape_signature,
    timed_jit_call,
)
from pydcop_tpu.observability import efficiency
from pydcop_tpu.observability.profiler import profiler
from pydcop_tpu.observability.trace import NOOP_SPAN, tracer
from pydcop_tpu.ops import maxsum as maxsum_ops

# Batch-size ladder used when a caller asks for bin padding without
# giving one: powers of two keep the compiled-program count per
# structure logarithmic in the largest batch.
DEFAULT_BIN_SIZES = (1, 2, 4, 8, 16, 32, 64)

# jit-cache warmth per (shape-signature, solver statics) — feeds the
# cold/warm split in timed_jit_call so serving dispatch latencies can
# separate compile stalls from steady-state batches.
_warm: set = set()


def stack_graphs(
    graphs: Sequence[CompiledFactorGraph],
) -> CompiledFactorGraph:
    """Stack same-shaped compiled graphs along a new leading axis."""
    shapes = [
        (g.var_costs.shape,) + tuple(b.costs.shape for b in g.buckets)
        for g in graphs
    ]
    if any(s != shapes[0] for s in shapes):
        raise ValueError(
            "Batched solving requires identical compiled shapes; got "
            f"{sorted(set(shapes))}"
        )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *graphs)


# Pre-promotion private name, kept for external callers.
_stack_graphs = stack_graphs


def bin_size_for(n: int, bin_sizes: Sequence[int]) -> int:
    """Smallest ladder size >= n; n itself when the ladder tops out
    below it (an oversized dispatch compiles once for its exact size
    rather than failing)."""
    for b in sorted(bin_sizes):
        if b >= n:
            return b
    return n


def pad_to_bin(
    graphs: Sequence[CompiledFactorGraph],
    bin_sizes: Sequence[int] = DEFAULT_BIN_SIZES,
) -> Tuple[List[CompiledFactorGraph], int, float]:
    """Pad a graph list up to the next bin size by repeating the last
    instance.  Returns (padded_graphs, n_real, pad_fraction) — padded
    lanes solve a duplicate problem whose results the caller drops.
    """
    n_real = len(graphs)
    if n_real == 0:
        return [], 0, 0.0
    target = bin_size_for(n_real, bin_sizes)
    padded = list(graphs) + [graphs[-1]] * (target - n_real)
    return padded, n_real, (target - n_real) / target


def _array_cells(graph: CompiledFactorGraph) -> int:
    """Total var-table + bucket-hypercube elements (the waste unit).
    ONE definition, shared with the scheduler's cost model: the
    pack-vs-solo decision (serving/binning.pack_decision) and the
    reported ``envelope_waste`` must never drift apart."""
    from pydcop_tpu.serving.binning import graph_cells

    return graph_cells(graph)


def pad_graph_to_envelope(graph: CompiledFactorGraph,
                          env) -> CompiledFactorGraph:
    """Mask-pad a compiled graph up to a shape envelope
    (serving/binning.Envelope, duck-typed: ``v_env``/``d_env``/
    ``rows``).  Every padding element is inert by the same masking the
    compiler already emits, so the padded graph's real variables
    compute BIT-IDENTICAL messages (battery-asserted):

    - domain slots ``d..d_env``: ``BIG`` cost, ``var_valid=False`` —
      they never win a min-reduction, are excluded from the
      mean-normalization and convergence test, and are masked out of
      the final argmin;
    - variable rows ``v..v_env``: invalid rows no factor references
      (nothing scatters into them, their argmin result is dropped);
    - bucket rows ``F..rows_env``: zero-cost rows whose ``var_ids``
      all point at the sentinel row ``v_env`` (the PR-7 autopad
      pattern — their messages aggregate into the sentinel row, which
      every consumer drops).

    The envelope must COVER the graph (each dimension >= the real
    size, identical arity set) — a violated envelope raises instead of
    silently truncating.  Aggregation arrays are dropped (scatter
    path), matching the serving dispatch's compiled graphs.
    """
    v, d = graph.n_vars, graph.dmax
    by_arity = {b.arity: b for b in graph.buckets}
    env_rows = dict(env.rows)
    if env.v_env < v or env.d_env < d:
        raise ValueError(
            f"envelope (v={env.v_env}, d={env.d_env}) does not cover "
            f"graph (v={v}, d={d})")
    if set(env_rows) != set(by_arity):
        raise ValueError(
            f"envelope arities {sorted(env_rows)} != graph arities "
            f"{sorted(by_arity)}")
    for a, b in by_arity.items():
        if env_rows[a] < b.n_factors:
            raise ValueError(
                f"envelope rows {env_rows[a]} < {b.n_factors} factors "
                f"at arity {a}")
    if (env.v_env == v and env.d_env == d
            and all(env_rows[a] == b.n_factors
                    for a, b in by_arity.items())):
        # Exact fit: nothing to pad, but the drop-aggregation-arrays
        # contract still holds — an exact-fit member stacked next to
        # padded members (agg fields None) must have the same pytree
        # structure, and agg array shapes (e.g. ell's [V+1, K]) are
        # not envelope-determined.
        if all(a is None for a in (graph.agg_perm,
                                   graph.agg_sorted_seg,
                                   graph.agg_starts, graph.agg_ends,
                                   graph.agg_ell)):
            return graph
        return CompiledFactorGraph(
            var_costs=graph.var_costs, var_valid=graph.var_valid,
            buckets=graph.buckets,
        )

    ve, de = env.v_env, env.d_env
    dtype = graph.var_costs.dtype
    var_costs = np.full((ve + 1, de), BIG, dtype=dtype)
    var_costs[:v, :d] = np.asarray(graph.var_costs)[:v]
    var_valid = np.zeros((ve + 1, de), dtype=bool)
    var_valid[:v, :d] = np.asarray(graph.var_valid)[:v]

    buckets = []
    for a in sorted(env_rows):
        b = by_arity[a]
        n_facs = b.n_factors
        costs = np.zeros((env_rows[a],) + (de,) * a,
                         dtype=b.costs.dtype)
        if n_facs:
            block = np.full((n_facs,) + (de,) * a, BIG,
                            dtype=b.costs.dtype)
            block[(slice(None),) + (slice(0, d),) * a] = \
                np.asarray(b.costs)
            costs[:n_facs] = block
        ids = np.full((env_rows[a], a), ve, dtype=np.int32)
        real_ids = np.asarray(b.var_ids).copy()
        # Re-point the graph's own sentinel (index v) at the
        # envelope's (index ve) — compile-time padding rows must stay
        # masked after the variable table grows.
        real_ids[real_ids == v] = ve
        ids[:n_facs] = real_ids
        buckets.append(FactorBucket(costs=costs, var_ids=ids))
    return CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid,
        buckets=tuple(buckets),
    )


def stack_to_envelope(
    graphs: Sequence[CompiledFactorGraph], env,
) -> Tuple[List[CompiledFactorGraph], List[float]]:
    """Pad a *different*-structure group up to one shape envelope so
    it stacks (``stack_graphs``) into a single vmapped dispatch.
    Returns ``(padded_graphs, envelope_waste)`` — per-member wasted
    fraction of the envelope's cells (``1 - real/envelope``), the
    honest-padding number ``run_stacked`` reports per dispatch."""
    padded = [pad_graph_to_envelope(g, env) for g in graphs]
    waste = [
        round(1.0 - _array_cells(g) / max(_array_cells(p), 1), 4)
        for g, p in zip(graphs, padded)
    ]
    return padded, waste


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_cycles", "damping", "damp_vars", "damp_factors",
        "stability", "prune",
    ),
)
def _batched_maxsum_solve(stacked, *, max_cycles, damping, damp_vars,
                   damp_factors, stability, prune=False):
    """One jitted program per solver-parameter combination (jit's own
    cache keys on the static args), reused across calls — a fresh
    closure per call would retrace and recompile every time.

    ``prune`` threads branch-and-bound pruning into each lane.  Under
    vmap the per-lane phase predicates batch, so the dense/compacted
    alternation degrades toward evaluating both sides more often than
    the solo engine would — the decision consumed here
    (serving/service: prune="auto") was raced on the SOLO path, where
    the win is largest; results are identical either way."""

    def solve_one(graph):
        state, values = maxsum_ops.run_maxsum(
            graph, max_cycles,
            damping=damping,
            damp_vars=damp_vars,
            damp_factors=damp_factors,
            stability=stability,
            stop_on_convergence=False,
            prune=prune,
        )
        return values, state.cycle, state.stable

    return jax.vmap(solve_one)(stacked)


# The rollup's per-structure cell label (ONE definition, shared with
# the dynamic engine — observability/efficiency.py).
_structure_label = efficiency.structure_label


def _assembly_span(n_real: int, packing: str):
    """``batch_assemble``, under a file session: a synchronous batch's
    assembly on the host, before ``timed_jit_call`` has the work (its
    ``engine_call`` holds the launch).  A pipelined dispatch assembles
    under ``SolveService.launch_dispatch``'s ``serve_launch``."""
    if not tracer.enabled:
        return NOOP_SPAN
    return tracer.span("batch_assemble", "engine", n_real=n_real,
                       packing=packing)


class _StackedPrep(NamedTuple):
    """Host-side assembly of one stacked dispatch — everything the
    decode/accounting tail needs, shared by the synchronous
    (:func:`run_stacked`) and pipelined (:func:`launch_stacked` /
    :func:`collect_stacked`) paths so the two cannot drift."""

    graphs: tuple
    stacked: CompiledFactorGraph
    statics: dict
    key: tuple
    n_real: int
    pad_fraction: float
    envelope_waste: Optional[List[float]]
    max_cycles: int
    t_pack: float


class PendingDispatch(NamedTuple):
    """A launched-but-uncollected device dispatch (JAX async
    dispatch): the device is executing while the host does other work.
    Produced by :func:`launch_stacked` / :func:`launch_lane_packed`,
    consumed exactly once by the matching ``collect_*``."""

    kind: str         # "stacked" | "lane"
    raw: Any          # launched device outputs (futures)
    prep: Any
    key: tuple
    t_launch: float


def _prepare_stacked(graphs, max_cycles, damping, damping_nodes,
                     stability, pad_to_bins, prune,
                     envelope) -> _StackedPrep:
    if not graphs:
        raise ValueError("run_stacked needs at least one graph")
    t_pack = time.perf_counter()
    envelope_waste: Optional[List[float]] = None
    if envelope is not None:
        graphs, envelope_waste = stack_to_envelope(graphs, envelope)
    n_real = len(graphs)
    pad_fraction = 0.0
    if pad_to_bins is not None:
        graphs, n_real, pad_fraction = pad_to_bin(graphs, pad_to_bins)
    stacked = stack_graphs(graphs)
    statics = dict(
        max_cycles=max_cycles,
        damping=damping,
        damp_vars=damping_nodes in ("vars", "both"),
        damp_factors=damping_nodes in ("factors", "both"),
        stability=stability,
        prune=prune,
    )
    key = (
        "maxsum_batch", len(graphs), _shape_signature(stacked),
        tuple(sorted(statics.items())),
    )
    return _StackedPrep(tuple(graphs), stacked, statics, key, n_real,
                        pad_fraction, envelope_waste, max_cycles,
                        t_pack)


def _finish_stacked(prep: _StackedPrep, values, cycles, stable,
                    elapsed: float, compile_s: float, run_s: float,
                    t0: float, pipelined: bool = False):
    """Decode + accounting tail shared by both dispatch paths: ONE
    coalesced ``device_get`` for the whole output pytree (one host
    sync per dispatch instead of three), then the DeviceRunResult
    metrics and the efficiency-plane dispatch sample."""
    values, cycles, stable = jax.device_get((values, cycles, stable))
    n_real = prep.n_real
    values = np.asarray(values)[:n_real]
    cycles = np.asarray(cycles)[:n_real]
    stable = np.asarray(stable)[:n_real]
    batch_result = DeviceRunResult(
        assignment={},
        cycles=int(cycles.max()) if cycles.size else 0,
        converged=bool(stable.all()) if stable.size else False,
        time_s=elapsed,
        compile_time_s=compile_s,
        metrics={
            "batch_size": len(prep.graphs),
            "n_real": n_real,
            "pad_fraction": prep.pad_fraction,
            "cold_start": compile_s > 0.0,
            "run_time_s": run_s,
            # Host-side batch assembly (envelope padding + stacking),
            # the ledger's ``prep`` share of this dispatch.
            "pack_host_s": t0 - prep.t_pack,
            # Per-request convergence verdicts (real lanes, dispatch
            # order): the serve plane folds lane i's flag into
            # request i's result.
            "converged_lanes": [bool(s) for s in stable],
            # Total device cells of the dispatched stack (padding
            # lanes included) and the jit program key: the
            # self-tuning pack planner regresses measured execute
            # walls on cells, and the speculative compiler matches
            # completed programs against its precompiled set.
            "cells_total": (_array_cells(prep.graphs[0])
                            * len(prep.graphs)),
            "program_key": str(prep.key),
        },
    )
    if pipelined:
        batch_result.metrics["pipelined"] = True
    if prep.envelope_waste is not None:
        envelope_waste = prep.envelope_waste
        batch_result.metrics["packing"] = "envelope"
        batch_result.metrics["envelope_waste_lanes"] = envelope_waste
        batch_result.metrics["envelope_waste"] = round(
            sum(envelope_waste) / len(envelope_waste), 4
        ) if envelope_waste else 0.0
    # Efficiency accounting: every batched dispatch is an attainment
    # sample — all lanes run the full max_cycles budget (no early
    # stop on the batched path), so the XLA per-iteration cost entry
    # scales by exactly max_cycles.  Everything (labels, backend
    # resolution) stays behind the enabled gate: PYDCOP_EFFICIENCY=0
    # must mean zero work, not discarded work.
    if efficiency.tracker.enabled:
        # Structure label AFTER envelope padding: a packed dispatch
        # runs ONE compiled envelope shape — labeling by whichever
        # member happened to be first would scatter the same program
        # across structure cells (the lane path labels its packed
        # union the same way).
        record = efficiency.tracker.record_dispatch(
            key=str(prep.key),
            structure=_structure_label(prep.graphs[0]),
            backend=efficiency.backend_name(),
            # The INNER device wall (sync-honest), not the outer
            # elapsed: the outer interval also holds the profiler's
            # one-off AOT capture on cold dispatches, which is host
            # work, not device attainment denominator.
            time_s=run_s, compile_s=compile_s, cycles=prep.max_cycles,
            n_real=n_real, batch_size=len(prep.graphs),
            pad_fraction=prep.pad_fraction,
            envelope_waste=batch_result.metrics.get(
                "envelope_waste", 0.0) or 0.0,
            packing=batch_result.metrics.get("packing") or (
                "batched" if n_real > 1 else "solo"),
            cost_entry=(profiler.get(prep.key)
                        if profiler.enabled else None),
        )
        if record is not None:
            batch_result.metrics["efficiency"] = record
    return values, cycles, batch_result


def launch_stacked(
    graphs: Sequence[CompiledFactorGraph],
    max_cycles: int = 200,
    damping: float = 0.5,
    damping_nodes: str = "both",
    stability: float = 0.1,
    pad_to_bins: Optional[Sequence[int]] = None,
    prune: bool = False,
    envelope=None,
) -> Optional[PendingDispatch]:
    """Async-launch a stacked dispatch without waiting for results
    (the pipelined serving flush: dispatch k+1 launches while k's
    arrays are still in flight).  Returns ``None`` when the program
    is COLD — trace+compile must stay on the synchronous
    :func:`run_stacked` path where the profiler/aotcache cold-call
    attribution lives — and the caller falls back."""
    prep = _prepare_stacked(graphs, max_cycles, damping,
                            damping_nodes, stability, pad_to_bins,
                            prune, envelope)
    if prep.key not in _warm:
        return None
    t0 = time.perf_counter()
    raw = launch_jit_call(
        _warm, prep.key,
        functools.partial(_batched_maxsum_solve, **prep.statics),
        prep.stacked)
    return PendingDispatch("stacked", raw, prep, prep.key, t0)


def collect_stacked(pending: PendingDispatch):
    """Force completion of a :func:`launch_stacked` dispatch and run
    the shared decode/accounting tail.  Returns the same
    ``(values, cycles, batch_result)`` triple as :func:`run_stacked`;
    ``run_time_s`` is the honest launch-to-completion device wall."""
    prep: _StackedPrep = pending.prep
    span = (tracer.span("engine_segment", "engine",
                        batch_size=len(prep.graphs),
                        n_real=prep.n_real, from_cycle=0,
                        extra_cycles=prep.max_cycles, pipelined=True)
            if tracer.active else None)
    with (span if span is not None else contextlib.nullcontext()):
        (values, cycles, stable), run_s = finish_jit_call(
            pending.key, pending.raw, pending.t_launch)
    elapsed = time.perf_counter() - pending.t_launch
    return _finish_stacked(prep, values, cycles, stable, elapsed,
                           0.0, run_s, pending.t_launch,
                           pipelined=True)


def run_stacked(
    graphs: Sequence[CompiledFactorGraph],
    max_cycles: int = 200,
    damping: float = 0.5,
    damping_nodes: str = "both",
    stability: float = 0.1,
    pad_to_bins: Optional[Sequence[int]] = None,
    prune: bool = False,
    envelope=None,
) -> Tuple[np.ndarray, np.ndarray, DeviceRunResult]:
    """One device dispatch over a stack of same-shaped compiled graphs.

    The serving hot path: all instances run ``max_cycles`` cycles (no
    convergence stop — a data-dependent loop bound would serialize the
    batch; converged instances freeze via send suppression, so extra
    cycles don't change their assignment).  With ``pad_to_bins`` the
    stack is padded up the bin ladder first (see module docstring).

    Returns ``(values, cycles, batch_result)``: per-instance selected
    value indices / cycle counts for the first ``n_real`` lanes
    (padding lanes already dropped), plus a batch-level
    :class:`DeviceRunResult` whose ``metrics`` carry the dispatch
    accounting — ``batch_size``, ``n_real``, ``pad_fraction``,
    ``cold_start`` — and whose ``assignment`` is empty (a batch has no
    single assignment; decode per instance via each meta).

    ``envelope`` (a serving/binning.Envelope) lifts the same-shape
    contract: every graph is mask-padded to the envelope's shapes
    first (:func:`stack_to_envelope`), so *different*-structure
    problems share the dispatch with bit-identical per-instance
    results; the metrics then additionally carry ``envelope_waste``
    (mean padded-cell fraction over real lanes) and
    ``envelope_waste_lanes`` (per lane, dispatch order).
    """
    with _assembly_span(
            len(graphs),
            "structure" if envelope is None else "envelope"):
        prep = _prepare_stacked(graphs, max_cycles, damping,
                                damping_nodes, stability, pad_to_bins,
                                prune, envelope)
    t0 = time.perf_counter()
    # A batched dispatch IS one engine segment (the whole solve in
    # one program): the span name matches the segmented loop's so
    # request-scoped trace queries see a uniform engine layer —
    # under a serve dispatch the thread-bound trace context stamps
    # the batch's trace_ids onto it.
    span = (tracer.span("engine_segment", "engine",
                        batch_size=len(prep.graphs),
                        n_real=prep.n_real,
                        from_cycle=0, extra_cycles=max_cycles)
            if tracer.active else None)
    with (span if span is not None else contextlib.nullcontext()):
        (values, cycles, stable), compile_s, run_s = timed_jit_call(
            _warm, prep.key,
            functools.partial(_batched_maxsum_solve, **prep.statics),
            prep.stacked,
        )
    elapsed = time.perf_counter() - t0
    return _finish_stacked(prep, values, cycles, stable, elapsed,
                           compile_s, run_s, t0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_cycles", "damping", "damp_vars", "damp_factors",
        "stability",
    ),
)
def _lane_packed_maxsum_solve(lane, *, max_cycles, damping, damp_vars,
                       damp_factors, stability):
    """One jitted lane-major solve of a packed union (see
    ``run_lane_packed``); the suppression counters ride out so the
    host can recover per-member convergence verdicts."""
    from pydcop_tpu.ops import maxsum_lane as lane_ops

    state, values = lane_ops.run_maxsum(
        lane, max_cycles,
        damping=damping, damp_vars=damp_vars,
        damp_factors=damp_factors, stability=stability,
        stop_on_convergence=False,
    )
    return values, state.cycle, state.v2f_count, state.f2v_count


def run_lane_packed(
    graphs: Sequence[CompiledFactorGraph],
    max_cycles: int = 200,
    damping: float = 0.5,
    damping_nodes: str = "both",
    stability: float = 0.1,
    d_env: Optional[int] = None,
    ladder=None,
) -> Tuple[List[np.ndarray], np.ndarray, DeviceRunResult]:
    """One device dispatch over a lane-packed DISJOINT UNION of
    different-structure graphs (ops/maxsum_lane.pack_graphs): members
    concatenate along the variable axis and each arity's factor/lane
    axis instead of padding to a common hypercube, so heterogeneous
    ``v_count``/factor counts carry no mask waste at all — only the
    shared domain rung ``d_env`` (default: the group's max) is padded.
    The tiny-domain route of the serving envelope tier
    (docs/serving.md "Envelope batching").

    ``ladder`` (a serving/binning.EnvelopeLadder) additionally rounds
    the union's variable/row counts up the ladder with masked sentinel
    rows, bounding the number of compiled union programs under
    changing group compositions.

    Returns ``(values, cycles, batch_result)`` like ``run_stacked``,
    with ``values`` a per-member list (members have different variable
    counts).  ``converged_lanes`` holds honest per-member verdicts
    recovered from the suppression counters
    (ops/maxsum_lane.converged_per_graph)."""
    with _assembly_span(len(graphs), "lane"):
        prep = _prepare_lane(graphs, max_cycles, damping,
                             damping_nodes, stability, d_env, ladder)
    t0 = time.perf_counter()
    span = (tracer.span("engine_segment", "engine",
                        batch_size=len(graphs), n_real=len(graphs),
                        packing="lane", from_cycle=0,
                        extra_cycles=max_cycles)
            if tracer.active else None)
    with (span if span is not None else contextlib.nullcontext()):
        (values, cycle, v2f_count, f2v_count), compile_s, run_s = \
            timed_jit_call(
                _warm, prep.key,
                functools.partial(_lane_packed_maxsum_solve, **prep.statics),
                prep.lane,
            )
    elapsed = time.perf_counter() - t0
    return _finish_lane(prep, values, cycle, v2f_count, f2v_count,
                        elapsed, compile_s, run_s, t0)


class _LanePrep(NamedTuple):
    """Host-side assembly of one lane-packed dispatch (see
    :class:`_StackedPrep`)."""

    graphs: tuple
    union: CompiledFactorGraph
    layout: Any
    lane: Any
    statics: dict
    key: tuple
    max_cycles: int
    t_pack: float


def _prepare_lane(graphs, max_cycles, damping, damping_nodes,
                  stability, d_env, ladder) -> _LanePrep:
    from pydcop_tpu.ops import maxsum_lane as lane_ops

    if not graphs:
        raise ValueError("run_lane_packed needs at least one graph")
    t_pack = time.perf_counter()
    union, layout = lane_ops.pack_graphs(graphs, d_env=d_env)
    if ladder is not None:
        from pydcop_tpu.serving.binning import envelope_key

        # Ladder-round the union's variable/row counts so group
        # compositions reuse compiled programs — but KEEP the exact
        # domain: the caller grouped by domain rung already, and
        # rounding d again would charge every member the rung's
        # hypercube blowup the lane pack exists to avoid.
        union = pad_graph_to_envelope(
            union,
            envelope_key(union, ladder)._replace(d_env=union.dmax))
    lane = lane_ops.to_lane_graph(union)
    statics = dict(
        max_cycles=max_cycles,
        damping=damping,
        damp_vars=damping_nodes in ("vars", "both"),
        damp_factors=damping_nodes in ("factors", "both"),
        stability=stability,
    )
    key = (
        "maxsum_lane_pack",
        (lane.var_costs.shape,)
        + tuple(b.costs.shape for b in lane.buckets),
        tuple(sorted(statics.items())),
    )
    return _LanePrep(tuple(graphs), union, layout, lane, statics,
                     key, max_cycles, t_pack)


def _finish_lane(prep: _LanePrep, values, cycle, v2f_count,
                 f2v_count, elapsed: float, compile_s: float,
                 run_s: float, t0: float, pipelined: bool = False):
    from pydcop_tpu.ops import maxsum_lane as lane_ops

    graphs = prep.graphs
    # ONE coalesced device_get for the whole output pytree (the old
    # path paid 4 separate host syncs per dispatch).
    values, cycle, v2f_count, f2v_count = jax.device_get(
        (values, cycle, v2f_count, f2v_count))
    values = np.asarray(values)
    per_values = [values[s:s + n] for s, n in prep.layout.var_slices]
    converged = lane_ops.converged_per_graph(
        v2f_count, f2v_count, prep.layout)
    n_cycles = int(cycle)
    cycles = np.full((len(graphs),), n_cycles, dtype=np.int32)
    # Honest waste accounting: members carry only domain-rung padding;
    # the union-level ladder rounding (sentinel rows) is shared
    # dispatch overhead, reported in the dispatch-level figure.
    from pydcop_tpu.serving.binning import lane_cells

    real_cells = [_array_cells(g) for g in graphs]
    union_cells = max(_array_cells(prep.union), 1)
    member_cells = [lane_cells(g, prep.lane.dmax) for g in graphs]
    lane_waste = [
        round(1.0 - r / max(m, 1), 4)
        for r, m in zip(real_cells, member_cells)
    ]
    batch_result = DeviceRunResult(
        assignment={},
        cycles=n_cycles,
        converged=all(converged),
        time_s=elapsed,
        compile_time_s=compile_s,
        metrics={
            "batch_size": len(graphs),
            "n_real": len(graphs),
            "pad_fraction": 0.0,
            "cold_start": compile_s > 0.0,
            "run_time_s": run_s,
            "pack_host_s": t0 - prep.t_pack,
            "packing": "lane",
            "converged_lanes": [bool(c) for c in converged],
            "envelope_waste_lanes": lane_waste,
            "envelope_waste": round(
                1.0 - sum(real_cells) / union_cells, 4),
            "cells_total": union_cells,
            "program_key": str(prep.key),
        },
    )
    if pipelined:
        batch_result.metrics["pipelined"] = True
    if efficiency.tracker.enabled:
        record = efficiency.tracker.record_dispatch(
            key=str(prep.key), structure=_structure_label(prep.union),
            backend=efficiency.backend_name(),
            time_s=run_s, compile_s=compile_s, cycles=prep.max_cycles,
            n_real=len(graphs), batch_size=len(graphs),
            pad_fraction=0.0,
            envelope_waste=batch_result.metrics["envelope_waste"],
            packing="lane",
            cost_entry=(profiler.get(prep.key)
                        if profiler.enabled else None),
        )
        if record is not None:
            batch_result.metrics["efficiency"] = record
    return per_values, cycles, batch_result


def launch_lane_packed(
    graphs: Sequence[CompiledFactorGraph],
    max_cycles: int = 200,
    damping: float = 0.5,
    damping_nodes: str = "both",
    stability: float = 0.1,
    d_env: Optional[int] = None,
    ladder=None,
) -> Optional[PendingDispatch]:
    """Async-launch a lane-packed dispatch (see
    :func:`launch_stacked`); ``None`` when the union program is cold —
    compile stays on the synchronous path."""
    prep = _prepare_lane(graphs, max_cycles, damping, damping_nodes,
                         stability, d_env, ladder)
    if prep.key not in _warm:
        return None
    t0 = time.perf_counter()
    raw = launch_jit_call(
        _warm, prep.key,
        functools.partial(_lane_packed_maxsum_solve, **prep.statics),
        prep.lane)
    return PendingDispatch("lane", raw, prep, prep.key, t0)


def collect_lane_packed(pending: PendingDispatch):
    """Force completion of a :func:`launch_lane_packed` dispatch and
    run the shared decode/accounting tail."""
    prep: _LanePrep = pending.prep
    span = (tracer.span("engine_segment", "engine",
                        batch_size=len(prep.graphs),
                        n_real=len(prep.graphs), packing="lane",
                        from_cycle=0, extra_cycles=prep.max_cycles,
                        pipelined=True)
            if tracer.active else None)
    with (span if span is not None else contextlib.nullcontext()):
        (values, cycle, v2f_count, f2v_count), run_s = \
            finish_jit_call(pending.key, pending.raw,
                            pending.t_launch)
    elapsed = time.perf_counter() - pending.t_launch
    return _finish_lane(prep, values, cycle, v2f_count, f2v_count,
                        elapsed, 0.0, run_s, pending.t_launch,
                        pipelined=True)


def solve_maxsum_batch(
    dcops: Sequence[DCOP],
    max_cycles: int = 200,
    noise_level: float = 0.01,
    damping: float = 0.5,
    damping_nodes: str = "both",
    stability: float = 0.1,
    pad_to_bins: Optional[Sequence[int]] = None,
) -> List[Dict]:
    """Solve a batch of same-shaped DCOPs in one vmapped program.

    Returns one dict per instance: assignment, cost (host-evaluated),
    cycles.  All instances run ``max_cycles`` cycles (no convergence
    stop: a data-dependent loop bound would serialize the batch).
    ``pad_to_bins`` pads the stack up a bin-size ladder so a sweep of
    ragged batch sizes reuses a bounded set of compiled programs; the
    shared dispatch accounting (incl. ``pad_fraction``) rides along in
    each result's ``batch`` key.
    """
    if not dcops:
        return []
    # Same-structured instances (same graph, different cost tables —
    # the repeated-traffic serving pattern) are exactly what the
    # structure-keyed compile cache serves: instance 1 builds the
    # layout/agg arrays, instances 2..N reuse them
    # (engine/compile.CompileCache), matching the device side where
    # vmap already made N solves cost barely more than one.
    compiled: List[Tuple[CompiledFactorGraph, FactorGraphMeta]] = [
        compile_dcop(d, noise_level=noise_level) for d in dcops
    ]
    graphs = [c[0] for c in compiled]
    metas = [c[1] for c in compiled]

    values, cycles, batch_result = run_stacked(
        graphs,
        max_cycles=max_cycles,
        damping=damping,
        damping_nodes=damping_nodes,
        stability=stability,
        pad_to_bins=pad_to_bins,
    )

    results = []
    for i, (dcop, meta) in enumerate(zip(dcops, metas)):
        assignment = meta.assignment_from_indices(values[i])
        cost, violations = dcop.solution_cost(assignment)
        results.append({
            "assignment": assignment,
            "cost": cost,
            "violations": violations,
            "cycles": int(cycles[i]),
            "batch": dict(batch_result.metrics),
        })
    return results
