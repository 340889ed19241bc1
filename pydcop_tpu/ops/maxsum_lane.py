"""Lane-major MaxSum superstep: factors on the TPU lane axis.

The edge-major kernels (ops/maxsum.py) keep messages as ``[F, arity, D]``
— domain values on the minor axis.  DCOP domains are tiny (D=3..8) so
that layout leaves 120+ of the 128 TPU lanes idle in every vector op,
and past the size that fits fast memory (~100k vars) the
scatter/gather traffic is issued in D-element slivers.  This module
is the full-superstep version of the transposed layout.  It is what a
plain single-device solve runs (``algorithms/maxsum.select_layout``;
the ``layout`` algo param names either layout outright).  On the chip
(TPU v5e, 10k variables / 15k binary factors, PERF.md section 6
"PR 36") a superstep takes 403 us against the edge-major 1 203: the
edge-major ``[edges, 3]`` buffers are padded 43x to the 128-lane
minor axis and relaid out by three copies a superstep; here the
factor and variable updates are 2 + 6 us and what is left is the
scatter-add (224 us) and the gathers of ``var_to_factor`` (169 us).

Layout (one bucket of arity ``a``, F factors, padded domain D):

- messages  ``[D, a, F]``  — F minor: every elementwise op fills lanes;
- costs     ``[D, ..., D, F]`` (``a`` domain axes, then F);
- var_ids   ``[a, F]`` (transposed bucket scope);
- var costs/valid/beliefs/sums ``[D, V+1]`` — variables on lanes.

The flatten feeding variable aggregation is ``[D, a, F] -> [D, a*F]``,
a contiguous reshape (position-major edge order), so the superstep
contains NO transposes: the layout choice is made once at compile time
(``to_lane_graph``) and everything stays lane-major.

Aggregation is a scatter-add along the minor axis
(``sums.at[:, seg].add(flat)``) — the lane-major analogue of the
edge-major ``segment_sum``.  Scatter order matches edge order, and all
other ops are elementwise or tiny-D reductions in identical order, so
trajectories are BIT-IDENTICAL to edge-major per element (asserted by
tests/unit/test_maxsum_lane.py) *except* where a variable's incoming
edges arrive in a different order across layouts: edge-major flattens
(factor, position), lane-major (position, factor).  For single-bucket
binary graphs built by generators the per-variable contribution sets
are identical, so sums differ only by float reassociation; the parity
tests therefore assert exact assignment equality plus message
agreement to float tolerance, and bit-equality where the instance has
at most one bucket position per variable.

Semantics are the reference's exactly, same as ops/maxsum.py (factor
update pydcop/algorithms/maxsum.py:382, variable update :623 with
mean-normalization :670-674, damping :679, approx_match :688,
SAME_COUNT suppression :106).
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pydcop_tpu.engine.compile import (
    BIG,
    CompiledFactorGraph,
    FactorBucket,
)
from pydcop_tpu.ops.maxsum import SAME_COUNT

Msgs = Tuple[jnp.ndarray, ...]  # one [D, arity, F] array per bucket


class LaneBucket(NamedTuple):
    """All factors of one arity, lane-major."""

    costs: jnp.ndarray    # [D]*arity + [F]
    var_ids: jnp.ndarray  # [arity, F] int32 (sentinel V on padding)

    @property
    def arity(self) -> int:
        return self.var_ids.shape[0]

    @property
    def n_factors(self) -> int:
        return self.var_ids.shape[1]


class LaneGraph(NamedTuple):
    """Lane-major twin of CompiledFactorGraph (scatter aggregation
    only — the sort-based strategies are edge-major concepts)."""

    var_costs: jnp.ndarray   # [Dmax, V+1]
    var_valid: jnp.ndarray   # [Dmax, V+1]
    buckets: Tuple[LaneBucket, ...]

    @property
    def n_vars(self) -> int:
        return self.var_costs.shape[1] - 1

    @property
    def dmax(self) -> int:
        return self.var_costs.shape[0]


class PackLayout(NamedTuple):
    """Where each member of a lane-packed union landed (ISSUE 11).

    Lane packing turns N *different*-structure problems into ONE
    disjoint-union factor graph: variables concatenate (one shared
    sentinel at the end), and each arity's factors concatenate on the
    lane (F) axis.  Because the union is a disjoint union, message
    passing decomposes exactly — no member's messages can reach
    another member's variables — so per-member results equal solo
    solves while the device sees one dense dispatch with NO
    per-member shape padding (the only mask waste is the shared
    domain rung)."""

    # Per member: (start, n_vars) into the union's variable rows.
    var_slices: Tuple[Tuple[int, int], ...]
    # Per member: ((bucket_index, start, n_rows), ...) into the
    # union's buckets — only arities the member actually has.
    row_slices: Tuple[Tuple[Tuple[int, int, int], ...], ...]
    # Union bucket arity order (sorted ascending).
    arities: Tuple[int, ...]


def pack_graphs(graphs, d_env: Optional[int] = None
                ) -> Tuple[CompiledFactorGraph, PackLayout]:
    """Disjoint-union pack: concatenate compiled graphs into one
    edge-major CompiledFactorGraph (host numpy), domains mask-padded
    to the shared ``d_env`` (default: the group's max) with the
    compiler's own discipline (``BIG`` cost, ``var_valid=False``).

    Members may have entirely different variable counts, factor
    counts and arity sets.  Each member's rows keep their relative
    order inside the union buckets, so the per-variable scatter-add
    accumulates a member's contributions in the same order a solo
    dispatch would — the parity the envelope battery asserts.

    The union keeps a single sentinel row (index ``sum(v_i)``);
    members' own compile-time sentinel references are re-pointed at
    it.  Aggregation arrays are dropped (scatter path).  Use
    :func:`to_lane_graph` on the result to run lane-major, and
    :func:`converged_per_graph` to recover per-member convergence.
    """
    if not graphs:
        raise ValueError("pack_graphs needs at least one graph")
    if d_env is None:
        d_env = max(g.dmax for g in graphs)
    if d_env < max(g.dmax for g in graphs):
        raise ValueError(
            f"d_env={d_env} below the group's max domain "
            f"{max(g.dmax for g in graphs)}")
    v_total = sum(g.n_vars for g in graphs)
    dtype = graphs[0].var_costs.dtype
    var_costs = np.full((v_total + 1, d_env), BIG, dtype=dtype)
    var_valid = np.zeros((v_total + 1, d_env), dtype=bool)
    var_slices = []
    offset = 0
    for g in graphs:
        v, d = g.n_vars, g.dmax
        var_costs[offset:offset + v, :d] = np.asarray(g.var_costs)[:v]
        var_valid[offset:offset + v, :d] = np.asarray(g.var_valid)[:v]
        var_slices.append((offset, v))
        offset += v

    arities = sorted({b.arity for g in graphs for b in g.buckets})
    bucket_index = {a: i for i, a in enumerate(arities)}
    costs_parts = {a: [] for a in arities}
    ids_parts = {a: [] for a in arities}
    row_cursor = {a: 0 for a in arities}
    row_slices = []
    for g, (start, _v) in zip(graphs, var_slices):
        v = g.n_vars
        member_rows = []
        for b in g.buckets:
            a, n_rows, d = b.arity, b.n_factors, g.dmax
            block = np.full((n_rows,) + (d_env,) * a, BIG,
                            dtype=b.costs.dtype)
            block[(slice(None),) + (slice(0, d),) * a] = \
                np.asarray(b.costs)
            ids = np.asarray(b.var_ids).astype(np.int32).copy()
            # Member-local indices -> union indices; the member's own
            # sentinel (v) re-points at the union sentinel (v_total).
            sent = ids == v
            ids = ids + start
            ids[sent] = v_total
            costs_parts[a].append(block)
            ids_parts[a].append(ids)
            member_rows.append(
                (bucket_index[a], row_cursor[a], n_rows))
            row_cursor[a] += n_rows
        row_slices.append(tuple(member_rows))

    buckets = tuple(
        FactorBucket(
            costs=np.concatenate(costs_parts[a], axis=0),
            var_ids=np.concatenate(ids_parts[a], axis=0),
        )
        for a in arities
    )
    union = CompiledFactorGraph(
        var_costs=var_costs, var_valid=var_valid, buckets=buckets,
    )
    layout = PackLayout(
        var_slices=tuple(var_slices),
        row_slices=tuple(row_slices),
        arities=tuple(arities),
    )
    return union, layout


def converged_per_graph(v2f_count, f2v_count,
                        layout: PackLayout) -> Tuple[bool, ...]:
    """Per-member convergence verdicts from a packed run's final
    send-suppression counters.  An edge's count is reset to 1 on a
    mismatched send and incremented on a match, so ``count >= 2`` on
    every edge of a member (both directions) is exactly that member's
    slice of the global ``stable`` conjunction — the packed dispatch
    reports honest per-request ``converged`` flags even though the
    union carries one shared flag.  Counter arrays are the lane-major
    ``[arity, F]`` per-bucket LaneState counters (the F axis is
    sliced)."""
    verdicts = []
    for member_rows in layout.row_slices:
        ok = True
        for bi, start, n_rows in member_rows:
            for counts in (v2f_count[bi], f2v_count[bi]):
                rows = np.asarray(counts)[:, start:start + n_rows]
                ok = ok and bool((rows >= 2).all())
        verdicts.append(ok)
    return tuple(verdicts)


def to_lane_graph(graph: CompiledFactorGraph) -> LaneGraph:
    """One-time compile-side relayout (host numpy; the superstep never
    transposes)."""
    return LaneGraph(
        var_costs=np.ascontiguousarray(np.asarray(graph.var_costs).T),
        var_valid=np.ascontiguousarray(np.asarray(graph.var_valid).T),
        buckets=tuple(
            LaneBucket(
                costs=np.ascontiguousarray(
                    np.moveaxis(np.asarray(b.costs), 0, -1)),
                var_ids=np.ascontiguousarray(np.asarray(b.var_ids).T),
            )
            for b in graph.buckets
        ),
    )


class LaneState(NamedTuple):
    v2f: Msgs            # last SENT variable -> factor messages
    f2v: Msgs            # last SENT factor -> variable messages
    v2f_count: Msgs      # [arity, F] int8 consecutive-same counts
    f2v_count: Msgs
    stable: jnp.ndarray  # scalar bool
    cycle: jnp.ndarray   # scalar int32


def init_state(graph: LaneGraph) -> LaneState:
    d = graph.var_costs.shape[0]
    dtype = graph.var_costs.dtype

    # Independent arrays per field (no tuple reuse): the segment jits
    # donate the state pytree (engine/runner.py), and donation rejects
    # the same buffer appearing in two donated slots.
    def zeros():
        return tuple(
            jnp.zeros((d,) + b.var_ids.shape, dtype=dtype)
            for b in graph.buckets
        )

    def counts():
        return tuple(
            jnp.zeros(b.var_ids.shape, dtype=jnp.int8)
            for b in graph.buckets
        )

    return LaneState(
        v2f=zeros(), f2v=zeros(),
        v2f_count=counts(), f2v_count=counts(),
        stable=jnp.asarray(False),
        cycle=jnp.asarray(0, dtype=jnp.int32),
    )


def _edge_match(new, old, stability, valid):
    """Per-edge approx_match over the domain axis (axis 0 here);
    algebra identical to ops/maxsum._edge_match.  Returns [a, F]."""
    delta = jnp.abs(new - old)
    s = jnp.abs(new + old)
    ok = (2 * delta < stability * s) | (delta == 0)
    return jnp.all(ok | ~valid, axis=0)


def _send_or_suppress(cand, prev, count, stability, valid, first):
    """SAME_COUNT send-suppression, lane-major (match flags are
    [a, F]; the broadcast goes on the leading domain axis)."""
    match = _edge_match(cand, prev, stability, valid) & ~first
    send = ~match | (count < SAME_COUNT)
    sent = jnp.where(send[None], cand, prev)
    new_count = jnp.where(
        match, jnp.minimum(count + 1, SAME_COUNT + 1), 1
    )
    return sent, new_count, match


def factor_to_var(graph: LaneGraph, v2f: Msgs) -> Msgs:
    """All factor→variable messages, one batched min-reduction per
    bucket over the leading domain axes (F rides along on lanes)."""
    out = []
    for bucket, msgs in zip(graph.buckets, v2f):
        d, arity, f = msgs.shape
        total = bucket.costs                     # [D, ..., D, F]
        for q in range(arity):
            shape = [1] * arity + [f]
            shape[q] = d
            total = total + msgs[:, q].reshape(shape)
        outs_p = []
        for p in range(arity):
            axes = tuple(i for i in range(arity) if i != p)
            reduced = jnp.min(total, axis=axes) if axes else total
            outs_p.append(reduced - msgs[:, p])
        out.append(jnp.stack(outs_p, axis=1))    # [D, a, F]
    return tuple(out)


def aggregate_beliefs(graph: LaneGraph, f2v: Msgs
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sum incoming factor messages per variable: scatter-add along
    the minor (variable) axis.  The feeding reshape is contiguous —
    this is the op the lane layout exists for."""
    sums = jnp.zeros_like(graph.var_costs)       # [D, V+1]
    for bucket, msgs in zip(graph.buckets, f2v):
        d = msgs.shape[0]
        flat = msgs.reshape(d, -1)               # [D, a*F]
        seg = bucket.var_ids.reshape(-1)         # [a*F]
        sums = sums.at[:, seg].add(flat)
    return graph.var_costs + sums, sums


def var_to_factor(graph: LaneGraph, f2v: Msgs, beliefs, sums) -> Msgs:
    """Belief minus own contribution, mean-normalized over valid
    domain slots (domain axis = axis 0)."""
    out = []
    for bucket, msgs in zip(graph.buckets, f2v):
        valid = graph.var_valid[:, bucket.var_ids]   # [D, a, F]
        raw = beliefs[:, bucket.var_ids] - msgs
        factor_sum = sums[:, bucket.var_ids] - msgs
        n_valid = jnp.maximum(
            jnp.sum(valid, axis=0, keepdims=True), 1
        )
        avg = (
            jnp.sum(jnp.where(valid, factor_sum, 0.0), axis=0,
                    keepdims=True)
            / n_valid
        )
        out.append(jnp.where(valid, raw - avg,
                             jnp.asarray(BIG, raw.dtype)))
    return tuple(out)


def select_values(graph: LaneGraph, beliefs: jnp.ndarray) -> jnp.ndarray:
    """Per-variable argmin of belief over valid slots ([V] int32)."""
    masked = jnp.where(graph.var_valid, beliefs, jnp.inf)
    return jnp.argmin(masked[:, :-1], axis=0).astype(jnp.int32)


def _damp(new: Msgs, old: Msgs, damping: float, first) -> Msgs:
    return tuple(
        jnp.where(first, n, damping * o + (1.0 - damping) * n)
        for n, o in zip(new, old)
    )


def superstep(state: LaneState, graph: LaneGraph, *, damping: float,
              damp_vars: bool, damp_factors: bool,
              stability: float) -> LaneState:
    """One synchronous cycle, same Jacobi semantics as
    ops/maxsum.superstep (both sides fire from last cycle's mail)."""
    first = state.cycle == 0
    with jax.named_scope("maxsum/update"):
        valids = tuple(
            graph.var_valid[:, b.var_ids] for b in graph.buckets
        )

    # Phase names as in ops/maxsum.superstep.
    with jax.named_scope("maxsum/f2v"):
        f2v_cand = factor_to_var(graph, state.v2f)
    if damp_factors and damping > 0:
        with jax.named_scope("maxsum/update"):
            f2v_cand = _damp(f2v_cand, state.f2v, damping, first)

    with jax.named_scope("maxsum/aggregate"):
        beliefs, sums = aggregate_beliefs(graph, state.f2v)
    with jax.named_scope("maxsum/v2f"):
        v2f_cand = var_to_factor(graph, state.f2v, beliefs, sums)
    if damp_vars and damping > 0:
        with jax.named_scope("maxsum/update"):
            v2f_cand = _damp(v2f_cand, state.v2f, damping, first)

    f2v_new, f2v_count = [], []
    v2f_new, v2f_count = [], []
    all_match = jnp.asarray(True)
    with jax.named_scope("maxsum/update"):
        for i, valid in enumerate(valids):
            sent, cnt, match = _send_or_suppress(
                f2v_cand[i], state.f2v[i], state.f2v_count[i],
                stability, valid, first)
            f2v_new.append(sent)
            f2v_count.append(cnt)
            all_match = all_match & jnp.all(
                match | ~jnp.any(valid, 0))
            sent, cnt, match = _send_or_suppress(
                v2f_cand[i], state.v2f[i], state.v2f_count[i],
                stability, valid, first)
            v2f_new.append(sent)
            v2f_count.append(cnt)
            all_match = all_match & jnp.all(
                match | ~jnp.any(valid, 0))

    return LaneState(
        v2f=tuple(v2f_new),
        f2v=tuple(f2v_new),
        v2f_count=tuple(v2f_count),
        f2v_count=tuple(f2v_count),
        stable=all_match & ~first,
        cycle=state.cycle + 1,
    )


def assignment_constraint_cost(graph: LaneGraph,
                               values: jnp.ndarray) -> jnp.ndarray:
    """Total factor-table cost of an assignment ([V] value indices);
    padding rows contribute 0 (see ops/maxsum counterpart)."""
    vals = jnp.concatenate(
        [values, jnp.zeros((1,), dtype=values.dtype)]
    )
    total = jnp.asarray(0.0, dtype=graph.var_costs.dtype)
    for bucket in graph.buckets:
        arity, f = bucket.var_ids.shape
        d = graph.var_costs.shape[0]
        idx = vals[bucket.var_ids]               # [arity, F]
        flat = jnp.zeros((f,), dtype=jnp.int32)
        for p in range(arity):
            flat = flat * d + idx[p]
        table = bucket.costs.reshape(-1, f)      # [D^arity, F]
        total = total + jnp.sum(
            jnp.take_along_axis(table, flat[None, :], axis=0)
        )
    return total


def _reject_prune(prune: bool):
    """Branch-and-bound pruning is an edge-major kernel (it gathers
    reduction rows of the [F, D, D] hypercubes); the lane layout's
    transposed messages would need their own compaction.  The engine
    refuses layout='lane' + prune at construction — this guard keeps
    the ops-level contract explicit for direct callers."""
    if prune:
        raise NotImplementedError(
            "prune=True is edge-major only; run with layout='edge'")


def run_maxsum(graph: LaneGraph, max_cycles: int, *,
               damping: float = 0.5, damp_vars: bool = True,
               damp_factors: bool = True, stability: float = 0.1,
               stop_on_convergence: bool = True,
               prune: bool = False,
               ) -> Tuple[LaneState, jnp.ndarray]:
    """Full lane-major MaxSum run in one XLA program."""
    return run_maxsum_from(
        graph, init_state(graph), max_cycles,
        damping=damping, damp_vars=damp_vars,
        damp_factors=damp_factors, stability=stability,
        stop_on_convergence=stop_on_convergence, prune=prune,
    )


def run_maxsum_from(graph: LaneGraph, state: LaneState,
                    extra_cycles: int, *,
                    damping: float = 0.5, damp_vars: bool = True,
                    damp_factors: bool = True, stability: float = 0.1,
                    stop_on_convergence: bool = True,
                    prune: bool = False,
                    ) -> Tuple[LaneState, jnp.ndarray]:
    _reject_prune(prune)

    def step(state):
        return superstep(
            state, graph, damping=damping, damp_vars=damp_vars,
            damp_factors=damp_factors, stability=stability,
        )

    limit = state.cycle + extra_cycles
    if stop_on_convergence:
        state = jax.lax.while_loop(
            lambda s: (s.cycle < limit) & ~s.stable, step, state,
        )
    else:
        state = jax.lax.while_loop(
            lambda s: s.cycle < limit, step, state,
        )
    with jax.named_scope("maxsum/select"):
        beliefs, _ = aggregate_beliefs(graph, state.f2v)
        values = select_values(graph, beliefs)
    return state, values


def run_maxsum_trace(graph: LaneGraph, max_cycles: int, *,
                     damping: float = 0.5, damp_vars: bool = True,
                     damp_factors: bool = True, stability: float = 0.1,
                     var_base_costs: Optional[jnp.ndarray] = None,
                     stop_on_convergence: bool = True,
                     prune: bool = False,
                     ) -> Tuple[LaneState, jnp.ndarray, jnp.ndarray]:
    """Lane-major twin of ops/maxsum.run_maxsum_trace (same while_loop
    + carried-cost-buffer structure, same early exit at the fixpoint
    with the tail of the curve holding the final cost).
    ``var_base_costs`` is [V, Dmax] edge-major (FactorGraphMeta
    convention) — transposed once here, not per cycle."""
    _reject_prune(prune)
    base_t = None if var_base_costs is None else var_base_costs.T

    def cost_of(values):
        cost = assignment_constraint_cost(graph, values)
        if base_t is not None:
            cost = cost + jnp.sum(jnp.take_along_axis(
                base_t, values[None, :], axis=0))
        return cost

    def step(carry):
        state, costs, last = carry
        state = superstep(
            state, graph, damping=damping, damp_vars=damp_vars,
            damp_factors=damp_factors, stability=stability,
        )
        with jax.named_scope("maxsum/select"):
            with jax.named_scope("maxsum/select"):
                beliefs, _ = aggregate_beliefs(graph, state.f2v)
                values = select_values(graph, beliefs)
                cost = cost_of(values)
        costs = jax.lax.dynamic_update_slice(
            costs, cost[None], (state.cycle - 1,))
        return state, costs, cost

    def done(carry):
        state = carry[0]
        out = state.cycle >= max_cycles
        if stop_on_convergence:
            out = out | state.stable
        return out

    zero = jnp.asarray(0.0, graph.var_costs.dtype)
    state, costs, last = jax.lax.while_loop(
        lambda c: ~done(c), step,
        (init_state(graph),
         jnp.zeros((max_cycles,), graph.var_costs.dtype), zero),
    )
    costs = jnp.where(
        jnp.arange(max_cycles) >= state.cycle, last, costs)
    with jax.named_scope("maxsum/select"):
        beliefs, _ = aggregate_beliefs(graph, state.f2v)
        values = select_values(graph, beliefs)
    return state, values, costs
