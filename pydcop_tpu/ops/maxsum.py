"""MaxSum message-update kernels: one BSP superstep as pure JAX.

Semantics mirror the reference algorithm exactly (factor update:
pydcop/algorithms/maxsum.py:382 factor_costs_for_var; variable update:
:623 costs_for_factor with mean-normalization :670-674; damping :679;
convergence test :688 approx_match), but batched:

- factor→variable: per arity-bucket, ``total = costs + Σ_q bcast(m_q)``
  then for each position p ``min`` over all axes except p minus ``m_p``
  (m_p is constant along the reduced axes, so subtracting it after the
  reduction equals excluding it before) — one batched reduction instead
  of a python loop over d^arity assignments;
- variable→factor: segment-sum of incoming messages over the bucket var
  indices, per-slot "subtract own contribution", mean-normalized over
  valid domain slots, damped;
- value selection: argmin of (own costs + message sums) masked to valid
  slots; argmin's lowest-index tie-break reproduces the reference's
  first-optimum ordering (maxsum.py:584 select_value iterates the domain
  in order).

Messages live in bucket space ([F, arity, D] per bucket): factor updates
touch only local rows, and the single segment-sum is the only op that
crosses shards when buckets are sharded over a mesh (one all-reduce of
the [V+1, D] totals per superstep).

All kernels minimize; `objective=max` problems are negated at compile
time (see engine.compile).

Pallas note: a hand-written Pallas kernel for the binary-factor update
(blocking F onto lanes, one fused min-reduce pass) was prototyped and
measured on a v5e chip at parity with XLA's fusion of this code
(~0.26-0.34 ms/superstep on the 15k-factor benchmark, both ways) —
the op mix here is gather/scatter + tiny-minor-dim elementwise, which
Mosaic cannot schedule better than XLA does.  The XLA path is kept;
revisit Pallas if a future problem shape makes the factor update
reduction-bound (large arity/domains) rather than dispatch-bound.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from pydcop_tpu.engine.compile import (
    BIG,
    PRUNE_MIN_DOMAIN,
    CompiledFactorGraph,
    prune_width,
)

Msgs = Tuple[jnp.ndarray, ...]  # one [F, arity, D] array per bucket

# Reference maxsum.py:106 SAME_COUNT: a message that approx-matches the
# previously sent one is re-sent at most this many times, then the edge
# goes quiet (the receiver keeps the last value).
SAME_COUNT = 4


class MaxSumState(NamedTuple):
    v2f: Msgs            # last SENT variable -> factor messages
    f2v: Msgs            # last SENT factor -> variable messages
    v2f_count: Msgs      # [F, arity] int8 consecutive-same send counts
    f2v_count: Msgs
    stable: jnp.ndarray  # scalar bool: all messages approx-matched
    cycle: jnp.ndarray   # scalar int32


def init_state(graph: CompiledFactorGraph) -> MaxSumState:
    d = graph.var_costs.shape[1]
    dtype = graph.var_costs.dtype

    # int8 counts: they saturate at SAME_COUNT + 1 = 5, and the two
    # counter arrays are read+written every cycle — int32 would
    # spend 4x the HBM traffic on values that never exceed 5.
    # Each field gets its OWN arrays (no tuple reuse across v2f/f2v):
    # the segment jits donate the state pytree (engine/runner.py), and
    # donation rejects the same buffer appearing in two donated slots.
    def zeros():
        return tuple(
            jnp.zeros(b.var_ids.shape + (d,), dtype=dtype)
            for b in graph.buckets
        )

    def counts():
        return tuple(
            jnp.zeros(b.var_ids.shape, dtype=jnp.int8)
            for b in graph.buckets
        )

    return MaxSumState(
        v2f=zeros(),
        f2v=zeros(),
        v2f_count=counts(),
        f2v_count=counts(),
        stable=jnp.asarray(False),
        cycle=jnp.asarray(0, dtype=jnp.int32),
    )


def _edge_match(new: jnp.ndarray, old: jnp.ndarray, stability: float,
                valid: jnp.ndarray) -> jnp.ndarray:
    """Per-edge reference approx_match (maxsum.py:688): relative change
    2|Δ|/|a+b| below `stability` on every domain slot (exact equality
    always matches).  Slots outside `valid` (domain padding, sentinel
    padding rows) are ignored so device padding cannot delay
    convergence.  Returns [F, arity] bool."""
    delta = jnp.abs(new - old)
    s = jnp.abs(new + old)
    # Algebraically identical to the reference's three-case test
    # (delta==0 → True; s==0 → False; else 2·delta/s < stability) with
    # two fewer ops per element: when delta>0 and s==0 the strict
    # comparison 0 < 0 is already False, and the delta==0 clause
    # restores the exact-equality case regardless of s.  Bit-identical
    # trajectories verified against the previous form at 10k vars
    # (~7% faster superstep on the CPU backend).
    ok = (2 * delta < stability * s) | (delta == 0)
    return jnp.all(ok | ~valid, axis=-1)


def _send_or_suppress(cand: jnp.ndarray, prev: jnp.ndarray,
                      count: jnp.ndarray, stability: float,
                      valid: jnp.ndarray, first: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reference send-suppression (maxsum.py:366-377 via send_damped):
    a candidate that approx-matches the last sent message is re-sent at
    most SAME_COUNT times, then the edge freezes on the last sent value
    (the thread runtime's receiver keeps its cached copy; here the
    frozen value simply stays in the state array).

    Returns (sent messages, new counts, per-edge match flags).
    """
    match = _edge_match(cand, prev, stability, valid) & ~first
    send = ~match | (count < SAME_COUNT)
    sent = jnp.where(send[..., None], cand, prev)
    new_count = jnp.where(
        match, jnp.minimum(count + 1, SAME_COUNT + 1), 1
    )
    return sent, new_count, match


def _read_pallas_flag() -> bool:
    import os

    return os.environ.get("PYDCOP_PALLAS_MAXSUM") == "1"


# Read ONCE at import (ADVICE r2): the engines' jit caches do not key on
# this flag, so a mid-process toggle would be silently ignored anyway —
# snapshotting it here makes the set-before-import contract explicit.
_PALLAS_FLAG = _read_pallas_flag()


def _use_pallas() -> bool:
    """Opt-in Pallas path for the binary-factor update
    (PYDCOP_PALLAS_MAXSUM=1 must be set before this module is
    imported; default off — see ops/pallas_maxsum.py for the status).
    The kernel is a TPU kernel for whole buckets on one device: asked
    for where it cannot run, it raises — it never gives way silently
    to the jnp expression (engines that cannot feed it refuse the
    flag through :func:`refuse_pallas`)."""
    if not _PALLAS_FLAG:
        return False
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "PYDCOP_PALLAS_MAXSUM=1 asks for the Pallas TPU kernel but "
            f"the backend is {jax.default_backend()!r}; unset it or "
            "run on a TPU")
    return True


def pallas_requested() -> bool:
    """Whether PYDCOP_PALLAS_MAXSUM=1 was set at import: the engine
    wiring keeps such a solve edge-major, the kernel's layout."""
    return _PALLAS_FLAG


def refuse_pallas(where: str) -> None:
    """Called by an engine whose buckets cannot feed the kernel: a
    bucket sharded over a mesh would have to be gathered whole per
    superstep, and the lane-major layout has its own update.  What
    matters is where the bucket lives, not how many chips exist — an
    unsharded solve on a multi-chip host keeps the kernel."""
    if _PALLAS_FLAG:
        raise RuntimeError(
            "PYDCOP_PALLAS_MAXSUM=1 runs whole edge-major buckets on "
            f"one device; unset it for {where}")


class PruneTable(NamedTuple):
    """Per-bucket branch-and-bound tables for the pruned binary-factor
    update (arXiv:1906.06863 applied to the min-plus aggregation).

    ``row_min``/``row_max`` hold, per factor and per slot of one scope
    position, the min/max of the cost hypercube over the *other*
    position's VALID slots — the message-independent halves of the
    per-row lower bound (``m_q[e] + row_min[e]``) and the running
    upper bound (``min_e(m_q[e] + row_max[e])``).  Both are pure
    functions of the cost tables, computed ONCE outside the jitted
    loop (never per superstep).  ``valid`` masks each position's
    domain-padding slots out of the survivor set and the upper bound.
    """

    row_min: Tuple[jnp.ndarray, jnp.ndarray]  # per position p: [F, D]
    row_max: Tuple[jnp.ndarray, jnp.ndarray]
    valid: Tuple[jnp.ndarray, jnp.ndarray]    # [F, D] bool
    costs_t: jnp.ndarray                      # [F, D, D] transposed
    width: int                                # static gather budget


def prune_tables(graph: CompiledFactorGraph
                 ) -> Tuple[Optional[PruneTable], ...]:
    """Branch-and-bound tables, one entry per bucket (None = bucket
    stays on the dense path: non-binary arity, or a domain small
    enough that the bound bookkeeping would cost more than the dense
    reduction).  Call OUTSIDE the superstep loop — the tables are
    loop-invariant."""
    out = []
    d = graph.var_costs.shape[1]
    for bucket in graph.buckets:
        if (bucket.var_ids.shape[1] != 2 or d < PRUNE_MIN_DOMAIN
                or bucket.var_ids.shape[0] == 0):
            out.append(None)
            continue
        valid0 = graph.var_valid[bucket.var_ids[:, 0]]   # [F, D]
        valid1 = graph.var_valid[bucket.var_ids[:, 1]]
        costs = bucket.costs                             # [F, D, D]
        inf = jnp.asarray(jnp.inf, costs.dtype)
        # Extrema over the VALID slots of the other position: BIG
        # domain padding must not loosen row_max into uselessness.
        m0 = valid0[:, :, None]
        m1 = valid1[:, None, :]
        out.append(PruneTable(
            row_min=(
                jnp.min(jnp.where(m1, costs, inf), axis=2),    # p=0
                jnp.min(jnp.where(m0, costs, inf), axis=1),    # p=1
            ),
            row_max=(
                jnp.max(jnp.where(m1, costs, -inf), axis=2),
                jnp.max(jnp.where(m0, costs, -inf), axis=1),
            ),
            valid=(valid0, valid1),
            # Direction p=0 gathers reduction rows indexed by the
            # q=1 slot: the transposed table makes that a CONTIGUOUS
            # row copy instead of a strided column gather (the
            # strided form measured 4x slower on XLA:CPU).  2x table
            # memory, paid only while pruning is on.
            costs_t=jnp.swapaxes(costs, 1, 2),
            width=prune_width(d),
        ))
    return tuple(out)


# Relative slack added to the survivor test: the lower/upper bounds
# and the reduction totals are DIFFERENT float computations of related
# real quantities, each off by a few ulps — an entry whose real margin
# is inside the rounding noise must survive, or the pruned min can
# differ from the dense min in the last bits.  ~200x f32 eps keeps
# every near-boundary entry (measured: zero extra survivors on the
# benchmark families, bit-identical trajectories restored at D=192
# where slack-free pruning drifted).
PRUNE_SLACK = 2.5e-5


def _survivors(msgs: jnp.ndarray, pt: PruneTable, p: int
               ) -> jnp.ndarray:
    """[F, D] bool: reduction rows of direction ``p`` that can still
    attain the min.  Row ``e`` is DOMINATED when its lower bound
    ``m_q[e] + row_min[e]`` exceeds the factor's running upper bound
    ``min_e(m_q[e] + row_max[e])`` by more than the rounding slack:
    every output entry is <= the upper bound, so removing the row is
    exact (ties and near-ties keep it)."""
    mq = msgs[:, 1 - p]
    vq = pt.valid[1 - p]
    inf = jnp.asarray(jnp.inf, mq.dtype)
    lb = mq + pt.row_min[p]
    ub = jnp.min(jnp.where(vq, mq + pt.row_max[p], inf),
                 axis=1, keepdims=True)
    tau = PRUNE_SLACK * (1.0 + jnp.abs(ub))
    return vq & (lb <= ub + tau)


def prune_fits(v2f: Msgs,
               prune: Tuple[Optional[PruneTable], ...]) -> jnp.ndarray:
    """Scalar bool: every prunable bucket's survivor count fits the
    static gather budget in BOTH directions for the messages about to
    be consumed — the phase predicate of the pruned solve loops (see
    run_maxsum_from).  O(E) bound arithmetic, no reduction hypercube
    touched."""
    fits = jnp.asarray(True)
    for msgs, pt in zip(v2f, prune):
        if pt is None:
            continue
        for p in range(2):
            n = jnp.max(jnp.sum(
                _survivors(msgs, pt, p).astype(jnp.int32), axis=1))
            fits = fits & (n <= pt.width)
    return fits


def _pruned_binary_update(bucket, msgs: jnp.ndarray,
                          pt: PruneTable) -> jnp.ndarray:
    """Branch-and-bound f2v update for one binary bucket ([F, 2, D]).

    PRECONDITION: every factor's survivor count fits ``pt.width`` in
    both directions (``prune_fits``) — the pruned solve loops only
    enter this kernel while that holds, so there is no in-kernel
    fallback.  (An XLA conditional here was measured to cost more
    than the dense reduction it avoids: conditional branch operands —
    the [F, D, D] cost tensors — don't alias across the control-flow
    boundary on CPU, so every cycle paid a hypercube-sized copy.
    While-loop phase switching keeps the big operands in the loop
    carry/closure where they DO alias.)

    Under the precondition the result is the SAME VALUE the dense
    reduction produces — dominated rows are strictly above the
    attainable min, ties survive, and the per-element add order
    matches the dense path exactly ((costs + m0) + m1, reduce,
    subtract own message) — so on integer cost tables the whole
    trajectory is bit-identical (asserted in
    tests/unit/test_workreduction_battery.py).

    Work shape: survivors are compacted sort-free — the j-th survivor
    index is recovered from the monotone prefix counts by an unrolled
    O(K·log D) binary search (XLA sort/scatter/top_k all measured
    20-30x slower per element on CPU) — then both directions gather
    CONTIGUOUS [K, D] row blocks (direction 0 from the pre-transposed
    table) and min-plus reduce over K instead of D.  Slots past the
    last survivor duplicate a row that is either itself a survivor or
    dominated — the gathered min stays exact either way.  The
    per-element add order matches the dense path exactly
    ((costs + m0) + m1, reduce, subtract own message): damping and
    mean-normalization accrete mantissa bits cycle over cycle, so an
    "algebraically equal" reassociation (e.g. skipping the
    add-then-subtract of the own message) measurably drifts within
    ~15 cycles even on integer tables.
    """
    costs = bucket.costs
    m0, m1 = msgs[:, 0], msgs[:, 1]
    k = pt.width
    d = costs.shape[1]
    outs = []
    for p in range(2):
        s = _survivors(msgs, pt, p)
        cum = jnp.cumsum(s.astype(jnp.int32), axis=1)       # [F, D]
        # idx[f, j] = first e with cum[e] == j+1 (the j-th survivor):
        # an unrolled branchless lower_bound over the monotone prefix
        # counts, all K targets searched at once — O(K·log D) gathers
        # instead of the O(K·D) compare-and-count matrix.
        target = jnp.arange(1, k + 1, dtype=jnp.int32)[None, :]
        idx = jnp.zeros((cum.shape[0], k), jnp.int32)
        bit = 1
        while bit * 2 <= d:
            bit <<= 1
        while bit:
            nxt = idx + bit
            probe = jnp.take_along_axis(
                cum, jnp.minimum(nxt, d) - 1, axis=1)
            idx = jnp.where((nxt <= d) & (probe < target), nxt, idx)
            bit >>= 1
        idx = jnp.minimum(idx, d - 1)
        if p == 0:
            c_g = jnp.take_along_axis(
                pt.costs_t, idx[:, :, None], axis=1)        # [F, K, D]
            m1_g = jnp.take_along_axis(m1, idx, axis=1)
            total = (c_g + m0[:, None, :]) + m1_g[:, :, None]
            outs.append(jnp.min(total, axis=1) - m0)
        else:
            c_g = jnp.take_along_axis(
                costs, idx[:, :, None], axis=1)             # [F, K, D]
            m0_g = jnp.take_along_axis(m0, idx, axis=1)
            total = (c_g + m0_g[:, :, None]) + m1[:, None, :]
            outs.append(jnp.min(total, axis=1) - m1)
    return jnp.stack(outs, axis=1)


def factor_to_var(graph: CompiledFactorGraph, v2f: Msgs,
                  prune: Optional[Tuple[Optional[PruneTable], ...]]
                  = None) -> Msgs:
    """All factor→variable messages for one superstep.  ``prune``
    (from :func:`prune_tables`) routes binary buckets through the
    branch-and-bound update — same values, less work as the messages
    concentrate."""
    out = []
    use_pallas = _use_pallas()
    for bi, (bucket, msgs) in enumerate(zip(graph.buckets, v2f)):
        if prune is not None and prune[bi] is not None:
            out.append(_pruned_binary_update(bucket, msgs, prune[bi]))
            continue
        if use_pallas and bucket.var_ids.shape[1] == 2:
            from pydcop_tpu.ops.pallas_maxsum import (
                binary_factor_update,
            )

            out.append(binary_factor_update(bucket.costs, msgs))
            continue
        f, arity, d = msgs.shape
        total = bucket.costs  # [F, D, ..., D]
        for q in range(arity):
            shape = [f] + [1] * arity
            shape[q + 1] = d
            total = total + msgs[:, q].reshape(shape)
        outs_p = []
        for p in range(arity):
            axes = tuple(i + 1 for i in range(arity) if i != p)
            reduced = jnp.min(total, axis=axes) if axes else total
            outs_p.append(reduced - msgs[:, p])
        out.append(jnp.stack(outs_p, axis=1))  # [F, arity, D]
    return tuple(out)


def aggregate_beliefs(graph: CompiledFactorGraph, f2v: Msgs
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sum incoming factor messages per variable.

    Returns (beliefs [V+1, D] = own costs + sums, sums [V+1, D]).
    This aggregation is the single cross-shard op per superstep, and
    the suspect past the size that fits fast memory (~100k vars).
    Strategy is chosen at compile time via the graph's ``agg_*`` arrays
    (engine/compile.build_aggregation_arrays; not yet decided on the
    chip: ROADMAP.md Queue 3 "Four aggregations"):

    - default: unsorted scatter-add, one ``segment_sum`` per bucket —
      the only option for sharded graphs;
    - sorted: per-cycle gather into compile-time-sorted edge order,
      then ``segment_sum(indices_are_sorted=True)``;
    - boundary: sorted gather + cumsum + per-variable boundary
      difference — no scatter at all.  EXPERIMENT-ONLY: the f32
      prefix sum grows with the total edge count, so the boundary
      differences cancel catastrophically at the million-edge scale
      this strategy targets (absolute error ~ulp of the running
      total, which dwarfs the 0.01 tie-breaking noise), and TPUs
      have no f64 to accumulate in.  Valid for throughput A/Bs and
      small problems; not offered as a maxsum algo param.
    - ell: dense gather + K-way sum over compile-time per-variable
      edge lists padded to the max degree — no scatter, no sort.
      Numerically safe (each variable's sum is over its own K terms,
      like scatter, just in sorted-edge order) and the shape TPU
      vectorizes best; single-device like the other non-scatter
      paths.
    """
    n_segments = graph.var_costs.shape[0]
    d = graph.var_costs.shape[1]
    if not graph.buckets:
        # Constraint-free DCOP: zero factor buckets means zero
        # incoming messages — the ell/sorted fast paths below would
        # hit jnp.concatenate([]) (ADVICE r5).  Beliefs are just the
        # unary costs.
        zeros = jnp.zeros_like(graph.var_costs)
        return graph.var_costs, zeros
    if graph.agg_ell is not None:
        from pydcop_tpu.ops.ell import gather_reduce

        flats = [msgs.reshape(-1, d) for msgs in f2v]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(
            flats, axis=0)
        sums = gather_reduce(graph.agg_ell, flat, 0.0, jnp.sum)
        return graph.var_costs + sums, sums
    if graph.agg_perm is not None:
        flats = [msgs.reshape(-1, d) for msgs in f2v]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(
            flats, axis=0)
        in_order = flat[graph.agg_perm]
        if graph.agg_starts is not None:
            cum = jnp.cumsum(in_order, axis=0)
            cz = jnp.concatenate(
                [jnp.zeros((1, d), cum.dtype), cum], axis=0)
            sums = cz[graph.agg_ends] - cz[graph.agg_starts]
        else:
            sums = jax.ops.segment_sum(
                in_order, graph.agg_sorted_seg,
                num_segments=n_segments, indices_are_sorted=True,
            )
        return graph.var_costs + sums, sums
    sums = jnp.zeros_like(graph.var_costs)
    for bucket, msgs in zip(graph.buckets, f2v):
        flat = msgs.reshape(-1, d)
        seg = bucket.var_ids.reshape(-1)
        sums = sums + jax.ops.segment_sum(
            flat, seg, num_segments=n_segments
        )
    return graph.var_costs + sums, sums


def var_to_factor(graph: CompiledFactorGraph, f2v: Msgs,
                  beliefs: jnp.ndarray, sums: jnp.ndarray) -> Msgs:
    """All variable→factor messages: belief minus own contribution,
    mean-normalized over valid slots (reference maxsum.py:670-674)."""
    out = []
    for bucket, msgs in zip(graph.buckets, f2v):
        valid = graph.var_valid[bucket.var_ids]        # [F, a, D]
        raw = beliefs[bucket.var_ids] - msgs           # own cost + others
        factor_sum = sums[bucket.var_ids] - msgs       # others only
        n_valid = jnp.maximum(
            jnp.sum(valid, axis=-1, keepdims=True), 1
        )
        avg = (
            jnp.sum(jnp.where(valid, factor_sum, 0.0), axis=-1,
                    keepdims=True)
            / n_valid
        )
        # BIG as the message dtype: a float32 literal would silently
        # promote bfloat16 message arrays back to f32.
        out.append(jnp.where(valid, raw - avg,
                             jnp.asarray(BIG, raw.dtype)))
    return tuple(out)


def select_values(graph: CompiledFactorGraph,
                  beliefs: jnp.ndarray) -> jnp.ndarray:
    """Per-variable argmin of belief over valid slots ([V] int32)."""
    masked = jnp.where(graph.var_valid, beliefs, jnp.inf)
    return jnp.argmin(masked[:-1], axis=1).astype(jnp.int32)


def _damp(new: Msgs, old: Msgs, damping: float,
          first: jnp.ndarray) -> Msgs:
    """damped = damping * prev + (1-damping) * new; no damping on the
    first cycle (reference apply_damping with prev=None, maxsum.py:679)."""
    return tuple(
        jnp.where(first, n, damping * o + (1.0 - damping) * n)
        for n, o in zip(new, old)
    )


def superstep(state: MaxSumState, graph: CompiledFactorGraph, *,
              damping: float, damp_vars: bool, damp_factors: bool,
              stability: float,
              prune: Optional[Tuple[Optional[PruneTable], ...]] = None,
              ) -> MaxSumState:
    """One synchronous MaxSum cycle with the reference's exact BSP
    semantics: in cycle k BOTH sides fire from the messages sent in
    cycle k-1 (Jacobi — a factor computation and a variable computation
    each see only last cycle's mail, reference
    SynchronousComputationMixin), with per-edge damping and SAME_COUNT
    send-suppression.  This cycle-for-cycle equivalence with the
    threaded agent runtime is what makes device-vs-thread parity
    assertable (tests/api/test_device_thread_parity.py)."""
    first = state.cycle == 0
    with jax.named_scope("maxsum/update"):
        valids = tuple(
            graph.var_valid[b.var_ids] for b in graph.buckets
        )

    # Each phase carries a ``jax.named_scope`` (the same names in both
    # layouts; metadata only, the operations and their order are
    # unchanged), so a device trace reduces by phase whatever the
    # compiler calls its fusions.
    with jax.named_scope("maxsum/f2v"):
        f2v_cand = factor_to_var(graph, state.v2f, prune=prune)
    if damp_factors and damping > 0:
        with jax.named_scope("maxsum/update"):
            f2v_cand = _damp(f2v_cand, state.f2v, damping, first)

    # Variable side uses the factor messages from the PREVIOUS cycle.
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
                match | ~jnp.any(valid, -1))
            sent, cnt, match = _send_or_suppress(
                v2f_cand[i], state.v2f[i], state.v2f_count[i],
                stability, valid, first)
            v2f_new.append(sent)
            v2f_count.append(cnt)
            all_match = all_match & jnp.all(
                match | ~jnp.any(valid, -1))

    return MaxSumState(
        v2f=tuple(v2f_new),
        f2v=tuple(f2v_new),
        v2f_count=tuple(v2f_count),
        f2v_count=tuple(f2v_count),
        stable=all_match & ~first,
        cycle=state.cycle + 1,
    )


def assignment_constraint_cost(graph: CompiledFactorGraph,
                               values: jnp.ndarray) -> jnp.ndarray:
    """Total factor-table cost of an assignment ([V] value indices).

    Padding rows contribute 0 (their tables are all-zero and their
    var_ids point at the sentinel row).  Variable-side costs (including
    tie-breaking noise) are NOT included — this is the constraint cost
    the host-side ``DCOP.solution_cost`` reports for problems whose
    variables carry no intrinsic costs."""
    vals = jnp.concatenate(
        [values, jnp.zeros((1,), dtype=values.dtype)]
    )
    total = jnp.asarray(0.0, dtype=graph.var_costs.dtype)
    for bucket in graph.buckets:
        f, arity = bucket.var_ids.shape
        d = graph.var_costs.shape[1]
        idx = vals[bucket.var_ids]               # [F, arity]
        flat = jnp.zeros((f,), dtype=jnp.int32)
        for p in range(arity):
            flat = flat * d + idx[:, p]
        table = bucket.costs.reshape(f, -1)
        total = total + jnp.sum(
            jnp.take_along_axis(table, flat[:, None], axis=1)
        )
    return total


def run_maxsum_trace(graph: CompiledFactorGraph, max_cycles: int, *,
                     damping: float = 0.5, damp_vars: bool = True,
                     damp_factors: bool = True, stability: float = 0.1,
                     var_base_costs=None,
                     stop_on_convergence: bool = True,
                     prune: bool = False,
                     ) -> Tuple[MaxSumState, jnp.ndarray, jnp.ndarray]:
    """Like run_maxsum, additionally recording the cost of the
    selected assignment after every cycle ([max_cycles] array) — the
    cost-vs-cycle curve used for time-to-equal-cost benchmark claims.
    ``var_base_costs`` ([V, D], noise-free variable costs) makes the
    trace match ``DCOP.solution_cost`` on problems with variable-side
    costs.

    With ``stop_on_convergence`` (the default, matching run_maxsum)
    the loop stops at the fixpoint: the cycle counter freezes at the
    convergence cycle (traced and untraced runs agree — asserted in
    the work-reduction battery) and the rest of the cost array holds
    the final value, so the curve keeps its static [max_cycles]
    shape.  Structured as a while_loop writing each cycle's cost into
    a carried [max_cycles] buffer (``dynamic_update_slice``) rather
    than a scan over a skip-conditional — conditional branch operands
    don't alias on the CPU backend, so a per-cycle ``lax.cond`` was
    measured to cost more than the superstep it skipped.  ``prune``
    uses the same dense/compacted phase alternation as run_maxsum_from
    (identical costs per cycle — pruning never changes values)."""
    pt = prune_tables(graph) if prune else None
    if pt is not None and all(t is None for t in pt):
        pt = None

    def cost_of(values):
        cost = assignment_constraint_cost(graph, values)
        if var_base_costs is not None:
            cost = cost + jnp.sum(jnp.take_along_axis(
                var_base_costs, values[:, None], axis=1))
        return cost

    def make_step(prune_t):
        def step(carry):
            state, costs, last = carry
            state = superstep(
                state, graph, damping=damping, damp_vars=damp_vars,
                damp_factors=damp_factors, stability=stability,
                prune=prune_t,
            )
            with jax.named_scope("maxsum/select"):
                beliefs, _ = aggregate_beliefs(graph, state.f2v)
                values = select_values(graph, beliefs)
                cost = cost_of(values)
            costs = jax.lax.dynamic_update_slice(
                costs, cost[None], (state.cycle - 1,))
            return state, costs, cost
        return step

    def done(carry):
        state = carry[0]
        out = state.cycle >= max_cycles
        if stop_on_convergence:
            out = out | state.stable
        return out

    zero = jnp.asarray(0.0, graph.var_costs.dtype)
    carry = (init_state(graph),
             jnp.zeros((max_cycles,), graph.var_costs.dtype), zero)
    step_dense = make_step(None)
    if pt is None:
        carry = jax.lax.while_loop(
            lambda c: ~done(c), step_dense, carry)
    else:
        step_fast = make_step(pt)

        def phases(c):
            c = jax.lax.while_loop(
                lambda c: ~done(c) & ~prune_fits(c[0].v2f, pt),
                step_dense, c)
            c = jax.lax.while_loop(
                lambda c: ~done(c) & prune_fits(c[0].v2f, pt),
                step_fast, c)
            return c

        carry = jax.lax.while_loop(lambda c: ~done(c), phases, carry)
    state, costs, last = carry
    # Early exit leaves the tail unwritten: hold the final cost so
    # the curve stays a valid anytime record at full length.
    costs = jnp.where(
        jnp.arange(max_cycles) >= state.cycle, last, costs)
    with jax.named_scope("maxsum/select"):
        beliefs, _ = aggregate_beliefs(graph, state.f2v)
        values = select_values(graph, beliefs)
    return state, values, costs


def run_maxsum(graph: CompiledFactorGraph, max_cycles: int, *,
               damping: float = 0.5, damp_vars: bool = True,
               damp_factors: bool = True, stability: float = 0.1,
               stop_on_convergence: bool = True,
               prune: bool = False,
               ) -> Tuple[MaxSumState, jnp.ndarray]:
    """Full MaxSum run in one XLA program (no host sync per cycle).

    Returns (final state, selected value indices [V]).
    """
    return run_maxsum_from(
        graph, init_state(graph), max_cycles,
        damping=damping, damp_vars=damp_vars,
        damp_factors=damp_factors, stability=stability,
        stop_on_convergence=stop_on_convergence, prune=prune,
    )


def run_maxsum_from(graph: CompiledFactorGraph, state: MaxSumState,
                    extra_cycles: int, *,
                    damping: float = 0.5, damp_vars: bool = True,
                    damp_factors: bool = True, stability: float = 0.1,
                    stop_on_convergence: bool = True,
                    prune: bool = False,
                    ) -> Tuple[MaxSumState, jnp.ndarray]:
    """Run up to ``extra_cycles`` more supersteps from an existing state
    — the warm-start primitive for dynamic DCOPs: after a graph event
    the surviving messages stay in place and the trajectory continues
    instead of restarting from zero (SURVEY §7 "dynamic graphs ...
    warm-starting messages").

    ``prune=True`` enables branch-and-bound pruning of the binary
    factor→variable reductions (:func:`prune_tables`): the solve
    becomes a pair of PHASE loops — a dense loop that runs while some
    factor's survivor set overflows the static gather budget, and a
    compacted fast loop that runs while every factor fits
    (:func:`prune_fits` rides the loop conditions; each body is
    entered only when its kernel is exact, so no per-cycle XLA
    conditional and no hypercube-sized branch-operand copies).  The
    two kernels produce the same values wherever both are legal, so
    the pruned trajectory equals the dense one — pruning changes
    wall-clock, never results."""
    pt = prune_tables(graph) if prune else None
    if pt is not None and all(t is None for t in pt):
        pt = None

    limit = state.cycle + extra_cycles

    def done(s):
        out = s.cycle >= limit
        if stop_on_convergence:
            out = out | s.stable
        return out

    def step_dense(s):
        return superstep(
            s, graph, damping=damping, damp_vars=damp_vars,
            damp_factors=damp_factors, stability=stability,
        )

    if pt is None:
        # The pre-pruning loop, kept VERBATIM (cond form included):
        # even a logically-equivalent condition rewrite compiles a
        # different XLA program, and on mesh runs a different fusion
        # reassociates the all-reduce enough to flip near-tied
        # argmins — the sharded bit-parity tests pin this.
        if stop_on_convergence:
            state = jax.lax.while_loop(
                lambda s: (s.cycle < limit) & ~s.stable,
                step_dense,
                state,
            )
        else:
            state = jax.lax.while_loop(
                lambda s: s.cycle < limit,
                step_dense,
                state,
            )
    else:
        def step_fast(s):
            return superstep(
                s, graph, damping=damping, damp_vars=damp_vars,
                damp_factors=damp_factors, stability=stability,
                prune=pt,
            )

        def fits(s):
            return prune_fits(s.v2f, pt)

        def phases(s):
            # Each outer iteration makes progress: whichever inner
            # condition holds first steps at least one cycle.
            s = jax.lax.while_loop(
                lambda s: ~done(s) & ~fits(s), step_dense, s)
            s = jax.lax.while_loop(
                lambda s: ~done(s) & fits(s), step_fast, s)
            return s

        state = jax.lax.while_loop(lambda s: ~done(s), phases, state)
    with jax.named_scope("maxsum/select"):
        beliefs, _ = aggregate_beliefs(graph, state.f2v)
        values = select_values(graph, beliefs)
    return state, values
