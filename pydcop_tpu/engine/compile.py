"""Compile a DCOP factor graph into dense, padded, bucketed device arrays.

This is the bridge between the host-side problem model and the jitted
engine.  The layout decisions are what make the kernels MXU/VPU friendly
and the sharding communication-minimal:

- **Arity buckets.** Factors are grouped by arity; each bucket stacks its
  cost hypercubes into one `[F, Dmax, ..., Dmax]` tensor so the
  factor→variable min-reduction is a single batched reduction per bucket
  (reference analogue: the O(d^arity) python enumeration in maxsum's
  factor_costs_for_var, pydcop/algorithms/maxsum.py:382).

- **Messages live in bucket space** as `[F, arity, Dmax]` arrays — the
  slot (f, p) holds the message on the edge between factor f and the
  variable at position p of its scope.  "Sending" is writing a row; there
  is no queue and no serialization (reference analogue: the Messaging
  priority queue, pydcop/infrastructure/communication.py:500).
  Variable-side aggregation is a segment-sum over `var_ids`; when buckets
  are sharded over a mesh axis this is the *only* cross-device op (one
  all-reduce of the [V, D] totals per superstep, riding ICI).

- **Domain padding.** All domains are padded to Dmax with `BIG` cost so
  padded slots never win a min-reduction; `var_valid` masks them out of
  normalizations and argmins.  For `objective=max` problems costs are
  negated at compile time and the final cost re-negated on the host, so
  kernels only ever minimize.

- **Device padding.** Bucket rows are padded to a multiple of `pad_to`
  (the mesh size); padding rows have zero cost and point at a sentinel
  variable row (index V) which is dropped after aggregation, so sharded
  runs need no ragged handling.

- **Zero-ary constraints** are folded into a host-side constant offset
  (`meta.constant_cost`).

Example (compile a 2-variable problem and inspect the device layout)::

    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> from pydcop_tpu.engine.compile import compile_dcop
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', 'x * y', [x, y]))
    >>> graph, meta = compile_dcop(dcop)
    >>> graph.var_costs.shape        # V+1 sentinel row, Dmax slots
    (3, 2)
    >>> [b.costs.shape for b in graph.buckets]  # one binary bucket
    [(1, 2, 2)]
    >>> meta.var_names
    ('x', 'y')
"""

import os
import threading
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Variable, stable_noise_batch
from pydcop_tpu.dcop.relations import Constraint, NAryFunctionRelation
from pydcop_tpu.observability.metrics import registry as metrics_registry
from pydcop_tpu.observability.trace import tracer

BIG = np.float32(1e9)


class CompileCache:
    """Process-wide structure-keyed layout cache.

    Re-solving a same-*shaped* problem (new cost tables, same
    variables/scopes — the repeated-traffic serving pattern the
    ROADMAP targets) should not pay layout construction again: the
    padded ``var_ids`` arrays and the aggregation indexing
    (``agg_perm``/``agg_sorted_seg``/``agg_starts``/``agg_ends``/
    ``agg_ell`` — an argsort + searchsorted + list fill over all E
    edges) are pure functions of the graph *structure* (variable
    count, per-factor scope indices, pad_to, aggregation), never of
    the costs.  ``compile_factor_graph`` keys them here; a hit skips
    layout and agg-array construction entirely (``layout_builds``
    counts the builds, so tests can assert the skip).  Cached arrays
    are frozen (``writeable=False``) — every consumer treats compiled
    graphs as immutable (the engines ``device_put`` them; decimation
    copies before clamping).

    Bounded LRU; ``PYDCOP_COMPILE_CACHE=0`` disables globally.
    Thread-safe: the solve service compiles on concurrent submitter
    threads (serving/service.py), so get/put must not race the LRU
    bookkeeping (an unlocked ``move_to_end`` can KeyError against a
    concurrent eviction).
    """

    def __init__(self, maxsize: int = 8):
        self.maxsize = maxsize
        self._entries: "OrderedDict" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.layout_builds = 0

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
            self.misses += 1
            return None

    def put(self, key, entry):
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.layout_builds = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "layout_builds": self.layout_builds,
                "entries": len(self._entries),
            }


compile_cache = CompileCache()


def _freeze(arr: Optional[np.ndarray]) -> Optional[np.ndarray]:
    if arr is not None:
        arr.flags.writeable = False
    return arr


class FactorBucket(NamedTuple):
    """All factors of one arity, stacked."""

    costs: np.ndarray    # [F, Dmax]*arity, f32, BIG on padded slots
    var_ids: np.ndarray  # [F, arity] int32 (sentinel V on padding rows)

    @property
    def arity(self) -> int:
        return self.var_ids.shape[1]

    @property
    def n_factors(self) -> int:
        return self.var_ids.shape[0]


class CompiledFactorGraph(NamedTuple):
    """Device-ready dense form of a factor graph.

    Array members are numpy on the host; the runner moves them to device
    (optionally sharded).

    The optional ``agg_*`` arrays select the variable-aggregation
    strategy for the MaxSum superstep (see ops/maxsum.aggregate_beliefs;
    not yet decided on the chip: ROADMAP.md Queue 3 "Four
    aggregations"):

    - all None (default): unsorted scatter-add (``segment_sum``);
    - perm + sorted_seg: compile-time edge sort, per-cycle gather into
      sorted order, ``segment_sum(indices_are_sorted=True)``;
    - perm + starts/ends: edge sort + cumsum + per-variable boundary
      gathers — no scatter at all (HBM-regime candidate);
    - ell: per-variable edge lists padded to the maximum degree
      ([V+1, K] indices into the flat edge order; dummy slots hold E,
      one past the last edge — the kernel clips the index and masks
      the contribution to zero) — the aggregation becomes a dense
      gather + K-way sum with no
      scatter and no sort, the layout XLA/TPU vectorizes best
      (scatter-add on TPU serializes row updates; measured on-chip
      round 5: 4.9 ms/iteration for 900k scattered rows at 100k
      vars, ~5.5 ns/row).

    Sharded graphs always use the scatter path (a global edge sort
    would turn the local gather into a cross-device one), so
    ``shard_graph`` drops these arrays.
    """

    var_costs: np.ndarray   # [V+1, Dmax] f32 (last row = sentinel)
    var_valid: np.ndarray   # [V+1, Dmax] bool
    buckets: Tuple[FactorBucket, ...]
    agg_perm: Optional[np.ndarray] = None        # [E] int32
    agg_sorted_seg: Optional[np.ndarray] = None  # [E] int32 (sorted)
    agg_starts: Optional[np.ndarray] = None      # [V+1] int32
    agg_ends: Optional[np.ndarray] = None        # [V+1] int32
    agg_ell: Optional[np.ndarray] = None         # [V+1, K] int32

    @property
    def n_vars(self) -> int:
        return self.var_costs.shape[0] - 1

    @property
    def dmax(self) -> int:
        return self.var_costs.shape[1]


class FactorGraphMeta(NamedTuple):
    """Host-side metadata to map device results back to the problem."""

    var_names: Tuple[str, ...]
    domains: Tuple[Tuple, ...]          # domain values per var
    factor_names: Tuple[str, ...]       # bucket order, real factors only
    bucket_sizes: Tuple[int, ...]       # real (unpadded) factors per bucket
    mode: str                           # 'min' or 'max'
    constant_cost: float = 0.0          # folded zero-ary constraints
    # [V, Dmax] sign-adjusted variable costs WITHOUT tie-breaking
    # noise (zeros on domain padding) — what DCOP.solution_cost
    # charges for variable-side costs; used by cost traces.
    var_base_costs: Optional[np.ndarray] = None

    def assignment_from_indices(self, idx: Sequence[int]) -> Dict:
        return {
            name: self.domains[i][int(idx[i])]
            for i, name in enumerate(self.var_names)
        }


def _round_up(n: int, multiple: int) -> int:
    if multiple <= 1:
        return n
    return ((n + multiple - 1) // multiple) * multiple


AGGREGATIONS = ("scatter", "sorted", "boundary", "ell")
AUTO_AGGREGATION = "auto"

# Branch-and-bound message pruning (ops/maxsum.prune_tables): the
# compacted factor->variable reduction gathers at most ``prune_width``
# surviving rows per factor — a STATIC width, so the pruned program
# keeps the bucketed layout's fixed shapes (the structure cache and
# every aggregation strategy see the same arrays).  max(2, min(8,
# D//8)) balances the reduction saving (the fast path's work scales
# with the budget) against how often the data-dependent survivor
# count fits it; below PRUNE_MIN_DOMAIN the dense reduction is
# already cheaper than the bound bookkeeping, so pruning compiles to
# the dense path there.
PRUNE_WIDTH_DIVISOR = 8
PRUNE_WIDTH_CAP = 8
PRUNE_MIN_DOMAIN = 8


def prune_width(dmax: int) -> int:
    """Static surviving-row budget of the pruned binary-factor update.
    Capped: the compacted reduction's work grows with the budget, and
    measured survivor counts at the fixpoint sit at 1-5 across every
    problem family tried — a budget past 8 only dilutes the win."""
    return max(2, min(PRUNE_WIDTH_CAP, dmax // PRUNE_WIDTH_DIVISOR))

# Placeholder costs array for layout-only FactorBucket shims — the
# aggregation builder reads only var_ids.
_EMPTY_COSTS = np.zeros((0,), np.float32)


def validated_aggregation(params: dict, pad_to: int) -> str:
    """Resolve an algorithm's ``aggregation`` param against the mesh
    size.  shard_graph rebuilds graphs WITHOUT the agg_* arrays (and
    the partitioned engine aggregates per shard with local scatter),
    so a non-scatter strategy on a mesh would silently measure
    scatter — refuse loudly instead (one policy for every algorithm
    family).

    ``"auto"`` resolves to ``"scatter"`` on a mesh (the only valid
    sharded strategy — not an error, auto means "pick a valid one for
    me") and passes through otherwise; the caller is expected to run
    the measured selection (engine/autotune.autotune_aggregation) on
    the compiled graph."""
    aggregation = params.get("aggregation", "scatter")
    if aggregation == AUTO_AGGREGATION:
        return "scatter" if pad_to > 1 else AUTO_AGGREGATION
    if pad_to > 1 and aggregation != "scatter":
        raise ValueError(
            f"aggregation={aggregation!r} is single-device; sharded "
            "runs always use the scatter path (engine/sharding."
            "shard_graph drops the aggregation arrays)")
    return aggregation


def build_aggregation_arrays(buckets: Sequence[FactorBucket],
                             n_segments: int, aggregation: str):
    """Compile-time edge indexing for the non-scatter aggregation paths.

    Edges are the flattened (bucket, factor, position) slots in bucket
    order — the same order ``aggregate_beliefs`` flattens messages in.
    Returns the 5 ``agg_*`` field values for CompiledFactorGraph:
    (perm, sorted_seg, starts, ends, ell).
    """
    if aggregation == "scatter":
        return None, None, None, None, None
    if aggregation not in AGGREGATIONS:
        raise ValueError(
            f"aggregation must be one of {AGGREGATIONS}, "
            f"got {aggregation!r}"
        )
    seg = np.concatenate(
        [b.var_ids.reshape(-1) for b in buckets]
    ) if buckets else np.zeros((0,), np.int32)
    perm = np.argsort(seg, kind="stable").astype(np.int32)
    sorted_seg = seg[perm].astype(np.int32)
    if aggregation == "sorted":
        return perm, sorted_seg, None, None, None
    starts = np.searchsorted(
        sorted_seg, np.arange(n_segments), side="left"
    ).astype(np.int32)
    ends = np.searchsorted(
        sorted_seg, np.arange(n_segments), side="right"
    ).astype(np.int32)
    if aggregation == "boundary":
        return perm, None, starts, ends, None
    # ell: [V+1, K] edge indices per variable, K = max REAL-variable
    # degree (the sentinel row V absorbs every padding-edge slot and
    # would otherwise inflate K; its sum is dropped by the kernel, so
    # its list stays all-dummy).  Dummy slots hold E — the kernel
    # clips the index and masks the contribution to zero.
    n_edges = seg.size
    deg = ends - starts
    k_max = int(deg[:-1].max()) if n_segments > 1 and n_edges else 1
    k_max = max(k_max, 1)
    # Hub guard: K is the MAX degree, so one power-law hub inflates
    # every variable's padded list ([V+1, K] int32 — a 1M-var graph
    # with a degree-10k hub would allocate 40 GB).  Refuse with
    # guidance instead of OOMing the device.
    ell_bytes = n_segments * k_max * 4
    if ell_bytes > 2 << 30:
        raise ValueError(
            f"aggregation='ell' would allocate a {n_segments} x "
            f"{k_max} edge-list array ({ell_bytes / (1 << 30):.1f} "
            "GiB): the max variable degree is far above the mean "
            f"({n_edges / max(n_segments - 1, 1):.1f}) — use "
            "aggregation='scatter' for hub-dominated graphs")
    ell = np.full((n_segments, k_max), n_edges, np.int32)
    # Position of each sorted edge within its variable's list.
    k_pos = np.arange(n_edges) - starts[sorted_seg]
    real = sorted_seg < (n_segments - 1)
    ell[sorted_seg[real], k_pos[real]] = perm[real]
    return None, None, None, None, ell


def _factor_table(c: Constraint, memo: Dict,
                  vectorize: bool) -> np.ndarray:
    """Dense table of one factor as the constraint gives it (the
    bucket casts and sign-adjusts its tables together), memoized on
    the structural table signature: factors whose expressions differ
    only in variable names (every generated-edge family) evaluate
    ONCE per bucket instead of once per factor, and each evaluation
    is the vectorized numpy path
    (relations.NAryFunctionRelation.to_array) instead of a d^arity
    python loop.  ``vectorize=False`` restores the per-factor
    per-assignment reference path the tests compare against."""
    if not vectorize:
        if isinstance(c, NAryFunctionRelation):
            # The pre-vectorization behavior: the base per-assignment
            # enumeration loop.
            return np.asarray(Constraint.to_array(c))
        return np.asarray(c.to_array())
    sig = c.table_signature()
    if sig is not None:
        table = memo.get(sig)
        if table is not None:
            return table
    table = np.asarray(c.to_array())
    if sig is not None:
        memo[sig] = table
    return table


def compile_factor_graph(
    variables: Sequence[Variable],
    constraints: Sequence[Constraint],
    mode: str = "min",
    noise_level: float = 0.0,
    noise_seed: Optional[int] = None,
    pad_to: int = 1,
    dtype=np.float32,
    aggregation: str = "scatter",
    vectorize: bool = True,
    use_cache: Optional[bool] = None,
) -> Tuple[CompiledFactorGraph, FactorGraphMeta]:
    """Build the dense arrays.  `noise_level` adds deterministic
    per-variable-value noise (maxsum's tie-breaking noise, reference
    maxsum.py:477-487, seeded here for reproducibility).

    ``vectorize`` enables the batched numpy cost-table evaluation
    plus the per-bucket table memo (see :func:`_factor_table`);
    ``use_cache`` controls the structure-keyed layout cache
    (:class:`CompileCache`; default on, ``PYDCOP_COMPILE_CACHE=0``
    disables process-wide)."""
    if use_cache is None:
        use_cache = os.environ.get("PYDCOP_COMPILE_CACHE") != "0"
    # Materialize before measuring: callers may pass iterators, which
    # have no len() (the body always listified them).
    variables = list(variables)
    constraints = list(constraints)
    # tracer.span is its own no-op when disabled; compile is a cold
    # path, so the kwargs build costs nothing worth guarding.
    with tracer.span("compile_graph", "engine",
                     n_vars=len(variables),
                     n_constraints=len(constraints)):
        return _compile_factor_graph(
            variables, constraints, mode, noise_level, noise_seed,
            pad_to, dtype, aggregation, vectorize, use_cache,
        )


def _variable_tables(variables, names, domains, sign, noise_level,
                     noise_seed, dtype):
    """``var_costs`` / ``var_valid`` (with the sentinel row) and the
    noise-free ``var_base``, filled over all variables at once: the
    domain mask from the sizes, the base costs stacked, the
    tie-breaking noise of every variable in one draw."""
    v_count = len(variables)
    sizes = np.fromiter(map(len, domains), dtype=np.int64, count=v_count)
    dmax = int(sizes.max()) if v_count else 1
    valid = np.arange(dmax) < sizes[:, None]
    # A plain Variable costs nothing by definition: only classes that
    # bring their own costs are asked for them.
    base = np.zeros((v_count, dmax), dtype=np.float64)
    costly = {
        cls for cls in set(map(type, variables))
        if cls.cost_for_val is not Variable.cost_for_val
        or cls.cost_vector is not Variable.cost_vector
    }
    if costly:
        for i, v in enumerate(variables):
            if type(v) in costly:
                d = sizes[i]
                base[i, :d] = v.cost_vector()[:d]
    costs = sign * base
    var_base = np.where(valid, costs, 0.0).astype(dtype)
    if noise_level:
        costs = costs + stable_noise_batch(
            names, dmax, noise_level, noise_seed)
    var_costs = np.full((v_count + 1, dmax), BIG, dtype=dtype)
    np.copyto(var_costs[:v_count], costs, where=valid, casting="unsafe")
    var_valid = np.zeros((v_count + 1, dmax), dtype=bool)
    var_valid[:v_count] = valid
    return var_costs, var_valid, var_base


def _bucket_costs(facs, n_rows, arity, dmax, sign, dtype, vectorize):
    """One bucket's ``[n_rows, dmax, ...]`` cost tensor: BIG on domain
    padding, zero on padding rows, and the factors' tables cast,
    sign-adjusted and written with one stacked assignment per table
    shape and dtype (a single one wherever every scope variable has
    ``dmax`` values, as the tables of one source have one dtype)."""
    costs = np.full((n_rows,) + (dmax,) * arity, BIG, dtype=dtype)
    memo: Dict = {}
    groups: Dict[Tuple, Tuple[List[int], List[np.ndarray]]] = {}
    for fi, c in enumerate(facs):
        table = _factor_table(c, memo, vectorize)
        rows, tables = groups.setdefault(
            (table.shape, table.dtype), ([], []))
        rows.append(fi)
        tables.append(table)
    for (shape, _), (rows, tables) in groups.items():
        where = slice(0, len(facs)) if len(groups) == 1 else rows
        costs[(where,) + tuple(slice(0, s) for s in shape)] = (
            sign * np.asarray(np.array(tables), dtype=dtype))
    # Padding rows keep cost 0 and the sentinel variable.
    costs[len(facs):] = 0.0
    return costs


def _compile_factor_graph(variables, constraints, mode, noise_level,
                          noise_seed, pad_to, dtype, aggregation,
                          vectorize, use_cache):
    names = [v.name for v in variables]
    var_index = {name: i for i, name in enumerate(names)}
    v_count = len(variables)
    sign = 1.0 if mode == "min" else -1.0

    # One walk over the constraints: membership of every scope
    # variable, the zero-ary constant, and per arity the factors with
    # their scope indices (flat, reshaped below).
    constant_cost = 0.0
    by_arity: Dict[int, List[Constraint]] = {}
    flat_ids: Dict[int, List[int]] = {}
    for c in constraints:
        dims = c.dimensions
        if not dims:
            constant_cost += float(c())
            continue
        try:
            ids = [var_index[v.name] for v in dims]
        except KeyError as missing:
            raise ValueError(
                f"Constraint {c.name} references variable "
                f"{missing.args[0]} "
                "which has no computation node — external (read-"
                "only) variables require the 'maxsum_dynamic' "
                "algorithm, which slices them out before compiling"
            ) from None
        by_arity.setdefault(len(dims), []).append(c)
        flat_ids.setdefault(len(dims), []).extend(ids)

    # Variable cost table (+ sentinel row for padding edges).
    domains = [v.domain for v in variables]
    var_costs, var_valid, var_base = _variable_tables(
        variables, names, domains, sign, noise_level, noise_seed, dtype)
    dmax = var_costs.shape[1]

    # Per-factor scope indices, one [n_facs, arity] array per arity.
    # Needed both for the bucket layout and as the structure-cache
    # key: the layout (padded var_ids + agg_* arrays) is a pure
    # function of these indices + (v_count, pad_to, aggregation).
    arities = sorted(by_arity)
    scope_ids: Dict[int, np.ndarray] = {
        arity: np.array(flat_ids[arity], dtype=np.int32).reshape(
            len(by_arity[arity]), arity)
        for arity in arities
    }

    layout = None
    cache_key = None
    if use_cache:
        cache_key = (
            v_count, pad_to, aggregation,
            tuple((a, scope_ids[a].tobytes()) for a in arities),
        )
        layout = compile_cache.get(cache_key)
        # registry.active gate, like every optional series this PR
        # adds: an unobserved solve must not accumulate samples that
        # a later observed solve's .prom dump would misattribute.
        if metrics_registry.active:
            metrics_registry.counter(
                "pydcop_compile_cache_total",
                "Structure-cache lookups by outcome",
            ).inc(outcome="hit" if layout is not None else "miss")
    if layout is None:
        compile_cache.layout_builds += 1
        if metrics_registry.active:
            metrics_registry.counter(
                "pydcop_layout_builds_total",
                "Factor-graph layout constructions (cache misses + "
                "uncached compiles)",
            ).inc()
        var_ids_by_arity = {}
        for arity in arities:
            n_facs = scope_ids[arity].shape[0]
            n_rows = _round_up(n_facs, pad_to)
            ids = np.full((n_rows, arity), v_count, dtype=np.int32)
            ids[:n_facs] = scope_ids[arity]
            var_ids_by_arity[arity] = _freeze(ids)
        agg = build_aggregation_arrays(
            [FactorBucket(_EMPTY_COSTS, ids)
             for ids in var_ids_by_arity.values()],
            v_count + 1, aggregation,
        )
        layout = (var_ids_by_arity, tuple(_freeze(a) for a in agg))
        if use_cache:
            compile_cache.put(cache_key, layout)
    var_ids_by_arity, (perm, sorted_seg, starts, ends, ell) = layout

    buckets = []
    factor_names: List[str] = []
    bucket_sizes: List[int] = []
    for arity in arities:
        facs = by_arity[arity]
        var_ids = var_ids_by_arity[arity]
        factor_names.extend(c.name for c in facs)
        buckets.append(FactorBucket(
            _bucket_costs(facs, var_ids.shape[0], arity, dmax, sign,
                          dtype, vectorize),
            var_ids))
        bucket_sizes.append(len(facs))
    compiled = CompiledFactorGraph(
        var_costs=var_costs,
        var_valid=var_valid,
        buckets=tuple(buckets),
        agg_perm=perm,
        agg_sorted_seg=sorted_seg,
        agg_starts=starts,
        agg_ends=ends,
        agg_ell=ell,
    )
    # Variables mostly share a few Domain objects: one tuple each.
    domain_values = {
        key: tuple(d) for key, d in {id(d): d for d in domains}.items()}
    meta = FactorGraphMeta(
        var_names=tuple(names),
        domains=tuple(domain_values[id(d)] for d in domains),
        factor_names=tuple(factor_names),
        bucket_sizes=tuple(bucket_sizes),
        mode=mode,
        constant_cost=constant_cost,
        var_base_costs=var_base,
    )
    return compiled, meta


def compile_dcop(dcop: DCOP, noise_level: float = 0.0,
                 noise_seed: Optional[int] = None, pad_to: int = 1,
                 aggregation: str = "scatter",
                 vectorize: bool = True,
                 use_cache: Optional[bool] = None,
                 ) -> Tuple[CompiledFactorGraph, FactorGraphMeta]:
    return compile_factor_graph(
        list(dcop.variables.values()),
        list(dcop.constraints.values()),
        mode=dcop.objective,
        noise_level=noise_level,
        noise_seed=noise_seed,
        pad_to=pad_to,
        aggregation=aggregation,
        vectorize=vectorize,
        use_cache=use_cache,
    )
