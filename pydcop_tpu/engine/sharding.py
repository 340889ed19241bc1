"""Mesh construction and sharding for the device engine.

Two sharding stories live here:

**Replicated-variable sharding** (:func:`shard_graph`, the original
scaling-book recipe): factor buckets row-shard over a one-axis mesh,
variable tables replicate, and the per-superstep segment-sum into the
replicated ``[V+1, D]`` totals is the one collective XLA inserts (an
all-reduce over ICI).  Simple and algorithm-agnostic — every device
algorithm rides it via ``n_devices`` — but the all-reduce moves
O(V·D) per superstep no matter how local the graph is.

**Partitioned sharding** (:func:`build_partitioned_graph` +
:class:`ShardOps`, the ``shards=`` path): a host-side min-edge-cut
partition (engine/partition.py) assigns variables AND factors to
shards; each shard owns a local slice of the variable tables and the
messages of its own factors, interior message updates are purely
local, and only HALO variables — endpoints of cut edges — are
exchanged per superstep through a compacted ``[B, D]`` boundary
buffer (``jax.lax.psum`` inside ``shard_map``).  Communication volume
becomes O(cut·D) instead of O(V·D).  The superstep further splits
into interior and boundary sub-updates: the boundary partial sums of
the messages just sent are psum'd at the TAIL of superstep *t* into a
double-buffered halo slot that superstep *t+1* consumes at its head —
the halo exchange of one cycle overlaps the interior factor→variable
work XLA schedules around it, without changing the BSP semantics
(the variable side always reads the previous cycle's factor
messages, so the "stale-looking" buffer is exactly the right one).

This replaces the reference's distribution-of-computations-over-agents
as the *intra-pod* scaling mechanism (reference: pydcop/distribution/);
the distribution algorithms remain for agent-mode and for balancing
which factors land on which shard.
"""

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pydcop_tpu.engine.compile import (
    BIG,
    CompiledFactorGraph,
    FactorBucket,
)
from pydcop_tpu.engine.partition import Partition, real_factor_rows
from pydcop_tpu.ops import maxsum as maxsum_ops

SHARD_AXIS = "shard"


def make_mesh(n_devices: Optional[int] = None,
              devices=None) -> Mesh:
    """A 1-D mesh over (the first n of) the available devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"Requested {n_devices} devices but only "
                f"{len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_graph(graph: CompiledFactorGraph,
                mesh: Mesh) -> CompiledFactorGraph:
    """Place the compiled graph on the mesh: buckets sharded on the
    factor axis, variable tables replicated.

    Bucket rows not divisible by the mesh size are auto-padded with
    sentinel rows (zero cost, var_ids pointing at the sentinel
    variable — identical to compile-time ``pad_to`` padding), so
    callers no longer have to know the mesh size at compile time.
    """
    replicated = NamedSharding(mesh, P())
    row_sharded = NamedSharding(mesh, P(SHARD_AXIS))
    sentinel = graph.var_costs.shape[0] - 1
    buckets = []
    for b in graph.buckets:
        costs = np.asarray(b.costs)
        var_ids = np.asarray(b.var_ids)
        pad = (-costs.shape[0]) % mesh.size
        if pad:
            costs = np.concatenate(
                [costs,
                 np.zeros((pad,) + costs.shape[1:], costs.dtype)],
                axis=0)
            var_ids = np.concatenate(
                [var_ids,
                 np.full((pad, var_ids.shape[1]), sentinel,
                         var_ids.dtype)],
                axis=0)
        buckets.append(FactorBucket(
            costs=jax.device_put(costs, row_sharded),
            var_ids=jax.device_put(var_ids, row_sharded),
        ))
    return CompiledFactorGraph(
        var_costs=jax.device_put(graph.var_costs, replicated),
        var_valid=jax.device_put(graph.var_valid, replicated),
        buckets=tuple(buckets),
    )


# --------------------------------------------------------------------- #
# Partitioned sharding: per-shard variable slices + halo exchange.


class ShardBucket(NamedTuple):
    """One arity bucket, stacked per shard: leading axis S, var_ids in
    the shard-LOCAL variable index space (see ShardedGraph)."""

    costs: Any     # [S, F, Dmax]*arity
    var_ids: Any   # [S, F, arity] int32, local L-space


class ShardedGraph(NamedTuple):
    """Partitioned device layout.  Every array has a leading shard
    axis S and is placed ``P('shard')`` — inside ``shard_map`` each
    shard sees its own block.

    Local variable index space per shard (size ``L``): slots
    ``[0, V_loc)`` hold OWNED variables (padded across shards to the
    max owned count), ``[V_loc, V_loc + H)`` hold HALO variables
    (owned elsewhere, referenced by local factors; cost rows are
    copies of the owner's rows so beliefs compute identically), and
    slot ``L-1`` is the sentinel absorbing padding edges.

    The boundary buffer covers the B variables that are halo for at
    least one shard; ``bnd_*``/``halo_bnd``/``bnd_edge_*`` are the
    index plumbing for the O(B·D) halo exchange (see ShardOps).
    """

    var_costs: Any     # [S, L, D] f32
    var_valid: Any     # [S, L, D] bool
    buckets: Tuple[ShardBucket, ...]
    local_global: Any  # [S, L-1] int32: global id per local slot (V=pad)
    bnd_local: Any     # [S, B] int32: local slot of boundary var b (L-1 if absent)
    bnd_present: Any   # [S, B] bool: shard holds a slot for b
    bnd_owner: Any     # [S, B] bool: shard owns b
    halo_bnd: Any      # [S, H] int32: boundary index of halo slot h (B=pad)
    bnd_edge_idx: Any  # [S, Eb] int32: flat f2v edge index of boundary edges
    bnd_edge_seg: Any  # [S, Eb] int32: boundary index of that edge (B=pad)

    @property
    def n_shards(self) -> int:
        return self.var_costs.shape[0]

    @property
    def dmax(self) -> int:
        return self.var_costs.shape[-1]

    @property
    def n_boundary(self) -> int:
        return self.bnd_local.shape[-1]

    @property
    def v_loc(self) -> int:
        return self.local_global.shape[-1] - self.halo_bnd.shape[-1]


class ShardedMaxSumState(NamedTuple):
    """MaxSum state for the partitioned engine.  Messages are stacked
    per shard ([S, F, arity, D], sharded); ``halo`` is the
    double-buffered boundary-sum slot — the psum'd totals of the
    CURRENT ``f2v`` messages, computed at the tail of the superstep
    that sent them and consumed at the head of the next one.
    ``stable``/``cycle`` are replicated scalars (``stable`` is the
    psum-combined global verdict, ``cycle`` advances identically on
    every shard)."""

    v2f: Tuple[Any, ...]
    f2v: Tuple[Any, ...]
    v2f_count: Tuple[Any, ...]
    f2v_count: Tuple[Any, ...]
    halo: Any      # [B, D] f32, replicated
    stable: Any    # scalar bool
    cycle: Any     # scalar int32


def build_partitioned_graph(graph: CompiledFactorGraph,
                            part: Partition, mesh: Mesh
                            ) -> Tuple[ShardedGraph, Dict[str, Any]]:
    """Materialize the per-shard layout for a partition: local
    variable tables (owned + halo + sentinel), locally-reindexed
    factor buckets, and the boundary-exchange index arrays.  Returns
    the placed ShardedGraph plus the metrics dict (partition stats +
    communication accounting)."""
    n_shards = mesh.size
    if part.n_shards != n_shards:
        raise ValueError(
            f"partition has {part.n_shards} shards but mesh has "
            f"{n_shards} devices")
    n_vars = graph.n_vars
    d = graph.dmax
    var_shard = part.var_shard
    var_costs = np.asarray(graph.var_costs)
    var_valid = np.asarray(graph.var_valid)

    owned = [np.nonzero(var_shard == s)[0] for s in range(n_shards)]
    # Per-bucket real rows + their shard assignment (padding rows of
    # the input graph are dropped; per-shard padding is rebuilt).
    bucket_rows = []
    for b, fs in zip(graph.buckets, part.factor_shard):
        ids = np.asarray(b.var_ids)
        rows = real_factor_rows(ids, n_vars)
        if rows.shape[0] != fs.shape[0]:
            raise ValueError(
                "partition factor assignment does not match the "
                f"graph ({rows.shape[0]} real factors vs "
                f"{fs.shape[0]} assigned)")
        bucket_rows.append((ids, np.asarray(b.costs), rows, fs))

    halo = []
    for s in range(n_shards):
        touched: list = []
        for ids, _, rows, fs in bucket_rows:
            sel = rows[fs == s]
            if sel.size:
                touched.append(np.unique(ids[sel]))
        all_touched = (np.unique(np.concatenate(touched))
                       if touched else np.zeros((0,), np.int64))
        halo.append(np.setdiff1d(all_touched, owned[s]))

    v_loc = max((len(o) for o in owned), default=0)
    v_loc = max(v_loc, 1)
    n_halo = max((len(h) for h in halo), default=0)
    L = v_loc + n_halo + 1

    bnd_list = (np.unique(np.concatenate(halo))
                if any(h.size for h in halo)
                else np.zeros((0,), np.int64))
    n_bnd = len(bnd_list)
    bnd_of = np.full(n_vars + 1, n_bnd, np.int64)
    bnd_of[bnd_list] = np.arange(n_bnd)

    s_var_costs = np.full((n_shards, L, d), BIG, var_costs.dtype)
    s_var_valid = np.zeros((n_shards, L, d), bool)
    s_local_global = np.full((n_shards, L - 1), n_vars, np.int32)
    s_bnd_local = np.full((n_shards, max(n_bnd, 0)), L - 1, np.int32)
    s_bnd_present = np.zeros((n_shards, n_bnd), bool)
    s_bnd_owner = np.zeros((n_shards, n_bnd), bool)
    s_halo_bnd = np.full((n_shards, n_halo), n_bnd, np.int32)

    local_of = np.full((n_shards, n_vars + 1), L - 1, np.int64)
    for s in range(n_shards):
        o, h = owned[s], halo[s]
        local_of[s, o] = np.arange(len(o))
        local_of[s, h] = v_loc + np.arange(len(h))
        rows = np.concatenate([o, h]).astype(np.int64)
        slots = local_of[s, rows]
        s_var_costs[s, slots] = var_costs[rows]
        s_var_valid[s, slots] = var_valid[rows]
        s_local_global[s, slots] = rows
        if n_bnd:
            s_bnd_local[s] = local_of[s, bnd_list]
            s_bnd_present[s] = s_bnd_local[s] != (L - 1)
            s_bnd_owner[s] = var_shard[bnd_list] == s
        if len(h):
            s_halo_bnd[s, :len(h)] = bnd_of[h]

    # Per-bucket local layouts, padded to the max per-shard factor
    # count so the stacked arrays are rectangular.
    buckets = []
    bucket_pad_counts = []
    flat_offsets = []
    offset = 0
    for ids, costs, rows, fs in bucket_rows:
        arity = ids.shape[1]
        counts = [int((fs == s).sum()) for s in range(n_shards)]
        f_max = max(counts + [0])
        s_costs = np.zeros((n_shards, f_max) + costs.shape[1:],
                           costs.dtype)
        s_ids = np.full((n_shards, f_max, arity), L - 1, np.int32)
        for s in range(n_shards):
            sel = rows[fs == s]
            k = sel.shape[0]
            if k:
                s_costs[s, :k] = costs[sel]
                s_ids[s, :k] = local_of[s][ids[sel]]
        buckets.append(ShardBucket(costs=s_costs, var_ids=s_ids))
        bucket_pad_counts.append(f_max)
        flat_offsets.append(offset)
        offset += f_max * arity
    total_edges = offset

    # Boundary-incident edges per shard, in the flat f2v order the
    # kernels use (bucket order, row-major [F, arity]).  These drive
    # the O(cut) boundary sub-update: the halo partial sums aggregate
    # ONLY these edges, never the interior ones.
    is_bnd_slot = np.zeros((n_shards, L), bool)
    for s in range(n_shards):
        if n_bnd:
            pres = s_bnd_present[s]
            is_bnd_slot[s, s_bnd_local[s][pres]] = True
    edge_idx = [[] for _ in range(n_shards)]
    edge_seg = [[] for _ in range(n_shards)]
    slot_bnd = np.full((n_shards, L), n_bnd, np.int64)
    for s in range(n_shards):
        if n_bnd:
            pres = s_bnd_present[s]
            slot_bnd[s, s_bnd_local[s][pres]] = np.nonzero(pres)[0]
    for bi, bucket in enumerate(buckets):
        arity = bucket.var_ids.shape[2]
        for s in range(n_shards):
            lids = bucket.var_ids[s].reshape(-1)
            sel = np.nonzero(is_bnd_slot[s][lids])[0]
            edge_idx[s].append(flat_offsets[bi] + sel)
            edge_seg[s].append(slot_bnd[s][lids[sel]])
    e_max = 0
    for s in range(n_shards):
        edge_idx[s] = (np.concatenate(edge_idx[s])
                       if edge_idx[s] else np.zeros((0,), np.int64))
        edge_seg[s] = (np.concatenate(edge_seg[s])
                       if edge_seg[s] else np.zeros((0,), np.int64))
        e_max = max(e_max, edge_idx[s].shape[0])
    s_edge_idx = np.zeros((n_shards, e_max), np.int32)
    s_edge_seg = np.full((n_shards, e_max), n_bnd, np.int32)
    for s in range(n_shards):
        k = edge_idx[s].shape[0]
        s_edge_idx[s, :k] = edge_idx[s]
        s_edge_seg[s, :k] = edge_seg[s]

    sharded = ShardedGraph(
        var_costs=s_var_costs,
        var_valid=s_var_valid,
        buckets=tuple(buckets),
        local_global=s_local_global,
        bnd_local=s_bnd_local,
        bnd_present=s_bnd_present,
        bnd_owner=s_bnd_owner,
        halo_bnd=s_halo_bnd,
        bnd_edge_idx=s_edge_idx,
        bnd_edge_seg=s_edge_seg,
    )
    row_sharded = NamedSharding(mesh, P(SHARD_AXIS))
    sharded = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, row_sharded), sharded)
    # Communication accounting: what one superstep moves between
    # shards on each path.  The partitioned exchange is the [B, D]
    # halo psum (+ one scalar convergence flag); the replicated
    # baseline all-reduces the dense [V+1, D] totals.  The shard-smoke
    # gate asserts partitioned < replicated.
    metrics = {
        **part.stats,
        "halo_exchange_elems_per_superstep": n_bnd * d,
        "replicated_allreduce_elems_per_superstep": (n_vars + 1) * d,
        "halo_exchange_bytes_per_superstep": n_bnd * d * 4,
        "replicated_allreduce_bytes_per_superstep":
            (n_vars + 1) * d * 4,
        "boundary_edges_per_shard_max": int(e_max),
        "local_factor_rows_per_shard": list(bucket_pad_counts),
        "total_flat_edges": int(total_edges),
    }
    return sharded, metrics


# ---------------------------- device kernels ------------------------- #


def _unblock_graph(g: ShardedGraph):
    """Strip the leading per-shard block axis: inside shard_map a
    shard's slice of the graph is just a CompiledFactorGraph over the
    local L-space, plus the boundary-index aux arrays."""
    lgraph = CompiledFactorGraph(
        var_costs=g.var_costs[0],
        var_valid=g.var_valid[0],
        buckets=tuple(
            FactorBucket(b.costs[0], b.var_ids[0]) for b in g.buckets
        ),
    )
    aux = g._replace(
        var_costs=g.var_costs[0], var_valid=g.var_valid[0],
        buckets=(), local_global=g.local_global[0],
        bnd_local=g.bnd_local[0], bnd_present=g.bnd_present[0],
        bnd_owner=g.bnd_owner[0], halo_bnd=g.halo_bnd[0],
        bnd_edge_idx=g.bnd_edge_idx[0], bnd_edge_seg=g.bnd_edge_seg[0],
    )
    return lgraph, aux


def _unblock_state(st: ShardedMaxSumState) -> ShardedMaxSumState:
    sq = lambda t: tuple(m[0] for m in t)  # noqa: E731
    return st._replace(v2f=sq(st.v2f), f2v=sq(st.f2v),
                       v2f_count=sq(st.v2f_count),
                       f2v_count=sq(st.f2v_count))


def _reblock_state(st: ShardedMaxSumState) -> ShardedMaxSumState:
    ex = lambda t: tuple(m[None] for m in t)  # noqa: E731
    return st._replace(v2f=ex(st.v2f), f2v=ex(st.f2v),
                       v2f_count=ex(st.v2f_count),
                       f2v_count=ex(st.f2v_count))


def _local_sums(lgraph: CompiledFactorGraph, f2v) -> jnp.ndarray:
    """Shard-local variable aggregation (the interior sub-update):
    the single-device scatter path of ops.maxsum.aggregate_beliefs on
    the local block (local graphs never carry agg_* arrays, so the
    scatter branch is guaranteed; the unused beliefs output is
    dead-code-eliminated by XLA).  Interior variables get their FULL
    sums here (all their factors are local by construction); boundary
    slots get this shard's partial, overwritten by the halo buffer in
    _combine_halo."""
    _, sums = maxsum_ops.aggregate_beliefs(lgraph, f2v)
    return sums


def _combine_halo(sums: jnp.ndarray, halo: jnp.ndarray,
                  aux) -> jnp.ndarray:
    """Overwrite boundary rows of the local sums with the exchanged
    global totals.  Absent boundary vars map to the sentinel slot and
    rewrite its (garbage) row with itself — a no-op."""
    if halo.shape[0] == 0:
        return sums
    rows = jnp.where(aux.bnd_present[:, None], halo,
                     sums[aux.bnd_local])
    return sums.at[aux.bnd_local].set(rows)


def _exchange_halo(f2v, aux, n_boundary: int) -> jnp.ndarray:
    """The boundary sub-update + halo exchange: partial sums over ONLY
    the boundary-incident edges of the just-sent factor messages,
    all-reduced across the mesh into the [B, D] double buffer.  This
    is the single O(cut·D) collective of the partitioned superstep;
    issued at the superstep tail so XLA can overlap it with the next
    superstep's interior factor work.  Callers skip the call entirely
    when ``n_boundary`` is 0 (an edge-free or perfectly-partitioned
    graph exchanges nothing)."""
    d = f2v[0].shape[-1]
    flats = [m.reshape(-1, d) for m in f2v]
    flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats, 0)
    contrib = flat[aux.bnd_edge_idx]            # [Eb, D]
    partials = jax.ops.segment_sum(
        contrib, aux.bnd_edge_seg, num_segments=n_boundary + 1,
    )[:n_boundary]
    return jax.lax.psum(partials, SHARD_AXIS)


def _global_all(flag: jnp.ndarray) -> jnp.ndarray:
    """AND a per-shard bool across the mesh (a 4-byte collective)."""
    return jax.lax.psum(flag.astype(jnp.int32), SHARD_AXIS) \
        == jax.lax.psum(1, SHARD_AXIS)


def _superstep_local(lgraph, aux, st: ShardedMaxSumState, *,
                     damping: float, damp_vars: bool,
                     damp_factors: bool, stability: float,
                     n_boundary: int,
                     prune=None) -> ShardedMaxSumState:
    """One partitioned MaxSum superstep on one shard's block — the
    exact semantics of ops.maxsum.superstep (Jacobi BSP, damping,
    SAME_COUNT send-suppression), with the variable aggregation split
    into the interior sub-update (_local_sums) plus the halo buffer
    consumed from the PREVIOUS superstep's tail exchange."""
    first = st.cycle == 0
    valids = tuple(
        lgraph.var_valid[b.var_ids] for b in lgraph.buckets
    )

    f2v_cand = maxsum_ops.factor_to_var(lgraph, st.v2f, prune=prune)
    if damp_factors and damping > 0:
        f2v_cand = maxsum_ops._damp(f2v_cand, st.f2v, damping, first)

    # Variable side reads the PREVIOUS cycle's factor messages; the
    # halo slot holds exactly their boundary totals (exchanged at the
    # tail of the previous superstep), so consuming it here is
    # semantics-preserving double buffering, not staleness.
    sums = _combine_halo(_local_sums(lgraph, st.f2v), st.halo, aux)
    beliefs = lgraph.var_costs + sums
    v2f_cand = maxsum_ops.var_to_factor(lgraph, st.f2v, beliefs, sums)
    if damp_vars and damping > 0:
        v2f_cand = maxsum_ops._damp(v2f_cand, st.v2f, damping, first)

    f2v_new, f2v_count = [], []
    v2f_new, v2f_count = [], []
    all_match = jnp.asarray(True)
    for i, valid in enumerate(valids):
        sent, cnt, match = maxsum_ops._send_or_suppress(
            f2v_cand[i], st.f2v[i], st.f2v_count[i],
            stability, valid, first)
        f2v_new.append(sent)
        f2v_count.append(cnt)
        all_match = all_match & jnp.all(match | ~jnp.any(valid, -1))
        sent, cnt, match = maxsum_ops._send_or_suppress(
            v2f_cand[i], st.v2f[i], st.v2f_count[i],
            stability, valid, first)
        v2f_new.append(sent)
        v2f_count.append(cnt)
        all_match = all_match & jnp.all(match | ~jnp.any(valid, -1))

    halo_new = (_exchange_halo(tuple(f2v_new), aux, n_boundary)
                if n_boundary else st.halo)
    stable = _global_all(all_match) & ~first
    return ShardedMaxSumState(
        v2f=tuple(v2f_new),
        f2v=tuple(f2v_new),
        v2f_count=tuple(v2f_count),
        f2v_count=tuple(f2v_count),
        halo=halo_new,
        stable=stable,
        cycle=st.cycle + 1,
    )


def _select_local(lgraph, aux, st, v_loc: int) -> jnp.ndarray:
    """Per-shard value selection over OWNED rows ([V_loc] int32)."""
    sums = _combine_halo(_local_sums(lgraph, st.f2v), st.halo, aux)
    beliefs = lgraph.var_costs + sums
    masked = jnp.where(lgraph.var_valid, beliefs, jnp.inf)
    return jnp.argmin(masked[:v_loc], axis=1).astype(jnp.int32)


def _exchange_values(values_owned, aux, v_loc: int, n_halo: int,
                     n_boundary: int) -> jnp.ndarray:
    """Owner-scatter + psum of the selected values of boundary vars,
    gathered back into this shard's halo slots ([H] int32) — the
    value-plane halo exchange cost traces need."""
    if n_boundary == 0:
        return jnp.zeros((n_halo,), jnp.int32)
    vals_pad = jnp.concatenate(
        [values_owned,
         jnp.zeros((n_halo + 1,), jnp.int32)])
    owner_vals = jnp.where(
        aux.bnd_owner, vals_pad[aux.bnd_local], 0)
    bnd_vals = jax.lax.psum(owner_vals, SHARD_AXIS)      # [B]
    bnd_ext = jnp.concatenate(
        [bnd_vals, jnp.zeros((1,), jnp.int32)])
    return bnd_ext[aux.halo_bnd]


class ShardOps:
    """ops.maxsum-compatible kernel namespace for a partitioned graph
    — MaxSumEngine's ``_ops`` seam lets the whole segmented/
    checkpointed/recovery runner machinery drive these unchanged.
    Holds the mesh and the global variable count (the only statics a
    ShardedGraph's array shapes cannot express)."""

    def __init__(self, mesh: Mesh, n_vars: int):
        self.mesh = mesh
        self.n_vars = n_vars

    # -- spec plumbing -------------------------------------------------- #

    def _graph_specs(self, graph: ShardedGraph):
        shard = P(SHARD_AXIS)
        return graph._replace(
            var_costs=shard, var_valid=shard,
            buckets=tuple(ShardBucket(shard, shard)
                          for _ in graph.buckets),
            local_global=shard, bnd_local=shard, bnd_present=shard,
            bnd_owner=shard, halo_bnd=shard,
            bnd_edge_idx=shard, bnd_edge_seg=shard,
        )

    def _state_specs(self, graph: ShardedGraph):
        shard = P(SHARD_AXIS)
        nb = len(graph.buckets)
        return ShardedMaxSumState(
            v2f=(shard,) * nb, f2v=(shard,) * nb,
            v2f_count=(shard,) * nb, f2v_count=(shard,) * nb,
            halo=P(), stable=P(), cycle=P(),
        )

    # -- state construction --------------------------------------------- #

    def _zeros_state(self, graph: ShardedGraph) -> ShardedMaxSumState:
        d = graph.dmax
        dtype = graph.var_costs.dtype
        msgs = tuple(
            jnp.zeros(b.var_ids.shape + (d,), dtype=dtype)
            for b in graph.buckets
        )
        counts = tuple(
            jnp.zeros(b.var_ids.shape, dtype=jnp.int8)
            for b in graph.buckets
        )
        # De-aliased per field (donation rejects duplicated buffers),
        # mirroring ops.maxsum.init_state.
        def zeros():
            return tuple(jnp.zeros_like(m) for m in msgs)

        def czeros():
            return tuple(jnp.zeros_like(c) for c in counts)

        return ShardedMaxSumState(
            v2f=zeros(), f2v=zeros(),
            v2f_count=czeros(), f2v_count=czeros(),
            halo=jnp.zeros((graph.n_boundary, d), dtype=dtype),
            stable=jnp.asarray(False),
            cycle=jnp.asarray(0, dtype=jnp.int32),
        )

    def init_state(self, graph: ShardedGraph) -> ShardedMaxSumState:
        """Placed initial state — also the checkpoint template
        (resilience/checkpoint.py restores snapshots into this exact
        pytree: shapes, dtypes AND shardings)."""
        state = self._zeros_state(graph)
        shard = NamedSharding(self.mesh, P(SHARD_AXIS))
        rep = NamedSharding(self.mesh, P())
        put = lambda t: tuple(  # noqa: E731
            jax.device_put(m, shard) for m in t)
        return state._replace(
            v2f=put(state.v2f), f2v=put(state.f2v),
            v2f_count=put(state.v2f_count),
            f2v_count=put(state.f2v_count),
            halo=jax.device_put(state.halo, rep),
            stable=jax.device_put(state.stable, rep),
            cycle=jax.device_put(state.cycle, rep),
        )

    # -- solve entry points (maxsum_ops signatures) ---------------------- #

    def run_maxsum_from(self, graph: ShardedGraph,
                        state: ShardedMaxSumState,
                        extra_cycles: int, *,
                        damping: float = 0.5, damp_vars: bool = True,
                        damp_factors: bool = True,
                        stability: float = 0.1,
                        stop_on_convergence: bool = True,
                        prune: bool = False):
        """Up to ``extra_cycles`` more partitioned supersteps from an
        existing state; returns ``(state, values)`` with ``values``
        reassembled to the GLOBAL [V] order (identical interface to
        ops.maxsum.run_maxsum_from, so the segmented runner, the
        checkpoint format and the recovery ladder work unchanged).

        ``prune=True`` applies branch-and-bound pruning to each
        shard's local factor reductions with the same dense/compacted
        phase alternation as the edge-major kernel; the phase
        predicate is the GLOBAL AND of the per-shard fit tests (one
        4-byte collective per loop-condition evaluation), so every
        shard always runs the same kernel and the collectives inside
        the superstep stay aligned."""
        n_bnd = graph.n_boundary
        v_loc = graph.v_loc

        def local_run(g, st):
            lgraph, aux = _unblock_graph(g)
            st = _unblock_state(st)
            step = partial(
                _superstep_local, lgraph, aux,
                damping=damping, damp_vars=damp_vars,
                damp_factors=damp_factors, stability=stability,
                n_boundary=n_bnd,
            )
            limit = st.cycle + extra_cycles
            if stop_on_convergence:
                done = lambda s: (s.cycle >= limit) | s.stable  # noqa: E731
            else:
                done = lambda s: s.cycle >= limit  # noqa: E731
            pt = maxsum_ops.prune_tables(lgraph) if prune else None
            if pt is not None and all(t is None for t in pt):
                pt = None
            if pt is None:
                st = jax.lax.while_loop(
                    lambda s: ~done(s), lambda s: step(st=s), st)
            else:
                step_fast = partial(
                    _superstep_local, lgraph, aux,
                    damping=damping, damp_vars=damp_vars,
                    damp_factors=damp_factors, stability=stability,
                    n_boundary=n_bnd, prune=pt,
                )

                def fits(s):
                    return _global_all(
                        maxsum_ops.prune_fits(s.v2f, pt))

                def phases(s):
                    s = jax.lax.while_loop(
                        lambda s: ~done(s) & ~fits(s),
                        lambda s: step(st=s), s)
                    s = jax.lax.while_loop(
                        lambda s: ~done(s) & fits(s),
                        lambda s: step_fast(st=s), s)
                    return s

                st = jax.lax.while_loop(
                    lambda s: ~done(s), phases, st)
            values = _select_local(lgraph, aux, st, v_loc)
            return _reblock_state(st), values[None]

        mapped = jax.shard_map(
            local_run, mesh=self.mesh,
            in_specs=(self._graph_specs(graph),
                      self._state_specs(graph)),
            out_specs=(self._state_specs(graph), P(SHARD_AXIS)),
            check_vma=False,
        )
        state, values_sh = mapped(graph, state)
        return state, self._assemble_values(graph, values_sh)

    def run_maxsum(self, graph: ShardedGraph, max_cycles: int, *,
                   damping: float = 0.5, damp_vars: bool = True,
                   damp_factors: bool = True, stability: float = 0.1,
                   stop_on_convergence: bool = True,
                   prune: bool = False):
        return self.run_maxsum_from(
            graph, self._zeros_state(graph), max_cycles,
            damping=damping, damp_vars=damp_vars,
            damp_factors=damp_factors, stability=stability,
            stop_on_convergence=stop_on_convergence, prune=prune,
        )

    def run_maxsum_trace(self, graph: ShardedGraph, max_cycles: int, *,
                         damping: float = 0.5, damp_vars: bool = True,
                         damp_factors: bool = True,
                         stability: float = 0.1,
                         var_base_costs=None,
                         stop_on_convergence: bool = True,
                         prune: bool = False):
        """Partitioned run recording the global assignment cost after
        every cycle: per-shard constraint cost over local factors +
        owned-variable base costs, psum'd — each factor and each
        variable is owned by exactly one shard, so the psum is a
        partition of the global sum (no double counting).  Halo
        variables' selected values ride a [B]-int exchange.

        Early exit (``stop_on_convergence``) mirrors the edge-major
        trace: a while_loop writes each cycle's cost into a carried
        buffer and the tail holds the final value; every shard leaves
        the loop on the same (globally-reduced) verdict.  ``prune`` is
        accepted for ops-interface parity but runs dense: pruning
        never changes values, and a trace is a value record."""
        n_bnd = graph.n_boundary
        v_loc = graph.v_loc
        n_halo = graph.local_global.shape[-1] - v_loc
        d = graph.dmax
        if var_base_costs is not None:
            base_ext = jnp.concatenate(
                [jnp.asarray(var_base_costs),
                 jnp.zeros((1, d), jnp.asarray(var_base_costs).dtype)],
                axis=0)
            base_local = base_ext[graph.local_global[:, :v_loc]]
        else:
            base_local = jnp.zeros(
                (graph.n_shards, v_loc, d), graph.var_costs.dtype)

        def local_run(g, base):
            lgraph, aux = _unblock_graph(g)
            base = base[0]
            step_fn = partial(
                _superstep_local, lgraph, aux,
                damping=damping, damp_vars=damp_vars,
                damp_factors=damp_factors, stability=stability,
                n_boundary=n_bnd,
            )

            def cost_of(st):
                values = _select_local(lgraph, aux, st, v_loc)
                halo_vals = _exchange_values(
                    values, aux, v_loc, n_halo, n_bnd)
                vals_full = jnp.concatenate([values, halo_vals])
                cost = maxsum_ops.assignment_constraint_cost(
                    lgraph, vals_full)
                if var_base_costs is not None:
                    cost = cost + jnp.sum(jnp.take_along_axis(
                        base, values[:, None], axis=1))
                return jax.lax.psum(cost, SHARD_AXIS), values

            def step(carry):
                st, costs, last = carry
                st = step_fn(st=st)
                cost, _ = cost_of(st)
                costs = jax.lax.dynamic_update_slice(
                    costs, cost[None], (st.cycle - 1,))
                return st, costs, cost

            def done(carry):
                st = carry[0]
                out = st.cycle >= max_cycles
                if stop_on_convergence:
                    # st.stable is already the global AND
                    # (_global_all inside the superstep), so every
                    # shard exits together.
                    out = out | st.stable
                return out

            zero = jnp.asarray(0.0, lgraph.var_costs.dtype)
            st, costs, last = jax.lax.while_loop(
                lambda c: ~done(c), step,
                (self._zeros_state_local(lgraph, n_bnd),
                 jnp.zeros((max_cycles,), lgraph.var_costs.dtype),
                 zero))
            costs = jnp.where(
                jnp.arange(max_cycles) >= st.cycle, last, costs)
            _, values = cost_of(st)
            return _reblock_state(st), values[None], costs

        mapped = jax.shard_map(
            local_run, mesh=self.mesh,
            in_specs=(self._graph_specs(graph), P(SHARD_AXIS)),
            out_specs=(self._state_specs(graph), P(SHARD_AXIS), P()),
            check_vma=False,
        )
        state, values_sh, costs = mapped(graph, base_local)
        return state, self._assemble_values(graph, values_sh), costs

    def _zeros_state_local(self, lgraph, n_bnd: int
                           ) -> ShardedMaxSumState:
        d = lgraph.var_costs.shape[1]
        dtype = lgraph.var_costs.dtype

        def zeros():
            return tuple(
                jnp.zeros(b.var_ids.shape + (d,), dtype=dtype)
                for b in lgraph.buckets)

        def counts():
            return tuple(
                jnp.zeros(b.var_ids.shape, dtype=jnp.int8)
                for b in lgraph.buckets)

        return ShardedMaxSumState(
            v2f=zeros(), f2v=zeros(),
            v2f_count=counts(), f2v_count=counts(),
            halo=jnp.zeros((n_bnd, d), dtype=dtype),
            stable=jnp.asarray(False),
            cycle=jnp.asarray(0, dtype=jnp.int32),
        )

    def recompute_halo(self, graph: ShardedGraph, f2v) -> jnp.ndarray:
        """The ``[B, D]`` boundary buffer for an EXISTING set of f2v
        messages: the same per-shard boundary partial sums + psum the
        superstep tail issues (``_exchange_halo``), run once outside
        the loop.  Shard-loss recovery uses this to rebuild the halo
        slot after remapping a snapshot onto a new partition — the
        double buffer must hold exactly the boundary totals of the
        snapshot's f2v messages, computed with the NEW layout's
        reduction order, or the first post-recovery superstep would
        read garbage."""
        n_bnd = graph.n_boundary
        d = graph.dmax
        if n_bnd == 0:
            return jax.device_put(
                jnp.zeros((0, d), graph.var_costs.dtype),
                NamedSharding(self.mesh, P()))
        nb = len(graph.buckets)

        def local(g, msgs):
            _, aux = _unblock_graph(g)
            return _exchange_halo(
                tuple(m[0] for m in msgs), aux, n_bnd)

        return jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._graph_specs(graph),
                      (P(SHARD_AXIS),) * nb),
            out_specs=P(),
            check_vma=False,
        )(graph, tuple(f2v))

    def assignment_constraint_cost(self, graph: ShardedGraph,
                                   values: jnp.ndarray) -> jnp.ndarray:
        """Global constraint cost of a GLOBAL [V] assignment on the
        partitioned graph (the segment-boundary guard's verdict
        input): values are scattered to each shard's local order and
        the per-shard factor costs psum'd."""
        ext = jnp.concatenate(
            [values.astype(jnp.int32),
             jnp.zeros((1,), jnp.int32)])
        vals_local = ext[graph.local_global]      # [S, L-1]

        def local_cost(g, vl):
            lgraph, _ = _unblock_graph(g)
            return jax.lax.psum(
                maxsum_ops.assignment_constraint_cost(lgraph, vl[0]),
                SHARD_AXIS)

        return jax.shard_map(
            local_cost, mesh=self.mesh,
            in_specs=(self._graph_specs(graph), P(SHARD_AXIS)),
            out_specs=P(),
            check_vma=False,
        )(graph, vals_local)

    def _assemble_values(self, graph: ShardedGraph, values_sh
                         ) -> jnp.ndarray:
        """[S, V_loc] per-shard owned values → global [V] order.
        Padding owned slots scatter to the sentinel index and are
        dropped by the final slice."""
        v_loc = graph.v_loc
        owned_global = graph.local_global[:, :v_loc]
        ext = jnp.zeros((self.n_vars + 1,), jnp.int32)
        return ext.at[owned_global.reshape(-1)].set(
            values_sh.reshape(-1))[: self.n_vars]


# ----------------------- shard-loss state remap ---------------------- #


def _factor_row_maps(source_graph: CompiledFactorGraph, part):
    """Per bucket: the positions (in real-factor row order) owned by
    each shard — the inverse of build_partitioned_graph's per-shard
    row packing (``rows[fs == s]`` in order)."""
    n_vars = source_graph.n_vars
    maps = []
    for b, fs in zip(source_graph.buckets, part.factor_shard):
        ids = np.asarray(b.var_ids)
        rows = real_factor_rows(ids, n_vars)
        maps.append((rows,
                     [np.nonzero(fs == s)[0]
                      for s in range(part.n_shards)]))
    return maps


def remap_partitioned_state(source_graph: CompiledFactorGraph,
                            old_part, new_part,
                            state: ShardedMaxSumState,
                            new_graph: ShardedGraph,
                            new_ops: "ShardOps"
                            ) -> ShardedMaxSumState:
    """Map a checkpointed/validated :class:`ShardedMaxSumState` from
    one partition's blocked layout onto another's — the shard-loss
    recovery step ("remap the global state onto the new layout").

    Messages and SAME_COUNT counters live per (factor, scope slot):
    the remap gathers each bucket's per-shard blocks back to global
    real-factor row order (host numpy — the recovery path runs once
    per device loss, not per superstep) and re-packs them under the
    new factor→shard assignment; padding rows in the new layout start
    zeroed, exactly like a fresh ``init_state`` (they scatter only
    into the sentinel slot, which nothing reads).  The halo double
    buffer is NOT remapped — the new partition has a different
    boundary set — but recomputed on device from the remapped f2v
    messages (:meth:`ShardOps.recompute_halo`), so the first
    post-recovery superstep consumes exactly what the tail exchange
    of the snapshot cycle would have produced under the new layout.
    ``stable``/``cycle`` carry over (replicated scalars are
    layout-free)."""
    state_host = jax.device_get(state)
    old_maps = _factor_row_maps(source_graph, old_part)
    new_maps = _factor_row_maps(source_graph, new_part)
    new_S = new_part.n_shards

    def regather(blocked, bucket_i):
        """[S_old, Fmax_old, ...] blocked → [F_real, ...] global."""
        blocked = np.asarray(blocked)
        rows, per_shard = old_maps[bucket_i]
        out = np.zeros((rows.shape[0],) + blocked.shape[2:],
                       blocked.dtype)
        for s, sel in enumerate(per_shard):
            out[sel] = blocked[s, :sel.shape[0]]
        return out

    def reblock(global_arr, bucket_i, f_max):
        """[F_real, ...] global → [S_new, f_max, ...] blocked."""
        _, per_shard = new_maps[bucket_i]
        out = np.zeros((new_S, f_max) + global_arr.shape[1:],
                       global_arr.dtype)
        for s, sel in enumerate(per_shard):
            out[s, :sel.shape[0]] = global_arr[sel]
        return out

    def remap_field(msgs):
        remapped = []
        for i, blocked in enumerate(msgs):
            f_max = new_graph.buckets[i].var_ids.shape[1]
            remapped.append(
                reblock(regather(blocked, i), i, f_max))
        return tuple(remapped)

    shard = NamedSharding(new_ops.mesh, P(SHARD_AXIS))
    rep = NamedSharding(new_ops.mesh, P())
    put = lambda t: tuple(  # noqa: E731
        jax.device_put(m, shard) for m in t)
    placed = ShardedMaxSumState(
        v2f=put(remap_field(state_host.v2f)),
        f2v=put(remap_field(state_host.f2v)),
        v2f_count=put(remap_field(state_host.v2f_count)),
        f2v_count=put(remap_field(state_host.f2v_count)),
        halo=jax.device_put(
            np.zeros((new_graph.n_boundary, new_graph.dmax),
                     np.asarray(state_host.halo).dtype), rep),
        stable=jax.device_put(np.asarray(state_host.stable), rep),
        cycle=jax.device_put(np.asarray(state_host.cycle), rep),
    )
    halo = new_ops.recompute_halo(new_graph, placed.f2v)
    return placed._replace(halo=halo)
