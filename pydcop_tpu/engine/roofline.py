"""Achieved-FLOPs / HBM-traffic accounting for the device engine.

The VERDICT-mandated honesty layer for benchmark claims: given a
compiled graph we count, from the bucket shapes alone, the arithmetic
and memory traffic one MaxSum superstep performs (ops/maxsum.py
superstep), so bench results can report achieved FLOP/s, an MFU against
the chip's matmul peak, and — the meaningful roofline for this op mix —
HBM bandwidth utilization.

The counts are *models*, not profiler measurements: they assume XLA
fuses elementwise chains (each logical array is read/written once per
use) and count one FLOP per add/multiply/compare.  MaxSum's op mix is
min-plus gather/scatter on tiny minor dimensions, so it cannot use the
MXU at all; the MFU-vs-matmul-peak number is included because the
benchmark contract asks for it, and it is honestly tiny.

`hbm_util` is the meaningful efficiency number, but ONLY when the
problem is big enough that its working set actually streams from HBM:
when `working_set_bytes` fits comfortably in on-chip VMEM (most
problems below ~1M variables, including the 10k north-star bench), XLA
keeps all state resident across supersteps, actual HBM traffic is near
zero, and the byte model is a ceiling rather than a measurement —
`hbm_util` is then None with `vmem_resident: True`.  The HBM-bound
regime waits for a cell of that size (ROADMAP.md Queue 2 B).

Peak numbers come from public chip specs, keyed on
`jax.devices()[0].device_kind` so each TPU generation gets its own
roofline; unknown kinds (and CPU backends) get `None` peaks and the
bench reports achieved numbers without a utilization claim.
"""

from typing import Dict, Optional, Tuple

from pydcop_tpu.engine.compile import CompiledFactorGraph

V5E_PEAK_FLOPS_BF16 = 197e12
V5E_HBM_BYTES_PER_S = 819e9

# device_kind -> (peak bf16 matmul FLOP/s, HBM bytes/s), public specs.
TPU_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v4": (275e12, 1.2e12),
    "TPU v5 lite": (V5E_PEAK_FLOPS_BF16, V5E_HBM_BYTES_PER_S),
    "TPU v5e": (V5E_PEAK_FLOPS_BF16, V5E_HBM_BYTES_PER_S),
    "TPU v5": (459e12, 2.765e12),
    "TPU v5p": (459e12, 2.765e12),
    "TPU v6 lite": (918e12, 1.64e12),
    "TPU v6e": (918e12, 1.64e12),
}

# On-chip vector memory (128 MiB on every generation in TPU_PEAKS;
# make this a per-kind table if that ever diverges).  When the solve's
# whole working set fits here, the compiler keeps state resident across
# loop iterations and steady-state HBM traffic is ~0 — the byte model
# below then describes a traffic CEILING, not actual traffic, so no
# hbm_util claim is made.
TPU_VMEM_BYTES = 128 << 20


def maxsum_superstep_flops(graph: CompiledFactorGraph) -> int:
    """Arithmetic ops in one superstep (adds + mins + compares).

    Derivation per bucket of F factors, arity a, padded domain D
    (ops/maxsum.py superstep):

    - factor→var: broadcast-add a messages into the [F, D^a] table
      (a·F·D^a), then per position a min-reduction over the table
      (a·F·D^a) and a subtract (a·F·D).
    - damping on both sides: damped = d·old + (1-d)·new → 3 ops per
      element over two [F, a, D] arrays.
    - belief segment-sum: one add per message element (F·a·D) plus the
      var-cost add over [V, D].
    - var→factor: two subtracts, masked mean (sum + divide ≈ 2), and
      the normalization subtract → ≈5 ops per [F, a, D] element.
    - convergence test: |Δ|, |Σ|, two compares on both message arrays
      → ≈8 ops per element, twice.
    """
    v_plus_1, d = graph.var_costs.shape
    total = v_plus_1 * d  # belief var-cost add
    for b in graph.buckets:
        f, a = b.var_ids.shape
        table = b.costs.size  # F * D^a
        total += 2 * a * table          # broadcast adds + min reductions
        per_msg = f * a * d
        total += per_msg * (1 + 6 + 1 + 5 + 16)  # sub, damp, seg, v2f, conv
    return int(total)


def maxsum_superstep_bytes(graph: CompiledFactorGraph) -> int:
    """HBM traffic (bytes) one fused superstep must move at minimum:
    read every factor cost table once, read old + write new messages on
    both sides (4 × [F, a, D]), read/write the [V, D] belief/sum
    tables a handful of times.

    With the ell aggregation the variable-side sum reads messages
    through the padded [V+1, K] edge lists instead of one scatter
    pass: V·K message rows (padding waste included — the kernel's
    clipped dummy reads are real traffic) plus the index array
    itself, replacing one of the six message passes."""
    itemsize = graph.var_costs.dtype.itemsize
    d = graph.var_costs.shape[1]
    total = 4 * graph.var_costs.size * itemsize
    msg_passes = 6
    if graph.agg_ell is not None:
        total += graph.agg_ell.size * 4           # edge-list reads
        total += graph.agg_ell.size * d * itemsize  # padded gather
        msg_passes = 5                            # replaces one pass
    for b in graph.buckets:
        f, a = b.var_ids.shape
        total += b.costs.size * itemsize          # cost tables (read)
        total += msg_passes * f * a * d * itemsize  # v2f/f2v old+new
        total += b.var_ids.size * 4               # gather indices
    return int(total)


def working_set_bytes(graph: CompiledFactorGraph) -> int:
    """Persistent solve state: graph tensors + both message arrays and
    their suppression counters (ops/maxsum.MaxSumState)."""
    total = graph.var_costs.size * graph.var_costs.dtype.itemsize
    total += graph.var_valid.size  # bool
    if graph.agg_ell is not None:
        total += graph.agg_ell.size * 4
    d = graph.var_costs.shape[1]
    for b in graph.buckets:
        f, a = b.var_ids.shape
        total += b.costs.size * b.costs.dtype.itemsize
        total += b.var_ids.size * 4
        # v2f + f2v messages carry the var_costs dtype (ops init_state)
        total += 2 * f * a * d * graph.var_costs.dtype.itemsize
        total += 2 * f * a * 1       # send-suppression counters (int8)
    return int(total)


def roofline_report(graph: CompiledFactorGraph, cycles_per_s: float,
                    platform: str,
                    device_kind: Optional[str] = None,
                    measured: Optional[Dict[str, float]] = None,
                    ) -> Dict[str, Optional[float]]:
    """Achieved FLOP/s + utilizations for a measured superstep rate.

    ``measured`` replaces the analytical per-cycle counts with
    XLA-reported ones (observability/profiler.py): a dict with
    ``flops_per_cycle`` and/or ``bytes_per_cycle`` — each present key
    overrides its model value and the report carries
    ``cost_source='xla'``; with ``measured=None`` (or an empty dict —
    the backend-returned-nothing case) the hand model stands and
    ``cost_source='model'``.  Utilization/residency logic is identical
    either way, so a measured report stays comparable run-over-run
    with modeled ones.

    Utilization claims (mfu/hbm_util) are made only when the concrete
    chip is recognized in TPU_PEAKS; `platform == "tpu"` with an
    unknown `device_kind` reports achieved numbers with `None`
    utilizations rather than assuming some generation's peaks.

    When the whole working set fits comfortably in on-chip VMEM
    (< half TPU_VMEM_BYTES, leaving room for fusion transients), the
    compiler keeps state resident across supersteps and actual HBM
    traffic is near zero; the byte model is then only a ceiling, so
    ``hbm_util`` is None and ``vmem_resident`` is True — claiming 400%
    "HBM utilization" on a VMEM-resident problem would be nonsense.
    """
    from pydcop_tpu.ops.maxsum_lane import LaneGraph

    if isinstance(graph, LaneGraph):
        # The counters below unpack edge-major shapes positionally; a
        # lane-major graph has every axis transposed and would count
        # garbage silently (a=F in the table term, ~1e6x off).
        raise TypeError(
            "roofline_report requires the edge-major "
            "CompiledFactorGraph; convert before accounting "
            "(ops/maxsum_lane.LaneGraph shapes are transposed)")
    model_flops = maxsum_superstep_flops(graph)
    model_bytes = maxsum_superstep_bytes(graph)
    flops, bytes_moved = model_flops, model_bytes
    cost_source = "model"
    if measured:
        if measured.get("flops_per_cycle"):
            flops = float(measured["flops_per_cycle"])
            cost_source = "xla"
        if measured.get("bytes_per_cycle"):
            bytes_moved = float(measured["bytes_per_cycle"])
            cost_source = "xla"
    ws = working_set_bytes(graph)
    achieved_flops = flops * cycles_per_s
    achieved_bw = bytes_moved * cycles_per_s
    peak_flops: Optional[float] = None
    peak_bw: Optional[float] = None
    vmem_resident: Optional[bool] = None
    if platform == "tpu":
        # VMEM capacity is kind-independent (see TPU_VMEM_BYTES), so
        # residency — and the achieved_gbps suppression it implies —
        # applies to ANY TPU; only the peak-based utilization claims
        # need a recognized generation.
        vmem_resident = ws < TPU_VMEM_BYTES // 2
        if device_kind in TPU_PEAKS:
            peak_flops, peak_bw = TPU_PEAKS[device_kind]
    out = {
        "cost_source": cost_source,
        "flops_per_cycle": float(flops),
        "bytes_per_cycle": float(bytes_moved),
        "working_set_bytes": float(ws),
        "vmem_resident": vmem_resident,
        "achieved_gflops": round(achieved_flops / 1e9, 3),
        "achieved_gbps": (
            None if vmem_resident else round(achieved_bw / 1e9, 3)
        ),
        # Not rounded: on small graphs these are ~1e-9 and rounding
        # would collapse an honest tiny number to a dishonest zero.
        "mfu": (
            achieved_flops / peak_flops if peak_flops else None
        ),
        "hbm_util": (
            achieved_bw / peak_bw
            if peak_bw and vmem_resident is False else None
        ),
        # Physics gate: modeled traffic x measured rate above the
        # chip's HBM peak means the RATE is wrong (a window that
        # closed before the device finished measures the enqueue).
        # The flag makes such a line self-refuting instead of
        # impressive.
        "hbm_util_exceeds_peak": (
            achieved_bw > peak_bw
            if peak_bw and vmem_resident is False else None
        ),
    }
    if cost_source == "xla":
        # Keep the hand model alongside the measurement: the delta
        # between them is itself a finding (a fused chain the model
        # double-counts, or traffic XLA materializes that the model
        # assumed fused away).
        out["model_flops_per_cycle"] = float(model_flops)
        out["model_bytes_per_cycle"] = float(model_bytes)
    return out
