"""MaxSum: synchronous belief-propagation on the factor graph.

Reference parity: pydcop/algorithms/maxsum.py — the north-star hot loop.
Parameters (:212-220): damping 0.5, damping_nodes both, stability 0.1,
noise 0.01, start_messages leafs.  Message semantics are implemented in
pydcop_tpu.ops.maxsum (batched) and, for agent mode, in
pydcop_tpu.infrastructure computations built from `build_computation`.

Device-path note: the batched BSP engine fires *all* factors and
variables each cycle, which corresponds to ``start_messages=all``
semantics; `start_messages` only changes the transient, not the fixed
point, and is accepted for compatibility.  Send-suppression after
SAME_COUNT identical messages (reference :106) is a wire-traffic
optimization with no effect on message *content*; on device, messages
are array rows and the optimization is moot.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'maxsum', max_cycles=50)
    >>> round(res['cost'], 3)
    0.0
"""

import time
from functools import partial
from typing import Optional

from pydcop_tpu.algorithms import AlgoParameterDef, AlgorithmDef
from pydcop_tpu.computations_graph import factor_graph as fg
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import compile_dcop, validated_aggregation
from pydcop_tpu.engine.runner import DeviceRunResult, MaxSumEngine
from pydcop_tpu.observability.trace import NOOP_SPAN, tracer
from pydcop_tpu.ops import maxsum as maxsum_ops

GRAPH_TYPE = "factor_graph"

# Partitioned sharding (api.solve(shards=N)): this module builds the
# ShardedMaxSumEngine; amaxsum and maxsum_dynamic delegate their
# device path here and re-declare the flag.
SUPPORTS_SHARDS = True

HEADER_SIZE = 0
UNIT_SIZE = 1
# Messages considered identical after this many resends (agent mode).
SAME_COUNT = 4
STABILITY_COEFF = 0.1

algo_params = [
    AlgoParameterDef("damping", "float", None, 0.5),
    AlgoParameterDef(
        "damping_nodes", "str", ["vars", "factors", "both", "none"], "both"
    ),
    AlgoParameterDef("stability", "float", None, STABILITY_COEFF),
    AlgoParameterDef("noise", "float", None, 0.01),
    AlgoParameterDef(
        "start_messages", "str", ["leafs", "leafs_vars", "all"], "all"
    ),
    # Device-path extension beyond the reference: decimation
    # (arXiv:1706.02209) — message passing alternating with clamping
    # the most confident variables at segment boundaries, the clamped
    # problem warm-starting from the surviving messages
    # (engine/runner.DecimationPlan).  0 disables (reference
    # behavior); > 0 enables with that fraction (in %) of variables
    # fixed per round.
    AlgoParameterDef("decimation", "int", None, 0),
    # Margin-threshold decimation: clamp ONLY variables whose belief
    # margin (best vs second-best value gap) exceeds this — converged
    # parts of the graph stop paying for message updates while
    # undecided regions keep iterating.  0 disables; combine with
    # decimation:N to cap the per-round clamp fraction.
    AlgoParameterDef("decimation_margin", "float", None, 0.0),
    # Branch-and-bound message pruning (arXiv:1906.06863;
    # ops/maxsum.prune_tables): per-edge running bounds mask dominated
    # hypercube rows out of the binary factor->variable
    # min-aggregation and a compacted reduction does ~D/K of the dense
    # work once the survivors fit the static budget.  Results are
    # IDENTICAL to the unpruned kernel (bit-identical on integer
    # tables: tests/unit/test_workreduction_battery.py); meant for
    # large domains (D >= ~32), edge layout only.  Not yet timed on
    # the chip: ROADMAP.md Queue 3 "Branch-and-bound pruning".
    AlgoParameterDef("prune", "bool", None, False),
    # Variable-aggregation strategy for the superstep (device path;
    # see engine/compile.build_aggregation_arrays).  "scatter" is the
    # parity default; "sorted" and "ell" (padded dense-gather edge
    # lists — no scatter at all) are the HBM-regime alternatives,
    # not yet decided on the chip (ROADMAP.md Queue 3 "Four
    # aggregations").  The fourth strategy of
    # engine/compile.AGGREGATIONS ("boundary", prefix-sum + boundary
    # differences) is
    # experiment-only: f32 prefix sums over millions of edges cancel
    # catastrophically at exactly the scale it targets, and TPUs have
    # no f64 to accumulate in — so it is not offered for solves.
    # Sharded runs always use scatter (shard_graph drops the sort
    # arrays).  "auto" micro-times the strategies on the compiled
    # graph and picks the measured winner (engine/autotune.py;
    # decision + timings land in result metrics, and a JSON shape
    # cache skips the measurement on re-solves).
    AlgoParameterDef(
        "aggregation", "str",
        ["scatter", "sorted", "ell", "auto"], "scatter"
    ),
    # Message-array layout (device path).  "edge" keeps messages as
    # [F, arity, D] (domain minor); "lane" transposes to [D, arity, F]
    # — factors on the TPU lane axis (ops/maxsum_lane.py) — so no
    # buffer is padded from D to the 128 lanes.  Unset, the code
    # selects (``select_layout``): lane for a plain single-device
    # scatter solve, edge wherever a feature indexes the edge-major
    # arrays.  A value given here is honoured as given; "lane" is
    # single-device and scatter-aggregation only.
    AlgoParameterDef("layout", "str", ["edge", "lane"], None),
]


def computation_memory(node) -> float:
    """Footprint: sum of incident message sizes (reference maxsum.py
    :127-171)."""
    return fg.computation_memory(node)


def communication_load(src, target: str) -> float:
    """One cost table per message (reference maxsum.py:174-209)."""
    return fg.communication_load(src, target)


def build_computation(comp_def):
    """Agent-mode computation factory."""
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("maxsum", comp_def)


def _replay_auto_choice(dcop: DCOP):
    """Pre-compile lookup of a persisted autotune decision.

    The shape key is computed from the DCOP directly (variable/domain
    counts, per-arity factor counts, max scope degree — identical to
    the compiled graph's key at pad_to=1, the only case 'auto'
    measures).  On a hit the winner is returned as the aggregation to
    COMPILE WITH, so the layout comes from engine/compile's structure
    cache; on a miss the caller compiles scatter and measures.

    Returns ``(aggregation, agg_info_or_None)``.
    """
    from pydcop_tpu.engine.autotune import cached_choice, dcop_shape_key

    key = dcop_shape_key(dcop)
    choice = cached_choice(key)
    if choice is None:
        return "scatter", None
    return choice, {
        "aggregation": choice,
        "aggregation_source": "cache",
        "aggregation_key": key,
    }


def decimation_plan_from_params(params: dict):
    """Resolve the ``decimation`` / ``decimation_margin`` params into
    an :class:`~pydcop_tpu.engine.runner.DecimationPlan` (None = off).

    ``decimation:N`` alone is the classic schedule — top-N% of free
    variables by belief margin clamped per round until everything is
    fixed.  ``decimation_margin:M`` switches to threshold mode — only
    variables whose margin exceeds M clamp (capped at N% per round
    when both are given; uncapped otherwise), and nothing is forced,
    so an undecided graph keeps message passing untouched."""
    n = int(params.get("decimation", 0) or 0)
    margin = float(params.get("decimation_margin", 0.0) or 0.0)
    if n <= 0 and margin <= 0:
        return None
    from pydcop_tpu.engine.runner import DecimationPlan

    return DecimationPlan(
        margin=margin,
        frac_per_round=(n / 100.0) if n > 0 else 1.0,
        force_progress=margin <= 0,
    )


def select_layout(params: dict, mesh=None,
                  n_devices: Optional[int] = None,
                  shards: Optional[int] = None,
                  whole_solve: bool = False):
    """``(layout, source)`` of a MaxSum solve: the ``layout`` param
    as given (source ``"param"``), else what the code selects
    (``"selected"``) from what it is handed — ``"lane"`` when the
    solve is the plain whole-solve program of one device with the
    scatter aggregation, ``"edge"`` wherever something reads the
    edge-major arrays: a mesh or a partition (``shard_graph`` and
    ``ShardOps`` shard edge-major rows), the sorted / ell / auto
    aggregations, ``prune``, decimation, the Pallas kernel, and the
    segmented loop (``whole_solve=False``: its checkpoints restore
    into an edge-major state and ``EngineProbe`` reads the edge
    graph)."""
    asked = params.get("layout")
    if asked is not None:
        return asked, "param"
    lane = (
        whole_solve
        and (mesh is None or mesh.size <= 1)
        and (n_devices or 1) <= 1
        and (shards or 1) <= 1
        and params.get("aggregation", "scatter") == "scatter"
        and not params.get("prune", False)
        and decimation_plan_from_params(params) is None
        and not maxsum_ops.pallas_requested()
    )
    return ("lane" if lane else "edge"), "selected"


def build_engine(dcop: DCOP, params: dict, mesh=None,
                 n_devices: Optional[int] = None,
                 shards: Optional[int] = None,
                 whole_solve: bool = False) -> MaxSumEngine:
    """:func:`_build_engine`, under a ``build_engine`` span while a
    file session traces (parent of ``compile_graph`` and
    ``engine_place``; args ``layout``, ``layout_source``: what ran)."""
    with (tracer.span("build_engine", "engine")
          if tracer.enabled else NOOP_SPAN) as span:
        engine = _build_engine(dcop, params, mesh=mesh,
                               n_devices=n_devices, shards=shards,
                               whole_solve=whole_solve)
        span.args["layout"] = engine.extra_metrics["layout"]
        span.args["layout_source"] = engine.extra_metrics[
            "layout_source"]
        return engine


def _build_engine(dcop: DCOP, params: dict, mesh=None,
                  n_devices: Optional[int] = None,
                  shards: Optional[int] = None,
                  whole_solve: bool = False) -> MaxSumEngine:
    """Compile + construct the engine from validated algo params — the
    single place the parameter->engine wiring lives (solve_on_device
    and the CLI's device-mode trace reconstruction both use it).

    ``whole_solve=True`` says the caller only runs the engine's
    whole-solve programs (``run`` / ``run_trace``), which lets an
    unset ``layout`` resolve to lane-major (:func:`select_layout`);
    every result's metrics say what ran (``layout``,
    ``layout_source``).

    ``aggregation='auto'`` compiles with scatter (the universally
    valid baseline), measures the candidate strategies on the actual
    compiled graph (engine/autotune.py — mesh and hub-guard
    constraints respected there), swaps in the winner's agg arrays,
    and annotates the engine so every result reports the decision.

    ``shards=N`` (N >= 2) selects the PARTITIONED engine instead of
    the replicated-variable mesh: a min-edge-cut partition
    (engine/partition.py) assigns variables and factors to shards,
    each shard owns its local slice of the variable tables, and only
    cut-edge (halo) state crosses devices per superstep — O(cut·D)
    communication instead of the replicated path's O(V·D)
    (engine/sharding.py; docs/sharding.md).  Mutually exclusive with
    ``mesh``/``n_devices``; partition statistics and communication
    accounting land in every result's ``metrics``."""
    layout, layout_source = select_layout(
        params, mesh=mesh, n_devices=n_devices, shards=shards,
        whole_solve=whole_solve)
    if shards is not None and shards > 1:
        if mesh is not None or n_devices:
            raise ValueError(
                "shards= (partitioned engine) and mesh=/n_devices= "
                "(replicated sharding) are mutually exclusive")
        if layout == "lane":
            raise ValueError(
                "layout='lane' is single-device; the partitioned "
                "engine uses the edge layout")
        if decimation_plan_from_params(params) is not None:
            raise ValueError(
                "decimation clamps the single-device var_costs "
                "table; run without shards=")
        # The partitioned superstep aggregates locally with scatter;
        # reuse the mesh aggregation policy (auto -> scatter,
        # anything else refused loudly).
        aggregation = validated_aggregation(params, max(shards, 2))
        from pydcop_tpu.engine.multihost import partitioned_mesh
        from pydcop_tpu.engine.runner import ShardedMaxSumEngine

        graph, meta = compile_dcop(
            dcop, noise_level=params.get("noise", 0.01),
            aggregation=aggregation,
        )
        engine = ShardedMaxSumEngine(
            graph, meta,
            mesh=partitioned_mesh(shards),
            damping=params.get("damping", 0.5),
            damping_nodes=params.get("damping_nodes", "both"),
            stability=params.get("stability", STABILITY_COEFF),
            prune=bool(params.get("prune", False)),
        )
        engine.extra_metrics["layout_source"] = layout_source
        return engine
    pad_to = 1
    if mesh is not None:
        pad_to = mesh.size
    elif n_devices:
        pad_to = n_devices
    aggregation = validated_aggregation(params, pad_to)
    agg_info = None
    if aggregation == "auto":
        # Compile with scatter (the universally valid baseline) and
        # tune on the compiled structure below — unless a persisted
        # decision replays pre-compile (see _replay_auto_choice).
        aggregation = "scatter"
    elif params.get("aggregation") == "auto":
        # validated_aggregation already resolved auto -> scatter for
        # the mesh case; record why nothing was measured.
        agg_info = {"aggregation": "scatter",
                    "aggregation_source": "mesh"}
    if params.get("aggregation") == "auto" and agg_info is None \
            and layout == "lane":
        # The lane layout carries its own scatter aggregation;
        # nothing to tune.
        agg_info = {"aggregation": "scatter",
                    "aggregation_source": "lane"}
    if params.get("aggregation") == "auto" and agg_info is None:
        # Replay a persisted decision BEFORE compiling: the winner
        # then lands in compile_dcop's aggregation argument and its
        # layout arrays come out of the structure cache — a warm
        # auto-solve rebuilds nothing.
        aggregation, agg_info = _replay_auto_choice(dcop)
    graph, meta = compile_dcop(
        dcop, noise_level=params.get("noise", 0.01), pad_to=pad_to,
        aggregation=aggregation,
    )
    if params.get("aggregation") == "auto" and agg_info is None:
        from pydcop_tpu.engine.autotune import (
            apply_aggregation,
            autotune_aggregation,
        )

        agg_info = autotune_aggregation(graph, pad_to=pad_to)
        if agg_info["aggregation"] != "scatter":
            try:
                graph = apply_aggregation(
                    graph, agg_info["aggregation"])
            except ValueError:
                # Builder refusal (e.g. hub guard) on a strategy that
                # nonetheless timed: never fail an 'auto' solve —
                # scatter is always valid.
                agg_info = dict(
                    agg_info, aggregation="scatter",
                    aggregation_source="fallback")
    engine = MaxSumEngine(
        graph, meta,
        damping=params.get("damping", 0.5),
        damping_nodes=params.get("damping_nodes", "both"),
        stability=params.get("stability", STABILITY_COEFF),
        mesh=mesh, n_devices=n_devices,
        layout=layout,
        prune=bool(params.get("prune", False)),
    )
    engine.extra_metrics["layout_source"] = layout_source
    if agg_info is not None:
        engine.extra_metrics.update(agg_info)
    return engine


def solve_on_device(dcop: DCOP, algo_def: AlgorithmDef,
                    max_cycles: int = 1000, mesh=None,
                    n_devices: Optional[int] = None,
                    shards: Optional[int] = None,
                    stop_on_convergence: bool = True,
                    warmup: bool = False, **_) -> DeviceRunResult:
    """Batched BSP MaxSum on TPU/CPU devices."""
    params = algo_def.params
    # The plain path: everything below runs whole-solve programs,
    # except decimation, which select_layout reads from the params.
    engine = build_engine(dcop, params, mesh=mesh,
                          n_devices=n_devices, shards=shards,
                          whole_solve=True)
    plan = decimation_plan_from_params(params)
    if plan is not None:
        # Decimation is the SEGMENTED mode: clamping happens at the
        # boundaries the engine already syncs on (zero new syncs in
        # the jitted loop), and the clamp set rides snapshots and
        # recovery retains.  warmup is a no-op here: the segmented
        # runner's metrics['cycles_per_s'] already excludes compile
        # time; re-running the whole solve would double wall time for
        # nothing.
        return engine.run_checkpointed(
            max_cycles=max_cycles,
            segment_cycles=plan.cycles_per_round,
            decimation=plan,
        )
    run = partial(
        engine.run, max_cycles=max_cycles,
        stop_on_convergence=stop_on_convergence,
    )
    if warmup:
        # Prime the jit cache so the timed run below is steady-state
        # (each run starts from fresh initial messages, so re-running
        # is side-effect free).
        t0 = time.perf_counter()
        run()
        warm_s = time.perf_counter() - t0
        res = run()
        res.metrics["warmup_time_s"] = warm_s
        return res
    return run()
