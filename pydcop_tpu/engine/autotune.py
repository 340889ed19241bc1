"""Aggregation autotuner: measure, don't guess.

The variable-side aggregation is the suspect op of the superstep
past ~100k variables, and the best strategy is backend- and
shape-dependent: scatter wins everywhere on CPU, while on TPU the
scatter-add serializes row updates and the dense ell gather is the
candidate (docs/performance.md).  A manual ``aggregation=`` flag nobody tunes leaves that
performance on the table; ``aggregation='auto'`` replaces it with a
per-graph measurement: micro-time the candidate strategies on the
*actual* compiled graph (same bucket shapes, same edge distribution,
random message payloads), pick the winner, and record the decision
in ``DeviceRunResult.metrics``.

Constraints the measurement respects (never violated, never silently
worked around):

- **mesh**: sharded graphs always use scatter (shard_graph drops the
  agg arrays) — callers resolve that before ever reaching here
  (engine/compile.validated_aggregation), and :func:`autotune_aggregation`
  re-checks ``pad_to``;
- **hub guard**: the ell builder refuses degree-skewed graphs whose
  padded lists would explode ([V+1, K] with K = max degree); the
  autotuner catches that refusal and drops ell from the candidate
  set instead of OOMing;
- **numerics**: "boundary" is timed for the record but NEVER
  selected — its f32 prefix sum cancels catastrophically at exactly
  the scale it targets (measured, docs/performance.md), which is why
  the maxsum param validation does not offer it either.

Decisions persist in a JSON cache keyed by (backend, graph shape):
re-serving a same-shaped problem skips the micro-benchmark entirely.
Default location ``<checkout>/.cache/pydcop_tpu/agg_autotune.json``
— beside the compile cache (engine/aotcache.DEFAULT_DIR), inside the
checkout and git-ignored: nothing around the checkout is read or
written (``PYDCOP_AGG_AUTOTUNE_CACHE`` overrides; an unwritable path
degrades to measuring every time, never to failing the solve).
"""

import json
import logging
import os
import tempfile
import threading
from typing import Any, Dict, Optional

import numpy as np

from pydcop_tpu.engine.aotcache import DEFAULT_DIR
from pydcop_tpu.engine.compile import (
    AGGREGATIONS,
    CompiledFactorGraph,
    build_aggregation_arrays,
)

logger = logging.getLogger("pydcop.engine.autotune")

# Strategies a solve may actually run with.  "boundary" is excluded
# on numerics (see module docstring), matching the algo-param policy.
SELECTABLE = ("scatter", "sorted", "ell")

_CACHE_VERSION = 1


def cache_path() -> str:
    env = os.environ.get("PYDCOP_AGG_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.dirname(DEFAULT_DIR), "pydcop_tpu",
        "agg_autotune.json",
    )


def shape_key(backend: str, n_vars: int, dmax: int,
              bucket_shapes, max_degree: int) -> str:
    """Stable string key for "same-shaped problem": backend + var/
    domain counts + per-bucket (arity, rows) + the max variable
    degree.  Cost values are deliberately absent — the aggregation op
    never reads them.  The degree term matters: the ell hub guard
    trips on max degree, so two graphs with identical bucket shapes
    but different degree skew must NOT share a cached 'ell' decision
    (a replay onto the hub-skewed twin would refuse to build).
    ``bucket_shapes`` is an iterable of (arity, rows), arity-sorted.
    """
    buckets = ";".join(f"{a}x{r}" for a, r in bucket_shapes)
    return (
        f"v{_CACHE_VERSION}|{backend}|V{n_vars}|D{dmax}"
        f"|{buckets}|K{max_degree}"
    )


def graph_max_degree(graph: CompiledFactorGraph) -> int:
    """Max real-variable degree over the flattened edge slots (the
    quantity the ell hub guard trips on; sentinel edges excluded)."""
    counts = np.zeros(graph.n_vars + 1, dtype=np.int64)
    for b in graph.buckets:
        counts += np.bincount(
            b.var_ids.reshape(-1), minlength=graph.n_vars + 1)
    return int(counts[:-1].max()) if graph.n_vars else 0


def graph_shape_key(graph: CompiledFactorGraph,
                    backend: Optional[str] = None) -> str:
    if backend is None:
        import jax

        backend = jax.default_backend()
    return shape_key(
        backend, graph.n_vars, graph.dmax,
        [(b.var_ids.shape[1], b.var_ids.shape[0])
         for b in graph.buckets],
        graph_max_degree(graph),
    )


def dcop_shape_key(dcop, backend: Optional[str] = None) -> str:
    """Shape key computed from a DCOP directly (variable/domain
    counts, per-arity factor counts, max scope degree) — identical to
    :func:`graph_shape_key` of its compiled graph at ``pad_to=1``, so
    persisted decisions replay BEFORE compiling."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    variables = list(dcop.variables.values())
    counts: Dict[int, int] = {}
    degree: Dict[str, int] = {}
    for c in dcop.constraints.values():
        if c.arity == 0:
            continue
        counts[c.arity] = counts.get(c.arity, 0) + 1
        for v in c.dimensions:
            degree[v.name] = degree.get(v.name, 0) + 1
    return shape_key(
        backend,
        len(variables),
        max((len(v.domain) for v in variables), default=1),
        sorted(counts.items()),
        max(degree.values(), default=0),
    )


def cached_choice(key: str,
                  cache_file: Optional[str] = None) -> Optional[str]:
    """Replay a persisted decision for ``key`` (None on miss/invalid)
    — lets callers resolve the strategy BEFORE compiling, so the
    winner's layout arrays come out of the compile-time structure
    cache instead of being rebuilt per solve."""
    cached = _load_cache(cache_file or cache_path()).get(key)
    if isinstance(cached, dict) \
            and cached.get("aggregation") in SELECTABLE:
        return cached["aggregation"]
    return None


def _load_cache(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict):
            return data
    except (OSError, ValueError):
        pass
    return {}


def _store_cache(path: str, data: Dict[str, Any]) -> None:
    """Atomic merge-and-write; failure logs and moves on (the cache
    is an optimization, not a dependency)."""
    try:
        directory = os.path.dirname(os.path.abspath(path))
        os.makedirs(directory, exist_ok=True)
        merged = _load_cache(path)
        merged.update(data)
        fd, tmp = tempfile.mkstemp(
            dir=directory, prefix=".autotune_", suffix=".json")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError as e:
        logger.warning("autotune cache not persisted to %s: %s",
                       path, e)


def apply_aggregation(graph: CompiledFactorGraph,
                      aggregation: str) -> CompiledFactorGraph:
    """Rebuild a compiled graph's agg_* arrays for ``aggregation``
    (structure-only: costs and var_ids are shared, not copied)."""
    perm, sorted_seg, starts, ends, ell = build_aggregation_arrays(
        graph.buckets, graph.n_vars + 1, aggregation
    )
    return graph._replace(
        agg_perm=perm, agg_sorted_seg=sorted_seg,
        agg_starts=starts, agg_ends=ends, agg_ell=ell,
    )


def _time_strategy(graph: CompiledFactorGraph, f2v, reps: int,
                   ) -> float:
    """Median seconds for one aggregation pass, warmed (compile
    excluded), honest completion via engine.timing.sync."""
    import jax

    from pydcop_tpu.engine.timing import sync, timed_call
    from pydcop_tpu.ops.maxsum import aggregate_beliefs

    fn = jax.jit(lambda g, m: aggregate_beliefs(g, m)[1])
    placed = jax.device_put(graph)
    sync(fn(placed, f2v))  # compile + warm
    times = [timed_call(fn, placed, f2v)[1] for _ in range(reps)]
    return float(np.median(times))


def autotune_aggregation(graph: CompiledFactorGraph, *,
                         pad_to: int = 1,
                         reps: int = 3,
                         use_cache: bool = True,
                         cache_file: Optional[str] = None,
                         ) -> Dict[str, Any]:
    """Pick the aggregation strategy for ``graph`` by measurement.

    Returns ``{"aggregation", "aggregation_source",
    "aggregation_timings_ms", "aggregation_key"}`` — the dict engines
    merge into ``DeviceRunResult.metrics``.  ``aggregation_source``
    is one of:

    - ``"mesh"``: sharded run, scatter is the only valid strategy
      (nothing measured);
    - ``"empty"``: no factor edges, nothing to aggregate;
    - ``"cache"``: decision replayed from the JSON shape cache;
    - ``"measured"``: micro-benchmarked on this process's backend.

    Timings are reported for all four named strategies where
    measurable (``None`` where not: hub-guard refusals, mesh runs);
    selection only ever happens among :data:`SELECTABLE`.
    """
    import jax

    backend = jax.default_backend()
    key = graph_shape_key(graph, backend)
    timings: Dict[str, Optional[float]] = {
        s: None for s in AGGREGATIONS}
    if pad_to > 1:
        return {
            "aggregation": "scatter",
            "aggregation_source": "mesh",
            "aggregation_timings_ms": timings,
            "aggregation_key": key,
        }
    n_edges = sum(
        int(np.prod(b.var_ids.shape)) for b in graph.buckets)
    if n_edges == 0:
        return {
            "aggregation": "scatter",
            "aggregation_source": "empty",
            "aggregation_timings_ms": timings,
            "aggregation_key": key,
        }

    path = cache_file or cache_path()
    if use_cache:
        cached = _load_cache(path).get(key)
        if (isinstance(cached, dict)
                and cached.get("aggregation") in SELECTABLE):
            return {
                "aggregation": cached["aggregation"],
                "aggregation_source": "cache",
                "aggregation_timings_ms": cached.get(
                    "aggregation_timings_ms", timings),
                "aggregation_key": key,
            }

    # Random message payloads: the aggregation's cost is layout- and
    # index-driven, value-independent — any dense payload measures it.
    # Placed on device ONCE: host-resident payloads would add the
    # same multi-MB host→device transfer to every rep of every
    # strategy, drowning the kernel-time differences being measured.
    rng = np.random.default_rng(0)
    d = graph.dmax
    f2v = jax.device_put(tuple(
        rng.standard_normal(
            b.var_ids.shape + (d,)).astype(np.float32)
        for b in graph.buckets
    ))
    notes: Dict[str, str] = {}
    for strategy in AGGREGATIONS:
        try:
            variant = apply_aggregation(graph, strategy)
        except ValueError as e:
            # The hub guard refusing ell (or any builder refusal):
            # record why, drop the candidate.
            notes[strategy] = str(e).split(":")[0]
            continue
        try:
            timings[strategy] = _time_strategy(variant, f2v, reps)
        except Exception as e:  # pragma: no cover - backend-specific
            notes[strategy] = f"{type(e).__name__}"
            logger.warning("autotune: %s failed to run: %s",
                           strategy, e)

    candidates = {
        s: t for s, t in timings.items()
        if s in SELECTABLE and t is not None
    }
    # Deterministic tie-break: strategy order in SELECTABLE (scatter
    # first — the parity default) wins exact ties.
    choice = min(
        candidates,
        key=lambda s: (candidates[s], SELECTABLE.index(s)),
    ) if candidates else "scatter"
    timings_ms = {
        s: (None if t is None else round(t * 1e3, 4))
        for s, t in timings.items()
    }
    result = {
        "aggregation": choice,
        "aggregation_source": "measured",
        "aggregation_timings_ms": timings_ms,
        "aggregation_key": key,
    }
    if notes:
        result["aggregation_notes"] = notes
    if use_cache:
        _store_cache(path, {key: {
            "aggregation": choice,
            "aggregation_timings_ms": timings_ms,
            "backend": backend,
        }})
    return result


# --------------------------------------------------------------------- #
# Whole-algorithm portfolio racer (ISSUE 10): the micro-timing pattern
# above, generalized from aggregation strategies to whole kernels.
# Each candidate races a short budget of cycles ON THE REAL COMPILED
# GRAPH; the winner is the fastest candidate whose final cost reaches
# the best cost any candidate achieved (within tolerance) — i.e. the
# decision optimizes time-to-target-cost, not cycles/sec.  Decisions
# persist in the same JSON shape cache as the aggregation autotuner
# (distinct key prefix), so a same-structure re-solve replays with
# zero measurement — api.solve(algo="auto") and the serving dispatch
# path both consume the cached decision.

# Candidate order IS the deterministic tie-break (parity-default
# maxsum first).  "dpop" (exact inference, ISSUE 17) is a *conditional*
# candidate: it only races when the caller supplies its runner via
# ``extra_runners`` — which :func:`dpop_portfolio_runner` refuses to
# build past the width ceiling, so wide structures never pay an exact
# attempt and always resolve to an iterative winner.
PORTFOLIO_CANDIDATES = (
    "maxsum", "maxsum_prune", "maxsum_decim", "dsa", "mgm", "gdba",
    "dpop",
)

# Winner -> (algorithm name, extra algo_params) for api.solve.
PORTFOLIO_PARAMS = {
    "maxsum": ("maxsum", {}),
    "maxsum_prune": ("maxsum", {"prune": True}),
    "maxsum_decim": ("maxsum", {"decimation": 10}),
    "dsa": ("dsa", {}),
    "mgm": ("mgm", {}),
    "gdba": ("gdba", {}),
    "dpop": ("dpop", {}),
}

# Width gate for *racing* exact inference: deliberately far below
# ops/dpop.MAX_NODE_ELEMENTS — the race is a latency probe, and a
# hypercube this side of the gate solves in the same ballpark as a
# 60-cycle iterative race leg.  Past it, DPOP may still be reachable
# explicitly (algo="dpop"), just not auto-raced.
DPOP_RACE_MAX_ELEMENTS = 2 ** 20

_PORTFOLIO_PREFIX = f"portfolio-v{_CACHE_VERSION}|"

# Candidates whose final cost must come within this fraction of the
# best achieved cost (plus an absolute epsilon for zero-cost targets)
# to be eligible on time.
_PORTFOLIO_COST_TOL = 0.02
_PORTFOLIO_RACE_CYCLES = 60


def portfolio_key(shape: str) -> str:
    return _PORTFOLIO_PREFIX + shape


def dcop_portfolio_key(dcop, backend: Optional[str] = None) -> str:
    return portfolio_key(dcop_shape_key(dcop, backend))


def cached_portfolio_choice(key: str,
                            cache_file: Optional[str] = None
                            ) -> Optional[str]:
    """Replay a persisted portfolio decision (None on miss/invalid)."""
    cached = _load_cache(cache_file or cache_path()).get(key)
    if isinstance(cached, dict) \
            and cached.get("algo") in PORTFOLIO_CANDIDATES:
        return cached["algo"]
    return None


# Public alias: consumers of the cached race timings (the serving
# envelope cost model scales them to a request's cycle budget) need
# the cycle count the race actually ran.
PORTFOLIO_RACE_CYCLES = _PORTFOLIO_RACE_CYCLES


def cached_portfolio_timing_ms(key: str,
                               cache_file: Optional[str] = None,
                               data: Optional[Dict[str, Any]] = None
                               ) -> Optional[float]:
    """The persisted portfolio WINNER's measured race time (ms over
    :data:`PORTFOLIO_RACE_CYCLES` cycles of the real compiled graph)
    for ``key`` — a free per-structure solve-time prior.  The serving
    scheduler's envelope pack-vs-solo cost model consumes it
    (serving/binning.solve_prior_ms): a structure the portfolio racer
    ever measured gets a real number instead of a cells*cycles
    estimate, at zero measurement cost on the serving path.  None on
    miss/invalid/unmeasured-winner.

    ``data`` is an already-loaded cache dict (:func:`_load_cache`) —
    the serving flush planner loads the JSON ONCE per flush and
    resolves every group member against it, instead of paying one
    file read per member."""
    cached = (data if data is not None
              else _load_cache(cache_file or cache_path())).get(key)
    if isinstance(cached, dict) \
            and cached.get("algo") in PORTFOLIO_CANDIDATES:
        timing = (cached.get("portfolio_timings_ms")
                  or {}).get(cached["algo"])
        if isinstance(timing, (int, float)) and timing > 0:
            return float(timing)
    return None


def _portfolio_runners(graph: CompiledFactorGraph, race_cycles: int,
                       meta=None):
    """Build (name -> zero-arg callable returning final cost) over the
    placed graph.  Each callable is self-contained and warmed by its
    first invocation; the caller times the second.

    ``meta`` (a FactorGraphMeta) makes the mgm/gdba race use the SAME
    lexical-name tie-break ranks the deployed winner would
    (algorithms/mgm.lexic_ranks) — a race with different tie-breaks
    would persist a decision about a trajectory the winner never
    runs.  Without meta, index order with the +inf sentinel is the
    closest stand-in."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from pydcop_tpu.ops import dsa as dsa_ops
    from pydcop_tpu.ops import gdba as gdba_ops
    from pydcop_tpu.ops import maxsum as maxsum_ops
    from pydcop_tpu.ops import mgm as mgm_ops
    from pydcop_tpu.ops.localsearch import assignment_cost

    placed = jax.device_put(graph)
    n_rows = graph.var_costs.shape[0]
    if meta is not None:
        from pydcop_tpu.algorithms.mgm import lexic_ranks

        ranks = jnp.asarray(lexic_ranks(meta))
    else:
        ranks = jnp.concatenate([
            jnp.arange(n_rows - 1, dtype=jnp.float32),
            jnp.asarray([jnp.inf], dtype=jnp.float32),
        ])

    def cost_of(values):
        full = jnp.concatenate(
            [values, jnp.zeros((1,), values.dtype)])
        return assignment_cost(placed, full)

    def maxsum_runner(prune: bool):
        fn = jax.jit(lambda g: cost_of(maxsum_ops.run_maxsum(
            g, race_cycles, stop_on_convergence=False,
            prune=prune)[1]))
        return lambda: float(fn(placed))

    def decim_runner():
        half = max(race_cycles // 2, 1)
        first = jax.jit(lambda g: maxsum_ops.run_maxsum(
            g, half, stop_on_convergence=False))
        margin_fn = jax.jit(_belief_margin)
        rest = jax.jit(lambda g, s: cost_of(
            maxsum_ops.run_maxsum_from(
                g, s, half, stop_on_convergence=False)[1]))

        def run():
            state, values = first(placed)
            margin = np.asarray(margin_fn(placed, state))
            vals = np.asarray(jax.device_get(values))
            var_costs = np.asarray(
                jax.device_get(placed.var_costs)).copy()
            n_vars = var_costs.shape[0] - 1
            k = max(1, n_vars // 5)
            chosen = np.argsort(-margin, kind="stable")[:k]
            d = var_costs.shape[1]
            from pydcop_tpu.engine.compile import BIG

            for i in chosen:
                keep = int(vals[i])
                row = np.full((d,), BIG, var_costs.dtype)
                row[keep] = var_costs[i, keep]
                var_costs[i] = row
            g2 = placed._replace(
                var_costs=jax.device_put(var_costs))
            state = state._replace(stable=jnp.asarray(False))
            return float(rest(g2, state))

        return run

    def ls_runner(run_fn, **kw):
        fn = jax.jit(partial(run_fn, max_cycles=race_cycles, **kw))
        return lambda: float(fn(placed)[1])

    return {
        "maxsum": maxsum_runner(False),
        "maxsum_prune": maxsum_runner(True),
        "maxsum_decim": decim_runner(),
        "dsa": ls_runner(dsa_ops.run_dsa),
        "mgm": ls_runner(mgm_ops.run_mgm, lexic_ranks=ranks),
        "gdba": ls_runner(gdba_ops.run_gdba, lexic_ranks=ranks),
    }


def dpop_portfolio_runner(dcop, graph: CompiledFactorGraph, meta):
    """Zero-arg exact-inference race leg, or None past the width gate.

    Width is decided from the pseudo-tree BEFORE any table exists
    (ops/dpop.tree_stats via engine.dpop.dpop_feasibility, CEC
    shrinkage included), so an over-wide structure costs one cheap
    host-side pass and never allocates a hypercube.  The returned
    runner scores its assignment through the SAME compiled-graph
    ``assignment_cost`` the iterative racers use — one cost scale for
    the whole race (max-objective negation included)."""
    from pydcop_tpu.computations_graph import pseudotree as pt
    from pydcop_tpu.engine.dpop import DpopEngine, dpop_feasibility

    try:
        ptree = pt.build_computation_graph(dcop)
    except Exception as e:  # noqa: BLE001 — no tree, no exact leg
        logger.debug("portfolio: no pseudo-tree for dpop leg: %s", e)
        return None
    verdict = dpop_feasibility(
        ptree, mode=dcop.objective, cec=True,
        max_elements=DPOP_RACE_MAX_ELEMENTS)
    if not verdict["feasible"]:
        logger.debug(
            "portfolio: dpop leg skipped (max_elements %s > gate %s)",
            verdict["max_elements"], DPOP_RACE_MAX_ELEMENTS)
        return None
    import jax
    import jax.numpy as jnp

    from pydcop_tpu.ops.localsearch import assignment_cost

    engine = DpopEngine(ptree, mode=dcop.objective, cec=True)
    placed = jax.device_put(graph)
    index_of = {
        name: {v: i for i, v in enumerate(dom)}
        for name, dom in zip(meta.var_names, meta.domains)
    }

    def run():
        res = engine.run()
        idx = jnp.asarray(
            [index_of[n][res.assignment[n]] for n in meta.var_names]
            + [0], dtype=jnp.int32)
        return float(assignment_cost(placed, idx))

    return run


def _belief_margin(graph, state):
    import jax.numpy as jnp

    from pydcop_tpu.ops import maxsum as maxsum_ops

    beliefs, _ = maxsum_ops.aggregate_beliefs(graph, state.f2v)
    masked = jnp.where(graph.var_valid, beliefs, jnp.inf)[:-1]
    best2 = jnp.sort(masked, axis=1)[:, :2]
    return best2[:, 1] - best2[:, 0]


def autotune_portfolio(graph: CompiledFactorGraph, *,
                       key: Optional[str] = None,
                       race_cycles: int = _PORTFOLIO_RACE_CYCLES,
                       use_cache: bool = True,
                       cache_file: Optional[str] = None,
                       candidates=PORTFOLIO_CANDIDATES,
                       meta=None,
                       extra_runners=None,
                       ) -> Dict[str, Any]:
    """Race whole algorithm kernels on ``graph`` toward a cost target.

    Every candidate runs ``race_cycles`` cycles (warmed — compile
    excluded; honest sync through the host fetch of the scalar cost);
    the target cost is the best final cost any candidate achieved, and
    the winner is the fastest candidate within ``_PORTFOLIO_COST_TOL``
    of it — deterministic tie-break by candidate order (parity-default
    maxsum first).  A candidate that fails to build/run is dropped
    with a note, never fatal (maxsum always runs).

    Returns ``{"algo", "portfolio_source", "portfolio_timings_ms",
    "portfolio_costs", "portfolio_target_cost", "portfolio_key"}``;
    persists the decision under ``key`` in the shared JSON shape
    cache (``portfolio_source`` is ``"cache"`` on replay — asserted
    against re-racing in the work-reduction battery)."""
    import time as _time

    if key is None:
        key = portfolio_key(graph_shape_key(graph))
    path = cache_file or cache_path()
    if use_cache:
        cached = _load_cache(path).get(key)
        if isinstance(cached, dict) \
                and cached.get("algo") in PORTFOLIO_CANDIDATES:
            return {
                "algo": cached["algo"],
                "portfolio_source": "cache",
                "portfolio_timings_ms": cached.get(
                    "portfolio_timings_ms", {}),
                "portfolio_costs": cached.get("portfolio_costs", {}),
                "portfolio_target_cost": cached.get(
                    "portfolio_target_cost"),
                "portfolio_key": key,
            }

    runners = _portfolio_runners(graph, race_cycles, meta=meta)
    if extra_runners:
        # Conditional candidates (e.g. the width-gated dpop leg): a
        # None value means "not raced on this structure" — same as an
        # absent runner.
        runners.update(
            {k: v for k, v in extra_runners.items() if v is not None})
    timings_ms: Dict[str, Optional[float]] = {}
    costs: Dict[str, Optional[float]] = {}
    notes: Dict[str, str] = {}
    for name in candidates:
        runner = runners.get(name)
        if runner is None:
            continue
        try:
            runner()  # warm: compile + one discarded run
            t0 = _time.perf_counter()
            cost = runner()
            timings_ms[name] = round(
                (_time.perf_counter() - t0) * 1e3, 4)
            costs[name] = cost
        except Exception as e:  # noqa: BLE001 — drop the candidate
            notes[name] = f"{type(e).__name__}"
            logger.warning("portfolio: %s failed to race: %s",
                           name, e)
            timings_ms[name] = None
            costs[name] = None

    scored = {n: (costs[n], timings_ms[n]) for n in candidates
              if costs.get(n) is not None
              and timings_ms.get(n) is not None}
    if not scored:
        choice = "maxsum"
        target = None
    else:
        target = min(c for c, _ in scored.values())
        tol = abs(target) * _PORTFOLIO_COST_TOL + 1e-9
        eligible = {n: t for n, (c, t) in scored.items()
                    if c <= target + tol}
        order = {n: i for i, n in enumerate(candidates)}
        choice = min(eligible, key=lambda n: (eligible[n], order[n]))
    result = {
        "algo": choice,
        "portfolio_source": "measured",
        "portfolio_timings_ms": timings_ms,
        "portfolio_costs": costs,
        "portfolio_target_cost": target,
        "portfolio_key": key,
    }
    if notes:
        result["portfolio_notes"] = notes
    if use_cache:
        import jax

        _store_cache(path, {key: {
            "algo": choice,
            "portfolio_timings_ms": timings_ms,
            "portfolio_costs": costs,
            "portfolio_target_cost": target,
            "backend": jax.default_backend(),
        }})
    return result


# --------------------------------------------------------------------
# Self-tuning pack-planner constants (ISSUE 18 tentpole c)
#
# The envelope pack-vs-solo decision (serving/binning.pack_decision)
# prices dispatches with an affine model ``overhead + cycles *
# (per_cycle + cells * per_cell)`` whose constants were fitted ONCE on
# the CPU backend.  Every completed serving dispatch is a measured
# sample of exactly that model (the request ledger's execute wall, the
# dispatch's padded cell total, its cycle budget), so the constants
# are re-fitted online per resolved backend: an exponentially-weighted
# least-squares regression of ms-per-cycle on cells (intercept →
# us_per_cycle, slope → ns_per_cell_cycle) plus an EW mean of the
# per-dispatch host overhead.  Persisted in the same shape-cache JSON
# as the portfolio timings (key ``packfit-v1|<backend>``) so a restart
# starts from the fleet's history; cold start (< _PACKFIT_MIN_SAMPLES
# samples, or a degenerate fit) falls back to the compiled-in
# defaults.  ``PYDCOP_PACK_FIT=0`` disables both recording and use.

PACKFIT_PREFIX = f"packfit-v{_CACHE_VERSION}|"
_PACKFIT_DECAY = 0.98
_PACKFIT_MIN_SAMPLES = 8
_PACKFIT_PERSIST_EVERY = 16
_packfit_lock = threading.Lock()
# backend -> EW sufficient statistics {w, wx, wy, wxx, wxy, wo, n}
_packfit_state: Dict[str, Dict[str, float]] = {}
_packfit_dirty: Dict[str, int] = {}


def pack_fit_enabled() -> bool:
    """``PYDCOP_PACK_FIT=0`` freezes the pack planner on the
    compiled-in default constants (the on/off isolation knob of an
    A/B; ROADMAP.md Queue 3 "Three packing tiers")."""
    return os.environ.get("PYDCOP_PACK_FIT", "1") != "0"


def _packfit_key(backend: str) -> str:
    return PACKFIT_PREFIX + str(backend)


def _packfit_load(backend: str,
                  cache_file: Optional[str] = None) -> Dict[str, float]:
    """Seed the in-memory EW state from the persisted JSON once per
    backend per process (under ``_packfit_lock``)."""
    state = _packfit_state.get(backend)
    if state is not None:
        return state
    persisted = _load_cache(cache_file or cache_path()).get(
        _packfit_key(backend))
    state = {"w": 0.0, "wx": 0.0, "wy": 0.0, "wxx": 0.0,
             "wxy": 0.0, "wo": 0.0, "n": 0.0}
    if isinstance(persisted, dict):
        stats = persisted.get("stats")
        if isinstance(stats, dict):
            for k in state:
                v = stats.get(k)
                if isinstance(v, (int, float)) and np.isfinite(v):
                    state[k] = float(v)
    _packfit_state[backend] = state
    return state


def record_pack_sample(backend: str, cells: int, cycles: int,
                       execute_s: float, overhead_s: float = 0.0,
                       cache_file: Optional[str] = None) -> None:
    """Feed one measured dispatch into the per-backend fit.

    ``execute_s`` is the dispatch's device execute wall (the ledger's
    ``execute`` component / the DeviceRunResult ``run_time_s`` of a
    warm dispatch), ``cells`` the PADDED cell total the device
    actually ran (``metrics['cells_total']``), ``overhead_s`` the
    host-side per-dispatch fixed cost (batch assembly + launch).
    Cold dispatches must not be fed — their wall is compile, not the
    affine compute model.  Persists every
    ``_PACKFIT_PERSIST_EVERY`` samples (atomic merge-write; failure
    degrades to in-memory-only)."""
    if not pack_fit_enabled():
        return
    if cells <= 0 or cycles <= 0 or execute_s <= 0:
        return
    x = float(cells)
    y = execute_s * 1e3 / float(cycles)  # ms per cycle
    with _packfit_lock:
        state = _packfit_load(backend, cache_file)
        d = _PACKFIT_DECAY
        for k in ("w", "wx", "wy", "wxx", "wxy", "wo"):
            state[k] *= d
        state["w"] += 1.0
        state["wx"] += x
        state["wy"] += y
        state["wxx"] += x * x
        state["wxy"] += x * y
        state["wo"] += max(overhead_s, 0.0) * 1e3
        state["n"] += 1.0
        _packfit_dirty[backend] = _packfit_dirty.get(backend, 0) + 1
        if _packfit_dirty[backend] >= _PACKFIT_PERSIST_EVERY:
            _packfit_dirty[backend] = 0
            fitted = _packfit_fit(state)
            _store_cache(cache_file or cache_path(), {
                _packfit_key(backend): {
                    "stats": dict(state),
                    "fitted": fitted,
                    "backend": backend,
                }})


def _packfit_fit(state: Dict[str, float]) -> Optional[Dict[str, float]]:
    """Solve the EW least squares for the model constants; None when
    under-sampled or degenerate (caller falls back to defaults)."""
    if state["n"] < _PACKFIT_MIN_SAMPLES or state["w"] <= 0:
        return None
    w, wx, wy, wxx, wxy = (state["w"], state["wx"], state["wy"],
                           state["wxx"], state["wxy"])
    denom = w * wxx - wx * wx
    if denom <= 1e-12:
        return None
    slope = (w * wxy - wx * wy) / denom        # ms/cycle per cell
    intercept = (wy - slope * wx) / w          # ms/cycle at 0 cells
    if not (np.isfinite(slope) and np.isfinite(intercept)):
        return None
    if slope <= 0 or intercept < 0:
        # A non-positive cell slope means the sampled range cannot
        # identify the model (e.g. one shape dominating traffic) —
        # an unidentified fit must not steer the planner.
        return None
    return {
        "us_per_cycle": round(intercept * 1e3, 6),
        "ns_per_cell_cycle": round(slope * 1e6, 6),
        "overhead_ms": round(state["wo"] / w, 6),
        "n": int(state["n"]),
    }


def fitted_pack_constants(backend: str,
                          cache_file: Optional[str] = None
                          ) -> Optional[Dict[str, float]]:
    """The current fitted constants for ``backend`` — the dict
    serving/binning.pack_decision consumes (``us_per_cycle``,
    ``ns_per_cell_cycle``, ``overhead_ms``, ``n``) — or None while
    cold/degenerate/disabled (the planner then uses the compiled-in
    defaults and records ``constants_source: "default"``)."""
    if not pack_fit_enabled():
        return None
    with _packfit_lock:
        state = _packfit_load(backend, cache_file)
        return _packfit_fit(state)


def _packfit_reset() -> None:
    """Test hook: drop the in-memory EW state (the JSON is untouched;
    point ``cache_file`` at a temp path to isolate persistence)."""
    with _packfit_lock:
        _packfit_state.clear()
        _packfit_dirty.clear()
