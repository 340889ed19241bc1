"""A-DSA: asynchronous DSA, clock-driven.

Reference parity: pydcop/algorithms/adsa.py (:121-131: params variant,
probability, period 0.5) — each variable re-evaluates on a periodic
clock tick using whatever neighbor values it has seen, instead of
waiting for a full cycle of value messages.

Device path: two schedules.

- ``schedule=lockstep`` (default): the engine evaluates every variable
  each superstep, i.e. the `period` is one superstep for everyone;
  `period` is accepted for compatibility and used by the agent-mode
  runtime (periodic actions on the agent clock).
- ``schedule=staggered``: the variable graph is greedily colored
  (ops/dsa.py greedy_classes) and each superstep only ONE color class
  may flip, so neighbors never flip simultaneously — emulating the
  clock skew that saves the true-async runtime from simultaneous-flip
  thrash.  One adsa *cycle* is a full sweep over the classes (every
  variable gets one update opportunity, like one async period), so
  stop_cycle/max_cycles are scaled by n_classes internally and budgets
  stay comparable.

Measured semantics cost of the lockstep substitution (20-seed paired
CI, tests/api/test_async_equivalence.py): at MATCHED cycle budgets
lockstep solution quality is slightly worse than the clock-driven
async runtime (mean gap ~3% of the constraint count — simultaneous
neighbor flips thrash where async's skewed updates do not); at native
budgets the gap vanishes, because device supersteps are ~free and the
engine simply runs more of them.

Staggered-schedule finding (round 5, recorded negative result): the
graph-colored schedule does NOT measurably change matched-budget
quality on the equivalence battery's family — the deterministic
device-device pairing measures staggered - lockstep = +1.45 mean cost
(~0.9% of constraints, statistically flat), and repeated thread-paired
batteries wander inside the thread-side noise floor (per-seed sd ~15).
Mechanism: at p=0.7 flip probability on sparse graphs (~3.9 avg
degree) simultaneous-neighbor flips are too rare for schedule skew to
matter — which also bounds the round-4 "+3% lockstep gap" attribution
as measurement noise.  The schedule stays available for denser /
higher-probability regimes where thrash is real.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'adsa', max_cycles=30, algo_params={'seed': 1})
    >>> round(res['cost'], 3)
    0.0
"""

from functools import partial
from typing import Optional

import jax.numpy as jnp

from pydcop_tpu.algorithms import AlgoParameterDef, AlgorithmDef
from pydcop_tpu.algorithms import dsa as _dsa
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import compile_dcop, validated_aggregation
from pydcop_tpu.engine.runner import DeviceRunResult, run_device_fn
from pydcop_tpu.ops.dsa import greedy_classes, run_dsa

GRAPH_TYPE = "constraints_hypergraph"

algo_params = [
    # Variable-aggregation strategy for the shared local-search
    # kernels (ops/localsearch.py): "scatter" is the parity
    # default; "ell" replaces every segment_sum/max/min with
    # compile-time dense-gather edge lists (the TPU HBM-regime
    # candidate, not yet decided on the chip: ROADMAP.md Queue 3
    # "Four aggregations").  Single-device;
    # sharded runs always use scatter.
    AlgoParameterDef(
        "aggregation", "str", ["scatter", "ell"], "scatter"
    ),
    AlgoParameterDef("probability", "float", None, 0.7),
    AlgoParameterDef("variant", "str", ["A", "B", "C"], "B"),
    AlgoParameterDef("period", "float", None, 0.5),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
    AlgoParameterDef("schedule", "str", ["lockstep", "staggered"],
                     "lockstep"),
]

computation_memory = _dsa.computation_memory
communication_load = _dsa.communication_load


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("adsa", comp_def)


def solve_on_device(dcop: DCOP, algo_def: AlgorithmDef,
                    max_cycles: int = 1000, mesh=None,
                    n_devices: Optional[int] = None,
                    warmup: bool = False,
                    **_) -> DeviceRunResult:
    params = algo_def.params
    if params.get("schedule", "lockstep") == "staggered":
        return _solve_staggered(
            dcop, algo_def, max_cycles=max_cycles, mesh=mesh,
            n_devices=n_devices, warmup=warmup,
        )
    inner = AlgorithmDef(
        "dsa",
        {
            "probability": params.get("probability", 0.7),
            "p_mode": "fixed",
            "variant": params.get("variant", "B"),
            "stop_cycle": params.get("stop_cycle", 0),
            "seed": params.get("seed", 0),
        },
        algo_def.mode,
    )
    return _dsa.solve_on_device(
        dcop, inner, max_cycles=max_cycles, mesh=mesh,
        n_devices=n_devices, warmup=warmup,
    )


def _solve_staggered(dcop: DCOP, algo_def: AlgorithmDef, *,
                     max_cycles: int, mesh, n_devices, warmup
                     ) -> DeviceRunResult:
    """Graph-colored schedule: one superstep flips one color class;
    one *cycle* (budget unit) is a full sweep over all classes, so
    every variable keeps one update opportunity per cycle like the
    async runtime's one per period."""
    params = algo_def.params
    pad_to = mesh.size if mesh is not None else (n_devices or 1)
    graph, meta = compile_dcop(
        dcop, pad_to=pad_to,
        aggregation=validated_aggregation(params, pad_to))
    classes_np, n_classes = greedy_classes(graph)
    classes = jnp.asarray(classes_np)
    cycles = params.get("stop_cycle") or max_cycles
    fn = partial(
        run_dsa,
        max_cycles=cycles * n_classes,
        variant=params.get("variant", "B"),
        probability=params.get("probability", 0.7),
        seed=params.get("seed", 0),
        classes=classes,
        n_classes=n_classes,
    )
    res = run_device_fn(
        graph, meta, fn, mesh=mesh, n_devices=n_devices, warmup=warmup,
        finished=bool(params.get("stop_cycle")),
    )
    res.metrics["schedule"] = "staggered"
    res.metrics["n_classes"] = n_classes
    res.metrics["supersteps"] = res.cycles
    # Report budget-comparable cycles (full sweeps).
    res.cycles = res.cycles // n_classes
    return res
