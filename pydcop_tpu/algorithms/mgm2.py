"""MGM2: coordinated 2-opt local search (Maheswaran et al. 2004).

Reference parity: pydcop/algorithms/mgm2.py (params :139-143: threshold
0.5, favor unilateral/no/coordinated, stop_cycle; 5-phase semantics
:399-1050).  Kernels: pydcop_tpu/ops/mgm2.py.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'mgm2', max_cycles=30, algo_params={'seed': 1})
    >>> round(res['cost'], 3)
    0.0
"""

from functools import partial
from typing import Optional

from pydcop_tpu.algorithms import AlgoParameterDef, AlgorithmDef
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import compile_dcop, validated_aggregation
from pydcop_tpu.engine.runner import DeviceRunResult, run_device_fn
from pydcop_tpu.ops.mgm2 import run_mgm2

GRAPH_TYPE = "constraints_hypergraph"

HEADER_SIZE = 100
UNIT_SIZE = 5

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
    AlgoParameterDef("threshold", "float", None, 0.5),
    AlgoParameterDef(
        "favor", "str", ["unilateral", "no", "coordinated"], "unilateral"
    ),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
]


def computation_memory(node) -> float:
    # Two values kept per neighbor: value + gain (mgm2.py:88).
    return len(node.neighbors) * 2 * UNIT_SIZE


def communication_load(src, target: str) -> float:
    # Offer messages carry up to |d_src|*|d_target| (val, val, gain)
    # triples (mgm2.py:91-124).
    target_dom = None
    for c in src.constraints:
        for v in c.dimensions:
            if v.name == target:
                target_dom = len(v.domain)
    if target_dom is None:
        raise ValueError(
            f"target {target!r} is not a neighbor of {src.name}"
        )
    nb_pairs = target_dom * len(src.variable.domain)
    return nb_pairs * UNIT_SIZE * 3 + HEADER_SIZE


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("mgm2", comp_def)


def solve_on_device(dcop: DCOP, algo_def: AlgorithmDef,
                    max_cycles: int = 1000, mesh=None,
                    n_devices: Optional[int] = None,
                    warmup: bool = False,
                    **_) -> DeviceRunResult:
    from pydcop_tpu.algorithms.mgm import lexic_ranks

    params = algo_def.params
    pad_to = mesh.size if mesh is not None else (n_devices or 1)
    graph, meta = compile_dcop(
        dcop, pad_to=pad_to,
        aggregation=validated_aggregation(params, pad_to))
    cycles = params.get("stop_cycle") or max_cycles
    fn = partial(
        run_mgm2,
        max_cycles=cycles,
        threshold=float(params.get("threshold", 0.5)),
        favor=params.get("favor", "unilateral"),
        lexic_ranks=lexic_ranks(meta),
        seed=params.get("seed", 0),
    )
    return run_device_fn(
        graph, meta, fn, mesh=mesh, n_devices=n_devices, warmup=warmup,
        finished=bool(params.get("stop_cycle")),
    )
