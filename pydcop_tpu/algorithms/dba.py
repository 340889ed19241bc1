"""DBA: Distributed Breakout Algorithm — constraint satisfaction.

Reference parity: pydcop/algorithms/dba.py (params :264-268: infinity
10000, max_distance 50; semantics :272-595).  Kernels:
pydcop_tpu/ops/dba.py.

DBA minimizes the number of violated constraints (a constraint is
violated when its cost reaches `infinity`); it only supports
minimization (dba.py:295-298).

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'dba', max_cycles=30, algo_params={'seed': 1})
    >>> round(res['cost'], 3)
    0.0
"""

from functools import partial
from typing import Optional

from pydcop_tpu.algorithms import AlgoParameterDef, AlgorithmDef
from pydcop_tpu.computations_graph import constraints_hypergraph as chg
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import compile_dcop, validated_aggregation
from pydcop_tpu.engine.runner import DeviceRunResult, run_device_fn
from pydcop_tpu.ops.dba import run_dba

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
    AlgoParameterDef("infinity", "int", None, 10000),
    AlgoParameterDef("max_distance", "int", None, 50),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
]


def computation_memory(node) -> float:
    return chg.computation_memory(node)


def communication_load(src, target: str) -> float:
    # ok/improve messages carry a value and an improvement (dba.py:92).
    return 2 * UNIT_SIZE + HEADER_SIZE


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("dba", comp_def)


def solve_on_device(dcop: DCOP, algo_def: AlgorithmDef,
                    max_cycles: int = 1000, mesh=None,
                    n_devices: Optional[int] = None,
                    warmup: bool = False,
                    **_) -> DeviceRunResult:
    if dcop.objective != "min":
        raise ValueError(
            "DBA is a constraint satisfaction algorithm and only "
            "supports minimization (reference dba.py:295)"
        )
    from pydcop_tpu.algorithms.mgm import lexic_ranks

    params = algo_def.params
    pad_to = mesh.size if mesh is not None else (n_devices or 1)
    graph, meta = compile_dcop(
        dcop, pad_to=pad_to,
        aggregation=validated_aggregation(params, pad_to))
    fn = partial(
        run_dba,
        max_cycles=max_cycles,
        infinity=float(params.get("infinity", 10000)),
        max_distance=int(params.get("max_distance", 50)),
        lexic_ranks=lexic_ranks(meta),
        seed=params.get("seed", 0),
    )
    return run_device_fn(graph, meta, fn, mesh=mesh, n_devices=n_devices,
                         warmup=warmup)
