"""MixedDSA: DSA variant for DCOPs mixing hard and soft constraints.

Reference parity: pydcop/algorithms/mixeddsa.py (params :119-124:
variant A/B/C, proba_hard 0.7, proba_soft 0.5; semantics :154-470).
Kernels: pydcop_tpu/ops/mixeddsa.py.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'mixeddsa', max_cycles=30, algo_params={'seed': 1})
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
from pydcop_tpu.ops.mixeddsa import run_mixeddsa

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
    AlgoParameterDef("proba_hard", "float", None, 0.7),
    AlgoParameterDef("proba_soft", "float", None, 0.5),
    AlgoParameterDef("variant", "str", ["A", "B", "C"], "B"),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
]


def computation_memory(node) -> float:
    # One value per neighbor (reference mixeddsa.py:92).
    return len(node.neighbors) * UNIT_SIZE


def communication_load(src, target: str) -> float:
    # Value messages carry a single value (reference mixeddsa.py:116).
    return UNIT_SIZE + HEADER_SIZE


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("mixeddsa", comp_def)


def solve_on_device(dcop: DCOP, algo_def: AlgorithmDef,
                    max_cycles: int = 1000, mesh=None,
                    n_devices: Optional[int] = None,
                    warmup: bool = False,
                    **_) -> DeviceRunResult:
    params = algo_def.params
    pad_to = mesh.size if mesh is not None else (n_devices or 1)
    graph, meta = compile_dcop(
        dcop, pad_to=pad_to,
        aggregation=validated_aggregation(params, pad_to))
    cycles = params.get("stop_cycle") or max_cycles
    fn = partial(
        run_mixeddsa,
        max_cycles=cycles,
        variant=params.get("variant", "B"),
        proba_hard=float(params.get("proba_hard", 0.7)),
        proba_soft=float(params.get("proba_soft", 0.5)),
        seed=params.get("seed", 0),
    )
    return run_device_fn(
        graph, meta, fn, mesh=mesh, n_devices=n_devices, warmup=warmup,
        finished=bool(params.get("stop_cycle")),
    )
