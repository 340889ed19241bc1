"""DSA: Distributed Stochastic Algorithm (variants A/B/C).

Reference parity: pydcop/algorithms/dsa.py (params :130-135: probability
0.7, p_mode fixed/arity, variant B, stop_cycle; semantics :214-431).
Kernels: pydcop_tpu/ops/dsa.py.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'dsa', max_cycles=30, algo_params={'seed': 1})
    >>> round(res['cost'], 3)
    0.0
"""

from functools import partial
from typing import Optional

import numpy as np

from pydcop_tpu.algorithms import AlgoParameterDef, AlgorithmDef
from pydcop_tpu.computations_graph import constraints_hypergraph as chg
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.engine.compile import compile_dcop, validated_aggregation
from pydcop_tpu.engine.runner import DeviceRunResult, run_device_fn
from pydcop_tpu.ops.dsa import run_dsa

GRAPH_TYPE = "constraints_hypergraph"

HEADER_SIZE = 0
UNIT_SIZE = 1

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
    AlgoParameterDef("p_mode", "str", ["fixed", "arity"], "fixed"),
    AlgoParameterDef("variant", "str", ["A", "B", "C"], "B"),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
]


def computation_memory(node) -> float:
    return chg.computation_memory(node)


def communication_load(src, target: str) -> float:
    return chg.communication_load(src, target)


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("dsa", comp_def)


def _arity_probabilities(graph, probability: float) -> np.ndarray:
    """p_mode=arity: p = 1.2 / sum(arity-1 over incident constraints)
    (reference dsa.py:257-263)."""
    n = graph.var_costs.shape[0]
    n_count = np.zeros(n, dtype=np.float64)
    for b in graph.buckets:
        arity = b.var_ids.shape[1]
        if arity < 2:
            continue
        for p in range(arity):
            np.add.at(n_count, np.asarray(b.var_ids[:, p]), arity - 1)
    probs = np.full(n, probability, dtype=np.float32)
    mask = n_count > 0
    probs[mask] = 1.2 / n_count[mask]
    return probs


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
    probability = params.get("probability", 0.7)
    if params.get("p_mode") == "arity":
        probability = _arity_probabilities(graph, probability)
    fn = partial(
        run_dsa,
        max_cycles=cycles,
        variant=params.get("variant", "B"),
        probability=probability,
        seed=params.get("seed", 0),
    )
    return run_device_fn(
        graph, meta, fn, mesh=mesh, n_devices=n_devices, warmup=warmup,
        finished=bool(params.get("stop_cycle")),
    )
