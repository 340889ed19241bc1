"""MGM: Maximum Gain Message — monotone distributed local search.

Reference parity: pydcop/algorithms/mgm.py (params :77-83: break_mode
lexic/random, stop_cycle; semantics :213-609).  Kernels:
pydcop_tpu/ops/mgm.py.

Example (doctest, runs on the CPU backend under ``make doctest``)::

    >>> from pydcop_tpu.api import solve
    >>> from pydcop_tpu.dcop.dcop import DCOP
    >>> from pydcop_tpu.dcop.objects import Domain, Variable
    >>> from pydcop_tpu.dcop.relations import constraint_from_str
    >>> d = Domain('d', '', [0, 1])
    >>> x, y = Variable('x', d), Variable('y', d)
    >>> dcop = DCOP('doc', objective='min')
    >>> dcop.add_constraint(constraint_from_str('c', '(x + y - 1)**2', [x, y]))
    >>> res = solve(dcop, 'mgm', max_cycles=30, algo_params={'seed': 1})
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
from pydcop_tpu.ops.mgm import run_mgm

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
    AlgoParameterDef("break_mode", "str", ["lexic", "random"], "lexic"),
    AlgoParameterDef("stop_cycle", "int", None, 0),
    AlgoParameterDef("seed", "int", None, 0),
]


def computation_memory(node) -> float:
    return chg.computation_memory(node)


def communication_load(src, target: str) -> float:
    return chg.communication_load(src, target)


def build_computation(comp_def):
    from pydcop_tpu.infrastructure.computations import build_algo_computation

    return build_algo_computation("mgm", comp_def)


def lexic_ranks(meta) -> np.ndarray:
    """Rank of each variable in lexical name order ([V+1] float32,
    sentinel +inf) — the reference's sorted-name tie-break (mgm.py:571)."""
    order = {
        name: i for i, name in enumerate(sorted(meta.var_names))
    }
    ranks = np.empty(len(meta.var_names) + 1, dtype=np.float32)
    for i, name in enumerate(meta.var_names):
        ranks[i] = order[name]
    ranks[-1] = np.inf
    return ranks


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
        run_mgm,
        max_cycles=cycles,
        lexic_ranks=lexic_ranks(meta),
        break_mode=params.get("break_mode", "lexic"),
        seed=params.get("seed", 0),
    )
    return run_device_fn(
        graph, meta, fn, mesh=mesh, n_devices=n_devices, warmup=warmup,
        finished=bool(params.get("stop_cycle")),
    )
