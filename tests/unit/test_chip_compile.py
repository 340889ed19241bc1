"""Chip-less compiles of the main path's programs for a TPU v5e.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is *described*, not attached (``jax.experimental.topologies``).
Nothing runs — a compile that passes says the chip's compiler accepts
the program and how much device memory it plans, never that the
result is right or fast (``chip_smoke.py`` runs them on the chip).

Tier-1 holds the compiles that take seconds, at the 10k-variable
north-star size: the Pallas kernel, the MaxSum whole-solve program in
both layouts, the DSA program, the serve plane's vmapped batch program
and the two four-chip programs (replicated psum, partitioned halo
exchange) on the described 2x2 mesh.  The cases marked ``slow`` are
the rehearsal at 100k and 1M variables (about a minute of compile
each); run them by hand and read the printed table::

    JAX_PLATFORMS=cpu python -m pytest tests/unit/test_chip_compile.py \\
        -m slow -s

Rules this file keeps (a second process cannot load the TPU library
while this one holds it, and xdist workers must collect identical
tests): the topology is described inside a module-scoped fixture that
skips when it cannot be described — never at import, in a ``skipif``,
in ``parametrize`` arguments or in ``conftest.py`` — every compile
runs in this process, and the persistent compile cache is off around
them (an entry written for a described chip cannot be read back
without one).

The last section is the host compile's (``engine/compile.py``): the
builder that fills the compiled graph in array passes against the
per-object builder it replaced, which is kept here word for word as
the oracle.  Every array must come out bit for bit, or the programs
above, their cache keys and every answer would move.
"""

import os
import time
from functools import partial
from typing import Dict, List

import jax
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from pydcop_tpu.algorithms.maxsum import STABILITY_COEFF
from pydcop_tpu.dcop.objects import (
    Domain,
    Variable,
    VariableNoisyCostFunc,
    VariableWithCostDict,
    VariableWithCostFunc,
    _stable_noise,
)
from pydcop_tpu.dcop.relations import (
    Constraint,
    NAryFunctionRelation,
    NAryMatrixRelation,
    UnaryFunctionRelation,
    ZeroAryRelation,
    constraint_from_str,
)
from pydcop_tpu.engine import compile as host_compile
from pydcop_tpu.engine.compile import (
    _EMPTY_COSTS,
    BIG,
    CompiledFactorGraph,
    FactorBucket,
    FactorGraphMeta,
    _freeze,
    _round_up,
    build_aggregation_arrays,
    compile_cache,
    compile_dcop,
    metrics_registry,
)
from pydcop_tpu.generators.graphcoloring import generate_graph_coloring

# The smoke's instances (chip_smoke.py): random 3-colouring, 1.5
# edges per variable at 10k.
N_VARS = 10_000
P_EDGE = 3e-4
CYCLES = 200
SERVE_GRID_VARS = 400
SERVE_BATCH = 4
SERVE_CYCLES = 100
V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from pydcop_tpu.engine.sharding import SHARD_AXIS

    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), (SHARD_AXIS,))


@pytest.fixture(scope="module", autouse=True)
def persistent_cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dcop_10k():
    return generate_graph_coloring(
        N_VARS, 3, "random", p_edge=P_EDGE, allow_subgraph=True,
        noagents=True, seed=1)


@pytest.fixture(scope="module")
def problem_10k(dcop_10k):
    return compile_dcop(dcop_10k, noise_level=0.01)


def abstract(tree, sharding_of):
    """The pytree as shapes placed by ``sharding_of(array)`` — a
    described device holds no array, so programs are lowered on
    shapes."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding_of(a)), tree)


def abstract_coloring_graph(n_vars: int, n_factors: int, sharding,
                            d: int = 3):
    """Shapes of a compiled binary colouring graph, without building
    it (the 1M-variable rehearsal only needs the shapes)."""
    sds = partial(jax.ShapeDtypeStruct, sharding=sharding)
    return CompiledFactorGraph(
        var_costs=sds((n_vars + 1, d), np.float32),
        var_valid=sds((n_vars + 1, d), np.bool_),
        buckets=(FactorBucket(
            costs=sds((n_factors, d, d), np.float32),
            var_ids=sds((n_factors, 2), np.int32)),),
    )


def compile_row(name, jitted, *args, **static):
    """Lower + compile for the described chip; one table row."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **static).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    row = {
        "program": name,
        "compile_s": round(seconds, 1),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }
    print(row)
    return compiled, row


def device_bytes(row) -> int:
    return (row["argument_bytes"] + row["output_bytes"]
            + row["temp_bytes"] + row["code_bytes"])


def maxsum_program(graph, meta, layout, max_cycles=CYCLES):
    """The whole-solve program ``api.solve(dcop, "maxsum")`` dispatches
    (engine/runner.MaxSumEngine.run) and its placed graph."""
    from pydcop_tpu.engine.runner import MaxSumEngine

    engine = MaxSumEngine(graph, meta, layout=layout)
    return engine._fn(max_cycles, True), engine.graph


# --------------------------------------------------------------------- #
# one chip, 10k variables (tier-1)


def test_pallas_kernel_compiles_at_the_10k_bucket_shape(
        problem_10k, one_chip):
    from pydcop_tpu.ops.pallas_maxsum import binary_factor_update

    graph, _ = problem_10k
    costs = graph.buckets[0].costs
    f, d, _ = costs.shape
    sds = jax.ShapeDtypeStruct
    compiled, _ = compile_row(
        f"pallas binary_factor_update F={f} D={d}",
        binary_factor_update,
        sds((f, d, d), costs.dtype, sharding=one_chip),
        sds((f, 2, d), costs.dtype, sharding=one_chip))
    # Compiled as a Mosaic kernel, not interpreted.
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["edge", "lane"])
def test_maxsum_whole_solve_compiles_10k(problem_10k, one_chip, layout):
    graph, meta = problem_10k
    fn, placed = maxsum_program(graph, meta, layout)
    _, row = compile_row(
        f"maxsum whole-solve {layout} {N_VARS} vars", fn,
        abstract(placed, lambda a: one_chip))
    assert device_bytes(row) < V5E_HBM_BYTES


def test_dsa_program_compiles_10k(problem_10k, one_chip):
    from pydcop_tpu.ops.dsa import run_dsa

    graph, _ = problem_10k
    fn = jax.jit(partial(run_dsa, max_cycles=100, variant="B",
                         probability=0.7, seed=0))
    _, row = compile_row(
        f"dsa run_device_fn {N_VARS} vars", fn,
        abstract(graph, lambda a: one_chip))
    assert device_bytes(row) < V5E_HBM_BYTES


def test_serve_batch_program_compiles(one_chip):
    """The vmapped program one same-structure serve batch dispatches
    (engine/batch._batched_maxsum_solve), at the smoke's batch size."""
    from pydcop_tpu.engine.batch import _batched_maxsum_solve, stack_graphs

    dcop = generate_graph_coloring(
        SERVE_GRID_VARS, 3, "grid", soft=True, noagents=True, seed=1)
    graph, _ = compile_dcop(dcop, noise_level=0.01)
    stacked = stack_graphs([graph] * SERVE_BATCH)
    _, row = compile_row(
        f"serve batch {SERVE_BATCH} x {SERVE_GRID_VARS}-var grid",
        _batched_maxsum_solve, abstract(stacked, lambda a: one_chip),
        max_cycles=SERVE_CYCLES, damping=0.5, damp_vars=True,
        damp_factors=True, stability=STABILITY_COEFF, prune=False)
    assert device_bytes(row) < V5E_HBM_BYTES


# --------------------------------------------------------------------- #
# four chips: the described 2x2 mesh (tier-1 at 10k)


def replicated_program(dcop, mesh, max_cycles=CYCLES):
    """``n_devices=4``: bucket rows padded to the mesh size at compile
    time (algorithms/maxsum.build_engine), then row-sharded with the
    variable tables replicated (engine/sharding.shard_graph), plain
    jit."""
    from pydcop_tpu.engine.sharding import SHARD_AXIS
    from pydcop_tpu.ops import maxsum as maxsum_ops

    fn = jax.jit(partial(
        maxsum_ops.run_maxsum, max_cycles=max_cycles, damping=0.5,
        damp_vars=True, damp_factors=True, stability=STABILITY_COEFF,
        stop_on_convergence=True))
    graph, _ = compile_dcop(dcop, noise_level=0.01, pad_to=mesh.size)
    rows = NamedSharding(mesh, P(SHARD_AXIS))
    whole = NamedSharding(mesh, P())
    args = CompiledFactorGraph(
        var_costs=abstract(graph.var_costs, lambda a: whole),
        var_valid=abstract(graph.var_valid, lambda a: whole),
        buckets=abstract(graph.buckets, lambda a: rows))
    return fn, args


def partitioned_program(graph, meta, mesh, max_cycles=CYCLES):
    """``shards=4``: the partitioned engine's whole-solve program
    (engine/sharding.ShardOps.run_maxsum).  The layout is built on
    four forced host devices — a described device holds no array —
    and the program is lowered on its shapes over the described
    mesh."""
    from pydcop_tpu.engine.runner import ShardedMaxSumEngine
    from pydcop_tpu.engine.sharding import SHARD_AXIS, ShardOps

    host = ShardedMaxSumEngine(graph, meta, n_shards=mesh.size)
    ops = ShardOps(mesh, len(meta.var_names))
    fn = jax.jit(partial(
        ops.run_maxsum, max_cycles=max_cycles, damping=0.5,
        damp_vars=True, damp_factors=True, stability=STABILITY_COEFF,
        stop_on_convergence=True))
    # Every ShardedGraph array has the leading shard axis.
    args = abstract(
        host.graph, lambda a: NamedSharding(mesh, P(SHARD_AXIS)))
    return fn, args, host.extra_metrics


COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


def collectives_in(compiled):
    text = compiled.as_text()
    return {op: text.count(f" {op}(") + text.count(f" {op}-start(")
            for op in COLLECTIVES
            if f" {op}(" in text or f" {op}-start(" in text}


def test_replicated_psum_program_compiles_on_the_mesh(
        dcop_10k, mesh4):
    fn, args = replicated_program(dcop_10k, mesh4)
    compiled, row = compile_row(
        f"maxsum n_devices=4 {N_VARS} vars (per device)", fn, args)
    found = collectives_in(compiled)
    print({"collectives": found})
    # The one collective of the replicated path: the belief sums.
    assert "all-reduce" in found
    assert device_bytes(row) < V5E_HBM_BYTES


def test_partitioned_halo_program_compiles_on_the_mesh(
        problem_10k, mesh4):
    graph, meta = problem_10k
    fn, args, part = partitioned_program(graph, meta, mesh4)
    compiled, row = compile_row(
        f"maxsum shards=4 {N_VARS} vars (per device)", fn, args)
    found = collectives_in(compiled)
    print({"collectives": found,
           "edge_cut_fraction": part["edge_cut_fraction"]})
    # psum inside shard_map: the halo exchange and the global
    # convergence verdict.
    assert "all-reduce" in found
    assert device_bytes(row) < V5E_HBM_BYTES


# --------------------------------------------------------------------- #
# the rehearsal at the sizes that take a minute each (not tier-1)


@pytest.fixture(scope="module")
def dcop_100k():
    return generate_graph_coloring(
        100_000, 3, "random", p_edge=2e-5, allow_subgraph=True,
        noagents=True, seed=1)


@pytest.fixture(scope="module")
def problem_100k(dcop_100k):
    return compile_dcop(dcop_100k, noise_level=0.01)


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["edge", "lane"])
def test_rehearse_maxsum_whole_solve_100k(problem_100k, one_chip,
                                          layout):
    graph, meta = problem_100k
    fn, placed = maxsum_program(graph, meta, layout, max_cycles=100)
    compiled, row = compile_row(
        f"maxsum whole-solve {layout} 100000 vars", fn,
        abstract(placed, lambda a: one_chip))
    assert device_bytes(row) < V5E_HBM_BYTES


@pytest.mark.slow
@pytest.mark.parametrize("n_vars", [10_000, 100_000])
def test_rehearse_maxsum_segment_program(problem_10k, problem_100k,
                                         one_chip, n_vars):
    """The segment program of checkpointed / observed solves
    (MaxSumEngine._segment_fn): state in, state out, donated."""
    from pydcop_tpu.engine.runner import MaxSumEngine

    graph, meta = problem_10k if n_vars == 10_000 else problem_100k
    engine = MaxSumEngine(graph, meta)
    fn = engine._segment_fn(50, True)
    place = lambda a: one_chip  # noqa: E731
    _, row = compile_row(
        f"maxsum segment(50) edge {n_vars} vars", fn,
        abstract(engine.graph, place),
        abstract(engine.init_state(), place))
    assert device_bytes(row) < V5E_HBM_BYTES


@pytest.mark.slow
def test_rehearse_four_chip_programs_100k(dcop_100k, problem_100k,
                                          mesh4):
    graph, meta = problem_100k
    fn, args = replicated_program(dcop_100k, mesh4, max_cycles=300)
    compiled, row = compile_row(
        "maxsum n_devices=4 100000 vars (per device)", fn, args)
    print({"collectives": collectives_in(compiled)})
    assert device_bytes(row) < V5E_HBM_BYTES
    fn, args, part = partitioned_program(
        graph, meta, mesh4, max_cycles=300)
    compiled, row = compile_row(
        "maxsum shards=4 100000 vars (per device)", fn, args)
    print({"collectives": collectives_in(compiled),
           "edge_cut_fraction": part["edge_cut_fraction"]})
    assert device_bytes(row) < V5E_HBM_BYTES


@pytest.mark.slow
def test_rehearse_does_a_1m_variable_program_fit(one_chip):
    """One compile at 1M variables / 1.5M factors: does the program
    fit the chip's 16 GB?  Recorded, not asserted — the answer is the
    finding (CHANGES.md, PR 22)."""
    from pydcop_tpu.ops import maxsum as maxsum_ops

    fn = jax.jit(partial(
        maxsum_ops.run_maxsum, max_cycles=100, damping=0.5,
        damp_vars=True, damp_factors=True, stability=STABILITY_COEFF,
        stop_on_convergence=True))
    args = abstract_coloring_graph(1_000_000, 1_500_000, one_chip)
    try:
        _, row = compile_row(
            "maxsum whole-solve edge 1000000 vars", fn, args)
    except Exception as exc:  # noqa: BLE001 — the refusal is the row
        print({"program": "maxsum whole-solve edge 1000000 vars",
               "refused": f"{type(exc).__name__}: {exc}"[:600]})
        return
    print({"fits_16GB": device_bytes(row) < V5E_HBM_BYTES,
           "device_bytes": device_bytes(row)})


# --------------------------------------------------------------------- #
# the host compile against the builder it replaced


# The parent's (PR 30's) ``_factor_table`` and ``_compile_factor_graph``,
# word for word: one Python statement per variable and per factor, and
# one ``np.random.default_rng`` per variable for the noise.
def _factor_table(c: Constraint, sign: float, dtype,
                  memo: Dict, vectorize: bool) -> np.ndarray:
    """Sign-adjusted dense table for one factor, memoized on the
    structural table signature: factors whose expressions differ only
    in variable names (every generated-edge family) evaluate ONCE per
    bucket instead of once per factor, and each evaluation is the
    vectorized numpy path (relations.NAryFunctionRelation.to_array)
    instead of a d^arity python loop.  ``vectorize=False`` restores
    the per-factor per-assignment reference path — the A/B baseline
    ``make perf-smoke`` measures against."""
    if not vectorize:
        if isinstance(c, NAryFunctionRelation):
            # The pre-vectorization behavior: the base per-assignment
            # enumeration loop.
            return sign * np.asarray(
                Constraint.to_array(c), dtype=dtype)
        return sign * np.asarray(c.to_array(), dtype=dtype)
    sig = c.table_signature()
    if sig is not None:
        table = memo.get(sig)
        if table is not None:
            return table
    table = sign * np.asarray(c.to_array(), dtype=dtype)
    if sig is not None:
        memo[sig] = table
    return table


def _compile_factor_graph(variables, constraints, mode, noise_level,
                          noise_seed, pad_to, dtype, aggregation,
                          vectorize, use_cache):
    variables = list(variables)
    constraints = list(constraints)
    var_index = {v.name: i for i, v in enumerate(variables)}
    for c in constraints:
        for v in c.dimensions:
            if v.name not in var_index:
                raise ValueError(
                    f"Constraint {c.name} references variable {v.name} "
                    "which has no computation node — external (read-"
                    "only) variables require the 'maxsum_dynamic' "
                    "algorithm, which slices them out before compiling"
                )
    v_count = len(variables)
    dmax = max((len(v.domain) for v in variables), default=1)
    sign = 1.0 if mode == "min" else -1.0

    # Variable cost table (+ sentinel row for padding edges).
    var_costs = np.full((v_count + 1, dmax), BIG, dtype=dtype)
    var_valid = np.zeros((v_count + 1, dmax), dtype=bool)
    var_base = np.zeros((v_count, dmax), dtype=dtype)
    for i, v in enumerate(variables):
        d = len(v.domain)
        costs = sign * v.cost_vector()[:d]
        var_base[i, :d] = costs
        if noise_level:
            costs = costs + _stable_noise(v.name, d, noise_level, noise_seed)
        var_costs[i, :d] = costs
        var_valid[i, :d] = True

    constant_cost = 0.0
    by_arity: Dict[int, List[Constraint]] = {}
    for c in constraints:
        if c.arity == 0:
            constant_cost += float(c())
            continue
        by_arity.setdefault(c.arity, []).append(c)

    # Per-factor scope indices, one [n_facs, arity] array per arity.
    # Needed both for the bucket layout and as the structure-cache
    # key: the layout (padded var_ids + agg_* arrays) is a pure
    # function of these indices + (v_count, pad_to, aggregation).
    arities = sorted(by_arity)
    scope_ids: Dict[int, np.ndarray] = {}
    for arity in arities:
        facs = by_arity[arity]
        scope_ids[arity] = np.array(
            [[var_index[v.name] for v in c.dimensions] for c in facs],
            dtype=np.int32,
        ).reshape(len(facs), arity)

    layout = None
    cache_key = None
    if use_cache:
        cache_key = (
            v_count, pad_to, aggregation,
            tuple((a, scope_ids[a].tobytes()) for a in arities),
        )
        layout = compile_cache.get(cache_key)
        # registry.active gate, like every optional series this PR
        # adds: an unobserved solve must not accumulate samples that
        # a later observed solve's .prom dump would misattribute.
        if metrics_registry.active:
            metrics_registry.counter(
                "pydcop_compile_cache_total",
                "Structure-cache lookups by outcome",
            ).inc(outcome="hit" if layout is not None else "miss")
    if layout is None:
        compile_cache.layout_builds += 1
        if metrics_registry.active:
            metrics_registry.counter(
                "pydcop_layout_builds_total",
                "Factor-graph layout constructions (cache misses + "
                "uncached compiles)",
            ).inc()
        var_ids_by_arity = {}
        for arity in arities:
            n_facs = scope_ids[arity].shape[0]
            n_rows = _round_up(n_facs, pad_to)
            ids = np.full((n_rows, arity), v_count, dtype=np.int32)
            ids[:n_facs] = scope_ids[arity]
            var_ids_by_arity[arity] = _freeze(ids)
        agg = build_aggregation_arrays(
            [FactorBucket(_EMPTY_COSTS, ids)
             for ids in var_ids_by_arity.values()],
            v_count + 1, aggregation,
        )
        layout = (var_ids_by_arity, tuple(_freeze(a) for a in agg))
        if use_cache:
            compile_cache.put(cache_key, layout)
    var_ids_by_arity, (perm, sorted_seg, starts, ends, ell) = layout

    buckets = []
    factor_names: List[str] = []
    bucket_sizes: List[int] = []
    for arity in arities:
        facs = by_arity[arity]
        n_rows = var_ids_by_arity[arity].shape[0]
        shape = (n_rows,) + (dmax,) * arity
        costs = np.full(shape, BIG, dtype=dtype)
        memo: Dict = {}
        for fi, c in enumerate(facs):
            factor_names.append(c.name)
            table = _factor_table(c, sign, dtype, memo, vectorize)
            idx = tuple(slice(0, s) for s in table.shape)
            costs[(fi,) + idx] = table
        # Padding rows keep cost 0 and the sentinel variable.
        costs[len(facs):] = 0.0
        buckets.append(FactorBucket(costs, var_ids_by_arity[arity]))
        bucket_sizes.append(len(facs))
    compiled = CompiledFactorGraph(
        var_costs=var_costs,
        var_valid=var_valid,
        buckets=tuple(buckets),
        agg_perm=perm,
        agg_sorted_seg=sorted_seg,
        agg_starts=starts,
        agg_ends=ends,
        agg_ell=ell,
    )
    meta = FactorGraphMeta(
        var_names=tuple(v.name for v in variables),
        domains=tuple(tuple(v.domain) for v in variables),
        factor_names=tuple(factor_names),
        bucket_sizes=tuple(bucket_sizes),
        mode=mode,
        constant_cost=constant_cost,
        var_base_costs=var_base,
    )
    return compiled, meta


D2 = Domain("d2", "", [0, 1])
D3 = Domain("d3", "", ["R", "G", "B"])
D5 = Domain("d5", "", [0, 1, 2, 3, 4])


def _family(module, spec, seed):
    from chipbench.lib import module_by_name

    dcop = module_by_name("families", module, "family").generate(spec, seed)
    return (list(dcop.variables.values()),
            list(dcop.constraints.values()), dcop.objective)


def _colouring_1000():
    """The solve cell's family (``gc_random_10k``) at a tenth."""
    return _family("graph_coloring", {
        "variables": 1000, "colors": 3, "graph": "random",
        "p_edge": 0.003, "soft": False, "constraints": 1500}, 3)


def _serve_grid():
    """One problem of the serve cells' pool: a soft 10x10 grid."""
    return _family("graph_coloring", {
        "variables": 100, "colors": 3, "graph": "grid", "soft": True},
        1000 * 2147483659 + 5)


def _secp():
    """Smart lighting: expression factors of arity 1 to 4."""
    return _family("secp", {
        "lights": 56, "models": 17, "rules": 28, "max_model_size": 3,
        "max_rule_size": 3,
        "factors_by_arity": {"1": 62, "2": 7, "3": 15, "4": 6}}, 11)


def _mixed_domains(mode="min"):
    """Domains of 2, 3 and 5 values in one problem, so tables of a
    bucket differ in shape; a ternary factor; one zero-ary."""
    rng = np.random.default_rng(5)
    doms = [D2, D3, D5]
    vs = [Variable(f"m{i}", doms[i % 3]) for i in range(12)]
    cs = [NAryMatrixRelation(
        [vs[i], vs[j]], rng.random((len(vs[i].domain),
                                    len(vs[j].domain))), f"c{i}_{j}")
        for i, j in [(0, 1), (1, 2), (2, 3), (3, 6), (4, 7), (5, 8),
                     (2, 5), (9, 11), (10, 0)]]
    cs.append(NAryMatrixRelation(
        [vs[0], vs[4], vs[8]], rng.random((2, 3, 5)), "t0"))
    cs.insert(3, ZeroAryRelation("k0", 2.5))
    cs.append(UnaryFunctionRelation("u0", vs[2], lambda v: v * 0.5))
    return vs, cs, mode


def _costed_variables(mode="min"):
    """Every variable class that brings costs of its own, beside
    plain ones, under non-ASCII and empty names."""
    vs = [
        Variable("plain", D3),
        VariableWithCostFunc("wf", D5, "wf * 0.25 - 1"),
        VariableNoisyCostFunc("nz", D5, "nz * 0.5", noise_level=0.05,
                              seed=2),
        VariableWithCostDict("wd", D3, {"R": 1.5, "B": -2.0}),
        Variable("é_变", D2),
        Variable("", D5),
    ]
    cs = [
        constraint_from_str("e1", "abs(wf - nz)", vs),
        constraint_from_str("e2", "3 if plain == wd else 0", vs),
        NAryMatrixRelation([vs[4], vs[5]], np.arange(10.).reshape(2, 5),
                           "m1"),
    ]
    return vs, cs, mode


def _shared_signatures():
    """Expression constraints that differ only in variable names
    share a ``table_signature`` and so one evaluated table; one
    python-function constraint has none."""
    vs = [Variable(f"s{i}", D3) for i in range(9)]
    cs = [constraint_from_str(
        f"ne{i}", f"10 if s{i} == s{i + 1} else 0", vs) for i in range(8)]
    cs.append(constraint_from_str("far", "1 if s0 == s8 else 0.5", vs))
    cs.append(NAryFunctionRelation(
        lambda a, b: float(a != b), [vs[1], vs[7]], name="py"))
    assert cs[0].table_signature() == cs[5].table_signature() is not None
    return vs, cs, "min"


PROBLEMS = {
    "colouring_1000": _colouring_1000,
    "serve_grid_100": _serve_grid,
    "secp_arity_1_to_4": _secp,
    "mixed_domains": _mixed_domains,
    "mixed_domains_max": partial(_mixed_domains, "max"),
    "costed_variables": _costed_variables,
    "costed_variables_max": partial(_costed_variables, "max"),
    "shared_signatures": _shared_signatures,
    "no_variables": lambda: ([], [ZeroAryRelation("k", 1.0)], "min"),
}


def _same_array(ours, theirs, what):
    if theirs is None:
        assert ours is None, what
        return
    assert ours.dtype == theirs.dtype and ours.shape == theirs.shape, what
    assert ours.flags.writeable == theirs.flags.writeable, what
    assert np.array_equal(ours, theirs), what
    # -0.0 (``objective: max`` on a zero cost) is 0.0 to array_equal.
    assert ours.tobytes() == theirs.tobytes(), what


def _same_compiled(ours, theirs):
    (graph, meta), (graph0, meta0) = ours, theirs
    for field in CompiledFactorGraph._fields:
        if field != "buckets":
            _same_array(getattr(graph, field), getattr(graph0, field),
                        field)
    assert len(graph.buckets) == len(graph0.buckets)
    for b, b0 in zip(graph.buckets, graph0.buckets):
        _same_array(b.costs, b0.costs, f"costs, arity {b0.arity}")
        _same_array(b.var_ids, b0.var_ids, f"var_ids, arity {b0.arity}")
    for field in FactorGraphMeta._fields:
        if field != "var_base_costs":
            assert getattr(meta, field) == getattr(meta0, field), field
            assert type(getattr(meta, field)) is type(
                getattr(meta0, field)), field
    _same_array(meta.var_base_costs, meta0.var_base_costs, "var_base")


@pytest.mark.parametrize("noise", [(0.0, None), (0.01, None), (0.01, 7)],
                         ids=["no_noise", "noise", "noise_seed_7"])
@pytest.mark.parametrize("pad_to", [1, 4])
@pytest.mark.parametrize("problem", list(PROBLEMS))
def test_host_compile_builds_the_parents_arrays(problem, pad_to, noise):
    variables, constraints, mode = PROBLEMS[problem]()
    args = (mode, *noise, pad_to, np.float32)
    for aggregation, vectorize, use_cache in [
            ("scatter", True, False), ("ell", True, True),
            ("sorted", False, False)]:
        built = []
        for build in (_compile_factor_graph,
                      host_compile._compile_factor_graph):
            # Each side builds its own layout: a cache hit would hand
            # the second the first's arrays.
            compile_cache.clear()
            built.append(build(variables, constraints, *args,
                               aggregation, vectorize, use_cache))
        _same_compiled(built[1], built[0])
    compile_cache.clear()


@pytest.mark.parametrize("noise_level", [0.0, 0.01])
def test_host_compile_refuses_a_scope_variable_without_a_node(
        noise_level):
    variables, constraints, mode = _mixed_domains()
    args = (variables[:8], constraints, mode, noise_level, None, 1,
            np.float32, "scatter", True, False)
    with pytest.raises(ValueError) as theirs:
        _compile_factor_graph(*args)
    with pytest.raises(ValueError) as ours:
        host_compile._compile_factor_graph(*args)
    assert str(ours.value) == str(theirs.value)
    assert "m8 which has no computation node" in str(ours.value)
