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
"""

import os
import time
from functools import partial

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
from pydcop_tpu.engine.compile import (
    CompiledFactorGraph,
    FactorBucket,
    compile_dcop,
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
