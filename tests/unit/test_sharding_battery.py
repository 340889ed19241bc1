"""Battery for ISSUE 7: min-edge-cut partitioning, the partitioned
shard_map engine's plumbing and per-shard trace lanes.

End-to-end sharded-vs-single parity lives in
tests/api/test_sharded_parity.py; this battery covers the host-side
pieces (partitioner invariants, cache, communication accounting,
merge-lane separation) plus kernel edge cases (mixed
arity, constraint-free graphs) that the api battery's problem
generators don't reach.
"""

import json

import numpy as np
import pytest

import jax

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import constraint_from_str
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.engine.partition import (
    Partition,
    build_adjacency,
    cut_statistics,
    partition_cache,
    partition_compiled,
    partition_factor_graph,
)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)


def _grid_scopes(side):
    """Scope-index array of a 4-neighbor grid (one binary bucket)."""
    edges = []
    for r in range(side):
        for c in range(side):
            i = r * side + c
            if r + 1 < side:
                edges.append((i, (r + 1) * side + c))
            if c + 1 < side:
                edges.append((i, r * side + c + 1))
    return [np.asarray(edges, np.int64)], side * side


def _grid_dcop(side=8, seed=0):
    """4-neighbor grid coloring with random integer tables (`pydcop
    generate graph_coloring -g grid --soft`), as the shard-smoke gate
    and the sharded parity tests build it."""
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    return generate_graph_coloring(
        side * side, 3, "grid", soft=True, noagents=True, seed=seed)


# ------------------------------ partitioner ------------------------- #


class TestPartitioner:
    def test_every_variable_assigned_once(self):
        scopes, n = _grid_scopes(12)
        part = partition_factor_graph(scopes, n, 8)
        assert part.var_shard.shape == (n,)
        assert part.var_shard.min() >= 0
        assert part.var_shard.max() <= 7
        assert sum(part.stats["owned_vars_per_shard"]) == n

    def test_balance_within_cap(self):
        scopes, n = _grid_scopes(12)
        part = partition_factor_graph(scopes, n, 8, imbalance=0.1)
        # The cap is integral: no shard may own more than
        # ceil(V/S * (1 + imbalance)) variables.
        cap = int(np.ceil(n / 8 * 1.1))
        assert max(part.stats["owned_vars_per_shard"]) <= cap

    def test_grid_cut_is_small(self):
        """The acceptance regime: a locally-connected loopy graph
        partitions with edge_cut_fraction < 0.3 (grids measure far
        below that — this is the honest floor, not the target)."""
        scopes, n = _grid_scopes(16)
        part = partition_factor_graph(scopes, n, 8)
        assert part.stats["edge_cut_fraction"] < 0.3

    def test_deterministic(self):
        scopes, n = _grid_scopes(10)
        a = partition_factor_graph(scopes, n, 4)
        b = partition_factor_graph(scopes, n, 4)
        assert np.array_equal(a.var_shard, b.var_shard)
        for fa, fb in zip(a.factor_shard, b.factor_shard):
            assert np.array_equal(fa, fb)

    def test_refinement_never_hurts(self):
        scopes, n = _grid_scopes(14)
        raw = partition_factor_graph(scopes, n, 8, refine_passes=0)
        refined = partition_factor_graph(scopes, n, 8, refine_passes=4)
        assert (refined.stats["edge_cut_fraction"]
                <= raw.stats["edge_cut_fraction"] + 1e-12)

    def test_factor_lands_on_scope_owner(self):
        """Majority assignment: every factor's shard owns at least
        one of its scope variables (otherwise every incidence would
        be cut — strictly worse than any scope shard)."""
        scopes, n = _grid_scopes(10)
        part = partition_factor_graph(scopes, n, 8)
        for sc, fs in zip(scopes, part.factor_shard):
            owner_hit = (part.var_shard[sc] == fs[:, None]).any(axis=1)
            assert owner_hit.all()

    def test_single_shard_degenerate(self):
        scopes, n = _grid_scopes(5)
        part = partition_factor_graph(scopes, n, 1)
        assert (part.var_shard == 0).all()
        assert part.stats["edge_cut_fraction"] == 0.0

    def test_adjacency_clique_for_high_arity(self):
        """Arity-3 scopes contribute their clique: all three pairs."""
        scopes = [np.asarray([[0, 1, 2]], np.int64)]
        nbrs, starts, ends = build_adjacency(scopes, 4)
        deg = ends - starts
        assert list(deg) == [2, 2, 2, 0]

    def test_cut_statistics_shape(self):
        scopes, n = _grid_scopes(6)
        part = partition_factor_graph(scopes, n, 4)
        s = part.stats
        assert s["cut_incidences"] <= s["total_incidences"]
        assert len(s["halo_vars_per_shard"]) == 4
        assert s["boundary_vars"] >= max(s["halo_vars_per_shard"])


class TestPartitionCache:
    def test_structure_keyed_hit(self):
        dcop = _grid_dcop(6)
        graph, _ = compile_dcop(dcop, noise_level=0.01)
        partition_cache.clear()
        a = partition_compiled(graph, 4)
        before = partition_cache.stats()
        b = partition_compiled(graph, 4)
        after = partition_cache.stats()
        assert after["hits"] == before["hits"] + 1
        assert after["builds"] == before["builds"]
        assert np.array_equal(a.var_shard, b.var_shard)

    def test_shard_count_in_key(self):
        dcop = _grid_dcop(6)
        graph, _ = compile_dcop(dcop, noise_level=0.01)
        partition_cache.clear()
        partition_compiled(graph, 2)
        partition_compiled(graph, 4)
        assert partition_cache.stats()["builds"] == 2

    def test_env_optout(self, monkeypatch):
        monkeypatch.setenv("PYDCOP_COMPILE_CACHE", "0")
        dcop = _grid_dcop(5)
        graph, _ = compile_dcop(dcop, noise_level=0.01,
                                use_cache=False)
        partition_cache.clear()
        partition_compiled(graph, 2)
        partition_compiled(graph, 2)
        stats = partition_cache.stats()
        assert stats["hits"] == 0
        assert stats["builds"] == 2


# --------------------------- partitioned engine --------------------- #


@needs_mesh
class TestPartitionedEngine:
    def test_comm_accounting_is_cut_times_d(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(10)
        engine = build_engine(dcop, {"noise": 0.01}, shards=8)
        m = engine.extra_metrics
        d = 3
        assert (m["halo_exchange_elems_per_superstep"]
                == m["boundary_vars"] * d)
        assert (m["replicated_allreduce_elems_per_superstep"]
                == (len(dcop.variables) + 1) * d)
        assert (m["halo_exchange_elems_per_superstep"]
                < m["replicated_allreduce_elems_per_superstep"])
        assert (m["halo_exchange_bytes_per_superstep"]
                == 4 * m["halo_exchange_elems_per_superstep"])

    def test_mixed_arity_parity(self):
        """Unary + binary + ternary factors through the partitioned
        kernels: local reindexing and the halo exchange must handle
        every bucket arity, not just the binary fast case."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dom = Domain("d", "", [0, 1, 2])
        dcop = DCOP("mixed", objective="min")
        vs = [Variable(f"v{i}", dom) for i in range(12)]
        for v in vs:
            dcop.add_variable(v)
        for i in range(12):
            dcop.add_constraint(constraint_from_str(
                f"u{i}", f"(v{i} - 1)**2", [vs[i]]))
            dcop.add_constraint(constraint_from_str(
                f"b{i}", f"abs(v{i} - v{(i + 1) % 12})",
                [vs[i], vs[(i + 1) % 12]]))
        for i in range(0, 12, 3):
            scope = [vs[i], vs[(i + 1) % 12], vs[(i + 2) % 12]]
            dcop.add_constraint(constraint_from_str(
                f"t{i}", f"v{i} * v{(i + 1) % 12} * v{(i + 2) % 12}",
                scope))
        params = {"noise": 0.01}
        r1 = build_engine(dcop, params).run(
            max_cycles=40, stop_on_convergence=False)
        r8 = build_engine(dcop, params, shards=8).run(
            max_cycles=40, stop_on_convergence=False)
        assert r8.assignment == r1.assignment

    def test_constraint_free_graph(self):
        """Zero factors → zero boundary buffer ([0, D] halo): the
        partitioned engine degenerates to per-variable argmin without
        crashing on empty collectives."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dom = Domain("d", "", [0, 1, 2])
        dcop = DCOP("free", objective="min")
        for i in range(8):
            dcop.add_variable(Variable(f"v{i}", dom))
        params = {"noise": 0.01}
        r1 = build_engine(dcop, params).run(max_cycles=5)
        r8 = build_engine(dcop, params, shards=8).run(max_cycles=5)
        assert r8.assignment == r1.assignment
        assert r8.metrics["boundary_vars"] == 0

    def test_guard_cost_matches_host(self):
        """ShardOps.assignment_constraint_cost (the recovery guard's
        verdict input) equals the host-evaluated constraint cost of
        the same global assignment."""
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(8, seed=2)
        engine = build_engine(dcop, {"noise": 0.01}, shards=8)
        res = engine.run(max_cycles=30, stop_on_convergence=False)
        values = np.asarray([
            domain.index(res.assignment[name]) for name, domain
            in zip(engine.meta.var_names, engine.meta.domains)
        ], np.int32)
        device_cost = float(engine._ops.assignment_constraint_cost(
            engine.graph, values))
        host_cost, _ = dcop.solution_cost(res.assignment)
        assert device_cost == pytest.approx(host_cost)

    def test_maxsum_family_delegation(self):
        """amaxsum and maxsum_dynamic share maxsum's device engine,
        so shards= flows through their delegation (SUPPORTS_SHARDS)
        and produces the same partitioned result."""
        from pydcop_tpu.api import solve

        dcop = _grid_dcop(6)
        base = solve(dcop, "maxsum", max_cycles=30, shards=8)
        for algo in ("amaxsum", "maxsum_dynamic"):
            res = solve(dcop, algo, max_cycles=30, shards=8)
            assert res.assignment == base.assignment, algo
            assert res.cost == base.cost

    def test_decimation_rejected(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(6)
        with pytest.raises(ValueError, match="decimation"):
            build_engine(dcop, {"decimation": 10}, shards=8)

    def test_lane_layout_rejected(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(6)
        with pytest.raises(ValueError, match="lane"):
            build_engine(dcop, {"layout": "lane"}, shards=8)

    def test_non_scatter_aggregation_rejected(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(6)
        with pytest.raises(ValueError, match="scatter"):
            build_engine(dcop, {"aggregation": "ell"}, shards=8)

    def test_too_many_shards_message(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        dcop = _grid_dcop(6)
        with pytest.raises(ValueError,
                           match="xla_force_host_platform"):
            build_engine(dcop, {}, shards=64)


# ------------------------- per-shard trace lanes -------------------- #


@needs_mesh
class TestShardTraceLanes:
    def _sharded_trace(self, tmp_path, name, **observe):
        from pydcop_tpu.api import solve

        path = str(tmp_path / name)
        solve(_grid_dcop(8), "maxsum", max_cycles=30, shards=8,
              trace=path, **observe)
        return path

    @pytest.mark.parametrize("segmented", [False, True])
    def test_engine_spans_tagged_and_instants_emitted(
            self, tmp_path, segmented):
        """``trace=`` alone traces the whole-solve program (one
        dispatch, no ``engine_segment``); with ``metrics_file`` the
        probe's segmented loop runs and its segments carry the shard
        count.  Either way every shard gets its instants."""
        from pydcop_tpu.observability.trace import load_trace_file

        observe = ({"metrics_file": str(tmp_path / "m.jsonl")}
                   if segmented else {})
        events = load_trace_file(
            self._sharded_trace(tmp_path, "a.json", **observe))
        segs = [e for e in events if e.get("name") == "engine_segment"]
        if segmented:
            assert segs and all(
                e["args"].get("shards") == 8 for e in segs)
        else:
            assert not segs
            assert [e for e in events if e.get("name") in (
                "jit_compile", "engine_call")]
        shard_ids = {e["args"]["shard"] for e in events
                     if e.get("name") == "shard_segment"}
        assert shard_ids == set(range(8))

    def test_merge_separates_shard_lanes(self, tmp_path):
        """The satellite's lane-separation assertion: after ``pydcop
        trace merge``, every shard id occupies its OWN lane (distinct
        tid, labeled "[shard N]"), disjoint from the host thread
        lane."""
        from pydcop_tpu.observability.trace import merge_traces

        a = self._sharded_trace(tmp_path, "a.json")
        b = self._sharded_trace(tmp_path, "b.json")
        out = str(tmp_path / "merged.json")
        info = merge_traces([a, b], out)
        assert info["aligned"]
        doc = json.load(open(out))
        events = doc["traceEvents"]
        lane_labels = {
            e["tid"]: e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "thread_name"
        }
        tids_per_file_shard = {}
        for e in events:
            if e.get("name") == "shard_segment":
                key = e["args"]["shard"]
                tids_per_file_shard.setdefault(key, set()).add(
                    e["tid"])
        # 8 shards x 2 files -> 16 distinct shard lanes, each
        # labeled with its shard id.
        all_shard_tids = set().union(*tids_per_file_shard.values())
        assert len(all_shard_tids) == 16
        for shard, tids in tids_per_file_shard.items():
            assert len(tids) == 2  # one lane per input file
            for tid in tids:
                assert f"[shard {shard}]" in lane_labels[tid]
        # Host-thread spans stay off the shard lanes.
        span_tids = {e["tid"] for e in events
                     if e.get("name") in ("jit_compile", "engine_call")}
        assert span_tids and span_tids.isdisjoint(all_shard_tids)
