"""Battery for the hot-path overhaul: vectorized compile + structure
cache, buffer donation, async checkpointing, and the aggregation
autotuner (ISSUE 3).

Contracts pinned here:

- the vectorized cost-table evaluation is bit-equal to the reference
  per-assignment loop, and falls back (never fails) on expressions it
  cannot vectorize;
- the structure-keyed compile cache returns identical layouts and
  skips layout/agg-array construction (counter-asserted), and never
  confuses different structures;
- segment/superstep buffer donation changes WHERE buffers live, never
  the trajectory (bit-identical states vs the undonated run);
- async checkpointing writes the same snapshots, overlaps device
  compute (trace-asserted), flushes before returning, and surfaces
  writer errors instead of swallowing them;
- ``aggregation='auto'`` only ever selects a valid strategy and
  records its decision in result metrics.
"""

import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import (
    Constraint,
    NAryMatrixRelation,
    constraint_from_str,
)
from pydcop_tpu.engine.compile import (
    AGGREGATIONS,
    compile_cache,
    compile_dcop,
    compile_factor_graph,
    validated_aggregation,
)


@pytest.fixture(autouse=True)
def _fresh_compile_cache():
    compile_cache.clear()
    yield
    compile_cache.clear()


def _domain(values=(0, 1, 2)):
    return Domain("colors", "", list(values))


def _ring_dcop(n=12, penalty=1):
    d = _domain()
    vs = [Variable(f"v{i}", d) for i in range(n)]
    dcop = DCOP("ring", objective="min")
    for v in vs:
        dcop.add_variable(v)
    for i in range(n):
        a, b = vs[i], vs[(i + 1) % n]
        dcop.add_constraint(constraint_from_str(
            f"c{i}", f"{penalty} if {a.name} == {b.name} else 0",
            [a, b]))
    return dcop


# ------------------------------------------------------------------ #
# Vectorized cost-table evaluation
# ------------------------------------------------------------------ #


class TestVectorizedToArray:

    @pytest.mark.parametrize("expr", [
        "10000 if v1 == v2 else 0",
        "(v1 + v2 - 1)**2",
        "math.sqrt(abs(v1 - v2)) + v2",
        "1 if 0 < v1 < 2 and v2 != 1 else -1",
        "min(v1, v2) + max(v1, 1)",
        "v1 * v2 + (3 if v1 >= v2 or not v2 else 7)",
    ])
    def test_matches_scalar_reference(self, expr):
        d = _domain()
        x, y = Variable("v1", d), Variable("v2", d)
        c = constraint_from_str("c", expr, [x, y])
        np.testing.assert_array_equal(
            c.to_array(), Constraint.to_array(c))

    def test_string_domains(self):
        d = _domain(["R", "G", "B"])
        a, b = Variable("a", d), Variable("b", d)
        c = constraint_from_str("c", "1 if a == b else 0", [a, b])
        np.testing.assert_array_equal(
            c.to_array(), Constraint.to_array(c))

    def test_random_expression_falls_back(self):
        d = _domain()
        x = Variable("v1", d)
        c = constraint_from_str("c", "v1 + 0 * random.random()", [x])
        arr = c.to_array()  # must not crash
        assert arr.shape == (3,)
        assert c.table_signature() is None

    def test_signature_shared_across_renamed_scopes(self):
        d = _domain()
        c1 = constraint_from_str(
            "c1", "7 if v12 == v37 else 0",
            [Variable("v12", d), Variable("v37", d)])
        c2 = constraint_from_str(
            "c2", "7 if a == b else 0",
            [Variable("a", d), Variable("b", d)])
        assert c1.table_signature() == c2.table_signature()
        np.testing.assert_array_equal(c1.to_array(), c2.to_array())

    def test_signature_distinguishes_constants_and_domains(self):
        d = _domain()
        c1 = constraint_from_str(
            "c1", "7 if a == b else 0",
            [Variable("a", d), Variable("b", d)])
        c2 = constraint_from_str(
            "c2", "8 if a == b else 0",
            [Variable("a", d), Variable("b", d)])
        assert c1.table_signature() != c2.table_signature()
        d2 = _domain((0, 1))
        c3 = constraint_from_str(
            "c3", "7 if a == b else 0",
            [Variable("a", d2), Variable("b", d2)])
        assert c1.table_signature() != c3.table_signature()

    def test_signature_immune_to_string_literals(self):
        """A variable name inside a string literal must NOT normalize
        like a variable reference — merging these two would silently
        swap cost tables."""
        d = _domain(["v1", "x"])
        c1 = constraint_from_str(
            "c1", "1 if v1 == 'v1' else 0", [Variable("v1", d)])
        c2 = constraint_from_str(
            "c2", "1 if x == 'x' else 0", [Variable("x", d)])
        assert c1.table_signature() != c2.table_signature()
        assert not np.array_equal(c1.to_array(), c2.to_array())


# ------------------------------------------------------------------ #
# Compile: vectorized path equals reference, cache semantics
# ------------------------------------------------------------------ #


def _mixed_problem(seed=0, penalty=9):
    rng = np.random.default_rng(seed)
    d = _domain()
    vs = [Variable(f"v{i}", d) for i in range(10)]
    cons = []
    for i in range(14):
        a, b = rng.choice(10, size=2, replace=False)
        cons.append(constraint_from_str(
            f"e{i}", f"{penalty} if v{a} == v{b} else 0",
            [vs[a], vs[b]]))
    cons.append(NAryMatrixRelation(
        [vs[0], vs[1]], rng.random((3, 3)), "m0"))
    cons.append(constraint_from_str("u0", "v3 * 2 + 1", [vs[3]]))
    cons.append(constraint_from_str(
        "t0", "v1 + v2 + v4", [vs[1], vs[2], vs[4]]))
    return vs, cons


class TestCompile:

    @pytest.mark.parametrize("mode", ["min", "max"])
    def test_vectorized_compile_equals_reference(self, mode):
        vs, cons = _mixed_problem()
        g_ref, m_ref = compile_factor_graph(
            vs, cons, mode=mode, noise_level=0.01,
            vectorize=False, use_cache=False)
        g_vec, m_vec = compile_factor_graph(
            vs, cons, mode=mode, noise_level=0.01,
            vectorize=True, use_cache=False)
        np.testing.assert_array_equal(g_ref.var_costs, g_vec.var_costs)
        assert m_ref.factor_names == m_vec.factor_names
        for b_ref, b_vec in zip(g_ref.buckets, g_vec.buckets):
            np.testing.assert_array_equal(b_ref.costs, b_vec.costs)
            np.testing.assert_array_equal(b_ref.var_ids, b_vec.var_ids)

    def test_cache_hit_skips_layout_build(self):
        vs, cons = _mixed_problem(penalty=9)
        g1, _ = compile_factor_graph(vs, cons, aggregation="ell")
        assert compile_cache.stats() == {
            "hits": 0, "misses": 1, "layout_builds": 1, "entries": 1}
        # Same structure, different cost tables.
        vs2, cons2 = _mixed_problem(penalty=4)
        g2, _ = compile_factor_graph(vs2, cons2, aggregation="ell")
        stats = compile_cache.stats()
        assert stats["hits"] == 1
        assert stats["layout_builds"] == 1  # NOT rebuilt
        # Layout arrays are the exact cached objects, agg included.
        for b1, b2 in zip(g1.buckets, g2.buckets):
            assert b1.var_ids is b2.var_ids
        assert g1.agg_ell is g2.agg_ell
        # Costs differ (the problem really changed; bucket 1 holds
        # the binary penalty factors).
        assert not np.array_equal(
            g1.buckets[1].costs, g2.buckets[1].costs)

    def test_cached_layout_is_frozen(self):
        vs, cons = _mixed_problem()
        g, _ = compile_factor_graph(vs, cons, aggregation="sorted")
        assert not g.buckets[0].var_ids.flags.writeable
        assert not g.agg_perm.flags.writeable

    def test_cache_distinguishes_structures(self):
        vs, cons = _mixed_problem(seed=0)
        compile_factor_graph(vs, cons)
        # Different edges -> different structure.
        vs2, cons2 = _mixed_problem(seed=1)
        compile_factor_graph(vs2, cons2)
        assert compile_cache.stats()["hits"] == 0
        # Same structure but different aggregation/pad_to -> miss.
        compile_factor_graph(vs, cons, aggregation="sorted")
        compile_factor_graph(vs, cons, pad_to=4)
        assert compile_cache.stats()["hits"] == 0
        # And the true re-compile does hit.
        compile_factor_graph(vs, cons)
        assert compile_cache.stats()["hits"] == 1

    def test_cache_opt_out(self):
        vs, cons = _mixed_problem()
        compile_factor_graph(vs, cons, use_cache=False)
        compile_factor_graph(vs, cons, use_cache=False)
        assert compile_cache.stats()["hits"] == 0
        assert compile_cache.stats()["entries"] == 0

    def test_compiled_solve_unchanged_by_cache(self):
        """A cache-hit compile must solve identically to a cold one."""
        from pydcop_tpu.api import solve

        dcop1 = _ring_dcop(10)
        ref = solve(dcop1, "maxsum", backend="device", max_cycles=60)
        dcop2 = _ring_dcop(10)  # same structure -> layout cache hit
        res = solve(dcop2, "maxsum", backend="device", max_cycles=60)
        assert compile_cache.stats()["hits"] >= 1
        assert res["assignment"] == ref["assignment"]
        assert res["cycles"] == ref["cycles"]


# ------------------------------------------------------------------ #
# Buffer donation
# ------------------------------------------------------------------ #


class TestDonation:

    def _engine(self, donate: bool):
        from pydcop_tpu.algorithms.maxsum import build_engine

        eng = build_engine(_ring_dcop(), {"noise": 0.01})
        eng.donate = donate
        return eng

    def test_trajectory_bit_identical_per_segment(self):
        """Donation relocates buffers; every state leaf must stay
        bit-identical to the undonated run at every segment
        boundary."""
        import jax

        e_d, e_u = self._engine(True), self._engine(False)
        s_d, s_u = e_d.init_state(), e_u.init_state()
        for _ in range(5):
            fn_d = e_d._segment_fn(7, True)
            fn_u = e_u._segment_fn(7, True)
            (s_d, v_d), _, _ = e_d._call(("seg", 7), fn_d,
                                         e_d.graph, s_d)
            (s_u, v_u), _, _ = e_u._call(("seg", 7), fn_u,
                                         e_u.graph, s_u)
            # Host copies BEFORE the next dispatch donates s_d.
            host_d = jax.device_get(s_d)
            host_u = jax.device_get(s_u)
            for leaf_d, leaf_u in zip(
                    jax.tree_util.tree_leaves(host_d),
                    jax.tree_util.tree_leaves(host_u)):
                np.testing.assert_array_equal(
                    np.asarray(leaf_d), np.asarray(leaf_u))
            np.testing.assert_array_equal(
                np.asarray(v_d), np.asarray(v_u))

    def test_donation_is_active(self):
        """The donated input state is actually consumed (buffer
        deleted) — the guarantee the zero-allocation claim rests on."""
        e = self._engine(True)
        state = e.init_state()
        fn = e._segment_fn(5, True)
        (new_state, _), _, _ = e._call(("seg", 5), fn, e.graph, state)
        with pytest.raises(Exception):
            np.asarray(state.v2f[0])  # deleted by donation
        np.asarray(new_state.v2f[0])  # output is live

    def test_run_checkpointed_matches_plain_run(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        ref = build_engine(_ring_dcop(), {"noise": 0.01}).run(
            max_cycles=100)
        seg = self._engine(True).run_checkpointed(
            max_cycles=100, segment_cycles=7)
        assert seg.assignment == ref.assignment
        assert seg.cycles == ref.cycles
        assert seg.converged == ref.converged

    def test_dynamic_engine_donation_roundtrip(self):
        from pydcop_tpu.engine.dynamic import DynamicMaxSumEngine

        d = _domain()
        vs = [Variable(f"v{i}", d) for i in range(6)]
        cons = [constraint_from_str(
            f"c{i}", f"1 if v{i} == v{(i + 1) % 6} else 0",
            [vs[i], vs[(i + 1) % 6]]) for i in range(6)]
        donated = DynamicMaxSumEngine(vs, cons, noise_seed=7,
                                      donate=True)
        plain = DynamicMaxSumEngine(vs, cons, noise_seed=7,
                                    donate=False)
        for _ in range(3):  # repeated warm-started runs
            r_d = donated.run(max_cycles=20)
            r_p = plain.run(max_cycles=20)
            assert r_d.assignment == r_p.assignment
            assert r_d.cycles == r_p.cycles
        # Edits (host array surgery) still compose with donation.
        donated.change_factor("c0", constraint_from_str(
            "c0", "5 if v0 == v1 else 0", [vs[0], vs[1]]))
        plain.change_factor("c0", constraint_from_str(
            "c0", "5 if v0 == v1 else 0", [vs[0], vs[1]]))
        r_d = donated.run(max_cycles=20)
        r_p = plain.run(max_cycles=20)
        assert r_d.assignment == r_p.assignment
        assert r_d.cycles == r_p.cycles


# ------------------------------------------------------------------ #
# Async checkpointing
# ------------------------------------------------------------------ #


class TestAsyncCheckpoint:

    def _engine(self):
        from pydcop_tpu.algorithms.maxsum import build_engine

        return build_engine(_ring_dcop(), {"noise": 0.01})

    def test_same_snapshots_as_sync(self, tmp_path):
        from pydcop_tpu.resilience.checkpoint import (
            CheckpointManager,
            read_meta,
        )

        m_async = CheckpointManager(str(tmp_path / "a"), every=5,
                                    keep=10)
        m_sync = CheckpointManager(str(tmp_path / "s"), every=5,
                                   keep=10)
        r_a = self._engine().run_checkpointed(
            max_cycles=40, manager=m_async, checkpoint_async=True,
            stop_on_convergence=False)
        r_s = self._engine().run_checkpointed(
            max_cycles=40, manager=m_sync, checkpoint_async=False,
            stop_on_convergence=False)
        assert r_a.assignment == r_s.assignment
        assert r_a.metrics["checkpoint_async"]
        assert not r_s.metrics["checkpoint_async"]
        cycles_a = [c for c, _ in m_async.checkpoints()]
        assert cycles_a == [c for c, _ in m_sync.checkpoints()]
        # Byte-level: identical snapshot payloads either way.
        for (ca, pa), (cs, ps) in zip(m_async.checkpoints(),
                                      m_sync.checkpoints()):
            assert read_meta(pa)["cycle"] == read_meta(ps)["cycle"]
            da = np.load(pa)
            ds = np.load(ps)
            for k in da.files:
                if k != "__meta__":
                    np.testing.assert_array_equal(da[k], ds[k])

    def test_writes_overlap_device_compute(self, tmp_path):
        """THE overlap criterion: checkpoint_write spans (writer
        thread) run concurrently with engine_segment spans (main
        thread)."""
        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.observability.trace import tracer
        from pydcop_tpu.resilience.checkpoint import CheckpointManager

        eng = build_engine(_ring_dcop(800), {"noise": 0.01})
        manager = CheckpointManager(str(tmp_path), every=20, keep=3)
        tracer.enable()
        try:
            eng.run_checkpointed(
                max_cycles=160, manager=manager,
                stop_on_convergence=False)
        finally:
            tracer.disable()
        events = tracer.events()
        segs = [(e["ts"], e["ts"] + e["dur"], e["tid"])
                for e in events if e["name"] == "engine_segment"]
        writes = [(e["ts"], e["ts"] + e["dur"], e["tid"])
                  for e in events if e["name"] == "checkpoint_write"]
        assert len(segs) >= 5 and len(writes) >= 5
        assert {t for _, _, t in writes}.isdisjoint(
            {t for _, _, t in segs})  # different lanes
        overlaps = sum(
            1 for ws, we, _ in writes for ss, se, _ in segs
            if ws < se and ss < we)
        assert overlaps >= 1, (
            "no checkpoint_write span overlapped any engine_segment "
            "span — async writes are serializing with compute")

    def test_flush_guarantee_on_interrupt(self, tmp_path):
        from pydcop_tpu.resilience.checkpoint import CheckpointManager

        manager = CheckpointManager(str(tmp_path), every=5, keep=2)
        res = self._engine().run_checkpointed(
            max_cycles=100, manager=manager, max_segments=1)
        assert res.metrics["interrupted"]
        # The (async) snapshot is on disk the moment the call returns.
        assert manager.latest() is not None
        assert manager.latest().endswith("ckpt_5.npz")

    def test_writer_error_surfaces(self, tmp_path):
        from pydcop_tpu.resilience.checkpoint import (
            AsyncCheckpointWriter,
            CheckpointManager,
        )

        manager = CheckpointManager(str(tmp_path), every=5)
        # Redirect writes into a path that is a FILE, so mkstemp
        # inside the atomic write fails on the writer thread.
        blocker = tmp_path / "blocker"
        blocker.write_text("x")
        manager.directory = str(blocker / "sub")
        writer = AsyncCheckpointWriter(manager)
        state = self._engine().init_state()
        writer.submit(state, 5)
        with pytest.raises(RuntimeError, match="async checkpoint"):
            writer.flush()
        writer.close()

    def test_writer_close_idempotent_and_rejects_after(self, tmp_path):
        from pydcop_tpu.resilience.checkpoint import (
            AsyncCheckpointWriter,
            CheckpointManager,
        )

        manager = CheckpointManager(str(tmp_path), every=5)
        writer = AsyncCheckpointWriter(manager)
        state = self._engine().init_state()
        writer.submit(state, 5)
        writer.close()
        writer.close()  # no-op
        assert manager.latest().endswith("ckpt_5.npz")
        with pytest.raises(RuntimeError, match="closed"):
            writer.submit(state, 10)


# ------------------------------------------------------------------ #
# Aggregation autotuner
# ------------------------------------------------------------------ #


class TestAutotuner:

    def test_choice_valid_and_recorded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PYDCOP_AGG_AUTOTUNE_CACHE",
                           str(tmp_path / "tune.json"))
        from pydcop_tpu.api import solve

        res = solve(_ring_dcop(), "maxsum", backend="device",
                    max_cycles=50, algo_params={"aggregation": "auto"})
        assert res["metrics"]["aggregation"] in AGGREGATIONS
        assert res["metrics"]["aggregation"] != "boundary"
        assert res["metrics"]["aggregation_source"] == "measured"
        timings = res["metrics"]["aggregation_timings_ms"]
        assert set(timings) == set(AGGREGATIONS)
        measured = {s for s, t in timings.items() if t is not None}
        assert {"scatter", "sorted", "ell"} <= measured

    def test_cache_roundtrip(self, tmp_path):
        from pydcop_tpu.engine.autotune import autotune_aggregation

        graph, _ = compile_dcop(_ring_dcop())
        cache = str(tmp_path / "tune.json")
        first = autotune_aggregation(graph, cache_file=cache)
        assert first["aggregation_source"] == "measured"
        second = autotune_aggregation(graph, cache_file=cache)
        assert second["aggregation_source"] == "cache"
        assert second["aggregation"] == first["aggregation"]

    def test_mesh_resolves_to_scatter_without_measuring(self):
        from pydcop_tpu.engine.autotune import autotune_aggregation

        assert validated_aggregation(
            {"aggregation": "auto"}, pad_to=4) == "scatter"
        graph, _ = compile_dcop(_ring_dcop(), pad_to=4)
        info = autotune_aggregation(graph, pad_to=4)
        assert info["aggregation"] == "scatter"
        assert info["aggregation_source"] == "mesh"
        assert all(t is None
                   for t in info["aggregation_timings_ms"].values())

    def test_hub_guard_excludes_ell(self, tmp_path, monkeypatch):
        """A hub-guard refusal (ell would OOM) must drop ell from the
        candidates, never crash or select it."""
        import pydcop_tpu.engine.autotune as autotune_mod

        real = autotune_mod.build_aggregation_arrays

        def guarded(buckets, n_segments, aggregation):
            if aggregation == "ell":
                raise ValueError(
                    "aggregation='ell' would allocate a huge array")
            return real(buckets, n_segments, aggregation)

        monkeypatch.setattr(
            autotune_mod, "build_aggregation_arrays", guarded)
        graph, _ = compile_dcop(_ring_dcop())
        info = autotune_mod.autotune_aggregation(
            graph, cache_file=str(tmp_path / "t.json"),
            use_cache=False)
        assert info["aggregation"] in ("scatter", "sorted")
        assert info["aggregation_timings_ms"]["ell"] is None
        assert "ell" in info["aggregation_notes"]

    def test_edge_free_graph(self):
        from pydcop_tpu.engine.autotune import autotune_aggregation

        d = _domain()
        dcop = DCOP("empty", objective="min")
        dcop.add_variable(Variable("x", d))
        graph, _ = compile_dcop(dcop)
        info = autotune_aggregation(graph, use_cache=False)
        assert info["aggregation"] == "scatter"
        assert info["aggregation_source"] == "empty"

    def test_corrupt_cache_ignored(self, tmp_path):
        from pydcop_tpu.engine.autotune import autotune_aggregation

        cache = tmp_path / "tune.json"
        cache.write_text("{not json")
        graph, _ = compile_dcop(_ring_dcop())
        info = autotune_aggregation(graph, cache_file=str(cache))
        assert info["aggregation_source"] == "measured"


# ------------------------------------------------------------------ #
# Satellites: edge-free aggregation crash, sync debug
# ------------------------------------------------------------------ #


class TestEdgeFreeAggregation:

    @pytest.mark.parametrize("aggregation", list(AGGREGATIONS))
    def test_aggregate_beliefs_no_buckets(self, aggregation):
        import jax.numpy as jnp

        from pydcop_tpu.ops.maxsum import aggregate_beliefs

        d = _domain()
        dcop = DCOP("empty", objective="min")
        for name in ("x", "y"):
            dcop.add_variable(Variable(name, d))
        graph, _ = compile_dcop(dcop, aggregation=aggregation)
        beliefs, sums = aggregate_beliefs(graph, ())
        np.testing.assert_array_equal(
            np.asarray(beliefs), np.asarray(graph.var_costs))
        assert not np.asarray(jnp.any(sums != 0))

    @pytest.mark.parametrize(
        "aggregation", ["scatter", "sorted", "ell", "auto"])
    def test_solve_edge_free(self, aggregation, tmp_path, monkeypatch):
        monkeypatch.setenv("PYDCOP_AGG_AUTOTUNE_CACHE",
                           str(tmp_path / "t.json"))
        from pydcop_tpu.api import solve

        d = _domain()
        dcop = DCOP("empty", objective="min")
        for name in ("x", "y"):
            dcop.add_variable(Variable(name, d))
        res = solve(dcop, "maxsum", backend="device", max_cycles=10,
                    algo_params={"aggregation": aggregation})
        assert res["status"] == "FINISHED"
        assert res["cost"] == 0.0


class TestSyncDebug:

    def test_debug_path_fetches_every_leaf(self, monkeypatch):
        import types

        import jax

        from pydcop_tpu.engine import timing

        fetched = []

        def counting_get(x):
            fetched.append(x)
            return jax.device_get(x)

        proxy = types.SimpleNamespace(
            tree_util=jax.tree_util, device_get=counting_get)
        monkeypatch.setattr(timing, "jax", proxy)
        import jax.numpy as jnp

        tree = (jnp.zeros(4), jnp.zeros(8), jnp.zeros((2, 2)))
        monkeypatch.delenv("PYDCOP_SYNC_DEBUG", raising=False)
        timing.sync(tree)
        assert len(fetched) == 1  # smallest-leaf contract
        fetched.clear()
        monkeypatch.setenv("PYDCOP_SYNC_DEBUG", "1")
        out = timing.sync(tree)
        assert out is tree
        assert len(fetched) == 3  # one barrier per leaf

    def test_empty_tree_noop(self):
        from pydcop_tpu.engine.timing import sync

        assert sync({"a": 1}) == {"a": 1}
