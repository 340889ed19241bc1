"""Runtime-semantics tests: orchestrator timeout, cost-trace runs and
the multihost initializer."""

import time

import numpy as np

from pydcop_tpu.algorithms import AlgorithmDef
from pydcop_tpu.computations_graph import constraints_hypergraph as chg
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import constraint_from_str
from pydcop_tpu.distribution.objects import Distribution
from pydcop_tpu.infrastructure.run import run_local_thread_dcop


def _dcop():
    d = Domain("c", "", ["R", "G", "B"])
    dcop = DCOP("t", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(3)]
    for v in vs:
        dcop.add_variable(v)
    for i in range(2):
        dcop.add_constraint(constraint_from_str(
            f"c{i}", f"1 if v{i} == v{i + 1} else 0",
            [vs[i], vs[i + 1]]))
    dcop.add_agents([AgentDef(f"a{i}") for i in range(3)])
    return dcop


class TestOrchestratorTimeout:
    def test_timeout_stops_run_and_sets_status(self):
        """A non-terminating algorithm (maxsum has no stop condition)
        must be cut at the timeout with status TIMEOUT, and the
        orchestrator must still produce final metrics (reference
        orchestrator.py:270-276 timeout timer)."""
        dcop = _dcop()
        algo = AlgorithmDef.build_with_default_param(
            "maxsum", mode="min")
        from pydcop_tpu.computations_graph import factor_graph as fg

        cg = fg.build_computation_graph(dcop)
        mapping = {"a0": [], "a1": [], "a2": []}
        for i, node in enumerate(cg.nodes):
            mapping[f"a{i % 3}"].append(node.name)
        orch = run_local_thread_dcop(
            algo, cg, Distribution(mapping), dcop)
        try:
            assert orch.wait_ready(10)
            orch.deploy_computations()
            t0 = time.perf_counter()
            orch.run(timeout=1.5)
            elapsed = time.perf_counter() - t0
            assert orch.status == "TIMEOUT"
            # The run returned promptly after the timeout, not after
            # some much longer internal grace period.
            assert elapsed < 10
            orch.stop_agents(5)
            metrics = orch.end_metrics()
            assert set(metrics["assignment"]) >= {"v0", "v1", "v2"}
        finally:
            orch.stop_agents(2)
            orch.stop()

    def test_finished_status_when_algorithm_terminates(self):
        """A terminating algorithm (dsa with stop_cycle) ends the run
        with FINISHED before the timeout."""
        dcop = _dcop()
        algo = AlgorithmDef(
            "dsa", {"stop_cycle": 10, "variant": "B",
                    "probability": 0.7}, "min")
        cg = chg.build_computation_graph(dcop)
        mapping = {"a0": [], "a1": [], "a2": []}
        for i, node in enumerate(cg.nodes):
            mapping[f"a{i % 3}"].append(node.name)
        orch = run_local_thread_dcop(
            algo, cg, Distribution(mapping), dcop)
        try:
            assert orch.wait_ready(10)
            orch.deploy_computations()
            orch.run(timeout=20)
            assert orch.status == "FINISHED"
        finally:
            orch.stop_agents(5)
            orch.stop()


class TestCostTrace:
    def test_trace_monotone_overall_and_matches_final(self):
        from pydcop_tpu.engine.compile import compile_dcop
        from pydcop_tpu.engine.runner import MaxSumEngine

        dcop = _dcop()
        graph, meta = compile_dcop(dcop, noise_level=0.01)
        engine = MaxSumEngine(graph, meta)
        res = engine.run_trace(max_cycles=40)
        trace = res.metrics["cost_trace"]
        assert trace.shape == (40,)
        # The final trace entry equals the host-evaluated cost of the
        # returned assignment (device cost accounting is consistent).
        host_cost, _ = dcop.solution_cost(res.assignment)
        assert float(trace[-1]) == host_cost
        # The trajectory improved from the first cycle's cost.
        assert float(trace[-1]) <= float(trace[0])


class TestMultihost:
    def test_single_host_noop(self, monkeypatch):
        """Without a coordinator the initializer is a silent no-op and
        the global mesh equals the local one."""
        import pydcop_tpu.engine.multihost as mh

        monkeypatch.setattr(mh, "_initialized", False)
        monkeypatch.delenv("PYDCOP_COORDINATOR", raising=False)
        monkeypatch.delenv("PYDCOP_NUM_PROCESSES", raising=False)
        assert mh.initialize_multihost() is False
        mesh = mh.global_mesh(4)
        assert mesh.size == 4

    def test_idempotent(self, monkeypatch):
        import pydcop_tpu.engine.multihost as mh

        monkeypatch.setattr(mh, "_initialized", False)
        monkeypatch.delenv("PYDCOP_COORDINATOR", raising=False)
        mh.initialize_multihost()
        # Second call must not try to re-join (jax.distributed raises
        # on double init); single-host path reports process_count()==1.
        assert mh.initialize_multihost() is False

    def test_env_var_plumbing(self, monkeypatch):
        """Env vars reach jax.distributed.initialize verbatim."""
        import pydcop_tpu.engine.multihost as mh

        monkeypatch.setattr(mh, "_initialized", False)
        monkeypatch.setenv("PYDCOP_COORDINATOR", "10.0.0.1:1234")
        monkeypatch.setenv("PYDCOP_NUM_PROCESSES", "2")
        monkeypatch.setenv("PYDCOP_PROCESS_ID", "1")
        calls = {}

        import jax

        def fake_init(coordinator_address=None, num_processes=None,
                      process_id=None):
            calls.update(
                addr=coordinator_address, n=num_processes,
                pid=process_id,
            )

        monkeypatch.setattr(jax.distributed, "initialize", fake_init)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        assert mh.initialize_multihost() is True
        assert calls == {"addr": "10.0.0.1:1234", "n": 2, "pid": 1}
