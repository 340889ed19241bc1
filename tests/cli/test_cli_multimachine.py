"""CLI tests for process mode + standalone orchestrator/agent commands.

This is how multi-node behavior is tested without a cluster (reference
strategy, tests/dcop_cli/test_solve.py:55-58): HTTP transports on
localhost ports.
"""

import json
import os
import socket
import subprocess
import sys
import time

from fixtures_paths import LOCAL_INSTANCES as INSTANCES
ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}
FIXTURE = os.path.join(INSTANCES, "coloring_4agents_10vars.yaml")

# The orchestrator binds ``port`` and the agent process binds
# ``port+1 .. port+n_agents`` — a CONTIGUOUS block.  Fixed ports
# (19340/19480 historically) flake on warm reruns: the previous run's
# sockets linger in TIME_WAIT, the agent process dies with
# EADDRINUSE, and the orchestrator then times out on an empty
# directory.  ``_free_port_block`` probes OS-chosen candidates until a
# whole block binds, and ``_run_orchestrated`` retries the spawn when
# the (tiny) pick-to-bind race still loses.
PORT_BLOCK = 5


def _free_port_block(n: int = PORT_BLOCK, attempts: int = 50) -> int:
    """A base port p such that p..p+n-1 all bind right now."""
    for _ in range(attempts):
        with socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            base = probe.getsockname()[1]
        if base + n >= 65536:
            continue
        held = []
        try:
            for offset in range(n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + offset))
                held.append(s)
        except OSError:
            continue
        finally:
            for s in held:
                s.close()
        return base
    raise RuntimeError(f"no free block of {n} ports found")


def _run_orchestrated(agent_args, orch_args, orch_timeout,
                      agent_wait, attempts: int = 3):
    """Spawn the agent process on a fresh port block, run the
    orchestrator against it, retry both ONLY on an EADDRINUSE loser
    (the agent dying on startup, or the orchestrator reporting the
    bind error) — any other orchestrator failure is a real failure
    and raises immediately, stderr attached."""
    last_error = None
    for _ in range(attempts):
        port = _free_port_block()
        agent_proc = subprocess.Popen(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli",
             *agent_args(port)],
            env=ENV, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        try:
            time.sleep(0.5)
            if agent_proc.poll() is not None:
                # Lost the pick-to-bind race: a fresh block, again.
                last_error = RuntimeError(
                    f"agent process died on startup (exit "
                    f"{agent_proc.returncode}, base port {port})")
                continue
            proc = subprocess.run(
                [sys.executable, "-m", "pydcop_tpu.dcop_cli",
                 *orch_args(port)],
                timeout=orch_timeout, env=ENV,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            )
            if proc.returncode != 0:
                stderr = proc.stderr.decode(errors="replace")
                if "Address already in use" not in stderr:
                    raise AssertionError(
                        f"orchestrator failed (exit "
                        f"{proc.returncode}), not a port race:\n"
                        f"{stderr[-1500:]}")
                last_error = RuntimeError(
                    f"orchestrator lost the port race on {port}")
                continue
            result = json.loads(proc.stdout)
            # Agents exit once the orchestrator stops them.
            assert agent_proc.wait(timeout=agent_wait) == 0
            return result
        finally:
            if agent_proc.poll() is None:
                agent_proc.kill()
    raise last_error


def _solve_process_mode(args, attempts: int = 4):
    """``pydcop <args>`` in process mode -> its JSON result.  Process
    mode binds the fixed ports 9000.., as does
    ``tests/cli/test_cli_run.py``'s process-mode case in another
    worker: a run that lost that race (and only that) is made again."""
    for attempt in range(attempts):
        proc = subprocess.run(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli", *args],
            timeout=180, env=ENV, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        if proc.returncode == 0:
            return json.loads(proc.stdout)
        stderr = proc.stderr.decode(errors="replace")
        if ("Address already in use" not in stderr
                or attempt == attempts - 1):
            raise AssertionError(
                f"pydcop {' '.join(args)} exited {proc.returncode}, "
                f"not a port race:\n{stderr[-1500:]}")
        time.sleep(5)


def test_solve_mode_process():
    result = _solve_process_mode(
        ["-t", "5", "solve", "-a", "dsa", "-d", "adhoc", "-m",
         "process", FIXTURE])
    assert result["backend"] == "process"
    assert len(result["assignment"]) == 10
    assert result["msg_count"] > 0


def test_orchestrator_and_agent_commands(tmp_path):
    result = _run_orchestrated(
        agent_args=lambda port: [
            "-t", "40", "agent", "-n", "a1", "a2", "a3", "a4",
            "-o", f"127.0.0.1:{port}", "-p", str(port + 1),
            "--capacity", "100"],
        orch_args=lambda port: [
            "-t", "4", "orchestrator", "-a", "dsa", "-d", "adhoc",
            "--port", str(port), FIXTURE],
        orch_timeout=120, agent_wait=30,
    )
    assert result["backend"] == "multi-machine"
    assert len(result["assignment"]) == 10


def test_solve_mode_process_maxsum():
    """MaxSum over HTTP: factor/variable computations and their custom
    wire format (MaxSumMessage costs dict) cross real process + JSON
    boundaries.  MaxSum has no stop condition, so the run always lasts
    the full -t: large enough to converge under machine load (8 s was
    flaky during parallel benches), small enough to keep the suite
    quick."""
    result = _solve_process_mode(
        ["-t", "12", "solve", "-a", "maxsum", "-d", "adhoc", "-m",
         "process", os.path.join(INSTANCES, "coloring_chain.yaml")])
    assert result["backend"] == "process"
    assert set(result["assignment"]) == {"w1", "w2", "w3", "w4"}
    # Converged to a feasible coloring of the 4-chain (maxsum folds the
    # unary preferences in, so any proper coloring costs <= 0.6).
    assert result["cost"] <= 0.6 + 1e-6


def test_solve_mode_process_mgm2():
    """MGM2's 5-phase protocol (value/offer/response/gain/go) over the
    HTTP transport: offers are tuple-triples that JSON converts to
    lists, so this exercises sequence-robust message handling."""
    result = _solve_process_mode(
        ["-t", "10", "solve", "-a", "mgm2", "-d", "adhoc", "-m",
         "process", "-p", "stop_cycle:20",
         os.path.join(INSTANCES, "coloring_chain.yaml")])
    assert result["backend"] == "process"
    assert set(result["assignment"]) == {"w1", "w2", "w3", "w4"}


def test_orchestrator_scenario_repair_over_http(tmp_path):
    """Dynamic multi-machine run: standalone orchestrator with a
    scenario that removes agent a1 mid-run, 2-replication, repair over
    real HTTP transports — the full reference resilience flow
    (orchestrator.py:955-1178) end to end."""
    scenario = os.path.join(
        os.path.dirname(__file__), "..", "instances",
        "scenario_remove_a1.yaml")
    result = _run_orchestrated(
        agent_args=lambda port: [
            "-t", "90", "agent", "-n", "a1", "a2", "a3", "a4",
            "-o", f"127.0.0.1:{port}", "-p", str(port + 1),
            "--capacity", "100", "--replication"],
        orch_args=lambda port: [
            "-t", "15", "orchestrator", "-a", "dsa", "-d", "adhoc",
            "-k", "2", "-s", scenario, "--port", str(port), FIXTURE],
        orch_timeout=120, agent_wait=45,
    )
    assert result["backend"] == "multi-machine"
    # All 10 variables still assigned despite a1's departure.
    assert len(result["assignment"]) == 10
    replication = result["replication"]
    assert replication["ktarget"] == 2
    # a1 hosted computations; they must have been repaired onto
    # surviving agents.
    assert replication["repaired"]
