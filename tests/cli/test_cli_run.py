"""CLI tests for the dynamic-DCOP commands: run + replica_dist.

Mirrors the reference's CLI test strategy (subprocess + JSON results,
tests/dcop_cli/).
"""

import json
import os
import subprocess
import sys

from fixtures_paths import LOCAL_INSTANCES as INSTANCES
ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def run_cli(args, timeout=120, port_race_attempts=1):
    """``pydcop <args>`` -> its JSON result.  Process mode binds the
    fixed ports 9000.., as ``tests/cli/test_cli_multimachine.py``'s
    process-mode cases do in another worker: a run that lost that
    race (and only that) is made again."""
    import time

    for attempt in range(port_race_attempts):
        proc = subprocess.run(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli"] + args,
            timeout=timeout, env=ENV, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        if proc.returncode == 0:
            return json.loads(proc.stdout)
        stderr = proc.stderr.decode(errors="replace")
        if ("Address already in use" not in stderr
                or attempt == port_race_attempts - 1):
            raise AssertionError(
                f"pydcop {' '.join(args)} exited {proc.returncode}:\n"
                f"{stderr[-1500:]}")
        time.sleep(5)


def test_replica_dist_places_replicas():
    out = subprocess.check_output(
        [sys.executable, "-m", "pydcop_tpu.dcop_cli",
         "replica_dist", "-a", "dsa", "-d", "adhoc", "-k", "2",
         os.path.join(INSTANCES,
                      "coloring_4agents_10vars.yaml")],
        timeout=120, env=ENV,
    ).decode()
    assert "replica_dist:" in out
    # Every variable computation must have 2 replicas.
    import yaml

    data = yaml.safe_load(out)
    mapping = data["replica_dist"]
    assert len(mapping) == 10
    for comp, hosts in mapping.items():
        assert len(hosts) == 2, f"{comp}: {hosts}"


def test_run_with_scenario_repairs():
    result = run_cli([
        "-t", "12",
        "run", "-a", "dsa", "-d", "adhoc", "-k", "2",
        "-s", os.path.join(INSTANCES, "scenario_remove_a1.yaml"),
        os.path.join(INSTANCES, "coloring_4agents_10vars.yaml"),
    ], timeout=180)
    assert result["status"] in ("FINISHED", "TIMEOUT")
    # All 10 variables still have a value despite a1's departure.
    assert len(result["assignment"]) == 10
    replication = result["replication"]
    assert replication["ktarget"] == 2
    # a1 hosted at least v1 (must_host hint): repair happened.
    assert replication["repaired"], "no computation was repaired"


def test_run_device_mode_scenario():
    """Device-path dynamic DCOP (VERDICT #7): scenario events against
    the warm-started device engine, with cost continuity asserted —
    an agent departure re-homes its computations in the placement map
    but cannot perturb the on-device trajectory."""
    result = run_cli([
        "-t", "60",
        "run", "-a", "maxsum", "-d", "adhoc", "-k", "2",
        "-m", "device", "-c", "500",
        "-s", os.path.join(INSTANCES, "scenario_remove_a1.yaml"),
        os.path.join(INSTANCES, "coloring_4agents_10vars.yaml"),
    ], timeout=240)
    assert result["backend"] == "device"
    assert len(result["assignment"]) == 10
    # The departed agent's computations were re-homed.
    assert result["replication"]["repaired"]
    assert "a1" not in result["replication"]["placement_agents"]
    # The warm-started engine kept its trajectory across the event:
    # the event snapshot carries a live cycle counter and the final run
    # continued past it without any recompile or state reset.
    assert result["events"]
    for ev in result["events"]:
        assert ev["cycle"] >= 1
        assert result["cycle"] > ev["cycle"]
    # No graph change happened, so the slack path never recompiled.
    assert result["recompiles"] == 0


def test_run_process_mode_scenario_repairs():
    """Dynamic DCOP over OS processes (reference run.py:387): scenario
    removes a1, repair migrates its computations, all over HTTP between
    spawned agent processes."""
    result = run_cli([
        "-t", "12",
        "run", "-a", "dsa", "-d", "adhoc", "-m", "process", "-k", "2",
        "-s", os.path.join(INSTANCES, "scenario_remove_a1.yaml"),
        os.path.join(INSTANCES, "coloring_4agents_10vars.yaml"),
    ], timeout=180, port_race_attempts=4)
    assert result["backend"] == "process"
    assert len(result["assignment"]) == 10
    assert result["replication"]["ktarget"] == 2
    assert result["replication"]["repaired"]
