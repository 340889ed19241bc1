"""No chip, no result — and the chip smoke's own control flow.

``chip_smoke.py`` is for the TPU: a run that finds none fails
(non-zero, no result line) instead of falling back.  The
smoke's phases are rehearsed here on the CPU at a tiny size, steered
from the test (sizes and the expected platform are patched here, not
options of the script), so a PR that breaks the script's paths or
arguments learns it before spending chip time.
"""

import json
import os
import subprocess
import sys
from functools import partial

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(script, **env_changes):
    env = dict(os.environ)
    for key, value in env_changes.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, script], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


class TestNoChipFails:
    def test_chip_smoke_on_cpu_exits_nonzero_without_result(self):
        proc = _run("chip_smoke.py", JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        lines = [json.loads(line)
                 for line in proc.stdout.strip().splitlines()]
        assert lines and not any("ok" in line for line in lines)
        assert "FAILED in phase 'device'" in proc.stderr


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke at a tiny size, expecting the platform tests run
    on; the Pallas kernel interpreted (no Mosaic compiler for the
    CPU — its compile for the chip is tests/unit/test_chip_compile)."""
    import chip_smoke
    from pydcop_tpu.ops import pallas_maxsum

    for name, value in dict(
            PLATFORM="cpu", SOLVE_VARS=120, SOLVE_P_EDGE=3.0 / 120,
            SOLVE_CYCLES=40, SEGMENT_CYCLES=20, DSA_CYCLES=20,
            BIG_VARS=300, BIG_P_EDGE=2.0 / 300, BIG_CYCLES=200,
            SERVE_GRID_VARS=16, SERVE_SAME_STRUCTURE=3,
            SERVE_OTHER=(("grid", 25), ("random", 12)),
            SERVE_CYCLES=30, SYNC_REPS=1).items():
        monkeypatch.setattr(chip_smoke, name, value)
    monkeypatch.setattr(
        pallas_maxsum, "binary_factor_update",
        partial(pallas_maxsum.binary_factor_update, interpret=True))
    # ``pydcop solve`` turns the persistent compile cache on for the
    # process; leave this test process the way it was found.
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from pydcop_tpu.engine import aotcache

    cache_dir = jax.config.jax_compilation_cache_dir
    with aotcache._lock:
        state = dict(aotcache._state)
    yield chip_smoke
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()
    with aotcache._lock:
        aotcache._state.update(state)


def _phases(capsys):
    return {line["phase"]: line for line in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}


class TestChipSmokeRehearsal:
    def test_solve_phases(self, smoke, tmp_path, capsys):
        smoke.phase_solve_10k(str(tmp_path), seed=1)
        smoke.phase_solve_100k(seed=1)
        phases = _phases(capsys)
        assert set(phases) == {
            "generate_10k", "solve_10k_maxsum_cli",
            "solve_10k_maxsum_segmented", "solve_10k_dsa",
            "pallas_kernel", "sync_vs_block_until_ready",
            "generate_100k", "solve_100k_maxsum"}
        seg = phases["solve_10k_maxsum_segmented"]
        assert seg["device_cost"] == seg["cost"]
        assert seg["cost"] == phases["solve_10k_maxsum_cli"]["cost"]

    def test_serve_phase(self, smoke, capsys):
        smoke.phase_serve(seed=1)
        serve = _phases(capsys)["serve"]
        assert serve["requests"] == 3 + 2 + 3
        assert serve["largest_batch"] > 1
        assert serve["backend"] == "cpu"

    def test_four_chip_phase_on_forced_host_devices(self, smoke,
                                                    capsys):
        smoke.phase_four_chips(seed=1)
        phases = _phases(capsys)
        assert phases["four_chip_parity"]["identical_assignments"]
        placement = phases["four_chip_placement"]
        for path in ("n_devices_4", "shards_4"):
            for array in ("bucket_costs", "var_costs"):
                assert len(placement[path][array]["devices"]) == 4

    def test_a_wrong_cost_fails_the_phase(self, smoke):
        dcop = smoke.generate(16, "grid", seed=1, soft=True)
        assignment = {v: "R" for v in dcop.variables}
        cost, violations = dcop.solution_cost(assignment)
        smoke.check_answer(dcop, assignment, cost, violations, "ok")
        with pytest.raises(smoke.SmokeFailure, match="host cost"):
            smoke.check_answer(dcop, assignment, cost + 1, violations,
                               "wrong")
