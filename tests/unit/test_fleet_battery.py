"""Fleet-serving battery (ISSUE 15): structure-affinity routing,
replicated workers, and the persistent AOT compile cache.

- the router-side affinity key partitions traffic EXACTLY like the
  workers' serving bin key (partition-equivalence over topologies,
  domains and solver params) without paying the cost-table fill;
- rendezvous hashing is deterministic across processes, spreads
  structures over replicas, and remaps ONLY a dead replica's keys
  (the property that keeps disk- and jit-warm programs warm through
  membership change);
- routing policy logic without any subprocess: affinity hits,
  least-loaded spillover past ``spill_slack``, breaker-aware
  shedding to 503, round-robin A/B mode, request-pin retention;
- the persistent AOT compile cache: enable/latch handling, hit
  accounting, the cold-call compile split (disk hit → compile =
  retrieval wall, any miss → whole-interval convention), and a
  REAL two-process proof that a fresh process serves a
  known-structure solve without recompiling;
- a real 2-replica fleet over HTTP: burst parity with solo
  ``api.solve``, ``affinity_hit_fraction`` on /stats, pinned
  /result polling, fleet /healthz, SIGTERM-equivalent drain to
  exit 0 (the SIGKILL handoff lives in tools/chaos_soak.py
  ``replica_kill`` and tools/serve_smoke.py).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine import aotcache
from pydcop_tpu.engine.compile import compile_dcop
from pydcop_tpu.serving import binning
from pydcop_tpu.serving.router import (
    DOWN,
    UP,
    FleetRouter,
    FleetUnavailable,
    Replica,
    _rendezvous_score,
)

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _ring(n: int, seed: int, colors: int = 3) -> DCOP:
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", list(range(colors)))
    dcop = DCOP(f"fleet_{n}_{colors}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n):
        table = rng.integers(0, 10,
                             size=(colors, colors)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[(k + 1) % n]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


# ------------------------------------------------------------------ #
# affinity key


class TestAffinityKey:
    def test_partition_equivalent_to_bin_key(self):
        """Two DCOPs share an affinity key iff they share a serving
        bin key — over same-structure/different-cost pairs, different
        topologies and different domain sizes."""
        instances = (
            [_ring(8, s) for s in range(3)]        # one structure
            + [_ring(11, s) for s in range(2)]     # another
            + [_ring(8, 7, colors=4)]              # domain differs
        )
        params = binning.normalize_params({"max_cycles": 60})
        keys = []
        for dcop in instances:
            graph, _meta = compile_dcop(dcop, noise_level=0.01)
            keys.append((binning.affinity_key(
                dcop, {"max_cycles": 60}),
                binning.bin_key(graph, params)))
        for i, (aff_i, bin_i) in enumerate(keys):
            for j, (aff_j, bin_j) in enumerate(keys):
                assert (aff_i == aff_j) == (bin_i == bin_j), (
                    f"affinity/bin partition disagreement between "
                    f"instance {i} and {j}")

    def test_params_ride_in_the_key(self):
        dcop = _ring(8, 0)
        assert binning.affinity_key(dcop, {"max_cycles": 60}) \
            != binning.affinity_key(dcop, {"max_cycles": 61})
        assert binning.affinity_key(dcop, {"max_cycles": 60}) \
            == binning.affinity_key(dcop, {"max_cycles": 60})

    def test_bad_params_reject_like_submit(self):
        with pytest.raises(ValueError):
            binning.affinity_key(_ring(8, 0), {"bogus": 1})

    def test_service_defaults_merge_into_the_key(self):
        """A client spelling a service default explicitly must hash
        to the same affinity key as one omitting it — the router
        merges its fleet default_params under the request params
        before keying (otherwise same-bin traffic splits across
        replicas whenever the fleet runs non-module defaults)."""
        router = FleetRouter(replicas=1,
                             default_params={"max_cycles": 60})
        dcop = _ring(8, 0)
        merged = dict(router.default_params)   # params={} request
        implicit = binning.affinity_key(dcop, merged)
        explicit = binning.affinity_key(dcop, {"max_cycles": 60})
        assert implicit == explicit
        assert implicit != binning.affinity_key(dcop, None)

    def test_no_cost_tables_needed(self):
        """The key is computable for a problem whose cost tables
        would be huge — the whole point of not compiling at the
        router (here just asserted cheap + stable)."""
        dcop = _ring(64, 3)
        t0 = time.perf_counter()
        digest = binning.affinity_key(dcop, None)
        assert time.perf_counter() - t0 < 0.5
        assert digest == binning.affinity_key(_ring(64, 99), None)


# ------------------------------------------------------------------ #
# rendezvous hashing


class TestRendezvous:
    def test_deterministic_and_spread(self):
        digests = [f"structure-{i}" for i in range(64)]
        owners = {
            d: max(range(4),
                   key=lambda k: _rendezvous_score(d, k))
            for d in digests
        }
        again = {
            d: max(range(4),
                   key=lambda k: _rendezvous_score(d, k))
            for d in digests
        }
        assert owners == again
        counts = [list(owners.values()).count(k) for k in range(4)]
        assert all(c > 0 for c in counts), counts

    def test_membership_change_remaps_only_dead_keys(self):
        """Remove replica 2: every key it did NOT own keeps its
        owner — the rendezvous property that preserves warm caches
        through a replica death."""
        digests = [f"structure-{i}" for i in range(128)]
        owners = {
            d: max(range(4),
                   key=lambda k: _rendezvous_score(d, k))
            for d in digests
        }
        survivors = [0, 1, 3]
        after = {
            d: max(survivors,
                   key=lambda k: _rendezvous_score(d, k))
            for d in digests
        }
        for d in digests:
            if owners[d] != 2:
                assert after[d] == owners[d]


# ------------------------------------------------------------------ #
# routing policy (no subprocesses)


def _bench_router(n=3, **kw) -> FleetRouter:
    """A router with synthetic UP replicas and no processes —
    pick()/pin()/stats() are pure bookkeeping."""
    router = FleetRouter(replicas=n, **kw)
    for k in range(n):
        replica = Replica(k, None, f"/dev/null-{k}")
        replica.status = UP
        replica.port = 1  # non-None: counts as addressable
        router.replicas.append(replica)
    return router


class TestRoutingPolicy:
    def test_affinity_hits_accumulate(self):
        router = _bench_router()
        first, hit0 = router.pick("digest-a")
        router.release(first)
        assert hit0 is False
        second, hit1 = router.pick("digest-a")
        router.release(second)
        assert hit1 is True and second is first
        stats = router.stats()
        assert stats["affinity_hit_fraction"] == 0.5

    def test_spillover_past_slack(self):
        router = _bench_router(spill_slack=2)
        primary, _hit = router.pick("digest-b")
        primary.in_flight = 10  # deep backlog on the warm replica
        chosen, _hit = router.pick("digest-b")
        assert chosen is not primary
        assert chosen.in_flight == 1
        assert router.spillovers == 1

    def test_breaker_aware_shedding(self):
        router = _bench_router(n=2)
        router.replicas[0].breaker_open = True
        chosen, _hit = router.pick("digest-c")
        assert chosen is router.replicas[1]
        router.replicas[1].status = DOWN
        with pytest.raises(FleetUnavailable):
            router.pick("digest-c")
        assert router.stats()["shed"] == 1

    def test_round_robin_mode_cycles(self):
        router = _bench_router(affinity="round_robin")
        picks = []
        for _ in range(6):
            replica, hit = router.pick("same-digest")
            router.release(replica)
            picks.append(replica.index)
        assert set(picks) == {0, 1, 2}

    def test_pin_table_bounded(self):
        import pydcop_tpu.serving.router as router_mod

        router = _bench_router(n=1)
        replica = router.replicas[0]
        keep = router_mod.PIN_KEEP
        for i in range(keep + 10):
            router.pin(f"r{i}", replica)
        assert len(router._pins) == keep
        assert router.pinned("r0") is None          # evicted oldest
        assert router.pinned(f"r{keep + 9}") is replica

    def test_rejects_bad_config(self):
        with pytest.raises(ValueError):
            FleetRouter(replicas=0)
        with pytest.raises(ValueError):
            FleetRouter(affinity="sticky")


# ------------------------------------------------------------------ #
# persistent AOT compile cache


class TestAotCache:
    def test_dispatch_compile_contract(self):
        """What one dispatch did, from the calling thread's counter
        snapshots around it: compiles, disk-cache loads, and the
        seconds both took — 0 when neither happened, never the whole
        interval."""
        before = {"hits": 2, "misses": 1, "retrieval_s": 0.5,
                  "saved_s": 0.0, "compiles": 3, "compile_s": 4.0}
        pure_hit = dict(before, hits=4, retrieval_s=0.56)
        with_compile = dict(pure_hit, misses=2, compiles=4,
                            compile_s=4.3)
        got = aotcache.dispatch_compile(1.0, before, pure_hit)
        assert got["cache_loads"] == 2 and got["xla_compiles"] == 0
        assert got["compile_s"] == pytest.approx(0.06)
        # Clamped into the measured interval.
        assert aotcache.dispatch_compile(
            0.01, before, pure_hit)["compile_s"] == pytest.approx(0.01)
        # A compile adds XLA's own seconds to the retrieval wall.
        got = aotcache.dispatch_compile(1.0, before, with_compile)
        assert got["xla_compiles"] == 1
        assert got["compile_s"] == pytest.approx(0.36)
        # Neither happened: nothing is charged.
        assert aotcache.dispatch_compile(1.0, before, dict(before)) == {
            "xla_compiles": 0, "cache_loads": 0, "compile_s": 0.0}

    @pytest.mark.parametrize("case", [
        "jax_env_wins", "explicit_arg", "pydcop_env", "default"])
    def test_cache_dir_resolution_order(self, case, tmp_path,
                                        monkeypatch):
        """JAX's own env setting stands; else the explicit directory
        (argument, or the env var workers inherit); else ONE fixed
        path inside the checkout — never a temp-, pid- or
        time-derived one (the path is part of every entry's key)."""
        monkeypatch.delenv(aotcache.JAX_ENV_DIR, raising=False)
        monkeypatch.delenv(aotcache.ENV_DIR, raising=False)
        arg = None
        if case == "jax_env_wins":
            monkeypatch.setenv(aotcache.JAX_ENV_DIR,
                               str(tmp_path / "jax_own"))
            monkeypatch.setenv(aotcache.ENV_DIR,
                               str(tmp_path / "inherited"))
            arg = str(tmp_path / "arg")
            want = (str(tmp_path / "jax_own"), "jax_env")
        elif case == "explicit_arg":
            monkeypatch.setenv(aotcache.ENV_DIR,
                               str(tmp_path / "inherited"))
            arg = str(tmp_path / "arg")
            want = (arg, "explicit")
        elif case == "pydcop_env":
            monkeypatch.setenv(aotcache.ENV_DIR,
                               str(tmp_path / "inherited"))
            want = (str(tmp_path / "inherited"), "explicit")
        else:
            want = (os.path.join(REPO, ".cache", "jax"), "default")
        assert aotcache.resolve_cache_dir(arg) == want
        # Asked twice, the same answer: nothing in it moves.
        assert aotcache.resolve_cache_dir(arg) == want

    @pytest.mark.parametrize("jax_env_set", [True, False])
    def test_enable_sets_the_directory_only_without_jax_env(
            self, jax_env_set, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set no code path calls
        ``jax.config.update("jax_compilation_cache_dir", ...)``;
        without it, exactly the resolved directory is set."""
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache,
        )
        from pydcop_tpu.engine.aotcache import _lock, _state

        monkeypatch.delenv(aotcache.ENV_DIR, raising=False)
        if jax_env_set:
            monkeypatch.setenv(aotcache.JAX_ENV_DIR,
                               str(tmp_path / "jax_own"))
        else:
            monkeypatch.delenv(aotcache.JAX_ENV_DIR, raising=False)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        monkeypatch.setattr(compilation_cache, "reset_cache",
                            lambda: None)
        with _lock:
            prior = dict(_state)
        try:
            got = aotcache.enable_persistent_compile_cache(
                str(tmp_path / "arg"))
        finally:
            with _lock:
                _state.update(prior)
        dirs = [v for k, v in updates
                if k == "jax_compilation_cache_dir"]
        if jax_env_set:
            assert got == str(tmp_path / "jax_own") and dirs == []
        else:
            assert got == str(tmp_path / "arg") and dirs == [got]

    def test_fresh_process_serves_without_recompiling(self, tmp_path):
        """THE acceptance mechanism: process A compiles a structure
        (disk miss), process B solves the same structure with its
        compile component collapsed to the cache-retrieval wall."""
        cache = str(tmp_path / "aot")
        code = (
            "import os, sys, json\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "from pydcop_tpu.engine import aotcache\n"
            "aotcache.enable_persistent_compile_cache("
            f"{cache!r})\n"
            "from tests.unit.test_fleet_battery import _ring\n"
            "from pydcop_tpu.api import solve\n"
            "res = solve(_ring(16, 5), 'maxsum', max_cycles=60)\n"
            "print(json.dumps({'compile': res['compile_time'],"
            " 'counters': aotcache.counters()}))\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO)
        runs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", code], env=env, cwd=REPO,
                capture_output=True, text=True, timeout=300)
            assert proc.returncode == 0, proc.stderr[-800:]
            runs.append(json.loads(proc.stdout.splitlines()[-1]))
        cold, warm = runs
        assert cold["counters"]["misses"] >= 1
        assert cold["counters"]["hits"] == 0
        assert warm["counters"]["hits"] >= 1
        assert warm["counters"]["misses"] == 0
        # The ledger claim: a warm-disk cold call's compile component
        # is the retrieval wall — far under the real compile.
        assert warm["compile"] < 0.5 * cold["compile"], (cold, warm)

    def test_stats_counts_disk_entries(self, tmp_path):
        from pydcop_tpu.engine.aotcache import _lock, _state

        (tmp_path / "a-cache").write_bytes(b"x" * 10)
        (tmp_path / "b-cache").write_bytes(b"y" * 20)
        (tmp_path / "b-atime").write_bytes(b"")
        with _lock:
            prior = dict(_state)
            _state["enabled"] = True
            _state["dir"] = str(tmp_path)
        try:
            stats = aotcache.stats()
        finally:
            with _lock:
                _state.update(prior)
        assert stats["entries"] == 2
        assert stats["bytes"] >= 30


# ------------------------------------------------------------------ #
# the real fleet, end to end


def _post(url, body, timeout=120):
    req = urllib.request.Request(
        url + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


class TestFleetEndToEnd:
    def test_two_replica_fleet_serves_like_one_service(self):
        from pydcop_tpu import api
        from pydcop_tpu.dcop.yamldcop import dcop_yaml

        handle = api.serve(port=0, replicas=2, batch_window_s=0.05,
                           max_batch=8, heartbeat_s=0.2)
        try:
            url = handle.url
            dcops = ([_ring(9, 30 + s) for s in range(3)]
                     + [_ring(12, 60 + s) for s in range(3)])
            payloads = [dcop_yaml(d) for d in dcops]
            results = [None] * len(dcops)

            def client(i):
                results[i] = _post(url, {
                    "dcop": payloads[i], "wait": True,
                    "timeout": 120,
                    "params": {"max_cycles": 60}})

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(dcops))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert all(r is not None and r[0] == 200
                       and r[1]["status"] == "FINISHED"
                       for r in results), results

            # Wire parity: the fleet answers exactly like solo
            # api.solve — clients cannot tell the fleet exists.
            for dcop, (_, res) in zip(dcops, results):
                solo = api.solve(dcop, "maxsum", backend="device",
                                 max_cycles=60,
                                 algo_params={"layout": "edge"})
                assert res["assignment"] == solo["assignment"]
                assert res["cost"] == solo["cost"]

            # Async path rides the pin table.
            status, ack = _post(url, {"dcop": payloads[0],
                                      "params": {"max_cycles": 60}})
            assert status == 202 and ack["id"].startswith("f")
            deadline = time.monotonic() + 60
            body = None
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(
                            url + "/result/" + ack["id"],
                            timeout=10) as resp:
                        if resp.status == 200:
                            body = json.loads(resp.read())
                            break
                except urllib.error.HTTPError:
                    pass
                time.sleep(0.1)
            assert body is not None \
                and body["status"] == "FINISHED"

            with urllib.request.urlopen(url + "/stats",
                                        timeout=30) as resp:
                stats = json.loads(resp.read())
            assert stats["up"] == 2
            assert stats["routed"] >= 7
            assert stats["affinity_hit_fraction"] is not None
            assert stats["affinity_hit_fraction"] > 0
            # Both structures warmed SOME replica; same-structure
            # traffic stayed put (rendezvous is deterministic).
            assert sum(w["forwarded"]
                       for w in stats["workers"]) == stats["routed"]
            with urllib.request.urlopen(url + "/healthz",
                                        timeout=10) as resp:
                assert json.loads(resp.read())["status"] == "ok"
        finally:
            summary = handle.stop()
        # Fleet drain: every worker exits 0 (the SIGTERM contract).
        assert [w["exit"] for w in summary["workers"]] == [0, 0]

    def test_unknown_result_404_and_bad_body_400(self):
        from pydcop_tpu import api

        handle = api.serve(port=0, replicas=2, batch_window_s=0.02)
        try:
            url = handle.url
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(url + "/result/nope",
                                       timeout=10)
            assert err.value.code == 404
            status, body = _post(url, {"dcop": "   "})
            assert status == 400
            status, body = _post(url, {"dcop": "not: [valid"})
            assert status == 400
        finally:
            handle.stop()


# ------------------------------------------------------------------ #
# CLI knobs


class TestServeCli:
    def test_fleet_knobs_parse(self):
        import argparse

        from pydcop_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        serve_cmd.set_parser(sub)
        args = parser.parse_args(
            ["serve", "--replicas", "4", "--affinity", "round_robin",
             "--compile_cache_dir", "/tmp/aot", "--heartbeat",
             "0.5", "--spill_slack", "7", "--port_file", "/tmp/p"])
        assert args.replicas == 4
        assert args.affinity == "round_robin"
        assert args.compile_cache_dir == "/tmp/aot"
        assert args.heartbeat == 0.5
        assert args.spill_slack == 7
        assert args.port_file == "/tmp/p"

    def test_params_json_knob_parses(self):
        import argparse

        from pydcop_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        serve_cmd.set_parser(sub)
        args = parser.parse_args(
            ["serve", "--params_json", '{"prune": 1}'])
        assert args.params_json == '{"prune": 1}'

    def test_fleet_forwards_full_default_params(self):
        """api.serve's fleet path must hand EVERY default-param key
        to the workers — a replicas=2 service silently dropping the
        caller's stability/prune defaults would solve differently
        than replicas=1."""
        import json as json_mod
        from unittest import mock

        from pydcop_tpu import api

        captured = {}

        class FakeRouter:
            def __init__(self, **kw):
                captured.update(kw)
                raise RuntimeError("stop here")

        with mock.patch(
                "pydcop_tpu.serving.router.FleetRouter", FakeRouter):
            with pytest.raises(RuntimeError, match="stop here"):
                api.serve(replicas=2, default_params={
                    "max_cycles": 99, "damping": 0.7,
                    "stability": 0.05, "prune": 1})
        worker_args = captured["worker_args"]
        assert worker_args[worker_args.index("--cycles") + 1] == "99"
        assert worker_args[
            worker_args.index("--damping") + 1] == "0.7"
        extra = json_mod.loads(
            worker_args[worker_args.index("--params_json") + 1])
        assert extra == {"stability": 0.05, "prune": 1}

    def test_affinity_choices_enforced(self):
        import argparse

        from pydcop_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        sub = parser.add_subparsers()
        serve_cmd.set_parser(sub)
        with pytest.raises(SystemExit):
            parser.parse_args(["serve", "--affinity", "sticky"])

    def test_worker_without_its_device_fails_the_start_at_once(
            self, tmp_path):
        """One process per chip: a worker takes its device before it
        binds, so one that cannot have it (here: a platform that does
        not exist; on a one-chip machine: the chip another worker
        holds) dies at start and the router reports the death with
        the worker's log tail — at once, not at the ready deadline.
        The router parent runs under the same setting and gets as far
        as spawning: it never initialises a backend itself."""
        env = dict(os.environ, JAX_PLATFORMS="no_such_platform",
                   PYTHONPATH=REPO,
                   PYDCOP_COMPILE_CACHE_DIR=str(tmp_path / "cache"))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli", "serve",
             "--port", "0", "--replicas", "2",
             "--port_file", str(tmp_path / "port")],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=110)
        elapsed = time.monotonic() - t0
        assert proc.returncode != 0
        assert elapsed < 60, elapsed
        assert "died on startup" in proc.stderr, proc.stderr[-800:]
        # The reason travels in the message, not only in a log file.
        assert "no_such_platform" in proc.stderr
        assert not (tmp_path / "port").exists()
