"""Serve-smoke gate: end-to-end proof of the solve service's batching.

Part of ``make test`` (like ``make trace-demo`` / ``make shard-smoke``).
Starts the real service on port 0 and drives it over HTTP:

1. **Coalescing + parity**: a concurrent burst of N same-structure
   requests (plus a second structure mixed in) must complete in FEWER
   than N device dispatches (batch-coalescing counters asserted), at
   least one dispatch must be multi-instance, the two structures must
   never share a dispatch (dispatch count >= 2), and EVERY response's
   assignment must equal the equivalent solo ``api.solve`` run.
2. **Overload**: with a tiny high-water mark and a slowed dispatch,
   a burst past the queue bound must yield 429s — not a hang and not
   a dropped request: every accepted request finishes, every rejected
   one is a clean 429, and ``pydcop_requests_total{status}`` accounts
   for every single request fired.
3. **kill -9 + journal replay** (ISSUE 8 acceptance): a REAL
   ``pydcop serve --journal_dir D`` subprocess is SIGKILLed mid-burst;
   every acknowledged (202) request must have its accepted record on
   disk, and a ``--recover`` start must replay every
   accepted-but-unfinished one to completion — zero acknowledged
   requests lost.
4. **SIGTERM drain** (ISSUE 8 satellite): an orchestrated-restart
   signal makes the serve process drain and exit 0, logging the
   drained/replayable counts — accepted work is never silently
   dropped.
5. **Session kill -9 + whole-session replay** (ISSUE 13
   acceptance): a stateful session is opened over HTTP, 3 event
   batches are acked, the process is SIGKILLed; every acked record
   must be on disk and a ``--recover`` start must resume the
   session, apply the journaled-but-unapplied batches, and close
   with exactly the uninterrupted run's final cost — zero acked
   events lost.
6. **Request-scoped tracing** (ISSUE 9 acceptance): a real-HTTP
   batched burst is traced; ``pydcop trace query --request ID`` (the
   REAL CLI, on the exported trace) must return a single well-nested
   tree holding the submit, queue, ``serve_dispatch`` and
   ``engine_segment`` spans all tagged with that request's trace_id —
   and the p99 bucket of ``pydcop_request_latency_seconds`` must
   expose an exemplar trace_id resolvable by the same query.
7. **Efficiency accounting** (ISSUE 14 acceptance): on a real serve
   burst every served request carries a time ledger whose components
   sum to its measured total latency within 5%, and the
   ``useful_work_fraction`` + attainment rollups are visible in
   ``/stats``, ``/metrics`` (backend-labeled), ``/profile`` and
   ``pydcop profile report --url`` (the real CLI).
8. **2-replica fleet burst** (ISSUE 15 acceptance): a mixed-structure
   burst against a real 2-worker fleet behind the structure-affinity
   router answers every request bit-identical to solo ``api.solve``,
   with affinity accounting on /stats and a clean whole-fleet drain.
9. **Elastic-fleet migration** (ISSUE 16 acceptance): an operator
   ``POST /admin/migrate`` moves a warm session between replicas of a
   host-striped fleet with zero acked events lost — the router pin
   follows the session and the fairness/migration control surfaces
   are live on /stats.
10. **Pipelined flushes + speculative compiles** (ISSUE 18
    acceptance): a real-HTTP mixed burst served with pipelining and
    speculation ON answers bit-identical to solo ``api.solve``;
    ``/stats`` shows ``speculative_compiles_total`` with >= 1 hit
    and >= 2 pipelined dispatches, and the ``/profile`` compile
    waste share is lower than the same workload with both OFF.
11. **Exact-inference tier** (ISSUE 17 acceptance): a request with
    ``params.algo="dpop"`` answers with ``optimal: true`` and the
    assignment the solo exact solve produces, while a problem whose
    UTIL hypercube exceeds the element cap gets a structured 400
    (``status: rejected_width``) — never a 500, and the service
    keeps serving iterative traffic afterwards.

Run:  python tools/serve_smoke.py      (exit 0 = all claims hold)
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

SAME_STRUCTURE_BURST = 8
OTHER_STRUCTURE_BURST = 3
MAX_CYCLES = 120
OVERLOAD_BURST = 10


def build_instance(n_vars: int, seed: int):
    """Small random-cost ring coloring; same ``n_vars`` -> same
    structure bin, different seeds -> different cost tables."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("colors", "", [0, 1, 2])
    dcop = DCOP(f"smoke_{n_vars}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    for k, (i, j) in enumerate(
            [(i, (i + 1) % n_vars) for i in range(n_vars)]):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(
            NAryMatrixRelation([vs[i], vs[j]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


def post(url: str, body: dict):
    req = urllib.request.Request(
        url + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read())


def scrape_requests_total(url: str) -> dict:
    with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for line in text.splitlines():
        m = re.match(
            r'pydcop_requests_total\{status="([^"]+)"\} (\S+)', line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def check(cond, message):
    if not cond:
        print(f"serve_smoke: FAIL — {message}", file=sys.stderr)
        sys.exit(1)
    print(f"serve_smoke: ok — {message}")


def leg_coalescing():
    from pydcop_tpu import api

    handle = api.serve(port=0, batch_window_s=0.3, max_batch=16,
                       max_queue=64)
    try:
        url = handle.url
        dcops = (
            [build_instance(12, seed)
             for seed in range(SAME_STRUCTURE_BURST)]
            + [build_instance(9, 100 + seed)
               for seed in range(OTHER_STRUCTURE_BURST)]
        )
        from pydcop_tpu.dcop.yamldcop import dcop_yaml

        payloads = [dcop_yaml(d) for d in dcops]
        results = [None] * len(dcops)

        def client(i):
            results[i] = post(url, {
                "dcop": payloads[i], "wait": True, "timeout": 120,
                "params": {"max_cycles": MAX_CYCLES},
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(dcops))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        check(all(r is not None and r[0] == 200
                  and r[1]["status"] == "FINISHED" for r in results),
              f"all {len(dcops)} burst responses valid")

        stats = handle.service.stats()
        n = len(dcops)
        check(stats["dispatches"] < n,
              f"{n} requests took {stats['dispatches']} device "
              f"dispatches (< {n}: batching coalesced)")
        check(stats["batched_dispatches"] >= 1,
              ">= 1 multi-instance batch dispatched "
              f"({stats['batched_dispatches']})")
        check(stats["dispatches"] >= 2,
              "two structures dispatched separately "
              f"({stats['dispatches']} dispatches)")

        # Every response must match the equivalent solo api.solve.
        for dcop, (_, res) in zip(dcops, results):
            solo = api_solve_cached(dcop, res)
            if res["assignment"] != solo["assignment"]:
                check(False,
                      f"served assignment for {dcop.name} differs "
                      "from solo api.solve")
        check(True,
              f"all {len(dcops)} served assignments identical to "
              "solo api.solve")
    finally:
        handle.stop()


def leg_mixed_envelope():
    """ISSUE 11 acceptance: a concurrent burst of DISTINCT-structure
    requests — one per topology, so pure structure binning would
    dispatch every one solo — must coalesce below one dispatch per
    request via the envelope tier, with every response bit-identical
    to the solo ``api.solve`` answer (masking proven end-to-end over
    real HTTP, not assumed)."""
    from pydcop_tpu import api

    handle = api.serve(port=0, batch_window_s=0.3, max_batch=16,
                       max_queue=64)
    try:
        url = handle.url
        # Five distinct topologies (different variable counts -> five
        # different structure signatures), ONE request each: zero
        # same-structure coalescing is possible.
        dcops = [build_instance(n, 40 + n)
                 for n in (9, 12, 15, 18, 21)]
        from pydcop_tpu.dcop.yamldcop import dcop_yaml

        payloads = [dcop_yaml(d) for d in dcops]
        results = [None] * len(dcops)

        def client(i):
            results[i] = post(url, {
                "dcop": payloads[i], "wait": True, "timeout": 120,
                "params": {"max_cycles": MAX_CYCLES},
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(dcops))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        check(all(r is not None and r[0] == 200
                  and r[1]["status"] == "FINISHED" for r in results),
              f"all {len(dcops)} mixed-structure responses valid")

        stats = handle.service.stats()
        n = len(dcops)
        check(stats["dispatches"] < n,
              f"{n} distinct-structure requests took "
              f"{stats['dispatches']} dispatches (< {n}: envelope "
              "packing coalesced structures)")
        check(stats["envelope_dispatches"] >= 1,
              ">= 1 envelope-packed dispatch "
              f"({stats['envelope_dispatches']}, lane "
              f"{stats['lane_dispatches']})")
        decisions = stats["envelope_decisions"]
        check(any(d.get("packed") for d in decisions),
              "pack-vs-solo cost decision recorded and packed "
              f"({decisions[-1] if decisions else None})")
        packed_responses = [
            r[1] for r in results
            if r[1].get("batch", {}).get("packing") in ("envelope",
                                                        "lane")]
        check(len(packed_responses) >= 2,
              f"{len(packed_responses)} responses carry packed-"
              "dispatch accounting (packing/envelope_waste keys)")

        # THE acceptance bar: every envelope-packed response equals
        # the solo api.solve answer bit for bit.
        for dcop, (_, res) in zip(dcops, results):
            solo = api_solve_cached(dcop, res)
            if res["assignment"] != solo["assignment"]:
                check(False,
                      f"mixed-burst assignment for {dcop.name} "
                      "differs from solo api.solve")
            if res["cost"] != solo["cost"]:
                check(False,
                      f"mixed-burst cost for {dcop.name} differs "
                      "from solo api.solve")
        check(True,
              f"all {len(dcops)} mixed-burst answers bit-identical "
              "to solo api.solve")
    finally:
        handle.stop()


def leg_efficiency():
    """ISSUE 14 acceptance: on a real serve burst, every served
    request carries a time ledger whose components sum to its
    measured total latency within 5%, and the
    ``useful_work_fraction`` + attainment rollups are visible on all
    four surfaces — ``/stats``, ``/metrics`` (backend-labeled),
    ``/profile`` and ``pydcop profile report --url`` (the real CLI
    entry point)."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.observability.efficiency import (
        ledger_component_sum,
    )

    handle = api.serve(port=0, batch_window_s=0.2, max_batch=16,
                       max_queue=64)
    try:
        url = handle.url
        payloads = [dcop_yaml(build_instance(7, 70 + s))
                    for s in range(4)]
        payloads.append(dcop_yaml(build_instance(11, 90)))

        def burst():
            results = [None] * len(payloads)

            def client(i):
                results[i] = post(url, {
                    "dcop": payloads[i], "wait": True,
                    "timeout": 120,
                    "params": {"max_cycles": MAX_CYCLES},
                })

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(payloads))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            return results

        burst()            # cold round: compiles + cost captures
        results = burst()  # warm round: the attainment evidence
        check(all(r is not None and r[0] == 200
                  and r[1]["status"] == "FINISHED" for r in results),
              f"all {len(payloads)} efficiency-burst responses "
              "finished")

        # 1. Every served request carries a summing time ledger.
        for _, res in results:
            ledger = res.get("ledger")
            check(isinstance(ledger, dict) and "total_s" in ledger,
                  f"response {res['id']} carries a time ledger")
            total = ledger["total_s"]
            gap = abs(ledger_component_sum(ledger) - total)
            check(total > 0 and gap <= 0.05 * total,
                  f"{res['id']} ledger components sum to the "
                  f"measured total within 5% (gap {gap * 1e3:.3f}ms "
                  f"of {total * 1e3:.1f}ms)")

        # 2. /stats carries the efficiency block with a real number.
        with urllib.request.urlopen(url + "/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        eff = stats.get("efficiency") or {}
        check(eff.get("backend") == "cpu",
              f"/stats efficiency block names the resolved backend "
              f"({eff.get('backend')})")
        check(eff.get("useful_work_fraction") is not None
              and 0 < eff["useful_work_fraction"] <= 1.0
              and eff.get("attainment") is not None,
              "/stats useful_work_fraction "
              f"({eff.get('useful_work_fraction')}) and attainment "
              f"({eff.get('attainment')}) populated after the warm "
              "round")
        check(eff.get("ledger_components_s", {}).get("execute", 0)
              > 0,
              "/stats ledger breakdown has device execute seconds")

        # 3. /metrics: backend-labeled gauges in the exposition.
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
        check(re.search(
            r'pydcop_useful_work_fraction\{backend="cpu"\} \S+',
            text) is not None,
            "backend-labeled pydcop_useful_work_fraction exported "
            "on /metrics")
        check(re.search(
            r'pydcop_device_execute_seconds_total\{backend="cpu"',
            text) is not None,
            "backend-labeled device-execute seconds exported on "
            "/metrics")

        # 4. /profile serves the live rollup.
        with urllib.request.urlopen(url + "/profile",
                                    timeout=30) as resp:
            profile = json.loads(resp.read())
        check(profile.get("backend", {}).get("backend") == "cpu"
              and profile.get("structures")
              and profile.get("waste_by_cause") is not None,
              "/profile serves the rollup (backend + structures + "
              "waste taxonomy)")
        cpu = profile.get("backends", {}).get("cpu") or {}
        check(cpu.get("useful_work_fraction") is not None,
              "/profile per-backend useful_work_fraction "
              f"({cpu.get('useful_work_fraction')})")

        # 5. The REAL CLI: pydcop profile report --url --json.
        proc = subprocess.run(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli", "profile",
             "report", "--url", url, "--json"],
            capture_output=True, text=True, timeout=120,
            cwd=REPO)
        check(proc.returncode == 0,
              f"pydcop profile report --url exits 0 "
              f"({proc.stderr.strip()[:200]})")
        doc = json.loads(proc.stdout)
        live = doc.get("live") or {}
        check(live.get("ledger", {}).get("components_s")
              and live.get("backends", {}).get("cpu", {})
              .get("useful_work_fraction") is not None,
              "profile report --json carries the ledger breakdown "
              "and the cpu useful_work_fraction")
    finally:
        handle.stop()


def leg_pipelined_speculation():
    """ISSUE 18 acceptance: a real-HTTP mixed burst served with
    pipelining + speculation ON answers bit-identical to solo
    ``api.solve``; ``/stats`` shows ``speculative_compiles_total``
    with >= 1 hit and >= 1 pipelined dispatch; and the ``/profile``
    compile waste share is LOWER than an identical workload served
    with both knobs OFF (the speculated program lands in the
    persistent AOT cache, so the first real dispatch retrieves
    instead of building)."""
    import tempfile as _tempfile

    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.engine import batch as engine_batch
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.observability import efficiency
    from pydcop_tpu.serving import binning

    def get_json(url, route):
        with urllib.request.urlopen(url + route, timeout=30) as r:
            return json.loads(r.read())

    def expected_key(dcop):
        graph, _ = compile_dcop(dcop)
        p = binning.normalize_params({"max_cycles": MAX_CYCLES})
        prep = engine_batch._prepare_stacked(
            [graph, graph], p["max_cycles"], p["damping"],
            p["damping_nodes"], p["stability"],
            (1, 2, 4, 8, 16), False, None)
        return str(prep.key)

    def run(on: bool, ns, cache_dir):
        # The comparison runs share one process, so each side gets
        # structures of its OWN sizes — a structure the other side
        # already compiled would serve from the warm jit cache and
        # hide the compile cost this leg exists to compare.
        efficiency.tracker.clear()
        handle = api.serve(
            port=0, batch_window_s=0.25, max_batch=16, max_queue=64,
            pipeline=on, speculate=on, compile_cache_dir=cache_dir)
        pairs = []
        try:
            url = handle.url
            for n in ns:
                # Two sequential solos seed the structure (and, ON,
                # the speculator's arrival histogram).
                for seed in (n * 10, n * 10 + 1):
                    d = build_instance(n, seed)
                    code, res = post(url, {
                        "dcop": dcop_yaml(d), "wait": True,
                        "timeout": 120,
                        "params": {"max_cycles": MAX_CYCLES}})
                    check(code == 200
                          and res["status"] == "FINISHED",
                          f"solo n={n} seed={seed} served "
                          f"(speculation={'on' if on else 'off'})")
                    pairs.append((d, res))
            if on:
                # Wait for the bin-of-2 programs the structures'
                # traffic predicts to land in the AOT cache, then
                # for the speculator to go quiet — on a small box
                # the background builds contend with live compiles
                # for cores, and the measured window below must see
                # only serving work.
                spec = handle.service._speculator
                deadline = time.time() + 120
                for n in ns:
                    want = expected_key(build_instance(n, n * 10))
                    while (time.time() < deadline
                           and want not in spec.compiled_keys):
                        time.sleep(0.1)
                    check(want in spec.compiled_keys,
                          f"speculative bin-of-2 build for n={n} "
                          f"landed ({spec.stats()})")
                while (time.time() < deadline
                       and spec.stats()["queued"] > 0):
                    time.sleep(0.1)
                time.sleep(0.5)
            # The measured serving window: the profile compared
            # below covers ONLY the traffic from here on, the same
            # window on both sides (the seeding solos above pay
            # first-arrival compiles no speculation can predict).
            efficiency.tracker.clear()
            for n in ns:
                # The predicted bin-of-2 arrives — cold in the jit
                # cache; ON, its executable comes off the disk.
                burst = [build_instance(n, n * 10 + s)
                         for s in (2, 3)]
                res2 = [None] * 2

                def client(i, d=None):
                    res2[i] = post(url, {
                        "dcop": dcop_yaml(d), "wait": True,
                        "timeout": 120,
                        "params": {"max_cycles": MAX_CYCLES}})

                threads = [threading.Thread(target=client,
                                            args=(i,),
                                            kwargs={"d": d})
                           for i, d in enumerate(burst)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=180)
                check(all(r is not None and r[0] == 200
                          and r[1]["status"] == "FINISHED"
                          for r in res2),
                      f"bin-of-2 burst for n={n} served")
                pairs.extend(
                    (d, r[1]) for d, r in zip(burst, res2))
            # Final mixed burst: both structures warm at bin 2 —
            # the flush the pipelined scheduler overlaps.
            mixed = [build_instance(n, n * 10 + s)
                     for n in ns for s in (4, 5)]
            resm = [None] * len(mixed)

            def mclient(i, d=None):
                resm[i] = post(url, {
                    "dcop": dcop_yaml(d), "wait": True,
                    "timeout": 120,
                    "params": {"max_cycles": MAX_CYCLES}})

            threads = [threading.Thread(target=mclient, args=(i,),
                                        kwargs={"d": d})
                       for i, d in enumerate(mixed)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            check(all(r is not None and r[0] == 200
                      and r[1]["status"] == "FINISHED"
                      for r in resm),
                  f"mixed {len(mixed)}-request burst served")
            pairs.extend((d, r[1]) for d, r in zip(mixed, resm))
            stats = get_json(url, "/stats")
            profile_doc = get_json(url, "/profile")
        finally:
            handle.stop()
        return pairs, stats, profile_doc

    def compile_share(doc):
        total = doc["ledger"]["total_s"]
        check(total > 0, "profile ledger total positive")
        return doc["waste_by_cause"]["compile_s"] / total

    # Everything (the solo-compare api.solve calls included) runs
    # with the tempdirs alive — the persistent-cache config latches
    # on the last enabled directory, and jit warns on every write
    # into a deleted one.  The XLA cost profiler is vetoed for the
    # comparison: its throwaway AOT build on every cold dispatch
    # runs BEFORE the engine's timed interval and (with the
    # persistent cache on) writes the disk entry the live jit then
    # retrieves, so with it enabled BOTH sides' /profile compile
    # waste collapses to retrieval-sized slivers and the check
    # compares noise.  Vetoed, the OFF side pays its full XLA
    # builds inside the timed interval while the ON side still
    # retrieves what the speculator pre-built.
    prior_profile = os.environ.get("PYDCOP_XLA_PROFILE")
    os.environ["PYDCOP_XLA_PROFILE"] = "0"
    try:
        with _tempfile.TemporaryDirectory() as td_off, \
                _tempfile.TemporaryDirectory() as td_on:
            pairs_off, stats_off, prof_off = run(
                False, (23, 25), td_off)
            pairs_on, stats_on, prof_on = run(True, (26, 29), td_on)
            _check_pipelined_speculation(
                compile_share, pairs_off, stats_off, prof_off,
                pairs_on, stats_on, prof_on)
    finally:
        if prior_profile is None:
            del os.environ["PYDCOP_XLA_PROFILE"]
        else:
            os.environ["PYDCOP_XLA_PROFILE"] = prior_profile


def _check_pipelined_speculation(compile_share, pairs_off, stats_off,
                                 prof_off, pairs_on, stats_on,
                                 prof_on):
    """Assertions for :func:`leg_pipelined_speculation`, run while
    the cache tempdirs are still alive (the solo-compare api.solve
    calls jit into the latched persistent-cache directory)."""
    check(not stats_off["pipeline"]["enabled"]
          and stats_off["pipeline"]["pipelined_dispatches"] == 0,
          "OFF run never pipelined")
    check(stats_on["speculation"]["enabled"],
          "speculation reported enabled on the ON run")
    check(stats_on["speculation"]
          ["speculative_compiles_total"] >= 1,
          "/stats shows speculative_compiles_total >= 1 "
          f"({stats_on['speculation']})")
    check(stats_on["speculation"]["hits"] >= 1,
          ">= 1 speculative hit on a real cold dispatch "
          f"({stats_on['speculation']})")
    check(stats_on["pipeline"]["pipelined_dispatches"] >= 2,
          ">= 2 pipelined dispatches on the mixed flush "
          f"({stats_on['pipeline']})")

    # THE acceptance bar: every ON response (pipelined,
    # speculated, packed or not) equals the solo api.solve
    # answer bit for bit.
    for dcop, res in pairs_on + pairs_off:
        solo = api_solve_cached(dcop, res)
        if res["assignment"] != solo["assignment"]:
            check(False,
                  f"served assignment for {dcop.name} differs "
                  "from solo api.solve")
    check(True,
          f"all {len(pairs_on) + len(pairs_off)} served "
          "answers bit-identical to solo api.solve")

    share_off = compile_share(prof_off)
    share_on = compile_share(prof_on)
    check(share_on < share_off,
          "compile waste share lower with speculation ON "
          f"({share_on:.3f} < {share_off:.3f})")


_SOLO_CACHE = {}


def api_solve_cached(dcop, res=None):
    """The solo ``api.solve`` a served answer ``res`` is held to: of
    the message layout its dispatch ran (a lane-packed union is
    lane-major, everything else the serve plane runs edge-major; an
    unset ``layout`` would let the solo solve pick lane-major, which
    sums each variable's messages in another order)."""
    from pydcop_tpu import api

    packing = (res or {}).get("batch", {}).get("packing")
    layout = "lane" if packing == "lane" else "edge"
    if (dcop.name, layout) not in _SOLO_CACHE:
        _SOLO_CACHE[dcop.name, layout] = api.solve(
            dcop, "maxsum", backend="device", max_cycles=MAX_CYCLES,
            algo_params={"layout": layout})
    return _SOLO_CACHE[dcop.name, layout]


def leg_overload():
    from pydcop_tpu import api

    handle = api.serve(port=0, batch_window_s=0.01, max_batch=2,
                       max_queue=32, high_water=3)
    try:
        url = handle.url
        # Slow the device call down so the burst genuinely overruns
        # the queue (an unthrottled CPU dispatch drains too fast to
        # ever hit the high-water mark on a quiet box).
        service = handle.service
        real_run = service._run_batch

        def slowed(reqs, params):
            time.sleep(0.25)
            return real_run(reqs, params)

        service._run_batch = slowed
        before = scrape_requests_total(url)
        from pydcop_tpu.dcop.yamldcop import dcop_yaml

        statuses = [None] * OVERLOAD_BURST
        payloads = [dcop_yaml(build_instance(10, 200 + i))
                    for i in range(OVERLOAD_BURST)]

        def client(i):
            statuses[i] = post(url, {
                "dcop": payloads[i],
                "params": {"max_cycles": 40},
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(OVERLOAD_BURST)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        check(all(s is not None for s in statuses),
              "no overload request hung (all POSTs returned)")
        accepted = [s for s in statuses if s[0] == 202]
        rejected = [s for s in statuses if s[0] == 429]
        check(not [s for s in statuses if s[0] not in (202, 429)],
              "overload responses are only 202 or 429")
        check(len(rejected) >= 1,
              f"queue past high-water yielded 429s "
              f"({len(rejected)}/{OVERLOAD_BURST})")
        # Every accepted request must finish — none dropped.
        deadline = time.monotonic() + 60
        for _, body in accepted:
            rid = body["id"]
            while time.monotonic() < deadline:
                result = handle.service.result(rid, wait=1.0)
                if result is not None:
                    break
            check(result is not None
                  and result["status"] == "FINISHED",
                  f"accepted request {rid} completed")
        after = scrape_requests_total(url)
        delta_ok = after.get("ok", 0) - before.get("ok", 0)
        delta_rej = (after.get("rejected_queue_full", 0)
                     - before.get("rejected_queue_full", 0))
        check(delta_ok == len(accepted)
              and delta_rej == len(rejected)
              and delta_ok + delta_rej == OVERLOAD_BURST,
              "pydcop_requests_total accounts for every request "
              f"(ok {delta_ok:.0f} + 429 {delta_rej:.0f} = "
              f"{OVERLOAD_BURST})")
    finally:
        handle.stop()


FLEET_BURST = 10


def leg_fleet_burst():
    """ISSUE 15 acceptance: a concurrent mixed-structure burst
    against a REAL 2-replica fleet (worker subprocesses behind the
    structure-affinity router) must answer every request
    bit-identical to solo ``api.solve`` — the fleet is wire-invisible
    — with both replicas carrying traffic, affinity accounting on
    /stats, and a clean whole-fleet drain (every worker exit 0)."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    handle = api.serve(port=0, replicas=2, batch_window_s=0.1,
                       max_batch=8, heartbeat_s=0.2)
    try:
        url = handle.url
        dcops = ([build_instance(10, 600 + s) for s in range(5)]
                 + [build_instance(14, 650 + s) for s in range(5)])
        payloads = [dcop_yaml(d) for d in dcops]
        results = [None] * len(dcops)

        def client(i):
            results[i] = post(url, {
                "dcop": payloads[i], "wait": True, "timeout": 120,
                "params": {"max_cycles": MAX_CYCLES},
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(dcops))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        check(all(r is not None and r[0] == 200
                  and r[1]["status"] == "FINISHED" for r in results),
              f"all {len(dcops)} fleet-burst responses finished")
        for dcop, (_, res) in zip(dcops, results):
            solo = api_solve_cached(dcop, res)
            if res["assignment"] != solo["assignment"] \
                    or res["cost"] != solo["cost"]:
                check(False,
                      f"fleet answer for {dcop.name} differs from "
                      "solo api.solve")
        check(True, f"all {len(dcops)} fleet answers bit-identical "
              "to solo api.solve")
        with urllib.request.urlopen(url + "/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        check(stats["up"] == 2, "both replicas up through the burst")
        loads = [w["forwarded"] for w in stats["workers"]]
        check(all(n > 0 for n in loads),
              f"both replicas carried traffic ({loads})")
        check(stats["affinity_hit_fraction"] is not None
              and stats["affinity_hit_fraction"] > 0,
              "affinity accounting on /stats (hit fraction "
              f"{stats['affinity_hit_fraction']})")
    finally:
        summary = handle.stop()
    check([w["exit"] for w in summary["workers"]] == [0, 0],
          "fleet drain: every worker exited 0 "
          f"({summary['workers']})")


def leg_elastic_fleet():
    """ISSUE 16 acceptance (smoke slice): on a real 2-replica fleet,
    an operator ``POST /admin/migrate`` moves a warm session between
    replicas with zero acked events lost — PATCHes before and after
    the move all land, the router pin follows the session, the final
    close answers from the new owner — and the elastic control
    surfaces (fairness ledger, migrations counter, per-host worker
    identity) are all live on /stats."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    handle = api.serve(port=0, replicas=2, hosts=2,
                       batch_window_s=0.1, max_batch=8,
                       heartbeat_s=0.2)
    try:
        url = handle.url
        base = build_path_instance(10, 1606)
        rng = np.random.default_rng(1606)
        params = {"noise": 0.01, "stability": 0.001,
                  "max_cycles": 500}
        req = urllib.request.Request(
            url + "/session",
            data=json.dumps({"dcop": dcop_yaml(base),
                             "params": params}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            ack = json.loads(resp.read())
            check(resp.status == 201 and ack.get("session_id"),
                  "fleet session opened (201 + id)")
        sid = ack["session_id"]

        def patch(batch):
            deadline = time.monotonic() + 90
            while True:
                req = urllib.request.Request(
                    url + f"/session/{sid}/events",
                    data=json.dumps({"events": batch,
                                     "wait": True}).encode(),
                    method="PATCH",
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req,
                                                timeout=60) as resp:
                        return json.loads(resp.read())
                except urllib.error.HTTPError as err:
                    check(err.code in (409, 503)
                          and time.monotonic() < deadline,
                          f"PATCH retryable during migration "
                          f"(got {err.code})")
                    time.sleep(0.2)

        batch = [{"type": "change_factor", "name": "c3",
                  "table": rng.integers(0, 10, size=(3, 3))
                  .astype(float).tolist()}]
        patch(batch)
        source = handle.router.pinned(
            sid, handle.router._session_pins)
        req = urllib.request.Request(
            url + "/admin/migrate",
            data=json.dumps({"session_id": sid}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            moved = json.loads(resp.read())
            check(resp.status == 200
                  and moved["from"] == source.index,
                  f"operator migrate moved the session "
                  f"({moved['from']} -> {moved['to']})")
        target = handle.router.pinned(
            sid, handle.router._session_pins)
        check(target.index != source.index,
              "router pin repointed to the new owner")
        out = patch(batch)
        check(out["seq"] == 2,
              "post-migration PATCH acked on the new owner "
              f"(seq {out['seq']})")
        with urllib.request.urlopen(url + f"/session/{sid}",
                                    timeout=30) as resp:
            st = json.loads(resp.read())
        check(st["applied_seq"] == 2 or st["seq"] == 2,
              f"zero acked events lost across the move ({st['seq']}"
              f"/{st['applied_seq']})")
        req = urllib.request.Request(url + f"/session/{sid}",
                                     method="DELETE")
        with urllib.request.urlopen(req, timeout=120) as resp:
            final = json.loads(resp.read())
        check(resp.status == 200 and final["status"] == "CLOSED",
              "migrated session closes cleanly on the new owner")
        with urllib.request.urlopen(url + "/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        check(stats["migrations"] == 1,
              f"migrations counter on /stats ({stats['migrations']})")
        check(stats["fairness"]["admitted"] >= 0
              and "active" in stats["fairness"],
              "weighted-fair admission ledger on /stats")
        hosts = {w["host_id"] for w in stats["workers"]}
        check(hosts == {"host0", "host1"},
              f"replicas striped over simulated hosts ({hosts})")
    finally:
        summary = handle.stop()
    check([w["exit"] for w in summary["workers"]] == [0, 0],
          "elastic fleet drain: every worker exited 0 "
          f"({summary['workers']})")


def build_wide_clique(n_vars: int = 12, d: int = 10):
    """Pairwise clique over a 10-value domain: induced width
    ``n_vars - 1`` puts the root UTIL hypercube at ``d**n_vars``
    cells — astronomically past the element cap, so the exact tier
    must refuse it cleanly."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(17)
    dom = Domain("d", "", list(range(d)))
    dcop = DCOP("smoke_wide", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    k = 0
    for i in range(n_vars):
        for j in range(i + 1, n_vars):
            dcop.add_constraint(NAryMatrixRelation(
                [vs[i], vs[j]], rng.random((d, d)), f"c{k}"))
            k += 1
    dcop.add_agents([AgentDef("a0")])
    return dcop


def leg_dpop_exact():
    """ISSUE 17 acceptance: the exact tier on the wire.  A
    ``params.algo="dpop"`` request answers ``optimal: true`` with
    the solo exact assignment; an over-width problem gets a
    structured 400 (``rejected_width``) — never a 500 — and the
    service still serves iterative traffic afterwards."""
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    handle = api.serve(port=0, batch_window_s=0.05, max_batch=8,
                       max_queue=64)
    try:
        url = handle.url
        dcop = build_path_instance(14, 1701)
        status, res = post(url, {
            "dcop": dcop_yaml(dcop), "wait": True, "timeout": 120,
            "params": {"algo": "dpop"},
        })
        check(status == 200 and res["status"] == "FINISHED",
              f"dpop request finished over HTTP (status {status})")
        check(res.get("optimal") is True,
              "exact-tier response carries optimal: true")
        solo = api.solve(dcop, "dpop", backend="device")
        check(res["assignment"] == solo["assignment"]
              and res["cost"] == solo["cost"],
              "served exact answer identical to solo api.solve "
              f"(cost {res['cost']})")

        status, body = post(url, {
            "dcop": dcop_yaml(build_wide_clique()), "wait": True,
            "timeout": 120, "params": {"algo": "dpop"},
        })
        check(status == 400,
              f"over-width exact request answers 400 (got {status})")
        check(body.get("status") == "rejected_width"
              and body.get("max_elements", 0)
              > body.get("max_elements_cap", 0)
              and body.get("retry") is False,
              "400 body is structured: rejected_width + element "
              f"count {body.get('max_elements')} > cap "
              f"{body.get('max_elements_cap')}, retry false")

        # The refusal must not poison the service for everyone else.
        status, res = post(url, {
            "dcop": dcop_yaml(build_instance(9, 1702)), "wait": True,
            "timeout": 120, "params": {"max_cycles": MAX_CYCLES},
        })
        check(status == 200 and res["status"] == "FINISHED",
              "iterative traffic still served after the width "
              "refusal")
        stats = handle.service.stats()
        check(stats["dpop_dispatches"] >= 1,
              "exact dispatches accounted on /stats "
              f"({stats['dpop_dispatches']})")
    finally:
        handle.stop()


KILL9_BURST = 10


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_serve(port: int, journal_dir: str, *extra) -> subprocess.Popen:
    """A REAL ``pydcop serve`` process (the kill target must be a
    process, not a thread)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "pydcop_tpu.dcop_cli", "serve",
         "--port", str(port), "--journal_dir", journal_dir,
         "--batch_window", "0.3", "--max_batch", "4",
         "--cycles", "200", *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def _wait_listening(proc, url: str, timeout: float = 90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            _, err = proc.communicate()
            check(False, "serve subprocess died on startup: "
                  + err.decode(errors="replace")[-800:])
        try:
            with urllib.request.urlopen(url + "/healthz", timeout=2):
                return
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(0.25)
    check(False, f"serve subprocess never listened on {url}")


def leg_kill9_replay():
    """SIGKILL a serving process mid-burst; prove the 202 was a
    durable promise: every acked request's accepted record is on
    disk, and --recover replays every unfinished one to completion."""
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving.journal import (
        pending_requests,
        scan_journal,
    )
    from pydcop_tpu.serving.service import SolveService

    journal_dir = tempfile.mkdtemp(prefix="serve_kill9_")
    port = _free_port()
    proc = _spawn_serve(port, journal_dir)
    url = f"http://127.0.0.1:{port}"
    try:
        _wait_listening(proc, url)
        dcops = {}
        acked = []
        for i in range(KILL9_BURST):
            dcop = build_instance(11, 400 + i)
            status, body = post(url, {
                "dcop": dcop_yaml(dcop),
                "params": {"max_cycles": MAX_CYCLES},
            })
            check(status == 202,
                  f"burst request {i} acked (got {status})")
            acked.append(body["id"])
            dcops[body["id"]] = dcop
        # Mid-burst: the batch window is still open, nothing has
        # finished.  No drain, no flush, no mercy.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    records, _, _ = scan_journal(
        os.path.join(journal_dir, "requests.jnl"))
    on_disk = {r["id"] for r in records if r["kind"] == "accepted"}
    check(set(acked) <= on_disk,
          f"all {len(acked)} acked requests journaled before the 202 "
          f"(SIGKILL lost {len(set(acked) - on_disk)})")
    pending = {r["id"] for r in pending_requests(records)}
    finished_before_kill = set(acked) - pending

    # --recover: the same path `pydcop serve --journal_dir D
    # --recover` takes on restart.
    svc = SolveService(journal_dir=journal_dir, recover=True,
                       batch_window_s=0.05, max_batch=4)
    svc.start()
    try:
        check(svc.replayed == len(pending),
              f"recovery replayed exactly the {len(pending)} "
              f"unfinished request(s) ({svc.replayed} replayed, "
              f"{len(finished_before_kill)} completed pre-kill)")
        for rid in sorted(pending):
            result = svc.result(rid, wait=120.0)
            check(result is not None
                  and result["status"] == "FINISHED",
                  f"replayed request {rid} completed after kill -9")
        # Parity: a replayed request's answer equals the solo solve.
        probe = sorted(pending)[0] if pending else None
        if probe is not None:
            replayed = svc.result(probe)
            solo = api_solve_cached(dcops[probe], replayed)
            check(replayed["assignment"] == solo["assignment"],
                  "replayed result identical to solo api.solve")
    finally:
        svc.stop(drain=False)
    check(True, f"kill -9 mid-burst lost zero of {len(acked)} "
          "acknowledged requests")


def build_path_instance(n_vars: int, seed: int):
    """Path (tree) coloring: max-sum is exact here, so the recovered
    session's final cost must EQUAL the uninterrupted replay's."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    dom = Domain("colors", "", [0, 1, 2])
    dcop = DCOP(f"smoke_path_{n_vars}_{seed}", objective="min")
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(n_vars - 1):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(
            NAryMatrixRelation([vs[k], vs[k + 1]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


SESSION_PARAMS = {"noise": 0.01, "stability": 0.001,
                  "max_cycles": 600, "segment_cycles": 100}


def leg_session_replay():
    """ISSUE-13 acceptance: SIGKILL a real serve subprocess
    mid-SESSION.  A stateful session is opened over HTTP, 3 event
    batches are acked (200s), the process dies with no drain; every
    acked record must be on disk, and a --recover start must resume
    the session, apply the journaled-but-unapplied batches, and
    close with EXACTLY the final cost an uninterrupted replay of the
    same event stream produces — zero acked events lost."""
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.engine.dynamic import build_dynamic_engine
    from pydcop_tpu.serving.journal import scan_journal
    from pydcop_tpu.serving.service import SolveService
    from pydcop_tpu.serving.sessions import apply_event_batch

    rng = np.random.default_rng(1306)
    base = build_path_instance(10, 1306)
    batches = [
        [{"type": "change_factor", "name": f"c{i}",
          "table": rng.integers(0, 10, size=(3, 3))
          .astype(float).tolist()}]
        for i in range(3)
    ]
    # The uninterrupted reference: the same open + event stream
    # through a local engine (deterministic on CPU).
    ref = build_dynamic_engine(base, SESSION_PARAMS)
    ref.run(max_cycles=SESSION_PARAMS["max_cycles"])
    for batch in batches:
        _applied, _touched, error = apply_event_batch(ref, batch)
        check(error is None, f"reference batch applied ({error})")
        ref.run(max_cycles=SESSION_PARAMS["max_cycles"])
    expected = ref.cost(
        ref.run(max_cycles=SESSION_PARAMS["max_cycles"]).assignment)

    journal_dir = tempfile.mkdtemp(prefix="serve_session_")
    port = _free_port()
    proc = _spawn_serve(port, journal_dir)
    url = f"http://127.0.0.1:{port}"
    try:
        _wait_listening(proc, url)
        req = urllib.request.Request(
            url + "/session",
            data=json.dumps({"dcop": dcop_yaml(base),
                             "params": SESSION_PARAMS}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            ack = json.loads(resp.read())
            check(resp.status == 201 and ack.get("session_id"),
                  "session opened over HTTP (201 + id)")
        sid = ack["session_id"]
        for i, batch in enumerate(batches):
            req = urllib.request.Request(
                url + f"/session/{sid}/events",
                data=json.dumps({"events": batch}).encode(),
                method="PATCH",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                body = json.loads(resp.read())
                check(resp.status == 200
                      and body["seq"] == i + 1,
                      f"event batch {i + 1} acked (durable 200)")
        # No drain, no close: the acks are the only promise left.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    records, _, _ = scan_journal(
        os.path.join(journal_dir, "requests.jnl"))
    kinds = [r["kind"] for r in records if r.get("id") == sid]
    check(kinds.count("session_open") == 1
          and kinds.count("session_event") == 3,
          "all acked session records on disk after SIGKILL "
          f"(found {kinds})")

    svc = SolveService(journal_dir=journal_dir, recover=True,
                       batch_window_s=0.05, max_batch=4)
    svc.start()
    try:
        status = svc.sessions.status(sid)
        check(status["seq"] == 3 and status["applied_seq"] == 3,
              "--recover resumed the session with ALL 3 acked "
              "event batches applied (zero lost)")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status = svc.sessions.status(sid)
            if status["last"] and status["last"].get("converged"):
                break
            time.sleep(0.1)
        final = svc.sessions.close(sid)
        check(final["status"] == "CLOSED"
              and final["cost"] == expected,
              "recovered session's final result equals the "
              f"uninterrupted run ({final['cost']} == {expected})")
    finally:
        svc.stop(drain=False)


def leg_sigterm_drain():
    """SIGTERM (the orchestrated-restart signal): the process drains
    accepted work and exits 0, logging the drained count."""
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.serving.journal import (
        pending_requests,
        scan_journal,
    )

    journal_dir = tempfile.mkdtemp(prefix="serve_sigterm_")
    port = _free_port()
    proc = _spawn_serve(port, journal_dir)
    url = f"http://127.0.0.1:{port}"
    acked = []
    try:
        _wait_listening(proc, url)
        for i in range(4):
            status, body = post(url, {
                "dcop": dcop_yaml(build_instance(9, 500 + i)),
                "params": {"max_cycles": 40},
            })
            check(status == 202, f"drain request {i} acked")
            acked.append(body["id"])
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            check(False, "SIGTERM'd serve process failed to exit")
        _, err = proc.communicate()
        stderr = err.decode(errors="replace")
        check(proc.returncode == 0,
              f"SIGTERM exits 0 (got {proc.returncode}): "
              f"{stderr[-400:]}")
        check("drained" in stderr and "replayable" in stderr,
              "shutdown banner logs the drained/replayable counts")
    finally:
        if proc.poll() is None:
            proc.kill()
    # Zero silently dropped: every acked id either completed inside
    # the drain window (journaled terminal) or is still replayable.
    records, _, _ = scan_journal(
        os.path.join(journal_dir, "requests.jnl"))
    on_disk = {r["id"] for r in records if r["kind"] == "accepted"}
    pending = {r["id"] for r in pending_requests(records)}
    terminal = on_disk - pending
    check(set(acked) <= (terminal | pending),
          f"every accepted request drained ({len(terminal)}) or "
          f"left replayable ({len(pending)}) — zero dropped")


TRACE_BURST = 5


def leg_request_tracing():
    """ISSUE 9 acceptance: per-request causality over real HTTP.

    A traced batched burst must leave every request reconstructable:
    ``pydcop trace query --request ID`` (the real CLI, against the
    exported trace file) returns ONE well-nested tree whose spans
    cover submit → queue → serve_dispatch → engine_segment, all
    tagged with that request's trace_id; and the latency histogram's
    p99 bucket carries an exemplar trace_id the SAME query resolves."""
    import re as _re

    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.observability.trace import tracer

    trace_path = os.path.join(
        tempfile.mkdtemp(prefix="serve_trace_"), "serve.jsonl")
    tracer.enable()
    handle = api.serve(port=0, batch_window_s=0.3, max_batch=8,
                       max_queue=32)
    try:
        url = handle.url
        payloads = [dcop_yaml(build_instance(10, 900 + i))
                    for i in range(TRACE_BURST)]
        results = [None] * TRACE_BURST

        def client(i):
            results[i] = post(url, {
                "dcop": payloads[i], "wait": True, "timeout": 120,
                "params": {"max_cycles": MAX_CYCLES},
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(TRACE_BURST)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        check(all(r is not None and r[0] == 200
                  and r[1]["status"] == "FINISHED" for r in results),
              f"traced burst of {TRACE_BURST} completed")
        trace_ids = [r[1].get("trace_id") for r in results]
        check(all(trace_ids) and len(set(trace_ids)) == TRACE_BURST,
              "every response carries a distinct trace_id")
        stats = handle.service.stats()
        check(stats["batched_dispatches"] >= 1,
              "traced burst was genuinely batched "
              f"({stats['batched_dispatches']} multi-instance "
              "dispatch(es))")

        # p99 exemplar: on the exposition AND resolvable below.
        # Exemplars are OpenMetrics-only syntax — negotiate the
        # dialect the way a real Prometheus with exemplar storage
        # does; the classic text format must stay exemplar-free.
        om_req = urllib.request.Request(
            url + "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        with urllib.request.urlopen(om_req, timeout=30) as resp:
            check("openmetrics-text" in resp.headers["Content-Type"],
                  "negotiated scrape answers as OpenMetrics")
            exposition = resp.read().decode()
        check(exposition.rstrip().endswith("# EOF"),
              "OpenMetrics exposition carries the # EOF terminator")
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=30) as resp:
            classic = resp.read().decode()
        check(" # {" not in classic,
              "classic text-format scrape stays exemplar-free "
              "(v0.0.4 parsers reject exemplar suffixes)")
        ex = _re.search(
            r'pydcop_request_latency_seconds_bucket\{[^}]*\}'
            r' \S+ # \{trace_id="([0-9a-f]+)"\}', exposition)
        check(ex is not None,
              "latency histogram exposes an OpenMetrics exemplar")
        with urllib.request.urlopen(url + "/stats",
                                    timeout=30) as resp:
            svc_stats = json.loads(resp.read())
        p99 = (svc_stats.get("latency_exemplars") or {}).get("p99")
        check(p99 is not None and p99["trace_id"] in trace_ids,
              "p99 latency exemplar names a burst trace_id "
              f"({p99 and p99['trace_id']})")
    finally:
        handle.stop()
        tracer.export_jsonl(trace_path)
        tracer.disable()

    def query(trace_id: str) -> dict:
        proc = subprocess.run(
            [sys.executable, "-m", "pydcop_tpu.dcop_cli", "trace",
             "query", "--request", trace_id, "--json", trace_path],
            capture_output=True, timeout=120)
        check(proc.returncode == 0,
              f"pydcop trace query --request {trace_id} exits 0")
        return json.loads(proc.stdout)

    tree = query(trace_ids[0])
    check(tree["well_nested"],
          "queried request tree is well-nested")
    names = set(tree["names"])
    for needed in ("serve_submit", "serve_queued", "serve_dispatch",
                   "engine_segment"):
        check(needed in names,
              f"request tree contains a {needed} span "
              f"(names: {sorted(names)})")

    def _flat(nodes):
        for node in nodes:
            yield node
            yield from _flat(node["children"])

    for node in _flat(tree["tree"]):
        args = node["args"]
        tagged = (args.get("trace_id") == trace_ids[0]
                  or trace_ids[0] in (args.get("trace_ids") or []))
        check(tagged, f"{node['name']} span tagged with the "
              "request's trace_id")
    # The p99 exemplar is one hop from its trace: the SAME query
    # resolves the trace_id the histogram exposed.
    ex_tree = query(p99["trace_id"])
    check(ex_tree["events"] > 0 and ex_tree["well_nested"]
          and "engine_segment" in ex_tree["names"],
          "p99 exemplar trace_id resolves to a full request tree "
          f"({ex_tree['events']} events)")


def main() -> int:
    t0 = time.perf_counter()
    # The tracing leg runs FIRST: the latency histogram is process-
    # global, so its p99-exemplar assertion needs the burst to be the
    # only traffic observed so far.  The other in-process legs only
    # read per-service stats or scrape deltas — order-independent.
    leg_request_tracing()
    leg_coalescing()
    leg_mixed_envelope()
    leg_efficiency()
    leg_pipelined_speculation()
    leg_overload()
    leg_dpop_exact()
    leg_fleet_burst()
    leg_elastic_fleet()
    leg_kill9_replay()
    leg_session_replay()
    leg_sigterm_drain()
    print(f"serve_smoke: PASS ({time.perf_counter() - t0:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
