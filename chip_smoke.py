"""The quickest proof that the system still starts on the chip.

Drives the main path once, on a TPU, through the entry points a user
would call — ``pydcop generate``, ``pydcop solve`` / ``api.solve``,
``api.serve`` answering HTTP — and checks every answer on the host.
It measures nothing it would claim: the times it prints are what one
cold run cost, kept for the record (PERF.md), not a benchmark.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the four-chip host: only the
                                       # mesh paths and their control

One process throughout: a chip belongs to one process at a time.

Standard output is one JSON object per line: one per phase (wall
time, compile time, cycles, cost, compile-cache hits and misses),
where the compile cache is, the Pallas kernel checked against the jnp
expression, ``engine.timing.sync`` beside ``jax.block_until_ready`` on
one dispatched program — and, last, only when every phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure — no TPU, a phase that raises, a cost that does not match
the host's — exits non-zero, names the phase on standard error and
prints no such line.
"""

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

import numpy as np

# Random 3-colouring from ``pydcop generate graph_coloring``.  10k
# variables at 1.5 edges per variable is the north-star instance
# (BASELINE.json; loopy — MaxSum runs its whole budget).  The 100k
# instance has 1.0 edges per variable: sparse enough that MaxSum
# quiesces, which is the regime where the sharded runs must reproduce
# the single-device run exactly (__graft_entry__.dryrun_multichip).
SOLVE_VARS = 10_000
SOLVE_P_EDGE = 3e-4
SOLVE_CYCLES = 200
SEGMENT_CYCLES = 50
DSA_CYCLES = 100
BIG_VARS = 100_000
BIG_P_EDGE = 2e-5
BIG_CYCLES = 300
COLORS = 3
# Serve traffic: one structure sent with different costs (a grid's
# topology does not depend on the seed, its soft tables do), the same
# requests again, and two structures seen once.
SERVE_GRID_VARS = 400
SERVE_SAME_STRUCTURE = 4
SERVE_OTHER = (("grid", 900), ("random", 300))
SERVE_CYCLES = 100
SYNC_REPS = 5
# What every phase must have run on.  (The CPU rehearsal of these
# phases, tests/cli/test_cli_misc.py, patches it from the test.)
PLATFORM = "tpu"

_phase = "start"


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class Phase:
    """Names the phase (for the failure message) and reports its wall
    time and compile-cache traffic when it ends."""

    def __init__(self, name: str):
        self.name = name
        self.fields = {}

    def __enter__(self):
        from pydcop_tpu.engine import aotcache

        global _phase
        _phase = self.name
        self._cache = aotcache.counters()
        self._t0 = time.perf_counter()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        from pydcop_tpu.engine import aotcache

        after = aotcache.counters()
        emit({
            "phase": self.name,
            "wall_s": round(time.perf_counter() - self._t0, 3),
            "cache_hits": after["hits"] - self._cache["hits"],
            "cache_misses": after["misses"] - self._cache["misses"],
            **self.fields,
        })
        return False


def pydcop(*args: str) -> None:
    """Run the ``pydcop`` CLI in this process (the chip's one
    process).  Its standard output is swallowed — ``pydcop solve``
    prints the whole assignment — so results are read from the file
    given with ``--output``."""
    from pydcop_tpu.dcop_cli import main

    with contextlib.redirect_stdout(io.StringIO()):
        rc = main(list(args))
    check(rc == 0, f"pydcop {' '.join(args)} exited {rc}")


def generate(variables: int, graph: str, seed: int, p_edge=None,
             soft=False):
    """The instance ``pydcop generate graph_coloring`` builds, as a
    DCOP object (the function behind the command: the 100k instance
    skips the YAML round trip, whose pure-Python parse takes
    minutes at that size)."""
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    return generate_graph_coloring(
        variables, COLORS, graph, soft=soft, p_edge=p_edge,
        allow_subgraph=True, noagents=True, seed=seed)


def check_answer(dcop, assignment, cost, violations, what: str):
    """The reported cost and hard-constraint violations against
    ``dcop.solution_cost`` on the host."""
    check(set(assignment) == set(dcop.variables),
          f"{what}: assignment covers "
          f"{len(assignment)}/{len(dcop.variables)} variables")
    host_cost, host_violations = dcop.solution_cost(assignment)
    check(np.isfinite(host_cost), f"{what}: host cost {host_cost}")
    check(float(cost) == float(host_cost),
          f"{what}: reported cost {cost} != host cost {host_cost}")
    check(int(violations) == int(host_violations),
          f"{what}: reported violations {violations} != host "
          f"{host_violations}")


def solve_fields(res, dcop, what: str) -> dict:
    """Check one ``api.solve`` result; the fields its phase line
    keeps.  A probed solve carries the cost the DEVICE computed for
    its final assignment (``metrics['cost_curve']``); DSA/MGM carry
    ``metrics['device_cost']``."""
    check_answer(dcop, res["assignment"], res["cost"],
                 res["violations"], what)
    metrics = res["metrics"]
    device_cost = metrics.get("device_cost")
    if device_cost is None:
        curve = metrics.get("cost_curve")
        check(bool(curve), f"{what}: no device-side cost reported")
        device_cost = curve[-1][1]
    check(float(device_cost) == float(res["cost"]),
          f"{what}: device cost {device_cost} != host cost "
          f"{res['cost']}")
    return {
        "status": res["status"], "cycles": res["cycles"],
        "cost": res["cost"], "violations": res["violations"],
        "device_cost": float(device_cost),
        "solve_s": round(res["time"], 3),
        "compile_s": round(res["compile_time"], 3),
    }


# --------------------------------------------------------------------- #
# phases


def phase_device(four_chips: bool) -> dict:
    with Phase("device") as out:
        import jax

        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(devices)}
        out.update(device)
        check(device["platform"] == PLATFORM,
              f"JAX resolved platform {device['platform']!r}, "
              f"not {PLATFORM!r}")
        want = 4 if four_chips else 1
        check(device["count"] >= want,
              f"{want} chip(s) needed, JAX sees {device['count']}")
    return device


def phase_solve_10k(workdir: str, seed: int) -> None:
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import load_dcop_from_file

    path = os.path.join(workdir, "coloring_10k.yaml")
    with Phase("generate_10k") as out:
        pydcop("--output", path, "generate", "graph_coloring",
               "-v", str(SOLVE_VARS), "-c", str(COLORS), "-g", "random",
               "-p", str(SOLVE_P_EDGE), "--allow_subgraph", "--noagents",
               "--seed", str(seed))
        dcop = load_dcop_from_file([path])
        out.update(variables=len(dcop.variables),
                   constraints=len(dcop.constraints))

    # The CLI: YAML in, the whole solve as ONE program, JSON out.
    with Phase("solve_10k_maxsum_cli") as out:
        result_path = os.path.join(workdir, "result.json")
        pydcop("--output", result_path, "solve", "-a", "maxsum",
               "-c", str(SOLVE_CYCLES), path)
        with open(result_path, encoding="utf-8") as f:
            result = json.load(f)
        check(result["platform"] == PLATFORM,
              f"pydcop solve ran on {result['platform']}")
        check_answer(dcop, result["assignment"], result["cost"],
                     result["violation"], "pydcop solve maxsum")
        out.update(status=result["status"], cycles=result["cycle"],
                   cost=result["cost"], violations=result["violation"],
                   solve_s=round(result["time"], 3),
                   compile_s=round(result["compile_time"], 3))

    # The API, observed: the segment program, with the cost of every
    # chunk computed on the device.  Same supersteps, so the same
    # answer as the one-program solve.
    with Phase("solve_10k_maxsum_segmented") as out:
        res = api.solve(
            dcop, "maxsum", max_cycles=SOLVE_CYCLES,
            metrics_file=os.path.join(workdir, "metrics.jsonl"),
            metrics_every=SEGMENT_CYCLES)
        out.update(solve_fields(res, dcop, "api.solve maxsum"))
        check(res["assignment"] == result["assignment"]
              and res["cycles"] == result["cycle"],
              "segmented solve differs from the one-program solve")

    with Phase("solve_10k_dsa") as out:
        res = api.solve(dcop, "dsa", max_cycles=DSA_CYCLES)
        out.update(solve_fields(res, dcop, "api.solve dsa"))

    phase_pallas_kernel(dcop)
    phase_sync_vs_block(dcop)


def phase_pallas_kernel(dcop) -> None:
    """The compiled (not interpreted) Pallas kernel against the jnp
    expression it would replace, on the 10k instance's bucket."""
    with Phase("pallas_kernel") as out:
        import jax

        from pydcop_tpu.engine.compile import compile_dcop
        from pydcop_tpu.ops import maxsum as maxsum_ops
        from pydcop_tpu.ops.pallas_maxsum import binary_factor_update

        graph, _ = compile_dcop(dcop, noise_level=0.01)
        graph = jax.device_put(graph)
        bucket = graph.buckets[0]
        msgs = jax.device_put(np.random.default_rng(0).normal(
            size=bucket.var_ids.shape + (COLORS,)).astype(np.float32))
        reference = np.asarray(
            maxsum_ops.factor_to_var(graph, (msgs,))[0])
        kernel = np.asarray(binary_factor_update(bucket.costs, msgs))
        np.testing.assert_allclose(kernel, reference, rtol=1e-6,
                                   atol=1e-6)
        out.update(shape=list(bucket.costs.shape),
                   max_abs_diff=float(np.abs(kernel - reference).max()),
                   equal=bool(np.array_equal(kernel, reference)))


def phase_sync_vs_block(dcop) -> None:
    """``engine.timing.sync`` (a host fetch) beside
    ``jax.block_until_ready`` on the same dispatched program: if the
    fetch after a returned ``block_until_ready`` costs only its
    round trip, the plain idiom is a true barrier here (ROADMAP
    Queue 3, "`engine/timing.py`'s `sync`")."""
    with Phase("sync_vs_block_until_ready") as out:
        import jax

        from pydcop_tpu.algorithms.maxsum import build_engine
        from pydcop_tpu.engine.timing import sync

        engine = build_engine(dcop, {})
        fn, graph = engine._fn(SOLVE_CYCLES, True), engine.graph
        sync(fn(graph))
        enqueue, block, fetch_after, sync_only = [], [], [], []
        for _ in range(SYNC_REPS):
            t0 = time.perf_counter()
            result = fn(graph)
            t1 = time.perf_counter()
            jax.block_until_ready(result)
            t2 = time.perf_counter()
            sync(result)
            t3 = time.perf_counter()
            enqueue.append(t1 - t0)
            block.append(t2 - t0)
            fetch_after.append(t3 - t2)
            t0 = time.perf_counter()
            sync(fn(graph))
            sync_only.append(time.perf_counter() - t0)
        out.update(
            program=f"maxsum whole-solve {SOLVE_VARS} vars "
                    f"{SOLVE_CYCLES} cycles",
            reps=SYNC_REPS,
            enqueue_s=float(np.median(enqueue)),
            block_until_ready_s=float(np.median(block)),
            sync_after_block_s=float(np.median(fetch_after)),
            sync_s=float(np.median(sync_only)))


def phase_solve_100k(seed: int) -> None:
    """A working set that does not sit in fast memory."""
    from pydcop_tpu import api

    with Phase("generate_100k") as out:
        dcop = generate(BIG_VARS, "random", seed, p_edge=BIG_P_EDGE)
        out.update(variables=len(dcop.variables),
                   constraints=len(dcop.constraints))
    with Phase("solve_100k_maxsum") as out:
        with tempfile.TemporaryDirectory() as tmp:
            res = api.solve(
                dcop, "maxsum", max_cycles=BIG_CYCLES,
                metrics_file=os.path.join(tmp, "metrics.jsonl"),
                metrics_every=SEGMENT_CYCLES)
        out.update(solve_fields(res, dcop, "api.solve maxsum 100k"))


def post_solve(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        check(resp.status == 200, f"POST /solve answered {resp.status}")
        return json.loads(resp.read())


def get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def burst(url: str, payloads) -> list:
    """Concurrent ``POST /solve`` with ``wait: true``, one client
    thread per request so they land in one batch window."""
    answers = [None] * len(payloads)
    errors = []

    def client(i):
        try:
            answers[i] = post_solve(url, {
                "dcop": payloads[i], "wait": True, "timeout": 600,
                "params": {"max_cycles": SERVE_CYCLES}})
        except Exception as exc:  # noqa: BLE001 — re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if errors:
        raise errors[0]
    check(all(a is not None for a in answers),
          "a serve client did not finish")
    return answers


def phase_serve(seed: int) -> None:
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml

    with Phase("serve") as out:
        same = [generate(SERVE_GRID_VARS, "grid", seed + i, soft=True)
                for i in range(SERVE_SAME_STRUCTURE)]
        other = [
            generate(n, graph, seed,
                     p_edge=3.0 / n if graph == "random" else None,
                     soft=True)
            for graph, n in SERVE_OTHER]
        dcops = same + other
        payloads = [dcop_yaml(d) for d in dcops]
        handle = api.serve(port=0, batch_window_s=0.5, max_batch=16,
                           max_queue=64)
        try:
            first = burst(handle.url, payloads)
            # The same structures again: the compiled programs are
            # warm now.
            again = burst(handle.url, payloads[:SERVE_SAME_STRUCTURE])
            stats = get_json(handle.url + "/stats")
        finally:
            handle.stop()
        # A thread left calling into JAX races the interpreter's
        # teardown (a segfault after the last line was printed).
        leftover = sorted(t.name for t in threading.enumerate()
                          if t.name.startswith("pydcop-"))
        check(not leftover,
              f"the stopped service left threads running: {leftover}")
        for dcop, answer in zip(dcops + same, first + again):
            check(answer["status"] == "FINISHED",
                  f"served {dcop.name}: status {answer['status']}")
            check_answer(dcop, answer["assignment"], answer["cost"],
                         answer["violations"], f"served {dcop.name}")
        for a, b in zip(first, again):
            check(a["assignment"] == b["assignment"],
                  "a repeated request got a different answer")
        # Masked packing must not change an answer: every served
        # assignment equals the solo solve of the same problem.
        for dcop, answer in zip(dcops, first):
            solo = api.solve(dcop, "maxsum", max_cycles=SERVE_CYCLES)
            check(solo["assignment"] == answer["assignment"],
                  f"served {dcop.name} differs from its solo solve")
        backend = stats["efficiency"]["backend"]
        check(backend == PLATFORM,
              f"/stats labels the backend {backend!r}")
        check(stats["batched_dispatches"] >= 1,
              "no serve dispatch held more than one request")
        out.update(
            requests=len(first) + len(again),
            dispatches=stats["dispatches"],
            batched_dispatches=stats["batched_dispatches"],
            largest_batch=max(
                int(a["batch"]["n_real"])
                for a in first + again),
            backend=backend,
            device_kind=stats["efficiency"]["device_kind"],
            costs=[a["cost"] for a in first],
            ledger_components_s=stats["efficiency"][
                "ledger_components_s"])


def placement(array) -> dict:
    """Where a placed array lives: the devices holding a shard, and
    one shard's shape beside the whole array's (equal = replicated,
    a fraction = split)."""
    shards = array.addressable_shards
    return {"devices": sorted({s.device.id for s in shards}),
            "shard_shape": list(shards[0].data.shape),
            "shape": list(array.shape)}


def phase_four_chips(seed: int) -> None:
    """The mesh paths users reach through ``pydcop solve --n_devices``
    (replicated psum) and ``--shards`` (partitioned halo exchange)
    beside the single-device solve, on the 100k instance.  Parity
    rule (__graft_entry__.dryrun_multichip): on a problem sparse
    enough that every edge quiesces, all three converge at the same
    cycle to the same assignment."""
    from pydcop_tpu import api
    from pydcop_tpu.algorithms.maxsum import build_engine

    with Phase("generate_100k") as out:
        dcop = generate(BIG_VARS, "random", seed, p_edge=BIG_P_EDGE)
        out.update(variables=len(dcop.variables),
                   constraints=len(dcop.constraints))
    results = {}
    # The mesh and the partitioned engine are edge-major: the parity
    # rule holds them to the single-device solve of that layout (an
    # unset layout would run the single device lane-major).
    for name, kwargs in (
            ("single", {"algo_params": {"layout": "edge"}}),
            ("n_devices_4", {"n_devices": 4}),
            ("shards_4", {"shards": 4})):
        with Phase(f"solve_100k_maxsum_{name}") as out:
            res = api.solve(dcop, "maxsum", max_cycles=BIG_CYCLES,
                            **kwargs)
            check_answer(dcop, res["assignment"], res["cost"],
                         res["violations"], f"api.solve {name}")
            results[name] = res
            out.update(
                status=res["status"], cycles=res["cycles"],
                cost=res["cost"], violations=res["violations"],
                solve_s=round(res["time"], 3),
                compile_s=round(res["compile_time"], 3),
                **{k: res["metrics"][k] for k in (
                    "edge_cut_fraction",
                    "halo_exchange_elems_per_superstep",
                    "replicated_allreduce_elems_per_superstep")
                   if k in res["metrics"]})
    with Phase("four_chip_parity") as out:
        single = results["single"]
        check(single["status"] == "FINISHED",
              "the single-device solve did not converge: the parity "
              "rule needs a quiescent problem")
        for name in ("n_devices_4", "shards_4"):
            res = results[name]
            check(res["status"] == "FINISHED",
                  f"{name} did not converge")
            check(res["cycles"] == single["cycles"],
                  f"{name} converged at cycle {res['cycles']}, "
                  f"single device at {single['cycles']}")
            check(res["assignment"] == single["assignment"],
                  f"{name} assignment differs from the single-device "
                  "solve")
            check(res["cost"] == single["cost"],
                  f"{name} cost {res['cost']} != {single['cost']}")
        out.update(cycles=single["cycles"], cost=single["cost"],
                   identical_assignments=True)
    # Where the placed arrays live: the placement code the solves
    # above ran (shard_graph / build_partitioned_graph).
    with Phase("four_chip_placement") as out:
        replicated = build_engine(dcop, {}, n_devices=4).graph
        partitioned = build_engine(dcop, {}, shards=4).graph
        placed = {
            "n_devices_4": {
                "bucket_costs": placement(replicated.buckets[0].costs),
                "var_costs": placement(replicated.var_costs)},
            "shards_4": {
                "bucket_costs": placement(
                    partitioned.buckets[0].costs),
                "var_costs": placement(partitioned.var_costs)},
        }
        out.update(placed)
        for name, arrays in placed.items():
            for array, where in arrays.items():
                check(len(where["devices"]) == 4,
                      f"{name} {array} lives on devices "
                      f"{where['devices']}, not on four")
        # Split, not copied: a quarter of the bucket per device.
        for name in placed:
            bucket = placed[name]["bucket_costs"]
            check(int(np.prod(bucket["shard_shape"])) * 4
                  == int(np.prod(bucket["shape"])),
                  f"{name} bucket is not split four ways: {bucket}")


# --------------------------------------------------------------------- #


def run(args) -> dict:
    from pydcop_tpu.engine import aotcache

    # Before the first jit, like every entry point.
    cache_dir = aotcache.enable_persistent_compile_cache()
    emit({"phase": "compile_cache", "dir": cache_dir,
          "source": aotcache.resolve_cache_dir()[1],
          "entries_at_start": aotcache.disk_stats(cache_dir)["entries"]})
    device = phase_device(args.four_chips)
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        with tempfile.TemporaryDirectory() as workdir:
            phase_solve_10k(workdir, args.seed)
        phase_solve_100k(args.seed)
        phase_serve(args.seed)
    emit({"phase": "compile_cache_totals", **aotcache.counters(),
          **aotcache.disk_stats(cache_dir)})
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the four-chip mesh paths and "
                             "the single-device solve they are "
                             "compared with (needs four chips)")
    parser.add_argument("--seed", type=int, default=1,
                        help="seed of every generated instance")
    args = parser.parse_args(argv)
    try:
        device = run(args)
    except Exception as exc:  # noqa: BLE001 — reported, exit non-zero
        traceback.print_exc()
        print(f"chip_smoke: FAILED in phase {_phase!r}: "
              f"{type(exc).__name__}: {exc}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
