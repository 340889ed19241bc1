"""Kind ``serve``: ``api.serve`` in this process (the chip's one
process), closed-loop callers posting a pool of small problems to
``POST /solve`` as YAML with ``wait: true``.

Configuration keys: ``generator``, ``pool`` (problems of one
structure, seeds ``1000 * seed + i``), ``params`` (sent with every
request), ``service`` (the arguments of ``api.serve``),
``cost_tolerance``.  Traffic keys: ``callers``, ``warmup_bursts``,
``warmup_rounds``, ``traced_seconds``.
"""

import itertools
import math
import statistics
import threading
import time

from chipbench import lib, reference

REQUEST_TIMEOUT_S = 600
JOIN_TIMEOUT_S = 900
# The service compiles ahead in the background, for batch sizes it has
# not seen yet (serving/speculate.py).  Set-up ends only when that has
# been quiet for this long: longer than loading one program from the
# disk cache, so that a run after the first finds nothing warming up
# inside its window.
SETTLE_S = 2.0
SETTLE_LIMIT_S = 300


class Callers:
    """Closed-loop callers over one pool: each posts its next request
    when its last was answered, and all walk the pool round-robin
    through one shared counter, so no two requests in flight are the
    same problem."""

    def __init__(self, url, payloads, params):
        self.url = url
        self.payloads = payloads
        self.params = params
        self._next = itertools.count()
        self._lock = threading.Lock()

    def post(self, records):
        with self._lock:
            index = next(self._next) % len(self.payloads)
        t0 = time.perf_counter()
        answer = lib.post_solve(self.url, {
            "dcop": self.payloads[index], "wait": True,
            "timeout": REQUEST_TIMEOUT_S, "params": self.params})
        done = time.perf_counter()
        records.append({"index": index, "latency_s": done - t0,
                        "done": done, "answer": answer})

    def run(self, callers, until=None, each=None):
        """``callers`` threads post until the clock passes ``until``
        (or ``each`` requests per thread); returns the records of
        every request, in the order they were answered."""
        records, errors = [], []

        def loop():
            try:
                for _ in (itertools.count() if each is None
                          else range(each)):
                    if until is not None and time.perf_counter() >= until:
                        break
                    self.post(records)
            except Exception as exc:  # noqa: BLE001 - re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=loop, name=f"caller-{i}")
                   for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=JOIN_TIMEOUT_S)
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads):
            raise lib.BenchFailure("a caller did not finish")
        return records


def settle(url, counters):
    """Wait until the service's speculative compiler has an empty
    queue and neither it nor the compile cache has moved for
    ``SETTLE_S``."""
    start = quiet_since = time.perf_counter()
    last = None
    while time.perf_counter() - quiet_since < SETTLE_S:
        if time.perf_counter() - start > SETTLE_LIMIT_S:
            raise lib.BenchFailure("the service never stopped compiling")
        speculation = lib.get_json(url + "/stats")["speculation"]
        now = (speculation, counters()["hits"], counters()["misses"])
        if now != last or speculation["queued"]:
            last, quiet_since = now, time.perf_counter()
        time.sleep(0.2)
    return time.perf_counter() - start


def faults_of(records, dcops, reference_costs, tolerance):
    """``(faults, compared)``: why answers are wrong (not FINISHED,
    wrong on the host, worse than the reference allows, or a repeated
    problem answered with another assignment), and the numbers
    compared of the worst answer, beside the counts of requests not
    FINISHED and of repeats that differed."""
    faults, checked, first = [], [], {}
    unfinished = differing = 0
    for n, record in enumerate(records):
        answer, index = record["answer"], record["index"]
        if answer.get("status") != "FINISHED":
            unfinished += 1
            fault = f"status {answer.get('status')}"
        else:
            fault, compared = lib.check_answer(
                dcops[index], answer, reference_costs[index], tolerance)
            if (fault is None and answer["assignment"]
                    != first.setdefault(index, answer["assignment"])):
                differing += 1
                fault = "a repeated problem got another assignment"
            checked.append((fault, compared))
        if fault:
            faults.append(f"request {n} (problem {index}): {fault}")
    compared = dict(lib.worst(checked)) if checked else {}
    compared["not_finished"] = [unfinished, 0]
    compared["repeats_differing"] = [differing, 0]
    return faults, compared


def run(cell):
    from pydcop_tpu import api
    from pydcop_tpu.dcop.yamldcop import dcop_yaml
    from pydcop_tpu.engine import aotcache

    config, traffic = cell.config, cell.traffic
    n_callers = traffic["callers"]

    # ---- set-up ------------------------------------------------------
    t0 = time.perf_counter()
    dcops = [lib.generate(config["generator"], 1000 * cell.seed + i)
             for i in range(config["pool"])]
    payloads = [dcop_yaml(d) for d in dcops]
    reference_costs = [
        reference.solve(d, config["params"]["max_cycles"], cell.seed)[1]
        for d in dcops]
    pool_s = time.perf_counter() - t0
    handle = api.serve(port=0, **config["service"])
    try:
        callers = Callers(handle.url, payloads, config["params"])
        t0 = time.perf_counter()
        warm = []
        for _ in range(traffic["warmup_rounds"]):
            for burst in traffic["warmup_bursts"]:
                warm += callers.run(burst, each=1)
        warmup_s = time.perf_counter() - t0
        lib.note(setup={"pool_s": pool_s, "warmup_s": warmup_s,
                        "settle_s": settle(handle.url, aotcache.counters),
                        "warmup_requests": len(warm),
                        "payload_bytes": len(payloads[0])})

        # ---- the window ----------------------------------------------
        counters_before = aotcache.counters()
        start = cell.start_window()
        records = callers.run(n_callers, until=start + cell.seconds)
        elapsed = max(r["done"] for r in records) - start
        latencies = [r["latency_s"] for r in records]
        finished = sum(r["answer"].get("status") == "FINISHED"
                       for r in records)
        end_to_end = {
            "serve_problems_per_s": finished / elapsed,
            "serve_p95_ms": 1e3 * lib.percentile(latencies, 0.95),
        }
        counters = aotcache.counters()
        lib.note(window={
            "requests": len(records), "finished": finished,
            "cache_hits": counters["hits"] - counters_before["hits"],
            "cache_misses": counters["misses"] - counters_before["misses"],
            "elapsed_s": elapsed,
            "p50_ms": 1e3 * statistics.median(latencies),
            "p95_ms": end_to_end["serve_p95_ms"],
            "max_ms": 1e3 * max(latencies),
            "samples_beyond_p95": len(latencies) - math.ceil(
                0.95 * len(latencies))})

        # ---- the traced block ----------------------------------------
        capture = None
        if cell.trace:
            capture = {"counters_before": counters_before,
                       "values": dict(end_to_end),
                       "stats_before": lib.get_json(handle.url + "/stats")}
            with lib.traced_block(cell, capture):
                traced = callers.run(
                    n_callers,
                    until=time.perf_counter() + traffic["traced_seconds"])
            capture["stats_after"] = lib.get_json(handle.url + "/stats")
            capture["counters_after"] = aotcache.counters()
            capture["values"]["latency_mean_ms"] = 1e3 * statistics.fmean(
                r["latency_s"] for r in traced)
            records += traced
        stats = lib.get_json(handle.url + "/stats")
    finally:
        handle.stop()
    # A thread left calling into JAX races the interpreter's teardown
    # (PR 22: a segfault after the last line was printed).
    leftover = sorted(t.name for t in threading.enumerate()
                      if t.name.startswith("pydcop-"))
    if leftover:
        raise lib.BenchFailure(
            f"the stopped service left threads running: {leftover}")
    if stats["efficiency"]["backend"] != cell.device["platform"]:
        raise lib.BenchFailure(
            f"/stats labels the backend {stats['efficiency']['backend']!r}")

    # ---- the checks, after the window --------------------------------
    faults, compared = faults_of(warm + records, dcops, reference_costs,
                                 config["cost_tolerance"])
    for fault in faults[:5]:
        lib.note(fault=fault)
    lib.note(service={key: stats[key] for key in (
        "completed", "failed", "expired", "deduped", "dispatches",
        "batched_dispatches")})
    if capture is not None:
        capture["values"]["cost_ratio"] = statistics.fmean(
            r["answer"]["cost"] / reference_costs[r["index"]]
            for r in records if r["answer"].get("status") == "FINISHED")
    return {"correct": not faults, "attempted": len(warm) + len(records),
            "failed": len(faults), "end_to_end": end_to_end,
            "capture": capture, "compared": compared}
