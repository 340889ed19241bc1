"""Trace-demo gate: solve a small graph-coloring instance with
``--trace`` + ``--metrics`` through the real CLI and assert the
artifacts validate — the Chrome trace loads as JSON with well-nested
spans and the expected span kinds, the metrics JSONL parses with a
monotone cycle counter, the Prometheus dump is well-formed, and
``pydcop trace summary`` aggregates the file without error.  A live
telemetry leg then starts the HTTP endpoint on port 0, scrapes
``/metrics`` twice MID-RUN around an advancing segmented solve, and
asserts both scrapes parse with a strictly increasing
``pydcop_cycles_total`` (plus ``/healthz`` answering 200).

Run: ``make trace-demo`` (part of ``make test``).  Exit 0 = clean.
"""

import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

DCOP_YAML = """\
name: trace_demo
objective: min
domains:
  colors:
    values: [R, G, B]
variables:
  v0: {domain: colors}
  v1: {domain: colors}
  v2: {domain: colors}
  v3: {domain: colors}
constraints:
  c0:
    type: intention
    function: 10 if v0 == v1 else 0
  c1:
    type: intention
    function: 10 if v1 == v2 else 0
  c2:
    type: intention
    function: 10 if v2 == v3 else 0
  c3:
    type: intention
    function: 10 if v3 == v0 else 0
agents: [a0, a1, a2, a3]
"""

_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.e+-]+|\+Inf)"
    # Optional OpenMetrics exemplar suffix on bucket samples
    # (`# {trace_id="..."} value ts`) — present once anything
    # observed a histogram with an exemplar.
    r"( # \{[^}]*\} -?[0-9.e+-]+( [0-9.]+)?)?$"
)


def fail(message: str) -> int:
    print(f"trace_demo: FAIL: {message}")
    return 1


def main() -> int:
    from pydcop_tpu.dcop_cli import main as cli_main
    from pydcop_tpu.observability.trace import (
        check_well_nested,
        load_trace_file,
    )

    with tempfile.TemporaryDirectory(prefix="trace_demo_") as tmp:
        dcop_file = os.path.join(tmp, "coloring.yaml")
        with open(dcop_file, "w", encoding="utf-8") as f:
            f.write(DCOP_YAML)
        trace_file = os.path.join(tmp, "trace.json")
        metrics_file = os.path.join(tmp, "metrics.jsonl")
        out_file = os.path.join(tmp, "result.json")

        rc = cli_main([
            "--output", out_file,
            "solve", "-a", "maxsum", "-c", "60",
            "--trace", trace_file, "--metrics", metrics_file,
            "--metrics_every", "10", dcop_file,
        ])
        if rc != 0:
            return fail(f"pydcop solve exited {rc}")
        result = json.load(open(out_file, encoding="utf-8"))
        if result.get("violation") != 0:
            return fail(f"demo solve left violations: {result}")

        # 1. Chrome trace: json loads, spans well-nested, the engine
        # span kinds present.
        events = load_trace_file(trace_file)
        if not events:
            return fail("trace file has no events")
        try:
            check_well_nested(events)
        except ValueError as e:
            return fail(f"trace spans not well nested: {e}")
        names = {ev.get("name") for ev in events}
        missing = {"solve", "engine_segment", "chunk"} - names
        if missing:
            return fail(f"trace missing span kinds: {sorted(missing)}")

        # 2. Metrics JSONL: parses, monotone cycle counter.
        rows = [json.loads(line)
                for line in open(metrics_file, encoding="utf-8")]
        if not rows:
            return fail("metrics file has no snapshots")
        cycles = [row["cycle"] for row in rows]
        if cycles != sorted(cycles) or cycles[-1] <= 0:
            return fail(f"cycle counter not monotone: {cycles}")

        # 3. Prometheus dump: HELP/TYPE lines + parsable samples.
        prom = open(f"{metrics_file}.prom", encoding="utf-8").read()
        if "# HELP pydcop_cycles_total" not in prom or \
                "# TYPE pydcop_cycles_total counter" not in prom:
            return fail("prometheus dump missing cycle counter family")
        for line in prom.strip().splitlines():
            if not line.startswith("#") and not _PROM_SAMPLE.match(line):
                return fail(f"unparsable prometheus sample: {line!r}")

        # 4. The summary command aggregates the trace without error —
        # in both human and machine form.
        rc = cli_main(["trace", "summary", trace_file])
        if rc != 0:
            return fail(f"pydcop trace summary exited {rc}")
        rc = cli_main(["trace", "summary", "--json", trace_file])
        if rc != 0:
            return fail(f"pydcop trace summary --json exited {rc}")

        # 5. Live telemetry endpoint, scraped MID-RUN.
        err = check_live_endpoint(dcop_file)
        if err:
            return fail(err)

        # 6. Request-scoped tracing (ISSUE 9): a served burst leaves
        # every request reconstructable by `pydcop trace query`.
        err = check_request_tracing(os.path.join(tmp, "serve.jsonl"))
        if err:
            return fail(err)

    print("trace_demo: OK (trace + metrics + summary + live "
          "endpoint + request query all validate)")
    return 0


def _scrape(url: str) -> str:
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.read().decode("utf-8")


def _parse_prom(text: str, what: str):
    """Validate Prometheus text; return the parsed samples dict or an
    error string."""
    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        if not _PROM_SAMPLE.match(line):
            return None, f"{what}: unparsable sample: {line!r}"
        name_part, value = line.rsplit(" ", 1)
        samples[name_part] = float(value)
    return samples, None


def check_live_endpoint(dcop_file: str):
    """Start the telemetry server on port 0, advance a segmented
    engine solve on a background thread, scrape /metrics twice while
    it runs and assert the cycle counter moved.  Returns an error
    string or None."""
    from pydcop_tpu.dcop.yamldcop import load_dcop_from_file
    from pydcop_tpu.engine.compile import compile_dcop
    from pydcop_tpu.engine.runner import MaxSumEngine
    from pydcop_tpu.observability.engine_probe import EngineProbe
    from pydcop_tpu.observability.metrics import registry
    from pydcop_tpu.observability.server import TelemetryServer

    dcop = load_dcop_from_file([dcop_file])
    graph, meta = compile_dcop(dcop, noise_level=0.01)
    engine = MaxSumEngine(graph, meta)
    probe = EngineProbe(engine)
    server = TelemetryServer(port=0).start()
    url = server.url
    done = threading.Event()

    def run():
        try:
            # Tiny segments keep the host boundary (where the
            # snapshotter fires) hot; no convergence stop so the run
            # outlives both scrapes.  2500 cycles ≈ a second or two:
            # long enough that the scrapes land mid-run, short enough
            # that the success path's drain wait below stays cheap.
            engine.run_checkpointed(
                max_cycles=2_500, segment_cycles=5,
                stop_on_convergence=False, probe=probe)
        finally:
            done.set()

    thread = threading.Thread(target=run, daemon=True)
    try:
        before = registry.value("pydcop_cycles_total")
        thread.start()
        first, err = _parse_prom(_scrape(f"{url}/metrics"),
                                 "live /metrics scrape 1")
        if err:
            return err
        # Wait (bounded) for the counter to advance, then rescrape:
        # the increase must be visible THROUGH the endpoint.
        deadline = time.time() + 30
        second = None
        while time.time() < deadline and not done.is_set():
            text = _scrape(f"{url}/metrics")
            second, err = _parse_prom(text, "live /metrics scrape 2")
            if err:
                return err
            if second.get("pydcop_cycles_total", 0) > max(
                    first.get("pydcop_cycles_total", 0), before):
                break
            time.sleep(0.05)
        c1 = first.get("pydcop_cycles_total", 0)
        c2 = (second or {}).get("pydcop_cycles_total", 0)
        if not (second and c2 > c1):
            return (f"cycle counter did not increase between live "
                    f"scrapes ({c1} -> {c2})")
        health = json.loads(_scrape(f"{url}/healthz"))
        if health.get("status") != "ok":
            return f"unexpected /healthz verdict: {health}"
    finally:
        done.wait(60)
        server.stop()
    return None


def check_request_tracing(trace_path: str):
    """ISSUE 9 gate: serve a 3-request burst with tracing on, then
    `pydcop trace query --request ID` (the real CLI, on the exported
    trace) must reconstruct ONE well-nested tree whose spans cover
    submit → queue → dispatch → engine, all tagged with that
    request's trace_id.  Returns an error string or None."""
    import contextlib
    import io

    import numpy as np

    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation
    from pydcop_tpu.dcop_cli import main as cli_main
    from pydcop_tpu.observability.trace import tracer
    from pydcop_tpu.serving.service import SolveService

    def instance(seed):
        rng = np.random.default_rng(seed)
        dom = Domain("c", "", [0, 1, 2])
        dcop = DCOP(f"demo{seed}", objective="min")
        vs = [Variable(f"v{i}", dom) for i in range(6)]
        for v in vs:
            dcop.add_variable(v)
        for k in range(6):
            dcop.add_constraint(NAryMatrixRelation(
                [vs[k], vs[(k + 1) % 6]],
                rng.integers(0, 10, size=(3, 3)).astype(float),
                f"c{k}"))
        dcop.add_agents([AgentDef("a0")])
        return dcop

    tracer.enable()
    svc = SolveService(batch_window_s=0.2, max_batch=4)
    svc.start()
    try:
        rids = [svc.submit(instance(100 + i),
                           params={"max_cycles": 40})
                for i in range(3)]
        trace_ids = []
        for rid in rids:
            result = svc.result(rid, wait=60.0)
            if result is None or result["status"] != "FINISHED":
                return f"burst request {rid} did not finish: {result}"
            trace_ids.append(result["trace_id"])
        if len(set(trace_ids)) != 3:
            return f"trace_ids not distinct: {trace_ids}"
    finally:
        svc.stop(drain=False)
        tracer.export_jsonl(trace_path)
        tracer.disable()

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli_main(["trace", "query", "--request", trace_ids[0],
                       "--json", trace_path])
    if rc != 0:
        return f"pydcop trace query exited {rc}"
    tree = json.loads(out.getvalue())
    if not tree["well_nested"]:
        return "queried request tree is not well-nested"
    names = set(tree["names"])
    needed = {"serve_submit", "serve_queued", "serve_dispatch",
              "engine_segment"}
    if not needed <= names:
        return (f"request tree missing spans: "
                f"{sorted(needed - names)} (have {sorted(names)})")

    def flat(nodes):
        for node in nodes:
            yield node
            yield from flat(node["children"])

    for node in flat(tree["tree"]):
        args = node["args"]
        if not (args.get("trace_id") == trace_ids[0]
                or trace_ids[0] in (args.get("trace_ids") or [])):
            return (f"{node['name']} span not tagged with the "
                    "request's trace_id")
    return None


if __name__ == "__main__":
    sys.exit(main())
