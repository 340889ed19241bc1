"""``pydcop solve``: one-shot local solve of a static DCOP.

Reference parity: pydcop/commands/solve.py (run_cmd :444, result JSON
keys :611-632: status/assignment/cost/violation/time/msg_count/msg_size/
cycle/agt_metrics).  Modes: ``--mode device`` (default — batched engine
on TPU/CPU), ``--mode thread`` / ``--mode process`` (agent runtime,
reference semantics).
"""

import argparse
import logging
import time

from pydcop_tpu.commands._utils import build_algo_def, emit_result

logger = logging.getLogger("pydcop.cli.solve")


def set_parser(subparsers):
    parser = subparsers.add_parser(
        "solve", help="solve a static DCOP")
    parser.add_argument("dcop_files", nargs="+", help="dcop yaml file(s)")
    parser.add_argument("-a", "--algo", required=True,
                        help="algorithm name, or 'auto' (device mode) "
                             "to race the whole-algorithm portfolio "
                             "on the compiled graph and solve with "
                             "the winner — decision cached by "
                             "structure signature "
                             "(docs/performance.md)")
    parser.add_argument("-p", "--algo_params", action="append",
                        help="algorithm parameter as name:value")
    parser.add_argument("-d", "--distribution", default="oneagent",
                        help="distribution method or file")
    parser.add_argument("-m", "--mode", default="device",
                        choices=["device", "thread", "process"],
                        help="execution mode")
    parser.add_argument("-c", "--cycles", type=int, default=1000,
                        help="max cycles (device/synchronous modes)")
    parser.add_argument("--n_devices", type=int, default=None,
                        help="replicated-variable sharding: row-shard "
                             "factor buckets over this many devices "
                             "(device mode, any algorithm)")
    parser.add_argument("--scenario", default=None, metavar="FILE",
                        help="dynamic DCOP: replay this scenario "
                             "yaml's events (dcop/scenario.py "
                             "vocabulary — change/add/remove factor, "
                             "add variable, agent placement) through "
                             "the incremental DynamicMaxSumEngine "
                             "after the initial solve converges — "
                             "warm-started between events, zero "
                             "recompiles while the shape survives "
                             "(device mode, maxsum family; "
                             "docs/sessions.md)")
    parser.add_argument("--scenario_event_cycles",
                        "--scenario-event-cycles",
                        type=int, default=None, metavar="CYCLES",
                        help="re-convergence cycle budget per "
                             "scenario event (default: --cycles)")
    parser.add_argument("--shards", type=int, default=None,
                        help="partitioned sharding (device mode, "
                             "maxsum family): min-edge-cut partition "
                             "of the factor graph, per-shard variable "
                             "slices, halo-only exchange — O(cut*D) "
                             "per-superstep communication instead of "
                             "O(V*D) (docs/sharding.md); mutually "
                             "exclusive with --n_devices")
    parser.add_argument("--collect_on", default="value_change",
                        choices=["value_change", "cycle_change", "period"])
    parser.add_argument("--period", type=float, default=1.0)
    parser.add_argument("--run_metrics", default=None,
                        help="csv file for run metrics")
    parser.add_argument("--end_metrics", default=None,
                        help="csv file for end metrics")
    parser.add_argument("--infinity", type=float, default=float("inf"))
    parser.add_argument("--uiport", type=int, default=None,
                        help="first websocket UI port (one per agent, "
                             "thread mode)")
    parser.add_argument("--trace", default=None,
                        help="trace file for the run; format chosen "
                             "by --trace_format (docs/observability"
                             ".md)")
    parser.add_argument("--trace_format", "--trace-format",
                        default="chrome",
                        choices=["chrome", "jsonl", "csv"],
                        help="chrome: trace_event JSON for "
                             "chrome://tracing / Perfetto; jsonl: one "
                             "event per line; csv: legacy per-step "
                             "rows (thread mode, infrastructure/"
                             "stats.py)")
    parser.add_argument("--metrics", default=None,
                        help="JSONL metrics-snapshot file; a "
                             "Prometheus text dump is written next to "
                             "it (<file>.prom)")
    parser.add_argument("--metrics_every", "--metrics-every",
                        type=int, default=100,
                        help="cycles between metrics snapshots (device "
                             "mode: also the engine chunk size)")
    parser.add_argument("--serve_metrics", "--serve-metrics",
                        type=int, default=None, metavar="PORT",
                        help="serve live telemetry over HTTP while "
                             "the solve runs: /metrics (Prometheus "
                             "text), /healthz, /events (SSE cycle/"
                             "cost stream); PORT 0 = OS-assigned, "
                             "printed on stderr "
                             "(docs/observability.md)")
    parser.add_argument("--flight_recorder_events",
                        "--flight-recorder-events",
                        type=int, default=None, metavar="N",
                        help="size of the always-on flight-recorder "
                             "ring (trace events kept for postmortem "
                             "bundles; 0 disables; default: "
                             "PYDCOP_FLIGHT_RECORDER or 2048 — "
                             "docs/observability.md)")
    parser.add_argument("--profile", default=None,
                        help="device mode: write a JAX profiler trace "
                             "of the solve to this directory (inspect "
                             "with TensorBoard / xprof)")
    parser.add_argument("--delay", type=float, default=None,
                        help="delay (s) between message deliveries — "
                             "for observing algorithms live, e.g. with "
                             "--uiport (thread/process modes; "
                             "reference solve --delay)")
    # Resilience knobs (docs/resilience.md).
    parser.add_argument("--checkpoint_dir", default=None,
                        help="device mode: snapshot solver state to "
                             "this directory between segments")
    parser.add_argument("--checkpoint_every", type=int, default=100,
                        help="cycles per checkpoint segment")
    parser.add_argument("--checkpoint_async",
                        action=argparse.BooleanOptionalAction,
                        default=True,
                        help="write snapshots on a background thread "
                             "overlapping device compute (default; "
                             "--no-checkpoint_async restores the "
                             "synchronous write between segments)")
    parser.add_argument("--checkpoint_keep", type=int, default=2,
                        help="keep-last-N checkpoint retention (the "
                             "newest valid snapshot is never pruned)")
    parser.add_argument("--resume", action="store_true",
                        help="device mode: continue from the newest "
                             "VALID checkpoint in --checkpoint_dir "
                             "(corrupt/truncated snapshots are "
                             "skipped with a warning)")
    # Self-healing knobs (docs/resilience.md).
    parser.add_argument("--recovery", action="store_true",
                        help="device mode: arm segment-boundary "
                             "guards (NaN/Inf scan) with rollback-"
                             "and-recover on a trip")
    parser.add_argument("--recovery_max_restarts", type=int, default=3,
                        help="restart budget before RecoveryExhausted")
    parser.add_argument("--recovery_noise", type=float, default=1e-3,
                        help="tie-break noise scale of the first "
                             "recovery escalation")
    parser.add_argument("--recovery_damping_bump", type=float,
                        default=0.2,
                        help="damping increase of the second recovery "
                             "escalation")
    parser.add_argument("--health", action="store_true",
                        help="thread mode: heartbeat failure "
                             "detection (phi-accrual suspicion, "
                             "bounded death verdicts feeding repair)")
    parser.add_argument("--health_interval", type=float, default=0.05,
                        help="seconds between agent heartbeats")
    parser.add_argument("--health_suspect_misses", type=float,
                        default=3.0,
                        help="missed intervals before an agent is "
                             "suspect")
    parser.add_argument("--health_dead_misses", type=float,
                        default=8.0,
                        help="missed intervals before an agent is "
                             "declared dead (the detection bound)")
    parser.add_argument("--fault_seed", type=int, default=0,
                        help="seed for deterministic fault injection "
                             "(thread mode)")
    parser.add_argument("--fault_drop", type=float, default=0.0,
                        help="per-message drop probability")
    parser.add_argument("--fault_dup", type=float, default=0.0,
                        help="per-message duplication probability")
    parser.add_argument("--fault_delay", type=float, default=0.0,
                        help="per-message delay probability")
    parser.add_argument("--fault_delay_time", type=float, default=0.05,
                        help="delay (s) applied to delayed messages")
    parser.add_argument("--fault_kill", action="append", default=None,
                        metavar="AGENT:CYCLE",
                        help="kill AGENT when the run reaches CYCLE "
                             "(repeatable; enables replication+repair)")
    parser.add_argument("--fault_replicas", type=int, default=2,
                        help="replicas placed before --fault_kill fires")
    parser.set_defaults(func=run_cmd)


def run_cmd(args) -> int:
    from pydcop_tpu.api import solve
    from pydcop_tpu.dcop.yamldcop import load_dcop_from_file

    if args.mode == "device":
        # Before the first jit (engine/aotcache latch): a second solve
        # of the same structure skips XLA compilation.
        from pydcop_tpu.engine.aotcache import (
            enable_persistent_compile_cache,
        )

        enable_persistent_compile_cache()
    if args.flight_recorder_events is not None:
        from pydcop_tpu.observability import flight

        flight.install(events=args.flight_recorder_events)

    # csv is the legacy per-step CSV (infrastructure/stats.py, thread
    # mode); chrome/jsonl route through the observability tracer via
    # api.solve's trace knob.
    trace_file = trace_format = None
    if args.trace and args.trace_format == "csv":
        from pydcop_tpu.infrastructure import stats

        stats.set_stats_file(args.trace)
    elif args.trace:
        trace_file, trace_format = args.trace, args.trace_format

    dcop = load_dcop_from_file(args.dcop_files)
    if args.algo == "auto":
        # api.solve resolves the portfolio (race or cached replay)
        # and builds the winner's AlgorithmDef itself.
        from pydcop_tpu.commands._utils import parse_algo_params

        if args.mode != "device":
            raise ValueError(
                "--algo auto races device kernels: use --mode device")
        algo_def = "auto"
        auto_params = parse_algo_params(args.algo_params)
    else:
        algo_def = build_algo_def(
            args.algo, args.algo_params, dcop.objective)
        auto_params = None

    if (args.checkpoint_dir or args.resume) and args.mode != "device":
        raise ValueError(
            "--checkpoint_dir/--resume segment the device engine's "
            "solve loop: use --mode device"
        )
    if args.scenario:
        return _run_scenario_cmd(args, dcop, algo_def)
    fault_plan = None
    if (args.fault_drop or args.fault_dup or args.fault_delay
            or args.fault_kill):
        from pydcop_tpu.resilience.faults import CrashEvent, FaultPlan

        if args.mode != "thread":
            raise ValueError(
                "--fault_* knobs need --mode thread (fault injection "
                "wraps in-process transports)"
            )
        fault_plan = FaultPlan(
            seed=args.fault_seed,
            drop=args.fault_drop,
            duplicate=args.fault_dup,
            delay=args.fault_delay,
            delay_time=args.fault_delay_time,
            crashes=tuple(
                CrashEvent.parse(s) for s in (args.fault_kill or [])
            ),
            replicas=args.fault_replicas,
        )
    health_config = None
    if args.health:
        from pydcop_tpu.resilience.health import HealthConfig

        if args.mode != "thread":
            raise ValueError(
                "--health needs --mode thread (heartbeats instrument "
                "in-process agents)"
            )
        health_config = HealthConfig(
            interval=args.health_interval,
            suspect_misses=args.health_suspect_misses,
            dead_misses=args.health_dead_misses,
        )
    recovery_policy = None
    if args.recovery:
        from pydcop_tpu.resilience.recovery import RecoveryPolicy

        if args.mode != "device":
            raise ValueError(
                "--recovery guards the device engine's segmented "
                "loop: use --mode device"
            )
        recovery_policy = RecoveryPolicy(
            max_restarts=args.recovery_max_restarts,
            noise_scale=args.recovery_noise,
            damping_bump=args.recovery_damping_bump,
        )

    t0 = time.perf_counter()
    if args.delay and args.mode == "device":
        logger.warning(
            "--delay only applies to agent modes (ignored in device "
            "mode)"
        )
    if args.mode == "device":
        import contextlib

        profile_ctx = contextlib.ExitStack()
        if args.profile:
            import jax

            from pydcop_tpu.observability.trace import tracer

            profile_ctx.enter_context(jax.profiler.trace(args.profile))
            if trace_file is None:
                # A file session with no file: while it is on, every
                # span of the program is also a ``pydcop:<name>``
                # annotation in the profiler's trace, so the raw
                # trace shows the program's spans beside the device's
                # operations, on one clock.  (With --trace, solve()
                # starts the session itself, inside the profile.)
                tracer.enable()
                profile_ctx.callback(tracer.disable)
        with profile_ctx:
            res = solve(
                dcop, algo_def, backend="device",
                algo_params=auto_params,
                max_cycles=args.cycles, n_devices=args.n_devices,
                shards=args.shards,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                checkpoint_async=args.checkpoint_async,
                checkpoint_keep=args.checkpoint_keep,
                resume=args.resume,
                recovery=recovery_policy,
                trace=trace_file, trace_format=trace_format or "chrome",
                metrics_file=args.metrics,
                metrics_every=args.metrics_every,
                serve_metrics=args.serve_metrics,
            )
        result = {
            "status": res["status"],
            "assignment": res["assignment"],
            "cost": res["cost"],
            "violation": res["violations"],
            "time": res["time"],
            "msg_count": res["metrics"].get("msg_count", 0),
            "msg_size": res["metrics"].get("msg_size", 0),
            "cycle": res["cycles"],
            "compile_time": res["compile_time"],
            "backend": "device",
            **_platform_keys(),
        }
        # A MaxSum engine says which message layout ran and whether
        # the code chose it ("selected") or a parameter did.
        for key in ("layout", "layout_source"):
            if key in res["metrics"]:
                result[key] = res["metrics"][key]
        # Device-mode cycle metrics: the whole solve is one XLA
        # program, so per-cycle rows come from a cost-trace run
        # (MaxSumEngine.run_trace) written post-hoc with the same CSV
        # schema thread mode streams live.  Decimated solves have no
        # equivalent single trace (host-driven clamping rounds), so
        # they only get the final summary row.
        if (args.run_metrics and args.collect_on == "cycle_change"
                and not isinstance(algo_def, str)
                and algo_def.algo in ("maxsum", "amaxsum")
                and not algo_def.params.get("decimation")
                and not algo_def.params.get("decimation_margin")):
            from pydcop_tpu.algorithms.maxsum import build_engine
            from pydcop_tpu.commands.metrics_io import add_csvline

            # The layout the solve reports it ran, named: the
            # reconstruction then follows the solve's own trajectory
            # whichever path selected it.
            trace_res = build_engine(
                dcop,
                dict(algo_def.params, layout=res["metrics"]["layout"]),
                n_devices=args.n_devices, shards=args.shards,
            ).run_trace(max_cycles=max(res["cycles"], 1))
            for i, cost in enumerate(
                    trace_res.metrics["cost_trace"]):
                add_csvline(args.run_metrics, "cycle_change", {
                    "time": None,
                    "cycle": i + 1,
                    "cost": float(cost),
                    "violation": None,
                    "msg_count": None,
                    "msg_size": None,
                    "status": "RUNNING",
                })
    else:
        # Algorithms without a termination condition would run forever:
        # bound thread/process runs when no explicit timeout was given.
        timeout = args.timeout if args.timeout is not None else 15.0
        collector = None
        if args.run_metrics and args.mode == "thread":
            from pydcop_tpu.commands.metrics_io import add_csvline

            def collector(metrics):
                add_csvline(args.run_metrics, args.collect_on, metrics)

        res = solve(
            dcop, algo_def, distribution=args.distribution,
            backend=args.mode, timeout=timeout,
            max_cycles=args.cycles, ui_port=args.uiport,
            collector=collector, collect_moment=args.collect_on,
            collect_period=args.period, delay=args.delay,
            fault_plan=fault_plan, health=health_config,
            trace=trace_file, trace_format=trace_format or "chrome",
            metrics_file=args.metrics,
            metrics_every=args.metrics_every,
            serve_metrics=args.serve_metrics,
        )
        result = {
            "status": res["status"],
            "assignment": res["assignment"],
            "cost": res["cost"],
            "violation": res["violations"],
            "time": res.get("time", time.perf_counter() - t0),
            "msg_count": res.get("msg_count", 0),
            "msg_size": res.get("msg_size", 0),
            "cycle": res.get("cycles", 0),
            "agt_metrics": res.get("agt_metrics", {}),
            "backend": res.get("backend", args.mode),
        }
        if "fault_stats" in res:
            result["fault_stats"] = res["fault_stats"]
            result["killed_agents"] = res.get("killed_agents", [])
        if "health" in res:
            result["health"] = res["health"]

    if args.run_metrics or args.end_metrics:
        from pydcop_tpu.commands.metrics_io import add_csvline

        # Thread mode streams run metrics live through the collector;
        # the final summary row is always appended so the file exists
        # even when no collection event fired.
        for path in (args.run_metrics, args.end_metrics):
            if path:
                add_csvline(path, args.collect_on, result)

    emit_result(result, args.output)
    return 0


def _platform_keys() -> dict:
    """Which platform the device backend resolved to — the result
    says where it ran (``tpu``/``cpu``), never assumes it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _run_scenario_cmd(args, dcop, algo_def) -> int:
    """``pydcop solve --scenario FILE``: dynamic-DCOP replay through
    the incremental engine (reference CLI parity for scenario runs;
    generators/scenario_gen.py makes the inputs).  Events apply
    between warm-started engine segments — the same machinery the
    serve plane's stateful sessions use (docs/sessions.md)."""
    import time as _time

    from pydcop_tpu.dcop.yamldcop import load_scenario_from_file
    from pydcop_tpu.engine.dynamic import replay_scenario

    if args.mode != "device":
        raise ValueError(
            "--scenario replays events through the device engine: "
            "use --mode device")
    if isinstance(algo_def, str) or algo_def.algo not in (
            "maxsum", "maxsum_dynamic", "amaxsum"):
        raise ValueError(
            "--scenario needs a maxsum-family algorithm (the "
            "incremental engine is MaxSum); got "
            f"{algo_def if isinstance(algo_def, str) else algo_def.algo!r}")
    scenario = load_scenario_from_file(args.scenario)
    params = dict(algo_def.params)
    # maxsum's decimation_margin knob defaults to 0.0 == OFF (same
    # contract as decimation_plan_from_params: margin <= 0 disables),
    # so the falsy coercion here is the knob's documented semantics.
    margin = params.get("decimation_margin") or None
    t0 = _time.perf_counter()
    out = replay_scenario(
        dcop, scenario, params=params, max_cycles=args.cycles,
        event_cycles=args.scenario_event_cycles,
        decimation_margin=margin,
    )
    result = {
        "status": "FINISHED" if out["converged"] else "TIMEOUT",
        "assignment": out["assignment"],
        # Cost and violations both come from the MUTATED (live)
        # factor set — a hard constraint the scenario removed or
        # replaced no longer binds the solution, so the original
        # problem's tables are not consulted.
        "cost": out["cost"],
        "violation": out["violations"],
        "time": _time.perf_counter() - t0,
        "cycle": out["cycles"],
        "backend": "device",
        **_platform_keys(),
        "scenario": {
            "file": args.scenario,
            "events_applied": out["event_count"],
            "recompiles": out["recompiles"],
            "clamped": out["clamped"],
            "orphaned_computations": out["orphaned"],
            "events": out["events"],
        },
    }
    emit_result(result, args.output)
    return 0
