"""High-level solve API.

Reference parity: pydcop/infrastructure/run.py:52 ``solve()`` — build
graph → distribute → run → return assignment.  Here the default backend
is the device engine (one jitted BSP program); ``backend="thread"`` runs
the agent-mode runtime for reference-equivalent distributed execution.
"""

import os
import time
from typing import Any, Dict, Optional, Union

from pydcop_tpu.algorithms import AlgorithmDef, load_algorithm_module
from pydcop_tpu.dcop.dcop import DCOP


class SolveResult(dict):
    """Dict-like result: assignment, cost, violations, cycles, times."""

    @property
    def assignment(self) -> Dict[str, Any]:
        return self["assignment"]

    @property
    def cost(self) -> float:
        return self["cost"]


def solve(dcop: DCOP, algo_def: Union[str, AlgorithmDef],
          distribution: str = "oneagent",
          backend: str = "device",
          timeout: Optional[float] = None,
          max_cycles: int = 1000,
          algo_params: Optional[Dict[str, Any]] = None,
          mesh=None, n_devices: Optional[int] = None,
          shards: Optional[int] = None,
          warmup: bool = False,
          ui_port: Optional[int] = None,
          collector=None,
          collect_moment: str = "value_change",
          collect_period: float = 1.0,
          delay: Optional[float] = None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_every: Optional[int] = None,
          checkpoint_async: bool = True,
          checkpoint_keep: int = 2,
          resume: bool = False,
          fault_plan=None,
          recovery=None,
          health=None,
          trace: Optional[str] = None,
          trace_format: str = "chrome",
          metrics_file: Optional[str] = None,
          metrics_every: Optional[int] = None,
          serve_metrics: Optional[int] = None,
          ) -> SolveResult:
    """Solve a DCOP and return assignment + quality metrics.

    backend="device": batched engine on TPU/CPU devices (default).
    backend="thread": agent-mode runtime (threads + in-process messages),
    reference-equivalent semantics.

    ``algo_def="auto"`` (device backend) races the whole-algorithm
    portfolio on the compiled graph — maxsum with/without
    branch-and-bound pruning and decimation, plus the vectorized
    local-search kernels (dsa/mgm/gdba) — toward the best cost
    reachable in a short budget, solves with the winner, and caches
    the decision by structure signature
    (engine/autotune.autotune_portfolio): a second same-structure
    solve replays the choice with zero measurement.  The decision and
    per-candidate timings land in ``metrics['portfolio']``.

    Scaling knobs (docs/sharding.md): ``n_devices`` row-shards factor
    buckets over a mesh with replicated variable tables (any device
    algorithm; per-superstep all-reduce is O(V·D)); ``shards=N``
    runs the PARTITIONED engine instead (maxsum family) — a
    min-edge-cut partition assigns variables and factors to shards
    and only cut-edge halo state is exchanged per superstep
    (O(cut·D)).  Partition statistics (``edge_cut_fraction``,
    ``halo_vars_per_shard``, ``balance``) and communication
    accounting come back in ``metrics``.  The two knobs are mutually
    exclusive.

    Resilience knobs (docs/resilience.md): ``checkpoint_dir`` chunks a
    device-mode solve into ``checkpoint_every``-cycle segments with an
    NPZ state snapshot between segments; ``resume=True`` continues
    from the newest snapshot in that directory instead of cycle 0
    (identical final result — the battery asserts it).
    ``checkpoint_async`` (default True) moves each snapshot's
    device→host copy + file write onto a background writer thread so
    it overlaps the next segment's device compute instead of
    serializing with it (all snapshots are flushed before the solve
    returns); ``checkpoint_async=False`` restores the synchronous
    write between segments.  ``checkpoint_keep`` bounds the retention
    (keep-last-N snapshots, default 2; the newest valid one is never
    pruned).  ``fault_plan`` (a resilience.faults.FaultPlan) runs the
    thread backend under seeded message faults and crash injection.

    Self-healing knobs (docs/resilience.md "Failure detection &
    recovery"): ``recovery`` (a resilience.recovery.RecoveryPolicy)
    arms segment-boundary guards on a device solve — NaN/Inf scan +
    optional cost-divergence window, rollback to the last valid
    snapshot with escalating intervention, ``RecoveryExhausted``
    carrying the partial trajectory once the restart budget is spent;
    guard trip/attempt counts come back in ``metrics``.  ``health``
    (a resilience.health.HealthConfig) runs the thread backend under
    active heartbeat failure detection — phi-accrual suspicion,
    bounded ``agent_dead`` verdicts feeding the repair path — and
    returns the verdict history under the result's ``health`` key.

    Observability knobs (docs/observability.md): ``trace`` records
    the whole solve on the process tracer and writes a Chrome
    ``trace_event`` JSON (``trace_format="chrome"``, open in
    chrome://tracing / Perfetto) or line-delimited JSON
    (``"jsonl"``) to that path; it does not change the program that
    runs (a traced device solve is the untraced one's whole-solve
    program, with ``engine_call`` in its trace).  ``metrics_file``
    and ``serve_metrics`` do: their per-chunk snapshots need the
    segmented loop.  ``metrics_file`` activates the
    metrics registry, appends JSONL snapshots — in device mode one per
    ``metrics_every``-cycle engine chunk (honest per-chunk timings +
    a cost-vs-cycle curve, returned in ``metrics['cost_curve']``),
    in thread mode one each time the global cycle advances by
    ``metrics_every`` — and writes a Prometheus text dump to
    ``<metrics_file>.prom`` when the solve ends.  ``serve_metrics``
    (a port; 0 = OS-assigned) serves live telemetry over HTTP for the
    duration of the solve — ``/metrics`` (Prometheus text),
    ``/healthz`` (health verdicts) and ``/events`` (SSE cycle/cost
    stream) — so a long run is scrapeable while it runs
    (observability/server.py).  A device solve with ``metrics_file``
    or ``serve_metrics`` also records XLA cost attribution:
    measured flops/bytes/peak memory per
    compiled segment land in ``metrics['xla_cost']`` keyed by jit
    cache key (explicit ``available: False`` markers on backends that
    return nothing).  All default off and cost nothing while off.
    Interactions: with ``checkpoint_dir`` the
    chunking follows ``checkpoint_every``, so snapshots land every
    ``max(checkpoint_every, metrics_every)`` cycles; ``warmup=True``
    keeps the plain (unsegmented) device path — the solve is still
    traced, but without per-chunk points or a cost curve.

    warmup=True runs the compiled program once untimed before the timed
    call, so one-shot solves report steady-state rates instead of
    compile-dominated ones (device backend only).  The warm-up run is a
    FULL discarded solve (the cycle count is baked into the compiled
    program, so a shorter variant would compile a different
    executable): expect ~2x wall time for large max_cycles, and prefer
    warmup=False when only the answer matters.  Host-driven sweep
    algorithms (dpop, syncbb, ncbb) and maxsum decimation ignore it —
    their runners already report compile time separately.

    Example::

        >>> from pydcop_tpu.dcop.dcop import DCOP
        >>> from pydcop_tpu.dcop.objects import Domain, Variable
        >>> from pydcop_tpu.dcop.relations import constraint_from_str
        >>> d = Domain('d', '', [0, 1])
        >>> x, y = Variable('x', d), Variable('y', d)
        >>> dcop = DCOP('doc', objective='min')
        >>> dcop.add_constraint(
        ...     constraint_from_str('c', '(x + y - 1)**2', [x, y]))
        >>> res = solve(dcop, 'dpop')
        >>> res['status'], round(res['cost'], 3)
        ('FINISHED', 0.0)
    """
    portfolio_info = None
    if isinstance(algo_def, str) and algo_def == "auto":
        if backend != "device":
            raise ValueError(
                "algo='auto' races device kernels: use "
                "backend='device'")
        algo_def, portfolio_info = _resolve_auto_algo(
            dcop, algo_params or {})
    if isinstance(algo_def, str):
        algo_def = AlgorithmDef.build_with_default_param(
            algo_def, algo_params or {}, mode=dcop.objective
        )
    module = load_algorithm_module(algo_def.algo)

    # Resilience knobs are backend-specific: reject silently-ignored
    # combinations instead of letting a chaos test believe faults were
    # injected (or a preemptible run believe it checkpointed).
    if fault_plan is not None and backend == "device":
        raise ValueError(
            "fault_plan wraps agent transports: use backend='thread'"
        )
    if (checkpoint_dir is not None or resume) and backend != "device":
        raise ValueError(
            "checkpointing segments the device engine's solve loop: "
            "use backend='device'"
        )
    if resume and checkpoint_dir is None:
        raise ValueError(
            "resume=True needs checkpoint_dir: there is no snapshot "
            "location to resume from"
        )
    if recovery is not None and backend != "device":
        raise ValueError(
            "recovery guards the device engine's segmented loop: "
            "use backend='device'"
        )
    if health is not None and backend != "thread":
        raise ValueError(
            "health monitoring instruments agent threads: use "
            "backend='thread'"
        )
    if shards is not None and shards > 1:
        if backend != "device":
            raise ValueError(
                "shards= partitions the device engine's factor "
                "graph: use backend='device'"
            )
        if not getattr(module, "SUPPORTS_SHARDS", False):
            raise NotImplementedError(
                f"Algorithm {algo_def.algo} has no partitioned "
                "engine (maxsum family only); use n_devices= for "
                "replicated-variable sharding"
            )

    session = None
    if (trace is not None or metrics_file is not None
            or serve_metrics is not None):
        from pydcop_tpu.observability import ObservabilitySession

        session = ObservabilitySession(
            trace, trace_format, metrics_file,
            serve_port=serve_metrics,
        ).start()
    try:
        from pydcop_tpu.observability.trace import tracer

        with tracer.span("solve", "api", algo=algo_def.algo,
                         backend=backend, max_cycles=max_cycles):
            result = _solve(
                dcop, algo_def, module, distribution=distribution,
                backend=backend, timeout=timeout,
                max_cycles=max_cycles, mesh=mesh, n_devices=n_devices,
                shards=shards,
                warmup=warmup, ui_port=ui_port, collector=collector,
                collect_moment=collect_moment,
                collect_period=collect_period, delay=delay,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_async=checkpoint_async,
                checkpoint_keep=checkpoint_keep, resume=resume,
                fault_plan=fault_plan, recovery=recovery,
                health=health,
                metrics_file=metrics_file, metrics_every=metrics_every,
                serving=serve_metrics is not None,
            )
            if portfolio_info is not None:
                result.setdefault("metrics", {})[
                    "portfolio"] = portfolio_info
            return result
    finally:
        if session is not None:
            session.finish()


def _resolve_auto_algo(dcop: DCOP, algo_params: Dict[str, Any]):
    """Resolve ``algo="auto"`` through the portfolio racer: replay a
    persisted same-structure decision when one exists (no re-race —
    asserted in the work-reduction battery), otherwise compile once
    and race the candidates on the real graph.  Returns
    ``(AlgorithmDef, info)`` with the winner's extra params merged
    over the caller's."""
    from pydcop_tpu.engine.autotune import (
        PORTFOLIO_PARAMS,
        autotune_portfolio,
        cached_portfolio_choice,
        dcop_portfolio_key,
        dpop_portfolio_runner,
    )

    key = dcop_portfolio_key(dcop)
    choice = cached_portfolio_choice(key)
    if choice is not None:
        info = {"algo": choice, "portfolio_source": "cache",
                "portfolio_key": key}
    else:
        from pydcop_tpu.engine.compile import compile_dcop

        graph, meta = compile_dcop(
            dcop, noise_level=float(
                algo_params.get("noise", 0.01) or 0.0))
        # Exact inference enters the race width-keyed: the runner is
        # None past DPOP_RACE_MAX_ELEMENTS (computed from the
        # pseudo-tree, CEC shrinkage included), so wide structures
        # resolve to an iterative winner without paying an exact
        # attempt.
        info = autotune_portfolio(
            graph, key=key, meta=meta,
            extra_runners={
                "dpop": dpop_portfolio_runner(dcop, graph, meta)})
    algo, extra = PORTFOLIO_PARAMS[info["algo"]]
    module = load_algorithm_module(algo)
    allowed = {p.name for p in module.algo_params}
    params = {k: v for k, v in algo_params.items() if k in allowed}
    dropped = sorted(set(algo_params) - set(params))
    if dropped:
        # The caller parameterized for one family; the race picked
        # another.  Dropping (loudly) beats failing the solve — the
        # caller asked for "whatever wins".
        import logging

        logging.getLogger("pydcop.api").warning(
            "algo='auto' winner %s does not take parameter(s) %s; "
            "ignored", algo, ", ".join(dropped))
    params.update(extra)
    return AlgorithmDef.build_with_default_param(
        algo, params, mode=dcop.objective), info


class ServeHandle:
    """A running solve service + HTTP front end.

    ``url``/``port`` locate the front end; ``service`` is the
    underlying :class:`~pydcop_tpu.serving.service.SolveService`
    (submit/result work in-process too); ``stop()`` drains the queue
    and shuts both down.  Context-manager friendly."""

    def __init__(self, service, front_end):
        self.service = service
        self.front_end = front_end

    @property
    def url(self):
        return self.front_end.url

    @property
    def port(self):
        return self.front_end.port

    def stop(self, drain: bool = True) -> Dict[str, Any]:
        """Stop front end + service; returns the service's drain
        summary (``drained`` / ``replayable`` / ``failed_pending``)."""
        self.front_end.stop()
        return self.service.stop(drain=drain)

    def __enter__(self) -> "ServeHandle":
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class FleetHandle:
    """A running fleet: N serve-worker processes behind one router
    front end (docs/serving.md "Fleet-scale serving").  ``router`` is
    the :class:`~pydcop_tpu.serving.router.FleetRouter` (replica
    states, routing stats); ``stop()`` SIGTERM-drains every worker
    and shuts the front end down."""

    def __init__(self, router, front_end):
        self.router = router
        self.front_end = front_end

    @property
    def url(self):
        return self.front_end.url

    @property
    def port(self):
        return self.front_end.port

    def stop(self, drain: bool = True) -> Dict[str, Any]:
        self.front_end.stop()
        return self.router.stop(drain=drain)

    def __enter__(self) -> "FleetHandle":
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def _write_port_file(path: str, port: int) -> None:
    """Atomically publish the bound port (the fleet router's worker
    handshake; also handy for scripts wrapping ``--port 0``)."""
    import tempfile

    directory = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".port_")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            f.write(f"{port}\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def serve(port: int = 8080, host: str = "127.0.0.1",
          max_queue: int = 256, batch_window_s: float = 0.02,
          max_batch: int = 16, high_water: Optional[int] = None,
          default_params: Optional[Dict[str, Any]] = None,
          breaker_failures: int = 3, breaker_reset_s: float = 5.0,
          result_keep: int = 4096,
          journal_dir: Optional[str] = None,
          journal_sync: bool = False,
          recover: bool = False,
          envelope_packing: bool = True,
          envelope_overhead_ms: Optional[float] = None,
          pipeline: bool = True,
          speculate: bool = True,
          session_max: int = 64,
          session_segment_cycles: Optional[int] = None,
          session_checkpoint_every_events: int = 8,
          session_certify_after: Optional[float] = None,
          replicas: int = 1,
          affinity: str = "structure",
          compile_cache_dir: Optional[str] = None,
          heartbeat_s: float = 0.25,
          probe_timeout_s: Optional[float] = None,
          spill_slack: int = 4,
          hosts: int = 1,
          slo_p99_ms: Optional[float] = None,
          min_replicas: Optional[int] = None,
          max_replicas: Optional[int] = None,
          join: Optional[str] = None,
          host_id: Optional[str] = None,
          fleet_trace: Optional[bool] = None,
          port_file: Optional[str] = None,
          block: bool = False) -> Optional[Any]:
    """Start the multi-tenant solve service (docs/serving.md).

    Incoming problems are binned by structure signature and
    same-structure requests are stacked into ONE vmapped device
    dispatch (the batched-BP throughput lever); results stream back
    per request with latency accounting and a time LEDGER whose
    components sum to the measured total
    (docs/observability.md "Efficiency accounting").  The front end
    serves ``POST /solve`` / ``GET /result/<id>`` / ``GET /stats``
    plus the live telemetry routes (``/metrics``, ``/healthz``,
    ``/events``, ``/profile`` — the backend-honest efficiency
    rollup ``pydcop profile report --url`` renders).

    Different-structure requests that structure binning would
    dispatch solo are additionally packed into shape-envelope
    dispatches when a per-flush cost model says the padded batch
    beats solo dispatches (``envelope_packing``, on by default —
    results stay bit-identical to solo solves;
    ``envelope_overhead_ms`` tunes the modeled per-dispatch fixed
    cost the decision weighs against padding waste — docs/serving.md
    "Envelope batching").

    Admission control: a submit past the queue's ``high_water``
    (default ``max_queue``) is rejected with 429; repeated dispatch
    failure opens a circuit breaker (``breaker_failures`` failures,
    ``breaker_reset_s`` probe delay) that turns submits 503 and
    ``/healthz`` failing.

    ``journal_dir`` enables the durable request journal (every 202 is
    crash-durable); ``recover=True`` replays accepted-but-unfinished
    journal entries through the queue on startup (``pydcop serve
    --journal_dir D --recover``); ``journal_sync`` fsyncs per record.

    Stateful sessions (docs/sessions.md): ``POST /session`` opens a
    long-lived dynamic-DCOP solve, ``PATCH /session/<id>/events``
    streams scenario events applied between engine segments without
    recompiling when the shape survives, SSE streams anytime results
    and the journal replays whole sessions after a crash.
    ``session_max`` bounds live sessions (each keeps a warm engine),
    ``session_segment_cycles`` overrides the default anytime-segment
    granularity, ``session_checkpoint_every_events`` the engine-state
    snapshot cadence (journaled services; smaller = faster recovery,
    more snapshot writes).  ``session_certify_after=S`` arms the
    exact-inference oracle tier (docs/sessions.md "The oracle tier"):
    a session whose event stream has quiesced for S seconds gets a
    background DPOP solve of its current problem that either
    certifies the warm fixpoint as optimal or upgrades the served
    assignment to the true optimum, publishing the certified-cost
    delta on the session SSE stream and in ``/stats``.

    Fleet scaling (docs/serving.md "Fleet-scale serving"):
    ``replicas=N`` (N > 1) spawns N ``pydcop serve`` WORKER PROCESSES
    — each a full solve service with its own scheduler thread,
    journal segment (``<journal_dir>/replica-<k>/``) and /metrics —
    behind a structure-affinity router speaking this same wire
    protocol; the return value is a :class:`FleetHandle`.
    ``affinity`` picks the routing policy (``"structure"``:
    rendezvous-hash on the admission-time structure key so
    same-structure traffic lands where the compiled program is warm;
    ``"round_robin"``: the A/B baseline), ``heartbeat_s`` /
    ``spill_slack`` tune replica death detection and hot-spot
    spillover.  ``compile_cache_dir`` enables the persistent AOT
    compile cache (engine/aotcache.py) — workers (and the
    single-service path) enable it BEFORE their first jit, so a fresh
    replica serves its first same-structure request without paying
    XLA compilation.

    Elastic fleet (docs/serving.md "Elastic fleet"): ``hosts=H``
    stripes locally spawned replicas over H simulated host identities
    (host-kill chaos, CI two-host topologies); ``slo_p99_ms`` +
    ``max_replicas`` arm SLO-driven autoscaling (the router grows the
    fleet toward ``max_replicas`` when rolling p99 or queue depth
    breaches the SLO, drains back toward ``min_replicas`` — migrating
    warm sessions off, never killing them — when quiet).  ``join``
    turns a SINGLE-replica serve into a remote fleet member: after
    the front end binds, the worker announces its own URL to the
    router at ``join`` via ``POST /fleet/join`` (``host_id``
    overrides the announced host identity, default
    :func:`pydcop_tpu.engine.multihost.fleet_host_id`); incompatible
    with ``replicas > 1``.

    ``port=0`` asks the OS for a free port (``port_file`` atomically
    publishes the assignment — the fleet worker handshake).
    ``block=True`` (the ``pydcop serve`` CLI) serves until
    SIGTERM/SIGINT, then STOPS WITH DRAIN — an orchestrated restart
    (k8s-style) never drops accepted work: queued requests either
    finish in the drain window or stay journaled-replayable, and the
    drained count is logged on exit.  Returns None.  ``block=False``
    returns a :class:`ServeHandle` / :class:`FleetHandle` (both
    context managers) for embedding and tests.

    ``fleet_trace`` forces fleet-wide causal tracing on/off
    (docs/observability.md "Fleet tracing"): the router mints one
    trace context per admission, stamps it on every forwarded
    submit/event-batch/fence/migration/retry, and collects replica
    spans for ``GET /fleet/forensics/<id>``.  ``None`` (default)
    defers to ``PYDCOP_FLEET_TRACE`` (on unless set to 0); an
    explicit value is exported to that env var so spawned workers
    inherit it.
    """
    if fleet_trace is not None:
        # The knob lives in the environment on purpose: spawned fleet
        # workers inherit it, and every header/shipping decision
        # reads it per call — so toggling is honest fleet-wide.
        os.environ["PYDCOP_FLEET_TRACE"] = "1" if fleet_trace else "0"
    if join and replicas > 1:
        raise ValueError(
            "join= is for single-replica remote workers; a local "
            "fleet (replicas > 1) IS the router — point the workers' "
            "join at its URL instead")
    if replicas > 1:
        return _serve_fleet(
            port=port, host=host, max_queue=max_queue,
            batch_window_s=batch_window_s, max_batch=max_batch,
            high_water=high_water, default_params=default_params,
            breaker_failures=breaker_failures,
            breaker_reset_s=breaker_reset_s, result_keep=result_keep,
            journal_dir=journal_dir, journal_sync=journal_sync,
            envelope_packing=envelope_packing,
            envelope_overhead_ms=envelope_overhead_ms,
            pipeline=pipeline, speculate=speculate,
            session_max=session_max,
            session_segment_cycles=session_segment_cycles,
            session_checkpoint_every_events=(
                session_checkpoint_every_events),
            session_certify_after=session_certify_after,
            replicas=replicas, affinity=affinity,
            compile_cache_dir=compile_cache_dir,
            heartbeat_s=heartbeat_s,
            probe_timeout_s=probe_timeout_s,
            spill_slack=spill_slack,
            hosts=hosts, slo_p99_ms=slo_p99_ms,
            min_replicas=min_replicas, max_replicas=max_replicas,
            port_file=port_file, block=block)
    if compile_cache_dir:
        # Before the service compiles anything: the cache-dir config
        # silently no-ops once a jit has run (engine/aotcache latch).
        from pydcop_tpu.engine.aotcache import (
            enable_persistent_compile_cache,
        )

        enable_persistent_compile_cache(compile_cache_dir)
    import sys

    import jax

    # Take the device NOW: a service that cannot have its accelerator
    # (a chip belongs to one process) fails at start — before it
    # binds, so a fleet router sees its worker die with the reason in
    # the log — not at its first request.
    devices = jax.devices()
    print(f"pydcop serve: backend {devices[0].platform} "
          f"({len(devices)} x {devices[0].device_kind})",
          file=sys.stderr)
    from pydcop_tpu.serving.admission import AdmissionPolicy
    from pydcop_tpu.serving.http import ServeFrontEnd
    from pydcop_tpu.serving.service import SolveService

    service = SolveService(
        max_queue=max_queue,
        batch_window_s=batch_window_s,
        max_batch=max_batch,
        default_params=default_params,
        admission=AdmissionPolicy(
            high_water=(high_water if high_water is not None
                        else max_queue),
            breaker_failures=breaker_failures,
            breaker_reset_s=breaker_reset_s,
        ),
        result_keep=result_keep,
        journal_dir=journal_dir,
        journal_sync=journal_sync,
        recover=recover,
        envelope_packing=envelope_packing,
        envelope_overhead_ms=envelope_overhead_ms,
        pipeline=pipeline,
        speculate=speculate,
        session_max=session_max,
        session_segment_cycles=session_segment_cycles,
        session_checkpoint_every_events=(
            session_checkpoint_every_events),
        session_certify_after=session_certify_after,
    ).start()
    try:
        front_end = ServeFrontEnd(service, port=port, host=host).start()
    except Exception:
        service.stop(drain=False)
        raise
    handle = ServeHandle(service, front_end)
    print(f"pydcop serve: listening on {handle.url} "
          "(POST /solve, GET /result/<id>, /metrics, /healthz)",
          file=sys.stderr)
    if port_file:
        _write_port_file(port_file, handle.port)
    if join:
        # Announce AFTER the front end binds: the router health-probes
        # the announced URL before admitting it to the fleet.
        _announce_join(join, handle.url, host_id,
                       journal_dir=journal_dir)
    if not block:
        return handle
    _serve_until_signal(
        handle,
        lambda summary: (
            "pydcop serve: shut down — "
            f"{summary['drained']} request(s) drained, "
            f"{summary['replayable']} journaled replayable, "
            f"{summary['failed_pending']} failed pending"))
    return None


def _announce_join(join_url: str, own_url: str,
                   host_id: Optional[str] = None,
                   journal_dir: Optional[str] = None) -> bool:
    """Announce this worker to a fleet router's ``POST /fleet/join``.

    Best-effort with small retries (the router may still be binding
    during a parallel bring-up): a failed announce leaves the worker
    serving standalone with a warning — operators re-announce by
    restarting or curling /fleet/join themselves — rather than
    refusing to serve at all.

    ``journal_dir`` rides along when the worker journals: a router
    that can see the same filesystem uses it for dead-session
    adoption (serving/migration.adopt_dead_sessions).  The socket I/O
    routes through the netfault seam like every other fleet link, so
    an injected partition also severs discovery."""
    import json
    import sys
    import time
    import urllib.parse

    from pydcop_tpu.engine.multihost import fleet_host_id
    from pydcop_tpu.serving import netfault

    own_host_id = host_id or fleet_host_id()
    doc = {"url": own_url, "host_id": own_host_id}
    if journal_dir:
        doc["journal_dir"] = journal_dir
    payload = json.dumps(doc).encode()
    parsed = urllib.parse.urlsplit(join_url)
    router_host = parsed.hostname or "127.0.0.1"
    router_port = parsed.port or 80
    path = (parsed.path.rstrip("/") or "") + "/fleet/join"
    last: Optional[Exception] = None
    for attempt in range(5):
        if attempt:
            time.sleep(min(0.5 * attempt, 2.0))
        try:
            status, _ctype, body = netfault.exchange(
                ("worker", own_host_id), ("router", router_host),
                router_host, router_port, "POST", path,
                body=payload, timeout=5.0)
            if status >= 400:
                raise ValueError(
                    f"join answered {status}: {body[:200]!r}")
            print(f"pydcop serve: joined fleet at {join_url}",
                  file=sys.stderr)
            return True
        except (OSError, ValueError) as exc:
            last = exc
    print(f"pydcop serve: fleet join at {join_url} failed ({last}); "
          "serving standalone", file=sys.stderr)
    return False


def _serve_fleet(*, port, host, max_queue, batch_window_s, max_batch,
                 high_water, default_params, breaker_failures,
                 breaker_reset_s, result_keep, journal_dir,
                 journal_sync, envelope_packing, envelope_overhead_ms,
                 pipeline, speculate,
                 session_max, session_segment_cycles,
                 session_checkpoint_every_events,
                 session_certify_after, replicas, affinity,
                 compile_cache_dir, heartbeat_s, probe_timeout_s,
                 spill_slack,
                 hosts, slo_p99_ms, min_replicas, max_replicas,
                 port_file, block) -> Optional["FleetHandle"]:
    """The ``replicas > 1`` serve path: build the worker CLI tail
    from the same kwargs the single-service path consumes (so the two
    cannot drift), spawn the fleet, mount the router front end."""
    from pydcop_tpu.serving.router import FleetRouter, RouterFrontEnd

    params = dict(default_params or {})
    worker_args = [
        "--max_queue", str(max_queue),
        "--batch_window", str(batch_window_s),
        "--max_batch", str(max_batch),
        "--breaker_failures", str(breaker_failures),
        "--breaker_reset", str(breaker_reset_s),
        "--result_keep", str(result_keep),
        "--session_max", str(session_max),
        "--session_checkpoint_every",
        str(session_checkpoint_every_events),
    ]
    if high_water is not None:
        worker_args += ["--high_water", str(high_water)]
    if "max_cycles" in params:
        worker_args += ["--cycles", str(params["max_cycles"])]
    if "damping" in params:
        worker_args += ["--damping", str(params["damping"])]
    # EVERY other default-param key rides as JSON — the fleet and
    # single-service paths must not drift (a replicas=2 service
    # dropping the caller's stability/noise/prune defaults would
    # solve differently than replicas=1 with no error anywhere).
    extra_params = {k: v for k, v in params.items()
                    if k not in ("max_cycles", "damping")}
    if extra_params:
        import json as json_mod

        worker_args += ["--params_json",
                        json_mod.dumps(extra_params)]
    if journal_sync:
        worker_args += ["--journal_sync"]
    if not envelope_packing:
        worker_args += ["--no_envelope"]
    if not pipeline:
        worker_args += ["--no_pipeline"]
    if not speculate:
        worker_args += ["--no_speculate"]
    if envelope_overhead_ms is not None:
        worker_args += ["--envelope_overhead_ms",
                        str(envelope_overhead_ms)]
    if session_segment_cycles is not None:
        worker_args += ["--session_segment_cycles",
                        str(session_segment_cycles)]
    if session_certify_after is not None:
        worker_args += ["--session_certify_after",
                        str(session_certify_after)]
    router = FleetRouter(
        replicas=replicas, worker_args=worker_args,
        journal_dir=journal_dir,
        compile_cache_dir=compile_cache_dir, affinity=affinity,
        heartbeat_s=heartbeat_s, probe_timeout_s=probe_timeout_s,
        spill_slack=spill_slack,
        default_params=params,
        hosts=hosts, slo_p99_ms=slo_p99_ms,
        min_replicas=min_replicas, max_replicas=max_replicas,
    ).start()
    try:
        front_end = RouterFrontEnd(router, port=port,
                                   host=host).start()
    except Exception:
        router.stop(drain=False)
        raise
    handle = FleetHandle(router, front_end)
    import sys

    print(f"pydcop serve: fleet of {replicas} replica(s) behind "
          f"{handle.url} (affinity={affinity})", file=sys.stderr)
    if port_file:
        _write_port_file(port_file, handle.port)
    if not block:
        return handle
    _serve_until_signal(
        handle,
        lambda summary: (
            "pydcop serve: fleet shut down — worker exits "
            + ", ".join(
                f"replica-{w['index']}={w['exit']}"
                for w in summary["workers"])))
    return None


def _serve_until_signal(handle, summarize) -> None:
    """``block=True`` shared tail: wait for SIGTERM/SIGINT, cut the
    black-box bundle, drain-stop the handle, log the summary."""
    import signal
    import sys
    import threading

    stop_event = threading.Event()
    got_signal = []

    def _on_signal(signum, frame):  # noqa: ARG001 — signal signature
        got_signal.append(signum)
        stop_event.set()

    # SIGTERM is what an orchestrator sends before the SIGKILL
    # grace deadline; both it and Ctrl-C route through the same
    # drain-first shutdown.  Original handlers restored on exit so an
    # embedding process is left the way it was found.  Handlers can
    # only be installed from the main thread — a background-thread
    # caller just blocks on the event (signals never reach it).
    previous = {}
    if threading.current_thread() is threading.main_thread():
        previous = {
            sig: signal.signal(sig, _on_signal)
            for sig in (signal.SIGTERM, signal.SIGINT)
        }
    try:
        stop_event.wait()
        print("pydcop serve: signal received, draining…",
              file=sys.stderr)
        # Fatal-signal anomaly: cut the black-box bundle BEFORE the
        # drain mutates the queue/journal — the bundle shows what the
        # process was doing when the orchestrator pulled the plug.
        from pydcop_tpu.observability import flight

        flight.trigger("fatal_signal", force=True,
                       signum=(got_signal[0] if got_signal else None))
    finally:
        summary = handle.stop(drain=True)
        for sig, handler in previous.items():
            signal.signal(sig, handler)
        print(summarize(summary), file=sys.stderr)


def _solve(dcop, algo_def, module, *, distribution, backend, timeout,
           max_cycles, mesh, n_devices, shards, warmup, ui_port,
           collector,
           collect_moment, collect_period, delay, checkpoint_dir,
           checkpoint_every, checkpoint_async, checkpoint_keep,
           resume, fault_plan, recovery, health,
           metrics_file, metrics_every, serving=False) -> SolveResult:
    if backend == "device":
        if not hasattr(module, "solve_on_device"):
            raise NotImplementedError(
                f"Algorithm {algo_def.algo} has no device path; use "
                "backend='thread'"
            )
        # Join the cross-host runtime when configured (PYDCOP_* env
        # vars / PYDCOP_MULTIHOST=auto); single-host runs no-op.
        from pydcop_tpu.engine.multihost import initialize_multihost

        initialize_multihost()
        t0 = time.perf_counter()
        # The engine probe needs chunk boundaries, so a solve that
        # writes metrics snapshots (``metrics_file``) or serves them
        # (``serve_metrics``) routes through the same segmented loop
        # checkpointing uses.  ``trace=`` alone does not: a traced
        # solve runs the program an untraced one runs (its trace
        # shows ``engine_call``, not ``engine_segment``).
        # Decimation IS a segmented mode now (clamping happens at
        # those same boundaries), so decimated solves checkpoint,
        # recover and probe like any other.  Excluded: warmup=True
        # (the segmented loop has no discarded warm-up call, and
        # silently dropping a requested steady-state measurement would
        # be worse than losing the cost curve) — it falls back to the
        # plain path, which still traces the overall device_solve span
        # and routes decimation through solve_on_device's own
        # segmented call.
        decim_plan = None
        if hasattr(module, "decimation_plan_from_params"):
            decim_plan = module.decimation_plan_from_params(
                algo_def.params)
        probed = (
            (metrics_file is not None or serving)
            and not warmup
            and hasattr(module, "build_engine")
        )
        if checkpoint_dir is not None or probed \
                or recovery is not None \
                or (decim_plan is not None and not warmup):
            if not hasattr(module, "build_engine"):
                raise NotImplementedError(
                    f"Algorithm {algo_def.algo} has no segmentable "
                    "engine: checkpointing/recovery supports "
                    "maxsum-family solves"
                )
            from pydcop_tpu.resilience.checkpoint import (
                CheckpointManager,
                resume_from_checkpoint,
            )

            engine = module.build_engine(
                dcop, algo_def.params, mesh=mesh, n_devices=n_devices,
                shards=shards,
            )
            probe = None
            if probed:
                from pydcop_tpu.observability.engine_probe import (
                    EngineProbe,
                )

                # Snapshots fire at chunk boundaries; with
                # checkpointing the chunk size is the checkpoint
                # cadence, so the effective snapshot period is
                # max(checkpoint_every, metrics_every).
                probe = EngineProbe(
                    engine, metrics_path=metrics_file,
                    metrics_every=metrics_every or 1,
                )
            manager = None
            segment_cycles = None
            if checkpoint_dir is not None:
                manager = CheckpointManager(
                    checkpoint_dir, every=checkpoint_every or 100,
                    keep=checkpoint_keep,
                )
            elif decim_plan is not None:
                # Decimation rounds set the boundary cadence unless
                # an explicit metrics cadence asks for finer points.
                segment_cycles = (metrics_every
                                  or decim_plan.cycles_per_round)
            else:
                segment_cycles = metrics_every or 100
            if resume:
                res = resume_from_checkpoint(
                    engine, manager, max_cycles=max_cycles,
                    probe=probe, checkpoint_async=checkpoint_async,
                    recovery=recovery, decimation=decim_plan,
                )
            else:
                res = engine.run_checkpointed(
                    max_cycles=max_cycles, manager=manager,
                    segment_cycles=segment_cycles, probe=probe,
                    checkpoint_async=checkpoint_async,
                    recovery=recovery, decimation=decim_plan,
                )
            if probe is not None:
                from pydcop_tpu.observability.engine_probe import (
                    attach_result_metrics,
                )

                attach_result_metrics(res, probe)
        else:
            extra = {}
            if shards is not None and shards > 1:
                # Only the maxsum family accepts shards (gated
                # above); other modules never see the kwarg.
                extra["shards"] = shards
            res = module.solve_on_device(
                dcop, algo_def, max_cycles=max_cycles, mesh=mesh,
                n_devices=n_devices, warmup=warmup, **extra,
            )
        from pydcop_tpu.observability.trace import NOOP_SPAN, tracer

        # The answer's cost on the host, one Python call per
        # constraint: its own span under a file session.
        with (tracer.span("result_cost", "api",
                          n_constraints=len(dcop.constraints),
                          n_variables=len(dcop.variables))
              if tracer.enabled else NOOP_SPAN):
            cost, violations = dcop.solution_cost(res.assignment)
        return SolveResult(
            status="FINISHED" if res.converged else "TIMEOUT",
            assignment=res.assignment,
            cost=cost,
            violations=violations,
            cycles=res.cycles,
            time=res.time_s,
            compile_time=res.compile_time_s,
            total_time=time.perf_counter() - t0,
            metrics=res.metrics,
            backend="device",
        )

    if backend in ("thread", "process"):
        from pydcop_tpu.infrastructure.agent_algorithms import (
            has_agent_computation,
        )
        from pydcop_tpu.infrastructure.run import solve_with_agents

        # Reject before deployment rather than crashing mid-run on the
        # first build_computation call.
        if not has_agent_computation(algo_def.algo):
            raise NotImplementedError(
                f"Algorithm {algo_def.algo!r} has no agent-mode "
                "computation yet; use backend='device'"
            )

        # Bound non-terminating algorithms: without an explicit timeout a
        # maxsum/dsa run would block forever on the finished event.
        if timeout is None:
            timeout = 15.0
        return solve_with_agents(
            dcop, algo_def, distribution=distribution,
            timeout=timeout, max_cycles=max_cycles, mode=backend,
            ui_port=ui_port, collector=collector,
            collect_moment=collect_moment,
            collect_period=collect_period, delay=delay,
            fault_plan=fault_plan, health_config=health,
            metrics_file=metrics_file, metrics_every=metrics_every,
            metrics_live=serving,
        )

    raise ValueError(f"Unknown backend {backend!r}")
