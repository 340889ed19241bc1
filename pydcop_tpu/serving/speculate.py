"""Speculative envelope compilation (ISSUE 18 tentpole piece b).

The serving plane's compile stalls are concentrated on *predictable*
programs: a structure that arrived once will arrive again (the
affinity router already banks on it), and when it does it will batch
— the flush will pad the group to the next bin rung and dispatch a
program whose shape is fully determined by (envelope, bin size,
solver statics).  Nothing about that program needs a live request to
exist: the stacked input's avals can be derived abstractly with
``jax.eval_shape`` (zero device work), and the executable can be
built with compile-only AOT lowering
(``_batched_maxsum_solve.lower(...).compile()``) which populates the PR-15
persistent compile cache on disk WITHOUT touching jit's dispatch
cache — so when the real traffic arrives, the "cold" jit call
resolves as a fast disk hit instead of a multi-hundred-ms XLA build
on the request path.

Discipline (battery-asserted):

* all compilation runs on ONE low-priority daemon thread, never the
  device-owning scheduler thread — every compile record carries its
  ``thread_ident`` so the battery can assert the separation;
* compile-only lowering only: the worker never calls the jitted
  entry point, never executes a program, and never touches
  ``engine.batch._warm`` (marking a speculated key warm would route
  the first REAL dispatch through the warm launch path with no
  compile attribution — the ledger would lie);
* the job queue is bounded (drops are counted, not blocked on) so a
  diverse stream cannot grow an unbounded compile backlog.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..engine import batch as engine_batch
from ..engine.compile import CompiledFactorGraph, FactorBucket
from ..observability.trace import tracer
from . import binning

log = logging.getLogger("pydcop_tpu.serving.speculate")

# Bin rungs speculated ahead of the observed group size: when a
# structure shows up at size n, the next flushes will most likely pad
# it to the next rung(s) up.  Two rungs ahead covers a doubling burst
# without flooding the queue on every observation.
_RUNGS_AHEAD = 2


def _padded_avals(graph, env: binning.Envelope) -> CompiledFactorGraph:
    """ShapeDtypeStruct skeleton of ``graph`` padded to ``env`` —
    every padded shape is fully determined by the envelope
    (``engine.batch.pad_graph_to_envelope`` docstring), so the
    skeleton can be built WITHOUT the numpy padding work and without
    a single device buffer.  Shape parity with the real padding path
    is battery-asserted (the speculated program key must equal the
    live ``_prepare_stacked`` key or every speculation misses)."""
    import numpy as np

    cost_dtype = graph.var_costs.dtype
    by_arity = {b.arity: b.costs.dtype for b in graph.buckets}
    buckets = tuple(
        FactorBucket(
            costs=jax.ShapeDtypeStruct(
                (rows,) + (env.d_env,) * arity,
                by_arity.get(arity, cost_dtype)),
            var_ids=jax.ShapeDtypeStruct((rows, arity), np.int32),
        )
        for arity, rows in env.rows
    )
    return CompiledFactorGraph(
        var_costs=jax.ShapeDtypeStruct(
            (env.v_env + 1, env.d_env), cost_dtype),
        var_valid=jax.ShapeDtypeStruct(
            (env.v_env + 1, env.d_env), np.bool_),
        buckets=buckets,
    )


def _statics_from_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The jit static-arg dict, derived EXACTLY like
    ``engine.batch._prepare_stacked`` does — key equality with the
    live dispatch path is the whole point."""
    damping_nodes = params.get("damping_nodes", "vars")
    return dict(
        max_cycles=params["max_cycles"],
        damping=params["damping"],
        damp_vars=damping_nodes in ("vars", "both"),
        damp_factors=damping_nodes in ("factors", "both"),
        stability=params["stability"],
        prune=bool(params.get("prune", 0)),
    )


class _Job:
    __slots__ = ("graph_avals", "env", "bs", "statics")

    def __init__(self, graph_avals, env, bs, statics):
        self.graph_avals = graph_avals
        self.env = env
        self.bs = bs
        self.statics = statics


class SpeculativeCompiler:
    """Arrival-histogram-driven background compiler for envelope
    programs.  ``observe()`` is called by the flush planner (cheap:
    histogram update + bounded enqueue); one daemon worker drains the
    queue with compile-only AOT lowering."""

    def __init__(self, bin_sizes: Tuple[int, ...] = (1, 2, 4, 8, 16),
                 max_queue: int = 16):
        self.bin_sizes = tuple(sorted(set(int(b) for b in bin_sizes)))
        self._queue: "queue.Queue[Optional[_Job]]" = queue.Queue(
            maxsize=max_queue)
        self._lock = threading.Lock()
        # Per-(envelope, statics) arrival counts — the structure
        # histogram the predictions rank on.
        self.histogram: Dict[tuple, int] = {}
        # str(program_key) of every executable this speculator built
        # (or queued — dedupe is at enqueue time so a slow compile
        # does not get queued twice).
        self._seen_keys: set = set()
        self.compiled_keys: set = set()
        self.records: List[Dict[str, Any]] = []
        self.compiled_total = 0
        self.dropped_total = 0
        self.hit_total = 0
        self.failed_total = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ----------------------------------------------------------- #
    # lifecycle

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._worker, name="pydcop-spec-compile",
            daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        """Stop the worker and wait for it: it ends after the compile
        in flight, which cannot be cancelled and takes seconds on a
        TPU.  A worker abandoned mid-compile is still calling into
        JAX when the interpreter tears down, and that segfaults (seen
        at the end of a ``chip_smoke.py`` run on the chip, PR 22)."""
        if self._thread is None:
            return
        self._stop.set()
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            log.warning("speculative compile thread did not stop in "
                        "%.1fs", timeout)
        self._thread = None

    # ----------------------------------------------------------- #
    # planner-side API (scheduler thread — must stay cheap)

    def observe(self, graph, env: binning.Envelope,
                params: Dict[str, Any], count: int) -> None:
        """Record one envelope group's arrival and enqueue the
        programs its structure will plausibly need next: the observed
        envelope at the next ``_RUNGS_AHEAD`` bin rungs above
        ``count``, plus the current rung itself (a recurring solo
        structure's next arrival is the most likely program of all).
        Two skeletons per prediction: the graph's RAW shapes (what an
        exact same-structure bin dispatches — ``run_stacked`` with
        ``envelope=None`` stacks the compiled graphs as-is) and the
        envelope-padded shapes (what a heterogeneous packed group
        dispatches); an exact-fit graph collapses both to one key.
        Derives avals from ``graph`` (shape skeletons only) so the
        jobs hold no device buffers."""
        statics = _statics_from_params(params)
        hkey = (env, tuple(sorted(statics.items())))
        with self._lock:
            self.histogram[hkey] = self.histogram.get(hkey, 0) + 1
        try:
            skeletons = [
                jax.tree_util.tree_map(
                    lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                               if hasattr(x, "shape")
                               and hasattr(x, "dtype") else x),
                    graph),
                _padded_avals(graph, env),
            ]
        except Exception:
            return  # never raise into the flush planner
        sizes: List[int] = []
        ahead = 0
        for b in self.bin_sizes:
            if b >= max(int(count), 1):
                sizes.append(b)
                ahead += 1
                if ahead > _RUNGS_AHEAD:
                    break
        for bs in sizes:
            for avals in skeletons:
                self._enqueue(_Job(avals, env, bs, statics))

    def _enqueue(self, job: _Job) -> None:
        try:
            key = self._program_key(job)
        except Exception:  # aval derivation failed — never raise into
            return         # the flush planner
        skey = str(key)
        with self._lock:
            if skey in self._seen_keys:
                return
            if key in engine_batch._warm:
                # Already live-compiled: nothing to speculate.
                self._seen_keys.add(skey)
                return
            self._seen_keys.add(skey)
        try:
            self._queue.put_nowait(job)
        except queue.Full:
            with self._lock:
                self.dropped_total += 1
                self._seen_keys.discard(skey)

    # ----------------------------------------------------------- #
    # worker side

    @staticmethod
    def _stacked_avals(job: _Job):
        """Abstract shapes of the stacked dispatch input — pure
        ``eval_shape`` over the already-padded skeleton, zero device
        work (asserted by the battery via the compile records' thread
        idents + compile_only flag)."""
        return jax.eval_shape(
            lambda g: engine_batch.stack_graphs([g] * job.bs),
            job.graph_avals,
        )

    def _program_key(self, job: _Job) -> tuple:
        stacked = self._stacked_avals(job)
        return (
            "maxsum_batch", job.bs,
            engine_batch._shape_signature(stacked),
            tuple(sorted(job.statics.items())),
        )

    def _worker(self) -> None:
        while not self._stop.is_set():
            try:
                job = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            if job is None:
                break
            try:
                self._compile_one(job)
            except Exception as exc:
                with self._lock:
                    self.failed_total += 1
                log.debug("speculative compile failed: %s", exc)

    def _compile_one(self, job: _Job) -> None:
        stacked = self._stacked_avals(job)
        key = (
            "maxsum_batch", job.bs,
            engine_batch._shape_signature(stacked),
            tuple(sorted(job.statics.items())),
        )
        if key in engine_batch._warm:
            return
        t0 = time.perf_counter()
        with tracer.span("speculative_compile", cat="serve",
                         key=str(key)[:120], compile_only=True,
                         thread=threading.get_ident()):
            # Compile-only AOT path: builds the executable (and
            # populates the persistent disk cache when enabled) but
            # NEVER dispatches — the device stays with the scheduler
            # thread.
            engine_batch._batched_maxsum_solve.lower(
                stacked, **job.statics).compile()
        wall = time.perf_counter() - t0
        with self._lock:
            self.compiled_total += 1
            self.compiled_keys.add(str(key))
            self.records.append({
                "key": str(key),
                "thread_ident": threading.get_ident(),
                "wall_s": round(wall, 6),
                "compile_only": True,
            })

    # ----------------------------------------------------------- #
    # completion-side API (hit accounting + stats)

    def record_hit(self, program_key: str) -> bool:
        """Called by the service when a cold dispatch's program key
        matches a speculated executable — the compile the request
        path just skipped (disk hit instead of XLA build)."""
        with self._lock:
            if program_key in self.compiled_keys:
                self.hit_total += 1
                return True
        return False

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "speculative_compiles_total": self.compiled_total,
                "speculative_hits_total": self.hit_total,
                "speculative_dropped_total": self.dropped_total,
                "speculative_failed_total": self.failed_total,
                "queued": self._queue.qsize(),
                "structures_observed": len(self.histogram),
            }
