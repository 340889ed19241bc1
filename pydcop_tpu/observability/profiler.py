"""XLA cost attribution: measured flops/bytes/peak-HBM per compiled
program, keyed by the engine's jit cache key.

The roofline numbers in ``engine/roofline.py`` are *models* — hand
derivations from bucket shapes that assume perfect fusion.  XLA itself
knows better: every compiled executable carries a ``cost_analysis()``
(flops, bytes accessed, transcendentals) and a ``memory_analysis()``
(argument/output/temp sizes — the peak-HBM story) computed from the
optimized HLO.  This module captures both per compiled segment and
feeds them to the metrics registry, the ``jit_compile`` trace span,
``DeviceRunResult.metrics`` and (via ``roofline_report(measured=...)``)
the benchmark's utilization claims — measured, not estimated.

Capture discipline: the running jit cache must never be disturbed, so
the profiler lowers the SAME jitted callable against
``ShapeDtypeStruct`` avals (no device buffers touched — safe even when
the arguments were donated) and compiles a throwaway AOT executable
purely for its analysis tables.  That is one extra compile per cache
key, paid only while profiling is enabled; the capture happens OUTSIDE
the engine's timed interval so measured rates are unpolluted.  Backends
that return nothing (or raise — the analysis API is not part of JAX's
stability contract) produce an explicit ``{"available": False,
"reason": ...}`` marker instead of silently missing data, so a reader
can distinguish "not profiled" from "profiled, backend said nothing".

Enablement: :class:`~pydcop_tpu.observability.ObservabilitySession`
turns the profiler on for observed solves; ``PYDCOP_XLA_PROFILE=1``
forces it on, ``=0`` forces it off regardless of session.
"""

import os
import threading
import time
from typing import Any, Dict, Optional

_FLOPS_KEYS = ("flops",)
_BYTES_KEYS = ("bytes accessed",)


def _env_override() -> Optional[bool]:
    raw = os.environ.get("PYDCOP_XLA_PROFILE")
    if raw is None:
        return None
    return raw not in ("0", "false", "no", "")


def key_str(key: Any) -> str:
    """Canonical string form of a jit cache key (used as the metrics
    label and the ``DeviceRunResult.metrics['xla_cost']`` key)."""
    return str(key)


class XlaCostProfiler:
    """Captures per-executable XLA cost/memory analysis, keyed by the
    engine's jit cache key.

    ``capture`` is called by ``timed_jit_call`` on every COLD dispatch
    (once per cache key); entries accumulate in :attr:`entries` until
    :meth:`clear`.  All failures are folded into unavailable markers —
    profiling must never break a solve.
    """

    def __init__(self):
        self._enabled = False
        self._lock = threading.Lock()
        self.entries: Dict[str, Dict[str, Any]] = {}

    @property
    def enabled(self) -> bool:
        env = _env_override()
        return self._enabled if env is None else env

    @enabled.setter
    def enabled(self, value: bool):
        self._enabled = bool(value)

    # -- capture -------------------------------------------------------- #

    def capture(self, key: Any, fn, args: tuple) -> Dict[str, Any]:
        """Lower+compile ``fn`` against the avals of ``args`` and
        record its cost/memory analysis under ``key``.

        Never raises; returns the entry (an unavailable marker when
        the backend yields nothing).  Idempotent per key — a re-cold
        dispatch (fresh engine, same key string) overwrites with
        identical data.
        """
        t0 = time.perf_counter()
        try:
            entry = self._analyze(fn, args)
        except Exception as exc:  # noqa: BLE001 — analysis API unstable
            entry = {
                "available": False,
                "reason": f"{type(exc).__name__}: {exc}"[:200],
            }
        entry["capture_s"] = round(time.perf_counter() - t0, 6)
        skey = key_str(key)
        with self._lock:
            self.entries[skey] = entry
        self._export_metrics(skey, entry)
        return entry

    @staticmethod
    def _analyze(fn, args: tuple) -> Dict[str, Any]:
        import jax

        def aval(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        # The batched/lane dispatch paths wrap their jitted callable
        # in functools.partial to bind static kwargs; partials have no
        # ``.lower``, so unwrap and re-apply the bound arguments —
        # without this the serving hot path (exactly where efficiency
        # attainment matters most) never got a cost entry.
        kwargs: Dict[str, Any] = {}
        target = fn
        if not hasattr(target, "lower"):
            inner = getattr(fn, "func", None)
            if inner is not None and hasattr(inner, "lower"):
                args = tuple(getattr(fn, "args", ()) or ()) + args
                kwargs = dict(getattr(fn, "keywords", {}) or {})
                target = inner
        compiled = target.lower(
            *jax.tree_util.tree_map(aval, args), **kwargs).compile()
        cost = compiled.cost_analysis()
        # Per-device list on some versions, plain dict on others.
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        entry: Dict[str, Any] = {}
        if isinstance(cost, dict):
            for k in _FLOPS_KEYS:
                if k in cost:
                    entry["flops"] = float(cost[k])
                    break
            for k in _BYTES_KEYS:
                if k in cost:
                    entry["bytes_accessed"] = float(cost[k])
                    break
            if "transcendentals" in cost:
                entry["transcendentals"] = float(cost["transcendentals"])
        mem = None
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001
            mem = None
        if mem is not None:
            for attr, out in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("generated_code_size_in_bytes", "code_bytes"),
            ):
                val = getattr(mem, attr, None)
                if val is not None:
                    entry[out] = float(val)
            # Peak device footprint of one dispatch: live arguments +
            # outputs + transient scratch.  (Donation aliases argument
            # and output buffers, so this is an upper bound.)
            peak = sum(entry.get(k, 0.0) for k in
                       ("argument_bytes", "output_bytes", "temp_bytes"))
            if peak:
                entry["peak_bytes"] = peak
        if not entry:
            return {
                "available": False,
                "reason": "backend returned no cost/memory analysis",
            }
        entry["available"] = True
        return entry

    def _export_metrics(self, skey: str, entry: Dict[str, Any]):
        from pydcop_tpu.observability.metrics import registry

        # (The trace holds the same numbers as the ``xla_cost`` arg
        # of the first dispatch's span: engine/runner.timed_jit_call.)
        # Key-labeled series are unbounded across engines, so — like
        # the runner's per-key jit accounting — they are opt-in
        # detail: only recorded while metrics were actually requested
        # (registry.active).  A bench/PYDCOP_XLA_PROFILE=1 run that
        # never activates the registry still gets its entries through
        # DeviceRunResult.metrics, without leaking stale samples into
        # a later solve's .prom dump.
        if not registry.active:
            return
        if entry.get("available"):
            if entry.get("flops"):
                registry.counter(
                    "pydcop_xla_flops_total",
                    "XLA-measured flops of compiled programs "
                    "(one increment per cold compile)",
                ).inc(entry["flops"], key=skey)
            if entry.get("bytes_accessed"):
                registry.counter(
                    "pydcop_xla_bytes_total",
                    "XLA-measured bytes accessed by compiled programs",
                ).inc(entry["bytes_accessed"], key=skey)
            if entry.get("peak_bytes"):
                registry.gauge(
                    "pydcop_xla_peak_bytes",
                    "Peak device bytes (args+outputs+temps) of a "
                    "compiled program",
                ).set(entry["peak_bytes"], key=skey)
        else:
            registry.counter(
                "pydcop_xla_analysis_unavailable_total",
                "Cold compiles whose backend returned no XLA "
                "cost/memory analysis",
            ).inc()

    # -- readback ------------------------------------------------------- #

    def get(self, key: Any) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self.entries.get(key_str(key))

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self.entries.items()}

    def clear(self):
        with self._lock:
            self.entries = {}


profiler = XlaCostProfiler()


def get_profiler() -> XlaCostProfiler:
    return profiler
