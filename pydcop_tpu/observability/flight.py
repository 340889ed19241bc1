"""Always-on flight recorder: a bounded ring of trace events plus
anomaly postmortem bundles.

A crash, a ``RecoveryExhausted``, a poison request — by the time an
operator looks, the evidence is gone: file tracing is off in
production (it buffers everything), and the journal only says WHAT
was accepted, not what the process was doing.  The flight recorder is
the black box: a bounded per-process ring buffer that receives every
span/instant recorded through the tracer EVEN WHILE file tracing is
off (``tracer.set_flight``; sites guard on ``tracer.active``), so the
last-N events before an anomaly are always available.  Overhead is a
deque append per event at segment/request cadence; the per-message hot
paths stay gated on ``tracer.enabled`` so the ring holds signal, not
message spam.

On an anomaly **trigger** — guard trip, ``RecoveryExhausted``, shard
loss, admission-breaker open, poison-bin isolation, journal-replay
start, or a shutdown signal — the recorder dumps a **postmortem
bundle** to disk: the ring tail (the triggering instant is recorded
into the ring first, so it is always in the tail), a metrics-registry
snapshot, the ``/healthz`` payload, the ``PYDCOP_*`` / ``JAX_*`` /
``XLA_*`` environment, the efficiency rollup, and the pending-journal
summary when a serve journal is active.  Bundles are rate-limited (a trip storm produces one bundle,
not one per trip); ``pydcop debug bundle`` (or ``GET /debug/bundle``
on the telemetry endpoint) cuts one on demand.

Knobs: ``PYDCOP_FLIGHT_RECORDER`` — ``0`` disables, ``1``/unset
enables the default ring, any larger integer sets the ring size
(also ``--flight_recorder_events`` on ``pydcop serve`` / ``pydcop
solve``); ``PYDCOP_FLIGHT_DIR`` sets the bundle directory (default:
``<tmpdir>/pydcop_bundles_<uid>``, created 0700).  The default
recorder is installed at
import of :mod:`pydcop_tpu.observability`.
"""

import glob
import json
import logging
import os
import socket
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from pydcop_tpu.observability.trace import tracer

logger = logging.getLogger("pydcop.observability.flight")

DEFAULT_EVENTS = 2048
# Seconds between automatic bundle dumps: an anomaly storm (repeated
# guard trips walking the escalation ladder) yields one bundle whose
# tail shows the storm, not a bundle per trip.
MIN_DUMP_INTERVAL_S = 2.0
# Keep-last-N retention for the bundle directory (PYDCOP_FLIGHT_KEEP
# overrides; 0 = unlimited): every orchestrated restart cuts a
# fatal_signal bundle and trip storms add one per interval — without
# a cap a long-lived host fills its disk with routine shutdowns and
# buries the one bundle that matters.
DEFAULT_KEEP = 50

# Pending-journal summary source (the serve plane registers one while
# a journaled service runs) — mirrors the /healthz provider pattern.
_journal_provider: Optional[Callable[[], Dict[str, Any]]] = None
_provider_lock = threading.Lock()


def set_journal_provider(fn: Optional[Callable[[], Dict[str, Any]]]):
    """Register (or clear, with ``None``) the pending-journal summary
    source folded into postmortem bundles.  One slot, last writer
    wins — a process hosting several journaled services should clear
    with :func:`clear_journal_provider` so a stopping service never
    wipes a sibling's registration."""
    global _journal_provider
    with _provider_lock:
        _journal_provider = fn


def clear_journal_provider(fn: Callable[[], Dict[str, Any]]):
    """Clear the provider ONLY if ``fn`` is still the registered one
    (identity-guarded): a service stopping after a sibling registered
    must not strip the sibling's journal section from future
    bundles."""
    global _journal_provider
    with _provider_lock:
        if _journal_provider is fn:
            _journal_provider = None


def get_journal_provider():
    with _provider_lock:
        return _journal_provider


def ring_size_from_env(value: Optional[str] = None) -> Optional[int]:
    """Parse ``PYDCOP_FLIGHT_RECORDER``: ``0``/``off``/``false``/
    ``no``/``none``/``disabled`` or any value ≤ 0 → None (disabled —
    every plausible way an operator spells "off" must actually turn
    it off), ``1``/unset/unparsable garbage → the default ring size
    (fail-open: the black box should survive a typo'd size), N > 1 →
    a ring of N events."""
    if value is None:
        value = os.environ.get("PYDCOP_FLIGHT_RECORDER", "1")
    text = str(value).strip().lower()
    if text in ("0", "off", "false", "no", "none", "disabled"):
        return None
    try:
        n = int(text)
    except ValueError:
        return DEFAULT_EVENTS
    if n <= 0:
        return None
    return n if n > 1 else DEFAULT_EVENTS


def default_bundle_dir() -> str:
    """Per-user default under the tmpdir: a fixed shared path would
    let another local user pre-create it (blocking our bundle
    writes) or read bundles that carry env values and hostnames.
    The uid suffix plus 0700 creation (``write_bundle``) keeps each
    user's black box their own."""
    uid = getattr(os, "getuid", lambda: "u")()
    return os.environ.get(
        "PYDCOP_FLIGHT_DIR",
        os.path.join(tempfile.gettempdir(), f"pydcop_bundles_{uid}"))


class FlightRecorder:
    """The ring + the bundle writer.

    ``record`` is the tracer-side sink (one bounded-deque append —
    atomic under the GIL, so the hot path takes no lock); ``snapshot``
    retries on ``deque mutated during iteration`` so a bundle cut on
    a busy process never loses its event tail to a concurrent
    append; ``trigger`` records the anomaly as a trace instant
    (which lands in the ring via the tracer) and dumps a bundle,
    rate limited; ``bundle`` builds/writes one unconditionally.
    """

    def __init__(self, events: int = DEFAULT_EVENTS,
                 bundle_dir: Optional[str] = None,
                 min_interval_s: float = MIN_DUMP_INTERVAL_S,
                 keep: Optional[int] = None):
        self.ring: "deque" = deque(maxlen=max(int(events), 2))
        self.bundle_dir = bundle_dir or default_bundle_dir()
        self.min_interval_s = min_interval_s
        if keep is None:
            try:
                keep = int(os.environ.get("PYDCOP_FLIGHT_KEEP",
                                          DEFAULT_KEEP))
            except ValueError:
                keep = DEFAULT_KEEP
        self.keep = max(int(keep), 0)
        self._lock = threading.Lock()
        self._last_dump = 0.0
        self._seq = 0
        self.dumped = 0
        self.suppressed = 0
        self.last_bundle_path: Optional[str] = None

    # -- recording ------------------------------------------------------ #

    def record(self, event: Dict[str, Any]) -> None:
        """Tracer sink: append one event to the ring (bounded —
        eviction is the deque's maxlen, never a scan; deque appends
        are atomic under the GIL, so the hot path takes no lock)."""
        self.ring.append(event)

    def snapshot(self) -> List[Dict[str, Any]]:
        """The ring's current contents, oldest first.  Copying the
        deque while another thread appends raises ``RuntimeError:
        deque mutated during iteration`` — and bundles are cut
        exactly when the process is busy — so retry (the copy runs
        within one GIL slice; a retry virtually always wins) with a
        per-element fallback (deque indexing never raises on
        concurrent mutation).

        Events (and their args dicts) are shallow-copied: the tracer
        hands the ring LIVE dicts, and at least one site mutates its
        args after the event is recorded (``timed_jit_call`` attaches
        measured XLA cost post-exit).  Serializing the live dict from
        the bundle writer while that mutation lands would raise
        mid-``json.dump`` — losing the black-box bundle at exactly
        the anomaly it exists to capture."""
        for _ in range(64):
            try:
                return [self._copy_event(e) for e in list(self.ring)]
            except RuntimeError:
                continue
        return [self._copy_event(self.ring[i])
                for i in range(len(self.ring))]

    @staticmethod
    def _copy_event(event: Dict[str, Any]) -> Dict[str, Any]:
        for _ in range(8):
            try:
                out = dict(event)
                args = out.get("args")
                if isinstance(args, dict):
                    out["args"] = dict(args)
                return out
            except RuntimeError:  # dict mutated during the copy
                continue
        return {"name": event.get("name"), "copy_error": True}

    # -- anomaly path --------------------------------------------------- #

    def trigger(self, kind: str, force: bool = False,
                **info) -> Optional[str]:
        """Anomaly hook: record the triggering instant (into the ring
        AND the session trace, when one is on) and dump a postmortem
        bundle.  Rate-limited unless ``force``; returns the bundle
        path, or None when suppressed or the dump failed.  Never
        raises — the anomaly path must not add a second failure."""
        try:
            tracer.instant("anomaly", "flight", kind=kind, **info)
        except Exception:  # noqa: BLE001 — never break the caller
            logger.exception("flight trigger instant failed")
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_dump \
                    < self.min_interval_s:
                self.suppressed += 1
                return None
            self._last_dump = now
        try:
            return self.bundle(kind, info)
        except Exception:  # noqa: BLE001 — never break the caller
            logger.exception("postmortem bundle dump failed")
            return None

    def make_bundle(self, kind: str,
                    info: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
        """The bundle document (not yet written): ring tail +
        registry snapshot + /healthz payload + env + efficiency
        rollup + pending-journal summary.  Every section is best-effort — a
        broken registry must not cost the event tail."""
        bundle: Dict[str, Any] = {
            "version": 1,
            "kind": kind,
            "info": dict(info or {}),
            "unix": time.time(),
            "pid": os.getpid(),
            "host": socket.gethostname(),
            "ring_capacity": self.ring.maxlen,
            "events": self.snapshot(),
        }
        try:
            from pydcop_tpu.observability.metrics import registry

            bundle["metrics"] = registry.snapshot()
        except Exception as exc:  # noqa: BLE001
            bundle["metrics"] = {"error": str(exc)}
        try:
            from pydcop_tpu.observability.server import health_verdict

            bundle["healthz"] = health_verdict()
        except Exception as exc:  # noqa: BLE001
            bundle["healthz"] = {"error": str(exc)}
        bundle["env"] = {
            k: v for k, v in sorted(os.environ.items())
            if k.startswith(("PYDCOP_", "JAX_", "XLA_"))
        }
        # The efficiency rollup (observability/efficiency.py): the
        # postmortem's "was the device even doing useful work, and on
        # which backend" section — backend identity, attainment and
        # the where-the-time-went ledger at the moment of the
        # anomaly.
        try:
            from pydcop_tpu.observability.efficiency import tracker

            bundle["efficiency"] = tracker.rollup(top_n=5)
        except Exception as exc:  # noqa: BLE001
            bundle["efficiency"] = {"error": str(exc)}
        provider = get_journal_provider()
        if provider is not None:
            try:
                bundle["journal"] = provider()
            except Exception as exc:  # noqa: BLE001
                bundle["journal"] = {"error": str(exc)}
        return bundle

    def bundle(self, kind: str,
               info: Optional[Dict[str, Any]] = None) -> str:
        """Build + atomically write one bundle; returns its path."""
        return self.write_bundle(self.make_bundle(kind, info))

    def write_bundle(self, doc: Dict[str, Any]) -> str:
        """Atomically write a built bundle document; returns its
        path."""
        kind = doc.get("kind", "bundle")
        os.makedirs(self.bundle_dir, mode=0o700, exist_ok=True)
        with self._lock:
            self._seq += 1
            seq = self._seq
        name = (f"bundle_{kind}_{os.getpid()}_"
                f"{int(doc['unix'])}_{seq}.json")
        path = os.path.join(self.bundle_dir, name)
        tmp = f"{path}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, default=str)
        os.replace(tmp, path)
        self.dumped += 1
        self.last_bundle_path = path
        self._prune()
        try:
            from pydcop_tpu.observability.metrics import registry

            registry.counter(
                "pydcop_flight_bundles_total",
                "Postmortem bundles written, by trigger kind",
            ).inc(kind=kind)
        except Exception:  # noqa: BLE001 — accounting is best-effort
            pass
        logger.warning("postmortem bundle (%s): %s", kind, path)
        return path


    def _prune(self):
        """Keep-last-N retention over the bundle directory (mtime
        order, all processes' bundles — the directory is the unit an
        operator's disk cares about).  Best-effort: a pruning failure
        must never cost the bundle that was just written."""
        if not self.keep:
            return
        try:
            bundles = sorted(
                glob.glob(os.path.join(self.bundle_dir,
                                       "bundle_*.json")),
                key=lambda p: os.path.getmtime(p))
            for stale in bundles[:-self.keep]:
                os.remove(stale)
        except OSError:
            pass


def get_flight() -> Optional[FlightRecorder]:
    """The recorder currently attached to the process tracer."""
    return tracer.flight


def install(events: Optional[int] = None,
            bundle_dir: Optional[str] = None
            ) -> Optional[FlightRecorder]:
    """Attach a flight recorder to the process tracer (replacing any
    existing one).  ``events=None`` reads ``PYDCOP_FLIGHT_RECORDER``;
    explicit values use the SAME semantics (≤ 0 detaches, 1 means
    the default size — ``--flight_recorder_events 1`` and
    ``PYDCOP_FLIGHT_RECORDER=1`` must not disagree).  Returns the
    recorder, or None when disabled."""
    size = ring_size_from_env(
        None if events is None else str(int(events)))
    if size is None:
        tracer.set_flight(None)
        return None
    recorder = FlightRecorder(events=size, bundle_dir=bundle_dir)
    tracer.set_flight(recorder)
    return recorder


def trigger(kind: str, force: bool = False, **info) -> Optional[str]:
    """Module-level anomaly hook: no-op (None) when no recorder is
    attached, so call sites need no guard."""
    recorder = tracer.flight
    if recorder is None:
        return None
    return recorder.trigger(kind, force=force, **info)
