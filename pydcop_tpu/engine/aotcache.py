"""Disk-persisted AOT compile cache: cold-start killer for the fleet.

Every fresh ``pydcop serve`` worker used to pay full XLA compilation
for every structure it ever saw — multi-second time-to-first-result
per structure per process, multiplied by the replica count (ROADMAP
open item 2).  This module wires up JAX's on-disk compilation cache so
a compiled executable persists ACROSS processes: the first worker that
compiles a structure's program writes it to ``cache_dir``, and every
later worker (a fresh replica, a crash-restarted one, the next bench
round) deserializes it in tens of milliseconds instead of recompiling.

**Where the cache lives** (:func:`resolve_cache_dir`, the only place
that decides).  The directory is part of every entry's key, so a
directory that moves never hits; it is therefore placed from outside
or fixed, never derived from ``tempfile``, a pid or the time:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own setting stands and no
   code here sets another (``--compile_cache_dir`` /
   ``PYDCOP_COMPILE_CACHE_DIR`` are ignored, with one log line);
2. otherwise an explicit directory (``pydcop serve
   --compile_cache_dir``, ``api.serve(compile_cache_dir=...)``, or
   ``PYDCOP_COMPILE_CACHE_DIR`` — how spawned fleet workers inherit
   the router's directory);
3. otherwise ``<checkout>/.cache/jax`` (:data:`DEFAULT_DIR`, derived
   from the package location, git-ignored).

``pydcop solve``, ``pydcop serve`` and ``chip_smoke.py`` all call
:func:`enable_persistent_compile_cache` before their first jit.

**The set-before-jit latch.**  JAX latches its cache configuration on
the FIRST jit compilation: setting ``jax_compilation_cache_dir`` after
any jit has run silently no-ops, because the process-wide cache object
was already initialized without a persistent backing store.
:func:`enable_persistent_compile_cache` therefore always calls
``reset_cache()`` after updating the config — safe before the first
jit, REQUIRED after it.

**Keying.**  JAX keys cache entries by the serialized HLO + compile
options + backend — a superset of our structure bin key
(serving/binning.bin_key): two same-structure requests lower to the
same HLO (cost tables are runtime operands, never constants), so the
structure key's equivalence classes map onto disk-cache hits.  The
cache composes with the PR-3 layout cache (host-side arrays) and the
per-process jit cache (live executables): layout cache saves host
compile work, this cache saves XLA compile work across processes, the
jit cache saves both within one.

**Hit accounting.**  JAX announces cache activity on its monitoring
bus; we subscribe once and keep process-wide counters so (a) tests and
the bench can assert a fresh process genuinely skipped compilation and
(b) ``timed_jit_call`` can say what a first dispatch did: its ``compile``
seconds are those XLA spent compiling plus those spent reading programs
off the disk (milliseconds for a call whose executables ALL came off
the disk cache), never the whole first-call interval
(:func:`dispatch_compile`).  The serve_cold_start bench leg and the
fleet docs (docs/serving.md "Persistent compile cache") build on
exactly this accounting.
"""

import logging
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

from pydcop_tpu.observability.trace import tracer

logger = logging.getLogger("pydcop.engine.aotcache")

ENV_DIR = "PYDCOP_COMPILE_CACHE_DIR"
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.cache/jax: this file is <checkout>/pydcop_tpu/engine/.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")

# JAX monitoring bus keys (jax/_src/compiler.py + compilation_cache.py).
_EVT_HIT = "/jax/compilation_cache/cache_hits"
_EVT_MISS = "/jax/compilation_cache/cache_misses"
_DUR_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_DUR_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# The stages of one jit dispatch that is not in the process's own jit
# cache (jax/_src/dispatch.py).  JAX reports each as it ends, on the
# dispatching thread.  The last one wraps ``compile_or_get_cached``,
# so it also fires after a load from the disk cache.
_DUR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_DUR_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_DUR_BACKEND = "/jax/core/compile/backend_compile_duration"
# Stage -> span name, recorded under a file session (``jax_trace`` /
# ``jax_lower`` / ``xla_compile`` / ``xla_cache_load``, cat engine).
_STAGE_SPANS = {_DUR_TRACE: "jax_trace", _DUR_LOWER: "jax_lower",
                _DUR_BACKEND: "xla_compile",
                _DUR_RETRIEVAL: "xla_cache_load"}
# While it traces a program JAX also reports every inner ``jit`` it
# meets (about a hundred per MaxSum program, microseconds each, all
# inside the outer stage's interval): no span for those.
_MIN_STAGE_SPAN_S = 1e-4

_lock = threading.Lock()
_state: Dict[str, Any] = {
    "enabled": False,
    "dir": None,
    "hits": 0,
    "misses": 0,
    "retrieval_s": 0.0,
    "saved_s": 0.0,
    "compiles": 0,
    "compile_s": 0.0,
    "listeners_installed": False,
}
# Per thread: its own tally of the counters, and when a disk-cache
# load last ended on it (the backend stage that contains that moment
# compiled nothing).
_thread = threading.local()


_COUNTERS = ("hits", "misses", "retrieval_s", "saved_s", "compiles",
             "compile_s")


def _bump(key: str, amount) -> None:
    """Add to the process-wide counter and to the calling thread's
    own.  JAX compiles (and reports) on the dispatching thread, so
    the thread's tally is what ONE dispatch did, whatever the
    service's background compiler does meanwhile on its thread."""
    with _lock:
        _state[key] += amount
    tally = getattr(_thread, "tally", None)
    if tally is None:
        tally = _thread.tally = dict.fromkeys(_COUNTERS, 0)
    tally[key] += amount


def _on_event(event: str, **kwargs) -> None:
    if event == _EVT_HIT:
        _bump("hits", 1)
    elif event == _EVT_MISS:
        _bump("misses", 1)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _DUR_SAVED:
        _bump("saved_s", float(duration))
        return
    name = _STAGE_SPANS.get(event)
    if name is None:
        return
    duration = float(duration)
    # JAX reports a stage as it ends, so it began ``duration`` ago.
    now = time.perf_counter()
    if event == _DUR_RETRIEVAL:
        _thread.loaded_at = now
        _bump("retrieval_s", duration)
    elif event == _DUR_BACKEND:
        loaded_at = getattr(_thread, "loaded_at", None)
        _thread.loaded_at = None
        if loaded_at is not None and loaded_at >= now - duration:
            # The program came off the disk inside this stage, and
            # the load was counted and recorded when it ended.
            return
        _bump("compiles", 1)
        _bump("compile_s", duration)
    if tracer.enabled and duration >= _MIN_STAGE_SPAN_S:
        # Back-dated, under the calling thread's open span (the
        # dispatch's ``engine_call``).
        tracer.complete(name, "engine", t0=now - duration, t1=now,
                        parent=tracer.current_span_id(),
                        fun=kwargs.get("fun_name"))


def install_listeners() -> None:
    """Subscribe to JAX's monitoring bus (once per process).  The
    persistent cache need not be on: ``timed_jit_call`` counts the
    compiles of a process that has none the same way."""
    with _lock:
        if _state["listeners_installed"]:
            return
        _state["listeners_installed"] = True
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def resolve_cache_dir(cache_dir: Optional[str] = None
                      ) -> Tuple[str, str]:
    """``(directory, source)`` of the persistent cache, by the order
    in the module docstring; ``source`` is ``"jax_env"``,
    ``"explicit"`` or ``"default"``."""
    explicit = cache_dir or os.environ.get(ENV_DIR) or None
    from_jax = os.environ.get(JAX_ENV_DIR)
    if from_jax:
        if explicit and os.path.abspath(explicit) != \
                os.path.abspath(from_jax):
            logger.warning(
                "%s=%s stands; ignoring compile cache dir %s",
                JAX_ENV_DIR, from_jax, explicit)
        return os.path.abspath(from_jax), "jax_env"
    if explicit:
        return os.path.abspath(explicit), "explicit"
    return DEFAULT_DIR, "default"


def enable_persistent_compile_cache(
        cache_dir: Optional[str] = None) -> str:
    """Turn JAX's persistent compilation cache on at the directory
    :func:`resolve_cache_dir` gives and make the setting stick (the
    set-before-jit latch: see module docstring).  Returns the
    directory.

    Call this ONCE, as early as possible — in a serve worker that
    means at spawn.  Calling after a jit still works (``reset_cache``
    drops the latched in-memory cache so the next compile re-reads
    the config), but every executable compiled before the call was
    never written to disk.

    JAX's persist thresholds (1 s of compile time, a minimum entry
    size) are lowered to none so the small programs the serve plane
    compiles are cached too — on a fleet the cache exists precisely
    to make tiny per-structure compiles free for the second process.
    """
    cache_dir, source = resolve_cache_dir(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if source != "jax_env":
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # THE LATCH: config alone is a silent no-op once any jit ran —
    # the process-wide cache object must be rebuilt to pick the
    # directory up.  Safe (idempotent) before the first jit.
    compilation_cache.reset_cache()
    install_listeners()
    with _lock:
        _state["enabled"] = True
        _state["dir"] = cache_dir
    logger.info("persistent compile cache at %s (%s)", cache_dir,
                source)
    return cache_dir


def enabled() -> bool:
    with _lock:
        return bool(_state["enabled"])


def counters(thread: bool = False) -> Dict[str, float]:
    """Monotone counter snapshot, of the process or (``thread``) of
    the calling thread alone: delta two of the thread's around a
    dispatch to attribute ITS activity (:func:`dispatch_compile`).
    ``hits`` / ``misses`` / ``retrieval_s`` / ``saved_s`` are the
    disk cache's (a miss is a program compiled AND written);
    ``compiles`` / ``compile_s`` count every program XLA compiled,
    written or not, cache on or off."""
    if thread:
        return dict(getattr(_thread, "tally", None)
                    or dict.fromkeys(_COUNTERS, 0))
    with _lock:
        return {key: _state[key] for key in _COUNTERS}


def dispatch_compile(elapsed_s: float, before: Dict[str, float],
                     after: Dict[str, float]) -> Dict[str, float]:
    """What one jit dispatch did besides run, from the calling
    thread's counter snapshots around it (``counters(thread=True)``,
    listeners installed): ``xla_compiles`` (programs XLA compiled),
    ``cache_loads`` (programs read from the disk cache) and
    ``compile_s``, the seconds those took, clamped into ``[0,
    elapsed]``: 0 when neither happened, the retrieval wall when the
    programs all came off the disk (the serve_cold_start acceptance,
    "compile ≈ 0 with a warm cache", is THIS number).  Tracing,
    lowering and the run itself are never in it."""
    spent = ((after["compile_s"] - before["compile_s"])
             + (after["retrieval_s"] - before["retrieval_s"]))
    return {
        "xla_compiles": int(after["compiles"] - before["compiles"]),
        "cache_loads": int(after["hits"] - before["hits"]),
        "compile_s": min(max(spent, 0.0), max(elapsed_s, 0.0)),
    }


def disk_stats(directory: Optional[str]) -> Dict[str, int]:
    """Entry count + byte size of a cache DIRECTORY, independent of
    this process's cache state.  The fleet router never jits, so its
    own ``enabled()`` stays False — but it still owns the shared cache
    dir its workers populate, and reports how warm the fleet's disk
    cache is (how much compile work a scale-up prewarm can skip) from
    here."""
    entries = 0
    size = 0
    if directory:
        try:
            for name in os.listdir(directory):
                if name.endswith("-cache"):
                    entries += 1
                try:
                    size += os.path.getsize(
                        os.path.join(directory, name))
                except OSError:
                    pass
        except OSError:
            pass
    return {"entries": entries, "bytes": size}


def stats() -> Dict[str, Any]:
    """Operator-facing snapshot: config + counters + on-disk size
    (surfaced in /stats on every worker and in the router's fleet
    stats)."""
    out: Dict[str, Any] = dict(counters())
    with _lock:
        out["enabled"] = _state["enabled"]
        out["dir"] = _state["dir"]
    out.update(disk_stats(out["dir"]))
    return out
