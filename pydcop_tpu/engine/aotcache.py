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

``pydcop solve``, ``pydcop serve``, ``chip_smoke.py`` and ``bench.py``
all call :func:`enable_persistent_compile_cache` before their first
jit.

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
(b) ``timed_jit_call`` can split a cold dispatch honestly: a cold call
whose executables ALL came off the disk cache did not compile — its
ledger ``compile`` component is the measured cache-retrieval wall
(milliseconds), not the whole first-call interval
(:func:`split_cold_call`).  The serve_cold_start bench leg and the
fleet docs (docs/serving.md "Persistent compile cache") build on
exactly this accounting.
"""

import logging
import os
import threading
from typing import Any, Dict, Optional, Tuple

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

_lock = threading.Lock()
_state: Dict[str, Any] = {
    "enabled": False,
    "dir": None,
    "hits": 0,
    "misses": 0,
    "retrieval_s": 0.0,
    "saved_s": 0.0,
    "listeners_installed": False,
}


def _on_event(event: str, **kwargs) -> None:
    if event == _EVT_HIT:
        with _lock:
            _state["hits"] += 1
    elif event == _EVT_MISS:
        with _lock:
            _state["misses"] += 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _DUR_RETRIEVAL:
        with _lock:
            _state["retrieval_s"] += float(duration)
    elif event == _DUR_SAVED:
        with _lock:
            _state["saved_s"] += float(duration)


def _install_listeners() -> None:
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
    _install_listeners()
    with _lock:
        _state["enabled"] = True
        _state["dir"] = cache_dir
    logger.info("persistent compile cache at %s (%s)", cache_dir,
                source)
    return cache_dir


def enabled() -> bool:
    with _lock:
        return bool(_state["enabled"])


def counters() -> Dict[str, float]:
    """Monotone counter snapshot (hits/misses/retrieval_s/saved_s) —
    delta two snapshots around a dispatch to attribute ITS cache
    activity (:func:`split_cold_call`)."""
    with _lock:
        return {
            "hits": _state["hits"],
            "misses": _state["misses"],
            "retrieval_s": _state["retrieval_s"],
            "saved_s": _state["saved_s"],
        }


def split_cold_call(elapsed_s: float, before: Dict[str, float],
                    after: Dict[str, float]) -> Optional[float]:
    """Honest ``compile`` seconds for one COLD jit dispatch given the
    counter snapshots around it.

    Returns the compile component to report, or None to keep the
    caller's default convention (cold interval == compile):

    - every executable the dispatch needed came off the disk cache
      (hits advanced, misses did not) → the dispatch did not compile;
      its compile component is the measured retrieval wall, clamped
      into ``[0, elapsed]`` — the serve_cold_start acceptance
      ("compile ≈ 0 with a warm cache") is THIS number;
    - any miss, or no cache activity at all (cache disabled,
      measurement unavailable) → None: the conservative whole-interval
      convention stands.
    """
    if not enabled():
        return None
    d_hits = after["hits"] - before["hits"]
    d_misses = after["misses"] - before["misses"]
    if d_hits <= 0 or d_misses > 0:
        return None
    retrieval = max(after["retrieval_s"] - before["retrieval_s"], 0.0)
    return min(retrieval, max(elapsed_s, 0.0))


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
