"""Device timing: close every window on a completed result.

JAX dispatch is asynchronous: a jitted call returns device futures
almost immediately, so a wall-clock window that does not wait for the
result measures the enqueue, not the run.  Two tools:

- :func:`sync` forces completion by fetching the smallest output
  buffer to the host.  Bytes cannot be fetched before they exist, on
  any backend, so this is a barrier that does not depend on how a
  platform implements ``jax.block_until_ready`` (``chip_smoke.py``
  prints both on the same dispatched program, so whether the plain
  idiom can replace this one is decided from a chip reading — ROADMAP
  Queue 3, "`engine/timing.py`'s `sync`").  Include the fetch in the timed window and the
  number is end to end.
- :func:`marginal_seconds_per_cycle` removes the per-call constant
  (enqueue + sync + fetch, independent of program length) by timing
  the same program at two cycle counts and taking the slope.  This is
  the chip's steady-state rate — the number roofline utilization
  claims must be based on, since the constant says nothing about HBM
  streaming.
"""

import os
import time
from typing import Any, Callable, Tuple

import jax
import numpy as np


def sync(out: Any) -> Any:
    """Block until ``out`` (any pytree of jax arrays) has actually been
    computed, then return it unchanged.

    Fetches the smallest leaf to the host: all leaves of one executed
    program materialize together, and a host fetch cannot complete
    before the buffer exists, whatever the platform.
    Cost: one round-trip plus the smallest leaf's transfer (pick your
    outputs so a scalar — cycle counter, convergence flag — is among
    them, which every ops.run_* in this package does).

    PRECONDITION (API contract): every array leaf of ``out`` must be
    an output of the SAME dispatched program (or of its dependency
    chain).  Fetching one leaf proves only *that* program finished; a
    pytree assembled from independent dispatches would leave the
    other programs in flight and silently turn the caller's timing
    back into an enqueue time — exactly the artifact this module
    exists to prevent.  Every call site in this package passes a
    single program's output pytree; keep it that way.

    Debug assertion path: ``PYDCOP_SYNC_DEBUG=1`` fetches EVERY leaf
    (one barrier per distinct buffer source, a true sync regardless
    of the precondition).  Run a suspicious measurement under this
    flag: if the number changes materially, a call site is violating
    the single-program contract.
    """
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "dtype")]
    if not leaves:
        return out
    if os.environ.get("PYDCOP_SYNC_DEBUG") == "1":
        for leaf in leaves:
            np.asarray(jax.device_get(leaf))
        return out
    smallest = min(leaves, key=lambda a: getattr(a, "size", 1))
    np.asarray(jax.device_get(smallest))
    return out


def timed_call(fn: Callable, *args: Any) -> Tuple[Any, float]:
    """``(out, seconds)`` for one fully-completed call of ``fn``.

    The window closes only after :func:`sync` — end to end on every
    backend, including the per-call constant.
    """
    t0 = time.perf_counter()
    out = sync(fn(*args))
    return out, time.perf_counter() - t0


def warmed_marginal(make_fn: Callable[[int], Callable], lo: int,
                    hi: int, args: Tuple = (), reps: int = 3,
                    ) -> Tuple[float, float, Any]:
    """Build + warm the two programs, then difference them.

    ``make_fn(n)`` returns a callable (typically jitted) running an
    n-cycle program; it is called once per cycle count, so per-call
    jitting inside it is fine.  Both programs are executed to
    completion once before any timed window (compile + warm), and the
    warm full-length output is returned as the third element so
    callers reuse the result instead of paying another run —
    every ops.run_* here is deterministic given its inputs, so the
    warm output IS the run's result.

    Returns ``(sec_per_cycle, fixed_s, out_hi)``.
    """
    fns = {c: make_fn(c) for c in (lo, hi)}
    outs = {c: sync(f(*args)) for c, f in fns.items()}
    per_cycle, fixed = marginal_seconds_per_cycle(
        lambda c: fns[c](*args), lo, hi, reps=reps)
    return per_cycle, fixed, outs[hi]


def marginal_seconds_per_cycle(
        run_cycles: Callable[[int], Any],
        lo: int, hi: int, reps: int = 3) -> Tuple[float, float]:
    """Steady-state per-cycle seconds via two-point differencing.

    ``run_cycles(n)`` must execute an n-cycle program to completion
    (caller jits per cycle count and calls :func:`sync`; both counts
    must be pre-compiled/warmed by the caller so compile time never
    lands in a timed window).  Returns ``(sec_per_cycle, fixed_s)``
    where ``fixed_s`` is the per-call constant (enqueue + round-trip +
    fetch) implied by the intercept — reported so benches can show how
    much of the end-to-end time is per-call overhead, not chip.

    Medians over ``reps`` repetitions: host-clock jitter can produce a
    negative slope on fast programs from a single rep; the median plus
    a floor at 0 keeps the estimate sane.
    ``hi - lo`` should be chosen so the real compute delta dominates
    that jitter (hundreds of cycles minimum for VMEM-resident
    problems).
    """
    if hi <= lo:
        raise ValueError(f"need hi > lo, got lo={lo} hi={hi}")
    t_lo, t_hi = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        sync(run_cycles(lo))
        t_lo.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        sync(run_cycles(hi))
        t_hi.append(time.perf_counter() - t0)
    med_lo = float(np.median(t_lo))
    med_hi = float(np.median(t_hi))
    per_cycle = max((med_hi - med_lo) / (hi - lo), 0.0)
    fixed = max(med_lo - per_cycle * lo, 0.0)
    return per_cycle, fixed
