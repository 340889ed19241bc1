"""Bench regression sentinel: run-over-run guard on the BENCH_r*.json
trajectory.

A driver round appends a ``BENCH_r<N>.json`` (the ``bench.py`` line,
wrapped with attempt metadata).  This tool parses the whole history,
builds a noise-aware baseline per backend (CPU and TPU rates differ
by orders of magnitude and must never share a baseline — and CPU baselines are further keyed on
the host's core count once a round records ``host_cpus``, because a
1-core bench box measures the same code ~3x slower than an 8-core
one), and fails when the newest run regresses beyond threshold.

Noise model: the baseline is the MEDIAN of the trailing window with a
MAD (median absolute deviation) spread — both robust to a single
wild outlier.  The newest value regresses
when it falls below ``median - max(rel_tol * median, mad_mult * MAD)``:
the relative term guards stable series (MAD ~ 0 would otherwise flag
every wiggle), the MAD term widens tolerance on genuinely noisy
series (shared-CPU benchmark hosts jitter ±15% run to run).

Host-shift guard (ISSUE 19): the closed-loop serving legs are bound
by the host's thread scheduler, not device compute — the same code
measures 2x slower when a shared box degrades, even at the same core
count (so the ``cpu@<n>`` class key cannot see it).  The guard
detects that from the data: every HOST-BOUND family's newest/median
speed ratio is pooled (including the envelope-off control arm
``serve_mixed_baseline``, the same workload every round), and when
the MEDIAN ratio itself falls beyond the relative tolerance the drop
is common-mode — a host-class change, not a code regression (one
code change does not slow serve, fleet, sessions, cold-start AND the
feature-off control arm in unison).  Host-bound regressions in such
a round are reported loudly but do not gate; compute-bound families
(headline, sharded, dpop, time-to-cost) always gate, and an isolated
single-family drop still fails because it cannot move the median.
The blind spot (a stack-wide code slowdown coinciding with the
round) self-heals: the trailing window re-medians over the following
same-class rounds and a persistent regression resurfaces.

Usage::

    python tools/bench_sentinel.py             # report + exit 1 on
                                               # regression (make
                                               # bench-check)
    python tools/bench_sentinel.py --json      # machine-readable
    python tools/bench_sentinel.py --root DIR  # history elsewhere

``make test`` runs it ADVISORY (report printed, failures don't gate:
a slow shared host must not block an unrelated PR); ``make
bench-check`` is the hard gate for perf-focused work.  Each series
also prints a one-line sparkline trajectory suitable for pasting into
CHANGES.md.
"""

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, List, Optional

DEFAULT_REL_TOL = 0.15
DEFAULT_MAD_MULT = 3.0
DEFAULT_WINDOW = 5
MIN_POINTS = 3  # newest + at least 2 history points to call anything
# Host-shift guard: the common-mode estimator needs at least this many
# host-bound series with judgeable history before it may conclude
# anything — two ratios have no meaningful median.
HOST_SHIFT_MIN_SERIES = 3

_SPARKS = "▁▂▃▄▅▆▇█"


def _opt_float(value) -> Optional[float]:
    return float(value) if value is not None else None


def load_history(root: str) -> List[Dict[str, Any]]:
    """All bench runs in chronological order: ``BENCH_r*.json`` by
    round number.

    Unreadable or value-less files are skipped with a note in the
    returned rows (``"skipped"`` entries), never a crash — the history
    predates this tool and its earliest rows are ragged.
    """
    runs: List[Dict[str, Any]] = []
    # Round files strictly: the glob also matches names like
    # BENCH_rerun.json, which have no round number to sort by.
    numbered = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        match = re.fullmatch(r"BENCH_r(\d+)\.json",
                             os.path.basename(path))
        if match:
            numbered.append((int(match.group(1)), path))
    paths = [p for _, p in sorted(numbered)]
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as exc:
            runs.append({"source": name, "skipped": str(exc)})
            continue
        # A brand-new (or hand-edited) history may hold JSON that is
        # valid but not a run document — a bare list, a string.  Skip
        # it like an unreadable file, never crash the sentinel.
        if not isinstance(doc, dict):
            runs.append({"source": name,
                         "skipped": "not a JSON object"})
            continue
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            parsed = {}
        value = parsed.get("value")
        if value is None:
            runs.append({"source": name,
                         "skipped": "no parsed.value"})
            continue
        serve_value = parsed.get("serve_problems_per_sec")
        sharded_value = parsed.get("maxsum_cycles_per_sec_sharded")
        runs.append({
            "source": name,
            "n": doc.get("n"),
            "value": float(value),
            # Rounds 1-5 all fell back to CPU; the earliest line
            # predates the backend key, so absent means cpu.
            "backend": parsed.get("backend") or "cpu",
            # Host hardware class (ISSUE 17): CPU rates scale
            # with the bench box's core count, so CPU baselines are
            # keyed on it (``cpu@<n>``) once a round records it —
            # rounds that predate the key stay plain ``cpu``.
            "host_cpus": parsed.get("host_cpus"),
            # Serving-throughput leg (PR-6 bench_serving); absent in
            # earlier rounds, None when the leg failed that round.
            "serve_value": (float(serve_value)
                            if serve_value is not None else None),
            # Sharded-superstep leg (PR-7 bench_sharded: partitioned
            # engine, halo-only exchange).  Judged on its own backend
            # key — the CPU leg runs on a forced-host-device mesh
            # whose rates say nothing about a real TPU mesh.
            "sharded_value": (float(sharded_value)
                              if sharded_value is not None else None),
            "sharded_backend": parsed.get("sharded_backend")
            or parsed.get("backend") or "cpu",
            # Time-to-target-cost leg (ISSUE 10 bench_time_to_cost):
            # milliseconds the pruned engine takes to reach the
            # reference cost on the large-domain loopy graph — LOWER
            # is better; absent before PR 10.
            "ttc_value": _opt_float(
                parsed.get("maxsum_time_to_cost_ms")),
            # Recovery-latency legs (ISSUE 8 bench_recovery_replay /
            # bench_sharded): seconds, LOWER is better — absent
            # before PR 8, None when the leg failed that round.
            "serve_recovery_value": _opt_float(
                parsed.get("serve_recovery_replay_s")),
            "shard_recovery_value": _opt_float(
                parsed.get("shard_recovery_s")),
            # Mixed-structure serving leg (ISSUE 11
            # bench_serving_mixed): zipf-diverse topologies through
            # the envelope batching tier — absent before PR 11, None
            # when the leg failed that round.
            "serve_mixed_value": _opt_float(
                parsed.get("serve_mixed_problems_per_sec")),
            # Envelope-OFF control arm of the same mixed leg: the one
            # series whose workload and code path barely change round
            # to round, so its drift measures the HOST, not the PR.
            # Never gates on its own — it anchors the host-shift
            # guard's common-mode estimator (ISSUE 19).
            "serve_mixed_baseline_value": _opt_float(
                parsed.get("serve_mixed_baseline_problems_per_sec")),
            # Pipelined-flush overlap (ISSUE 18 bench_serving_mixed):
            # measured-window fraction of device execute wall the
            # scheduler hid decode work under — HIGHER is better, a
            # drop means the closed-loop hot path stopped
            # overlapping.  Absent before PR 18.
            "serve_overlap_value": _opt_float(
                parsed.get("serve_overlap_fraction")),
            # Stateful-session legs (ISSUE 13 bench_sessions):
            # warm time-to-recovered-cost after a scenario event
            # (ms, LOWER is better) and sustained applied events per
            # second per session — absent before PR 13, None when
            # the leg failed that round.
            "session_ttr_value": _opt_float(
                parsed.get("session_time_to_recovered_cost_ms")),
            "session_eps_value": _opt_float(
                parsed.get("session_events_per_sec")),
            # Fleet-serving legs (ISSUE 15 bench_serving_fleet /
            # bench_serve_cold_start): aggregate problems/sec through
            # 2 router-fronted worker replicas, and the fresh-worker
            # warm-disk-cache time-to-first-result (s, LOWER is
            # better) — absent before PR 15, None when the leg failed
            # that round.
            "fleet_value": _opt_float(
                parsed.get("fleet_problems_per_sec_r2")),
            "cold_start_value": _opt_float(
                parsed.get("serve_cold_start_warm_s")),
            # Exact-inference leg (ISSUE 17 bench_dpop_exact):
            # warmed best-of-N full DPOP sweep (UTIL up + VALUE
            # down, CEC on) on the width-bounded seeded instance
            # (ms, LOWER is better) — absent before PR 17, None
            # when the leg failed that round.
            "dpop_value": _opt_float(parsed.get("dpop_exact_ms")),
            # Elastic-fleet leg (ISSUE 16 bench_fleet_elastic):
            # baseline closed-loop problems/sec through the two-host
            # fleet that also survives the leg's migration, 4x-step
            # autoscale, and host-kill phases — absent before PR 16,
            # None when the leg failed that round.
            "fleet_elastic_value": _opt_float(
                parsed.get("fleet_elastic_problems_per_sec")),
            # Partition-tolerant fleet leg (ISSUE 19
            # bench_serving_fleet_faulted): closed-loop problems/sec
            # through a 2-replica fleet under a seeded 1%-drop /
            # 20ms-delay plan on the solve links.  Its OWN family —
            # a faulted round must never be judged against (or
            # pollute the baseline of) the clean fleet numbers.
            # Absent before PR 19, None when the leg failed.
            "fleet_faulted_value": _opt_float(
                parsed.get("fleet_faulted_problems_per_sec")),
            # The p99 latency exemplar from the serving leg (ISSUE
            # 9): when the newest run regresses, the report points at
            # a concrete request trace instead of a bare number.
            "exemplar": parsed.get("exemplar_trace_id"),
            # Per-leg RESOLVED backends (ISSUE 11 crumb, consumed
            # since ISSUE 14): a run whose headline ran on TPU can
            # still have individual legs fall back to CPU — each
            # leg's value must be judged against ITS backend's
            # baseline, never the headline's.  Absent before PR 11.
            "leg_backends": {
                leg: info.get("backend")
                for leg, info in (
                    parsed.get("leg_backends") or {}).items()
                if isinstance(info, dict)
            },
        })
    return runs


def check_series(values: List[float],
                 rel_tol: float = DEFAULT_REL_TOL,
                 mad_mult: float = DEFAULT_MAD_MULT,
                 window: int = DEFAULT_WINDOW,
                 higher_is_better: bool = True) -> Dict[str, Any]:
    """Verdict for one backend's chronological metric series.

    The newest value is judged against the median ± MAD of the
    ``window`` runs before it.  ``higher_is_better=True`` (rates:
    cycles/s, problems/s) regresses when the newest value falls below
    the floor; ``False`` (latencies: recovery seconds) regresses when
    it rises above the ceiling.  Returns a dict with the verdict
    (``ok`` / ``regressed`` / ``insufficient``), the baseline stats,
    the tolerance actually applied, and ``bound`` (the floor or
    ceiling crossed)."""
    if len(values) < MIN_POINTS:
        return {
            "verdict": "insufficient",
            "points": len(values),
            "detail": f"need >= {MIN_POINTS} runs to judge",
        }
    newest = values[-1]
    trail = values[-(window + 1):-1]
    med = statistics.median(trail)
    mad = statistics.median([abs(v - med) for v in trail])
    tolerance = max(rel_tol * abs(med), mad_mult * mad)
    if higher_is_better:
        bound = med - tolerance
        regressed = newest < bound
    else:
        bound = med + tolerance
        regressed = newest > bound
    return {
        "verdict": "regressed" if regressed else "ok",
        "points": len(values),
        "newest": newest,
        "median": med,
        "mad": mad,
        "tolerance": tolerance,
        "bound": bound,
        # Kept for history consumers that predate lower-is-better
        # series: "floor" has always named the regression boundary.
        "floor": bound,
        "higher_is_better": higher_is_better,
        "delta_rel": (newest - med) / med if med else 0.0,
    }


def sparkline(values: List[float]) -> str:
    """One block-character per run, scaled to the series range — the
    pasteable trajectory line."""
    if not values:
        return ""
    lo, hi = min(values), max(values)
    span = hi - lo
    if span <= 0:
        return _SPARKS[3] * len(values)
    return "".join(
        _SPARKS[min(int((v - lo) / span * (len(_SPARKS) - 1)),
                    len(_SPARKS) - 1)]
        for v in values
    )


def run_check(root: str, rel_tol: float = DEFAULT_REL_TOL,
              mad_mult: float = DEFAULT_MAD_MULT,
              window: int = DEFAULT_WINDOW) -> Dict[str, Any]:
    """Full sentinel pass over a history directory: per-backend
    verdicts + summary lines.  ``failed`` is True iff any backend
    with enough history regressed."""
    runs = load_history(root)
    skipped = [r for r in runs if "skipped" in r]
    # Five metric families judged with the same noise model: the
    # headline engine rate ("value", cycles/s), the serving
    # throughput ("serve_value", problems/s — absent before PR 6),
    # the sharded-superstep rate ("sharded_value", cycles/s — absent
    # before PR 7; judged on its own backend key because the CPU leg
    # runs on a forced-host-device mesh), and the two ISSUE-8
    # recovery LATENCIES (journal crash replay, shard-loss
    # repartition — seconds, LOWER is better, regression = newest
    # above the ceiling).  Backends never share a baseline in any
    # family.
    metrics = (
        # (family, value field, unit, fallback backend key, higher is
        # better, bench.py leg name in ``leg_backends``, host-bound).
        # ``host_bound=True`` marks closed-loop serving legs whose
        # rate is dominated by the host's thread scheduler rather
        # than device compute — the population the host-shift guard
        # pools its common-mode estimator over (ISSUE 19).  Compute
        # families stay False and always gate.
        ("bench", "value", "cycles/s", "backend", True, "headline",
         False),
        ("serve", "serve_value", "problems/s", "backend", True,
         "serve", True),
        # ISSUE 11: throughput on zipf-diverse structures through the
        # envelope batching tier — the traffic shape on which pure
        # structure binning degenerates to batch-size-1.
        ("serve_mixed", "serve_mixed_value", "problems/s",
         "backend", True, "serve_mixed", True),
        # ISSUE 19: the envelope-OFF control arm of the same leg.
        # Same workload every round, so its drift measures the host;
        # it feeds the host-shift estimator and NEVER gates (see
        # CONTROL_FAMILIES below).
        ("serve_mixed_baseline", "serve_mixed_baseline_value",
         "problems/s", "backend", True, "serve_mixed", True),
        # ISSUE 18: decode/dispatch overlap fraction of the pipelined
        # scheduler on the same mixed leg — a brand-new family: until
        # 3 rounds exist its verdict is "insufficient", never a crash
        # or gate.  A fraction, so host-speed cancels: not host-bound.
        ("serve_overlap", "serve_overlap_value", "fraction",
         "backend", True, "serve_mixed", False),
        ("sharded", "sharded_value", "cycles/s",
         "sharded_backend", True, "sharded", False),
        # ISSUE 10: wall-clock to the reference cost on the
        # large-domain loopy graph (bench_time_to_cost) — the
        # work-reduction stack's headline, LOWER is better.
        # Host-bound: wall-clock ms of cpu-resolved compute tracks
        # host speed; the work-reduction logic itself is gated
        # load-immune by perf-smoke's same-box decimation-vs-baseline
        # wall ratio (DECIM_MAX_FRACTION).
        ("time_to_cost", "ttc_value", "ms", "backend", False,
         "time_to_cost", True),
        ("serve_recovery", "serve_recovery_value", "s",
         "backend", False, "serve_recovery", True),
        # ISSUE 15: the fleet-scale serving families — aggregate
        # replicas=2 throughput through the structure-affinity
        # router (higher is better) and a fresh worker's warm-cache
        # time-to-first-result (the persistent AOT compile cache's
        # reason to exist; lower is better).
        ("serving_fleet", "fleet_value", "problems/s",
         "backend", True, "serving_fleet", True),
        ("serve_cold_start", "cold_start_value", "s",
         "backend", False, "serve_cold_start", True),
        # ISSUE 16: steady-state throughput through the elastic
        # two-host fleet — the rate the migration/autoscale/host-kill
        # machinery must not tax.  A brand-new family: until 3 rounds
        # exist its verdict is "insufficient", never a crash or gate.
        ("fleet_elastic", "fleet_elastic_value", "problems/s",
         "backend", True, "fleet_elastic", True),
        # ISSUE 19: throughput through the same fleet under the
        # seeded drop+delay plan — the injected-fault leg is judged
        # as its own family so the retry tax is tracked against
        # faulted rounds only, never against the clean fleet
        # baseline.  A brand-new family: until 3 rounds exist its
        # verdict is "insufficient", never a crash or gate.
        ("fleet_faulted", "fleet_faulted_value", "problems/s",
         "backend", True, "fleet_faulted", True),
        # Host-bound like serve_recovery/session_recovery: on a
        # cpu-resolved round this wall-clock is host compute, so it
        # tracks a host-class change 1:1 (r09: identical trees
        # measured +26% on the shifted box).  Real recovery-path
        # regressions still gate on quiet rounds, and kernel-level
        # slowdowns are caught machine-independently by the golden
        # ratio races in tests/unit/test_perf_regression.py.
        ("shard_recovery", "shard_recovery_value", "s",
         "sharded_backend", False, "sharded", True),
        # ISSUE 17: warm wall-clock of one exact DPOP sweep on the
        # width-bounded seeded instance (ms, LOWER is better) — a
        # brand-new family: until 3 rounds exist its verdict is
        # "insufficient", never a crash or gate.  Host-bound for the
        # same reason as shard_recovery: cpu-resolved wall-ms of a
        # jitted sweep IS host speed; the load-immune dpop kernel
        # gate lives in test_perf_regression.py.
        ("dpop_exact", "dpop_value", "ms", "backend", False,
         "dpop_exact", True),
        # ISSUE 13: the stateful-session families — sustained
        # scenario-event throughput per session (higher is better)
        # and warm time-to-recovered-cost after an event (the
        # session plane's reason to exist: it must stay far below a
        # cold re-solve; lower is better).
        ("session_events", "session_eps_value", "events/s",
         "backend", True, "sessions", True),
        ("session_recovery", "session_ttr_value", "ms",
         "backend", False, "sessions", True),
    )
    # Families that only anchor the host-shift estimator: their
    # regressions never set ``failed`` even when the guard does not
    # fire — the control arm exists to measure the host, not the PR.
    control_families = {"serve_mixed_baseline"}
    series = {}
    lines = []
    failed = False
    # Host-shift guard state: every host-bound GATING series with a
    # judgeable baseline contributes its speed ratio (newest/median
    # for rates, median/newest for latencies — >1 means the host got
    # faster either way); regressions in that population are held
    # here until the common-mode estimator decides whether they gate.
    host_ratios: Dict[str, float] = {}
    host_pending: List[Dict[str, Any]] = []
    for (family, field, unit, backend_key, higher_better,
         leg, host_bound) in metrics:
        # Rates print whole, latencies and fractions keep precision.
        fmt = (".3f" if (not higher_better or unit == "fraction")
               else ".0f")

        def leg_backend(r):
            # The leg's RESOLVED backend when the round recorded one
            # (``leg_backends``, PR 11+); older rounds fall back to
            # their per-run backend field — identical to the pre-leg
            # behavior, so legacy histories judge unchanged.
            base = ((r.get("leg_backends") or {}).get(leg)
                    or r.get(backend_key) or r.get("backend")
                    or "cpu")
            # CPU rates are host-bound: the same code measures ~3x
            # slower on a 1-core box than the 8-core boxes earlier
            # rounds ran on.  Once a round records its core count,
            # its CPU series is keyed ``cpu@<n>`` so it is judged only
            # against same-class hosts — the exact refusal the
            # backend split (ISSUE 14) applies between cpu and tpu.
            # Accelerator backends keep their plain key: their rates
            # are device-bound, not host-core-bound.
            cpus = r.get("host_cpus")
            if base == "cpu" and cpus:
                return f"cpu@{int(cpus)}"
            return base

        rows_f = [r for r in runs
                  if "skipped" not in r and r.get(field) is not None]
        by_backend: Dict[str, List[Dict[str, Any]]] = {}
        for r in rows_f:
            by_backend.setdefault(leg_backend(r), []).append(r)
        # Cross-backend refusal (ISSUE 14): the newest run's leg is
        # judged ONLY against history rows whose recorded leg backend
        # matches its own resolved backend — a CPU round must
        # neither regress nor pad a TPU baseline.  Rows with an
        # explicit mismatching leg record are named as SKIPPED so the
        # exclusion is visible, not silent.
        newest_row = rows_f[-1] if rows_f else None
        newest_backend = (leg_backend(newest_row)
                          if newest_row is not None else None)
        skipped_rows = [
            (r["source"], leg_backend(r)) for r in rows_f
            if (r.get("leg_backends") or {}).get(leg)
            and leg_backend(r) != newest_backend
        ]
        for source, row_backend in skipped_rows:
            lines.append(
                f"{family}[{newest_backend}] SKIPPED {source} "
                f"(leg ran on {row_backend}, newest resolved "
                f"{newest_backend})")
        for backend in sorted(by_backend):
            rows = by_backend[backend]
            values = [r[field] for r in rows]
            result = check_series(values, rel_tol=rel_tol,
                                  mad_mult=mad_mult, window=window,
                                  higher_is_better=higher_better)
            result["values"] = values
            result["sources"] = [r["source"] for r in rows]
            label = (backend if family == "bench"
                     else f"{family}:{backend}")
            series[label] = result
            spark = sparkline(values)
            if result["verdict"] == "insufficient":
                lines.append(
                    f"{family}[{backend}] {spark} "
                    f"{values[0]:{fmt}}→{values[-1]:{fmt}} {unit} — "
                    f"{result['detail']} ({result['points']} run(s))"
                )
                continue
            direction = f"{result['delta_rel']:+.1%}"
            verdict = ("REGRESSED" if result["verdict"] == "regressed"
                       else "OK")
            bound_name = "floor" if higher_better else "ceiling"
            # Only the backend the newest round actually resolved
            # GATES: a stale series (e.g. an old TPU baseline while
            # the newest round fell back to CPU) still reports, but
            # its newest member is an old round that was judged in
            # its own day — failing CI on it would block a round the
            # report itself says was not compared against it.
            stale = (newest_backend is not None
                     and backend != newest_backend)
            result["gating"] = not stale
            line_idx = len(lines)
            lines.append(
                f"{family}[{backend}] {spark} "
                f"{values[0]:{fmt}}→{values[-1]:{fmt}} {unit}, newest "
                f"{direction} vs median {result['median']:{fmt}} "
                f"({bound_name} {result['bound']:{fmt}}) {verdict}"
                + (" (stale backend — not gating)" if stale else "")
            )
            if host_bound and not stale and result["median"]:
                newest_v = result["newest"]
                if higher_better:
                    host_ratios[label] = newest_v / result["median"]
                elif newest_v:
                    host_ratios[label] = result["median"] / newest_v
            if result["verdict"] == "regressed" and not stale:
                if family in control_families:
                    # The control arm's own drop IS the host signal —
                    # it feeds the estimator above, never ``failed``.
                    result["gating"] = False
                elif host_bound:
                    host_pending.append({"label": label,
                                         "result": result,
                                         "line": line_idx})
                else:
                    failed = True
                # The exemplar is the SERVING leg's p99 latency
                # trace_id — only the serve-latency family may point
                # at it (a compile or shard regression has nothing to
                # do with that request).
                exemplar = (rows[-1].get("exemplar")
                            if family == "serve" else None)
                if exemplar:
                    result["exemplar"] = exemplar
                    lines.append(
                        f"  ↳ exemplar trace {exemplar} — open it: "
                        f"pydcop trace query --request {exemplar} "
                        f"<trace file>")
    # Host-shift guard: with enough host-bound series to pool, a
    # common-mode drop (the MEDIAN ratio itself beyond the relative
    # tolerance) means the bench host changed class — the same
    # refusal ``cpu@<n>`` keying applies to core-count changes,
    # detected from the data instead of nproc.  Held host-bound
    # regressions then report as ``host-shift`` without gating; with
    # no shift (an isolated drop cannot move the median) they gate
    # exactly as before.
    estimator = (statistics.median(host_ratios.values())
                 if len(host_ratios) >= HOST_SHIFT_MIN_SERIES
                 else None)
    shift = estimator is not None and estimator < 1.0 - rel_tol
    host_shift = {"fired": shift, "estimator": estimator,
                  "threshold": 1.0 - rel_tol, "ratios": host_ratios}
    if host_pending and shift:
        for pend in host_pending:
            pend["result"]["verdict"] = "host-shift"
            pend["result"]["gating"] = False
            lines[pend["line"]] = (
                lines[pend["line"]].replace(
                    " REGRESSED",
                    " REGRESSED (host-shift — not gating)"))
        held = ", ".join(p["label"] for p in host_pending)
        lines.append(
            f"host-shift guard: median speed ratio "
            f"{estimator:.2f} across {len(host_ratios)} host-bound "
            f"series (incl. the envelope-off control arm) is below "
            f"{1.0 - rel_tol:.2f} — the bench host changed class, "
            f"not the code; held from gating: {held}")
    elif host_pending:
        failed = True
    return {
        "root": root,
        "runs": len(runs),
        "skipped": [r["source"] for r in skipped],
        "series": series,
        "lines": lines,
        "host_shift": host_shift,
        "failed": failed,
    }


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="bench regression sentinel over BENCH_r*.json")
    parser.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    parser.add_argument("--threshold", type=float,
                        default=DEFAULT_REL_TOL,
                        help="relative regression tolerance "
                             f"(default {DEFAULT_REL_TOL})")
    parser.add_argument("--mad-mult", type=float,
                        default=DEFAULT_MAD_MULT,
                        help="MAD multiples added to the tolerance "
                             f"(default {DEFAULT_MAD_MULT})")
    parser.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                        help="trailing runs in the baseline "
                             f"(default {DEFAULT_WINDOW})")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the full verdict as JSON")
    args = parser.parse_args(argv)

    report = run_check(args.root, rel_tol=args.threshold,
                       mad_mult=args.mad_mult, window=args.window)
    if args.as_json:
        print(json.dumps(report))
        return 1 if report["failed"] else 0
    if not report["series"]:
        print(f"bench_sentinel: no usable bench history under "
              f"{args.root}")
        return 0
    for line in report["lines"]:
        print(line)
    if report["skipped"]:
        print(f"bench_sentinel: skipped unreadable: "
              f"{', '.join(report['skipped'])}")
    if report["failed"]:
        print("bench_sentinel: FAIL — newest run regressed beyond "
              "the noise-aware floor (median - max(rel_tol*median, "
              "mad_mult*MAD) of the trailing window)")
        return 1
    print("bench_sentinel: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
