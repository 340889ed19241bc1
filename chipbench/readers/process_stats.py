"""The process's own clocks and counters over the traced block: the
``session_process`` object in the header of the span file, which is
``/stats``'s ``process`` object (the collector's pauses by generation,
resident memory, the CPU seconds of every thread, a wall clock) as
the tracer's file session read it when it started and when it ended.
The session lies inside ``jax.profiler``'s, so what the profiler
spends starting and writing its file is in neither read; the two
``GET /stats`` of the runner lie around both.  None where the header
has no such object, as on a commit from before PR 41.

Args of a metric file: ``keys`` (dotted paths into the object; their
differences are summed), ``per`` (a dotted path: the sum is divided by
its difference) or ``per_span`` (by the number of spans of that name),
``scale``.
"""

import json

from chipbench.readers import spans, stats


def read(capture, keys, per=None, per_span=None, scale=1.0):
    path = capture.get("spans")
    if not path:
        return None
    with open(path, encoding="utf-8") as f:
        header = json.load(f).get("pydcop_trace_header") or {}
    session = header.get("session_process")
    if not session:
        return None
    sides = {"stats_before": session["start"],
             "stats_after": session["end"]}
    value = sum(stats.difference(sides, key) for key in keys)
    if per is not None:
        count = stats.difference(sides, per)
    elif per_span is not None:
        count = sum(e["name"] == per_span for e in spans.load(path))
    else:
        count = 1
    if not count:
        return None
    return value / count * scale
