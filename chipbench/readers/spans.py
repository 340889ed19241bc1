"""Chrome-JSON spans of the program's tracer
(``pydcop_tpu.observability.trace.tracer.export_chrome``) -> time by
span name.

Args of a metric file: ``names`` (the spans read), ``stat``
(``total`` or ``self``: a span's duration, or that minus what its
child spans cover), ``reduce`` (``median`` or ``mean`` over the
spans, or ``per`` = their sum over the number of spans named
``per``), ``scale`` (the trace is in microseconds).
"""

import json
import statistics


def load(path):
    """The complete (``ph == "X"``) events of a Chrome trace file."""
    with open(path, encoding="utf-8") as f:
        events = json.load(f)["traceEvents"]
    return [e for e in events if e.get("ph") == "X"]


def union_length(intervals):
    """Total length covered by ``(start, end)`` intervals."""
    covered, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            covered += end - start
            reach = end
        elif end > reach:
            covered += end - reach
            reach = end
    return covered


def self_times(events):
    """``{span_id: duration minus the part its children cover}``."""
    children = {}
    for e in events:
        children.setdefault(e["args"].get("parent_id", 0), []).append(e)
    out = {}
    for e in events:
        start, end = e["ts"], e["ts"] + e["dur"]
        covered = union_length(
            (max(start, c["ts"]), min(end, c["ts"] + c["dur"]))
            for c in children.get(e["args"].get("span_id"), ())
            if c["ts"] < end and c["ts"] + c["dur"] > start)
        out[e["args"].get("span_id")] = e["dur"] - covered
    return out


def by_name(events, stat="total"):
    """``{name: [value per span]}`` in the trace's microseconds."""
    own = self_times(events) if stat == "self" else None
    out = {}
    for e in events:
        value = own[e["args"].get("span_id")] if own else e["dur"]
        out.setdefault(e["name"], []).append(value)
    return out


def read(capture, names, stat="total", reduce="mean", per=None,
         scale=1.0):
    path = capture.get("spans")
    if not path:
        return None
    events = load(path)
    times = by_name(events, stat)
    values = [v for name in names for v in times.get(name, ())]
    if not values:
        return None
    if reduce == "median":
        value = statistics.median(values)
    elif reduce == "mean":
        value = statistics.fmean(values)
    elif reduce == "per":
        count = len(times.get(per, ()))
        if not count:
            return None
        value = sum(values) / count
    else:
        raise ValueError(f"spans reader: unknown reduce {reduce!r}")
    return value * scale
