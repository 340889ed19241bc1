"""What the host was doing while the device was idle, on ONE clock:
the device's idle intervals intersected with the program's spans,
which the tracer's profiler bridge writes into the same
``.xplane.pb`` as ``pydcop:<name>`` annotations.

The block is taken from the first annotation's start to the last
one's end (the tracer is enabled as the block starts and disabled as
it ends); the device is idle wherever no event of its ``XLA Ops`` line
runs.  Annotations of all host threads count, so a wait on one thread
does not hide work on another: an idle instant is "under" a name if
any thread has such a span open.

Before the clock is trusted, at least ``MIN_ANCHORED`` of the
device's busy time has to lie inside spans that enclose a dispatch
(``ANCHORS``); if not, the reader notes why and reads nothing.

Args of a metric file: ``stat`` = ``share`` (% of the idle time under
the spans in ``names``), ``ms_per`` (idle ms under them per span
named ``per``) or ``complement`` (% of the idle time under no
``pydcop:`` annotation at all).
"""

from chipbench.lib import note
from chipbench.readers import xspace

# Spans that enclose a device dispatch: timed_jit_call's two names,
# the segmented loop's and the serve plane's; a pipelined serve
# dispatch is launched before its serve_dispatch span opens, inside
# the scheduler's flush.
ANCHORS = ("engine_call", "jit_compile", "engine_segment",
           "serve_dispatch", "sched_flush")
MIN_ANCHORED = 0.9


def merge(intervals):
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def length(intervals):
    return sum(end - start for start, end in intervals)


def intersect(a, b):
    """Of two merged lists, the merged list of what both cover."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > start:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals, window):
    """What of ``window`` the merged ``intervals`` leave uncovered."""
    out, at = [], window[0]
    for start, end in intervals:
        if start > at:
            out.append((at, min(start, window[1])))
        at = max(at, end)
        if at >= window[1]:
            break
    if at < window[1]:
        out.append((at, window[1]))
    return [i for i in out if i[1] > i[0]]


def spans_named(annotations, names):
    return merge((start, start + dur) for name, start, dur in annotations
                 if names is None or name in names)


def attribute(ops, annotations, stat, names=None, per=None):
    """The stat from one device plane's operations ``[(name,
    start_ns, duration_ns)]`` and the annotations ``[(span name,
    start_ns, duration_ns)]``; ``(None, reason)`` where the clock
    cannot be trusted or there is nothing to read."""
    if not annotations:
        return None, "the trace has no pydcop: annotation"
    window = (min(a[1] for a in annotations),
              max(a[1] + a[2] for a in annotations))
    busy = intersect(merge((s, s + d) for _, s, d in ops), [window])
    if not busy:
        return None, "no device operation inside the annotated block"
    anchored = length(intersect(busy, spans_named(annotations, ANCHORS)))
    if anchored < MIN_ANCHORED * length(busy):
        return None, (f"only {anchored / length(busy):.0%} of the "
                      "device's busy time lies inside a span that "
                      "encloses a dispatch: the clocks do not agree")
    idle = complement(busy, window)
    if not idle:
        return None, "the device was never idle"
    if stat == "complement":
        under = intersect(idle, spans_named(annotations, None))
        return 100.0 * (1.0 - length(under) / length(idle)), None
    under = length(intersect(idle, spans_named(annotations, names)))
    if stat == "share":
        return 100.0 * under / length(idle), None
    if stat == "ms_per":
        count = sum(1 for name, _, _ in annotations if name == per)
        if not count:
            return None, f"no span named {per!r}"
        return under / count / 1e6, None
    raise ValueError(f"idle_under reader: unknown stat {stat!r}")


def read(capture, stat, names=None, per=None):
    path = xspace.profile_path(capture)
    if path is None:
        return None
    trace = xspace.load(path)
    values = []
    for ops in trace["ops"]:
        value, why = attribute(ops, trace["annotations"], stat,
                               names=names, per=per)
        if value is None:
            note(idle_under={"stat": stat, "names": names,
                             "nothing_read": why})
            return None
        values.append(value)
    return sum(values) / len(values) if values else None
