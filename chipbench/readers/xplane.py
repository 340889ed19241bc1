"""``jax.profiler`` trace (``.xplane.pb``) -> device busy time and
per-operation totals.

Busy time is the union of the intervals of the events on each device
plane's operations line, averaged over the device planes.  Only
durations are used, so no clock has to be aligned with the host's.

Args of a metric file: ``stat`` = ``busy_per`` (busy time over the
runner's value named ``per``, times ``scale``) or ``idle_share``
(100 x (1 - busy / traced wall)).
"""

import re

from chipbench.readers.spans import union_length

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def describe(data):
    """``[(plane name, [(line name, events)])]``, for a look by hand."""
    return [(plane.name, [(line.name, sum(1 for _ in line.events))
                          for line in plane.lines])
            for plane in data.planes]


def device_events(data, plane_prefix=DEVICE_PLANE_PREFIX,
                  line_name=OPS_LINE):
    """``[[(name, start_ns, duration_ns)] per device plane]``."""
    planes = []
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name == line_name:
                planes.append([(e.name, e.start_ns, e.duration_ns)
                               for e in line.events])
    return planes


def busy_ns(events):
    """Union of the events' intervals on one plane, in ns."""
    return union_length((start, start + dur) for _, start, dur in events)


def short_name(text):
    """``%fusion.48 fusion kCustom`` from an event named by its whole
    HLO instruction."""
    name, _, rest = text.partition(" = ")
    op = re.search(r"\s([a-z][a-z0-9_.-]*)\(", rest)
    kind = re.search(r"kind=(\w+)", rest)
    return " ".join(p for p in (name, op and op.group(1),
                                kind and kind.group(1)) if p)


def op_totals(events):
    """``[(name, total self ns)]`` of one plane, largest first.  An
    operation that encloses others on the line (a ``while`` around
    its body's fusions) is charged only what they do not cover."""
    totals, stack = {}, []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            totals[parent] -= min(dur, stack[-1][1] - start)
        totals[name] = totals.get(name, 0) + dur
        stack.append((name, start + dur))
    return sorted(totals.items(), key=lambda kv: -kv[1])


def summary(data):
    """``{"busy_s", "ops": [(name, seconds)]}`` averaged over the
    device planes, or None where the trace has no device plane."""
    planes = device_events(data)
    if not planes:
        return None
    totals = {}
    for events in planes:
        for name, ns in op_totals(events):
            totals[name] = totals.get(name, 0) + ns / len(planes)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns(ev) for ev in planes) / len(planes) / 1e9,
        "ops": [(short_name(name), ns / 1e9) for name, ns in ops],
    }


def read(capture, stat, per=None, scale=1.0):
    device = capture.get("device_trace")
    if not device:
        return None
    if stat == "busy_per":
        count = capture["values"].get(per)
        return device["busy_s"] / count * scale if count else None
    if stat == "idle_share":
        return 100.0 * (1.0 - device["busy_s"] / capture["traced_wall_s"])
    raise ValueError(f"xplane reader: unknown stat {stat!r}")
