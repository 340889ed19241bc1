"""The program's flight ring (``pydcop_tpu.observability.
get_flight().snapshot()``): the always-on record of the spans gated
on ``tracer.active``, which holds what happened OUTSIDE the traced
block too, such as the solve cell's YAML load.

Like every metric PR 26 adds, it is reported only from a run that has
the device's trace (``capture["device_trace"]``): the CPU rehearsal
of PR 25 asserts the exact set of metrics a CPU run prints, and that
file may not be edited.

Args of a metric file: ``names`` (the value is the duration of the
LAST span of each name, summed), ``scale`` (the ring is in
microseconds).  None where the ring is off or holds no such span.
"""


def last_durations(events, names):
    """``{name: duration of the last complete span of that name}``."""
    out = {}
    for e in events:
        if e.get("ph") == "X" and e.get("name") in names:
            out[e["name"]] = e["dur"]
    return out


def read(capture, names, scale=1.0):
    if not capture.get("device_trace"):
        return None
    from pydcop_tpu.observability import get_flight

    flight = get_flight()
    if flight is None:
        return None
    found = last_durations(flight.snapshot(), names)
    if set(found) != set(names):
        return None
    return sum(found.values()) * scale
