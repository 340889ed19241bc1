"""Chrome-JSON spans of the program's tracer -> the thread's CPU time
inside them, and the time their threads were not running.

Since PR 41 a live span recorded under a file session carries
``tdur``, Chrome's thread-clock duration in microseconds
(``time.thread_time_ns`` read beside ``perf_counter``).  A span's wall
less its ``tdur`` is the time its thread was off the CPU: blocked on
the device, a socket or a queue, or waiting for the interpreter lock;
for a span that is Python and C under the lock from end to end it is
the wait for the lock and nothing else.

Args of a metric file: ``names`` (the spans read; ``null`` = the root
spans of every thread: no parent in the file), ``stat`` (``cpu``: the
sum of their ``tdur``; ``wait``: the sum of each one's self wall less
its self ``tdur``, its children on its own thread subtracted from
both), ``per`` (the sum is divided by the number of spans of that
name), ``scale`` (the trace is in microseconds).  A span without
``tdur`` (a back-dated one, or a trace from before PR 41) is skipped,
as a child too; none found reads None.
"""

from chipbench.readers import spans


def selected(events, names):
    """The spans named that have a thread clock (``names`` None: the
    roots)."""
    if names is None:
        ids = {e["args"].get("span_id") for e in events}
        return [e for e in events if "tdur" in e
                and e["args"].get("parent_id", 0) not in ids]
    return [e for e in events if "tdur" in e and e["name"] in names]


def wait(event, children):
    """Self wall less self CPU of one span, in the trace's
    microseconds: both less what its ``children`` cover."""
    start, end = event["ts"], event["ts"] + event["dur"]
    covered = spans.union_length(
        (max(start, c["ts"]), min(end, c["ts"] + c["dur"]))
        for c in children
        if c["ts"] < end and c["ts"] + c["dur"] > start)
    own_cpu = event["tdur"] - sum(c["tdur"] for c in children)
    return (event["dur"] - covered) - own_cpu


def read(capture, names=None, stat="cpu", per=None, scale=1.0):
    path = capture.get("spans")
    if not path:
        return None
    events = spans.load(path)
    found = selected(events, names)
    if not found:
        return None
    if stat == "cpu":
        value = sum(e["tdur"] for e in found)
    elif stat == "wait":
        children = {}
        for e in events:
            if "tdur" in e:
                children.setdefault(
                    (e["tid"], e["args"].get("parent_id", 0)),
                    []).append(e)
        value = sum(
            wait(e, children.get((e["tid"], e["args"].get("span_id")), ()))
            for e in found)
    else:
        raise ValueError(f"span_cpu reader: unknown stat {stat!r}")
    if per is not None:
        count = sum(e["name"] == per for e in events)
        if not count:
            return None
        value /= count
    return value * scale
