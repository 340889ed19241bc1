"""Device self time by ``jax.named_scope``: the superstep's phases
(``maxsum/f2v``, ``maxsum/aggregate``, ``maxsum/v2f``,
``maxsum/update``; ``ops/maxsum.py`` ``superstep``), whatever the
compiler calls its fusions in this compile.

Self time follows ``xplane.op_totals`` (an operation that encloses
others on the line, a ``while`` around its body, is charged only what
they do not cover), so the scopes' times add up to the device's busy
time.  An operation's scope is the last ``maxsum/<phase>`` in the
op_name of its HLO instruction (``xspace.instruction_op_names``); a
fusion has the op_name of its root, so one that mixes two phases is
charged whole to one.

Args of a metric file: ``scope`` (the phase, or null: everything
under no ``maxsum/*`` scope), ``per`` (the runner's value that counts
the supersteps traced), ``scale`` (the trace is in nanoseconds).
"""

import bisect
import re

from chipbench.readers import xplane, xspace

SCOPE = re.compile(r"maxsum/(\w+)")


def scope_of(op_name):
    """``f2v`` from ``jit(f)/while/body/maxsum/f2v/reduce_min``; None
    where there is no op_name or no ``maxsum/*`` scope in it."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def instruction_name(event_name):
    """``fusion.48`` from an event named by its HLO text,
    ``%fusion.48 = f32[8,3]{1,0} fusion(...)``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def by_module(ops, modules):
    """``{module name: [operations that start inside one of its
    runs]}`` on one device plane; None names operations under no
    module's run."""
    runs = sorted((start, start + dur, name)
                  for name, start, dur in modules)
    starts = [run[0] for run in runs]
    out = {}
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        inside = i >= 0 and op[1] < runs[i][1]
        out.setdefault(runs[i][2] if inside else None, []).append(op)
    return out


def scope_totals(ops, modules, op_names):
    """``{scope or None: self ns}`` on one device plane."""
    totals = {}
    for module, events in by_module(ops, modules).items():
        names = op_names.get(module, {})
        for event_name, ns in xplane.op_totals(events):
            scope = scope_of(names.get(instruction_name(event_name)))
            totals[scope] = totals.get(scope, 0) + ns
    return totals


def read(capture, scope, per, scale=1.0):
    path = xspace.profile_path(capture)
    count = capture.get("values", {}).get(per)
    if path is None or not count:
        return None
    trace = xspace.load(path)
    if not trace["ops"] or not any(
            scope_of(op_name) for names in trace["op_names"].values()
            for op_name in names.values()):
        # No device plane, or a program without the scopes (the
        # parent's): nothing to read, not a zero.
        return None
    planes = [scope_totals(ops, modules, trace["op_names"])
              for ops, modules in zip(trace["ops"], trace["modules"])]
    ns = sum(plane.get(scope, 0) for plane in planes) / len(planes)
    return ns / count * scale
