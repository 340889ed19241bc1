"""What both runners share: the checks of an answer, the in-process
``pydcop`` CLI, the HTTP helpers (all lifted from ``chip_smoke.py``,
which proved them on the chip in PR 22), the lookup of a module by
name (runner, reader, family), the instance and its shapes, the
percentile and the traced block."""

import contextlib
import glob
import importlib
import io
import json
import math
import os
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchFailure(Exception):
    """The run cannot give a result: no last line, exit code 1."""


def note(**fields):
    """A line of the run's own record, before the last line."""
    print(json.dumps(fields), flush=True)


def module_by_name(package, name, what):
    """``chipbench/<package>/<name>.py``, imported; a name that
    resolves to no file names the file looked for."""
    path = os.path.join(HERE, package, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFailure(f"{what} {name!r}: no file {path}")
    return importlib.import_module(f"chipbench.{package}.{name}")


def family_of(spec):
    """The module of ``chipbench/families/`` that a configuration's
    ``generator`` names.  There is no default family."""
    if "family" not in spec:
        raise BenchFailure(
            "the generator states no `family` (a file of "
            f"{os.path.join(HERE, 'families')})")
    return module_by_name("families", spec["family"], "family")


def generate(spec, seed):
    """The instance of the spec's family for this seed, as a DCOP
    object, from the program's own generator."""
    return family_of(spec).generate(spec, seed)


def shapes(dcop):
    """The problem's own shapes, for ``chipbench/roofline.py`` and to
    hold an instance to what its family says every seed has."""
    by_arity = {}
    for c in dcop.constraints.values():
        by_arity[len(c.dimensions)] = by_arity.get(len(c.dimensions), 0) + 1
    first = next(iter(dcop.variables.values()))
    return {"variables": len(dcop.variables),
            "domain": len(first.domain.values),
            "factors_by_arity": by_arity}


def pydcop(*args):
    """Run the ``pydcop`` CLI in this process (the chip's one
    process).  Its standard output is swallowed; results are read
    from the file given with ``--output``."""
    from pydcop_tpu.dcop_cli import main

    with contextlib.redirect_stdout(io.StringIO()):
        rc = main(list(args))
    if rc != 0:
        raise BenchFailure(f"pydcop {' '.join(args)} exited {rc}")


def check_answer(dcop, answer, reference_cost, tolerance, ends=None):
    """``(fault, compared)`` of one answer (``assignment``, ``cost``,
    ``violations`` and, where ``ends`` is stated, ``status`` and
    ``cycles``).  ``fault`` says why it is wrong, or is None: it
    covers every variable, its reported cost and violations equal
    ``dcop.solution_cost`` on the host exactly, the cost is no worse
    than the reference's by more than ``tolerance`` (relative,
    one-sided), and the solve ended as the configuration's ``ends``
    states.  ``compared`` holds each number that was compared beside
    its limit, ``{name: [value, limit]}``."""
    assignment, cost = answer["assignment"], answer["cost"]
    limit = reference_cost + tolerance * abs(reference_cost)
    compared = {
        "cost": [cost, limit],
        "unassigned": [len(set(assignment) ^ set(dcop.variables)), 0]}
    if ends is not None:
        compared["cycles"] = [answer["cycles"], ends["cycles"]]
    if compared["unassigned"][0]:
        return (f"assignment covers {len(assignment)}/"
                f"{len(dcop.variables)} variables"), compared
    host_cost, host_violations = dcop.solution_cost(assignment)
    compared["cost_minus_host"] = [float(cost) - float(host_cost), 0]
    compared["violations_minus_host"] = [
        int(answer["violations"]) - int(host_violations), 0]
    fault = None
    if not np.isfinite(host_cost):
        fault = f"host cost {host_cost}"
    elif compared["cost_minus_host"][0]:
        fault = f"reported cost {cost} != host cost {host_cost}"
    elif compared["violations_minus_host"][0]:
        fault = (f"reported violations {answer['violations']} != host "
                 f"{host_violations}")
    elif host_cost > limit:
        fault = (f"cost {host_cost} is more than {tolerance:.0%} worse "
                 f"than the reference's {reference_cost}")
    elif ends is not None and (answer["status"], answer["cycles"]) != (
            ends["status"], ends["cycles"]):
        fault = (f"ended {answer['status']} at cycle {answer['cycles']}; "
                 f"the configuration states {ends['status']} at "
                 f"{ends['cycles']}")
    return fault, compared


def worst(checked):
    """Of ``(fault, compared)`` pairs, the ``compared`` of the worst
    answer: one at fault before a sound one, then the cost that lies
    highest against its limit."""
    return max(checked, key=lambda c: (
        c[0] is not None, c[1]["cost"][0] - c[1]["cost"][1]))[1]


def post_solve(url, body):
    req = urllib.request.Request(
        url + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        if resp.status != 200:
            raise BenchFailure(f"POST /solve answered {resp.status}")
        return json.loads(resp.read())


def get_json(url):
    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def percentile(values, share):
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(share * len(ordered)) - 1)]


@contextlib.contextmanager
def traced_block(cell, capture):
    """Run the body with the program's tracer on and, on the chip,
    under ``jax.profiler.trace``.  Fills ``capture`` with the span
    file, the block's host wall time and the device trace's summary.
    On any other platform no device capture is made, so no
    device-derived metric can be read from a CPU run."""
    from pydcop_tpu.observability.trace import tracer

    with contextlib.ExitStack() as stack:
        profile_dir = None
        if cell.device_capture:
            import jax

            profile_dir = os.path.join(cell.workdir, "profile")
            options = jax.profiler.ProfileOptions()
            # The Python tracer would slow the host path it observes.
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            stack.enter_context(jax.profiler.trace(
                profile_dir, profiler_options=options))
        tracer.enable()
        try:
            t0 = time.perf_counter()
            yield
            capture["traced_wall_s"] = time.perf_counter() - t0
        finally:
            tracer.disable()
    capture["spans"] = os.path.join(cell.workdir, "spans.json")
    tracer.export_chrome(capture["spans"])
    if profile_dir is not None:
        from chipbench.readers import xplane

        found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise BenchFailure(f"no .xplane.pb under {profile_dir}")
        data = xplane.load(found[0])
        note(trace_planes=xplane.describe(data))
        capture["device_trace"] = xplane.summary(data)
        if capture["device_trace"] is None:
            raise BenchFailure(
                "the trace has no plane named "
                f"{xplane.DEVICE_PLANE_PREFIX}* with a line "
                f"{xplane.OPS_LINE!r}")
