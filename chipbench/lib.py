"""What both runners share: the checks of an answer, the in-process
``pydcop`` CLI, the HTTP helpers (all lifted from ``chip_smoke.py``,
which proved them on the chip in PR 22), the lookup of a module by
name (runner, reader, family, reference), the instance and its shapes,
the percentile and the traced block."""

import contextlib
import glob
import importlib
import importlib.util
import io
import json
import math
import os
import statistics
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchFailure(Exception):
    """The run cannot give a result: no last line, exit code 1."""


def note(**fields):
    """A line of the run's own record, before the last line."""
    print(json.dumps(fields), flush=True)


def module_by_name(package, name, what, data_dir=HERE):
    """``<package>/<name>.py`` of the benchmark's data directory,
    imported (a family or a reference may be a file of a copy's own);
    where that directory is not ``chipbench/`` and has no such file (a
    rehearsal's copy holds only the files it adds), or none is given,
    ``chipbench/<package>/<name>.py``.  A name that resolves to no
    file names the files looked for."""
    own = os.path.join(HERE, package, f"{name}.py")
    path = os.path.join(os.path.abspath(data_dir), package, f"{name}.py")
    if path != own and os.path.isfile(path):
        spec = importlib.util.spec_from_file_location(
            f"chipbench_data.{package}.{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    if not os.path.isfile(own):
        looked = own if path == own else f"{path} nor {own}"
        raise BenchFailure(f"{what} {name!r}: no file {looked}")
    return importlib.import_module(f"chipbench.{package}.{name}")


def family_of(spec, data_dir=HERE):
    """The module of ``families/`` that a configuration's
    ``generator`` names.  There is no default family."""
    if "family" not in spec:
        raise BenchFailure(
            "the generator states no `family` (a file of "
            f"{os.path.join(HERE, 'families')})")
    return module_by_name("families", spec["family"], "family", data_dir)


def reference_of(config, data_dir=HERE):
    """The module of ``references/`` that a configuration's
    ``reference`` names: ``solve(dcop, cycles, seed) -> (assignment,
    cost)``, the cost in the problem's own sign.  There is no default
    reference."""
    if "reference" not in config:
        raise BenchFailure(
            "the configuration states no `reference` (a file of "
            f"{os.path.join(HERE, 'references')})")
    return module_by_name("references", config["reference"], "reference",
                          data_dir)


def placement(config):
    """Where a solve configuration's solves run: its ``shards`` or
    ``n_devices``, as keywords of ``api.solve`` (none: one device)."""
    return {key: config[key] for key in ("shards", "n_devices")
            if key in config}


def generate(spec, seed, data_dir=HERE):
    """The instance of the spec's family for this seed, as a DCOP
    object, from the program's own generator."""
    return family_of(spec, data_dir).generate(spec, seed)


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
    one-sided: on a problem whose ``objective`` is ``max`` the cost is
    a utility, and worse is lower), and the solve ended as the
    configuration's ``ends`` states.  ``compared`` holds each number
    that was compared beside its limit, ``{name: [value, limit]}``,
    each sound where value <= limit: on a max problem ``cost`` holds
    the negated utility and the negated floor."""
    assignment, cost = answer["assignment"], answer["cost"]
    sign = -1 if dcop.objective == "max" else 1
    limit = sign * reference_cost + tolerance * abs(reference_cost)
    compared = {
        "cost": [sign * cost, limit],
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
    elif sign * host_cost > limit:
        fault = (f"utility {host_cost} is worse than the reference's "
                 f"{reference_cost} by more than {tolerance:g} of it"
                 if sign < 0 else
                 f"cost {host_cost} is more than {tolerance:.0%} worse "
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
    highest against its limit (``check_answer`` signs it, so on a max
    problem the utility that lies lowest)."""
    return max(checked, key=lambda c: (
        c[0] is not None, c[1]["cost"][0] - c[1]["cost"][1]))[1]


def cost_ratio(dcop, cost, reference_cost):
    """An answer against the reference, 1 where they are equal and
    above 1 where the answer is worse: its cost over the reference's,
    and on a max problem one plus the utility it falls short by, as a
    share of the reference's (so for a utility of either sign).  None
    where the reference's value is 0, which gives no ratio."""
    if reference_cost == 0:
        return None
    if dcop.objective == "max":
        return 1 + (reference_cost - cost) / abs(reference_cost)
    return cost / reference_cost


def mean_cost_ratio(answers):
    """The mean ``cost_ratio`` of ``(dcop, cost, reference_cost)``
    triples; None where one of them gives no ratio."""
    ratios = [cost_ratio(*answer) for answer in answers]
    return None if None in ratios else statistics.fmean(ratios)


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
