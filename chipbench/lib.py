"""What both runners share: the checks of an answer, the in-process
``pydcop`` CLI, the HTTP helpers (all lifted from ``chip_smoke.py``,
which proved them on the chip in PR 22), the instance generator, the
percentile and the traced block."""

import contextlib
import glob
import io
import json
import math
import os
import time
import urllib.request

import numpy as np


class BenchFailure(Exception):
    """The run cannot give a result: no last line, exit code 1."""


def note(**fields):
    """A line of the run's own record, before the last line."""
    print(json.dumps(fields), flush=True)


def generate(spec, seed):
    """The instance ``pydcop generate graph_coloring`` builds, as a
    DCOP object (the function behind the command).  Where the
    configuration fixes ``constraints``, the random graph's edges are
    cut or filled to exactly that many, so that every seed has the
    same shapes and finds the same compiled program."""
    from pydcop_tpu.generators.graphcoloring import (
        generate_graph_coloring,
    )

    dcop = generate_graph_coloring(
        spec["variables"], spec["colors"], spec["graph"],
        soft=spec.get("soft", False), p_edge=spec.get("p_edge"),
        allow_subgraph=True, noagents=True, seed=seed)
    if "constraints" in spec:
        fix_constraint_count(dcop, spec["constraints"], seed)
    return dcop


def fix_constraint_count(dcop, count, seed):
    """Drop seeded-random constraints, or add copies of the (one)
    hard table between seeded-random pairs that share none yet."""
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    names = list(dcop.constraints)
    surplus = len(names) - count
    if surplus > 0:
        for i in rng.choice(len(names), surplus, replace=False):
            del dcop.constraints[names[i]]
        return
    table = np.asarray(dcop.constraints[names[0]].matrix)
    if any(not np.array_equal(c.matrix, table)
           for c in dcop.constraints.values()):
        raise BenchFailure("constraints can be added only where all "
                           "share one table (not to a soft instance)")
    variables = list(dcop.variables.values())
    taken = {frozenset(v.name for v in c.dimensions)
             for c in dcop.constraints.values()}
    while len(dcop.constraints) < count:
        i, j = sorted(rng.choice(len(variables), 2, replace=False))
        pair = frozenset((variables[i].name, variables[j].name))
        if pair not in taken:
            taken.add(pair)
            dcop.add_constraint(NAryMatrixRelation(
                [variables[i], variables[j]], table.copy(),
                f"c{len(dcop.constraints)}x"))


def pydcop(*args):
    """Run the ``pydcop`` CLI in this process (the chip's one
    process).  Its standard output is swallowed; results are read
    from the file given with ``--output``."""
    from pydcop_tpu.dcop_cli import main

    with contextlib.redirect_stdout(io.StringIO()):
        rc = main(list(args))
    if rc != 0:
        raise BenchFailure(f"pydcop {' '.join(args)} exited {rc}")


def answer_fault(dcop, assignment, cost, violations, reference_cost,
                 tolerance):
    """Why an answer is wrong, or None: it covers every variable, its
    reported cost and violations equal ``dcop.solution_cost`` on the
    host exactly, and the cost is no worse than the reference's by
    more than ``tolerance`` (relative, one-sided)."""
    if set(assignment) != set(dcop.variables):
        return (f"assignment covers {len(assignment)}/"
                f"{len(dcop.variables)} variables")
    host_cost, host_violations = dcop.solution_cost(assignment)
    if not np.isfinite(host_cost):
        return f"host cost {host_cost}"
    if float(cost) != float(host_cost):
        return f"reported cost {cost} != host cost {host_cost}"
    if int(violations) != int(host_violations):
        return (f"reported violations {violations} != host "
                f"{host_violations}")
    if host_cost > reference_cost + tolerance * abs(reference_cost):
        return (f"cost {host_cost} is more than {tolerance:.0%} worse "
                f"than the reference's {reference_cost}")
    return None


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
