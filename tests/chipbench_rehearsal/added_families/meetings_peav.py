"""``pydcop generate meetings`` (PEAV: one variable per person and
meeting over the slots, 0 = not held; ``objective: max``), as a
rehearsal's copy of the benchmark adds a family: a file of its own in
the copy's ``families/``, which ``chipbench`` does not have.

Spec keys: ``slots``, ``events``, ``resources``,
``max_resources_event`` (the command's arguments), and ``variables``
and ``factors_by_arity``: the counts every seed's instance has.  How
many people a meeting takes is drawn, so the instance is the first
draw of the seed's sequence (``[seed, 0]``, ``[seed, 1]``, ...) that
has those counts.
"""

from chipbench.lib import BenchFailure

TRIES = 1000


def generate(spec, seed):
    from chipbench import lib
    from pydcop_tpu.generators.meetingscheduling import generate_meetings

    stated = shapes(spec)
    for attempt in range(TRIES):
        dcop = generate_meetings(
            spec["slots"], spec["events"], spec["resources"],
            spec["max_resources_event"], no_agents=True,
            seed=[seed, attempt])
        if lib.shapes(dcop) == stated:
            return dcop
    raise BenchFailure(f"no draw of {TRIES} for seed {seed} has the "
                       f"shapes {stated}")


def shapes(spec):
    return {"variables": spec["variables"], "domain": spec["slots"] + 1,
            "factors_by_arity": {int(arity): count for arity, count
                                 in spec["factors_by_arity"].items()}}


def small(spec):
    return spec


def check(spec):
    """Every meeting has one person at least and
    ``max_resources_event`` at most."""
    if not (spec["events"] <= spec["variables"]
            <= spec["events"] * spec["max_resources_event"]):
        raise ValueError(
            f"{spec['variables']} variables cannot come of "
            f"{spec['events']} meetings of 1 to "
            f"{spec['max_resources_event']} people")
