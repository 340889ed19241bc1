"""Which message layout a MaxSum solve runs is the code's choice.

``layout`` is unset by default; ``algorithms/maxsum.select_layout``
resolves it from what ``build_engine`` is handed: lane-major
(ops/maxsum_lane.py) for the plain whole-solve program of one device
with the scatter aggregation, edge-major (ops/maxsum.py) wherever
something reads the edge-major arrays.  What must hold:

- the rule, case by case, and that a named layout is honoured with
  lane's refusals intact;
- every result says what ran (``metrics['layout']`` /
  ``['layout_source']``, the ``engine_call`` span's ``layout`` arg,
  ``pydcop solve``'s result file);
- a second default solve of the same shapes is warm;
- a checkpoint written by an edge-major engine (every checkpoint
  written before the default moved) still resumes under defaults;
- ``pydcop solve``'s post-hoc cost trace ends on the solve's cost;
- the two layouts agree at size as far as float32 reassociation of
  one sum per variable lets them (the numeric gate).
"""

import csv
import json
import os

import numpy as np
import pytest

from chipbench import lib
from pydcop_tpu import api
from pydcop_tpu.algorithms.maxsum import build_engine, select_layout
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.dcop.yamldcop import dcop_yaml, load_dcop_from_file
from pydcop_tpu.engine import aotcache, runner
from pydcop_tpu.engine.sharding import make_mesh
from pydcop_tpu.observability.trace import tracer
from pydcop_tpu.ops import maxsum as maxsum_ops
from pydcop_tpu.ops import maxsum_lane as lane_ops

CYCLES = 23
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ring(n: int = 14, seed: int = 1, chords: int = 3) -> DCOP:
    """A loopy ring of ``n`` 3-valued variables with seeded tables."""
    rng = np.random.default_rng(seed)
    dom = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"ring{n}_{seed}", objective="min")
    vs = [Variable(f"v{i:02d}", dom) for i in range(n)]
    for v in vs:
        dcop.add_variable(v)
    pairs = [(k, (k + 1) % n) for k in range(n)]
    pairs += [(k, (k + n // 2) % n) for k in range(chords)]
    for i, (a, b) in enumerate(pairs):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(
            NAryMatrixRelation([vs[a], vs[b]], table, f"c{i}"))
    dcop.add_agents([AgentDef("a0")])
    return dcop


# ------------------------------------------------------------------ #
# (a) the rule

# What build_engine is handed -> the layout an unset param resolves to.
UNSET = {
    "plain": (dict(), "lane"),
    "n_devices_1": (dict(n_devices=1), "lane"),
    "n_devices_2": (dict(n_devices=2), "edge"),
    "mesh": (dict(mesh=2), "edge"),
    "shards_2": (dict(shards=2), "edge"),
    "prune": (dict(params={"prune": True}), "edge"),
    "aggregation_sorted": (dict(params={"aggregation": "sorted"}),
                           "edge"),
    "aggregation_ell": (dict(params={"aggregation": "ell"}), "edge"),
    "aggregation_auto": (dict(params={"aggregation": "auto"}), "edge"),
    "decimation": (dict(params={"decimation": 10}), "edge"),
    "decimation_margin": (dict(params={"decimation_margin": 0.5}),
                          "edge"),
    "segmented": (dict(whole_solve=False), "edge"),
}


@pytest.mark.parametrize("case", list(UNSET))
def test_an_unset_layout_resolves_from_what_the_engine_is_handed(case):
    given, expected = UNSET[case]
    given = dict({"whole_solve": True, "params": {}}, **given)
    if "mesh" in given:
        given["mesh"] = make_mesh(given["mesh"])
    params = given.pop("params")
    assert select_layout(params, **given) == (expected, "selected")
    # An explicit None is what prepare_algo_params hands on.
    assert select_layout(dict(params, layout=None), **given) \
        == (expected, "selected")


def test_the_pallas_flag_keeps_the_solve_edge_major(monkeypatch):
    assert select_layout({}, whole_solve=True) == ("lane", "selected")
    monkeypatch.setattr(maxsum_ops, "_PALLAS_FLAG", True)
    assert select_layout({}, whole_solve=True) == ("edge", "selected")


@pytest.mark.parametrize("asked", ["edge", "lane"])
@pytest.mark.parametrize("whole_solve", [True, False])
def test_a_named_layout_is_honoured(asked, whole_solve):
    engine = build_engine(ring(), {"layout": asked},
                          whole_solve=whole_solve)
    assert engine.layout == asked
    assert engine._ops is (lane_ops if asked == "lane" else maxsum_ops)
    result = engine.run(max_cycles=CYCLES)
    assert result.metrics["layout"] == asked
    assert result.metrics["layout_source"] == "param"


REFUSED = {
    "n_devices": (dict(n_devices=2), ValueError),
    "mesh": (dict(mesh=2), ValueError),
    "shards": (dict(shards=2), ValueError),
    "prune": (dict(params={"prune": True}), ValueError),
    "aggregation_sorted": (dict(params={"aggregation": "sorted"}),
                           ValueError),
    "aggregation_ell": (dict(params={"aggregation": "ell"}), ValueError),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_a_named_lane_layout_still_refuses_what_it_cannot_run(case):
    given, error = REFUSED[case]
    given = dict(given)
    if "mesh" in given:
        given["mesh"] = make_mesh(given["mesh"])
    params = dict(given.pop("params", {}), layout="lane")
    with pytest.raises(error):
        build_engine(ring(), params, **given)


def test_a_named_lane_layout_refuses_decimation():
    with pytest.raises(ValueError, match="edge"):
        api.solve(ring(), "maxsum", max_cycles=CYCLES,
                  algo_params={"layout": "lane", "decimation": 10})


# ------------------------------------------------------------------ #
# (b) through api.solve: what ran, and that every result says so

def _solve(dcop=None, algo="maxsum", **kwargs):
    return api.solve(dcop or ring(), algo, max_cycles=CYCLES, **kwargs)


def test_the_plain_solve_runs_lane_major_and_says_so():
    res = _solve()
    assert res["metrics"]["layout"] == "lane"
    assert res["metrics"]["layout_source"] == "selected"
    named = _solve(algo_params={"layout": "lane"})
    assert named["metrics"]["layout_source"] == "param"
    assert named["assignment"] == res["assignment"]
    assert named["cost"] == res["cost"]


@pytest.mark.parametrize("algo", ["amaxsum", "maxsum_dynamic"])
def test_the_algorithms_that_delegate_follow_the_rule(algo):
    res = _solve(algo=algo)
    assert res["metrics"]["layout"] == "lane"
    assert res["metrics"]["layout_source"] == "selected"


API_EDGE = {
    "n_devices": lambda tmp: dict(n_devices=2),
    "shards": lambda tmp: dict(shards=2),
    "prune": lambda tmp: dict(algo_params={"prune": True}),
    "aggregation_ell": lambda tmp: dict(
        algo_params={"aggregation": "ell"}),
    "decimation": lambda tmp: dict(algo_params={"decimation": 20}),
    "checkpoint_dir": lambda tmp: dict(checkpoint_dir=str(tmp / "ck"),
                                       checkpoint_every=10),
    "metrics_file": lambda tmp: dict(
        metrics_file=str(tmp / "metrics.jsonl"), metrics_every=10),
    "recovery": lambda tmp: dict(recovery=_policy()),
}


def _policy():
    from pydcop_tpu.resilience.recovery import RecoveryPolicy

    return RecoveryPolicy()


@pytest.mark.parametrize("case", list(API_EDGE))
def test_what_reads_the_edge_major_arrays_keeps_the_edge_layout(
        case, tmp_path):
    """Each of these solves as it did before the default moved: the
    answer of a solve that names ``layout: edge``."""
    kwargs = API_EDGE[case](tmp_path)
    res = _solve(**kwargs)
    assert res["metrics"]["layout"] == "edge"
    assert res["metrics"]["layout_source"] == "selected"
    if case == "checkpoint_dir":
        kwargs["checkpoint_dir"] = str(tmp_path / "ck_named")
    if case == "metrics_file":
        kwargs["metrics_file"] = str(tmp_path / "metrics_named.jsonl")
    if case == "recovery":
        kwargs["recovery"] = _policy()
    params = dict(kwargs.pop("algo_params", {}), layout="edge")
    named = _solve(algo_params=params, **kwargs)
    assert named["assignment"] == res["assignment"]
    assert named["cost"] == res["cost"]
    assert named["cycles"] == res["cycles"]


def test_the_engine_call_span_carries_the_layout():
    dcop = ring()
    tracer.enable()
    try:
        _solve(dcop)
        _solve(dcop, algo_params={"layout": "edge"})
        calls = [e for e in tracer.events()
                 if e["name"] in ("engine_call", "jit_compile")]
    finally:
        tracer.disable()
        tracer.clear()
    assert [c["args"]["layout"] for c in calls] == ["lane", "edge"]
    assert all("lane" in c["args"]["key"] or "edge" in c["args"]["key"]
               for c in calls)


def test_a_second_default_solve_of_the_same_shapes_is_warm():
    first = _solve(ring(seed=1))
    assert first["metrics"]["cold_start"] is True
    counters = aotcache.counters()
    programs = len(runner._process_programs)
    second = _solve(ring(seed=2))  # another problem, the same shapes
    assert second["metrics"]["layout"] == "lane"
    assert second["metrics"]["cold_start"] is False
    assert second["compile_time"] == 0.0
    assert aotcache.counters() == counters
    assert len(runner._process_programs) == programs


# ------------------------------------------------------------------ #
# (c) checkpoints and the CLI's cost trace

def test_an_edge_major_checkpoint_resumes_under_the_defaults(tmp_path):
    """Every checkpoint on disk from before the default moved holds
    an edge-major state.  The segmented path still builds the edge
    engine, so it restores into the same pytree and ends where the
    uninterrupted edge solve ends."""
    dcop = ring(seed=3)
    whole = api.solve(dcop, "maxsum", max_cycles=40,
                      algo_params={"layout": "edge", "stability": 0.0})
    interrupted = build_engine(
        dcop, {"layout": "edge", "stability": 0.0}).run_checkpointed(
            max_cycles=40, checkpoint_dir=str(tmp_path),
            segment_cycles=10, max_segments=2)
    assert interrupted.metrics["interrupted"] and interrupted.cycles == 20
    resumed = api.solve(dcop, "maxsum", max_cycles=40,
                        algo_params={"stability": 0.0},
                        checkpoint_dir=str(tmp_path), resume=True)
    assert resumed["metrics"]["layout"] == "edge"
    assert resumed["metrics"]["resumed_from_cycle"] == 20
    assert resumed["cycles"] == whole["cycles"] == 40
    assert resumed["assignment"] == whole["assignment"]
    assert resumed["cost"] == whole["cost"]


CLI_CASES = {
    "default": ([], "lane", "selected"),
    "layout_edge": (["-p", "layout:edge"], "edge", "param"),
    "layout_lane": (["-p", "layout:lane"], "lane", "param"),
    "n_devices_2": (["--n_devices", "2"], "edge", "selected"),
}


@pytest.mark.parametrize("case", list(CLI_CASES))
def test_the_cli_cost_trace_ends_on_the_solves_own_cost(case, tmp_path):
    """``pydcop solve`` rebuilds an engine for its per-cycle cost
    rows: it names the layout the solve reported, so the rows follow
    the solve's trajectory whichever path selected it."""
    extra, layout, source = CLI_CASES[case]
    problem = tmp_path / "ring.yaml"
    problem.write_text(dcop_yaml(ring(seed=4)), encoding="utf-8")
    rows_path, out_path = tmp_path / "rows.csv", tmp_path / "out.json"
    lib.pydcop("--output", str(out_path), "solve", "-a", "maxsum",
               "-c", "30", *extra, "--collect_on", "cycle_change",
               "--run_metrics", str(rows_path), str(problem))
    result = json.loads(out_path.read_text())
    assert (result["layout"], result["layout_source"]) == (layout, source)
    rows = list(csv.DictReader(rows_path.read_text().splitlines()))
    cycle_rows = [r for r in rows if r["status"] == "RUNNING"]
    assert len(cycle_rows) == result["cycle"]
    assert float(cycle_rows[-1]["cost"]) == pytest.approx(
        result["cost"], abs=1e-4)
    # The rows are the named layout's own trace, to the bit.
    dcop = load_dcop_from_file([str(problem)])
    kwargs = {"n_devices": 2} if case == "n_devices_2" else {}
    trace = build_engine(dcop, {"layout": layout}, **kwargs).run_trace(
        max_cycles=result["cycle"]).metrics["cost_trace"]
    assert [float(r["cost"]) for r in cycle_rows] \
        == [float(c) for c in trace]


# ------------------------------------------------------------------ #
# (d) the numeric gate: the layouts' disagreement at size
#
# The lane scatter sums a variable's incoming messages in (position,
# factor) order, the edge one in (factor, position): one float32
# reassociation per variable and cycle, which on a loopy instance can
# tip a near-tie and part the trajectories.  Read here (sandbox CPU,
# PR 36): all six instances equal to the last value and the last
# digit of the cost (share 1.0, difference 0).  The bounds leave room
# for a few tipped ties and none for a layout fault: a random
# assignment of the small colouring costs ten times the solver's,
# and agrees with it on a third of the values.  PERF.md section 6
# has the readings, and the same two numbers at 10k on the chip.

def _gc_small():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gc_random_10k.json"), encoding="utf-8") as f:
        spec = json.load(f)["generator"]
    return lib.family_of(spec).small(spec), 200


def _grid():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "serve_gc_grid100.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    return config["generator"], config["params"]["max_cycles"]


# family -> (spec and cycles, least share of equal values, largest
# relative cost difference)
GATE = {
    "gc_random_small": (_gc_small, 0.95, 0.10),
    "soft_grid_10x10": (_grid, 0.95, 0.02),
}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("family", list(GATE))
def test_the_layouts_agree_at_size(family, seed):
    make, least_equal, most_cost = GATE[family]
    spec, cycles = make()
    dcop = lib.generate(spec, seed)
    edge, lane = (
        api.solve(dcop, "maxsum", max_cycles=cycles,
                  algo_params={"layout": layout})
        for layout in ("edge", "lane"))
    assert edge["metrics"]["layout"] == "edge"
    assert lane["metrics"]["layout"] == "lane"
    equal = sum(edge["assignment"][k] == lane["assignment"][k]
                for k in edge["assignment"]) / len(edge["assignment"])
    cost_diff = abs(edge["cost"] - lane["cost"]) / max(
        abs(edge["cost"]), 1.0)
    print(f"gate {family} seed {seed}: equal values {equal:.6f}, "
          f"relative cost difference {cost_diff:.6g}, costs "
          f"{edge['cost']} / {lane['cost']}, cycles "
          f"{edge['cycles']} / {lane['cycles']}")
    assert equal >= least_equal
    assert cost_diff <= most_cost
