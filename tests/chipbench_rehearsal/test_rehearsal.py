"""The benchmark's own control flow and arithmetic, rehearsed on the
CPU at tiny sizes.

The cells are the test's own: a temporary BENCHMARK.json with tiny
configurations and mixes beside a copy of ``chipbench/metrics``, given
to the harness with ``--bench``.  The expected platform is patched
from here (``run.PLATFORM``), not an option of the command.  A CPU run
proves paths, keys and counts, never a time: with the platform patched
it makes no ``jax.profiler`` capture and reports no device-derived
metric.
"""

import itertools
import json
import os
import shutil
import statistics

import numpy as np
import pytest
from rehearsal_benchmarks import (
    CHIPBENCH,
    WHICH,
    benchmark,
    per_benchmark,
    per_layer_names,
)

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                  "compared"}
DEVICE_DERIVED = {"kernel.superstep_us", "maxsum_superstep_roofline",
                  "device.idle.solve", "device.idle.serve"}
END_TO_END = {
    "solve": {"setup_s", "cli_solve_s", "solve_p50_s"},
    "serve": {"setup_s", "serve_problems_per_s", "serve_p95_ms"},
}
# The per-layer metrics a traced CPU run of each tiny cell prints.
CPU_PER_LAYER = {
    "solve": {"yaml.load_s", "hostcompile.ms", "engine.ms",
              "entry.self_ms", "xla.compiles.solve", "solve.cost_ratio"},
    "serve": {"serve.frontend_ms", "serve.queue_ms", "serve.prep_ms",
              "serve.execute_ms", "serve.batch_mean",
              "xla.compiles.serve", "serve.cost_ratio"},
}
TINY_CONFIGS = {
    "tiny_solve": {
        "name": "tiny_solve", "kind": "solve",
        "generator": {"family": "graph_coloring", "variables": 30,
                      "colors": 3, "graph": "random", "p_edge": 0.3,
                      "constraints": 130},
        "algo": "maxsum", "algo_params": {}, "max_cycles": 30,
        "ends": {"status": "TIMEOUT", "cycles": 30}, "cli_solve": True,
        "cost_tolerance": 3.0},
    "tiny_serve": {
        "name": "tiny_serve", "kind": "serve",
        "generator": {"family": "graph_coloring", "variables": 16,
                      "colors": 3, "graph": "grid", "soft": True},
        "pool": 3, "params": {"max_cycles": 30},
        "service": {"batch_window_s": 0.005, "max_batch": 4,
                    "max_queue": 64},
        "cost_tolerance": 0.5},
}
TINY_TRAFFIC = {
    "resolve": {"name": "resolve", "kind": "solve", "warmup_solves": 1,
                "traced_solves": 2},
    "closed_2": {"name": "closed_2", "kind": "serve", "callers": 2,
                 "warmup_bursts": [1, 2], "warmup_rounds": 1,
                 "traced_seconds": 1},
}
TINY_CELLS = {"solve": "tiny_solve.resolve", "serve": "tiny_serve.closed_2"}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture
def bench(tmp_path):
    """A BENCHMARK.json of two tiny cells; returns its path.  It lists
    the per-layer metrics the assertions of this file name, whatever
    else the repository's own file lists by now: a metric a later PR
    adds is rehearsed in a file of its own."""
    data = tmp_path / "data"
    shutil.copytree(os.path.join(CHIPBENCH, "metrics"), data / "metrics")
    for name, config in TINY_CONFIGS.items():
        write_json(str(data / "configs" / f"{name}.json"), config)
    for name, mix in TINY_TRAFFIC.items():
        write_json(str(data / "traffic" / f"{name}.json"), mix)
    units = {m["name"]: m["unit"] for m in benchmark("real")["end_to_end"]}
    bench_path = tmp_path / "BENCHMARK.json"
    write_json(str(bench_path), {
        "configs": [{"name": n, "file": f"data/configs/{n}.json"}
                    for n in TINY_CONFIGS],
        "workloads": [
            {"name": "tiny_solve.resolve", "config": "tiny_solve",
             "traffic": "resolve", "chips": 1},
            {"name": "tiny_serve.closed_2", "config": "tiny_serve",
             "traffic": "closed_2", "chips": 1}],
        "end_to_end": [
            {"name": name, "unit": units.get(name, "s"),
             "workloads": [TINY_CELLS[kind] for kind in END_TO_END
                           if name in END_TO_END[kind]]}
            for name in sorted(set().union(*END_TO_END.values()))],
        "per_layer": [{"name": name} for name in sorted(
            DEVICE_DERIVED.union(*CPU_PER_LAYER.values()))],
    })
    return str(bench_path)


@pytest.fixture
def harness(monkeypatch, tmp_path):
    """``chipbench.run`` expecting the platform the tests run on, with
    the compile cache in the test's own directory; the process's
    cache settings are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from chipbench import run
    from pydcop_tpu.engine import aotcache

    monkeypatch.setattr(run, "PLATFORM", "cpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("PYDCOP_COMPILE_CACHE_DIR", str(tmp_path / "jaxcache"))

    def no_capture(*args, **kwargs):
        raise AssertionError("a CPU rehearsal made a jax.profiler capture")

    monkeypatch.setattr(jax.profiler, "trace", no_capture)
    cache_dir = jax.config.jax_compilation_cache_dir
    with aotcache._lock:
        state = dict(aotcache._state)
    yield run
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()
    with aotcache._lock:
        aotcache._state.update(state)


def last_line(capsys, compared_of=None):
    """The result line and the notes before it.  Where ``compared_of``
    names the kind of cell that ran sound, the line has to end with
    the numbers compared beside their limits, and standard error too."""
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    line = json.loads(lines[-1])
    if compared_of is not None:
        compared_is_last_and_sound(line, captured.err, compared_of)
    return line, [json.loads(x) for x in lines[:-1] if x.startswith("{")]


def compared_is_last_and_sound(line, err, kind):
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    names = {"cost", "cost_minus_host", "violations_minus_host",
             "unassigned"}
    names |= ({"cycles"} if kind == "solve"
              else {"not_finished", "repeats_differing"})
    assert set(compared) == names
    for name, (value, limit) in compared.items():
        assert value <= limit, name
    cost, limit = compared["cost"]
    assert 0 < cost <= limit
    if kind == "solve":
        ends = TINY_CONFIGS["tiny_solve"]["ends"]
        assert compared["cycles"] == [ends["cycles"], ends["cycles"]]
    # The same, as the last lines of standard error.
    said = [x for x in err.splitlines()
            if x.startswith("chipbench: compared ")]
    assert said == err.splitlines()[-len(compared):]
    assert [x.split()[2].rstrip(":") for x in said] == list(compared)


def unread(notes):
    """The listed per-layer metrics a traced run says it could not
    read."""
    return next(n["listed_and_read_nothing"] for n in notes
                if "listed_and_read_nothing" in n)


def run_cell(run, bench, kind, trace, seed=3000000001):
    return run.main(["--workload", TINY_CELLS[kind], "--seed", str(seed),
                     "--seconds", "1", "--trace", str(trace),
                     "--bench", bench])


# --------------------------------------------------------------------- #
# each kind, end to end


@pytest.mark.parametrize("kind", ["solve", "serve"])
def test_untraced_run_prints_the_end_to_end_metrics(
        harness, bench, capsys, kind):
    assert run_cell(harness, bench, kind, 0) == 0
    line, _ = last_line(capsys, compared_of=kind)
    assert set(line) == LAST_LINE_KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == END_TO_END[kind]
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert metric["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("kind", ["solve", "serve"])
def test_traced_run_prints_the_per_layer_metrics_a_cpu_can_give(
        harness, bench, capsys, kind):
    assert run_cell(harness, bench, kind, 1) == 0
    line, notes = last_line(capsys, compared_of=kind)
    assert set(line) == LAST_LINE_KEYS | {"breakdown"}
    assert line["correct"] is True
    # No number from a CPU run under a device metric's name.
    assert set(line["metrics"]) == CPU_PER_LAYER[kind]
    assert not set(line["metrics"]) & DEVICE_DERIVED
    # The run says itself which listed metrics read nothing: here the
    # device's and the other kind's.
    assert set(unread(notes)) == DEVICE_DERIVED.union(
        *CPU_PER_LAYER.values()) - CPU_PER_LAYER[kind]
    assert "busy_s" not in line["device"]
    assert "device_ops" not in line["breakdown"]
    assert line["breakdown"]["idle_gaps"]
    assert line["metrics"][f"xla.compiles.{kind}"]["value"] >= 0


def test_serve_batches_and_answers_every_request(harness, bench, capsys):
    assert run_cell(harness, bench, "serve", 0, seed=7) == 0
    line, notes = last_line(capsys)
    service = next(n["service"] for n in notes if "service" in n)
    assert service["completed"] == line["attempted"]
    assert service["failed"] == service["expired"] == 0
    assert service["deduped"] == 0


def test_a_wrong_cost_makes_correct_false(harness, bench, capsys,
                                          monkeypatch):
    from pydcop_tpu import api

    honest = api.solve

    def off_by_one(*args, **kwargs):
        result = honest(*args, **kwargs)
        result["cost"] += 1
        return result

    monkeypatch.setattr(api, "solve", off_by_one)
    assert run_cell(harness, bench, "solve", 0) == 0
    line, notes = last_line(capsys)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any("reported cost" in n.get("fault", "") for n in notes)
    # The planted fault shows in the numbers compared, of the worst
    # answer, beside their limits.
    assert line["compared"]["cost_minus_host"] == [1.0, 0]
    assert line["compared"]["violations_minus_host"] == [0, 0]


def test_an_infinite_cost_is_printed_as_a_string_and_is_not_correct(
        harness, bench, capsys, monkeypatch):
    """The last line has to parse under a strict JSON parser, which
    refuses ``Infinity`` and ``NaN``."""
    from pydcop_tpu import api

    honest = api.solve

    def infinite(*args, **kwargs):
        return dict(honest(*args, **kwargs), cost=float("inf"))

    def refuse(constant):
        raise AssertionError(f"{constant} in the last line")

    monkeypatch.setattr(api, "solve", infinite)
    assert run_cell(harness, bench, "solve", 0) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1],
                      parse_constant=refuse)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["compared"]["cost"][0] == "inf"
    assert line["compared"]["cost_minus_host"] == ["inf", 0]
    assert "chipbench: compared cost: 'inf'" in captured.err
    # A limit that is not finite fails the run too, whatever the cost.
    assert harness.strict({"cost": [3.0, float("inf")],
                           "unassigned": [0, 0]}) == (
        {"cost": [3.0, "inf"], "unassigned": [0, 0]}, False)
    assert harness.strict({"cost": [3.0, 4.0]}) == (
        {"cost": [3.0, 4.0]}, True)


def test_no_chip_no_result(bench, capsys):
    from chipbench import run

    assert run.PLATFORM == "tpu"
    assert run.main(["--workload", TINY_CELLS["solve"], "--seed", "1",
                     "--seconds", "1", "--bench", bench]) == 1
    captured = capsys.readouterr()
    assert "not 'tpu'" in captured.err
    assert not any("correct" in x for x in captured.out.splitlines())


# --------------------------------------------------------------------- #
# a name that resolves to no file names the file looked for


def _edit(path, **changes):
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    obj.update(changes)
    write_json(path, obj)


@pytest.mark.parametrize("what", ["cell", "config", "mix", "runner"])
def test_an_unknown_name_fails_naming_what_was_looked_for(
        harness, bench, capsys, what):
    data = os.path.join(os.path.dirname(bench), "data")
    cell = TINY_CELLS["solve"]
    if what == "cell":
        cell, looked_for = "no_such_cell", "no_such_cell"
    elif what == "config":
        looked_for = os.path.join(data, "configs", "tiny_solve.json")
        os.remove(looked_for)
    elif what == "mix":
        looked_for = os.path.join(data, "traffic", "resolve.json")
        os.remove(looked_for)
    else:
        for path in (os.path.join(data, "configs", "tiny_solve.json"),
                     os.path.join(data, "traffic", "resolve.json")):
            _edit(path, kind="nokind")
        looked_for = os.path.join(CHIPBENCH, "runners", "nokind.py")
    assert harness.main(["--workload", cell, "--seed", "1", "--seconds",
                         "1", "--bench", bench]) == 1
    captured = capsys.readouterr()
    assert looked_for in captured.err
    assert "correct" not in captured.out


def test_an_unknown_reader_fails_naming_its_file(tmp_path):
    from chipbench import run
    from chipbench.lib import BenchFailure

    write_json(str(tmp_path / "metrics" / "m.json"), {
        "name": "m", "unit": "s", "kinds": ["solve"], "reader": "nope"})
    with pytest.raises(BenchFailure) as failure:
        run.per_layer_metrics(str(tmp_path), "solve", {})
    assert os.path.join(CHIPBENCH, "readers", "nope.py") in str(
        failure.value)


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(tmp_path):
    from chipbench import run

    shutil.copytree(os.path.join(CHIPBENCH, "metrics"),
                    tmp_path / "metrics")
    assert run.per_layer_metrics(str(tmp_path), "solve", {}) == {}
    assert run.per_layer_metrics(str(tmp_path), "serve", {}) == {}


# --------------------------------------------------------------------- #
# the reductions, on hand-built events


def test_interval_union():
    from chipbench.readers.spans import union_length

    assert union_length([]) == 0
    assert union_length([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert union_length([(20, 30), (0, 10), (10, 12)]) == 22


def test_xplane_busy_time_and_self_time_of_operations():
    from chipbench.readers import xplane

    loop = ("%while.3 = (f32[8,3]{1,0:T(8,128)S(1)}) while((f32[8,3]{1,0}) "
            "%tuple.5), condition=%cond, body=%body")
    fusion = ("%fusion.48 = f32[8,3]{1,0:T(8,128)} fusion(s32[16]{0} %g), "
              "kind=kCustom, calls=%fused")
    events = [(loop, 0, 100), (fusion, 10, 30), (fusion, 50, 30),
              ("%copy.1 = f32[3]{0} copy(f32[3]{0} %x)", 120, 5)]
    assert xplane.busy_ns(events) == 105
    totals = dict(xplane.op_totals(events))
    assert totals == {fusion: 60, loop: 40, events[3][0]: 5}
    assert xplane.short_name(fusion) == "%fusion.48 fusion kCustom"
    assert xplane.short_name(loop) == "%while.3 while"
    capture = {"device_trace": {"busy_s": 0.5}, "traced_wall_s": 2.0,
               "values": {"cycles": 500}}
    assert xplane.read(capture, "idle_share") == 75.0
    assert xplane.read(capture, "busy_per", per="cycles",
                       scale=1e6) == 1000.0
    assert xplane.read({}, "idle_share") is None


def _span(name, span_id, parent, ts, dur):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur,
            "args": {"span_id": span_id, "parent_id": parent}}


def test_span_self_time_and_reductions(tmp_path):
    from chipbench.readers import spans

    events = [
        _span("solve", 1, 0, 0, 1000),
        _span("compile_graph", 2, 1, 100, 300),
        _span("jit_compile", 3, 1, 350, 400),   # overlaps its sibling
        _span("solve", 4, 0, 2000, 500),
        _span("engine_call", 5, 4, 2100, 200),
        {"name": "mark", "ph": "i", "ts": 5, "args": {}},
    ]
    path = str(tmp_path / "spans.json")
    write_json(path, {"traceEvents": events})
    loaded = spans.load(path)
    assert len(loaded) == 5
    own = spans.by_name(loaded, "self")
    assert own["solve"] == [1000 - 650, 500 - 200]
    assert own["compile_graph"] == [300]
    capture = {"spans": path}
    assert spans.read(capture, ["solve"], stat="self", reduce="mean",
                      scale=0.001) == pytest.approx(0.325)
    assert spans.read(capture, ["jit_compile", "engine_call"],
                      reduce="per", per="solve") == 300
    assert spans.read(capture, ["compile_graph"], reduce="median") == 300
    assert spans.read(capture, ["absent"]) is None
    assert spans.read({}, ["solve"]) is None


def test_stats_and_counters_readers_take_differences():
    from chipbench.readers import counters, stats

    capture = {
        "stats_before": {"completed": 10, "dispatches": 4, "efficiency": {
            "ledger_components_s": {"queue": 1.0, "prep": 2.0}}},
        "stats_after": {"completed": 30, "dispatches": 9, "efficiency": {
            "ledger_components_s": {"queue": 2.0, "prep": 6.0}}},
        "values": {"latency_mean_ms": 400.0},
        "counters_before": {"hits": 3, "misses": 1},
        "counters_after": {"hits": 5, "misses": 1},
    }
    assert stats.read(capture, ["efficiency.ledger_components_s.queue"],
                      per="completed", scale=1000.0) == 50.0
    assert stats.read(capture, ["completed"], per="dispatches") == 4.0
    assert stats.read(
        capture, ["efficiency.ledger_components_s"], per="completed",
        scale=1000.0, subtract_from="latency_mean_ms") == 150.0
    assert stats.read({}, ["completed"]) is None
    assert counters.read(capture, ["misses"]) == 0
    assert counters.read(capture, ["hits", "misses"]) == 2
    assert counters.read({}, ["misses"]) is None


def test_roofline_bytes_and_peaks():
    from chipbench import roofline
    from chipbench.readers import roofline as reader

    shapes = {"variables": 10000, "domain": 3,
              "factors_by_arity": {2: 15000}}
    # 4 passes over [V, D] + tables + 6 passes over [F, 2, D] + indices.
    assert roofline.maxsum_superstep_bytes(**shapes) == (
        4 * 10000 * 3 * 4 + 15000 * 9 * 4 + 6 * 15000 * 2 * 3 * 4
        + 15000 * 2 * 4)
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peak("cpu", "hbm_bytes_per_s")
    capture = {"device_trace": {"busy_s": 0.2}, "shapes": shapes,
               "values": {"cycles": 200}, "device_kind": "TPU v5 lite"}
    share = reader.read(capture, per="cycles")
    assert share == pytest.approx(
        100 * (3300000 / 819e9) / 0.001, rel=1e-9)
    assert reader.read({"values": {}}, per="cycles") is None


def test_percentile_is_nearest_rank_over_all_values():
    from chipbench.lib import percentile

    values = list(range(1, 101))
    assert percentile(values, 0.95) == 95
    assert percentile([5.0], 0.95) == 5.0
    assert percentile([3, 1, 2], 0.5) == 2


def test_spread_is_the_quartile_distance_over_the_median():
    from chipbench import sets

    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert sets.spread(values) == (q3 - q1) / statistics.median(values)


# --------------------------------------------------------------------- #
# the reference, the instance and the checks


def _random_problem(rng, scopes, size):
    """A DCOP of ``size`` values a variable and one factor of random
    costs over each scope."""
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.objects import Domain, Variable
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    domain = Domain("d", "d", [f"x{i}" for i in range(size)])
    variables = [Variable(f"v{i}", domain)
                 for i in range(1 + max(max(scope) for scope in scopes))]
    dcop = DCOP("random", objective="min")
    for i, scope in enumerate(scopes):
        dcop.add_constraint(NAryMatrixRelation(
            [variables[j] for j in scope],
            rng.random((size,) * len(scope)) * 10, f"c{i}"))
    return dcop


def _brute_force(dcop):
    names = list(dcop.variables)
    return min(
        dcop.solution_cost(dict(zip(names, values)))[0]
        for values in itertools.product(
            *(dcop.variables[n].domain.values for n in names)))


def test_reference_finds_the_optimum_of_a_tree():
    from chipbench import reference

    dcop = _random_problem(np.random.default_rng(5), [
        (0, 1), (0, 2), (1, 3), (1, 4), (2, 5)], 3)
    assignment, cost = reference.solve(dcop, cycles=30, seed=1)
    assert cost == pytest.approx(_brute_force(dcop))
    assert dcop.solution_cost(assignment)[0] == pytest.approx(cost)


FAMILY_SPECS = {
    "graph_coloring-cut": {
        "family": "graph_coloring", "variables": 40, "colors": 3,
        "graph": "random", "p_edge": 0.06, "constraints": 30},
    "graph_coloring-filled": {
        "family": "graph_coloring", "variables": 40, "colors": 3,
        "graph": "random", "p_edge": 0.06, "constraints": 60},
    "graph_coloring-grid": {
        "family": "graph_coloring", "variables": 16, "colors": 3,
        "graph": "grid", "soft": True},
    "secp": {
        "family": "secp", "lights": 56, "models": 17, "rules": 28,
        "max_model_size": 3, "max_rule_size": 3,
        "factors_by_arity": {"1": 60, "2": 6, "3": 11, "4": 4}},
}


def test_a_family_that_is_no_file_fails_naming_the_file_looked_for():
    from chipbench import lib

    with pytest.raises(lib.BenchFailure) as failure:
        lib.generate({"family": "nofamily"}, 1)
    assert os.path.join(CHIPBENCH, "families", "nofamily.py") in str(
        failure.value)
    # There is no default family.
    with pytest.raises(lib.BenchFailure, match="states no `family`"):
        lib.generate({"variables": 9, "colors": 3, "graph": "grid"}, 1)


def test_check_answer_names_each_way_an_answer_can_be_wrong():
    from chipbench import lib

    dcop = lib.generate(FAMILY_SPECS["graph_coloring-grid"], 1)
    assignment = {name: "R" for name in dcop.variables}
    cost, violations = dcop.solution_cost(assignment)
    answer = {"assignment": assignment, "cost": cost,
              "violations": violations, "status": "TIMEOUT", "cycles": 30}

    def fault(reference_cost=cost, tolerance=0.0, ends=None, **altered):
        return lib.check_answer(dcop, dict(answer, **altered),
                                reference_cost, tolerance, ends)

    assert fault() == (None, {
        "cost": [cost, cost], "unassigned": [0, 0],
        "cost_minus_host": [0.0, 0], "violations_minus_host": [0, 0]})
    said, compared = fault(
        assignment=dict(list(assignment.items())[:-1]))
    assert "covers 15/16" in said and compared["unassigned"] == [1, 0]
    said, compared = fault(cost=cost + 1)
    assert "reported cost" in said
    assert compared["cost_minus_host"] == [1.0, 0]
    said, compared = fault(violations=violations + 1)
    assert "reported violations" in said
    assert compared["violations_minus_host"] == [1, 0]
    said, compared = fault(cost / 2, 0.5)
    assert "worse than the reference" in said
    assert compared["cost"] == [cost, 0.75 * cost]
    assert fault(cost / 2, 1.0)[0] is None
    ends = {"status": "TIMEOUT", "cycles": 30}
    assert fault(ends=ends) == (None, dict(fault()[1], cycles=[30, 30]))
    said, compared = fault(ends=ends, status="FINISHED", cycles=17)
    assert said == ("ended FINISHED at cycle 17; the configuration "
                    "states TIMEOUT at 30")
    assert compared["cycles"] == [17, 30]
    assert "ended TIMEOUT at cycle 29" in fault(ends=ends, cycles=29)[0]


def test_the_worst_answer_is_one_at_fault_then_the_costliest():
    from chipbench import lib

    def compared(cost):
        return {"cost": [cost, 10.0]}

    checked = [(None, compared(4.0)), (None, compared(9.0)),
               (None, compared(2.0))]
    assert lib.worst(checked) == compared(9.0)
    assert lib.worst(checked + [("reported cost", compared(1.0))]) == (
        compared(1.0))


# --------------------------------------------------------------------- #
# BENCHMARK.json and the data files agree: in the repository's own
# benchmark, and in the copy to which a fourth cell was added


@pytest.mark.parametrize("which", WHICH)
def test_every_cell_resolves_to_its_files_and_a_runner(benchmarks, which):
    from chipbench import run

    path, data = benchmarks[which]
    bench = benchmark(which)
    assert bench["paths"] == ["chipbench", "tests/chipbench_rehearsal"]
    for cell in bench["workloads"]:
        _, entry, config, traffic, data_dir = run.resolve(
            path, cell["name"])
        assert data_dir == data
        assert config["name"] == entry["config"]
        assert traffic["name"] == entry["traffic"]
        assert os.path.isfile(os.path.join(
            CHIPBENCH, "runners", f"{config['kind']}.py"))
        reported = {m["name"] for m in run.listed(
            bench, "end_to_end", cell["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert run.listed(bench, "per_layer", cell["name"])


@pytest.mark.parametrize("which,name", per_benchmark(per_layer_names))
def test_a_per_layer_metric_is_a_file_that_agrees_with_its_entry(
        benchmarks, which, name):
    bench = benchmark(which)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cells = {w["name"] for w in bench["workloads"]}
    path = os.path.join(benchmarks[which][1], "metrics", f"{name}.json")
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert os.path.isfile(os.path.join(
        CHIPBENCH, "readers", f"{spec['reader']}.py"))
    # The metric it moves is reported in every cell it is in.
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry.get("workloads", cells)) <= set(
        moved.get("workloads", cells))


@pytest.mark.parametrize("which", WHICH)
def test_every_metric_file_is_listed_in_the_benchmark(benchmarks, which):
    """A file in ``metrics/`` that ``BENCHMARK.json`` does not list is
    evaluated in every traced run and printed in none."""
    files = {name[:-len(".json")]
             for name in os.listdir(os.path.join(benchmarks[which][1],
                                                 "metrics"))}
    assert files == set(per_layer_names(which))
