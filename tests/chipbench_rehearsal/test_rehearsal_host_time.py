"""The metrics and readers PR 41 adds, rehearsed on the CPU: the child
spans of ``solve`` and ``sched_flush``, the thread's CPU time on every
span (``span_cpu``), the collector's spans and the process's clocks
and counters over the tracer's session (``process_stats``).

The end-to-end rehearsal runs the tiny cells of ``test_rehearsal.py``
with a benchmark that lists PR 41's metrics alone; ``span_cpu`` is
also checked as a pure function on a hand-made span file, and
``process_stats`` on a hand-made header.  A CPU run
proves names, paths and signs, never a time.
"""

import json
import os
import shutil

import pytest
from rehearsal_benchmarks import WHICH
from test_rehearsal import (  # noqa: F401 - harness is a fixture
    CHIPBENCH,
    TINY_CELLS,
    TINY_CONFIGS,
    TINY_TRAFFIC,
    harness,
    last_line,
    run_cell,
    unread,
    write_json,
)

NEW = {
    "solve": {"entry.result_cost_ms", "engine.place_ms",
              "engine.result_decode_ms"},
    "serve": {"serve.sched_plan_ms", "serve.sched_launch_ms",
              "serve.sched_decode_ms", "serve.host_cpu_ms",
              "serve.lock_wait_ms", "serve.gc_pause_ms",
              "serve.gc_full_ms", "serve.cpu_cores"},
}
ALL_NEW = sorted(NEW["solve"] | NEW["serve"])
# What may read 0: no full collection in a second.
MAY_BE_ZERO = {"serve.gc_full_ms"}


@pytest.fixture
def host_time_bench(tmp_path):
    """A BENCHMARK.json of the two tiny cells that lists, and whose
    data directory holds, the metrics of PR 41 alone."""
    data = tmp_path / "data"
    os.makedirs(data / "metrics")
    for name in ALL_NEW:
        shutil.copy(os.path.join(CHIPBENCH, "metrics", f"{name}.json"),
                    data / "metrics")
    for name, config in TINY_CONFIGS.items():
        write_json(str(data / "configs" / f"{name}.json"), config)
    for name, mix in TINY_TRAFFIC.items():
        write_json(str(data / "traffic" / f"{name}.json"), mix)
    bench_path = tmp_path / "BENCHMARK.json"
    write_json(str(bench_path), {
        "configs": [{"name": n, "file": f"data/configs/{n}.json"}
                    for n in TINY_CONFIGS],
        "workloads": [
            {"name": cell, "config": cell.split(".")[0],
             "traffic": cell.split(".")[1], "chips": 1}
            for cell in TINY_CELLS.values()],
        "end_to_end": [],
        "per_layer": [{"name": name} for name in ALL_NEW],
    })
    return str(bench_path)


@pytest.fixture
def collections_in_every_block():
    """A tiny cell allocates too little to cross the collector's
    default threshold inside its traced block: lower it, so that the
    block holds ``gc_collect`` spans as a real cell's does."""
    import gc

    thresholds = gc.get_threshold()
    gc.set_threshold(50, 5, 5)
    yield
    gc.set_threshold(*thresholds)


@pytest.mark.parametrize("kind", ["solve", "serve"])
def test_every_new_metric_reads_on_the_rehearsal_cells(
        harness, host_time_bench, collections_in_every_block, capsys,
        monkeypatch, kind):
    monkeypatch.setattr(harness, "BREAKDOWN_ENTRIES", 10**6)
    assert run_cell(harness, host_time_bench, kind, 1) == 0
    line, notes = last_line(capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == NEW[kind]
    assert unread(notes) == sorted(NEW[
        "serve" if kind == "solve" else "solve"])
    for name, metric in line["metrics"].items():
        if name not in MAY_BE_ZERO:
            assert metric["value"] > 0, name
    # The new spans are named where the host's time went.
    gaps = dict(line["breakdown"]["idle_gaps"])
    named = ({"build_engine", "engine_place", "result_decode",
              "result_cost"} if kind == "solve" else
             {"sched_plan", "serve_launch", "serve_decode"})
    assert {f"host:{name}" for name in named} <= set(gaps)
    assert "host:gc_collect" in gaps
    if kind == "serve":
        # A lock held by one thread at a time: what the spans wait is
        # no more than what the block lasted, per thread.
        assert line["metrics"]["serve.cpu_cores"]["value"] < 64


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("name", ALL_NEW)
def test_a_new_metric_is_of_one_kind_and_reads_nothing_from_nothing(
        benchmarks, which, name):
    from chipbench import run

    kind = next(k for k in NEW if name in NEW[k])
    with open(os.path.join(benchmarks[which][1], "metrics",
                           f"{name}.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["kinds"] == [kind]
    reader = run.module_by_name("readers", spec["reader"], name)
    assert reader.read({}, **spec["args"]) is None
    # None of them reads the runner's two GET /stats, which lie
    # around the profiler's start and stop.
    stats = {"completed": 5, "dispatches": 2,
             "process": {"cpu_s": 1.0, "wall_s": 2.0, "rss_bytes": 3,
                         "gc": {"pause_s": {"gen2": 0.5}}}}
    assert reader.read({"stats_before": stats, "stats_after": stats},
                       **spec["args"]) is None


def test_the_parents_span_file_reads_nothing(tmp_path):
    """Every new metric on a span file as the parent commit writes
    it: the old spans, none with ``tdur``, a header without
    ``session_process``."""
    from chipbench import run

    path = tmp_path / "spans.json"
    write_json(str(path), {"traceEvents": [
        _span("http_request", 1, 0, 0, 100, None, tid=1),
        _span("yaml_parse", 2, 1, 10, 50, None, tid=1),
        _span("sched_flush", 3, 0, 0, 80, None, tid=2),
        _span("solve", 4, 0, 0, 80, None, tid=3)],
        "pydcop_trace_header": {"version": 1, "pid": 1}})
    for name in ALL_NEW:
        with open(os.path.join(CHIPBENCH, "metrics", f"{name}.json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        reader = run.module_by_name("readers", spec["reader"], name)
        assert reader.read({"spans": str(path)}, **spec["args"]) is None, name


# --------------------------------------------------------------------- #
# span_cpu: a hand-made span file


def _span(name, span_id, parent, ts, dur, tdur, tid=1):
    event = {"name": name, "ph": "X", "ts": ts, "dur": dur, "tid": tid,
             "args": {"span_id": span_id, "parent_id": parent}}
    if tdur is not None:
        event["tdur"] = tdur
    return event


@pytest.fixture
def hand_made(tmp_path):
    """Thread 1: an ``http_request`` of 1000 us that ran 300; inside it
    a ``yaml_parse`` of 400 that ran 250, which holds a ``gc_collect``
    of 100 that ran 100, and a back-dated child with no clock.  Thread
    2: a root ``sched_flush`` of 600 that ran 200, holding a
    ``serve_decode`` of 300 that ran 120.  A second ``http_request``
    on thread 3 has no clock at all (a trace from before PR 41)."""
    path = tmp_path / "spans.json"
    write_json(str(path), {"traceEvents": [
        {"name": "thread_name", "ph": "M", "tid": 1, "args": {}},
        _span("http_request", 1, 0, 0, 1000, 300),
        _span("yaml_parse", 2, 1, 100, 400, 250),
        _span("gc_collect", 3, 2, 200, 100, 100),
        _span("back_dated", 4, 2, 320, 50, None),
        _span("sched_flush", 5, 0, 50, 600, 200, tid=2),
        _span("serve_decode", 6, 5, 300, 300, 120, tid=2),
        _span("http_request", 7, 0, 0, 900, None, tid=3),
        {"name": "serve_accepted", "ph": "i", "ts": 5, "tid": 1,
         "args": {"span_id": 8, "parent_id": 1}},
    ]})
    return {"spans": str(path)}


@pytest.mark.parametrize("case", [
    "cpu_of_roots", "cpu_of_names", "wait", "wait_of_a_parent",
    "per", "no_clock", "absent", "per_absent", "unknown_stat"])
def test_span_cpu_on_a_hand_made_file(hand_made, case):
    from chipbench.readers import span_cpu

    if case == "cpu_of_roots":
        # The two roots that have a clock; the third is skipped.
        assert span_cpu.read(hand_made, names=None, stat="cpu") == 500
    elif case == "cpu_of_names":
        assert span_cpu.read(hand_made, names=["yaml_parse",
                                               "serve_decode"]) == 370
    elif case == "wait":
        # serve_decode has no child: 300 - 120.
        assert span_cpu.read(hand_made, names=["serve_decode"],
                             stat="wait") == 180
    elif case == "wait_of_a_parent":
        # yaml_parse: self wall 400 - 100 (the collection; the
        # back-dated child stays in), self CPU 250 - 100.
        assert span_cpu.read(hand_made, names=["yaml_parse"],
                             stat="wait") == 300 - 150
    elif case == "per":
        # Per http_request span, with or without a clock: 2 of them.
        assert span_cpu.read(hand_made, names=None, stat="cpu",
                             per="http_request", scale=0.001) == 0.25
    elif case == "no_clock":
        assert span_cpu.read(hand_made, names=["back_dated"]) is None
    elif case == "absent":
        assert span_cpu.read(hand_made, names=["result_cost"]) is None
        assert span_cpu.read({}, names=None) is None
    elif case == "per_absent":
        assert span_cpu.read(hand_made, names=["yaml_parse"],
                             per="solve") is None
    else:
        with pytest.raises(ValueError, match="unknown stat"):
            span_cpu.read(hand_made, names=None, stat="self")


# --------------------------------------------------------------------- #
# process_stats: a hand-made header


def _process(cpu_s, wall_s, full_s, rss):
    return {"cpu_s": cpu_s, "wall_s": wall_s, "rss_bytes": rss,
            "gc": {"collections": {"gen0": 0, "gen1": 0, "gen2": 0},
                   "pause_s": {"gen0": 0.0, "gen1": 0.0, "gen2": full_s},
                   "max_pause_s": full_s, "max_full_pause_s": full_s}}


@pytest.fixture
def session_file(tmp_path):
    """A session of 4 s that burnt 6 CPU seconds and spent 0.25 s in
    full collections, over two ``http_request`` spans."""
    path = tmp_path / "spans.json"
    write_json(str(path), {
        "traceEvents": [_span("http_request", 1, 0, 0, 1000, 300),
                        _span("http_request", 2, 0, 0, 900, 200, tid=2)],
        "pydcop_trace_header": {"version": 1, "session_process": {
            "start": _process(10.0, 100.0, 0.5, 3 << 29),
            "end": _process(16.0, 104.0, 0.75, 1 << 30)}}})
    return {"spans": str(path)}


@pytest.mark.parametrize("case", [
    "per_a_path", "per_span", "a_sum_of_keys", "an_object_is_summed",
    "no_divisor", "a_divisor_that_did_not_move", "no_span_of_the_name",
    "a_session_left_open"])
def test_process_stats_on_a_hand_made_header(session_file, tmp_path,
                                             case):
    from chipbench.readers import process_stats

    if case == "per_a_path":
        assert process_stats.read(session_file, keys=["cpu_s"],
                                  per="wall_s") == 1.5
    elif case == "per_span":
        assert process_stats.read(
            session_file, keys=["gc.pause_s.gen2"],
            per_span="http_request", scale=1000.0) == 125.0
    elif case == "a_sum_of_keys":
        assert process_stats.read(
            session_file, keys=["cpu_s", "wall_s"]) == 10.0
    elif case == "an_object_is_summed":
        assert process_stats.read(session_file,
                                  keys=["gc.pause_s"]) == 0.25
    elif case == "no_divisor":
        # Memory given back reads below 0.
        assert process_stats.read(session_file, keys=["rss_bytes"],
                                  scale=1 / 1024) == -(1 << 19)
    elif case == "a_divisor_that_did_not_move":
        assert process_stats.read(session_file, keys=["cpu_s"],
                                  per="gc.collections") is None
    elif case == "no_span_of_the_name":
        assert process_stats.read(session_file, keys=["cpu_s"],
                                  per_span="solve") is None
    else:
        # A file exported while its session still ran has no `end`,
        # and the tracer writes no `session_process` then.
        path = tmp_path / "open.json"
        write_json(str(path), {"traceEvents": [],
                               "pydcop_trace_header": {"version": 1}})
        assert process_stats.read({"spans": str(path)},
                                  keys=["cpu_s"]) is None
