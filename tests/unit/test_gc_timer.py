"""The process-wide timer of the garbage collector (ISSUE 41,
``observability/trace.py``): one ``gc.callbacks`` hook for as long
as somebody owns it, always-on counters, a ``gc_collect`` span under a
file session, and the ``process`` object of ``/stats``.
"""

import gc
import json
import threading

import pytest

from pydcop_tpu.observability import trace
from pydcop_tpu.observability.trace import (
    HEADER_KEY,
    NOOP_SPAN,
    GcTimer,
    gc_timer,
    tracer,
)


@pytest.fixture
def no_automatic_collection():
    """Only the collections the test asks for."""
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


@pytest.fixture
def file_session():
    tracer.enable()
    try:
        yield tracer
    finally:
        tracer.disable()
        tracer.clear()


def _collections(events):
    return [e for e in events if e["name"] == "gc_collect"]


def test_a_forced_collection_leaves_one_span_under_the_open_span(
        no_automatic_collection, file_session):
    before = gc_timer.counters()
    with tracer.span("outer", "t") as outer:
        gc.collect()
    events = tracer.events()
    (span,) = _collections(events)
    assert span["ph"] == "X" and span["cat"] == "gc"
    assert span["args"]["generation"] == 2
    assert span["args"]["collected"] >= 0
    assert span["args"]["uncollectable"] >= 0
    assert span["parent"] == outer.span_id
    # On the thread it ran on, inside the span that was open there.
    (parent,) = [e for e in events if e["name"] == "outer"]
    assert span["tid"] == parent["tid"]
    assert parent["ts"] <= span["ts"]
    assert span["ts"] + span["dur"] <= parent["ts"] + parent["dur"]
    after = gc_timer.counters()
    assert after["collections"]["gen2"] == \
        before["collections"]["gen2"] + 1
    assert after["pause_s"]["gen2"] > before["pause_s"]["gen2"]
    assert after["max_full_pause_s"] > 0
    assert after["max_pause_s"] >= after["max_full_pause_s"]


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_every_generation_leaves_a_span_and_counts(
        no_automatic_collection, file_session, generation):
    key = f"gen{generation}"
    before = gc_timer.counters()
    gc.collect(generation)
    (span,) = _collections(tracer.events())
    assert span["args"]["generation"] == generation
    assert span["parent"] == 0
    after = gc_timer.counters()
    for other in ("gen0", "gen1", "gen2"):
        moved = after["collections"][other] - before["collections"][other]
        assert moved == (1 if other == key else 0)


def test_a_collection_on_another_thread_is_on_that_threads_lane(
        no_automatic_collection, file_session):
    seen = {}

    def collect():
        with tracer.span("worker", "t") as span:
            seen["id"] = span.span_id
            gc.collect()

    thread = threading.Thread(target=collect, name="collector")
    thread.start()
    thread.join(timeout=60)
    assert not thread.is_alive()
    (span,) = _collections(tracer.events())
    assert span["parent"] == seen["id"]
    assert tracer.thread_names()[span["tid"]] == "collector"


def test_a_collection_under_the_tracers_own_lock_does_not_deadlock(
        no_automatic_collection, file_session):
    """An allocation inside ``tracer.events()`` can start a collection
    on a thread the session has not seen yet; its ``gc_collect`` span
    registers that thread's buffer, under the lock the thread holds."""
    done = []

    def collect():
        with tracer._lock:
            gc.collect()
        done.append(True)

    thread = threading.Thread(target=collect, daemon=True)
    thread.start()
    thread.join(timeout=30)
    assert done == [True]
    assert len(_collections(tracer.events())) == 1


def test_the_hook_lives_as_long_as_its_last_owner(
        no_automatic_collection):
    timer = GcTimer()
    assert not timer.installed           # never at construction
    first, second = object(), object()
    timer.release(first)                 # not an owner: nothing
    timer.acquire(first)
    timer.acquire(first)                 # once per owner
    timer.acquire(second)
    assert timer.installed
    assert gc.callbacks.count(timer._on_gc) == 1
    timer.release(first)
    assert timer.installed
    gc.collect()
    assert timer.counters()["collections"]["gen2"] == 1
    timer.release(second)
    assert not timer.installed
    gc.collect()
    assert timer.counters()["collections"]["gen2"] == 1


def test_a_file_session_owns_the_process_timer():
    assert not tracer.enabled
    installed_before = gc_timer.installed
    tracer.enable()
    try:
        assert gc_timer.installed
    finally:
        tracer.disable()
        tracer.clear()
    assert gc_timer.installed == installed_before


def test_the_counters_are_monotone(no_automatic_collection):
    owner = object()
    gc_timer.acquire(owner)
    try:
        reads = [gc_timer.counters()]
        for generation in (0, 2, 1, 2, 0):
            gc.collect(generation)
            reads.append(gc_timer.counters())
    finally:
        gc_timer.release(owner)
    for before, after in zip(reads, reads[1:]):
        for key in ("gen0", "gen1", "gen2"):
            assert after["collections"][key] >= before["collections"][key]
            assert after["pause_s"][key] >= before["pause_s"][key]
        assert after["max_pause_s"] >= before["max_pause_s"]
        assert after["max_full_pause_s"] >= before["max_full_pause_s"]
    assert (reads[-1]["collections"]["gen2"]
            == reads[0]["collections"]["gen2"] + 2)


def test_with_tracing_off_a_collection_counts_and_records_nothing(
        no_automatic_collection, monkeypatch):
    from pydcop_tpu.observability import trace as trace_mod

    made = []
    real = trace_mod._Span.__init__

    def counting(self, *args, **kwargs):
        made.append(1)
        real(self, *args, **kwargs)

    monkeypatch.setattr(trace_mod._Span, "__init__", counting)
    previous = tracer.flight
    tracer.set_flight(None)
    owner = object()
    gc_timer.acquire(owner)
    try:
        assert not tracer.active
        before = gc_timer.counters()["collections"]["gen2"]
        gc.collect()
        assert gc_timer.counters()["collections"]["gen2"] == before + 1
        assert tracer.span("x", "t") is NOOP_SPAN
    finally:
        gc_timer.release(owner)
        tracer.set_flight(previous)
    assert made == [] and tracer.events() == []


def test_a_running_service_owns_the_timer_and_stats_say_process():
    from pydcop_tpu.serving.service import SolveService

    installed_before = gc_timer.installed
    service = SolveService(speculate=False).start()
    try:
        assert gc_timer.installed
        first = service.stats()["process"]
        gc.collect()
        second = service.stats()["process"]
    finally:
        service.stop()
    assert gc_timer.installed == installed_before
    assert set(first) == {"gc", "rss_bytes", "cpu_s", "wall_s"}
    assert set(first["gc"]) == {"collections", "pause_s", "max_pause_s",
                                "max_full_pause_s"}
    for key in ("collections", "pause_s"):
        assert set(first["gc"][key]) == {"gen0", "gen1", "gen2"}
    assert second["gc"]["collections"]["gen2"] > \
        first["gc"]["collections"]["gen2"]
    assert second["gc"]["pause_s"]["gen2"] > first["gc"]["pause_s"]["gen2"]
    assert first["rss_bytes"] > 0 and trace.rss_bytes() > 0
    assert second["cpu_s"] >= first["cpu_s"] > 0
    assert second["wall_s"] >= first["wall_s"]


@pytest.mark.parametrize("fmt", ["chrome", "jsonl"])
def test_an_ended_session_exports_the_process_at_its_two_ends(
        tmp_path, no_automatic_collection, fmt):
    """``session_process`` in the header: ``process_stats()`` as
    ``enable`` and ``disable`` read it, so a full collection inside
    the session is in their difference and one after it is not."""
    path = tmp_path / f"trace.{fmt}"
    tracer.enable()
    try:
        gc.collect()
        tracer.export(str(path), fmt)
        with open(path, encoding="utf-8") as f:
            still_open = (json.load(f) if fmt == "chrome"
                          else json.loads(f.readline()))[HEADER_KEY]
    finally:
        tracer.disable()
    owner = object()
    gc_timer.acquire(owner)
    try:
        gc.collect()
    finally:
        gc_timer.release(owner)
    tracer.disable()  # a second disable reads nothing again
    tracer.export(str(path), fmt)
    tracer.clear()
    with open(path, encoding="utf-8") as f:
        header = (json.load(f) if fmt == "chrome"
                  else json.loads(f.readline()))[HEADER_KEY]
    assert "session_process" not in still_open
    start, end = (header["session_process"][k] for k in ("start", "end"))
    assert set(start) == set(end) == {"gc", "rss_bytes", "cpu_s", "wall_s"}
    assert (end["gc"]["collections"]["gen2"]
            - start["gc"]["collections"]["gen2"]) == 1
    assert end["wall_s"] >= start["wall_s"]
    assert end["cpu_s"] >= start["cpu_s"]
