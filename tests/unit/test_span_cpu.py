"""The thread's CPU time on every live span (ISSUE 41): ``tdur``
beside ``dur``, so that a span's wall less its ``tdur`` is the time
its thread was not running: blocked, or waiting for the interpreter
lock.

Spinning is measured on the thread's own CPU clock (a spin lasts until
the thread has burnt so many milliseconds), so a busy machine makes
the walls longer and no assertion wrong.
"""

import json
import threading
import time

import pytest

from pydcop_tpu.observability import trace as trace_mod
from pydcop_tpu.observability.flight import FlightRecorder
from pydcop_tpu.observability.trace import Tracer, load_trace_file

SPIN_MS = 40
SLEEP_S = 0.05


def _spin(ms: float = SPIN_MS) -> None:
    """Pure Python, under the interpreter lock, until this thread has
    been on a CPU for ``ms`` more milliseconds."""
    until = time.thread_time_ns() + int(ms * 1e6)
    while time.thread_time_ns() < until:
        sum(range(200))


@pytest.fixture
def session():
    """A tracer of the test's own under a file session."""
    tracer = Tracer()
    tracer.enable()
    try:
        yield tracer
    finally:
        tracer.disable()


def _one(tracer, name):
    found = [e for e in tracer.events() if e["name"] == name]
    assert len(found) == 1, [e["name"] for e in tracer.events()]
    return found[0]


def test_a_span_that_sleeps_was_hardly_on_a_cpu(session):
    with session.span("sleeps", "t"):
        time.sleep(SLEEP_S)
    event = _one(session, "sleeps")
    assert event["dur"] >= SLEEP_S * 1e6 * 0.9
    assert 0 <= event["tdur"] < 0.2 * event["dur"]


def test_a_span_that_spins_was_on_a_cpu_for_what_it_burnt(session):
    with session.span("spins", "t"):
        _spin()
    event = _one(session, "spins")
    # The CPU clock is read inside the wall interval.
    assert SPIN_MS * 1e3 <= event["tdur"] <= event["dur"]


def test_a_parent_holds_its_childrens_cpu_time(session):
    with session.span("parent", "t"):
        with session.span("child", "t"):
            _spin(10)
        time.sleep(0.01)
    parent, child = _one(session, "parent"), _one(session, "child")
    assert child["tdur"] >= 10e3
    assert parent["tdur"] >= child["tdur"]
    assert parent["dur"] - parent["tdur"] >= 0.01e6 * 0.9


def test_two_threads_spinning_wait_for_the_lock_half_their_time(session):
    """Each burns the same CPU time in pure Python: one runs while the
    other waits for the interpreter lock, so each span's wall is about
    twice its ``tdur`` (more on a busy machine, never much less)."""
    barrier = threading.Barrier(2)

    def work(name):
        barrier.wait(timeout=30)
        with session.span(name, "t"):
            _spin(100)

    threads = [threading.Thread(target=work, args=(name,))
               for name in ("one", "two")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for name in ("one", "two"):
        event = _one(session, name)
        assert event["tdur"] >= 100e3
        assert event["dur"] >= 1.5 * event["tdur"], (name, event)


def test_a_back_dated_span_has_no_thread_clock(session):
    session.complete("back_dated", "t", t0=1.0, t1=2.0)
    session.instant("point", "t")
    assert "tdur" not in _one(session, "back_dated")
    assert "tdur" not in _one(session, "point")


@pytest.mark.parametrize("mode", ["off", "ring_only"])
def test_no_second_clock_is_read_outside_a_file_session(
        monkeypatch, mode):
    reads = []

    class Clock:
        """``time``, with its thread clock counted."""

        perf_counter = staticmethod(time.perf_counter)
        process_time = staticmethod(time.process_time)
        monotonic = staticmethod(time.monotonic)
        time = staticmethod(time.time)

        @staticmethod
        def thread_time_ns():
            reads.append(1)
            return time.thread_time_ns()

    monkeypatch.setattr(trace_mod, "time", Clock)
    tracer = Tracer()
    ring = FlightRecorder(events=16)
    if mode == "ring_only":
        tracer.set_flight(ring)
    with tracer.span("quiet", "t"):
        pass
    tracer.instant("point", "t")
    tracer.complete("back_dated", "t", t0=1.0, t1=2.0)
    assert reads == []
    assert tracer.events() == []
    if mode == "off":
        assert tracer.span("quiet", "t") is trace_mod.NOOP_SPAN
        assert ring.snapshot() == []
    else:
        spans = [e for e in ring.snapshot() if e["ph"] == "X"]
        assert sorted(e["name"] for e in spans) == ["back_dated", "quiet"]
        assert all("tdur" not in e for e in spans)
    # And under a session the clock is read, twice a span.
    tracer.enable()
    try:
        with tracer.span("loud", "t"):
            pass
    finally:
        tracer.disable()
    assert len(reads) == 2


@pytest.mark.parametrize("fmt", ["chrome", "jsonl", "merged"])
def test_every_export_carries_the_thread_clock(session, tmp_path, fmt):
    with session.span("spins", "t"):
        _spin(5)
    session.complete("back_dated", "t", t0=1.0, t1=2.0)
    session.disable()
    path = str(tmp_path / f"trace.{fmt}")
    if fmt == "merged":
        parts = [str(tmp_path / f"part{i}.json") for i in (0, 1)]
        for part in parts:
            session.export_chrome(part)
        trace_mod.merge_traces(parts, path)
    else:
        session.export(path, fmt)
    events = load_trace_file(path)
    spins = [e for e in events if e["name"] == "spins"]
    assert spins and all(e["tdur"] >= 5e3 for e in spins)
    assert all(e["tdur"] <= e["dur"] for e in spins)
    assert all("tdur" not in e for e in events
               if e["name"] == "back_dated")
    if fmt == "chrome":
        # Chrome's own key, beside `dur`, in microseconds.
        with open(path, encoding="utf-8") as f:
            raw = [e for e in json.load(f)["traceEvents"]
                   if e["name"] == "spins"]
        assert raw[0]["tdur"] == spins[0]["tdur"]
