"""YAML loader tests, including round-trips on the committed local
instances and — when mounted — the reference's own fixture files.

The reference fixtures are the parity oracle: our loader must accept
them and produce the same problems.  Those tests skip cleanly when the
reference checkout is absent, keeping the suite self-contained.
"""

import contextlib
import gc
import os
import sys
import threading

import pytest

from fixtures_paths import (
    REF_INSTANCES,
    local,
    local_instances,
    ref_instances,
    requires_reference,
)

from pydcop_tpu.dcop import yamldcop
from pydcop_tpu.dcop.objects import VariableNoisyCostFunc, VariableWithCostFunc
from pydcop_tpu.dcop.yamldcop import (
    DcopInvalidFormatError,
    dcop_yaml,
    load_dcop,
    load_dcop_from_file,
    load_dist,
    load_scenario,
    yaml_dist,
    yaml_scenario,
)

def test_minimal():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1:
    values: [0, 1, 2]
variables:
  v1:
    domain: d1
constraints:
  c1:
    type: intention
    function: v1 * 2
"""
    )
    assert dcop.name == "test"
    assert list(dcop.domains["d1"].values) == [0, 1, 2]
    assert dcop.constraint("c1")(v1=2) == 4


def test_range_domain():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1:
    values: [1 .. 5]
variables:
  v1: {domain: d1}
"""
    )
    assert list(dcop.domains["d1"].values) == [1, 2, 3, 4, 5]


def test_bool_domain():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1:
    values: [true, false]
variables:
  v1: {domain: d1}
"""
    )
    assert list(dcop.domains["d1"].values) == [True, False]


def test_variable_cost_and_noise():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1: {values: [0, 1, 2]}
variables:
  v1:
    domain: d1
    cost_function: v1 * 0.5
  v2:
    domain: d1
    cost_function: v2 * 2
    noise_level: 0.1
"""
    )
    v1, v2 = dcop.variable("v1"), dcop.variable("v2")
    assert isinstance(v1, VariableWithCostFunc)
    assert v1.cost_for_val(2) == 1.0
    assert isinstance(v2, VariableNoisyCostFunc)
    assert 2.0 <= v2.cost_for_val(1) < 2.1


def test_multiline_function_constraint():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1: {values: [0, 1, 2]}
variables:
  v1: {domain: d1}
constraints:
  c1:
    type: intention
    function: |
      if v1 == 2:
          return 10
      return v1
"""
    )
    c = dcop.constraint("c1")
    assert c(v1=2) == 10
    assert c(v1=1) == 1


def test_extensional_constraint():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1: {values: [1, 2, 3]}
variables:
  v1: {domain: d1}
  v2: {domain: d1}
constraints:
  c1:
    type: extensional
    default: 100
    variables: [v1, v2]
    values:
      10: 1 2 | 2 1
      0: 3 3
"""
    )
    c = dcop.constraint("c1")
    assert c(v1=1, v2=2) == 10
    assert c(v1=2, v2=1) == 10
    assert c(v1=3, v2=3) == 0
    assert c(v1=1, v2=1) == 100


def test_external_variable_and_partial():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1: {values: [0, 1, 2]}
  dbool: {values: [true, false]}
variables:
  v1: {domain: d1}
  v2: {domain: d1}
external_variables:
  e1:
    domain: dbool
    initial_value: true
constraints:
  c1:
    type: intention
    function: v1 if e1 else 2
  c2:
    type: intention
    function: v1 * 10 + v2
    partial:
      v2: 1
"""
    )
    assert dcop.get_external_variable("e1").value is True
    c2 = dcop.constraint("c2")
    assert c2.scope_names == ["v1"]
    assert c2(v1=2) == 21


def test_agents_routes_hosting():
    dcop = load_dcop(
        """
name: test
objective: min
domains:
  d1: {values: [0, 1]}
variables:
  v1: {domain: d1}
agents:
  a1: {capacity: 100}
  a2: {capacity: 50}
  a3: {}
routes:
  default: 5
  a1: {a2: 10}
hosting_costs:
  default: 1000
  a1:
    default: 7
    computations: {v1: 3}
"""
    )
    a1, a2, a3 = (dcop.agent(n) for n in ("a1", "a2", "a3"))
    assert a2.capacity == 50
    assert a1.route("a2") == 10
    assert a2.route("a1") == 10  # symmetric
    assert a1.route("a3") == 5
    assert a1.hosting_cost("v1") == 3
    assert a1.hosting_cost("other") == 7
    assert a3.hosting_cost("v1") == 1000


def test_duplicate_route_raises():
    from pydcop_tpu.dcop.yamldcop import DcopInvalidFormatError

    with pytest.raises(DcopInvalidFormatError):
        load_dcop(
            """
name: test
domains: {d1: {values: [0]}}
variables: {v1: {domain: d1}}
agents: [a1, a2]
routes:
  a1: {a2: 10}
  a2: {a1: 6}
"""
        )


def test_agents_as_list():
    dcop = load_dcop(
        """
name: test
domains: {d1: {values: [0]}}
variables: {v1: {domain: d1}}
agents: [a1, a2]
"""
    )
    assert set(dcop.agents) == {"a1", "a2"}


@pytest.mark.parametrize(
    "path",
    local_instances(),
    ids=[os.path.basename(p) for p in local_instances()],
)
def test_load_local_fixture(path):
    """Every committed local instance must load without error."""
    dcop = load_dcop_from_file(path)
    assert dcop.name
    assert dcop.variables


@requires_reference
@pytest.mark.parametrize(
    "fixture",
    sorted(os.path.basename(p) for p in ref_instances()),
)
def test_load_reference_fixture(fixture):
    """Parity tier: every reference fixture must load without error."""
    dcop = load_dcop_from_file(os.path.join(REF_INSTANCES, fixture))
    assert dcop.name
    assert dcop.variables


def test_local_coloring_semantics():
    dcop = load_dcop_from_file(local("coloring_chain.yaml"))
    assert dcop.objective == "min"
    c = dcop.constraint("clash_12")
    assert c(w1="B", w2="B") == 3
    assert c(w1="B", w2="Y") == 0
    assert dcop.variable("w1").cost_for_val("B") == -0.2
    cost, violations = dcop.solution_cost(
        {"w1": "B", "w2": "B", "w3": "P", "w4": "B"})
    # clash_12 (3) + prefs: -0.2 (w1=B) + 0.1 (w2=B) + 0.0 + -0.2
    assert abs(cost - 2.7) < 1e-9
    assert violations == 0
    assert dcop.dist_hints.must_host("b1") == ["w1"]


@requires_reference
def test_reference_graph_coloring_semantics():
    dcop = load_dcop_from_file(
        os.path.join(REF_INSTANCES, "graph_coloring1.yaml"))
    assert dcop.objective == "min"
    c = dcop.constraint("diff_1_2")
    assert c(v1="R", v2="R") == 1
    assert c(v1="R", v2="G") == 0
    assert dcop.variable("v1").cost_for_val("R") == -0.1
    cost, violations = dcop.solution_cost({"v1": "R", "v2": "G", "v3": "G"})
    assert abs(cost - 0.7) < 1e-9
    assert violations == 0
    assert dcop.dist_hints.must_host("a1") == ["v1"]


def test_external_python_constraint_fixture():
    dcop = load_dcop_from_file(local("coloring_chain_func.yaml"))
    assert dcop.constraint("clash_23")(w2="B", w3="B") == 3
    assert dcop.constraint("clash_23")(w2="B", w3="Y") == 0


def test_roundtrip_through_dump():
    src = load_dcop_from_file(local("coloring_chain.yaml"))
    dumped = dcop_yaml(src)
    again = load_dcop(dumped)
    assert set(again.variables) == set(src.variables)
    assert set(again.constraints) == set(src.constraints)
    asst = {"w1": "B", "w2": "Y", "w3": "P", "w4": "B"}
    assert again.solution_cost(asst) == src.solution_cost(asst)


def test_scenario_roundtrip():
    s = load_scenario(
        """
events:
  - id: w
    delay: 1
  - id: e1
    actions:
      - type: remove_agent
        agent: a2
"""
    )
    assert len(s) == 2
    assert s.events[0].is_delay
    assert s.events[1].actions[0].type == "remove_agent"
    assert s.events[1].actions[0].args == {"agent": "a2"}
    s2 = load_scenario(yaml_scenario(s))
    assert s2.events == s.events


def test_distribution_roundtrip():
    d = load_dist(
        """
distribution:
  a0: []
  a1: [v1, v2]
"""
    )
    assert d.computations_hosted("a1") == ["v1", "v2"]
    assert d.agent_for("v1") == "a1"
    d2 = load_dist(yaml_dist(d))
    assert d2 == d


# ------------------------------------------------------------------ #
# the cyclic collector is paused while a load is in flight
# (counts and states only: a time comes from the chip)

CHAIN = """
name: chain
objective: min
domains:
  d: {values: [0, 1]}
variables:
  v1: {domain: d}
  v2: {domain: d}
constraints:
  c1: {type: intention, function: v1 + v2}
"""

NOT_YAML = "name: [unclosed\n  - {"
NO_NAME = "objective: min\ndomains:\n  d: {values: [0, 1]}\n"


def _set_collector(enabled):
    (gc.enable if enabled else gc.disable)()


@pytest.fixture
def collector():
    """The test finds the collector as pytest runs it, and leaves it so."""
    was = gc.isenabled()
    assert yamldcop._collector_pause.in_flight == 0
    yield
    _set_collector(was)


@contextlib.contextmanager
def _flight_ring(recorder):
    """``recorder`` (or none: ``tracer.active`` false) as the flight
    ring for the block."""
    from pydcop_tpu.observability.trace import tracer

    previous = tracer.flight
    tracer.set_flight(recorder)
    try:
        yield recorder
    finally:
        tracer.set_flight(previous)
        tracer.clear()


@pytest.fixture(params=["off", "ring"])
def session(request):
    """Both paths of ``load_dcop``: ``tracer.active`` false, and the
    path with spans (a flight ring attached)."""
    from pydcop_tpu.observability.flight import FlightRecorder

    ring = FlightRecorder(events=64) if request.param == "ring" else None
    with _flight_ring(ring):
        yield


def _grid_colouring(side, seed=7):
    from pydcop_tpu.generators.graphcoloring import generate_graph_coloring

    return generate_graph_coloring(
        side * side, 3, "grid", allow_subgraph=True, noagents=True,
        seed=seed)


@pytest.mark.parametrize("enabled", [True, False])
def test_load_leaves_the_collector_as_it_found_it(
        enabled, collector, session):
    _set_collector(enabled)
    dcop = load_dcop(CHAIN)
    assert gc.isenabled() is enabled
    assert yamldcop._collector_pause.in_flight == 0
    assert set(dcop.variables) == {"v1", "v2"}


def test_collector_is_off_inside_both_halves_of_a_load(
        collector, session, monkeypatch):
    seen = []

    def spy(name, callee):
        def wrapped(*args):
            seen.append((name, gc.isenabled(),
                         yamldcop._collector_pause.in_flight))
            return callee(*args)
        monkeypatch.setattr(yamldcop, name, wrapped)

    spy("_yaml_load", yamldcop._yaml_load)
    spy("_build_dcop", yamldcop._build_dcop)
    gc.enable()
    load_dcop(CHAIN)
    assert seen == [("_yaml_load", False, 1), ("_build_dcop", False, 1)]
    assert gc.isenabled()


@pytest.mark.parametrize("text, error", [
    (NOT_YAML, yamldcop.yaml.YAMLError),
    (NO_NAME, DcopInvalidFormatError),
], ids=["not_yaml", "no_name"])
def test_a_load_that_raises_restores_the_collector(
        text, error, collector, session):
    gc.enable()
    with pytest.raises(error):
        load_dcop(text)
    assert gc.isenabled()
    assert yamldcop._collector_pause.in_flight == 0


def test_a_build_that_raises_restores_the_collector(
        collector, session, monkeypatch):
    def broken(data, main_dir):
        assert not gc.isenabled()
        raise RuntimeError("in the build")

    monkeypatch.setattr(yamldcop, "_build_dcop", broken)
    gc.enable()
    with pytest.raises(RuntimeError, match="in the build"):
        load_dcop(CHAIN)
    assert gc.isenabled()
    assert yamldcop._collector_pause.in_flight == 0


def test_eight_threads_share_one_pause(collector, monkeypatch):
    """Off while any load is in flight, on after the last, count zero:
    every thread is held inside its parse until all eight are in, and
    half of them then fail."""
    n = 8
    inside = threading.Barrier(n, timeout=30)
    seen, failures = [], []
    real = yamldcop._yaml_load

    def held(text):
        inside.wait()
        seen.append((gc.isenabled(), yamldcop._collector_pause.in_flight))
        inside.wait()
        return real(text)

    def caller(k):
        try:
            load_dcop(NO_NAME if k % 2 else CHAIN)
        except DcopInvalidFormatError:
            failures.append(k)
        # Another load may still be in flight: then it is still off.
        with yamldcop._collector_pause._lock:
            seen.append((gc.isenabled(),
                         yamldcop._collector_pause.in_flight > 0))

    monkeypatch.setattr(yamldcop, "_yaml_load", held)
    gc.enable()
    threads = [threading.Thread(target=caller, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert seen[:n] == [(False, n)] * n
    assert all(enabled != in_flight for enabled, in_flight in seen[n:])
    assert sorted(failures) == [1, 3, 5, 7]
    assert gc.isenabled()
    assert yamldcop._collector_pause.in_flight == 0


def test_overlapping_loads_stress_leaves_count_zero(collector):
    """More threads than cores, a short switch interval, good and bad
    bodies: a lost update of the count would leave it off zero or the
    collector off."""
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    errors = []

    def caller(k):
        for i in range(40):
            try:
                load_dcop(NO_NAME if (k + i) % 3 == 0 else CHAIN)
            except DcopInvalidFormatError:
                pass
            except Exception as exc:  # noqa: BLE001 - reported below
                errors.append(exc)

    gc.enable()
    try:
        threads = [threading.Thread(target=caller, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert gc.isenabled()
    assert yamldcop._collector_pause.in_flight == 0


@contextlib.contextmanager
def _eager_collector():
    """A full collection every few thousand allocations, whatever the
    test process's heap: CPython skips one until a quarter of the old
    generation is new, so the heap is frozen out of that generation."""
    thresholds = gc.get_threshold()
    gc.collect()
    gc.freeze()
    gc.set_threshold(200, 3, 3)
    try:
        yield
    finally:
        gc.set_threshold(*thresholds)
        gc.unfreeze()


def _full_collections_during(load, text):
    before = gc.get_stats()[2]["collections"]
    dcop = load(text)
    return gc.get_stats()[2]["collections"] - before, dcop


def test_a_large_load_runs_no_full_collection(collector, monkeypatch):
    text = dcop_yaml(_grid_colouring(34))
    gc.enable()
    with _eager_collector():
        paused, dcop = _full_collections_during(load_dcop, text)
        monkeypatch.setattr(yamldcop, "_collector_pause",
                            contextlib.nullcontext(False))
        unpaused, again = _full_collections_during(load_dcop, text)
    assert len(dcop.constraints) == len(again.constraints) >= 2000
    assert (paused, unpaused > 0) == (0, True), (paused, unpaused)


@pytest.mark.parametrize("enabled", [True, False])
def test_yaml_parse_span_says_whether_the_pause_engaged(
        enabled, collector):
    from pydcop_tpu.observability.flight import FlightRecorder

    _set_collector(enabled)
    with _flight_ring(FlightRecorder(events=16)) as recorder:
        load_dcop(CHAIN)
        with pytest.raises(DcopInvalidFormatError):
            load_dcop(NO_NAME)
    spans = [e for e in recorder.snapshot() if e["name"] == "yaml_parse"]
    assert len(spans) == 2
    for span in spans:
        assert span["args"]["gc_paused"] is enabled
        assert span["args"]["gc_full_collections"] == 0
        assert span["args"]["loader"] == yamldcop.YAML_LOADER


def test_generated_instance_round_trips_bytes(collector):
    text = dcop_yaml(_grid_colouring(6))
    assert dcop_yaml(load_dcop(text)) == text
