"""Deep battery over dcop/yamldcop.py — format parsing, every
constraint/variable flavor, error paths, and dump→reload round-trips
(reference test_dcop_serialization.py depth)."""

import importlib.util
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest
import yaml

from pydcop_tpu.dcop import yamldcop
from pydcop_tpu.dcop.dcop import DCOP
from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.dcop.yamldcop import (
    DcopInvalidFormatError,
    dcop_yaml,
    load_agents,
    load_dcop,
    load_scenario,
    yaml_agents,
    yaml_scenario,
)
from pydcop_tpu.generators.graphcoloring import generate_graph_coloring
from pydcop_tpu.generators.iot import generate_iot
from pydcop_tpu.generators.ising import generate_ising
from pydcop_tpu.generators.meetingscheduling import generate_meetings
from pydcop_tpu.generators.secp import generate_secp
from pydcop_tpu.generators.smallworld import generate_small_world

BASE = """
name: t
objective: min
domains:
  d3:
    values: [0, 1, 2]
variables:
  v1: {domain: d3}
  v2: {domain: d3}
"""


class TestDomains:
    def test_range_string(self):
        d = load_dcop("""
name: t
domains:
  d: {values: "1 .. 4"}
variables:
  v: {domain: d}
""")
        assert list(d.domain("d")) == [1, 2, 3, 4]

    def test_range_inside_list(self):
        d = load_dcop("""
name: t
domains:
  d:
    values: ["1 .. 3", "7"]
variables:
  v: {domain: d}
""")
        assert list(d.domain("d")) == [1, 2, 3, 7]

    def test_string_ints_coerced(self):
        d = load_dcop("""
name: t
domains:
  d: {values: ["1", "2"]}
variables:
  v: {domain: d}
""")
        assert list(d.domain("d")) == [1, 2]

    def test_mixed_strings_stay_strings(self):
        d = load_dcop("""
name: t
domains:
  d: {values: [R, G, B]}
variables:
  v: {domain: d}
""")
        assert list(d.domain("d")) == ["R", "G", "B"]

    def test_domain_type_preserved(self):
        d = load_dcop("""
name: t
domains:
  d: {values: [0, 1], type: luminosity}
variables:
  v: {domain: d}
""")
        assert d.domain("d").type == "luminosity"


class TestErrors:
    def test_missing_name(self):
        with pytest.raises(DcopInvalidFormatError, match="name"):
            load_dcop("objective: min")

    def test_empty_document(self):
        with pytest.raises(DcopInvalidFormatError):
            load_dcop("")

    def test_unknown_constraint_type(self):
        with pytest.raises(DcopInvalidFormatError, match="invalid type"):
            load_dcop(BASE + """
constraints:
  c1:
    type: nope
""")

    def test_extensional_unknown_variable(self):
        with pytest.raises(DcopInvalidFormatError, match="Unknown"):
            load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    variables: [v1, ghost]
    values:
      1: 0 0
""")

    def test_extensional_bad_row_width(self):
        with pytest.raises(DcopInvalidFormatError, match="expected 2"):
            load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    variables: [v1, v2]
    values:
      1: 0 0 0
""")

    def test_external_variable_requires_initial_value(self):
        with pytest.raises(DcopInvalidFormatError, match="initial_value"):
            load_dcop("""
name: t
domains:
  d: {values: [0, 1]}
external_variables:
  e: {domain: d}
""")

    def test_duplicate_route_rejected(self):
        with pytest.raises(DcopInvalidFormatError, match="more than once"):
            load_dcop(BASE + """
agents: [a1, a2]
routes:
  a1: {a2: 3}
  a2: {a1: 4}
""")


class TestConstraints:
    def test_intention(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: intention
    function: abs(v1 - v2)
""")
        c = d.constraints["c1"]
        assert set(c.scope_names) == {"v1", "v2"}
        assert c(v1=0, v2=2) == 2

    def test_intention_partial(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: intention
    function: v1 * 10 + v2
    partial: {v1: 2}
""")
        c = d.constraints["c1"]
        assert c.scope_names == ["v2"]
        assert c(1) == 21
        assert c.name == "c1"

    def test_extensional_default(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    default: 5
    variables: [v1, v2]
    values:
      0: 1 1
""")
        c = d.constraints["c1"]
        assert c(1, 1) == 0
        assert c(0, 0) == 5

    def test_extensional_multi_assignments_per_cost(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    variables: [v1, v2]
    values:
      7: 0 0 | 1 1 | 2 2
""")
        c = d.constraints["c1"]
        for i in range(3):
            assert c(i, i) == 7
        assert c(0, 1) == 0

    def test_extensional_unary(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    variables: v1
    values:
      2: 1
""")
        c = d.constraints["c1"]
        assert c.arity == 1
        assert c(1) == 2 and c(0) == 0

    def test_extensional_quoted_string_values(self):
        d = load_dcop("""
name: t
domains:
  d: {values: ['hot water', cold]}
variables:
  v1: {domain: d}
constraints:
  c1:
    type: extensional
    variables: v1
    values:
      3: "'hot water'"
""")
        assert d.constraints["c1"]("hot water") == 3

    def test_hard_constraint_infinity(self):
        d = load_dcop(BASE + """
constraints:
  c1:
    type: extensional
    default: .inf
    variables: [v1, v2]
    values:
      0: 0 1
""")
        assert d.constraints["c1"](0, 0) == float("inf")
        assert d.constraints["c1"](0, 1) == 0


class TestVariablesAndAgents:
    def test_variable_with_cost_function(self):
        d = load_dcop("""
name: t
domains:
  d: {values: [0, 1, 2]}
variables:
  v1:
    domain: d
    cost_function: v1 * 2
""")
        assert d.variables["v1"].cost_for_val(2) == 4

    def test_variable_noisy_cost(self):
        d = load_dcop("""
name: t
domains:
  d: {values: [0, 1]}
variables:
  v1:
    domain: d
    cost_function: v1 * 2
    noise_level: 0.05
""")
        v = d.variables["v1"]
        assert 0 <= v.cost_for_val(0) < 0.05

    def test_initial_value(self):
        d = load_dcop("""
name: t
domains:
  d: {values: [0, 1]}
variables:
  v1: {domain: d, initial_value: 1}
""")
        assert d.variables["v1"].initial_value == 1

    def test_agents_list_form(self):
        d = load_dcop(BASE + "agents: [a1, a2]\n")
        assert set(d.agents) == {"a1", "a2"}

    def test_agents_with_capacity(self):
        d = load_dcop(BASE + """
agents:
  a1: {capacity: 7}
""")
        assert d.agents["a1"].capacity == 7

    def test_hosting_costs_and_routes(self):
        d = load_dcop(BASE + """
agents: [a1, a2]
routes:
  default: 5
  a1: {a2: 2}
hosting_costs:
  default: 9
  a1:
    default: 3
    computations: {v1: 1}
""")
        a1, a2 = d.agents["a1"], d.agents["a2"]
        assert a1.route("a2") == 2
        assert a2.route("a1") == 2   # symmetric
        assert a1.hosting_cost("v1") == 1
        assert a1.hosting_cost("other") == 3
        assert a2.hosting_cost("v1") == 9   # global default

    def test_distribution_hints(self):
        d = load_dcop(BASE + """
distribution_hints:
  must_host:
    a1: [v1]
""")
        assert d.dist_hints.must_host("a1") == ["v1"]


class TestRoundTrips:
    def _roundtrip(self, yaml_str):
        d1 = load_dcop(yaml_str)
        d2 = load_dcop(dcop_yaml(d1))
        return d1, d2

    def test_intention_roundtrip(self):
        d1, d2 = self._roundtrip(BASE + """
constraints:
  c1:
    type: intention
    function: abs(v1 - v2)
""")
        for a in ((0, 0), (0, 2), (2, 1)):
            assert d1.constraints["c1"](*a) == d2.constraints["c1"](*a)

    def test_extensional_roundtrip(self):
        d1, d2 = self._roundtrip(BASE + """
constraints:
  c1:
    type: extensional
    default: 4
    variables: [v1, v2]
    values:
      1: 0 0 | 2 2
""")
        c1, c2 = d1.constraints["c1"], d2.constraints["c1"]
        for i in range(3):
            for j in range(3):
                assert c1(i, j) == c2(i, j)

    def test_objective_and_name_roundtrip(self):
        d1, d2 = self._roundtrip(
            BASE.replace("objective: min", "objective: max"))
        assert d2.name == "t" and d2.objective == "max"

    def test_agents_roundtrip(self):
        _, d2 = self._roundtrip(BASE + """
agents:
  a1: {capacity: 7}
  a2: {capacity: 8}
routes:
  a1: {a2: 2}
""")
        assert d2.agents["a1"].capacity == 7
        assert d2.agents["a1"].route("a2") == 2

    def test_yaml_agents_roundtrip(self):
        agents = [AgentDef("a1", capacity=5), AgentDef("a2", foo="x")]
        loaded = load_agents(yaml_agents(agents))
        assert [a.name for a in loaded] == ["a1", "a2"]
        assert loaded[0].capacity == 5

    def test_scenario_roundtrip(self):
        s = load_scenario("""
events:
  - id: e1
    delay: 2.5
  - id: e2
    actions:
      - type: remove_agent
        agent: a1
""")
        s2 = load_scenario(yaml_scenario(s))
        assert len(s2.events) == 2
        assert s2.events[0].is_delay and s2.events[0].delay == 2.5
        assert s2.events[1].actions[0].type == "remove_agent"
        assert s2.events[1].actions[0].args["agent"] == "a1"

    def test_device_solve_after_roundtrip(self):
        # The dumped file must stay solvable with identical cost.
        from pydcop_tpu.api import solve

        yaml_str = BASE + """
constraints:
  c1:
    type: intention
    function: 1 if v1 == v2 else 0
"""
        d1, d2 = self._roundtrip(yaml_str)
        r1 = solve(d1, "dpop")
        r2 = solve(d2, "dpop")
        assert r1["cost"] == r2["cost"] == 0


# ------------------------------------------------------------------ #
# The libyaml binding (ISSUE 27): the chosen loader against PyYAML's
# pure-Python SafeLoader, on every document the repo ships or makes.

INSTANCES_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "instances")
INSTANCE_FILES = sorted(
    f for f in os.listdir(INSTANCES_DIR) if f.endswith((".yml", ".yaml")))
# One small instance of each ``pydcop generate`` family that
# test_generators.py builds.
GENERATED = {
    "graph_coloring": lambda: generate_graph_coloring(
        10, 3, "random", p_edge=0.3, seed=1),
    "graph_coloring_soft_grid": lambda: generate_graph_coloring(
        16, 3, "grid", soft=True, seed=3),
    "ising": lambda: generate_ising(3, 3, seed=1)[0],
    "meetings": lambda: generate_meetings(4, 3, 3, 2, seed=0),
    "secp": lambda: generate_secp(6, 2, 3, seed=0),
    "iot": lambda: generate_iot(12, seed=0),
    "small_world": lambda: generate_small_world(12, 4, seed=0),
}


def _document(source: str) -> str:
    if source in GENERATED:
        return dcop_yaml(GENERATED[source]())
    with open(os.path.join(INSTANCES_DIR, source), encoding="utf-8") as f:
        return f.read()


def _assert_same_dcop(a: DCOP, b: DCOP):
    assert a.name == b.name and a.objective == b.objective
    assert list(a.variables) == list(b.variables)
    assert {n: list(d.values) for n, d in a.domains.items()} == \
        {n: list(d.values) for n, d in b.domains.items()}
    for name, v in a.variables.items():
        assert v.domain.name == b.variables[name].domain.name
        assert v.initial_value == b.variables[name].initial_value
    assert list(a.constraints) == list(b.constraints)
    for name, c in a.constraints.items():
        other = b.constraints[name]
        assert c.scope_names == other.scope_names
        np.testing.assert_array_equal(c.to_array(), other.to_array())
    assert list(a.agents) == list(b.agents)


@pytest.mark.parametrize("source", INSTANCE_FILES + sorted(GENERATED))
def test_chosen_loader_gives_the_pure_python_loaders_data(source):
    text = _document(source)
    data = yamldcop._yaml_load(text)
    reference = yaml.load(text, Loader=yaml.SafeLoader)
    assert data == reference
    if "name" not in reference:
        # The scenario file: no DCOP to build, the events instead.
        assert [e.id for e in load_scenario(text).events] == \
            [e["id"] for e in reference["events"]]
        return
    _assert_same_dcop(yamldcop._build_dcop(data, INSTANCES_DIR),
                      yamldcop._build_dcop(reference, INSTANCES_DIR))


@pytest.mark.parametrize(
    "source", [f for f in INSTANCE_FILES if "scenario" not in f]
    + sorted(GENERATED))
def test_dcop_yaml_bytes_are_safe_dumps_and_round_trip(source,
                                                       monkeypatch):
    """Served payloads and ``instance.yaml`` are ``dcop_yaml``'s bytes:
    they must stay PyYAML's ``safe_dump`` of the same data."""
    dcop = load_dcop(_document(source), main_dir=INSTANCES_DIR)
    handed = []
    dump = yamldcop._yaml_dump

    def spy(data, **kw):
        handed.append((data, kw))
        return dump(data, **kw)

    monkeypatch.setattr(yamldcop, "_yaml_dump", spy)
    text = dcop_yaml(dcop)
    (data, kw), = handed
    assert text.encode() == yaml.safe_dump(
        data, sort_keys=False, **kw).encode()
    if source != "coloring_chain_func.yaml":
        # (``dcop_yaml`` has always dropped that file's ``source:``
        # of an external python constraint: it cannot be re-loaded.)
        _assert_same_dcop(load_dcop(text, main_dir=INSTANCES_DIR), dcop)


def test_agents_scenario_and_dist_dumps_are_safe_dumps():
    agents = [AgentDef("a1", capacity=5, hosting_costs={"v1": 2}),
              AgentDef("a2", routes={"a1": 3})]
    assert yaml_agents(agents) == yaml.safe_dump(
        yaml.load(yaml_agents(agents), Loader=yaml.SafeLoader),
        sort_keys=False)
    scenario = load_scenario(_document("scenario_remove_a1.yaml"))
    assert yaml_scenario(scenario) == yaml.safe_dump(
        yaml.load(yaml_scenario(scenario), Loader=yaml.SafeLoader),
        sort_keys=False)
    dist = yamldcop.load_dist("distribution: {a1: [v1, v2], a2: []}")
    assert dist.mapping == {"a1": ["v1", "v2"], "a2": []}
    assert yamldcop.load_dist(yamldcop.yaml_dist(dist, cost=3)).mapping \
        == dist.mapping


MALFORMED = {
    "bad_indent": "name: t\ndomains:\n  d: {values: [0, 1]}\n"
                  " variables:\n  v: {domain: d}\n",
    "unclosed_flow_sequence": "name: t\ndomains:\n  d: {values: [0, 1}\n",
    "tab_indentation": "name: t\ndomains:\n\td: {values: [0, 1]}\n",
    "python_object_tag": "name: !!python/object/apply:os.getcwd []\n",
    "empty_document": "",
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_document_raises_as_under_safe_loader(case):
    text = MALFORMED[case]
    errors = (yaml.YAMLError, DcopInvalidFormatError)
    with pytest.raises(errors) as under_python:
        yamldcop._build_dcop(yaml.load(text, Loader=yaml.SafeLoader), ".")
    with pytest.raises(errors) as under_chosen:
        load_dcop(text)
    # The same family of refusal, not merely some refusal.
    assert isinstance(under_chosen.value, yaml.YAMLError) == \
        isinstance(under_python.value, yaml.YAMLError)


def test_post_solve_answers_400_for_a_malformed_document():
    from pydcop_tpu.serving.http import ServeFrontEnd
    from pydcop_tpu.serving.service import SolveService

    svc = SolveService(batch_window_s=0.1, max_batch=8)
    svc.start()
    front = ServeFrontEnd(svc, port=0).start()
    try:
        req = urllib.request.Request(
            front.url + "/solve",
            data=json.dumps(
                {"dcop": MALFORMED["python_object_tag"]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 400
        assert "bad problem" in json.loads(err.value.read())["error"]
    finally:
        front.stop()
        svc.stop(drain=False)


@pytest.mark.parametrize("body, status", [
    ("name: [unclosed\n  - {", 400),
    (BASE + "constraints:\n  c: {type: intention, function: v1 + v2}\n",
     200),
], ids=["not_yaml", "well_formed"])
def test_a_served_load_leaves_the_server_its_collector(body, status):
    """The handler thread's load pauses the process's collector: a
    bad body must not leave a long-lived server without it."""
    import gc

    from pydcop_tpu.serving.http import ServeFrontEnd
    from pydcop_tpu.serving.service import SolveService

    assert gc.isenabled()
    svc = SolveService(batch_window_s=0.01, max_batch=8)
    svc.start()
    front = ServeFrontEnd(svc, port=0).start()
    try:
        req = urllib.request.Request(
            front.url + "/solve",
            data=json.dumps({"dcop": body, "wait": True,
                             "params": {"max_cycles": 10}}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as reply:
                code, answer = reply.status, json.loads(reply.read())
        except urllib.error.HTTPError as err:
            code, answer = err.code, json.loads(err.read())
        assert code == status, answer
        if status == 400:
            assert "bad problem" in answer["error"]
        else:
            assert answer["status"] == "FINISHED"
        assert gc.isenabled()
        assert yamldcop._collector_pause.in_flight == 0
    finally:
        front.stop()
        svc.stop(drain=False)


def _module_copy(name: str):
    """``dcop/yamldcop.py`` executed again under another name: what
    ``importlib.reload`` would choose, without replacing the classes
    the rest of the process already holds."""
    spec = importlib.util.spec_from_file_location(name, yamldcop.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_loader_is_libyamls_where_pyyaml_has_it():
    assert yamldcop.YAML_LOADER == (
        "c" if yaml.__with_libyaml__ else "python")
    assert issubclass(yamldcop._Loader, (
        yaml.CSafeLoader if yaml.__with_libyaml__ else yaml.SafeLoader))


def test_version_line_names_the_loader(capsys):
    from pydcop_tpu.dcop_cli import main

    assert main(["--version"]) == 0
    assert f"(yaml loader: {yamldcop.YAML_LOADER})" in \
        capsys.readouterr().out


def test_without_libyaml_the_python_loader_is_chosen_and_loads(
        monkeypatch):
    monkeypatch.setattr(yaml, "__with_libyaml__", False)
    for name in ("CSafeLoader", "CSafeDumper", "CLoader", "CDumper"):
        monkeypatch.delattr(yaml, name, raising=False)
    plain = _module_copy("_yamldcop_without_libyaml")
    assert plain.YAML_LOADER == "python"
    assert plain._Loader is yaml.SafeLoader
    text = _document("coloring_12_3.yml")
    assert plain._yaml_load(text) == yamldcop._yaml_load(text)
    dcop = plain.load_dcop(text)
    _assert_same_dcop(dcop, load_dcop(text))
    assert plain.dcop_yaml(dcop) == dcop_yaml(dcop)


@pytest.mark.parametrize("session", ["ring", "file"])
def test_yaml_parse_span_names_the_loader(session):
    from pydcop_tpu.observability.flight import FlightRecorder
    from pydcop_tpu.observability.trace import tracer

    recorder = FlightRecorder(events=16)
    previous = tracer.flight
    tracer.set_flight(recorder if session == "ring" else None)
    if session == "file":
        tracer.enable()
    try:
        load_dcop(_document("coloring_chain.yaml"))
    finally:
        if session == "file":
            tracer.disable()
        tracer.set_flight(previous)
    events = tracer.events() if session == "file" else recorder.snapshot()
    tracer.clear()
    parse, = [e for e in events if e["name"] == "yaml_parse"]
    assert parse["args"]["loader"] == yamldcop.YAML_LOADER
