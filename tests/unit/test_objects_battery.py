"""Deep battery over dcop/objects.py — domains, the variable family,
agents, and the mass-creation helpers (reference test_dcop_variables.py
depth)."""

import numpy as np
import pytest

from pydcop_tpu.dcop.objects import (
    AgentDef,
    BinaryVariable,
    Domain,
    ExternalVariable,
    Variable,
    VariableDomain,
    VariableNoisyCostFunc,
    VariableWithCostDict,
    VariableWithCostFunc,
    _stable_noise,
    _uniform_streams,
    binary_domain,
    create_agents,
    create_binary_variables,
    create_variables,
    stable_noise_batch,
)
from pydcop_tpu.utils.simple_repr import from_repr, simple_repr

d3 = Domain("d3", "num", [0, 1, 2])


class TestDomain:
    def test_basics(self):
        d = Domain("colors", "color", ["R", "G"])
        assert d.name == "colors"
        assert d.type == "color"
        assert d.domain_type == "color"
        assert len(d) == 2
        assert list(d) == ["R", "G"]
        assert d[1] == "G"
        assert "R" in d and "B" not in d

    def test_index(self):
        assert d3.index(2) == 2
        with pytest.raises(ValueError):
            d3.index(99)

    def test_to_domain_value_exact_and_string(self):
        assert d3.to_domain_value(1) == (1, 1)
        assert d3.to_domain_value("1") == (1, 1)
        with pytest.raises(ValueError, match="not in domain"):
            d3.to_domain_value("9")

    def test_equality_and_hash(self):
        a = Domain("d", "t", [1, 2])
        b = Domain("d", "t", [1, 2])
        c = Domain("d", "t", [2, 1])
        assert a == b and hash(a) == hash(b)
        assert a != c
        assert a != Domain("d", "other", [1, 2])

    def test_values_immutable_tuple(self):
        assert isinstance(d3.values, tuple)

    def test_alias_and_binary_domain(self):
        assert VariableDomain is Domain
        assert list(binary_domain) == [0, 1]

    def test_wire_roundtrip(self):
        d = Domain("d", "t", ["x", "y"])
        assert from_repr(simple_repr(d)) == d


class TestVariable:
    def test_plain(self):
        v = Variable("v", d3)
        assert v.name == "v"
        assert v.domain is d3
        assert v.initial_value is None
        assert v.has_cost is False
        assert v.cost_for_val(2) == 0.0

    def test_domain_from_iterable(self):
        v = Variable("v", [5, 6])
        assert isinstance(v.domain, Domain)
        assert list(v.domain) == [5, 6]

    def test_initial_value_validated(self):
        assert Variable("v", d3, initial_value=2).initial_value == 2
        with pytest.raises(ValueError, match="not in domain"):
            Variable("v", d3, initial_value=9)

    def test_cost_vector_zero(self):
        np.testing.assert_array_equal(
            Variable("v", d3).cost_vector(), [0.0, 0.0, 0.0])

    def test_clone_equal(self):
        v = Variable("v", d3, initial_value=1)
        c = v.clone()
        assert c == v and c is not v
        assert c.initial_value == 1

    def test_equality_is_type_sensitive(self):
        assert Variable("b", binary_domain) != BinaryVariable("b")

    def test_wire_roundtrip(self):
        v = Variable("v", d3, initial_value=2)
        v2 = from_repr(simple_repr(v))
        assert v2 == v and v2.initial_value == 2


class TestCostVariables:
    def test_cost_dict(self):
        v = VariableWithCostDict("v", d3, {0: 1.5, 2: 3.0})
        assert v.has_cost
        assert v.cost_for_val(0) == 1.5
        assert v.cost_for_val(1) == 0.0   # missing -> 0
        np.testing.assert_array_equal(v.cost_vector(), [1.5, 0.0, 3.0])
        assert v.costs == {0: 1.5, 2: 3.0}

    def test_cost_func_callable(self):
        v = VariableWithCostFunc("v", d3, cost_func=lambda x: x * 2)
        assert v.cost_for_val(2) == 4

    def test_cost_func_expression(self):
        v = VariableWithCostFunc("v", d3, cost_func="v * 10")
        assert v.cost_for_val(1) == 10

    def test_cost_func_expression_must_use_own_name(self):
        with pytest.raises(ValueError, match="depend exactly"):
            VariableWithCostFunc("v", d3, cost_func="other + 1")

    def test_cost_func_wire_roundtrip(self):
        v = VariableWithCostFunc("v", d3, cost_func="v * 10")
        v2 = from_repr(simple_repr(v))
        assert v2.cost_for_val(2) == 20

    def test_noisy_cost_deterministic_in_name_and_seed(self):
        a = VariableNoisyCostFunc("v", d3, "v * 1.0", noise_level=0.1,
                                  seed=4)
        b = VariableNoisyCostFunc("v", d3, "v * 1.0", noise_level=0.1,
                                  seed=4)
        c = VariableNoisyCostFunc("v", d3, "v * 1.0", noise_level=0.1,
                                  seed=5)
        assert a.cost_for_val(1) == b.cost_for_val(1)
        assert a.cost_for_val(1) != c.cost_for_val(1)

    def test_noisy_cost_bounded(self):
        v = VariableNoisyCostFunc("v", d3, "v * 1.0", noise_level=0.01)
        for val in d3:
            assert 0 <= v.cost_for_val(val) - float(val) < 0.01
        assert v.noise_level == 0.01

    def test_noisy_clone_same_noise(self):
        v = VariableNoisyCostFunc("v", d3, "v * 1.0", seed=7)
        assert v.clone().cost_for_val(2) == v.cost_for_val(2)

    def test_noisy_wire_roundtrip_preserves_noise(self):
        v = VariableNoisyCostFunc("v", d3, "v * 1.0", noise_level=0.05,
                                  seed=3)
        v2 = from_repr(simple_repr(v))
        assert v2.cost_for_val(1) == v.cost_for_val(1)


# The batch draw (the host compile's, engine/compile.py) against the
# per-name ``default_rng`` stream it replaces there: `==` on every
# element, so a moved bit fails here and not in a tie-break.
NOISE_NAMES = {
    "ascii": ["v", "x_12", "light_0003", "a" * 300, "v"],
    "non_ascii": ["é_ü", "变量_1", "ß", "🙂"],
    "empty_name": [""],
    "generated_10k": [f"v{i:05d}" for i in range(10_000)],
    "no_names": [],
}
NOISE_SEEDS = [None, 0, 2**32 - 1, 2**32 + 5]


@pytest.mark.parametrize("n", [1, 2, 3, 5, 10, 40])
@pytest.mark.parametrize("seed", NOISE_SEEDS,
                         ids=["none", "0", "under_2_32", "over_2_32"])
@pytest.mark.parametrize("names", list(NOISE_NAMES))
def test_batch_noise_is_the_per_name_stream(names, seed, n):
    names = NOISE_NAMES[names]
    if len(names) > 1000 and (seed, n) not in [(None, 3), (2**32 + 5, 40)]:
        names = names[::97]  # the whole 10k on two cases, a stride else
    batch = stable_noise_batch(names, n, 0.01, seed)
    assert batch.dtype == np.float64 and batch.shape == (len(names), n)
    for name, row in zip(names, batch):
        assert (row == _stable_noise(name, n, 0.01, seed)).all(), name


@pytest.mark.parametrize("seed", [0, 2**32 - 1, 2**32, 2**64 - 1])
def test_uniform_streams_are_numpys_default_rng(seed):
    # Straight against numpy: a release that moved SeedSequence's
    # mixing or PCG64's stream would change every noisy solve.
    ours = _uniform_streams(np.array([seed, seed], dtype=np.uint64), 40)
    theirs = np.random.default_rng(seed).random(40)
    assert (ours[0] == theirs).all() and (ours[1] == theirs).all()


def test_batch_noise_prefix_and_level():
    # Mixed domain sizes draw the largest and mask: the first d of n
    # draws are the d-draw stream; a level of 0 is all zeros.
    names = ["a", "b", "c"]
    wide = stable_noise_batch(names, 10, 0.02, 7)
    assert (wide[:, :3] == stable_noise_batch(names, 3, 0.02, 7)).all()
    assert (0 <= wide).all() and (wide < 0.02).all()
    assert not stable_noise_batch(names, 4, 0.0, 7).any()


class TestBinaryAndExternal:
    def test_binary_variable(self):
        b = BinaryVariable("b")
        assert list(b.domain) == [0, 1]
        assert b.initial_value == 0
        assert b.clone() == b

    def test_external_default_value(self):
        e = ExternalVariable("e", d3)
        assert e.value == 0   # first domain value

    def test_external_set_validates(self):
        e = ExternalVariable("e", d3, value=1)
        with pytest.raises(ValueError, match="not in domain"):
            e.value = 9

    def test_external_fires_callbacks_on_change_only(self):
        e = ExternalVariable("e", d3, value=0)
        seen = []
        e.subscribe(seen.append)
        e.value = 1
        e.value = 1   # unchanged: no event
        e.value = 2
        assert seen == [1, 2]

    def test_external_unsubscribe(self):
        e = ExternalVariable("e", d3)
        seen = []
        e.subscribe(seen.append)
        e.unsubscribe(seen.append)
        e.value = 1
        assert seen == []

    def test_external_wire_roundtrip(self):
        e = ExternalVariable("e", d3, value=2)
        e2 = from_repr(simple_repr(e))
        assert e2.value == 2 and e2.name == "e"


class TestMassCreation:
    def test_create_variables_string_indexes(self):
        vs = create_variables("x_", ["a", "b"], d3)
        assert set(vs) == {"x_a", "x_b"}
        assert vs["x_a"].name == "x_a"

    def test_create_variables_cartesian(self):
        vs = create_variables("x_", [["a", "b"], range(2)], d3)
        assert set(vs) == {("a", 0), ("a", 1), ("b", 0), ("b", 1)}
        assert vs[("b", 1)].name == "x_b_1"

    def test_create_variables_range(self):
        vs = create_variables("v", range(3), d3)
        assert set(vs) == {"v0", "v1", "v2"}

    def test_create_binary_variables(self):
        vs = create_binary_variables("x_", [["c1", "c2"], ["a1"]])
        assert set(vs) == {("c1", "a1"), ("c2", "a1")}
        assert isinstance(vs[("c1", "a1")], BinaryVariable)

    def test_create_agents_range(self):
        ags = create_agents("a", range(2), capacity=42)
        assert set(ags) == {"a0", "a1"}
        assert ags["a0"].capacity == 42


class TestAgentDef:
    def test_defaults(self):
        a = AgentDef("a1")
        assert a.capacity == 100
        assert a.default_hosting_cost == 0
        assert a.default_route == 1
        assert a.hosting_cost("anything") == 0
        assert a.route("a2") == 1

    def test_route_to_self_is_zero(self):
        assert AgentDef("a1").route("a1") == 0

    def test_explicit_costs_and_routes(self):
        a = AgentDef("a1", default_hosting_cost=5,
                     hosting_costs={"c1": 2},
                     default_route=3, routes={"a2": 7})
        assert a.hosting_cost("c1") == 2
        assert a.hosting_cost("c9") == 5
        assert a.route("a2") == 7
        assert a.route("a9") == 3

    def test_extra_attrs_as_attributes(self):
        a = AgentDef("a1", capacity=11, foo="bar")
        assert a.capacity == 11
        assert a.foo == "bar"
        with pytest.raises(AttributeError):
            _ = a.nope

    def test_equality(self):
        assert AgentDef("a1", capacity=5) == AgentDef("a1", capacity=5)
        assert AgentDef("a1", capacity=5) != AgentDef("a1", capacity=6)

    def test_wire_roundtrip_with_extras(self):
        a = AgentDef("a1", capacity=9, hosting_costs={"c": 1.5},
                     routes={"a2": 2.0}, foo="bar")
        a2 = from_repr(simple_repr(a))
        assert a2 == a
        assert a2.foo == "bar"
        assert a2.hosting_cost("c") == 1.5
