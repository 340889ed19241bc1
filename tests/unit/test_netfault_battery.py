"""Netfault battery (ISSUE 19): the injectable link-fault plane and
the epoch-fenced ownership it exists to prove.

- plan grammar: clause/partition parsing, bad specs rejected, the
  ``PYDCOP_NETFAULT`` / install() / clear() registry;
- determinism: the same seeded plan over the same call sequence
  injects the identical fault pattern (thread timing elsewhere must
  not perturb a chaos replay);
- seam semantics: drop/blackhole/partition raise the retry-safe
  :class:`NotSent`, ``lose_response`` surfaces as a plain ambiguous
  ``OSError`` *after* delivery, ``times=`` retires clauses,
  ``path=`` scopes a clause away from the probes sharing its link;
- seam coverage: nothing in ``pydcop_tpu/serving/`` opens a socket
  outside the seam (the tools/static_check.py lint, run in-process);
- epoch monotonicity: the router's per-session epoch authority only
  advances — across note/bump/floor — and fences merge by max;
- the 409 fencing surface over real HTTP: a stale-epoch PATCH and a
  PATCH against a fenced session both answer a structured 409
  (``stale_epoch: true`` + both epochs), fencing is idempotent, and
  a fence carrying a lower epoch than the copy's is itself rejected.
"""

import os

import pytest

from pydcop_tpu.serving import netfault
from pydcop_tpu.serving.netfault import FaultPlan, NotSent

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_plan():
    netfault.clear()
    yield
    netfault.clear()


# ------------------------------------------------------------------ #
# plan grammar


class TestPlanGrammar:
    def test_clause_parse(self):
        p = FaultPlan.parse(
            "seed=7;link=router>replica-*,drop=0.25,delay_ms=20;"
            "link=*>hostB,lose_response=1.0,times=1,path=/solve")
        assert p.seed == 7
        assert len(p.clauses) == 2
        c0, c1 = p.clauses
        assert (c0.src, c0.dst, c0.drop, c0.delay_ms) == \
            ("router", "replica-*", 0.25, 20.0)
        assert (c1.dst, c1.lose_response, c1.times, c1.path) == \
            ("hostB", 1.0, 1, "/solve")

    def test_partition_parse(self):
        p = FaultPlan.parse("partition=host0+host1/hostB,hold_s=0.01")
        assert len(p.partitions) == 1
        part = p.partitions[0]
        assert part.group_a == ["host0", "host1"]
        assert part.group_b == ["hostB"]
        assert part.hold_s == 0.01
        assert part.severs(("router", "host0"), ("replica-2", "hostB"))
        assert part.severs(("worker", "hostB"), ("router", "host1"))
        assert not part.severs(("router", "host0"),
                               ("replica-1", "host1"))

    @pytest.mark.parametrize("spec", [
        "link=router,drop=0.1",          # no '>'
        "drop",                          # not key=value
        "link=a>b,wobble=1",             # unknown key
        "partition=justonegroup",        # no '/'
        "partition=a/b,drop=0.5",        # stray key on a partition
    ])
    def test_bad_specs_rejected(self, spec):
        with pytest.raises(ValueError):
            FaultPlan.parse(spec)

    def test_install_clear_registry(self):
        assert netfault.plan() is None
        p = netfault.install("link=a>b,drop=1.0")
        assert netfault.plan() is p
        assert netfault.counters() == {}
        netfault.clear()
        assert netfault.plan() is None
        assert netfault.counters() == {}


# ------------------------------------------------------------------ #
# determinism + fault semantics (decide() directly — no sockets)


def _pattern(plan, n=64):
    out = []
    for _ in range(n):
        try:
            post = plan.decide("router", ("replica-1", "hostB"),
                               timeout=0.01)
            out.append("L" if post["lose_response"]
                       else "D" if post["dup"] else ".")
        except NotSent:
            out.append("x")
    return "".join(out)


class TestDeterminism:
    def test_same_plan_same_sequence_same_faults(self):
        spec = "seed=11;link=*>replica-*,drop=0.3,dup=0.1"
        a = _pattern(FaultPlan.parse(spec))
        b = _pattern(FaultPlan.parse(spec))
        assert a == b
        assert "x" in a  # drops actually fired at p=0.3 over 64 draws

    def test_seed_changes_the_pattern(self):
        a = _pattern(FaultPlan.parse("seed=1;drop=0.5"))
        b = _pattern(FaultPlan.parse("seed=2;drop=0.5"))
        assert a != b

    def test_link_scoping_misses_other_links(self):
        p = FaultPlan.parse("link=router>hostB,drop=1.0")
        with pytest.raises(NotSent):
            p.decide("router", ("replica-2", "hostB"), timeout=0.01)
        assert p.decide("router", ("replica-0", "host0"),
                        timeout=0.01) == \
            {"dup": False, "lose_response": False}

    def test_plan_that_matches_nothing_injects_nothing(self):
        """An installed plan whose clauses and partition all miss the
        live link scans in full on every hop and counts no fault."""
        p = FaultPlan.parse(
            "seed=5;link=*>replica-*,drop=1.0,delay_ms=5;"
            "link=*>*,path=/no-such-endpoint,blackhole=1;"
            "partition=ghost-a/ghost-b")
        for _ in range(40):
            assert p.decide("client", "worker-0", timeout=0.01,
                            path="/solve") == \
                {"dup": False, "lose_response": False}
        assert p.injected() == {}

    def test_times_retires_the_clause(self):
        p = FaultPlan.parse("link=*>*,lose_response=1.0,times=1")
        first = p.decide("router", "replica-0", timeout=0.01)
        assert first["lose_response"] is True
        for _ in range(5):
            post = p.decide("router", "replica-0", timeout=0.01)
            assert post["lose_response"] is False
        assert p.clauses[0].fired == 1

    def test_path_scope_spares_the_probes(self):
        p = FaultPlan.parse("link=*>*,blackhole=1,path=/solve,"
                            "hold_s=0.0")
        # The probe sharing the link is untouched...
        p.decide("router", "replica-0", timeout=0.01,
                 path="/healthz")
        # ...the scoped path is eaten.
        with pytest.raises(NotSent):
            p.decide("router", "replica-0", timeout=0.01,
                     path="/solve")
        assert p.injected() == {"blackhole": 1}

    def test_partition_is_bidirectional_notsent(self):
        p = FaultPlan.parse("partition=host0/hostB,hold_s=0.0")
        with pytest.raises(NotSent):
            p.decide(("router", "host0"), ("w", "hostB"),
                     timeout=0.01)
        with pytest.raises(NotSent):
            p.decide(("w", "hostB"), ("router", "host0"),
                     timeout=0.01)
        assert p.injected()["partition"] == 2


# ------------------------------------------------------------------ #
# seam coverage


class TestSeamCoverage:
    def test_connect_failure_is_notsent(self):
        # Port 9 unbound: a real connect refusal maps to the
        # retry-safe class, with or without a plan installed.
        with pytest.raises(NotSent):
            netfault.exchange("a", "b", "127.0.0.1", 9, "GET", "/x",
                              timeout=0.2)

    def test_injected_blackhole_never_touches_the_socket(self):
        netfault.install("link=a>b,blackhole=1,hold_s=0.0")
        with pytest.raises(NotSent):
            # Host that would hang a real connect: the injected fault
            # must fire before any socket work.
            netfault.exchange("a", "b", "203.0.113.1", 80, "GET",
                              "/x", timeout=0.05)
        assert netfault.counters() == {"blackhole": 1}

    def test_router_notsent_is_the_seam_class(self):
        from pydcop_tpu.serving import router as router_mod

        assert router_mod.ForwardNotSent is NotSent

    def test_serving_has_no_raw_socket_io(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "static_check",
            os.path.join(REPO, "tools", "static_check.py"))
        static_check = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(static_check)
        assert static_check.check_netfault_seam() == 0


# ------------------------------------------------------------------ #
# epoch monotonicity (router authority, no processes)


class TestEpochMonotonicity:
    def _router(self):
        from pydcop_tpu.serving.router import FleetRouter

        return FleetRouter(replicas=1)

    def test_note_then_bump_only_advances(self):
        router = self._router()
        assert router.session_epoch("s1") == 1
        router.note_session("s1")
        assert router.session_epoch("s1") == 1
        seen = [router.bump_epoch("s1") for _ in range(4)]
        assert seen == [2, 3, 4, 5]
        assert router.session_epoch("s1") == 5

    def test_floor_keeps_the_advance_strict(self):
        router = self._router()
        assert router.bump_epoch("s1", floor=7) == 7
        # A floor BELOW the tracked epoch still advances past it.
        assert router.bump_epoch("s1", floor=3) == 8

    def test_fences_merge_by_max(self):
        router = self._router()
        router.record_fence(0, "s1", 3)
        router.record_fence(0, "s1", 2)
        router.record_fence(0, "s2", 4)
        assert router._fences[0] == {"s1": 3, "s2": 4}


# ------------------------------------------------------------------ #
# the 409 fencing surface (real single service over HTTP)


def _path_dcop(seed=3):
    import numpy as np

    from pydcop_tpu.dcop.objects import AgentDef, Domain, Variable
    from pydcop_tpu.dcop.dcop import DCOP
    from pydcop_tpu.dcop.relations import NAryMatrixRelation

    rng = np.random.default_rng(seed)
    d = Domain("c", "", [0, 1, 2])
    dcop = DCOP(f"netfault_fence_{seed}", objective="min")
    vs = [Variable(f"v{i}", d) for i in range(4)]
    for v in vs:
        dcop.add_variable(v)
    for k in range(3):
        table = rng.integers(0, 10, size=(3, 3)).astype(float)
        dcop.add_constraint(NAryMatrixRelation(
            [vs[k], vs[k + 1]], table, f"c{k}"))
    dcop.add_agents([AgentDef("a0")])
    batch = [{"type": "change_factor", "name": "c1",
              "table": rng.integers(0, 10, size=(3, 3))
              .astype(float).tolist()}]
    return dcop, batch


@pytest.mark.slow
class TestFencingSurface:
    def _request(self, url, method="GET", payload=None):
        import json
        import urllib.error
        import urllib.request

        data = (json.dumps(payload).encode()
                if payload is not None else None)
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as err:
            return err.code, json.loads(err.read())

    def test_stale_epoch_patch_and_fence(self):
        from pydcop_tpu import api
        from pydcop_tpu.dcop.yamldcop import dcop_yaml

        dcop, batch = _path_dcop()
        handle = api.serve(port=0, batch_window_s=0.05)
        try:
            url = handle.url
            status, body = self._request(
                url + "/session", "POST",
                {"dcop": dcop_yaml(dcop),
                 "params": {"noise": 0.01, "stability": 0.001,
                            "max_cycles": 200}})
            assert status == 201, body
            sid = body["session_id"]

            # Correct epoch applies; a stale one is a structured 409.
            status, out = self._request(
                url + f"/session/{sid}/events", "PATCH",
                {"events": batch, "epoch": 1})
            assert status == 200, out
            status, out = self._request(
                url + f"/session/{sid}/events", "PATCH",
                {"events": batch, "epoch": 99})
            assert status == 409 and out["stale_epoch"] is True, out
            assert out["session_epoch"] == 1
            assert out["request_epoch"] == 99

            # A fence below the copy's epoch is itself stale...
            status, out = self._request(
                url + "/admin/fence_session", "POST",
                {"session_id": sid, "epoch": 0})
            assert status == 409 and out["stale_epoch"] is True, out
            # ...a current-or-higher one revokes the copy, terminally
            # and idempotently.
            for _ in range(2):
                status, out = self._request(
                    url + "/admin/fence_session", "POST",
                    {"session_id": sid, "epoch": 3})
                assert status == 200, out
                assert out["status"] == "FENCED"
            status, st = self._request(url + f"/session/{sid}")
            assert st["status"] == "FENCED" and st["epoch"] == 3, st

            # Every write against the fenced copy — even carrying the
            # new epoch — answers the structured 409.
            status, out = self._request(
                url + f"/session/{sid}/events", "PATCH",
                {"events": batch, "epoch": 3})
            assert status == 409 and out["stale_epoch"] is True, out
        finally:
            handle.stop()
