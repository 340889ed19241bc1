"""engine/timing.py — device timing under asynchronous dispatch:
every window closes on a host fetch of the result, and the per-call
constant is removed by two-point differencing."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pydcop_tpu.engine.timing import (
    marginal_seconds_per_cycle,
    sync,
    timed_call,
    warmed_marginal,
)


class TestSync:
    def test_fetches_smallest_leaf_to_host(self, monkeypatch):
        """sync must force a REAL host fetch (device_get), and of the
        cheapest leaf: the scalar, not the big array — the fetch is
        the barrier, its size is the overhead."""
        import pydcop_tpu.engine.timing as timing_mod

        fetched = []
        real_device_get = jax.device_get

        def spy(x):
            fetched.append(getattr(x, "size", None))
            return real_device_get(x)

        monkeypatch.setattr(timing_mod.jax, "device_get", spy)
        big = jnp.ones((64, 64))
        small = jnp.int32(7)
        out = sync((big, small))
        assert out == (big, small)
        assert fetched == [1], (
            "sync must fetch exactly one leaf, the smallest")

    def test_no_fetch_without_array_leaves(self, monkeypatch):
        import pydcop_tpu.engine.timing as timing_mod

        fetched = []
        monkeypatch.setattr(
            timing_mod.jax, "device_get",
            lambda x: fetched.append(x))
        assert sync((1, "x", None)) == (1, "x", None)
        assert fetched == []

    def test_returns_pytree_unchanged(self):
        out = {"a": jnp.arange(4), "b": (jnp.float32(1.5),)}
        got = sync(out)
        assert got is out

    def test_handles_non_array_leaves(self):
        out = (jnp.arange(3), 7, "label", None)
        assert sync(out) is out

    def test_handles_empty_and_no_array_trees(self):
        assert sync({}) == {}
        assert sync((1, "x")) == (1, "x")

    def test_forces_materialization(self):
        # The smallest leaf is fetched; after sync the value must be
        # host-readable and correct.
        out = sync((jnp.arange(100), jnp.int32(42)))
        assert int(out[1]) == 42


class TestTimedCall:
    def test_out_and_positive_elapsed(self):
        fn = jax.jit(lambda x: (x * 2, jnp.sum(x)))
        x = jnp.arange(8.0)
        out, elapsed = timed_call(fn, x)
        assert elapsed > 0
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.arange(8.0) * 2)


class TestMarginalSecondsPerCycle:
    def test_recovers_slope_and_fixed(self):
        # Simulated device: fixed dispatch latency + linear per-cycle
        # cost, the regime the differencing exists for.
        per, fixed = 0.002, 0.005

        def run_cycles(n):
            time.sleep(fixed + per * n)

        got_per, got_fixed = marginal_seconds_per_cycle(
            run_cycles, 10, 40, reps=3)
        assert got_per == pytest.approx(per, rel=0.5)
        assert got_fixed == pytest.approx(fixed, abs=0.02)

    @pytest.mark.parametrize("fixed", [0.0, 0.004, 0.02])
    def test_slope_invariant_to_injected_constant_offset(self, fixed):
        """The whole point of the two-point differencing: a constant
        per-call offset (dispatch, sync, fetch) of ANY size must
        not move the recovered per-cycle rate."""
        per = 0.001

        got_per, got_fixed = marginal_seconds_per_cycle(
            lambda n: time.sleep(fixed + per * n), 5, 45, reps=3)
        assert got_per == pytest.approx(per, rel=0.5)
        # And the offset itself lands in the fixed term, not the rate.
        assert got_fixed == pytest.approx(fixed, abs=0.02)

    def test_noise_floored_at_zero(self):
        # A program faster than timer noise must clamp to 0, never a
        # negative rate.
        got_per, got_fixed = marginal_seconds_per_cycle(
            lambda n: None, 1, 2, reps=3)
        assert got_per >= 0.0
        assert got_fixed >= 0.0

    def test_rejects_bad_bounds(self):
        with pytest.raises(ValueError, match="hi > lo"):
            marginal_seconds_per_cycle(lambda n: None, 5, 5)

    def test_warmed_marginal_builds_once_and_returns_hi_output(self):
        calls = []

        def make_fn(n):
            calls.append(n)
            return lambda x: (x, jnp.int32(n))

        x = jnp.arange(4.0)
        per, fixed, out = warmed_marginal(make_fn, 3, 9, args=(x,),
                                          reps=2)
        # One build per cycle count, never per rep.
        assert sorted(calls) == [3, 9]
        # The third element is the warm full-length output — callers
        # reuse it instead of re-running the program.
        assert int(out[1]) == 9
        assert per >= 0.0 and fixed >= 0.0

    def test_real_jit_program_scales(self):
        # End-to-end on the test backend (CPU): a kernel whose work
        # scales with the cycle count must report a positive slope.
        def make(n):
            def body(i, a):
                return jnp.sin(a) + 1e-6 * i
            return jax.jit(
                lambda x: jax.lax.fori_loop(0, n, body, x))

        x = jnp.ones((512, 512), jnp.float32)
        fns = {n: make(n) for n in (2, 80)}
        for f in fns.values():
            sync(f(x))
        per, _ = marginal_seconds_per_cycle(
            lambda n: fns[n](x), 2, 80, reps=3)
        assert per > 0
