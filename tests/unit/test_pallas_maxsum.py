"""Pallas binary-factor kernel tests (interpret mode: validates the
lane-major layout and the unrolled min-plus on any backend).  The
oracle is the XLA path (ops.maxsum.factor_to_var) on the same bucket.
"""

import numpy as np
import pytest

from pydcop_tpu.dcop.objects import Domain, Variable
from pydcop_tpu.dcop.relations import NAryMatrixRelation
from pydcop_tpu.engine.compile import compile_factor_graph
from pydcop_tpu.ops import maxsum as ops
from pydcop_tpu.ops.pallas_maxsum import binary_factor_update


def _bucket(n_factors: int, d: int, seed: int):
    rng = np.random.default_rng(seed)
    dom = Domain("d", "", list(range(d)))
    n_vars = max(4, n_factors // 2)
    vs = [Variable(f"v{i}", dom) for i in range(n_vars)]
    cs = []
    for k in range(n_factors):
        i, j = rng.choice(n_vars, size=2, replace=False)
        table = rng.normal(size=(d, d))
        cs.append(NAryMatrixRelation([vs[i], vs[j]], table, f"c{k}"))
    graph, _ = compile_factor_graph(vs, cs)
    assert len(graph.buckets) == 1 and graph.buckets[0].arity == 2
    msgs = rng.normal(size=(graph.buckets[0].n_factors, 2, d)).astype(
        np.float32)
    return graph, msgs


@pytest.mark.parametrize("n_factors,d,seed", [
    (7, 3, 0),        # smaller than one lane block
    (128, 3, 1),      # exactly one block
    (300, 5, 2),      # multiple blocks + padding remainder
    (50, 8, 3),       # largest SECP-style domain
])
def test_matches_xla_factor_to_var(n_factors, d, seed):
    graph, msgs = _bucket(n_factors, d, seed)
    xla = np.asarray(ops.factor_to_var(graph, (msgs,))[0])
    pallas = np.asarray(binary_factor_update(
        graph.buckets[0].costs, msgs, interpret=True))
    np.testing.assert_allclose(pallas, xla, rtol=1e-6, atol=1e-6)


def test_zero_messages_give_row_minima():
    """With no incoming messages the update is the plain table min —
    an independently checkable closed form."""
    graph, msgs = _bucket(20, 4, 5)
    zeros = np.zeros_like(msgs)
    out = np.asarray(binary_factor_update(
        graph.buckets[0].costs, zeros, interpret=True))
    costs = np.asarray(graph.buckets[0].costs)
    np.testing.assert_allclose(
        out[:, 0, :], costs.min(axis=2), rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 1, :], costs.min(axis=1), rtol=1e-6)


class TestFlagAskedForAndUnavailable:
    """PYDCOP_PALLAS_MAXSUM=1 where the kernel cannot run raises; it
    never gives way silently to the jnp expression."""

    def test_non_tpu_backend_raises(self, monkeypatch):
        monkeypatch.setattr(ops, "_PALLAS_FLAG", True)
        graph, msgs = _bucket(7, 3, 0)
        with pytest.raises(RuntimeError, match="PYDCOP_PALLAS_MAXSUM"):
            ops.factor_to_var(graph, (msgs,))

    @pytest.mark.parametrize("kwargs", [
        {"n_devices": 2}, {"shards": 2}, {"layout": "lane"}])
    def test_engines_that_cannot_feed_it_refuse_the_flag(
            self, monkeypatch, kwargs):
        from pydcop_tpu.engine.runner import (
            MaxSumEngine,
            ShardedMaxSumEngine,
        )

        monkeypatch.setattr(ops, "_PALLAS_FLAG", True)
        graph, _ = _bucket(40, 3, 1)
        meta = None  # refused before the meta is ever read
        with pytest.raises(RuntimeError, match="one device"):
            if "shards" in kwargs:
                ShardedMaxSumEngine(graph, meta, n_shards=2)
            else:
                MaxSumEngine(graph, meta, **kwargs)

    def test_unsharded_engine_keeps_the_flag_on_a_multi_device_host(
            self, monkeypatch):
        """What matters is where the bucket lives, not how many
        devices exist (8 here): an unsharded engine is not refused."""
        import jax

        from pydcop_tpu.engine.runner import MaxSumEngine

        assert jax.device_count() > 1
        monkeypatch.setattr(ops, "_PALLAS_FLAG", True)
        graph, _ = _bucket(40, 3, 1)
        MaxSumEngine(graph, None)
