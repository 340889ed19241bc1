"""Engine-level telemetry for the jitted solvers.

The device engine's solve is one XLA program per segment — a host
callback per cycle would serialize the loop through the host and
destroy the very rate being measured.  The probe therefore
piggybacks on ``MaxSumEngine.run_checkpointed``'s existing K-cycle
segmentation: each segment already ends with one ``sync`` (the
forced host fetch in ``timed_jit_call``, engine/timing.py), so the
per-chunk wall time handed to :meth:`EngineProbe.on_segment` covers
the completed segment, and the
probe adds NO host syncs inside the jitted loop — its only extra work
is one tiny jitted cost evaluation per chunk, on the chunk boundary
the engine already pays for.

Per chunk the probe emits: a ``chunk`` trace instant (cycle, cost,
converged, honest seconds), the monotone cycle counter + cost gauge
through a :class:`~pydcop_tpu.observability.metrics.CycleSnapshotter`
(JSONL snapshot per chunk when a metrics path is set), and a point on
the in-memory cost-vs-cycle curve that ``api.solve`` returns in
``metrics['cost_curve']``.  The cost computation mirrors
``run_maxsum_trace``'s exactly (constraint cost + noise-free variable
base costs, mode sign, constant term), so the curve's final point
equals the solver's reported cost — asserted in the battery.

**Convergence health** (the measured foundation the decimation /
message-pruning kernels need to decide *when* to prune, and the
oscillation signal an operator reads off a live solve): per segment
the probe also computes the **message residual** (mean |Δ| of the
f2v messages vs the previous segment's) and the **assignment flip
rate** (fraction of variables whose selected value changed) — both
evaluated ON DEVICE by one jitted comparison whose two scalars ride
the segment boundary's existing host fetch, zero syncs inside the
jitted loop.  They land in the ``pydcop_msg_residual`` /
``pydcop_flip_rate`` gauges, the per-chunk SSE ``/events`` payload
(``residual`` / ``flip_rate`` fields), the ``chunk`` trace instant,
and ``metrics['convergence_curve']`` on the result.
"""

import logging
from typing import Any, List, Optional, Tuple

logger = logging.getLogger("pydcop.observability.engine_probe")


class EngineProbe:
    """Per-chunk cost/convergence/timing recorder for a
    ``MaxSumEngine`` (edge layout; the lane layout's graph has no
    host-side cost tables, so its chunks record timing only)."""

    def __init__(self, engine, metrics_path: Optional[str] = None,
                 metrics_every: int = 1, registry=None):
        from pydcop_tpu.observability.metrics import CycleSnapshotter

        self.engine = engine
        self.snapshotter = CycleSnapshotter(
            metrics_path, every=metrics_every, reg=registry
        )
        reg = self.snapshotter.registry
        self._seg_seconds = reg.histogram(
            "pydcop_engine_segment_seconds",
            "Honest (sync-forced) wall seconds per engine chunk")
        self._compile_seconds = reg.counter(
            "pydcop_engine_compile_seconds_total",
            "Seconds spent jit-compiling engine programs")
        self._residual_g = reg.gauge(
            "pydcop_msg_residual",
            "Mean |delta| of f2v messages vs the previous segment "
            "(convergence health; 0 = message fixpoint)")
        self._flip_g = reg.gauge(
            "pydcop_flip_rate",
            "Fraction of variables whose selected value changed "
            "since the previous segment (oscillation signal)")
        # (cycle, cost, converged, seconds) per chunk.
        self.chunks: List[Tuple[int, Optional[float], bool, float]] = []
        # (cycle, residual, flip_rate) per chunk; None on the first
        # chunk (no previous segment to diff against).
        self.convergence: List[Tuple[int, Optional[float],
                                     Optional[float]]] = []
        self._cost_fn = None
        self._conv_fn = None
        self._prev_msgs = None
        self._prev_values = None

    def _build_cost_fn(self):
        import jax
        import jax.numpy as jnp

        from pydcop_tpu.ops import maxsum as maxsum_ops

        # The engine's own kernel namespace when it has one: the
        # partitioned engine's graph is a ShardedGraph whose cost
        # evaluation needs the halo-value exchange, and its ShardOps
        # exposes the same assignment_constraint_cost surface over a
        # GLOBAL [V] assignment.
        ops = getattr(self.engine, "_ops", maxsum_ops)
        constraint_cost = getattr(
            ops, "assignment_constraint_cost",
            maxsum_ops.assignment_constraint_cost)

        meta = self.engine.meta
        base = meta.var_base_costs
        base_arr = None if base is None else jnp.asarray(base)

        def cost_of(values):
            cost = constraint_cost(self.engine.graph, values)
            if base_arr is not None:
                cost = cost + jnp.sum(jnp.take_along_axis(
                    base_arr, values[:, None], axis=1))
            return cost

        return jax.jit(cost_of)

    def _chunk_cost(self, values) -> Optional[float]:
        if getattr(self.engine, "layout", "edge") != "edge":
            return None
        try:
            if self._cost_fn is None:
                self._cost_fn = self._build_cost_fn()
            raw = float(self._cost_fn(values))
        except Exception:
            logger.exception("Chunk cost evaluation failed")
            return None
        meta = self.engine.meta
        sign = 1.0 if meta.mode == "min" else -1.0
        return sign * raw + meta.constant_cost

    def _build_conv_fn(self):
        import jax
        import jax.numpy as jnp

        def conv(prev_msgs, msgs, prev_values, values):
            num = jnp.asarray(0.0, jnp.float32)
            den = 0
            for a, b in zip(jax.tree_util.tree_leaves(prev_msgs),
                            jax.tree_util.tree_leaves(msgs)):
                num = num + jnp.sum(jnp.abs(
                    b.astype(jnp.float32) - a.astype(jnp.float32)))
                den += a.size
            residual = num / max(den, 1)
            flips = jnp.mean(
                (values != prev_values).astype(jnp.float32))
            return residual, flips

        return jax.jit(conv)

    def _convergence(self, state, values
                     ) -> Tuple[Optional[float], Optional[float]]:
        """Residual/flip-rate vs the previous segment — one jitted
        device comparison, two scalars fetched at the boundary the
        host already pays for.  None/None on the first segment and
        for engines whose state carries no ``f2v`` messages."""
        import jax
        import jax.numpy as jnp

        msgs = getattr(state, "f2v", None)
        if msgs is None or values is None:
            return None, None
        residual = flips = None
        if self._prev_msgs is not None:
            try:
                if self._conv_fn is None:
                    self._conv_fn = self._build_conv_fn()
                r, f = jax.device_get(self._conv_fn(
                    self._prev_msgs, msgs,
                    self._prev_values, values))
                residual, flips = float(r), float(f)
            except Exception:
                logger.exception("convergence probe failed")
                self._prev_msgs = None
                self._prev_values = None
                return None, None
        # Retain copies for the next boundary: with buffer donation
        # the next segment consumes the state's buffers in place
        # (device-side copy, overlaps — no host sync); the values
        # output is not donated, so its reference stays valid.
        self._prev_msgs = jax.tree_util.tree_map(jnp.copy, msgs)
        self._prev_values = values
        return residual, flips

    def on_segment(self, state, values, seconds: float,
                   compile_s: float):
        """Record one completed chunk (called by ``run_checkpointed``
        on the chunk boundary, after its honest sync).

        A first call per program reports its whole elapsed time as
        BOTH compile and run (timed_jit_call's overlapping-fields
        convention — never sum them), so the run-only portion here is
        ``seconds - compile_s``: compile time goes to its own counter,
        not into the segment-seconds series.
        """
        from pydcop_tpu.observability.trace import tracer

        cycle = int(state.cycle)
        converged = bool(state.stable)
        cost = self._chunk_cost(values)
        residual, flips = self._convergence(state, values)
        run_s = max(float(seconds) - float(compile_s), 0.0)
        self.chunks.append((cycle, cost, converged, run_s))
        self.convergence.append((cycle, residual, flips))
        self._seg_seconds.observe(run_s)
        if compile_s:
            self._compile_seconds.inc(float(compile_s))
        if residual is not None:
            self._residual_g.set(residual)
        if flips is not None:
            self._flip_g.set(flips)
        self.snapshotter(cycle, cost, residual=residual,
                         flip_rate=flips)
        if tracer.active:
            tracer.instant(
                "chunk", "engine", cycle=cycle, cost=cost,
                converged=converged, seconds=run_s,
                compile_s=float(compile_s),
                residual=residual, flip_rate=flips,
            )

    def cost_curve(self) -> List[Tuple[int, float]]:
        """(cycle, cost) points for chunks where cost was computable."""
        return [(cycle, cost) for cycle, cost, _, _ in self.chunks
                if cost is not None]

    def convergence_curve(self) -> List[Tuple[int, float, float]]:
        """(cycle, residual, flip_rate) points where both signals
        were computable (segment 2 onward)."""
        return [(cycle, residual, flips)
                for cycle, residual, flips in self.convergence
                if residual is not None and flips is not None]

    def summary(self) -> dict:
        run_s = sum(s for _, _, _, s in self.chunks)
        return {
            "chunks": len(self.chunks),
            "chunk_seconds": run_s,
            "cost_curve": self.cost_curve(),
        }


def attach_result_metrics(result: Any, probe: "EngineProbe"):
    """Fold the probe's curve into a ``DeviceRunResult``/dict metrics
    mapping (shared by api.solve's probed paths)."""
    metrics = (result.metrics if hasattr(result, "metrics")
               else result.setdefault("metrics", {}))
    metrics["cost_curve"] = probe.cost_curve()
    metrics["probe_chunks"] = len(probe.chunks)
    metrics["convergence_curve"] = probe.convergence_curve()
    return result
