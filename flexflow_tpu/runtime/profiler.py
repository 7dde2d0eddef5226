"""Profiling & tracing.

Reference parity (SURVEY.md §5 tracing/profiling):
* Legion iteration tracing → here the train step is already ONE compiled
  XLA program (jit), so "tracing" is structural; what remains is
  observability:
* per-op ``profiling`` flag gating kernel timing printfs (config.h:125)
  → ``StepProfiler`` wall-clock step timing + summary, and
  ``device_trace`` — a context manager around jax.profiler for a real
  XLA/TPU timeline (viewable in TensorBoard/Perfetto);
* on-device op cost measurement (model.cu:38-74 warmup+repeat cuda
  events) → ``measure_operator_cost``: jit the op's forward alone and
  time it on the real chip — used to calibrate the analytic cost model.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import numpy as np


class StepProfiler:
    """Wall-clock per-step timing with compile-step exclusion, plus
    named host-side phases within a step (``dispatch``/``wait`` in
    model.fit) — the measured side of the obs DriftReport."""

    def __init__(self):
        self.step_times: List[float] = []
        self.phase_times: Dict[str, List[float]] = {}
        self._t_last: Optional[float] = None
        self._phase_t0: Dict[str, float] = {}

    def start_step(self) -> None:
        self._t_last = time.perf_counter()

    def end_step(self) -> None:
        if self._t_last is not None:
            self.step_times.append(time.perf_counter() - self._t_last)
            self._t_last = None

    def start_phase(self, name: str) -> None:
        self._phase_t0[name] = time.perf_counter()

    def end_phase(self, name: str) -> None:
        t0 = self._phase_t0.pop(name, None)
        if t0 is not None:
            self.phase_times.setdefault(name, []).append(
                time.perf_counter() - t0)

    def summary(self, skip_first: int = 1) -> Dict[str, float]:
        """Stats excluding the first (compile) steps.  When every
        recorded step WOULD be skipped the stats still cover all steps
        but say so via ``includes_compile`` — silently folding the
        compile step back in used to misreport single-step runs as
        steady-state."""
        kept = self.step_times[skip_first:]
        includes_compile = (
            not kept and bool(self.step_times) and skip_first > 0
        )
        ts = np.asarray(kept or self.step_times)
        if len(ts) == 0:
            return {"steps": 0}
        return {
            "steps": len(ts),
            "mean_s": float(ts.mean()),
            "p50_s": float(np.percentile(ts, 50)),
            "p95_s": float(np.percentile(ts, 95)),
            "max_s": float(ts.max()),
            "includes_compile": includes_compile,
        }

    def phase_summary(self, skip_first: int = 1) -> Dict[str, Dict[str, float]]:
        """Per-phase stats with the same compile-step exclusion (and
        the same ``includes_compile`` honesty flag) as ``summary``."""
        out: Dict[str, Dict[str, float]] = {}
        for name, times in self.phase_times.items():
            kept = times[skip_first:]
            includes_compile = not kept and bool(times) and skip_first > 0
            ts = np.asarray(kept or times)
            if len(ts) == 0:
                continue
            out[name] = {
                "count": len(ts),
                "mean_s": float(ts.mean()),
                "total_s": float(ts.sum()),
                "includes_compile": includes_compile,
            }
        return out

    def __str__(self) -> str:
        s = self.summary()
        if not s.get("steps"):
            return "StepProfiler(no steps)"
        return (f"steps={s['steps']} mean={s['mean_s']*1e3:.2f}ms "
                f"p50={s['p50_s']*1e3:.2f}ms p95={s['p95_s']*1e3:.2f}ms")


@contextlib.contextmanager
def device_trace(logdir: str):
    """XLA device timeline trace (TensorBoard `Profile` tab / Perfetto).
    The TPU analog of the reference's `-lg:prof` external tooling.
    The program's ``ff.phase/*`` spans and ``ff.lane/*`` stamps
    (obs/annotate.py) are TraceAnnotations, always on, so the capture
    carries the tags ``obs/trace_ingest.py`` matches back to the
    simulator's predicted lanes."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def measure_operator_cost(op, batch_inputs=None,
                          warmup: int = 2, repeats: int = 5,
                          weight_shapes=None):
    """Median wall seconds of one jitted forward of ``op`` on the real
    device, or None when the op cannot be measured meaningfully: no
    floating input/weight to thread a timing dependence through, or the
    op is cheaper than timer noise (a clamped floor would mark it free
    in the calibration table).  Reference: Op::measure_operator_cost +
    model.cu:38-74.

    Builds zero inputs from the op's input shapes unless given; weights
    are initialized via the op's specs (``weight_shapes`` overrides
    per-weight shapes — calibration probes ops at their per-SHARD
    shapes, see search/calibration.py). Results feed the CalibrationTable
    consulted by CostModel.op_cost before its roofline fallback.
    """
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import LoweringContext

    if batch_inputs is None:
        batch_inputs = [
            jnp.zeros(s.sizes, s.dtype.to_numpy()) for s in op.input_shapes
        ]
    key = jax.random.key(0)
    weights = {}
    for i, ws in enumerate(getattr(op, "_weight_specs", ())):
        shape = (weight_shapes or {}).get(ws.name, ws.shape)
        weights[ws.name] = ws.initializer.init(
            jax.random.fold_in(key, i), shape, ws.dtype.to_numpy()
        )
    state_in = {}
    for spec in (op.state_specs() if getattr(op, "state_specs", None) else ()):
        name, shape, dtype, fill = spec
        state_in[f"{op.name}/{name}"] = jnp.full(shape, fill, dtype)

    # A single dispatch costs far more than most ops run, so per-op
    # timing must (a) fence with a host scalar readback and (b)
    # amortize: run the op N times inside ONE jitted lax.scan with a
    # serial data dependence through the carry, then difference two
    # scan lengths — the dispatch cost cancels.
    # Serial dependence: perturb the first floating input (or weight)
    # by a scalar derived from the previous iteration's outputs.
    tgt_kind, tgt_key = None, None
    for i, x in enumerate(batch_inputs):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            tgt_kind, tgt_key = "input", i
            break
    if tgt_kind is None:
        for name, w in weights.items():
            if jnp.issubdtype(jnp.asarray(w).dtype, jnp.floating):
                tgt_kind, tgt_key = "weight", name
                break
    if tgt_kind is None:
        # no floating leaf to thread the carry through: the scan body
        # would be loop-invariant, XLA would hoist the op out, and the
        # "measurement" would be the 1e-9 floor — poisoning the
        # calibration table with a free op.  Decline instead; callers
        # keep the analytic roofline for such (integer-only) ops.
        return None

    def make(n):
        def fn(inputs, weights):
            def body(c, _):
                ins = list(inputs)
                ws = dict(weights)
                if tgt_kind == "input":
                    ins[tgt_key] = ins[tgt_key] + c.astype(ins[tgt_key].dtype)
                elif tgt_kind == "weight":
                    ws[tgt_key] = ws[tgt_key] + c.astype(ws[tgt_key].dtype)
                ctx = LoweringContext(
                    compute_dtype=jnp.float32, train=False,
                    rng=jax.random.key(1), seq_length=-1,
                    state_in=dict(state_in), mesh=None,
                )
                outs = op.forward(ctx, ins, ws)
                s = sum(jnp.sum(o).astype(jnp.float32) for o in outs)
                # tiny magnitude keeps the perturbation from changing
                # the op's numeric regime while preserving dependence
                return s * jnp.float32(1e-30), None

            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c

        return jax.jit(fn)

    def run_pair(n1, n2):
        j1, j2 = make(n1), make(n2)
        for _ in range(max(1, warmup)):
            float(j1(batch_inputs, weights))
            float(j2(batch_inputs, weights))
        diffs = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            float(j1(batch_inputs, weights))
            t1 = time.perf_counter()
            float(j2(batch_inputs, weights))
            diffs.append((time.perf_counter() - t1) - (t1 - t0))
        return float(np.median(diffs)), n2 - n1

    # Adaptive scan length: cheap ops (softmax, layernorm, pool, topk)
    # run below timer noise at the base length, which used to leave
    # them UNMEASURED (the round-3 calibration table had no record for
    # any of them).  Scale the iteration-count difference until the
    # measured delta is resolvable, then trust the per-iteration time.
    span = 5 * max(1, repeats)
    per_iter = None
    for scale in (1, 16, 256):
        delta, iters = run_pair(2, 2 + span * scale)
        if delta > 2e-5:  # well above perf_counter noise
            return delta / iters
        if delta > 0:
            per_iter = delta / iters
    # never resolvable above noise: keep the best positive estimate, or
    # decline (a clamped floor would mark the op free and the search
    # would over-place work on it)
    return per_iter
