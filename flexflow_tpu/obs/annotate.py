"""Named trace annotations: the search's lane vocabulary stamped onto
real execution.

The simulator prices weight-gradient sync as LANES — per-bucket
collective records named ``bucket:<name>:sync`` (scheduled) or
``<op>:sync`` (monolithic) in ``Simulator.simulate``'s
``comm_schedule``/``sync_buckets`` output.  This module stamps the same
identifiers onto the EXECUTED program so a real
``runtime.profiler.device_trace`` capture carries them and
``obs/trace_ingest.py`` can match measured events to predicted lanes
by TAG EQUALITY — never by fuzzy kernel names:

* ``phase_span(tag)`` — a host-side ``jax.profiler.TraceAnnotation``
  around dispatch-level phases (``ff.phase/step``,
  ``ff.phase/decode_frame``); armed only while a capture is active
  (``arm()``/``disarm()``, driven by ``runtime.profiler.device_trace``
  and ``model.fit``'s capture window), one boolean check otherwise.
* ``lane_stamp(tag, dep)`` — an ordered ``io_callback`` INSIDE the
  jitted step that (a) emits a zero-length ``TraceAnnotation`` marker
  into the live trace at the moment the runtime reaches that point of
  the dataflow and (b) records the host timestamp in ``LANES``.  A
  bucket's collective is bracketed by ``<tag>#issue``/``<tag>#done``
  markers whose data dependences (payload → issue → collective →
  done) pin them to the lane's real execution window.  Stamps are
  lowered only when ``FFConfig.device_trace_dir`` is set — the default
  program is byte-identical to history (zero cost when the bus/trace
  is off).

CPU-mesh caveat (honesty rule): the host trace carries these named
scopes and the markers measure host-observed issue/completion of the
lane's thunks; ICI/DCN wire behavior stays simulated until the same
capture runs on a real TPU, where ``scope()``'s ``jax.named_scope``
additionally prefixes the lane tag onto the lowered HLO (visible in
the xplane device rows).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List

LANE_PREFIX = "ff.lane/"
PHASE_PREFIX = "ff.phase/"
STEP_PHASE = PHASE_PREFIX + "step"
DECODE_PHASE = PHASE_PREFIX + "decode_frame"
PREFILL_PHASE = PHASE_PREFIX + "prefill_chunk"
ISSUE_MARK = "#issue"
DONE_MARK = "#done"

# host-annotation arming: flipped by the device_trace context manager /
# fit's capture window.  The disarmed fast path is one module-global
# load + branch — the same contract as the event bus.
_ARMED = False


def arm() -> None:
    global _ARMED
    _ARMED = True


def disarm() -> None:
    global _ARMED
    _ARMED = False


def armed() -> bool:
    return _ARMED


_NULL = contextlib.nullcontext()


def lane_tag(lane_id: str) -> str:
    """The annotation tag for a simulator lane id (e.g.
    ``bucket:b0:sync`` -> ``ff.lane/bucket:b0:sync``)."""
    return LANE_PREFIX + lane_id


def parse_tag(name: str):
    """``(lane_id, marker)`` for a lane tag (marker ``"issue"``/
    ``"done"``/``None`` for a plain span), or None when ``name`` is not
    a lane tag."""
    if not name.startswith(LANE_PREFIX):
        return None
    body = name[len(LANE_PREFIX):]
    for mark, label in ((ISSUE_MARK, "issue"), (DONE_MARK, "done")):
        if body.endswith(mark):
            return body[: -len(mark)], label
    return body, None


def phase_span(tag: str):
    """Context manager: a host TraceAnnotation when a capture is
    armed, a shared null context otherwise (one boolean on the off
    path)."""
    if not _ARMED:
        return _NULL
    import jax

    return jax.profiler.TraceAnnotation(tag)


def scope(lane_id: str):
    """Tracing-time ``jax.named_scope`` carrying the lane tag — zero
    runtime cost (HLO metadata only); a TPU xplane capture shows the
    lane's ops under this prefix."""
    import jax

    return jax.named_scope(lane_tag(lane_id))


class LaneRecorder:
    """Host-side lane stamp buffer: (tag, perf_counter seconds) rows in
    arrival order, appended by the ``lane_stamp`` callbacks.  The
    trace-file ingest is the primary consumer of lane timings; this
    buffer is the in-process cross-check (and the only measured side
    when no capture is running)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows: List[tuple] = []

    def record(self, tag: str, t: float) -> None:
        with self._lock:
            self.rows.append((tag, t))

    def clear(self) -> None:
        with self._lock:
            self.rows.clear()

    def spans(self) -> Dict[str, List[tuple]]:
        """lane_id -> [(issue_t, done_t), ...] paired in arrival
        order; unpaired stamps are dropped."""
        with self._lock:
            rows = list(self.rows)
        open_t: Dict[str, float] = {}
        out: Dict[str, List[tuple]] = {}
        for tag, t in rows:
            parsed = parse_tag(tag)
            if parsed is None:
                continue
            lane, marker = parsed
            if marker == "issue":
                open_t[lane] = t
            elif marker == "done" and lane in open_t:
                out.setdefault(lane, []).append((open_t.pop(lane), t))
        return out


LANES = LaneRecorder()


def lane_stamp(lane_id: str, marker: str, dep):
    """A host-callback stamp inside a jitted program: returns a
    float32 scalar (always 0.0) that depends on ``dep``; callers MUST
    thread the result into downstream live values — that data
    dependence both pins the stamp's execution point (after ``dep``,
    before its consumers) and keeps it from being dead-code
    eliminated.  At run time the callback records
    ``time.perf_counter`` into ``LANES`` and emits a marker
    ``TraceAnnotation`` so an active ``device_trace`` capture carries
    the tag.  ``pure_callback`` rather than the ordered ``io_callback``
    on purpose: the data dependence on ``dep`` already orders the
    stamp, and an ordered-effect token would add entry parameters to
    the sharded train step.  Call only from lowering code
    that is itself gated (``FFConfig.device_trace_dir``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tag = lane_tag(lane_id) + (ISSUE_MARK if marker == "issue"
                               else DONE_MARK)

    def _cb(_x):
        LANES.record(tag, time.perf_counter())
        with jax.profiler.TraceAnnotation(tag):
            pass
        return np.float32(0.0)

    return jax.pure_callback(_cb, jax.ShapeDtypeStruct((), jnp.float32),
                             dep)


def lane_stamps_armed(config) -> bool:
    """Whether the lowering should thread lane stamps into the step:
    opt-in via ``FFConfig.device_trace_dir`` (the capture consumer) —
    the default program stays byte-identical to history."""
    return bool(getattr(config, "device_trace_dir", None))
