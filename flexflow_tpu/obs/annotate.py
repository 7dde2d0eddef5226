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

* ``phase_span(tag)`` — THE span primitive of the program: a host-side
  ``jax.profiler.TraceAnnotation`` around a dispatch-level phase
  (``ff.phase/step``, ``ff.phase/decode_frame``, ``ff.phase/serve.wait``,
  ``ff.phase/setup.search`` ...), one sample of its duration in a
  ``METRICS`` histogram (``hist_name(tag)``: ``serve.wait_s``) AND, when
  it closes, one entry ``(seq, parent_seq, tag, t0_ns, t1_ns, key)`` in
  the program's TIMELINE: a ring of the last ``RING_SPANS`` closed
  spans (``timeline()``), stamped with ``time.perf_counter_ns``, each
  naming the span open around it on its thread.  Always on: a TraceMe
  records only while some profiler session is live, whoever started it
  (``runtime.profiler.device_trace``, ``fit``'s ``device_trace_dir``
  capture, a benchmark's own ``start_trace``, an operator's
  ``jax.profiler.start_server``); the whole span costs about 2 µs with
  none live.  The histogram is what ``obs/exposition.py`` shows an
  operator with no capture at all; the ring is what self times and "what
  was the host doing while the device idled" are computed from
  (``benchmarks/harness/timeline_readers.py``) and what ``FLIGHT.dump``
  writes out after the events.
* ``lane_stamp(tag, dep)`` — a host callback INSIDE the jitted step
  that emits a zero-length ``TraceAnnotation`` marker into the live
  trace at the moment the runtime reaches that point of the dataflow.
  A bucket's collective is bracketed by ``<tag>#issue``/``<tag>#done``
  markers whose data dependences (payload → issue → collective →
  done) pin them to the lane's real execution window.  Stamps are
  lowered only when ``FFConfig.device_trace_dir`` is set — the default
  program is byte-identical to history (zero cost when the bus/trace
  is off).

CPU-mesh caveat (honesty rule): the host trace carries these named
scopes and the markers measure host-observed issue/completion of the
lane's thunks; ICI/DCN wire behavior stays simulated until the same
capture runs on a real TPU.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.obs.metrics import METRICS

LANE_PREFIX = "ff.lane/"
PHASE_PREFIX = "ff.phase/"
STEP_PHASE = PHASE_PREFIX + "step"
DECODE_PHASE = PHASE_PREFIX + "decode_frame"
PREFILL_PHASE = PHASE_PREFIX + "prefill_chunk"
# + program name: the first call of a jitted program (trace + lower +
# compile, or load from the persistent cache)
FIRST_CALL_PHASE = PHASE_PREFIX + "setup.first_call."
# + program name: every LATER call of it — ``jitted(*args)`` and nothing
# else (flattening the arguments, the enqueue, any wait for room in the
# device's queue)
CALL_PHASE = PHASE_PREFIX + "call."
ISSUE_MARK = "#issue"
DONE_MARK = "#done"

# histograms of the three tags that predate the naming rule (a tag
# without its prefix, plus ``_s``) keep the meaning their readers know:
# ``decode.frame_s`` is dispatch + wait only, ``fit.step_s`` a fenced
# step under ``profiling`` — so the spans get names of their own
_HIST_NAMES = {
    STEP_PHASE: "fit.dispatch_s",
    DECODE_PHASE: "serve.step_s",
    PREFILL_PHASE: "serve.prefill_chunk_s",
}


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


def hist_name(tag: str) -> str:
    """The ``METRICS`` histogram a phase tag's durations land in:
    ``ff.phase/serve.admit`` -> ``serve.admit_s``."""
    return _HIST_NAMES.get(tag) or tag[len(PHASE_PREFIX):] + "_s"


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, imported on first use
_HISTS: Dict[str, object] = {}  # tag -> its Histogram, looked up once

# The timeline.  65,536 closed spans: the busiest loop there is, the
# decode-heavy serving step (~1,130 frames of ~10 spans in 2.5 s), fits
# more than five times over; 6 MB at most.
RING_SPANS = 65536
Span = Tuple[int, int, str, int, int, Optional[int]]
_RING: "collections.deque[Span]" = collections.deque(maxlen=RING_SPANS)
_SEQ = itertools.count(1)  # process-wide; ``next`` is atomic under the GIL
_OPEN = threading.local()  # .stack: the spans open on this thread


def timeline(since_ns: int = 0) -> List[Span]:
    """The ring's spans that closed at or after ``since_ns`` (a
    ``time.perf_counter_ns`` stamp), in the order they closed — a child
    before its parent.  Each is ``(seq, parent_seq, tag, t0_ns, t1_ns,
    key)``: ``seq`` a process-wide running number taken when the span
    OPENED, ``parent_seq`` that of the span open around it on the same
    thread (0 for a root), ``key`` what the root of its tree was given
    (a frame number, a global step) or None."""
    spans = list(_RING)
    if since_ns:
        spans = [s for s in spans if s[4] >= since_ns]
    return spans


class phase_span:
    """Context manager: ``tag`` as a host TraceAnnotation on the
    profiler's clock, its ``perf_counter_ns`` duration (seconds) into
    the tag's histogram, and the closed span into the timeline's ring.
    One fresh object per use; spans nest by a stack of open spans a
    thread, so threads share nothing.  ``key`` is for a ROOT span (the
    frame number, the global step); a span inside another takes its
    root's.  Setting ``keep = False`` inside the block leaves the
    histogram without this sample; the ring keeps the span — the host
    did spend that time."""

    __slots__ = ("_ann", "_hist", "_tag", "_t0", "_seq", "_parent", "_key",
                 "_stack", "keep")

    def __init__(self, tag: str, key: Optional[int] = None):
        global _TraceAnnotation
        hist = _HISTS.get(tag)
        if hist is None:
            if _TraceAnnotation is None:
                from jax.profiler import TraceAnnotation

                _TraceAnnotation = TraceAnnotation
            hist = _HISTS[tag] = METRICS.histogram(hist_name(tag))
        self._hist = hist
        self._tag = tag
        self._key = key
        self._ann = _TraceAnnotation(tag)
        self.keep = True

    def __enter__(self):
        try:
            stack = _OPEN.stack
        except AttributeError:
            stack = _OPEN.stack = []
        if stack:
            around = stack[-1]
            self._parent = around._seq
            self._key = around._key
        else:
            self._parent = 0
        self._seq = next(_SEQ)
        stack.append(self)
        self._stack = stack
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        self._stack.pop()
        _RING.append((self._seq, self._parent, self._tag, self._t0, t1,
                      self._key))
        if self.keep:
            self._hist.observe((t1 - self._t0) * 1e-9)
        return False


def spanned(tag: str, iterable):
    """Yield ``iterable``'s items with every FETCH under
    ``phase_span(tag)`` — the wait for a loader's next batch.  The fetch
    that finds the iterator exhausted is no sample of the histogram; the
    timeline's ring keeps it as a span like any other."""
    it = iter(iterable)
    while True:
        with phase_span(tag) as span:
            try:
                item = next(it)
            except StopIteration:
                span.keep = False
                return
        yield item


def lane_stamp(lane_id: str, marker: str, dep):
    """A host-callback stamp inside a jitted program: returns a
    float32 scalar (always 0.0) that depends on ``dep``; callers MUST
    thread the result into downstream live values — that data
    dependence both pins the stamp's execution point (after ``dep``,
    before its consumers) and keeps it from being dead-code
    eliminated.  At run time the callback emits a marker
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
