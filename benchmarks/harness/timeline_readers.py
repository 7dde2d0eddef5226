"""Readers that lay the PROGRAM's own span timeline on the device trace
(PR 38): which phase of the serving step the chip was waiting in, and
how much of a phase is its own Python and how much the spans inside it.

The program keeps a ring of its closed spans
(``flexflow_tpu.obs.annotate.timeline()``: ``(seq, parent_seq, tag,
t0_ns, t1_ns, key)``, stamped with ``time.perf_counter_ns``).  The
profiler's clock runs at a constant offset from that one, and the
harness's own spans fix it: every ``bench.executor_step`` of the traced
tail wraps exactly one ``ex.step()``, i.e. one ``ff.phase/decode_frame``
root of the ring.  So the last n roots of the ring ARE the tail's n
steps; offset = median of (bench start − root start); the readers give
None, and print why, where the two cannot be paired one to one, where
more than one pair in ten lies further than ``PAIR_TOLERANCE_S`` off the
median (roots that are not the tail's steps lie a step's period off, all
of them; a lone pair is the host descheduled between the two stamps, and
the median does not move for it), or where the ring has already dropped
spans of the tail's first step.

As in readers.py each reader takes the run's context and returns one
number, or None where it finds nothing to read: a program without
``annotate.timeline`` (the parent of PR 38), an empty ring, a training
cell (no ``bench.executor_step``).  ``ctx["ring"]``, where a test puts
one, stands for the program's timeline.
"""

from __future__ import annotations

import statistics

from benchmarks.harness.trace_reduce import merged_intervals

BENCH_STEP = "bench.executor_step"
STEP_ROOT = "ff.phase/decode_frame"
OUTSIDE = "(outside)"  # inside no span of the program: between two steps
PAIR_TOLERANCE_S = 100e-6


def _program_timeline():
    try:
        from flexflow_tpu.obs import annotate
    except ImportError:
        return None
    read = getattr(annotate, "timeline", None)
    return read() if read is not None else None


def _pair(trace: dict, ring: list, log):
    """(the tail's roots, what to add to a ring stamp in seconds since
    the first root's start to land on the trace's clock), or None."""
    steps = [s for s in trace["host_spans"] if s[0] == BENCH_STEP]
    roots = [s for s in ring if s[2] == STEP_ROOT and s[1] == 0]
    if not steps or len(roots) < len(steps):
        log(f"[timeline] {len(steps)} {BENCH_STEP} spans in the trace, "
            f"{len(roots)} {STEP_ROOT} roots in the ring: nothing to pair")
        return None
    roots = roots[-len(steps):]
    base = roots[0][3]
    if ring[0][4] > base:
        log("[timeline] the ring's oldest span closed after the tail's "
            "first step opened: the ring no longer holds the tail's head")
        return None
    offsets = [step[1] - (root[3] - base) * 1e-9
               for step, root in zip(steps, roots)]
    offset = statistics.median(offsets)
    off = sorted(abs(o - offset) for o in offsets)
    far = sum(1 for o in off if o > PAIR_TOLERANCE_S)
    log(f"[timeline] {len(steps)} steps paired; the profiler's clock is the "
        f"ring's + {offset:.9f} s (from the first step's start), pairs within "
        f"{off[-1] * 1e6:.3f} us of that (p50 {off[len(off) // 2] * 1e6:.3f}, "
        f"{far} beyond {PAIR_TOLERANCE_S * 1e6:.0f})")
    if far * 10 > len(off):
        log(f"[timeline] more than one pair in ten lies over "
            f"{PAIR_TOLERANCE_S * 1e6:.0f} us off the median: these are not "
            f"the tail's steps")
        return None
    return roots, offset


def _tail_spans(ring: list, roots: list, offset: float) -> list:
    """The paired roots and every span under them, as ``(seq, parent,
    tag, start_s, end_s, top)`` on the trace's clock; ``top`` is the tag
    of the root's child a span sits under (its own, for such a child;
    the root's for the root).  Spans of other threads have other roots
    and are left out."""
    base = roots[0][3]
    top_of = {root[0]: root[2] for root in roots}
    root_seqs = set(top_of)
    out = []
    # a span closes before the span around it: newest first, a parent
    # is met before its children
    for seq, parent, tag, t0, t1, _ in reversed(ring):
        if t1 < base:
            break  # closed before the tail opened, as all before it did
        if seq not in root_seqs:
            if parent not in top_of:
                continue
            top_of[seq] = tag if parent in root_seqs else top_of[parent]
        out.append((seq, parent, tag, (t0 - base) * 1e-9 + offset,
                    (t1 - base) * 1e-9 + offset, top_of[seq]))
    return out


def innermost_intervals(spans: list) -> list:
    """``(start_s, end_s, tag, top)``, sorted and disjoint: the time in
    which each of the properly nested ``spans`` is the deepest one open.
    Time inside none of them is in no interval."""
    out, stack, cursor = [], [], 0.0

    def emit(until):
        if until > cursor:
            _, _, tag, _, _, top = stack[-1]
            out.append((cursor, until, tag, top))

    for span in sorted(spans, key=lambda s: (s[3], -s[4], s[0])):
        while stack and stack[-1][4] <= span[3]:
            emit(stack[-1][4])
            cursor = max(cursor, stack.pop()[4])
        if stack:
            emit(span[3])
        stack.append(span)
        cursor = span[3]
    while stack:
        emit(stack[-1][4])
        cursor = max(cursor, stack.pop()[4])
    return out


def device_idle_intervals(trace: dict) -> list:
    """The idle intervals of the first device inside its traced span:
    the complement of the union of its ops (what
    ``trace_reduce.device_busy`` counts as idle, and
    ``trace_reduce.idle_gaps`` gives whole to one ``bench.*`` span)."""
    dev = next(iter(trace["devices"].values()))
    busy = merged_intervals(dev["ops"])
    return [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])]


def charge(gaps: list, intervals: list) -> dict:
    """{(tag, top): idle seconds}: every gap split ACROSS the innermost
    intervals it overlaps, second for second; what no interval covers
    goes to ``(OUTSIDE, OUTSIDE)``."""
    charged = {}
    k = 0
    for a, b in gaps:
        left = b - a
        while k < len(intervals) and intervals[k][1] <= a:
            k += 1
        j = k
        while j < len(intervals) and intervals[j][0] < b:
            start, end, tag, top = intervals[j]
            part = min(b, end) - max(a, start)
            charged[tag, top] = charged.get((tag, top), 0.0) + part
            left -= part
            j += 1
        charged[OUTSIDE, OUTSIDE] = charged.get((OUTSIDE, OUTSIDE), 0.0) + left
    return charged


def tail(ctx, log=print):
    """{"steps", "spans", "idle"} of the traced tail, worked out (and
    its lines printed) once a run; None where there is nothing to read."""
    if "timeline" not in ctx:
        ctx["timeline"] = _tail(ctx, log)
    return ctx["timeline"]


def _tail(ctx, log):
    if "ring" not in ctx:
        ctx["ring"] = _program_timeline()
    ring, trace = ctx["ring"], ctx.get("trace")
    if not ring or not trace or not trace.get("devices"):
        return None
    paired = _pair(trace, ring, log)
    if paired is None:
        return None
    roots, offset = paired
    spans = _tail_spans(ring, roots, offset)
    idle = charge(device_idle_intervals(trace), innermost_intervals(spans))
    for (tag, top), seconds in sorted(idle.items(), key=lambda kv: -kv[1]):
        log(f"[timeline] device idle while the innermost span was {tag} "
            f"(under {top}): {seconds:.6f} s")
    return {"steps": len(roots), "spans": spans, "idle": idle}


def starved_ms_per_step(ctx, under=None, outside_of=None):
    """Idle milliseconds of the first device a step of the tail, charged
    to spans whose top-level phase — the child of ``decode_frame`` they
    sit under, ``decode_frame`` itself, or ``(outside)`` — is one of
    ``under``, or is none of ``outside_of``."""
    found = tail(ctx)
    if found is None:
        return None
    def wanted(top):
        return top in under if under is not None else top not in outside_of

    seconds = sum(s for (_, top), s in found["idle"].items() if wanted(top))
    return seconds / found["steps"] * 1e3


def self_ms(ctx, tag, per):
    """Milliseconds the tail's spans of ``tag`` spent in no child span
    (duration − the union of the children inside it), summed, ÷ the
    tail's count of spans ``per``.  None where the tail holds none."""
    found = tail(ctx)
    if found is None:
        return None
    spans = found["spans"]
    count = sum(1 for s in spans if s[2] == per)
    if not count:
        return None
    own = {s[0]: s for s in spans if s[2] == tag}
    children = {}
    for seq, parent, _, start, end, _ in spans:
        if parent in own:
            children.setdefault(parent, []).append((seq, start, end - start))
    total = 0.0
    for seq, (_, _, _, start, end, _) in own.items():
        covered = sum(min(b, end) - max(a, start)
                      for a, b in merged_intervals(children.get(seq, ()))
                      if b > start and a < end)
        total += (end - start) - covered
    return total / count * 1e3
