"""The readers that lay the program's span timeline on the device trace
(benchmarks/harness/timeline_readers.py), on a hand-built trace and a
hand-built ring: no chip, no clock.  Times below are milliseconds after
the tail's first step opens; the ring's stamps are nanoseconds on a
clock of its own, the trace's seconds on the profiler's.
"""

import json
import os

import pytest

from benchmarks.harness import spec, trace_reduce
from benchmarks.harness import timeline_readers as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
P = "ff.phase/"
RING_T0_NS = 5_000_000_000_123  # the ring's stamp of the tail's first step
TRACE_T0_S = 3.25               # the same instant on the profiler's clock
STEP_MS, PERIOD_MS = 10.0, 12.0
# one step, as (tag, start, end, children): what ContinuousBatchingExecutor
# .step leaves — the root's own lines lie between its children
STEP = ("decode_frame", 0.0, STEP_MS, [
    ("serve.admit", 0.5, 3.0, [
        ("prefill_chunk", 1.0, 2.5, [("call.prefill_chunk", 1.2, 2.2, [])]),
    ]),
    ("serve.compose", 3.1, 3.2, []),
    ("serve.dispatch", 3.5, 5.5, [("call.decode_frame", 3.8, 5.0, [])]),
    ("serve.wait", 5.6, 8.0, []),
    ("serve.harvest", 8.1, 8.3, []),
    ("serve.evict", 8.4, 8.5, []),
])
METRIC_FILES = {
    "serve.starved_ms_per_step.admit": 1.0 / 3,
    "serve.starved_ms_per_step.dispatch": 0.5 / 3,
    "serve.starved_ms_per_step.rest": (0.5 + 3.5) / 3,
    "serve.self_ms_per_step.admit": 2.5 - 1.5,
    "serve.self_ms_per_step.dispatch": 2.0 - 1.2,
    "serve.self_ms_per_chunk.prefill": 1.5 - 1.0,
}


def ns(ms):
    return RING_T0_NS + round(ms * 1e6)


class Ring:
    """Spans in the order a program closes them: a child before the span
    around it."""

    def __init__(self):
        self.spans, self.seq = [], 0

    def add(self, tree, at_ms, key=None, parent=0):
        tag, start, end, children = tree
        self.seq += 1
        seq = self.seq
        for child in children:
            self.add(child, at_ms, key, seq)
        self.spans.append((seq, parent, P + tag, ns(at_ms + start),
                           ns(at_ms + end), key))


def tail_ring(steps=3, before=2):
    """``before`` older steps (the window's), a span of another thread
    that overlaps the tail, then the tail's ``steps``."""
    ring = Ring()
    for k in range(before):
        ring.add(STEP, (k - before) * 50.0, key=k)
    other = ("fit.data", 1.0, 30.0, [])
    for k in range(steps):
        ring.add(STEP, k * PERIOD_MS, key=before + k)
        if k == 0:
            ring.add(other, 0.0)
    return ring.spans


def trace_of(steps=3, ops=((-1.0, 2.0), (4.0, 9.0), (12.5, 36.0)),
             late_us=(1.0, 3.0, 2.0)):
    """The tail as the profiler saw it: one ``bench.executor_step`` a
    step, opened ``late_us`` BEFORE the program's root did, and one
    device whose ops leave two gaps: 2.0-4.0 (it straddles admit ->
    dispatch of step 0) and 9.0-12.5 (step 0's end, the time between
    the steps, step 1's first lines)."""
    def s(ms):
        return TRACE_T0_S + ms * 1e-3

    host = [("bench.executor_step",
             s(k * PERIOD_MS) - late_us[k % len(late_us)] * 1e-6,
             (STEP_MS + 0.1) * 1e-3) for k in range(steps)]
    host.append(("bench.submit", s(10.5), 1e-4))
    device = {"ops": [(f"fusion.{i}", s(a), (b - a) * 1e-3)
                      for i, (a, b) in enumerate(ops)], "modules": []}
    return {"devices": {"/device:TPU:0": device},
            "host_spans": sorted(host, key=lambda e: e[1])}


def ctx_of(ring=None, trace=None):
    return {"ring": tail_ring() if ring is None else ring,
            "trace": trace_of() if trace is None else trace}


def quiet(*_):
    pass


def test_the_offset_is_recovered_and_the_tail_is_the_paired_steps(capsys):
    ctx = ctx_of()
    found = tr.tail(ctx)
    assert found["steps"] == 3
    roots = sorted(s for s in found["spans"] if s[2] == P + "decode_frame")
    # the median pair opened 2 us late: the ring lands on the trace's clock
    for k, root in enumerate(roots):
        assert root[3] == pytest.approx(
            TRACE_T0_S + k * PERIOD_MS * 1e-3 - 2e-6, abs=1e-9)
    # the tail's three trees and nothing else: not the window's steps,
    # not the other thread's span
    assert len(found["spans"]) == 3 * 10
    assert {s[2] for s in found["spans"]} == {
        P + t for t in ("decode_frame", "serve.admit", "prefill_chunk",
                        "call.prefill_chunk", "serve.compose",
                        "serve.dispatch", "call.decode_frame", "serve.wait",
                        "serve.harvest", "serve.evict")}
    out = capsys.readouterr().out
    assert "3 steps paired" in out and "pairs within 1.000 us" in out
    assert "0 beyond 100" in out
    assert out.count("[timeline] device idle while") == len(found["idle"])
    tr.tail(ctx)
    assert capsys.readouterr().out == ""  # worked out, and said, once a run


def test_top_is_the_child_of_the_root_a_span_sits_under():
    tops = {(s[2], s[5]) for s in tr.tail(ctx_of(), quiet)["spans"]}
    assert (P + "call.prefill_chunk", P + "serve.admit") in tops
    assert (P + "prefill_chunk", P + "serve.admit") in tops
    assert (P + "call.decode_frame", P + "serve.dispatch") in tops
    assert (P + "serve.wait", P + "serve.wait") in tops
    assert (P + "decode_frame", P + "decode_frame") in tops
    assert len(tops) == 10


@pytest.mark.parametrize("trace,ring,why", [
    (trace_of(late_us=(2.0, 1002.0, 2.0)), None, "off the median"),
    # roots that are not the tail's steps: the window's last two and the
    # tail's first, each a step's period off its bench span
    (trace_of(), tail_ring()[:-10], "off the median"),
    (trace_of(steps=6), None, "nothing to pair"),
    # the ring's oldest span is a child of the tail's first step
    (None, tail_ring(before=0)[1:], "no longer holds the tail's head"),
    (None, [], None),
    ({"devices": trace_of()["devices"], "host_spans": []}, None,
     "nothing to pair"),  # a training cell: no bench.executor_step
], ids=["a_pair_1ms_off", "roots_of_other_steps", "more_steps_than_roots",
        "ring_lost_the_head",
        "empty_ring", "no_bench_step"])
def test_what_cannot_be_paired_gives_none_and_says_why(trace, ring, why,
                                                       capsys):
    ctx = ctx_of(ring, trace)
    assert tr.tail(ctx) is None
    assert tr.starved_ms_per_step(ctx, under=[P + "serve.admit"]) is None
    assert tr.self_ms(ctx, P + "serve.admit", P + "decode_frame") is None
    out = capsys.readouterr().out
    assert (why in out) if why else (out == "")
    assert out.count("[timeline]") <= 2  # said once, not once a reader


def test_a_lone_pair_far_off_does_not_void_the_run(capsys):
    """The host descheduled between the harness's stamp and the
    program's, once in twelve steps: the median offset stands."""
    late = [2.0] * 12
    late[7] = 1002.0
    ring = Ring()
    ring.add(STEP, -50.0)
    for k in range(12):
        ring.add(STEP, k * PERIOD_MS, key=k)
    found = tr.tail(ctx_of(ring.spans, trace_of(steps=12, late_us=late)))
    assert found["steps"] == 12
    assert "1 beyond 100" in capsys.readouterr().out


def test_a_gap_that_straddles_two_phases_is_split_second_for_second():
    idle = tr.tail(ctx_of(trace=trace_of(ops=((-1.0, 2.0), (4.0, 40.0)))),
                   quiet)["idle"]
    want = {  # the gap 2.0-4.0 of step 0, by the innermost span open
        (P + "call.prefill_chunk", P + "serve.admit"): 0.2,
        (P + "prefill_chunk", P + "serve.admit"): 0.3,
        (P + "serve.admit", P + "serve.admit"): 0.5,
        (P + "decode_frame", P + "decode_frame"): 0.1 + 0.3,
        (P + "serve.compose", P + "serve.compose"): 0.1,
        (P + "serve.dispatch", P + "serve.dispatch"): 0.3,
        (P + "call.decode_frame", P + "serve.dispatch"): 0.2,
        (tr.OUTSIDE, tr.OUTSIDE): 0.0,
    }
    assert set(idle) == set(want)
    for key, ms in want.items():
        # the pairs' median is 2 us late, so every edge sits 2 us early
        assert idle[key] == pytest.approx(ms * 1e-3, abs=5e-6), key
    assert sum(idle.values()) == pytest.approx(2.0e-3, abs=1e-12)


def test_a_gap_between_two_steps_goes_outside_and_so_to_rest():
    ctx = ctx_of(trace=trace_of(ops=((-1.0, 9.0), (12.5, 40.0)),
                                late_us=(0.0,)))
    idle = tr.tail(ctx, quiet)["idle"]
    assert idle[tr.OUTSIDE, tr.OUTSIDE] == pytest.approx(2.0e-3, abs=1e-9)
    assert idle[P + "decode_frame", P + "decode_frame"] == pytest.approx(
        1.5e-3, abs=1e-9)  # step 0's last lines, step 1's first
    assert len(idle) == 2
    assert tr.starved_ms_per_step(ctx, under=[P + "serve.admit"]) == 0.0
    assert tr.starved_ms_per_step(ctx, under=[P + "serve.dispatch"]) == 0.0
    assert tr.starved_ms_per_step(
        ctx, outside_of=[P + "serve.admit", P + "serve.dispatch"]
    ) == pytest.approx(3.5 / 3, abs=1e-9)


def test_the_three_starved_values_are_the_traces_idle_seconds():
    ctx = ctx_of()
    both = [P + "serve.admit", P + "serve.dispatch"]
    parts = [tr.starved_ms_per_step(ctx, under=both[:1]),
             tr.starved_ms_per_step(ctx, under=both[1:]),
             tr.starved_ms_per_step(ctx, outside_of=both)]
    assert all(p > 0 for p in parts)
    busy = trace_reduce.device_busy(ctx["trace"])
    idle_s = busy["idle_share"] * busy["window_s"]
    assert idle_s == pytest.approx(5.5e-3, abs=1e-12)
    assert sum(parts) * 3 / 1e3 == pytest.approx(idle_s, abs=1e-9)


def test_self_time_subtracts_overlapping_children_once():
    ring = Ring()
    ring.add(STEP, -50.0)
    ring.add(("decode_frame", 0.0, STEP_MS, [
        ("serve.dispatch", 3.5, 5.5, [("call.decode_frame", 3.8, 5.0, []),
                                      ("call.decode_frame", 4.5, 5.2, []),
                                      ("call.decode_frame", 5.3, 5.4, [])]),
    ]), 0.0)
    ctx = ctx_of(ring.spans, trace_of(steps=1))
    # 2.0 - the union (3.8-5.2, 5.3-5.4) = 0.5, not 2.0 - 2.0
    assert tr.self_ms(ctx, P + "serve.dispatch",
                      P + "decode_frame") == pytest.approx(0.5, abs=1e-9)
    assert tr.self_ms(ctx, P + "serve.dispatch",
                      P + "call.decode_frame") == pytest.approx(0.5 / 3,
                                                                abs=1e-9)
    # a leaf's self time is its duration; a tag the tail lacks is None
    assert tr.self_ms(ctx, P + "call.decode_frame",
                      P + "decode_frame") == pytest.approx(2.0, abs=1e-9)
    assert tr.self_ms(ctx, P + "serve.admit", P + "prefill_chunk") is None


def test_innermost_intervals_partition_the_spans_they_are_given():
    spans = tr.tail(ctx_of(), quiet)["spans"]
    cut = tr.innermost_intervals(spans)
    assert all(a < b for a, b, _, _ in cut)
    assert all(x[1] <= y[0] for x, y in zip(cut, cut[1:]))
    assert sum(b - a for a, b, _, _ in cut) == pytest.approx(
        3 * STEP_MS * 1e-3, abs=1e-9)
    by_tag = {}
    for a, b, tag, _ in cut:
        by_tag[tag] = by_tag.get(tag, 0.0) + (b - a)
    assert by_tag[P + "serve.dispatch"] == pytest.approx(3 * 0.8e-3, abs=1e-9)
    assert by_tag[P + "call.decode_frame"] == pytest.approx(3 * 1.2e-3,
                                                            abs=1e-9)


def test_a_program_without_a_timeline_gives_none_from_every_reader(
        monkeypatch, capsys):
    """The parent of the PR that added the ring runs under these files."""
    from flexflow_tpu.obs import annotate

    monkeypatch.delattr(annotate, "timeline")
    for name in METRIC_FILES:
        metric = spec.load_json(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".json"))
        ctx = {"trace": trace_of()}
        assert spec.resolve_dotted(metric["reader"])(
            ctx, **metric["args"]) is None, name
    assert capsys.readouterr().out == ""


def test_the_programs_own_ring_is_what_the_readers_read():
    """No hand-built ring: spans the program itself closed, a trace made
    from their stamps — the device idle between two ops, the profiler's
    clock 7 s behind the ring's."""
    import time

    from flexflow_tpu.obs.annotate import phase_span, timeline

    with phase_span(P + "t.before_the_tail"):
        pass
    since = time.perf_counter_ns()
    for k in range(4):
        with phase_span(P + "decode_frame", key=k):
            with phase_span(P + "serve.admit"):
                pass
            with phase_span(P + "serve.dispatch"):
                with phase_span(P + "call.decode_frame"):
                    pass
    roots = [s for s in timeline(since) if s[2] == P + "decode_frame"]

    def s(stamp_ns):
        return stamp_ns * 1e-9 - 7.0

    first, last = roots[0], roots[-1]
    ops = [("fusion.1", s(first[3]) - 2e-3, 1e-3),
           ("fusion.2", s(last[4]) + 1e-3, 1e-3)]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
             "host_spans": [("bench.executor_step", s(r[3]),
                             (r[4] - r[3]) * 1e-9) for r in roots]}
    ctx = {"trace": trace}
    both = [P + "serve.admit", P + "serve.dispatch"]
    parts = [tr.starved_ms_per_step(ctx, under=both[:1]),
             tr.starved_ms_per_step(ctx, under=both[1:]),
             tr.starved_ms_per_step(ctx, outside_of=both)]
    assert ctx["timeline"]["steps"] == 4
    idle_s = (s(last[4]) + 1e-3) - (s(first[3]) - 1e-3)
    assert sum(parts) * 4 / 1e3 == pytest.approx(idle_s, abs=1e-9)
    assert parts[2] > 2e-3 * 1e3 / 4  # the 2 ms before and after the steps
    own = tr.self_ms(ctx, P + "serve.dispatch", P + "decode_frame")
    inside = tr.self_ms(ctx, P + "call.decode_frame", P + "decode_frame")
    whole = sum(s[4] - s[3] for s in timeline(since)
                if s[2] == P + "serve.dispatch") * 1e-6 / 4
    assert own > 0 and own + inside == pytest.approx(whole, abs=1e-6)


@pytest.mark.parametrize("name", sorted(METRIC_FILES))
def test_a_timeline_metric_resolves_for_the_serving_cells_and_reads(name):
    bench = spec.load_benchmark(ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"].startswith("benchmarks.harness.timeline_readers:")
    assert entry["source"] == metric["source"]
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "ms", "lower", "serve_tokens_per_s")
    assert "traced tail alone" in metric["reads"]
    serving = [w["name"] for w in bench["workloads"]
               if w["name"] in next(m for m in bench["end_to_end"]
                                    if m["name"] == "serve_tokens_per_s")
               ["workloads"]]
    assert sorted(entry["workloads"]) == sorted(serving)
    for workload in entry["workloads"]:
        cell = spec.resolve_cell(ROOT, workload)
        assert name in [m["name"] for m in cell.per_layer]
    value = spec.resolve_dotted(metric["reader"])(ctx_of(), **metric["args"])
    # ms; the pairs' median opens 2 us late, which moves a gap's edges
    assert value == pytest.approx(METRIC_FILES[name], abs=2.5e-3)
