"""Request-scoped tracing, the always-on flight recorder, and SLO
burn-rate signals (ISSUE 17 — the serving fleet's observability spine).

Contract highlights:

* every request served by a traced executor/fleet yields a WELL-FORMED
  span tree: one root, a ``route`` decision stamp, ``queue``/
  ``prefill``/``decode`` phase children that nest inside the root and
  sum to the measured e2e within tolerance; preemption re-opens the
  queue span so the tree narrates the re-queue;
* the tracer is off by default and one-boolean cheap on the decode hot
  path (the ``BUS.enabled`` read-count contract in test_obs.py already
  pins the bus side; here the tracer side must add NO bus reads);
* ``export_chrome_trace`` writes the ``ph:"X"``/``ph:"M"`` µs shape
  Perfetto loads — one thread row per trace, slices carrying
  span/parent ids;
* the flight recorder rides EVERY emit (armed bus or not) into a
  bounded ring; fault injections dump the ring plus the in-flight
  requests' open spans as a post-mortem JSONL;
* the multi-window burn-rate computer fires on persistent moderate SLO
  violations BEFORE (or while never) the raw p99-drift trigger, and a
  lone spike under a loose error budget stays quiet;
* ``TrainingController.observe_burn_rate`` arms a ``burn_rate``
  re-search trigger from a fleet's finished-request records.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from flexflow_tpu.obs.events import BUS
from flexflow_tpu.obs.flight import FLIGHT, FlightRecorder
from flexflow_tpu.obs.slo import burn_rates, first_fire_indices
from flexflow_tpu.obs.tracing import (
    REQUEST_PHASES,
    TRACER,
    Tracer,
    forest_stats,
    span_forest,
)
from flexflow_tpu.runtime.decode import (
    ContinuousBatchingExecutor,
    DecodeRequest,
    SLOClass,
)
from flexflow_tpu.runtime.fleet import FleetExecutor

SLO_TABLE = (
    SLOClass("interactive", priority=2, deadline_frames=0),
    SLOClass("standard", priority=1, deadline_frames=0),
    SLOClass("batch", priority=0, deadline_frames=0, quantile=0.9),
)


@pytest.fixture(autouse=True)
def _obs_teardown():
    yield
    BUS.close()
    TRACER.reset()
    TRACER.enabled = False
    FLIGHT.reset()
    FLIGHT.dump_dir = None
    FLIGHT.enabled = True


def _synthetic_step(vocab=97):
    def step(ids, table, lens):
        ids = np.asarray(ids)
        lens = np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


def _mk_executor(**kw):
    args = dict(max_seqs=4, page_size=4, pages_per_seq=4,
                slo_classes=SLO_TABLE)
    args.update(kw)
    return ContinuousBatchingExecutor(_synthetic_step(), **args)


# ---------------------------------------------------------------------------
# span trees from the traced runtime
# ---------------------------------------------------------------------------
def test_fleet_request_span_trees_validate(tmp_path):
    """THE acceptance property: every request routed through a traced
    fleet yields a well-formed span tree — single root, route stamp
    with the replica tag, queue/prefill/decode children nesting inside
    the root, phase durations summing to the measured e2e."""
    BUS.configure(str(tmp_path / "obs.jsonl"))
    TRACER.reset()
    TRACER.enabled = True
    fl = FleetExecutor(
        [_mk_executor(replica_label=str(i)) for i in range(2)],
        {c.name: [0.5, 0.5] for c in SLO_TABLE},
        slo_classes=SLO_TABLE, seed=7)
    reqs = [DecodeRequest(rid=f"r{i}", prompt=[2 + i, 3 + i, 4 + i],
                          max_new_tokens=3 + i % 3,
                          slo=SLO_TABLE[i % 3].name)
            for i in range(8)]
    fl.run(reqs)
    recs = {r["rid"]: r for r in fl.request_records
            if r.get("phase") == "finish"}
    assert len(recs) == 8
    assert TRACER.open_spans() == []
    seen = 0
    for tid in TRACER.trace_ids():
        rid = tid.split("#", 1)[0]
        rec = recs[rid]
        assert TRACER.validate_trace(tid, e2e_s=rec["e2e_s"]) == []
        spans = TRACER.trace_spans(tid)
        root = [s for s in spans if s.parent_id is None]
        assert len(root) == 1 and root[0].name == "request"
        names = {s.name for s in spans}
        assert {"route", "queue", "prefill", "decode"} <= names
        route = next(s for s in spans if s.name == "route")
        assert route.attrs["replica"] == fl.assignments[rid]
        seen += 1
    assert seen == 8


def test_preemption_reopens_queue_span(tmp_path):
    """A preempted request's tree narrates the re-queue: queue →
    prefill → decode → queue (requeue) → prefill → decode, and still
    validates against the measured e2e."""
    BUS.configure(str(tmp_path / "obs.jsonl"))
    TRACER.reset()
    TRACER.enabled = True
    ex = _mk_executor(max_seqs=1)
    ex.submit([DecodeRequest(rid="victim", prompt=[2, 3],
                             max_new_tokens=8, slo="batch")])
    ex.step()  # admit + first frame
    ex.submit([DecodeRequest(rid="vip", prompt=[4, 5],
                             max_new_tokens=2, slo="interactive")])
    ex.run(max_frames=100)
    recs = {r["rid"]: r for r in ex.request_records
            if r.get("phase") == "finish"}
    vt = [t for t in TRACER.trace_ids() if t.startswith("victim#")][0]
    assert TRACER.validate_trace(vt, e2e_s=recs["victim"]["e2e_s"]) == []
    names = [s.name for s in TRACER.trace_spans(vt)]
    assert names.count("queue") == 2  # the requeue re-opened it
    requeues = [s for s in TRACER.trace_spans(vt)
                if s.name == "queue" and s.attrs.get("requeue")]
    assert len(requeues) == 1
    root = [s for s in TRACER.trace_spans(vt) if s.parent_id is None][0]
    assert root.attrs.get("preempted") == 1


def test_tracer_disabled_adds_nothing(tmp_path):
    """Default-off: an untraced run mints no spans and no rid maps —
    the runtime edits must be invisible when the flag is down."""
    BUS.configure(str(tmp_path / "obs.jsonl"))
    assert not TRACER.enabled
    _mk_executor().run([DecodeRequest(rid="r0", prompt=[2, 3],
                                      max_new_tokens=2)])
    assert TRACER.trace_ids() == []
    assert TRACER.open_spans() == []


def test_validate_trace_flags_defects():
    t = Tracer()
    t.enabled = True
    tid = t.request_root("r0")
    t.begin(tid, "queue", parent="request")
    # still-open spans are a defect
    assert any("still open" in p for p in t.validate_trace(tid))
    t.end(tid, "queue")
    t.finish_request("r0")
    # a wildly wrong measured e2e trips the phase-sum check
    assert any("phase spans" in p
               for p in t.validate_trace(tid, e2e_s=1e6))
    # orphan detection is the forest helpers' job (dump/log replay)
    forest = span_forest([
        {"kind": "trace.span", "trace_id": "x", "span_id": 1,
         "parent_id": None, "span": "request"},
        {"kind": "trace.span", "trace_id": "x", "span_id": 2,
         "parent_id": 99, "span": "queue"},
    ])
    total, _depth, orphans = forest_stats(forest)
    assert (total, orphans) == (2, 1)


def test_rid_reuse_mints_fresh_trace():
    t = Tracer()
    t.enabled = True
    a = t.request_root("r0")
    t.finish_request("r0")
    b = t.request_root("r0")
    assert a != b and t.trace_of("r0") == b


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------
def test_chrome_trace_export_shape(tmp_path):
    t = Tracer()
    t.enabled = True
    tid = t.request_root("r0", slo="standard")
    t.annotate(tid, "route", parent="request", replica=1)
    t.begin(tid, "queue", parent="request")
    t.end(tid, "queue")
    t.finish_request("r0")
    eid = t.episode_root(trigger="burn_rate")
    t.begin(eid, "research", parent="controller.episode")  # left OPEN
    path = str(tmp_path / "trace.json")
    n = t.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(slices) == n == 5
    # one process row + one thread row per trace, named by trace id
    assert {m["name"] for m in metas} == {"process_name", "thread_name"}
    threads = {m["args"]["name"] for m in metas
               if m["name"] == "thread_name"}
    assert threads == {tid, eid}
    for e in slices:
        assert e["ts"] >= 0 and e["dur"] > 0
        assert {"trace_id", "span_id", "parent_id", "open"} \
            <= set(e["args"])
    open_slices = [e for e in slices if e["args"]["open"]]
    assert {e["name"] for e in open_slices} \
        == {"controller.episode", "research"}


def test_span_bound_evicts_oldest():
    t = Tracer(max_spans=4)
    t.enabled = True
    for i in range(6):
        tid = t.request_root(f"r{i}")
        t.finish_request(f"r{i}")
    assert len(t.spans) == 4 and t.dropped == 2


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_ring_records_disabled_bus_and_bounds(tmp_path):
    """The post-mortem point: the ring sees every emit even while the
    bus is OFF, stays bounded, and the dump carries the last-N events
    plus the open spans of the in-flight requests."""
    assert not BUS.enabled
    FLIGHT.reset()
    FLIGHT.configure(capacity=16)
    try:
        for i in range(50):
            BUS.emit("search.log", msg=f"m{i}")
        assert FLIGHT.recorded == 50 and len(FLIGHT.ring) == 16
        TRACER.reset()
        TRACER.enabled = True
        tid = TRACER.request_root("inflight", slo="standard")
        TRACER.begin(tid, "queue", parent="request")
        path = str(tmp_path / "dump.jsonl")
        assert FLIGHT.dump(path, reason="test") == path
        rows = [json.loads(ln) for ln in open(path)]
        meta = rows[0]
        assert meta["kind"] == "flight.meta" and meta["reason"] == "test"
        assert meta["events"] == 16 and meta["dropped"] == 34
        kinds = [r["kind"] for r in rows[1:]]
        assert kinds[:16] == ["search.log"] * 16
        opens = [r for r in rows if r["kind"] == "trace.open"]
        assert {r["span"] for r in opens} == {"request", "queue"}
        assert all(r["trace_id"] == tid for r in opens)
    finally:
        FLIGHT.configure(capacity=512)


def test_flight_dump_carries_the_last_phase_spans_after_the_events(tmp_path):
    """The operator's way to the program's timeline (obs/annotate.py):
    a dump writes the newest ``PHASE_SPANS`` closed spans as
    ``phase.span`` lines — tag, start, duration, seq, parent, key —
    after the events, each a valid event."""
    import time

    from flexflow_tpu.obs import flight
    from flexflow_tpu.obs.annotate import PHASE_PREFIX, phase_span, timeline
    from flexflow_tpu.obs.events import validate_event

    FLIGHT.reset()
    BUS.emit("search.log", msg="before")
    since = time.perf_counter_ns()
    for k in range(flight.PHASE_SPANS):  # 2 spans each: the first fall out
        with phase_span(PHASE_PREFIX + "t.dump_root", key=k):
            with phase_span(PHASE_PREFIX + "t.dump_child"):
                pass
    path = str(tmp_path / "dump.jsonl")
    wall = time.time()
    assert FLIGHT.dump(path, reason="test") == path
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0]["phase_spans"] == flight.PHASE_SPANS
    kinds = [r["kind"] for r in rows]
    assert kinds == (["flight.meta", "search.log"]
                     + ["phase.span"] * flight.PHASE_SPANS)
    spans = [r for r in rows if r["kind"] == "phase.span"]
    assert all(validate_event(r) == [] for r in spans)
    want = timeline(since)[-flight.PHASE_SPANS:]
    assert [(r["seq"], r["parent"], r["tag"], r["key"]) for r in spans] == [
        (seq, parent, tag, key) for seq, parent, tag, _, _, key in want]
    for r, (_, _, _, t0, t1, _) in zip(spans, want):
        assert r["start_s"] == pytest.approx(t0 * 1e-9, abs=1e-9)
        assert r["dur_s"] == pytest.approx((t1 - t0) * 1e-9, abs=1e-12)
        assert wall - 60 < r["ts"] <= wall + 1  # the close, on the wall clock
    child, root = spans[-2:]
    assert child["parent"] == root["seq"] and root["parent"] == 0
    assert child["key"] == root["key"] == flight.PHASE_SPANS - 1


def test_flight_disabled_is_a_true_noop(tmp_path):
    FLIGHT.reset()
    FLIGHT.enabled = False
    BUS.emit("search.log", msg="x")
    assert FLIGHT.recorded == 0
    assert FLIGHT.dump(str(tmp_path / "d.jsonl")) is None


def test_fault_injection_dumps_post_mortem(tmp_path):
    """Every fault injector writes the flight post-mortem when a dump
    dir is armed — the injected failure rehearses the unplanned one."""
    from flexflow_tpu.runtime.faults import FaultPlan

    FLIGHT.reset()
    FLIGHT.configure(dump_dir=str(tmp_path))
    BUS.emit("search.log", msg="before-fault")
    plan = FaultPlan.parse("p99_drift@0", seed=7)
    ratio = plan.inject_p99_drift(plan.due(0)[0])
    assert ratio > 1.5
    path = FLIGHT.last_dump_path
    assert path is not None and os.path.exists(path)
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0]["reason"] == "fault-p99_drift-step0"
    assert any(r.get("msg") == "before-fault" for r in rows)


def test_flight_dump_without_destination_is_none():
    rec = FlightRecorder(capacity=4)
    rec.record("x", {})
    assert rec.dump(reason="nowhere") is None  # opt-in by destination


# ---------------------------------------------------------------------------
# burn rate
# ---------------------------------------------------------------------------
def test_burn_fires_before_p99_drift():
    target = 0.1
    # persistent moderate violation: every completion at 1.3x target —
    # the budget torches while raw p99 sits under the 1.5x threshold
    burn_at, drift_at = first_fire_indices([0.13] * 48, target)
    assert burn_at == 8 and drift_at is None
    # load ramp: burn leads the raw p99 trigger by many completions
    ramp = [0.08 + i * (0.12 / 47.0) for i in range(48)]
    burn_at, drift_at = first_fire_indices(ramp, target)
    assert burn_at is not None and drift_at is not None
    assert burn_at < drift_at
    # a healthy stream fires neither
    assert first_fire_indices([0.05] * 48, target) == (None, None)


def test_burn_rate_spike_robust_under_loose_budget():
    lat = [0.05] * 20 + [0.4] + [0.05] * 20
    burn_at, _ = first_fire_indices(lat, 0.1, budget=0.1)
    assert burn_at is None  # one spike inside a 10% budget stays quiet


def test_burn_rates_per_class_map(tmp_path):
    BUS.configure(str(tmp_path / "obs.jsonl"))
    recs = ([{"phase": "finish", "slo": "standard", "ttft_s": 0.13}] * 12
            + [{"phase": "finish", "slo": "batch", "ttft_s": 0.05}] * 12)
    rates = burn_rates(recs, {"standard": 0.1, "batch": 0.1},
                       budgets={"standard": 0.01, "batch": 0.01})
    assert rates["standard"]["fired"] and not rates["batch"]["fired"]
    assert rates["standard"]["completions"] == 12


def test_controller_observe_burn_rate_arms_trigger(tmp_path):
    from flexflow_tpu.runtime.controller import TrainingController

    BUS.configure(str(tmp_path / "obs.jsonl"))
    model = SimpleNamespace(
        compiled=object(),
        fleet=SimpleNamespace(per_class_p99_s={"standard": 0.1}))
    ctl = TrainingController(model)
    source = SimpleNamespace(
        request_records=[{"phase": "finish", "slo": "standard",
                          "ttft_s": 0.13}] * 12,
        slo_classes={"standard": SLOClass("standard", priority=1,
                                          deadline_frames=0)})
    rates = ctl.observe_burn_rate(source)
    assert rates["standard"]["fired"]
    assert ctl._burn_trigger == "standard"
    BUS.flush()
    evs = [json.loads(ln)
           for ln in open(str(tmp_path / "obs.jsonl"))]
    burns = [e for e in evs if e["kind"] == "controller.burn_rate"]
    assert burns and burns[-1]["slo"] == "standard" \
        and burns[-1]["fired"]
    # no fleet proposal on the model -> honest None, no trigger
    ctl._burn_trigger = None
    ctl.model = SimpleNamespace(compiled=object())
    assert ctl.observe_burn_rate(source) is None
    assert ctl._burn_trigger is None


# ---------------------------------------------------------------------------
# ffobs trace rendering
# ---------------------------------------------------------------------------
def test_ffobs_trace_renders_and_flags_orphans(tmp_path):
    import subprocess
    import sys

    log = tmp_path / "trace.jsonl"
    rows = [
        {"ts": 1.0, "kind": "trace.span", "trace_id": "r0#1",
         "span": "request", "span_id": 1, "parent_id": None,
         "start_s": 0.0, "end_s": 1.0, "dur_s": 1.0},
        {"ts": 1.0, "kind": "trace.span", "trace_id": "r0#1",
         "span": "queue", "span_id": 2, "parent_id": 1,
         "start_s": 0.0, "end_s": 0.4, "dur_s": 0.4},
    ]
    log.write_text("".join(json.dumps(r) + "\n" for r in rows))
    ffobs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "ffobs.py")
    proc = subprocess.run(
        [sys.executable, ffobs, "trace", str(log)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "trace r0#1" in proc.stdout and "queue" in proc.stdout
    assert "0 orphan span(s)" in proc.stdout
    # an orphan flips the exit code — validation failure, not cosmetics
    rows.append({"ts": 1.0, "kind": "trace.span", "trace_id": "r0#1",
                 "span": "ghost", "span_id": 3, "parent_id": 77,
                 "start_s": 0.0, "end_s": 0.1, "dur_s": 0.1})
    log.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = subprocess.run(
        [sys.executable, ffobs, "trace", str(log)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "ORPHAN" in proc.stdout


def test_ffobs_trace_renders_the_phase_spans_of_a_flight_dump(tmp_path):
    """``phase.span`` lines come out as the program's timeline: trees by
    ``parent``, oldest first, a root with its key; a span whose parent
    the dump does not hold (it was still open) stands as a root and says
    so — beside the request trees, and alone."""
    import subprocess
    import sys

    def span(seq, parent, tag, start, dur, key):
        return {"ts": 100.0 + start + dur, "kind": "phase.span",
                "tag": "ff.phase/" + tag, "start_s": start, "dur_s": dur,
                "seq": seq, "parent": parent, "key": key}

    rows = [
        {"ts": 1.0, "kind": "flight.meta", "reason": "t", "events": 0,
         "dropped": 0, "phase_spans": 5},
        span(12, 10, "serve.dispatch", 5.0020, 0.0015, 7),
        span(13, 12, "call.decode_frame", 5.0022, 0.0011, 7),
        span(11, 10, "serve.admit", 5.0001, 0.0004, 7),
        span(10, 0, "decode_frame", 5.0, 0.004, 7),
        span(21, 20, "serve.admit", 5.0051, 0.0002, 8),
    ]
    log = tmp_path / "dump.jsonl"
    log.write_text("".join(json.dumps(r) + "\n" for r in rows))
    ffobs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "ffobs.py")
    proc = subprocess.run(
        [sys.executable, ffobs, "trace", str(log)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.splitlines()[:6] == [
        "program timeline  (5 spans)",
        "  ff.phase/decode_frame  4.000 ms  key=7",
        "    ff.phase/serve.admit  0.400 ms",
        "    ff.phase/serve.dispatch  1.500 ms",
        "      ff.phase/call.decode_frame  1.100 ms",
        "  ff.phase/serve.admit  0.200 ms  key=8  "
        "(inside span 20, still open)",
    ]
    assert "no trace.span events" not in proc.stdout
    # beside a request tree, after it
    rows.append({"ts": 1.0, "kind": "trace.span", "trace_id": "r0#1",
                 "span": "request", "span_id": 1, "parent_id": None,
                 "start_s": 0.0, "end_s": 1.0, "dur_s": 1.0})
    log.write_text("".join(json.dumps(r) + "\n" for r in rows))
    proc = subprocess.run(
        [sys.executable, ffobs, "trace", str(log)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert out.index("trace r0#1") < out.index("program timeline")
    assert "0 orphan span(s)" in out
