"""The measured side of the loop (ISSUE 14): device-trace ingestion +
lane matching (obs/trace_ingest.py + obs/annotate.py), per-request
serving telemetry (runtime/decode.py), the Prometheus exposition
(obs/exposition.py), and the seeded-reservoir histogram fix.

The committed fixture ``tests/data/device_trace_fixture.trace.json``
exercises the parser and tag matcher without a live capture; the
tier-1 smoke at the bottom runs the REAL pipeline — a short fit with
``device_trace_dir`` on the 8-dev CPU mesh, a decode serve with obs
on, ingest → match → ``LaneDriftReport`` — and asserts ``ffobs
report`` renders it, ``ffobs validate`` exits 0, and ``ffobs
metrics`` renders the Prometheus exposition from the snapshot JSONL
offline.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.obs.annotate import lane_tag, parse_tag
from flexflow_tpu.obs.drift import build_drift_report
from flexflow_tpu.obs.events import BUS, validate_event
from flexflow_tpu.obs.exposition import render_prometheus
from flexflow_tpu.obs.metrics import Histogram, MetricsRegistry
from flexflow_tpu.obs.trace_ingest import (
    apply_lane_measurements,
    build_lane_drift_report,
    ingest,
    match_lanes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "data",
                       "device_trace_fixture.trace.json")


@pytest.fixture(autouse=True)
def _bus_teardown():
    yield
    BUS.close()


# ---------------------------------------------------------------------------
# annotation tag vocabulary
def test_lane_tag_roundtrip():
    assert lane_tag("bucket:b0:sync") == "ff.lane/bucket:b0:sync"
    assert parse_tag("ff.lane/bucket:b0:sync#issue") == \
        ("bucket:b0:sync", "issue")
    assert parse_tag("ff.lane/bucket:b0:sync#done") == \
        ("bucket:b0:sync", "done")
    assert parse_tag("ff.lane/x:sync") == ("x:sync", None)
    assert parse_tag("dot.4") is None


# ---------------------------------------------------------------------------
# fixture: parser + pairing
def test_fixture_ingest_parses_and_pairs():
    result = ingest(FIXTURE, emit=False)
    assert result is not None
    assert result.events > 10
    # two annotated step windows, in time order
    assert result.step_spans == [(1000.0, 2000.0), (3000.0, 3800.0)]
    # issue/done pairs per lane: the out-of-window b0 pair still pairs
    # here (windows apply at MATCH time); the unpaired trailing b1
    # issue is dropped
    assert sorted(result.lanes) == [
        "bucket:b0:sync", "bucket:b1:sync", "bucket:zz:sync"]
    assert len(result.lanes["bucket:b0:sync"]) == 3
    assert len(result.lanes["bucket:b1:sync"]) == 2
    # non-step phase spans are collected with their durations
    assert result.phases["ff.phase/decode_frame"] == [300.0 / 1e6]


def _predicted(total_s=0.001, b1_sync=0.0003):
    """A Simulator.simulate(breakdown=...)-shaped prediction whose
    lanes mirror the fixture: b0 issues at 20% of the step for 30%,
    b1 at 60% for 30% — matching the fixture's measured fractions."""
    return {
        "total_s": total_s,
        "sync_buckets": [
            {"name": "b0", "lane": "bucket:b0:sync", "ops": ["x"],
             "start_s": 0.0002, "sync_s": 0.0003, "exposed_s": 0.0},
            {"name": "b1", "lane": "bucket:b1:sync", "ops": ["y"],
             "start_s": 0.0006, "sync_s": b1_sync, "exposed_s": 0.0},
        ],
    }


def test_fixture_lane_match_by_tag():
    result = ingest(FIXTURE, emit=False)
    report = match_lanes(result, _predicted(), threshold=0.5,
                         emit=False)
    assert report is not None
    assert report.steps == 2
    assert report.matched_all and report.matched == 2
    by = {r["lane"]: r for r in report.lanes}
    b0 = by["bucket:b0:sync"]
    # only the two IN-WINDOW occurrences count (the 6000us pair sits
    # outside every step span)
    assert b0["samples"] == 2
    # window 1: issue 200us/dur 300us of a 1000us step; window 2:
    # issue 160us/dur 240us of 800us — means over both
    assert b0["measured_issue_s"] == pytest.approx(180e-6)
    assert b0["measured_sync_s"] == pytest.approx(270e-6)
    assert b0["measured_issue_frac"] == pytest.approx(0.2, rel=1e-6)
    assert b0["measured_sync_frac"] == pytest.approx(0.3, rel=1e-6)
    # the prediction put b0 at the same fractions: ratio 1.0
    assert b0["issue_frac_ratio"] == pytest.approx(1.0, rel=1e-6)
    assert b0["sync_frac_ratio"] == pytest.approx(1.0, rel=1e-6)
    assert report.stale_lanes == []
    # the lane the prediction does not know is reported, not silently
    # absorbed into a fuzzy match
    assert report.unmatched_trace == ["bucket:zz:sync"]


def test_fixture_lane_drift_flags_stale_lane():
    """A lane whose measured step share is far off its predicted share
    lands in stale_lanes — the per-lane drift signal."""
    result = ingest(FIXTURE, emit=False)
    report = match_lanes(result, _predicted(b1_sync=0.00001),
                         threshold=0.5, emit=False)
    assert report.stale_lanes == ["bucket:b1:sync"]


def test_fixture_unmatched_predicted_lane():
    pred = _predicted()
    pred["sync_buckets"].append(
        {"name": "b9", "lane": "bucket:b9:sync", "ops": ["z"],
         "start_s": 0.0008, "sync_s": 0.0001, "exposed_s": 0.0})
    report = match_lanes(ingest(FIXTURE, emit=False), pred, emit=False)
    assert not report.matched_all
    assert report.unmatched_predicted == ["bucket:b9:sync"]


def test_apply_lane_measurements_fills_drift_report():
    """The previously-None measured bucket fields of the DriftReport
    are populated from a matched capture."""
    pred = _predicted()
    drift = build_drift_report(pred, measured_step_s=0.0011)
    assert all(b["measured_s"] is None for b in drift.sync_buckets)
    report = match_lanes(ingest(FIXTURE, emit=False), pred, emit=False)
    filled = apply_lane_measurements(drift, report)
    assert filled == 2
    by = {b["lane"]: b for b in drift.sync_buckets}
    assert by["bucket:b0:sync"]["measured_s"] == pytest.approx(270e-6)
    assert by["bucket:b0:sync"]["measured_issue_s"] == \
        pytest.approx(180e-6)
    assert by["bucket:b0:sync"]["measured_source"] == "host_trace"


def test_ingest_emits_schema_valid_events(tmp_path):
    log = str(tmp_path / "log.jsonl")
    BUS.configure(log)
    build_lane_drift_report(FIXTURE, _predicted(), threshold=0.5)
    BUS.close()
    events = [json.loads(x) for x in open(log)]
    kinds = [e["kind"] for e in events]
    assert "trace.ingest" in kinds
    assert kinds.count("trace.lane_match") == 2
    for e in events:
        assert validate_event(e) == [], e


# ---------------------------------------------------------------------------
# satellite: seeded reservoir histogram
def test_histogram_reservoir_tracks_whole_stream():
    """The old first-N sampling froze percentiles on the first 4096
    observations — a long-running server reported its warm-up p99
    forever.  The reservoir keeps tracking: a stream whose second half
    is 10x slower must raise the reported p99 accordingly."""
    frozen_like = Histogram("t", max_samples=512)
    for _ in range(2000):
        frozen_like.observe(1.0)
    for _ in range(2000):
        frozen_like.observe(10.0)
    s = frozen_like.summary()
    # exact aggregates never sampled
    assert s["count"] == 4000
    assert s["sum"] == pytest.approx(2000 * 1.0 + 2000 * 10.0)
    assert s["min"] == 1.0 and s["max"] == 10.0
    # ~half the reservoir is late observations: p95/p99 must see them
    assert s["p99"] == 10.0
    assert s["p50"] in (1.0, 10.0)


def test_histogram_reservoir_deterministic():
    """Same metric name + same stream => identical reservoir (the
    seed derives from the name), including across reset()."""
    rng = np.random.default_rng(3)
    stream = rng.normal(10.0, 2.0, size=5000).tolist()
    a, b = Histogram("x", max_samples=256), Histogram("x", max_samples=256)
    for v in stream:
        a.observe(v)
        b.observe(v)
    assert a.summary() == b.summary()
    reg = MetricsRegistry()
    h = reg.histogram("x")
    h.max_samples = 256
    for v in stream:
        h.observe(v)
    first = h.summary()
    reg.reset()
    for v in stream:
        h.observe(v)
    assert h.summary() == first


# ---------------------------------------------------------------------------
# satellite: Prometheus exposition
def test_render_prometheus_families():
    reg = MetricsRegistry()
    reg.counter("fit.steps").inc(7)
    reg.gauge("fit.drift_ratio").set(1.25)
    h = reg.histogram("decode.ttft_s")
    for v in (0.01, 0.02, 0.03):
        h.observe(v)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE flexflow_tpu_fit_steps counter" in text
    assert "flexflow_tpu_fit_steps 7" in text
    assert "# TYPE flexflow_tpu_fit_drift_ratio gauge" in text
    assert "flexflow_tpu_fit_drift_ratio 1.25" in text
    assert "# TYPE flexflow_tpu_decode_ttft_s summary" in text
    assert 'flexflow_tpu_decode_ttft_s{quantile="0.99"}' in text
    assert "flexflow_tpu_decode_ttft_s_count 3" in text
    assert "flexflow_tpu_decode_ttft_s_sum" in text


def test_metrics_http_endpoint():
    """The stdlib endpoint serves the live registry at /metrics; an
    ephemeral port keeps the test hermetic."""
    import urllib.request

    from flexflow_tpu.obs.exposition import MetricsServer

    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(3)
    srv = MetricsServer(0, registry=reg)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
        assert b"flexflow_tpu_serve_requests 3" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# satellite: per-request decode telemetry (+ the one-check contract is
# in tests/test_obs.py next to the bus-overhead test)
def _synthetic_step(vocab=97):
    def step(ids, table, lens):
        ids = np.asarray(ids)
        lens = np.asarray(lens)
        nxt = (ids[:, 0] * 7 + lens * 13 + 5) % vocab
        logits = np.zeros((ids.shape[0], 1, vocab), np.float32)
        logits[np.arange(ids.shape[0]), 0, nxt] = 1.0
        return logits

    return step


def test_decode_request_lifecycle_telemetry(tmp_path):
    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    log = str(tmp_path / "log.jsonl")
    BUS.configure(log)
    base = METRICS.histogram("decode.ttft_s").count
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=4,
        predicted_step_s=1e-4)
    reqs = [DecodeRequest(rid=f"r{i}", prompt=[3 + i, 11],
                          max_new_tokens=3) for i in range(4)]
    out = ex.run(reqs, max_frames=200)
    assert len(out) == 4
    # one lifecycle record per completed request
    assert len(ex.request_records) == 4
    for rec in ex.request_records:
        assert rec["tokens"] == 3
        assert rec["e2e_s"] > 0 and rec["ttft_s"] > 0
        assert rec["queue_s"] >= 0
        assert rec["tpot_s"] is not None  # 3 tokens => steady TPOT
        assert rec["ttft_s"] <= rec["e2e_s"]
    # the last two requests queued behind the first two: their queue
    # wait includes real frames
    s = ex.summary()
    assert s["requests_recorded"] == 4
    assert s["ttft_p99_s"] >= s["ttft_p50_s"] > 0
    assert s["tpot_p99_s"] > 0 and s["e2e_p99_s"] > 0
    # TTFT/TPOT histograms in the metrics registry grew
    assert METRICS.histogram("decode.ttft_s").count == base + 4
    # the continuous p99 drift signal
    assert ex.measured_p99() > 0
    assert ex.measured_p99(window=2) > 0
    rep = ex.decode_drift_report(window=3)
    assert rep is not None and rep.phases["decode"]["ratio"] == rep.ratio
    BUS.close()
    events = [json.loads(x) for x in open(log)]
    reqs_ev = [e for e in events if e["kind"] == "decode.request"]
    assert len(reqs_ev) == 4
    for e in events:
        assert validate_event(e) == [], e


# ---------------------------------------------------------------------------
# tier-1 smoke: the full measured-lane pipeline on the 8-dev CPU mesh
def _stop_stray_capture():
    """fit stops the capture it started; a fit that RAISED inside it did
    not, and a capture left running would fail every later capture of
    this worker instead of this one test."""
    import jax

    try:
        jax.profiler.stop_trace()
    except RuntimeError:
        pass  # no capture was left running: the normal case


def test_lane_capture_smoke_e2e(tmp_path, mesh8):
    """fit with device_trace_dir: a REAL capture on the CPU mesh
    round-trips into a LaneDriftReport with every annotated sync
    bucket tag-matched, the DriftReport's measured bucket fields
    populated; a decode serve with obs on rides the same log; ffobs
    report renders lane + request sections, validate exits 0, and
    metrics renders the Prometheus exposition offline."""
    from flexflow_tpu.models import build_transformer
    from flexflow_tpu.obs.metrics import METRICS
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    log = str(tmp_path / "obs.jsonl")
    tdir = str(tmp_path / "device_trace")
    BUS.close()
    BUS.configure(log)
    cfg = ff.FFConfig(batch_size=8, num_devices=8, epochs=2,
                      only_data_parallel=True, compute_dtype="float32",
                      sync_schedule="search", profiling=True,
                      obs_log_file=log, device_trace_dir=tdir)
    m = build_transformer(cfg, num_layers=1, hidden=512, num_heads=4,
                          ff_dim=2048, seq_len=8)
    m.compile(loss_type="mean_squared_error", metrics=[])
    assert m.sync_schedule is not None and m.sync_schedule.buckets
    rng = np.random.default_rng(0)
    x = rng.normal(size=(24, 8, 512)).astype(np.float32)
    y = rng.normal(size=(24, 8, 512)).astype(np.float32)
    try:
        m.fit(x=x, y=y, verbose=False, shuffle=False)
    finally:
        _stop_stray_capture()

    report = m.lane_drift_report
    assert report is not None, "capture did not ingest"
    # every annotated sync bucket tag-matched — no fuzzy-name matching
    assert report.matched_all, report.to_dict()
    assert len(report.lanes) == len(m.sync_schedule.buckets)
    assert report.steps >= 2
    for lane in report.lanes:
        assert lane["samples"] >= 1
        assert lane["measured_issue_s"] > 0
        assert lane["measured_sync_s"] > 0
    # the previously-None measured bucket fields are populated
    assert m.drift_report is not None
    for b in m.drift_report.sync_buckets:
        assert b["measured_s"] is not None
        assert b["measured_source"] == "host_trace"

    # decode serve with obs on, feeding the same log + registry
    ex = ContinuousBatchingExecutor(
        _synthetic_step(), max_seqs=2, page_size=4, pages_per_seq=4,
        predicted_step_s=1e-4)
    ex.run([DecodeRequest(rid=f"q{i}", prompt=[2 + i, 5],
                          max_new_tokens=2) for i in range(3)],
           max_frames=100)
    ex.decode_drift_report()
    METRICS.emit_snapshot()
    BUS.close()

    # every line schema-valid, the new kinds present
    kinds = set()
    with open(log) as f:
        for line in f:
            obj = json.loads(line)
            assert validate_event(obj) == [], (validate_event(obj), line)
            kinds.add(obj["kind"])
    assert {"trace.ingest", "trace.lane_match", "decode.request",
            "metrics.snapshot"} <= kinds

    ffobs = os.path.join(REPO, "tools", "ffobs.py")
    rep = subprocess.run([sys.executable, ffobs, "report", log],
                        capture_output=True, text=True, timeout=120)
    assert rep.returncode == 0, rep.stderr
    assert "Measured lanes (device-trace capture)" in rep.stdout
    assert "bucket:b0:sync" in rep.stdout
    assert "Per-request telemetry" in rep.stdout
    val = subprocess.run([sys.executable, ffobs, "validate", log],
                        capture_output=True, text=True, timeout=120)
    assert val.returncode == 0, val.stdout + val.stderr
    met = subprocess.run([sys.executable, ffobs, "metrics", log],
                        capture_output=True, text=True, timeout=120)
    assert met.returncode == 0, met.stdout + met.stderr
    assert "flexflow_tpu_decode_ttft_s_count" in met.stdout
    assert "# TYPE" in met.stdout
