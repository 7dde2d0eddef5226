"""Unified telemetry (flexflow_tpu/obs): event bus, metrics registry,
Chrome-trace export, drift reporting — plus the satellites: lazy
RecursiveLogger gating, StepProfiler compile-step honesty, and
measure_operator_cost declining unmeasurable ops.

The tier-1 smoke here is the acceptance gate: a tiny search+fit with
telemetry on must emit schema-valid JSONL only, and
``tools/ffobs.py report`` must render it with exit code 0.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.obs.drift import build_drift_report
from flexflow_tpu.obs.events import BUS, EventBus, validate_event
from flexflow_tpu.obs.metrics import METRICS, MetricsRegistry
from flexflow_tpu.runtime.profiler import StepProfiler, measure_operator_cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _bus_teardown():
    yield
    BUS.close()


def _blobs(n=64, dim=64, classes=16, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, dim)).astype(np.float32),
            rng.integers(0, classes, size=(n,)).astype(np.int32))


# ---------------------------------------------------------------------------
# event bus
def test_event_bus_off_by_default_and_cheap():
    bus = EventBus()
    assert not bus.enabled
    t0 = time.perf_counter()
    for _ in range(100_000):
        bus.emit("search.log", msg="x")
    elapsed = time.perf_counter() - t0
    # one attribute check per call: 100k disabled emits in well under a
    # second even on a loaded CI host
    assert elapsed < 1.0, f"disabled emit too slow: {elapsed:.3f}s"


def test_decode_request_spans_one_bus_check_per_frame(monkeypatch):
    """The off-by-default contract on the decode hot path: with
    FLEXFLOW_TPU_OBS unset, request-span instrumentation must cost
    exactly one ``BUS.enabled`` read per frame (plus one per submit
    batch and one at run end) and one ``TRACER.enabled`` read per frame
    (plus one per submit batch) — no per-slot stamps, no histogram
    traffic, no lifecycle records.  The program's span timeline
    (obs/annotate.py) is always on and asks neither: the run leaves one
    ``decode_frame`` tree a frame in the ring all the same."""
    import time

    from flexflow_tpu.obs import annotate
    from flexflow_tpu.runtime import decode as decode_mod
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    class CountingBus:
        def __init__(self):
            self.reads = 0

        @property
        def enabled(self):
            self.reads += 1
            return False

        def emit(self, *a, **k):  # pragma: no cover — enabled is False
            raise AssertionError("emit while disabled")

    class CountingTracer:
        def __init__(self):
            self.reads = 0

        @property
        def enabled(self):
            self.reads += 1
            return False

        def __getattr__(self, name):  # pragma: no cover — enabled is False
            raise AssertionError(f"TRACER.{name} while disabled")

    bus, tracer = CountingBus(), CountingTracer()
    monkeypatch.setattr(decode_mod, "BUS", bus)
    monkeypatch.setattr(decode_mod, "TRACER", tracer)
    since = time.perf_counter_ns()

    def step(ids, table, lens):
        b = np.asarray(ids).shape[0]
        logits = np.zeros((b, 1, 7), np.float32)
        logits[:, 0, 3] = 1.0
        return logits

    ex = ContinuousBatchingExecutor(step, max_seqs=2, page_size=4,
                                    pages_per_seq=2)
    ex.run([DecodeRequest(rid=f"r{i}", prompt=[1, 2], max_new_tokens=2)
            for i in range(3)], max_frames=50)
    frames = ex.frame
    # one read per frame + one per submit batch + one at run end
    assert bus.reads <= frames + 2, (bus.reads, frames)
    assert tracer.reads <= frames + 1, (tracer.reads, frames)
    roots = [s for s in annotate.timeline(since)
             if s[2] == annotate.DECODE_PHASE]
    assert [s[5] for s in roots] == list(range(frames))
    # and none of the span machinery ran
    assert ex.request_records == []
    assert ex.queue == []
    assert all(s is None for s in ex.slots)


def test_event_bus_jsonl_sink_and_schema(tmp_path):
    bus = EventBus()
    path = str(tmp_path / "log.jsonl")
    bus.configure(path)
    bus.emit("search.begin", nodes=3, devices=8)
    bus.emit("search.substitution", xfer="t", action="pushed", est_s=0.1)
    bus.close()
    lines = [json.loads(x) for x in open(path)]
    assert [e["kind"] for e in lines] == [
        "obs.meta", "search.begin", "search.substitution"]
    for e in lines:
        assert validate_event(e) == []


def test_validate_event_rejects_bad_events():
    assert validate_event({"kind": "search.begin"})  # no ts, no fields
    assert validate_event({"ts": 1.0, "kind": "nope.unknown"})
    assert validate_event(
        {"ts": 1.0, "kind": "search.substitution", "xfer": "t",
         "action": "exploded"})  # action outside the enum
    assert validate_event(
        {"ts": 1.0, "kind": "search.begin", "nodes": 1, "devices": 8}) == []


def test_metrics_registry_reset_keeps_objects():
    reg = MetricsRegistry()
    c = reg.counter("a")
    c.inc(3)
    h = reg.histogram("h")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["histograms"]["h"]["count"] == 3
    reg.reset()
    assert reg.counter("a") is c and c.value == 0
    assert reg.histogram("h").summary() == {"count": 0}


# ---------------------------------------------------------------------------
# satellites: StepProfiler honesty + lazy RecursiveLogger
def test_step_profiler_flags_compile_only_summary():
    p = StepProfiler()
    p.start_step()
    p.end_step()
    s = p.summary(skip_first=1)
    # a single (compile) step is reported, not silently passed off as
    # steady-state
    assert s["steps"] == 1 and s["includes_compile"] is True
    for _ in range(3):
        p.start_step()
        p.end_step()
    s = p.summary(skip_first=1)
    assert s["steps"] == 3 and s["includes_compile"] is False


def test_step_profiler_phases():
    p = StepProfiler()
    for _ in range(2):
        p.start_step()
        p.start_phase("dispatch")
        p.end_phase("dispatch")
        p.start_phase("wait")
        time.sleep(0.001)
        p.end_phase("wait")
        p.end_step()
    ps = p.phase_summary()
    assert set(ps) == {"dispatch", "wait"}
    assert ps["wait"]["mean_s"] > 0 and ps["wait"]["count"] == 1


def test_recursive_logger_lazy_env_and_set_enabled(monkeypatch, tmp_path):
    import io

    from flexflow_tpu.utils.logging import RecursiveLogger

    stream = io.StringIO()
    lg = RecursiveLogger("t", stream=stream)
    monkeypatch.delenv("FLEXFLOW_TPU_SEARCH_LOG", raising=False)
    assert not lg.enabled
    # the env var is re-read lazily — the import-time snapshot this
    # replaces could never be toggled by tests
    monkeypatch.setenv("FLEXFLOW_TPU_SEARCH_LOG", "1")
    assert lg.enabled
    lg.set_enabled(False)
    assert not lg.enabled
    lg.set_enabled(None)  # re-arm the env lookup
    assert lg.enabled
    lg.set_enabled(True)
    lg.log("hello")
    assert "hello" in stream.getvalue()


def test_recursive_logger_routes_through_bus(tmp_path):
    import io

    from flexflow_tpu.utils.logging import RecursiveLogger

    path = str(tmp_path / "log.jsonl")
    BUS.configure(path)
    lg = RecursiveLogger("t", enabled=False, stream=io.StringIO())
    with lg.enter("outer"):
        lg.log("inner")
    BUS.close()
    events = [json.loads(x) for x in open(path)]
    logs = [e for e in events if e["kind"] == "search.log"]
    assert [e["msg"] for e in logs] == ["outer", "inner"]
    assert logs[1]["depth"] == 1
    for e in events:
        assert validate_event(e) == []


# ---------------------------------------------------------------------------
# satellite: measure_operator_cost declines unmeasurable ops
def test_measure_operator_cost_declines_integer_only_op():
    from flexflow_tpu.core.ptensor import ParallelTensorShape

    class IntOnlyOp:
        """No floating input or weight: the timing scan would be
        loop-invariant and XLA would hoist the op — a clamped floor
        would poison the calibration table with a free op."""

        name = "int_only"
        _weight_specs = ()
        input_shapes = [ParallelTensorShape.make((64, 32), "int32")]

        def state_specs(self):
            return ()

        def forward(self, ctx, inputs, weights):
            return [inputs[0] * 2]

    assert measure_operator_cost(IntOnlyOp(), warmup=1, repeats=1) is None


def test_declined_probe_keeps_roofline_fallback():
    from flexflow_tpu.core.machine import MachineSpec, MachineView
    from flexflow_tpu.core.ptensor import ParallelTensorShape
    from flexflow_tpu.ops.linear import LinearOp
    from flexflow_tpu.search.calibration import CalibrationTable
    from flexflow_tpu.search.machine_model import CostModel

    op = LinearOp("lin", [ParallelTensorShape.make((64, 128), "float32")],
                  out_dim=64)
    mv = MachineView.data_parallel(2, 8)
    machine = MachineSpec.tpu_v5e(8)
    empty = CalibrationTable()  # a declined probe stores nothing
    with_table = CostModel(machine, calibration=empty, num_devices=8)
    without = CostModel(machine, calibration=None, num_devices=8)
    c_t = with_table.op_cost(op, mv)
    c_r = without.op_cost(op, mv)
    assert np.isfinite(c_t) and c_t > 0
    assert c_t == c_r  # no record -> identical analytic roofline


# ---------------------------------------------------------------------------
# chrome-trace export + drift report units
def test_chrome_trace_schema(tmp_path):
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.simulator import Simulator

    cfg = ff.FFConfig(batch_size=32, num_devices=8)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64], name="x")
    t = m.dense(x, 64, activation="relu", name="l1")
    m.dense(t, 8, name="l2")
    g = m.graph
    sim = Simulator(cfg.machine_spec, num_devices=8)
    path = str(tmp_path / "trace.json")
    cost = sim.export_chrome_trace(g, data_parallel_strategy(g, 8), path)
    assert np.isfinite(cost) and cost > 0
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    slices = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert slices and metas
    names = {e["name"] for e in slices}
    assert {"l1", "l2"} <= names
    for e in slices:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["tid"], int) and e["pid"] == 0
    # weight-sync collectives land on the comm rows
    assert any(e["name"].endswith(":sync") for e in slices)


def test_simulate_breakdown_totals():
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.simulator import Simulator

    cfg = ff.FFConfig(batch_size=32, num_devices=8)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 64], name="x")
    m.dense(x, 64, name="l1")
    g = m.graph
    sim = Simulator(cfg.machine_spec, num_devices=8)
    bd = {}
    cost = sim.simulate(g, data_parallel_strategy(g, 8), breakdown=bd)
    assert bd["total_s"] == cost
    assert bd["total_s"] == pytest.approx(
        max(bd["compute_end_s"], bd["comm_end_s"]))
    assert bd["sync_total_s"] > 0  # the dense weight allreduce


def test_drift_report_staleness_flags():
    pred = {"total_s": 0.010, "compute_end_s": 0.008, "comm_end_s": 0.010}
    ok = build_drift_report(pred, measured_step_s=0.011, threshold=0.5)
    assert ok is not None and not ok.stale
    assert ok.phases["step"]["ratio"] == pytest.approx(1.1)
    slow = build_drift_report(pred, measured_step_s=0.030, threshold=0.5,
                              calibrated=True)
    assert slow.stale and slow.calibration_stale
    fast = build_drift_report(pred, measured_step_s=0.005, threshold=0.5)
    assert fast.stale and not fast.calibration_stale
    assert build_drift_report({"total_s": float("inf")}, 0.01) is None


def test_strategy_io_meta_roundtrip(tmp_path):
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.strategy_io import (
        attach_meta,
        export_strategy,
        import_strategy,
        read_meta,
    )

    cfg = ff.FFConfig(batch_size=32, num_devices=8)
    m = ff.FFModel(cfg)
    x = m.create_tensor([32, 16], name="x")
    m.dense(x, 8, name="l1")
    g = m.graph
    strategy = data_parallel_strategy(g, 8)
    path = str(tmp_path / "s.json")
    export_strategy(path, g, strategy, meta={"predicted": {"total_s": 1.0}})
    # the reserved __meta__ key never leaks into the imported strategy
    imported = import_strategy(path, g)
    assert set(imported) == set(strategy)
    attach_meta(path, drift={"ratio": 1.2})
    meta = read_meta(path)
    assert meta["predicted"]["total_s"] == 1.0
    assert meta["drift"]["ratio"] == 1.2


# ---------------------------------------------------------------------------
# tier-1 smoke: search + fit with telemetry on, schema-valid log,
# ffobs report exits 0
def test_search_fit_telemetry_smoke(tmp_path):
    log = str(tmp_path / "obs.jsonl")
    strat = str(tmp_path / "strategy.json")
    trace = str(tmp_path / "pred_timeline.json")
    cfg = ff.FFConfig(batch_size=32, epochs=2, num_devices=8,
                      compute_dtype="float32", profiling=True,
                      search_budget=4, search_timeout_s=30.0,
                      obs_log_file=log, obs_trace_file=trace,
                      export_strategy_file=strat)
    model = ff.FFModel(cfg)
    x = model.create_tensor([32, 64], name="in")
    t = model.dense(x, 256, activation="relu", name="d1")
    model.dense(t, 16, name="d2")
    model.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    dx, dy = _blobs()
    model.fit(x=dx, y=dy, verbose=False)
    BUS.close()

    kinds = set()
    with open(log) as f:
        for line in f:
            obj = json.loads(line)
            assert validate_event(obj) == [], (validate_event(obj), line)
            kinds.add(obj["kind"])
    # the three layers all reported: search decisions, compile-time
    # strategy table, runtime profile + drift
    assert {"search.begin", "search.baseline", "search.floor",
            "search.result", "dp.summary", "strategy.table",
            "profile.summary", "drift.report"} <= kinds

    assert model.drift_report is not None
    assert model.drift_report.phases["step"]["ratio"] is not None
    # drift persisted alongside the exported strategy
    meta = json.load(open(strat))["__meta__"]
    assert "predicted" in meta and "drift" in meta
    # predicted timeline is Perfetto-loadable chrome-trace JSON
    doc = json.load(open(trace))
    assert doc["traceEvents"]

    # metrics registry saw the fit steps (the PROFILE-print replacement)
    assert METRICS.counter("fit.steps").value > 0

    # the CLI renders the log and exits 0
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffobs.py"),
         "report", log],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "Chosen strategy" in proc.stdout
    assert "Drift" in proc.stdout
    val = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ffobs.py"),
         "validate", log],
        capture_output=True, text=True, timeout=120)
    assert val.returncode == 0, val.stdout + val.stderr


# ---------------------------------------------------------------------------
# event-kind completeness guard (ISSUE 9 satellite): every emit site in
# the tree must name a registered kind, so the PR-8 class of
# "pre-existing ffobs validate gap" (search.chain emitted but never
# registered) cannot recur


def test_every_emit_site_names_a_registered_kind():
    """AST sweep over flexflow_tpu/ + tools/ + the bench drivers: every
    event-bus ``emit("<kind>", ...)`` call with a literal kind must
    name a key of ``EVENT_KINDS`` — an unregistered kind would make
    every log containing it fail ``ffobs validate``.  Bus receivers
    are identified by name (``BUS`` / ``_obs_bus`` bindings, plus the
    bus's own ``self.emit`` inside obs/events.py) so the frontends'
    unrelated ``emit(op_kind, ...)`` builders do not false-positive."""
    import ast

    from flexflow_tpu.obs.events import EVENT_KINDS

    def _receiver_is_bus(func: ast.Attribute, path: str) -> bool:
        base = func.value
        if isinstance(base, ast.Name):
            if base.id in ("BUS", "_obs_bus", "bus"):
                return True
            return base.id == "self" and path.endswith(
                os.path.join("obs", "events.py"))
        # dotted spellings like events.BUS.emit / obs.events.BUS.emit
        return isinstance(base, ast.Attribute) and base.attr == "BUS"

    roots = [os.path.join(REPO, "flexflow_tpu"),
             os.path.join(REPO, "tools")]
    files = [os.path.join(REPO, f) for f in os.listdir(REPO)
             if f.startswith("bench") and f.endswith(".py")]
    for root in roots:
        for dirpath, _dirs, names in os.walk(root):
            if "__pycache__" in dirpath:
                continue
            files += [os.path.join(dirpath, n) for n in names
                      if n.endswith(".py")]
    assert files
    unregistered = []
    emit_sites = 0
    for path in sorted(files):
        tree = ast.parse(open(path).read(), filename=path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit"
                    and _receiver_is_bus(node.func, path)
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            emit_sites += 1
            kind = node.args[0].value
            if kind not in EVENT_KINDS:
                unregistered.append(
                    f"{path}:{node.lineno}: emit({kind!r})")
    assert emit_sites > 20, "the sweep found implausibly few emit sites"
    assert not unregistered, (
        "emit sites with unregistered kinds (add them to "
        "obs.events.EVENT_KINDS so ffobs validate accepts the logs):\n"
        + "\n".join(unregistered))


# ---------------------------------------------------------------------------
# event-volume sampling (ISSUE 17 satellite): per-kind caps/rates for
# the serving hot-path kinds, deterministic under a seed, with exact
# suppressed counts so totals stay recoverable from the log


def test_sampling_deterministic_and_interleave_independent(tmp_path):
    """A fractional rate keeps a seeded, per-ordinal subset: the same
    (kind, seed) keeps the same ordinals regardless of how OTHER kinds
    interleave, so two runs of the same workload sample identically."""

    def kept_ordinals(interleave):
        bus = EventBus()
        path = str(tmp_path / f"s{interleave}.jsonl")
        bus.configure(path)
        bus.configure_sampling("decode.request=0.25", seed=3)
        for i in range(200):
            bus.emit("decode.request", rid=f"r{i}", phase="finish")
            if interleave:
                bus.emit("search.log", msg="noise")
        bus.close()
        evs = [json.loads(ln) for ln in open(path)]
        return [e["rid"] for e in evs if e["kind"] == "decode.request"]

    plain = kept_ordinals(0)
    noisy = kept_ordinals(1)
    assert plain == noisy
    assert 20 < len(plain) < 80  # ~25% of 200, seeded not exact


def test_sampling_cap_and_exact_suppressed_counts(tmp_path):
    """An integer spec caps a kind at its first N events; everything
    suppressed is counted exactly and rolled up as one ``obs.sampled``
    event at close — the log's totals stay reconstructible."""
    bus = EventBus()
    path = str(tmp_path / "cap.jsonl")
    bus.configure(path)
    bus.configure_sampling({"fleet.route": 10})
    for i in range(90):
        bus.emit("fleet.route", rid=f"r{i}", replica=0, slo="standard")
    bus.emit("search.log", msg="unlisted kinds are never sampled")
    assert bus.sampled_out == {"fleet.route": 80}
    bus.close()
    evs = [json.loads(ln) for ln in open(path)]
    routed = [e for e in evs if e["kind"] == "fleet.route"]
    assert len(routed) == 10
    assert [e["rid"] for e in routed] == [f"r{i}" for i in range(10)]
    assert any(e["kind"] == "search.log" for e in evs)
    rollup = [e for e in evs if e["kind"] == "obs.sampled"]
    assert len(rollup) == 1
    assert rollup[0]["counts"] == {"fleet.route": 80}


def test_sampling_keeps_summary_counts_exact(tmp_path):
    """Sampling thins the LOG, never the measurement: with
    ``decode.request`` capped at 1, the executor's request_records and
    summary still see every completion."""
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
    )

    path = str(tmp_path / "obs.jsonl")
    BUS.configure(path)
    BUS.configure_sampling("decode.request=1")
    try:

        def step(ids, table, lens):
            b = np.asarray(ids).shape[0]
            logits = np.zeros((b, 1, 7), np.float32)
            logits[:, 0, 3] = 1.0
            return logits

        ex = ContinuousBatchingExecutor(step, max_seqs=2, page_size=4,
                                        pages_per_seq=2)
        ex.run([DecodeRequest(rid=f"r{i}", prompt=[1, 2],
                              max_new_tokens=2) for i in range(4)])
        assert len(ex.request_records) == 4  # the measurement is whole
        assert ex.summary()["completed"] == 4
        BUS.close()
        evs = [json.loads(ln) for ln in open(path)]
        assert sum(e["kind"] == "decode.request" for e in evs) == 1
        rollup = [e for e in evs if e["kind"] == "obs.sampled"]
        assert rollup and rollup[0]["counts"] == {"decode.request": 3}
    finally:
        BUS.configure_sampling(None)


def test_sampling_off_keeps_disabled_emit_cheap():
    """The one-boolean contract survives the sampling knob: with no
    spec armed (the default), a disabled bus still costs one attribute
    read per emit — 100k emits well under a second."""
    bus = EventBus()
    assert bus._sample is None
    t0 = time.perf_counter()
    for _ in range(100_000):
        bus.emit("search.log", msg="x")
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, f"disabled emit too slow: {elapsed:.3f}s"


# ---------------------------------------------------------------------------
# exposition label edge cases (ISSUE 17 satellite)


def test_exposition_labeled_histogram_renders_label_blocks():
    from flexflow_tpu.obs.exposition import render_prometheus

    reg = MetricsRegistry()
    hist = reg.histogram("decode.ttft_s|replica=0,slo=interactive")
    for v in (0.01, 0.02, 0.03):
        hist.observe(v)
    reg.counter("fleet.route.total|slo=interactive").inc()
    text = render_prometheus(reg.snapshot())
    assert ('flexflow_tpu_decode_ttft_s_count'
            '{replica="0",slo="interactive"} 3') in text
    # labeled quantile lines merge the series labels with the quantile
    assert ('flexflow_tpu_decode_ttft_s'
            '{replica="0",slo="interactive",quantile="0.50"}') in text
    assert ('flexflow_tpu_fleet_route_total'
            '{slo="interactive"} 1') in text


def test_exposition_empty_registry_renders_empty():
    from flexflow_tpu.obs.exposition import render_prometheus

    assert render_prometheus(MetricsRegistry().snapshot()) == ""
    assert render_prometheus({}) == ""


def test_exposition_zero_observation_histogram():
    """A histogram that exists but never observed renders only its
    ``_count 0`` line — no NaN quantiles, no sum."""
    from flexflow_tpu.obs.exposition import render_prometheus

    text = render_prometheus(
        {"histograms": {"trace.span_s|span=queue": {"count": 0}}})
    assert text == ("# TYPE flexflow_tpu_trace_span_s summary\n"
                    'flexflow_tpu_trace_span_s_count{span="queue"} 0\n')


def test_exposition_malformed_label_suffix_keeps_series():
    from flexflow_tpu.obs.exposition import render_prometheus

    text = render_prometheus(
        {"gauges": {"slo.burn_rate|slo=": 2.5, "ok|a=b": 1.0}})
    # the malformed suffix stays part of the name; the series survives
    assert "2.5" in text and 'ok{a="b"} 1.0' in text
