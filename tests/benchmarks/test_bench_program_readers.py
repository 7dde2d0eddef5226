"""The readers of what the program measures on itself
(benchmarks/harness/program_readers.py): each on a hand-filled registry,
None on an empty one, and every metric file that names one resolves for
the cells it names."""

import json
import os

import pytest

from benchmarks.harness import program_readers as pr
from benchmarks.harness import spec
from flexflow_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODULE = "benchmarks.harness.program_readers:"


def ctx_of(registry: MetricsRegistry) -> dict:
    return {"registry": registry.snapshot()}


@pytest.fixture
def filled():
    reg = MetricsRegistry()
    for wait, step in ((0.030, 0.040), (0.034, 0.040), (0.032, 0.040)):
        reg.histogram("serve.wait_s").observe(wait)
        reg.histogram("serve.step_s").observe(step)
    reg.counter("decode.active_slot_frames").inc(30)
    reg.counter("decode.slot_frames").inc(48)
    reg.counter("decode.frames").inc(3)
    reg.counter("decode.prefill_tokens")  # exists, reads 0
    reg.histogram("setup.lower_s").observe(1.5)
    reg.histogram("setup.init_params_s").observe(0.25)
    reg.histogram("serve.evict_s")  # exists, no sample
    return ctx_of(reg)


def test_hist_reads_one_statistic_scaled(filled):
    assert pr.hist(filled, "serve.wait_s", "p50", 1e3) == pytest.approx(32.0)
    assert pr.hist(filled, "serve.wait_s", "max") == pytest.approx(0.034)
    assert pr.hist(filled, "serve.wait_s", "count") == 3
    assert pr.hist(filled, "serve.step_s", "sum") == pytest.approx(0.12)


def test_ratio_and_its_complement(filled):
    assert pr.ratio(filled, "decode.active_slot_frames",
                    "decode.slot_frames", 100.0) == pytest.approx(62.5)
    # histogram sums divide like counters: 1 - 0.096 / 0.120
    assert pr.one_minus_ratio(filled, "serve.wait_s", "serve.step_s",
                              100.0) == pytest.approx(20.0)
    # a numerator that counted nothing is 0, not missing
    assert pr.ratio(filled, "decode.prefill_tokens", "decode.frames") == 0.0


def test_mean_outside_is_a_spans_mean_less_its_childs(filled):
    # three steps of 40 ms of which the host waited 30, 34 and 32
    assert pr.mean_outside(filled, "serve.step_s", "serve.wait_s",
                           1e3) == pytest.approx((120 - 96) / 3)
    assert pr.mean_outside(filled, "serve.step_s", "serve.evict_s") is None
    assert pr.mean_outside(filled, "no.such_s", "serve.wait_s") is None
    assert pr.mean_outside(ctx_of(MetricsRegistry()), "serve.step_s",
                           "serve.wait_s") is None


def test_total_adds_counters_and_histogram_sums(filled):
    assert pr.total(filled, ["setup.lower_s", "setup.init_params_s"]) \
        == pytest.approx(1.75)
    assert pr.total(filled, ["decode.frames"]) == 3
    assert pr.total(filled, ["decode.prefill_tokens"]) == 0


@pytest.mark.parametrize("call,on_filled", [
    (lambda c: pr.hist(c, "serve.wait_s", "p50"), "a number"),
    (lambda c: pr.hist(c, "serve.evict_s", "p50"), None),  # no sample
    (lambda c: pr.total(c, ["jax.compile_requests"]), None),
    (lambda c: pr.total(c, ["setup.lower_s", "no.such_s"]), None),
    (lambda c: pr.ratio(c, "decode.prefill_tokens", "no.such"), None),
    (lambda c: pr.ratio(c, "no.such", "decode.frames"), None),
    (lambda c: pr.one_minus_ratio(c, "serve.wait_s", "no.such_s"), None),
])
def test_nothing_to_read_is_none_not_an_error(call, on_filled, filled):
    """An older program has no such span or counter: the harness leaves
    the metric out of the line."""
    assert call(ctx_of(MetricsRegistry())) is None
    assert (call(filled) is None) == (on_filled is None)


def test_a_zero_denominator_is_none(filled):
    reg = MetricsRegistry()
    reg.counter("decode.frames")
    reg.counter("decode.prefill_tokens").inc(5)
    assert pr.ratio(ctx_of(reg), "decode.prefill_tokens",
                    "decode.frames") is None


def test_the_live_registry_is_snapshotted_once_a_run():
    from flexflow_tpu.obs.metrics import METRICS

    ctx = {}
    METRICS.counter("t.readers.live").inc(2)
    try:
        assert pr.total(ctx, ["t.readers.live"]) == 2
        METRICS.counter("t.readers.live").inc(5)
        assert pr.total(ctx, ["t.readers.live"]) == 2  # the same snapshot
    finally:
        METRICS.counter("t.readers.live").value = 0


def program_metric_files():
    folder = os.path.join(ROOT, "benchmarks", "layer_metrics")
    out = []
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            metric = json.load(f)
        if metric["reader"].startswith(MODULE):
            out.append(metric)
    return out


def test_the_metrics_that_read_the_program_are_those_whose_source_says_so():
    """Not a count: the files whose reader is in ``program_readers`` are
    exactly the ``per_layer`` entries that give a span or a counter of
    the program as their source, so a later PR adds one by a file and an
    entry."""
    bench = spec.load_benchmark(ROOT)
    by_source = {m["name"] for m in bench["per_layer"]
                 if m["source"] in ("program_span", "program_counter")}
    by_reader = {m["name"] for m in program_metric_files()}
    # ``serve.frame_ms_p50`` reads ``ex.frame_seconds``, a list the
    # executor keeps, through the driver: a counter of the program's that
    # is not in its registry
    assert by_source - by_reader == {"serve.frame_ms_p50"}
    assert by_reader <= by_source


@pytest.mark.parametrize("metric", program_metric_files(),
                         ids=lambda m: m["name"])
def test_a_program_metric_resolves_for_its_cells_and_reads(metric):
    bench = spec.load_benchmark(ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric["name"])
    assert entry["source"] in ("program_span", "program_counter")
    assert entry["source"] == metric["source"]
    assert "Whole process" in metric["reads"]
    reader = spec.resolve_dotted(metric["reader"])
    for workload in entry["workloads"]:
        cell = spec.resolve_cell(ROOT, workload)
        assert metric["name"] in [m["name"] for m in cell.per_layer]
    # on a registry that holds what the file names, the reader gives a
    # number; on an empty one, None
    reg = MetricsRegistry()
    args = metric["args"]
    names = args.get("names") or [
        args[k] for k in ("name", "num", "den", "whole", "part") if k in args]
    for name in names:
        if name.endswith("_s"):
            reg.histogram(name).observe(0.5)
        else:
            reg.counter(name).inc(4)
    assert isinstance(reader(ctx_of(reg), **args), (int, float))
    assert reader(ctx_of(MetricsRegistry()), **args) is None
