"""``serve.live_page_share``: the share of the frame's page slots that
the ragged kernel walks, read from the executor's own counters
(``decode.live_pages`` / ``decode.page_slots``) — on hand-made counters,
and in the tiny serving cell's run on the CPU."""

import os
import time

import pytest

from benchmarks.harness import program_readers, serve, spec
from flexflow_tpu.obs.metrics import METRICS, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def metric():
    return spec.load_json(os.path.join(
        BDIR, "layer_metrics", "serve.live_page_share.json"))


def test_live_page_share_on_hand_made_counters(metric):
    reg = MetricsRegistry()
    reg.counter("decode.live_pages").inc(128)
    reg.counter("decode.page_slots").inc(512)
    reader = spec.resolve_dotted(metric["reader"])
    assert reader is program_readers.ratio
    assert reader({"registry": reg.snapshot()}, **metric["args"]) == 25.0
    # a program older than the counters: the line leaves the metric out
    assert reader({"registry": MetricsRegistry().snapshot()},
                  **metric["args"]) is None


def test_live_page_share_is_listed_for_both_serving_cells(
        metric, bench_root, named):
    """By name, wherever the entry stands: on the real file, and on a
    copy to which a later PR appended its own (conftest.py)."""
    bench = spec.load_benchmark(bench_root)
    entry = named(bench["per_layer"], "serve.live_page_share")  # once
    serving = [w["name"] for w in bench["workloads"]
               if w["config"] == "opt-350m-serve"]
    assert entry["workloads"] == serving and len(serving) == 2
    for key in ("unit", "layer", "moves", "better", "source"):
        assert entry[key] == metric[key]
    assert (entry["layer"], entry["moves"]) == (
        "kernels", "serve_tokens_per_s")


def test_the_tiny_serving_run_counts_live_pages_and_page_slots(metric):
    before = METRICS.snapshot()["counters"]
    cell = spec.Cell(
        name="tiny-serve.tiny-closed", chips=1,
        config=spec.load_json(os.path.join(BDIR, "configs",
                                           "tiny-serve.json")),
        traffic=spec.load_json(os.path.join(BDIR, "traffic",
                                            "tiny-closed.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    out = serve.run(cell, SEED, 0.3, False, time.perf_counter(),
                    log=lambda *_: None)
    assert out["correct"]
    after = METRICS.snapshot()["counters"]
    grew = {k: after[k] - before.get(k, 0) for k in (
        "decode.live_pages", "decode.page_slots", "decode.slot_frames")}
    pages_per_seq = cell.config["harness"]["pages_per_seq"]
    # every row of every frame has all its page slots in the table and
    # walks at least one page, never more than it has
    assert grew["decode.page_slots"] == \
        grew["decode.slot_frames"] * pages_per_seq > 0
    assert grew["decode.slot_frames"] <= grew["decode.live_pages"] \
        <= grew["decode.page_slots"]
    value = program_readers.ratio({}, **metric["args"])
    assert value is not None and 100.0 / pages_per_seq <= value <= 100.0
