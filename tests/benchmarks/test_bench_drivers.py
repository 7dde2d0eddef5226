"""The two drivers at a tiny size on the CPU: counts, token bookkeeping
and the result object's shape — never a speed.  The plain reference
(benchmarks/reference/opt_block.py) is held against the system here too:
the training loss of step 0, and the logits of decode frames after
chunked prefill through the paged cache.

Tolerances: the tiny models compute in bf16 like the cells, so the
drivers' own bounds apply (train.STEP0_LOSS_RTOL, serve.PROBE_LOGIT_ATOL,
each with its reason where it is defined); a float32-vs-bf16 slip of a
whole layer, a wrong mask or a wrong cache position moves either by far
more.
"""

import json
import os
import time

import pytest

from benchmarks.harness import result, serve, spec, traffic, train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 11  # the driver's seeds are large


def tiny_cell(config, traffic_name):
    bdir = os.path.join(ROOT, "benchmarks")
    return spec.Cell(
        name=f"{config}.{traffic_name}", chips=1,
        config=spec.load_json(os.path.join(bdir, "configs", config + ".json")),
        traffic=spec.load_json(os.path.join(bdir, "traffic",
                                            traffic_name + ".json")),
        end_to_end=[], per_layer=[], run_seconds=1)


@pytest.fixture(scope="module")
def train_out():
    return train.run(tiny_cell("tiny-train", "tiny-train"), SEED, 0.3, False,
                     time.perf_counter(), log=lambda *_: None)


def test_train_driver_counts_whole_epochs(train_out):
    facts = train_out["facts"]
    epochs = facts["epoch_seconds"]
    assert len(epochs) >= 2
    assert train_out["attempted"] == len(epochs) * facts["steps_per_epoch"]
    tokens = len(epochs) * 3 * 2 * 128  # epochs x steps x batch x seq
    assert train_out["end_to_end"]["train_tokens_per_s"] == pytest.approx(
        tokens / sum(epochs))
    assert train_out["end_to_end"]["setup_s"] > 0
    assert train_out["failed"] == 0


def test_train_reference_agrees_and_checks_pass(train_out):
    checks = train_out["facts"]["checks"]
    assert checks["step0_loss_equals_reference"]
    assert checks["losses_finite"] and checks["loss_fell"]
    assert train_out["correct"]
    gap = train_out["compared"]["step0_loss_rel_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == train.STEP0_LOSS_RTOL


def test_train_driver_prices_the_window_by_the_work_module(train_out):
    facts = train_out["facts"]
    config = tiny_cell("tiny-train", "tiny-train").config
    per_token = spec.resolve_module(config["work"]).trained_token_flops(
        config, 128)
    tokens = len(facts["epoch_seconds"]) * 3 * 2 * 128
    assert facts["window_flops"] == tokens * per_token
    assert facts["window_s"] == pytest.approx(sum(facts["epoch_seconds"]))


def test_epoch_clock_opens_and_closes_the_window():
    clock = train.EpochClock(seconds=0.0, warmup_epochs=2)
    assert clock.on_epoch_end(0, {"loss": 3.0}) is None   # warm-up
    assert clock.t_start is None
    assert clock.on_epoch_end(1, {"loss": 2.0}) is False  # window opens; 0 s
    assert clock.t_start == clock.stamps[1]
    clock.seconds = 3600.0
    assert clock.on_epoch_end(2, {"loss": 1.0}) is None
    assert len(clock.window_epochs()) == 1 and clock.losses == [3.0, 2.0, 1.0]


@pytest.fixture(scope="module")
def serve_out():
    return serve.run(tiny_cell("tiny-serve", "tiny-closed"), SEED, 0.5,
                     False, time.perf_counter(), log=lambda *_: None)


def test_serve_driver_closed_loop(serve_out):
    assert serve_out["attempted"] >= 6 and serve_out["failed"] == 0
    assert set(serve_out["end_to_end"]) == {
        "serve_tokens_per_s", "setup_s", "itl_p95_ms", "ttft_p95_ms"}
    assert all(v > 0 for v in serve_out["end_to_end"].values())
    assert len(serve_out["facts"]["window_frame_seconds"]) > 0
    assert serve_out["facts"]["pool_itemsize"] == 4


def test_serve_reference_agrees_and_checks_pass(serve_out):
    checks = serve_out["facts"]["checks"]
    assert checks["logits_equal_reference"] and checks["tokens"]
    assert checks["every_finished_request_has_its_tokens"]
    assert serve_out["correct"]
    assert set(serve_out["compared"]) == {
        "probe_logit_gap", "probe_token_under_best",
        "mosaic_calls_in_frame", "requests_short_of_their_tokens"}
    gap = serve_out["compared"]["probe_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == serve.PROBE_LOGIT_ATOL


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The timed path broken underneath: the executor hands every slot
    the token NEXT to the one its logits put first.  The frames' logits
    still follow the altered stream, so only the tokens tell."""
    from flexflow_tpu.runtime.decode import ContinuousBatchingExecutor

    harvest = ContinuousBatchingExecutor._harvest

    def altered(self, logits, active, now, tr):
        tokens = (logits[:, 0].argmax(axis=-1) + 1) % logits.shape[-1]
        return harvest(self, tokens[:, None].astype("int32"), active, now,
                       tr)

    monkeypatch.setattr(ContinuousBatchingExecutor, "_harvest", altered)
    out = serve.run(tiny_cell("tiny-serve", "tiny-closed"), SEED, 0.3,
                    False, time.perf_counter(), log=lambda *_: None)
    checks = out["facts"]["checks"]
    assert checks["logits_equal_reference"]           # the frames are fine
    assert not checks["tokens_are_the_references_best"]
    assert not out["correct"]
    gap = out["compared"]["probe_token_under_best"]
    assert gap["value"] > 5 * gap["limit"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    """Adam at step size 0: every step returns the parameters it got, the
    step-0 loss still equals the reference's, and the loss never falls."""
    cell = tiny_cell("tiny-train", "tiny-train")
    cell.config = dict(cell.config, optimizer={"type": "adam", "alpha": 0.0})
    out = train.run(cell, SEED, 0.2, False, time.perf_counter(),
                    log=lambda *_: None)
    checks = out["facts"]["checks"]
    assert checks["step0_loss_equals_reference"] and not checks["loss_fell"]
    assert not out["correct"]


def test_serve_driver_open_loop_bursts_and_shared_prefixes():
    out = serve.run(tiny_cell("tiny-serve", "tiny-open"), SEED, 0.5, False,
                    time.perf_counter(), log=lambda *_: None)
    assert out["correct"] and out["attempted"] >= 1
    assert out["end_to_end"]["ttft_p95_ms"] > 0


class FakeLive:
    def __init__(self, rid, generated):
        self.req = traffic.Request(rid, [1], 0)
        self.generated = generated


class FakeExecutor:
    def __init__(self):
        self.slots = [None, None]
        self.finished = {}


def test_ledger_stamps_tokens_gaps_and_ttft_from_issue():
    ledger, ex = serve.Ledger(), FakeExecutor()
    a = traffic.Request("a", [1, 2], 3)
    b = traffic.Request("b", [1], 2)
    ledger.issue(a, 9.0)    # before the window opens at 10.0
    ledger.issue(b, 10.5)
    inflight = {"a", "b"}
    ex.slots = [FakeLive("a", 1), None]
    assert ledger.harvest(ex, inflight, 9.5) == []        # a: token 1
    ex.slots = [FakeLive("a", 2), FakeLive("b", 1)]
    assert ledger.harvest(ex, inflight, 11.0) == []       # a: 2, b: 1
    ex.slots = [None, None]                                # both evicted
    ex.finished = {"a": [7, 7, 7], "b": [7]}               # b one short
    assert sorted(ledger.harvest(ex, inflight, 12.0)) == ["a", "b"]
    assert ledger.tokens == {"a": [9.5, 11.0, 12.0], "b": [11.0]}

    w = ledger.window(10.0, 12.0)
    assert w["tokens"] == 3            # 9.5 fell before the window
    assert w["gaps"] == [1.0]          # a: 11 -> 12; 9.5 -> 11 starts outside
    assert w["ttft"] == [0.5]          # b only: a was issued before 10.0
    assert w["completed"] == 2 and w["short"] == 1
    # a window that closes earlier sees neither completion
    assert ledger.window(10.0, 11.5)["completed"] == 0


def test_every_seed_gets_the_same_lengths_in_another_order():
    mix = spec.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                      "closed16-decode-heavy.json"))
    pool = traffic.length_pool(mix)
    assert len(pool) == mix["pool"] == 32
    assert all(32 <= p <= 256 and 128 <= n <= 384 for p, n in pool)

    def first(seed):
        stream = traffic.requests(mix, 1000, seed)
        return [next(stream) for _ in range(32)]

    one, two = first(1), first(SEED)
    lengths = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]
    assert sorted(lengths(one)) == sorted(lengths(two)) == sorted(pool)
    assert lengths(one) != lengths(two)
    assert lengths(first(1)) == lengths(one)              # same seed, same
    assert first(1)[0].prompt == one[0].prompt
    assert all(1 <= t < 1000 for t in one[0].prompt)


def test_a_fixed_order_gives_every_seed_the_same_sequence():
    """``"order": "fixed"`` (the prefill-heavy mix: so few requests fit a
    window that their order alone moved the throughput): lengths,
    sessions and arrival times come from the file's ``order_seed``, the
    run's seed draws only the token ids."""
    mix = spec.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                      "closed16-prefill-heavy.json"))
    assert mix["order"] == "fixed"

    def first(seed, mix=mix, n=150):   # more than two passes of the pool
        stream = traffic.requests(mix, 1000, seed)
        return [next(stream) for _ in range(n)]

    one, two = first(1), first(SEED)
    lengths = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]
    assert lengths(one) == lengths(two)
    assert sorted(lengths(one)[:64]) == sorted(traffic.length_pool(mix))
    assert lengths(one)[:64] != lengths(one)[64:128]      # passes reshuffle
    assert one[0].prompt != two[0].prompt                 # ids: the seed's
    other = dict(mix, order_seed=mix["order_seed"] + 1)
    assert lengths(first(1, other)) != lengths(one)
    # sessions and arrivals follow the order too
    tiny = dict(spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "tiny-open.json")), order="fixed")
    a, b = first(1, tiny, 40), first(SEED, tiny, 40)
    assert lengths(a) == lengths(b)
    assert [r.prompt[:8] == a[0].prompt[:8] for r in a] == \
        [r.prompt[:8] == b[0].prompt[:8] for r in b]
    due = lambda seed: [t for t, _ in zip(traffic.arrivals(tiny, seed),
                                          range(40))]
    assert due(1) == due(SEED)
    with pytest.raises(ValueError):
        next(traffic.requests(dict(mix, order="shuffled"), 1000, 1))


def test_burst_arrivals_and_shared_prefixes():
    mix = spec.load_json(os.path.join(ROOT, "benchmarks", "traffic",
                                      "tiny-open.json"))
    due = traffic.arrivals(mix, 3)
    times = [next(due) for _ in range(200)]
    assert times == sorted(times)
    period = mix["burst_period_s"]
    assert all(int(t // period) % 2 == 0 for t in times)  # on-periods only
    stream = traffic.requests(mix, 128, 3)
    prompts = [next(stream).prompt for _ in range(20)]
    assert len({tuple(p[:8]) for p in prompts}) <= mix["sessions"]


def test_result_line_is_the_contracts_object():
    line = result.result_line(
        correct=True, attempted=4, failed=0,
        values={"setup_s": 1.25}, units={"setup_s": "s"},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0},
        breakdown={"device_ops": [["a", 0.1]], "idle_gaps": []})
    obj = json.loads(line)
    assert "\n" not in line
    assert set(obj) == {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown"}
    assert obj["metrics"] == {"setup_s": {"value": 1.25, "unit": "s"}}
    compared = {"gap": {"value": 0.5, "limit": 1.0}}
    last = result.result_line(
        correct=True, attempted=1, failed=0, values={}, units={}, device={},
        breakdown={"device_ops": [], "idle_gaps": []}, compared=compared)
    assert list(json.loads(last))[-1] == "compared"   # the line's end
    assert json.loads(last)["compared"] == compared
    assert result.compared_lines(compared) == "[compared] gap = 0.5 (limit 1.0)"
    plain = json.loads(result.result_line(
        correct=False, attempted=0, failed=0, values={}, units={},
        device={}))
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device"}
