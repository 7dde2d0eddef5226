"""The JoyAI-LLM-Flash configuration in the benchmark: its file against
the published config and the guide's floors, its ``work`` module by
numbers worked out by hand, its three expert-layer metrics on hand-made
counters, and a tiny preset of the same builder through the training
driver on the CPU — ``correct`` against the plain reference
(benchmarks/reference/joyai_flash.py) included."""

import os
import time

import pytest

from benchmarks.harness import program_readers, spec, train
from flexflow_tpu.obs.metrics import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
CELL = "joyai-llm-flash.train-seq4096"
SEED = 2 ** 31 + 11  # the driver's seeds are large


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(BDIR, "configs",
                                       "joyai-llm-flash-train.json"))


def test_every_width_is_published_and_the_cuts_keep_the_guides_floors(config):
    spec.check_against_source(config)
    pub, kw = config["published"], config["builder_kwargs"]
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    # the widths the issue names, as published
    assert (kw["hidden"], kw["dense_ff_dim"], kw["expert_ff_dim"]) == (
        2048, 7168, 768)
    assert (kw["q_lora_rank"], kw["kv_lora_rank"]) == (1536, 512)
    assert (kw["num_heads"], kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"],
            kw["v_head_dim"]) == (32, pub["qk_head_dim"], 128) == (32, 192, 128)
    # the router keeps its published width and its experts a token
    assert kw["n_routed_experts"] == pub["n_routed_experts"] == 256
    assert kw["experts_per_token"] == pub["num_experts_per_tok"] == 8
    # floors: the leading dense layer + at least 4 expert layers, at least
    # 8 experts held, at least an eighth of the vocabulary
    assert kw["first_dense_layers"] == pub["first_k_dense_replace"] == 1
    assert kw["num_layers"] - kw["first_dense_layers"] >= 4
    assert 8 <= kw["experts_held"] == config["n_routed_experts"]
    assert kw["vocab"] * 8 >= pub["vocab_size"]
    assert kw["mtp_layers"] == pub["num_nextn_predict_layers"] == 1
    # the row bound is the CHIP's, a stated factor over the uniform
    # expectation of all held experts together, and wide enough for one
    # expert to draw every token while the others draw twice their mean
    expectation = (kw["seq_len"] * kw["experts_per_token"] * kw["experts_held"]
                   // kw["n_routed_experts"])
    assert expectation == 2048 and kw["expert_rows"] % expectation == 0
    factor = kw["expert_rows"] / expectation
    assert kw["expert_rows"] >= kw["seq_len"] + 2 * expectation * (
        kw["experts_held"] - 1) // kw["experts_held"]
    assert any(f"expert_rows {kw['expert_rows']}" in note
               and f"{factor:.1f} x" in note for note in config["assumed"])
    # Mosaic calls of the step: three flash calls an attention block
    # (dense + expert layers + MTP), and in every expert block the nine
    # grouped products (3 forward, 6 backward) XLA:TPU runs as kernels of
    # its own plus their two metadata calls
    blocks = kw["num_layers"] + kw["mtp_layers"]
    expert_blocks = blocks - kw["first_dense_layers"]
    assert config["harness"]["mosaic_calls"]["train_step"] == (
        3 * blocks + (9 + 2) * expert_blocks)


def test_the_cell_resolves_with_its_joined_and_its_new_metrics(config):
    cell = spec.resolve_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == config["harness"]["seq_len"] == 4096
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"train.mfu", "train.flash_roofline_share", "train.step_ms",
            "train.moe_dropped_share", "train.moe_slot_fill",
            "train.moe_load_max_over_mean", "search.compile_s"} <= names
    assert not any(n.startswith("serve.") for n in names)


def test_the_work_module_by_hand(config):
    work = spec.resolve_module(config["work"])
    held = config["builder_kwargs"]["experts_held"]
    attention = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576
                 + 512 * 32 * 256 + 32 * 128 * 2048)
    assert attention == 26_345_472
    expert = 3 * 2048 * 768
    # router + shared expert + the 8 * held / 256 routed experts a token meets
    expert_block = 2048 * 256 + expert + 8 * held / 256 * expert
    head = 2048 * 16160
    weights = (6 * attention + 3 * 2048 * 7168 + 5 * expert_block + 2 * head
               + 4096 * 2048)
    causal = 3 * 4096 * 32 * (192 + 128) * 6
    # a share's five routers take no gradient: forward only, 2 and not 6
    assert held < 256
    trained = 6 * weights - 4 * 5 * 2048 * 256 + causal
    assert work.trained_token_flops(config, 4096) == pytest.approx(
        trained, rel=1e-12)
    assert 4 * 5 * 2048 * 256 / trained == pytest.approx(0.004, abs=5e-4)
    assert work.attention_kernel_flops(config, 1, 4096) == causal * 4096
    if held == 16:
        assert work.trained_token_flops(config, 4096) == pytest.approx(
            2.633e9, rel=1e-3)
    # serving in the latent form: (512 + 64) numbers a token a layer
    assert work.cached_token_bytes(config, 2) == (512 + 64) * 5 * 2
    served = weights - (attention + expert_block + head + 4096 * 2048)
    assert work.served_token_flops(config, 100) == pytest.approx(
        2 * served + 2 * 100 * 32 * (2 * 512 + 64) * 5, rel=1e-12)


def ctx_of(registry):
    return {"registry": registry.snapshot()}


def test_the_expert_layer_metrics_on_hand_made_counters():
    """Two layer-steps of 16 held experts under a chip-level bound of
    2,048 rows: loads that add up to 2,000 and 2,100, the fullest expert
    150 and 300 — the second step passes the bound by 52."""
    metrics = {name: spec.load_json(os.path.join(
        BDIR, "layer_metrics", f"train.moe_{name}.json"))
        for name in ("dropped_share", "slot_fill", "load_max_over_mean")}
    reg = MetricsRegistry()
    reg.counter("moe.assignments").inc(2000 + 2100)
    reg.counter("moe.assignments_dropped").inc(52)
    reg.counter("moe.rows_filled").inc(2000 + 2100 - 52)
    reg.counter("moe.row_slots").inc(2 * 2048)
    reg.counter("moe.expert_load_max").inc(150 + 300)
    reg.counter("moe.rows_at_fullest_load").inc(16 * (150 + 300))

    def read(name, registry=reg):
        metric = metrics[name]
        assert metric["reader"] == "benchmarks.harness.program_readers:ratio"
        return program_readers.ratio(ctx_of(registry), **metric["args"])

    assert read("dropped_share") == pytest.approx(52 / 4100 * 100)
    assert read("slot_fill") == pytest.approx(4048 / 4096 * 100)
    # (150 + 300) / 2 layer-steps against a mean load of 4100 / 32
    assert read("load_max_over_mean") == pytest.approx(225 / (4100 / 32))
    # a program without the counters (the parent) reads nothing
    assert all(read(name, MetricsRegistry()) is None for name in metrics)
    # nothing dropped reads 0, not None
    reg.counter("moe.assignments_dropped").value = 0
    assert read("dropped_share") == 0.0


@pytest.fixture(scope="module")
def tiny_out():
    cell = spec.Cell(
        name="tiny-joyai", chips=1,
        config=spec.load_json(os.path.join(
            BDIR, "configs", "tiny-joyai-flash-train.json")),
        traffic=spec.load_json(os.path.join(BDIR, "traffic",
                                            "tiny-joyai-train.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    lines = []
    # traced, as a ``--trace 1`` run is: one more epoch under the
    # profiler, then the compiled step's name scopes among the facts
    out = train.run(cell, SEED, 0.3, True, time.perf_counter(),
                    log=lines.append)
    return cell, out, lines


def test_the_traced_tiny_run_hands_the_expert_scopes_to_the_reader(tiny_out):
    """``train.moe_time_share`` charges device time to the scopes its
    file names; the compiled step's text has instructions under each of
    the four, forward and — but for the router, which takes no gradient
    in a share — transposed.  (No device plane on a CPU: the share itself
    is a chip's to read.)"""
    _, out, _ = tiny_out
    metric = spec.load_json(os.path.join(
        BDIR, "layer_metrics", "train.moe_time_share.json"))
    assert metric["reader"] == "benchmarks.harness.readers:scope_time_share"
    # XLA:TPU's grouped-product kernels lose the scope (their op_name is
    # ``ragged-dot-none``): the file names them beside it
    assert metric["args"] == {"prefixes": ["ff.moe.", "ragged-dot"]}
    facts = out["facts"]
    assert set(facts["scopes"]) == set(facts["scope_families"])
    for part in ("route", "dispatch", "experts", "combine"):
        under = [s for s in facts["scopes"].values() if f"ff.moe.{part}" in s]
        assert under, part
        assert any("transpose(" in s for s in under) == (part != "route"), part
    assert out["trace"]["devices"] == {}


def test_the_tiny_preset_is_correct_against_the_plain_reference(tiny_out):
    _, out, _ = tiny_out
    checks = out["facts"]["checks"]
    assert checks["step0_loss_equals_reference"], out["compared"]
    assert checks["losses_finite"] and checks["loss_fell"]
    assert out["correct"] and out["failed"] == 0
    gap = out["compared"]["step0_loss_rel_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == train.STEP0_LOSS_RTOL


def test_the_tiny_preset_counts_epochs_and_prices_them_by_its_work_module(
        tiny_out):
    cell, out, _ = tiny_out
    facts = out["facts"]
    tokens = len(facts["epoch_seconds"]) * 3 * 2 * 128
    assert len(facts["epoch_seconds"]) >= 1
    assert out["end_to_end"]["train_tokens_per_s"] == pytest.approx(
        tokens / sum(facts["epoch_seconds"]))
    per_token = spec.resolve_module(cell.config["work"]).trained_token_flops(
        cell.config, 128)
    assert facts["window_flops"] == tokens * per_token


def test_the_tiny_run_published_the_expert_counters_and_dropped_nothing(
        tiny_out):
    """``fit`` publishes the device counters at every epoch end; the
    readers of the three metrics find them in the run's registry."""
    from flexflow_tpu.obs.metrics import METRICS

    counters = METRICS.snapshot()["counters"]
    assert counters["moe.assignments"] > 0
    assert counters["moe.rows_filled"] == counters["moe.assignments"]
    assert counters["moe.assignments_dropped"] == 0
    assert METRICS.snapshot()["gauges"]["fit.mtp_loss"] > 0
    ctx = {}
    for name in ("dropped_share", "slot_fill"):
        metric = spec.load_json(os.path.join(
            BDIR, "layer_metrics", f"train.moe_{name}.json"))
        value = program_readers.ratio(ctx, **metric["args"])
        assert value == 0.0 if name == "dropped_share" else 0 < value <= 100
