"""The Ouro-2.6B configuration in the benchmark: its file against the
published config (a cut of the loop count or of a width is refused), its
``work`` module by numbers worked out by hand, the lists of
``BENCHMARK.json`` its cell joins, and a tiny preset of the same builder
through the training driver on the CPU — ``correct`` against the plain
reference (benchmarks/reference/ouro.py) included, and every per-layer
metric of the cell that a CPU can give.

The two looped-stack ratios (``train.loop_expected_exit_step``,
``train.loop_exit_entropy``; ``LOOP_RATIOS`` reads their files) are
per-layer metrics of the cell since PR 34, over the ``loop.*`` device
counters the exit objective publishes."""

import copy
import os
import time

import pytest

from benchmarks.harness import program_readers, spec, train
from flexflow_tpu.obs.metrics import METRICS, MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
CELL = "ouro-2.6b.train-seq4096"
LOOP_METRICS = ("train.loop_expected_exit_step", "train.loop_exit_entropy")
# what each of the two metrics' files hands ``program_readers:ratio``
LOOP_RATIOS = {
    name: spec.load_json(os.path.join(BDIR, "layer_metrics",
                                      name + ".json"))["args"]
    for name in LOOP_METRICS}
# the lists the cell joined in PR 32; later PRs add to them, none leaves
JOINED_IN_PR32 = {
    "train_tokens_per_s", "train.step_ms", "train.device_idle_share",
    "train.flash_time_share", "train.flash_roofline_share",
    "train.data_ms_per_step", "train.dispatch_ms_per_step", "train.mfu",
    "setup.phase_s.native_build", "setup.phase_s.search",
    "setup.phase_s.lower", "setup.jax_compile_requests",
    "setup.jax_compile_s", "setup.jax_cache_misses"}
SEED = 2 ** 31 + 13  # the driver's seeds are large


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(BDIR, "configs", "ouro-2.6b-train.json"))


def test_every_width_and_the_loop_count_are_published_and_only_depth_is_cut(
        config):
    spec.check_against_source(config)
    pub, kw = config["published"], config["builder_kwargs"]
    assert sorted(config["reduced"]) == ["layer_types", "num_hidden_layers"]
    entry = next(c for c in spec.load_benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    # the widths the issue names, as published; the loop count with them
    assert (kw["hidden"], kw["ff_dim"], kw["num_heads"], kw["head_dim"]) == (
        2048, 5632, 16, 128)
    assert kw["loop_steps"] == pub["total_ut_steps"] == 4
    assert kw["vocab"] == pub["vocab_size"] == 49152      # held whole
    assert kw["rope_theta"] == pub["rope_theta"] == 1e6
    assert pub["num_attention_heads"] == pub["num_key_value_heads"]
    # the guide's floor on depth, the ladder's ceiling
    layers = kw["num_layers"]
    assert 4 <= layers <= 9 and pub["num_hidden_layers"] == 48
    assert config["layer_types"] == ["full_attention"] * layers
    assert config["harness"]["layers"] == layers
    # three flash kernels a layer application, and under recomputation
    # by block the forward kernel once more
    assert config["ffconfig"]["remat"] is True
    assert config["harness"]["mosaic_calls"]["train_step"] == (
        (3 + 1) * kw["loop_steps"] * layers)
    assert len(config["assumed"]) >= 5 and len(config["departures"]) >= 4


@pytest.mark.parametrize("key, value", [
    ("total_ut_steps", 2), ("hidden_size", 1024), ("intermediate_size", 2816),
    ("head_dim", 64), ("num_attention_heads", 8), ("vocab_size", 6144)])
def test_a_copy_with_the_loop_count_or_a_width_changed_is_refused(
        config, key, value):
    cut = copy.deepcopy(config)
    cut[key] = value
    with pytest.raises(spec.SpecError):
        spec.check_against_source(cut)
    # listing it under ``reduced`` does not help a width
    if key in config["widths"]:
        cut["reduced"][key] = f"published {config['published'][key]}, held {value}"
        with pytest.raises(spec.SpecError, match="a width"):
            spec.check_against_source(cut)


def test_the_cell_resolves_with_the_metrics_it_joined(config):
    cell = spec.resolve_cell(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["batch"] == 1
    assert cell.traffic["seq_len"] == config["harness"]["seq_len"] == 4096
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"train.mfu", "train.flash_roofline_share", "train.flash_time_share",
            "train.step_ms", "train.device_idle_share", "search.compile_s",
            "setup.jax_compile_requests"} <= names
    assert not any(n.startswith(("serve.", "train.moe_")) for n in names)


def test_the_cell_joins_the_training_lists_and_nothing_the_benchmark_had_moves(
        bench_root, named):
    """Every entry is found by its name, wherever it stands: on the real
    file, and on a copy to which a later PR appended a configuration, a
    cell and a metric of its own (conftest.py)."""
    bench = spec.load_benchmark(bench_root)
    held = named(bench["configs"], "ouro-2.6b-train")
    assert held == {
        "name": "ouro-2.6b-train", "why": held["why"],
        "source": "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/"
                  "config.json",
        "file": "benchmarks/configs/ouro-2.6b-train.json",
        "reduced": ["num_hidden_layers", "layer_types"]}
    entry = named(bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "ouro-2.6b-train",
                     "traffic": "train-seq4096", "chips": 1,
                     "why": entry["why"]}
    # the two looped-stack metrics are the cell's alone, each by a file
    for name in LOOP_METRICS:
        metric = named(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"], metric["source"]) == (
            "looped stack", "train_tokens_per_s", "program_counter")
        assert os.path.isfile(os.path.join(bench_root, "benchmarks",
                                           "layer_metrics", name + ".json"))
    # the cell is in every list that holds both older training cells —
    # how many there are is the file's to say — and in no serving list
    serving = {w["name"] for w in bench["workloads"]
               if w["config"] == "opt-350m-serve"}
    joined = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if {"joyai-llm-flash.train-seq4096",
                "opt-350m.train-seq2048"} <= set(cells):
            assert CELL in cells, m["name"]
            joined.add(m["name"])
        elif CELL in cells:
            assert not serving & set(cells), m["name"]
    assert JOINED_IN_PR32 <= joined


def test_the_work_module_by_hand(config):
    work = spec.resolve_module(config["work"])
    layers = config["builder_kwargs"]["num_layers"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    # T = 4 uses of every layer, of the head and of the gate
    weights = 4 * (layers * layer + 49152 * 2048 + 2048)
    causal = 3 * 4096 * 16 * (128 + 128) * 4 * layers
    assert work.trained_token_flops(config, 4096) == pytest.approx(
        6 * weights + causal, rel=1e-12)
    assert work.attention_kernel_flops(config, 1, 4096) == causal * 4096
    if layers == 4:
        assert work.trained_token_flops(config, 4096) == pytest.approx(
            8.155e9, rel=1e-3)
        # with 44 layers absent the head's four passes are a large share
        assert 6 * 4 * 49152 * 2048 / work.trained_token_flops(
            config, 4096) == pytest.approx(0.296, abs=5e-3)
    # serving: a cached layer a (loop step, layer), K and V of 16 x 128
    assert work.cached_token_bytes(config, 2) == 2 * 2048 * 4 * layers * 2
    assert work.served_token_flops(config, 100) == pytest.approx(
        2 * weights + 4 * 100 * 2048 * 4 * layers, rel=1e-12)
    assert work.served_token_flops(config, 0, logits=False) == pytest.approx(
        2 * 4 * (layers * layer + 2048), rel=1e-12)


def read(name, registry):
    return program_readers.ratio({"registry": registry.snapshot()},
                                 **LOOP_RATIOS[name])


def test_the_loop_ratios_on_hand_made_counters():
    """Three counted positions whose exit distributions expect steps
    1.5, 2.25 and 4 and hold 0.693, 1.386 and 0 nats."""
    reg = MetricsRegistry()
    reg.counter("loop.gated_tokens").inc(3)
    reg.counter("loop.exit_step_milli").inc(1500 + 2250 + 4000)
    reg.counter("loop.exit_entropy_milli").inc(693 + 1386 + 0)
    assert read("train.loop_expected_exit_step", reg) == pytest.approx(
        7.75 / 3)
    assert read("train.loop_exit_entropy", reg) == pytest.approx(2.079 / 3)
    # a program without the counters (the parent) reads nothing
    assert all(read(name, MetricsRegistry()) is None for name in LOOP_RATIOS)


@pytest.fixture(scope="module")
def tiny_out():
    METRICS.reset()
    cell = spec.Cell(
        name="tiny-ouro", chips=1,
        config=spec.load_json(os.path.join(BDIR, "configs",
                                           "tiny-ouro-train.json")),
        traffic=spec.load_json(os.path.join(BDIR, "traffic",
                                            "tiny-train.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    lines = []
    # traced, as a ``--trace 1`` run is: one more epoch under the
    # profiler, then the compiled step's name scopes among the facts
    out = train.run(cell, SEED, 0.3, True, time.perf_counter(),
                    log=lines.append)
    return cell, out, lines


def test_the_traced_tiny_run_hands_the_exit_scope_to_the_reader(tiny_out):
    """``train.exit_chain_time_share`` charges device time to the scope
    its file names; the compiled step's text has instructions under it
    and under every loop step, forward and transposed.  (No device plane
    on a CPU: the share itself is a chip's to read.)"""
    _, out, _ = tiny_out
    metric = spec.load_json(os.path.join(
        BDIR, "layer_metrics", "train.exit_chain_time_share.json"))
    assert metric["reader"] == "benchmarks.harness.readers:scope_time_share"
    assert metric["args"] == {"prefixes": ["ff.exit"]}
    facts = out["facts"]
    assert set(facts["scopes"]) == set(facts["scope_families"])
    for scope in ("ff.exit", "ff.loop1", "ff.loop2", "ff.loop3", "ff.loop4"):
        under = [s for s in facts["scopes"].values() if scope in s]
        assert under and any("transpose(" in s for s in under), scope
    assert out["trace"]["devices"] == {}


def test_the_tiny_preset_is_correct_against_the_plain_reference(tiny_out):
    cell, out, lines = tiny_out
    checks = out["facts"]["checks"]
    assert checks["step0_loss_equals_reference"], out["compared"]
    assert checks["losses_finite"] and checks["loss_fell"]
    assert out["correct"] and out["failed"] == 0
    gap = out["compared"]["step0_loss_rel_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == train.STEP0_LOSS_RTOL
    # one copy of every layer is counted: 2 layers, not 4 x 2
    kw = cell.config["builder_kwargs"]
    d, f, v = kw["hidden"], kw["ff_dim"], kw["vocab"]
    n_params = 2 * v * d + d + d + 1 + kw["num_layers"] * (
        4 * d * d + 3 * d * f + 4 * d)
    assert any(f"{n_params / 1e6:.1f} M parameters" in line for line in lines)


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


def test_the_tiny_run_gives_every_metric_of_the_cell_a_cpu_can(tiny_out):
    """The readers of the cell's program-side metrics find their spans
    and counters in the run's registry; the two loop metrics among them
    equal a hand count over the counters ``fit`` published."""
    _, out, _ = tiny_out
    # step 0, the warm-up epoch, the window's epochs and the traced one
    steps = 1 + (1 + len(out["facts"]["epoch_seconds"]) + 1) * 3
    counters = METRICS.snapshot()["counters"]
    assert counters["loop.gated_tokens"] == steps * 2 * 127
    cell = spec.resolve_cell(ROOT, CELL)
    ctx, values = {}, {}
    for metric in cell.per_layer:
        if metric["source"] in ("program_span", "program_counter"):
            values[metric["name"]] = spec.resolve_dotted(metric["reader"])(
                ctx, **metric.get("args", {}))
    assert {"train.data_ms_per_step", "train.dispatch_ms_per_step",
            "setup.phase_s.search", "setup.phase_s.lower",
            "setup.jax_compile_requests", *LOOP_METRICS} <= set(values)
    # native_build and the jax_* cache metrics depend on the process
    # (a library already loaded, a cache that is off in tests)
    for name, value in values.items():
        if name.startswith(("train.", "setup.phase_s.search",
                            "setup.phase_s.lower")):
            assert value is not None and value >= 0, name
    assert values["train.loop_expected_exit_step"] == pytest.approx(
        counters["loop.exit_step_milli"] / counters["loop.gated_tokens"] / 1e3)
    assert values["train.loop_exit_entropy"] == pytest.approx(
        counters["loop.exit_entropy_milli"] / counters["loop.gated_tokens"]
        / 1e3)
    assert 1.0 < values["train.loop_expected_exit_step"] < 4.0
    assert 0.0 < values["train.loop_exit_entropy"] < 1.3863
    gauges = METRICS.snapshot()["gauges"]
    assert all(gauges[f"fit.exit_loss.{t}"] > 0 for t in (1, 2, 3, 4))
    assert 0 < gauges["fit.exit_mass_last"] < 1
