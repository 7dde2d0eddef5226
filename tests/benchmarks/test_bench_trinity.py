"""The Trinity-Large-Preview serving configuration in the benchmark: its
file against the published config, its ``work`` module by the numbers
worked out by hand (4,322 M parameters held; 4,096 B a cached token a
layer), every entry this PR appended found BY NAME, the two readers of
``harness/window_readers.py`` on hand-made contexts, and the tiny preset
of the same builder through the serving driver on the CPU — ``correct``
against the plain reference (benchmarks/reference/trinity.py) with a
probe past the window and the ring's wrap."""

import copy
import os
import time

import pytest

from benchmarks.harness import program_readers, serve, spec, window_readers
from flexflow_tpu.obs.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
CONFIG = "trinity-large-preview-serve"
CELL = "trinity-large-preview.serve-longctx-decode"
NEW_METRICS = {
    "serve.window_paged_roofline_share": "kernels",
    "serve.kv_pages_skipped_share": "kernels",
    "serve.moe_experts_touched_share": "expert layer",
    "serve.moe_dropped_share": "expert layer",
    "serve.moe_frame_time_share": "expert layer"}
JOINED = {
    "serve_tokens_per_s", "setup.phase_s.native_build",
    "setup.phase_s.search", "setup.phase_s.lower",
    "setup.jax_compile_requests", "setup.jax_compile_s",
    "setup.jax_cache_misses", "serve.frame_ms_p50",
    "serve.device_idle_share", "serve.prefill_device_share",
    "serve.phase_ms_p50.admit", "serve.phase_ms_p50.dispatch",
    "serve.phase_ms_p50.wait", "serve.phase_ms_p50.harvest",
    "serve.frame_occupancy", "serve.frames_ahead_share",
    "serve.rows_dropped_share", "serve.host_ms_per_step", "serve.mfu",
    "serve.prefill_chunk_ms_p50", "serve.prefill_tokens_per_frame",
    # the admission's stall is this cell's first bottleneck (PERF.md 5)
    "serve.ttft_p50_ms", "serve.ttft_p95_ms"}
SEED = 2 ** 31 + 17  # the driver's seeds are large


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(BDIR, "configs", CONFIG + ".json"))


def test_every_width_is_published_and_six_keys_are_cut(config):
    spec.check_against_source(config)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size", "max_position_embeddings"}
    for key in config["widths"]:
        assert config[key] == config["published"][key], key
    assert {"sliding_window", "route_scale", "num_experts_per_tok",
            "head_dim", "num_key_value_heads"} <= set(config["widths"])
    # published layers 0, 6, 7, 8, 9: a dense layer, then one period
    layers = config["published"]["layer_types"]
    assert config["layer_types"] == [layers[i] for i in (0, 6, 7, 8, 9)]
    kw = config["builder_kwargs"]
    assert kw["n_routed_experts"] == 256 and kw["experts_held"] == 32
    assert config["ffconfig"]["param_dtype"] == "bfloat16"
    assert config["probe"]["prompt_tokens"] > kw["window"] + kw[
        "prefill_chunk"] + kw["page_size"]  # past the window and the wrap


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 2048), ("num_experts_per_tok", 2), ("head_dim", 64),
    ("moe_intermediate_size", 1024), ("hidden_size", 2048)])
def test_a_copy_with_a_width_changed_is_refused(config, key, value):
    broken = copy.deepcopy(config)
    broken[key] = value
    with pytest.raises(spec.SpecError):
        spec.check_against_source(broken)


def test_the_work_module_by_hand(config):
    work = spec.resolve_module(config["work"])
    attention = 3072 * (3 * 6144 + 2 * 1024)            # 62.9 M
    expert = 3 * 3072 * 3072                            # 28.3 M
    dense = attention + 3 * 3072 * 12288                # 176.2 M
    moe = attention + 3072 * 256 + expert + 32 * expert
    held = dense + 4 * moe + 2 * 25024 * 3072
    assert work.held_parameters(config) == held
    assert round(held / 1e6) == 4322
    assert 2 * held < 8.65e9                            # bf16, held once
    # K and V of one cached token: 2 x 8 x 128 x 2 B a layer, 5 layers
    assert work.cached_token_bytes(config, 2) == 5 * 4096
    # a served token behind 9,000 cached: the routed experts at the
    # share's expectation 4 x 32 / 256, attention over min(context, 4096)
    # in the four window layers and over all 9,000 in the global one
    multiplied = (5 * attention + 3 * 3072 * 12288
                  + 4 * (3072 * 256 + expert + 0.5 * expert))
    seen = 9000 + 4 * 4096
    assert work.served_token_flops(config, 9000, logits=False) == \
        pytest.approx(2 * multiplied + 4 * seen * 48 * 128)
    assert work.served_token_flops(config, 9000) == pytest.approx(
        2 * (multiplied + 3072 * 25024) + 4 * seen * 48 * 128)
    assert work.served_token_flops(config, 100) == pytest.approx(
        2 * (multiplied + 3072 * 25024) + 4 * 500 * 48 * 128)
    # the kernels' bytes: rows of 100 and 9,000 cached tokens
    assert work.attention_kernel_bytes(config, [100, 9000], 2) == \
        (5 * 100 + 9000 + 4 * 4096) * 4096


def test_every_new_entry_is_found_by_name(named):
    bench = spec.load_benchmark(ROOT)
    entry = named(bench["configs"], CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    config = spec.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["reduced"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    cell = named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed16-longctx-decode", 1)
    for name, layer in NEW_METRICS.items():
        metric = named(bench["per_layer"], name)
        assert metric["workloads"] == [CELL] and metric["layer"] == layer
        assert metric["moves"] == "serve_tokens_per_s"
    for name in JOINED:
        lists = bench["end_to_end"] + bench["per_layer"]
        assert CELL in named(lists, name)["workloads"], name
    # one constant a cached token would read past 100 % here
    for name in ("serve.ragged_roofline_share", "serve.live_page_share",
                 "itl_p95_ms"):
        lists = bench["end_to_end"] + bench["per_layer"]
        assert CELL not in named(lists, name)["workloads"], name


def test_the_cell_resolves_with_its_metrics_and_traffic():
    cell = spec.resolve_cell(ROOT, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names and (JOINED - {"serve_tokens_per_s"}
                                          ) <= names
    traffic = cell.traffic
    assert traffic["clients"] == cell.config["slots"] == 16
    assert traffic["order"] == "fixed"
    from benchmarks.harness import traffic as gen
    # ISSUE 35's lengths: prompts 4,096-12,288 log-uniform, answers
    # 768-1,536 uniform, 32 pairs; ragged on purpose (rows below, at and
    # above the window in one frame); the longest fits the context
    assert traffic["prompt_tokens"] == {"dist": "log_uniform", "lo": 4096,
                                        "hi": 12288}
    assert traffic["max_new_tokens"] == {"dist": "uniform", "lo": 768,
                                         "hi": 1536}
    pool = gen.length_pool(traffic)
    assert len(pool) == 32 and len(set(pool)) == 32
    assert min(p for p, _ in pool) >= 4096 and max(
        p + n for p, n in pool) <= 13824 <= cell.config["harness"]["context"]


def ctx_of(facts, trace, config=None):
    cell = spec.Cell(name="handmade", chips=1, config=config or {},
                     traffic={}, end_to_end=[], per_layer=[], run_seconds=1)
    return {"cell": cell, "facts": facts, "trace": trace,
            "device_kind": "TPU v5 lite"}


def test_window_roofline_reader_on_a_hand_made_trace(config):
    ops = [("grouped_paged_attention.3", 0.0, 0.001),
           ("grouped_paged_attention.4", 0.002, 0.001),
           ("fusion.1", 0.004, 0.5)]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}
    facts = {"traced_live_seq_lens": [9000, 100], "pool_itemsize": 2}
    need = (5 * 100 + 9000 + 4 * 4096) * 4096
    got = window_readers.window_paged_roofline_share(
        ctx_of(facts, trace, config))
    assert got == pytest.approx(need / 0.002 / 819e9 * 100.0)
    # nothing to read: no traced lengths, no such kernel, a work module
    # without the function (an older program's configuration)
    assert window_readers.window_paged_roofline_share(
        ctx_of({"pool_itemsize": 2}, trace, config)) is None
    silent = {"devices": {"d": {"ops": ops[2:], "modules": []}}}
    assert window_readers.window_paged_roofline_share(
        ctx_of(facts, silent, config)) is None
    opt = spec.load_json(os.path.join(BDIR, "configs", "opt-350m-serve.json"))
    assert window_readers.window_paged_roofline_share(
        ctx_of(facts, trace, opt)) is None


def test_frame_scope_reader_reads_the_frames_instructions_only():
    modules = [("jit_frame(1)", 0.0, 1.0), ("jit_fwd(2)", 1.0, 1.0),
               ("jit_frame(1)", 2.0, 1.0)]
    ops = [("fusion.1", 0.1, 0.2),        # frame: under ff.moe.experts
           ("ragged-dot.2", 0.4, 0.1),    # frame: XLA's own kernel
           ("fusion.9", 0.6, 0.3),        # frame: attention
           ("fusion.1", 1.1, 0.7),        # the CHUNK's fusion.1: not read
           ("fusion.1", 2.1, 0.2), ("fusion.9", 2.5, 0.2)]
    trace = {"devices": {"d": {"ops": ops, "modules": modules}},
             "families": {"fusion.1": "fusion", "ragged-dot.2": "ragged-dot",
                          "fusion.9": "fusion"}}
    facts = {"scopes": {"fusion.1": "jit(frame)/ff.moe.experts/dot",
                        "ragged-dot.2": "ragged-dot-none",
                        "fusion.9": "jit(frame)/ff.attn.window/mul"},
             "scope_families": dict(trace["families"])}
    got = window_readers.frame_scope_time_share(
        ctx_of(facts, trace), ["ff.moe.", "ragged-dot"])
    assert got == pytest.approx((0.2 + 0.1 + 0.2) / 1.0 * 100.0)
    assert window_readers.frame_scope_time_share(
        ctx_of(facts, trace), ["ff.attn."]) == pytest.approx(50.0)
    assert window_readers.frame_scope_time_share(
        ctx_of({}, trace), ["ff.moe."]) is None
    assert window_readers.frame_scope_time_share(
        ctx_of(facts, trace), ["ff.nothing"]) is None
    unplaced = dict(facts, scopes={"fusion.1": "ff.moe.experts"})
    assert window_readers.frame_scope_time_share(
        ctx_of(unplaced, trace), ["ff.moe."]) is None
    # a tail that OPENS inside an admission: the trace keeps the first
    # event's family of a name, the chunk's; the frame's ops are still
    # the frame's (seen on the chip: ragged admissions, the metric None)
    opens_in_a_chunk = dict(trace, families=dict(
        trace["families"], **{"fusion.1": "convolution"}))
    assert window_readers.frame_scope_time_share(
        ctx_of(facts, opens_in_a_chunk), ["ff.moe.", "ragged-dot"]
    ) == pytest.approx(got)


def test_the_counter_metrics_on_hand_made_counters():
    args = {name: spec.load_json(os.path.join(
        BDIR, "layer_metrics", name + ".json")) for name in NEW_METRICS}
    registry = {"counters": {"decode.kv_pages_walked": 450,
                             "decode.kv_pages_live": 705,
                             "moe.experts_touched": 7, "moe.experts_held": 32,
                             "moe.assignments_dropped": 0,
                             "moe.assignments": 8}}
    ctx = {"registry": registry}

    def read(name):
        m = args[name]
        return spec.resolve_dotted(m["reader"])(ctx, **m["args"])

    assert read("serve.kv_pages_skipped_share") == pytest.approx(
        (1 - 450 / 705) * 100)
    assert read("serve.moe_experts_touched_share") == pytest.approx(21.875)
    assert read("serve.moe_dropped_share") == 0.0
    empty = {"registry": {"counters": {}}}
    assert all(spec.resolve_dotted(m["reader"])(empty, **m["args"]) is None
               for m in args.values() if m["source"] == "program_counter")
    assert program_readers.ratio(ctx, "moe.experts_touched",
                                 "moe.experts_held") == 7 / 32


# ---- the tiny preset through the serving driver ----------------------------
@pytest.fixture(scope="module")
def tiny_out():
    bdir = BDIR
    cell = spec.Cell(
        name="tiny-trinity-serve.tiny-closed", chips=1,
        config=spec.load_json(os.path.join(bdir, "configs",
                                           "tiny-trinity-serve.json")),
        traffic=spec.load_json(os.path.join(bdir, "traffic",
                                            "tiny-closed.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    spec.check_against_source(cell.config)
    walked = METRICS.snapshot()["counters"].get("decode.kv_pages_walked", 0)
    out = serve.run(cell, SEED, 0.5, False, time.perf_counter(),
                    log=lambda *_: None)
    out["walked_before"] = walked
    return out


def test_the_tiny_preset_is_correct_against_the_plain_reference(tiny_out):
    checks = tiny_out["facts"]["checks"]
    assert checks["logits_equal_reference"] and checks["tokens"]
    assert checks["tokens_are_the_references_best"]
    assert checks["attention_path"] and checks["nothing_expired"]
    assert tiny_out["correct"] and tiny_out["failed"] == 0
    gap = tiny_out["compared"]["probe_logit_gap"]
    assert 0 < gap["value"] <= gap["limit"] == serve.PROBE_LOGIT_ATOL


def test_the_tiny_run_fills_what_the_new_metrics_read(tiny_out):
    assert tiny_out["facts"]["pool_itemsize"] == 2
    counters = METRICS.snapshot()["counters"]
    assert counters["decode.kv_pages_walked"] > tiny_out["walked_before"]
    assert counters["decode.kv_pages_walked"] < counters[
        "decode.kv_pages_live"]
    assert counters["moe.experts_held"] > 0
    assert counters.get("moe.assignments_dropped", 0) == 0
    assert set(tiny_out["end_to_end"]) >= {"serve_tokens_per_s", "setup_s"}
    gauges = METRICS.snapshot()["gauges"]
    assert gauges["decode.weight_bytes"] == gauges[
        "decode.weight_bytes_master"]


# ---- the replay that picks the traffic file's order_seed -------------------
def test_the_order_replay_is_deterministic_and_follows_its_costs():
    """tools/replay_order.py: the benchmark's driver and the real executor
    under a virtual clock.  One order replays to the same reading, another
    order of the same requests to another, and a slower frame to fewer
    tokens — the tiny preset, a window of one virtual second."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "replay_order.py")
    loaded = importlib.util.spec_from_file_location("replay_order", path)
    replay_order = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(replay_order)
    cell = spec.Cell(
        name="tiny-trinity-serve.tiny-closed", chips=1,
        config=spec.load_json(os.path.join(BDIR, "configs",
                                           "tiny-trinity-serve.json")),
        traffic=dict(spec.load_json(os.path.join(BDIR, "traffic",
                                                 "tiny-closed.json")),
                     order="fixed"),
        end_to_end=[], per_layer=[], run_seconds=1)
    costs = (1e-3, 1e-9, 4e-3)  # a frame, a K/V byte, a chunk: seconds
    first = replay_order.replay(cell, 3, costs)
    assert first == replay_order.replay(cell, 3, costs)
    assert first["frames"] > 100 and first["chunks"] > 50
    assert first["window_s"] >= 1.0 and first["tokens"] > 0
    other = replay_order.replay(cell, 4, costs)
    assert other["tokens"] != first["tokens"]
    slow = replay_order.replay(cell, 3, costs, frame_scale=1.5)
    assert slow["tokens"] < first["tokens"]
