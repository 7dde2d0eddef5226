"""The Keye-VL-2.0-30B-A3B serving configuration in the benchmark: its
file against the published config, its ``work`` module by the numbers
worked out by hand (853 M parameters held; 2,176 B a cached token a
layer), every entry this configuration appended found BY NAME, the
readers of ``harness/sparse_readers.py`` on hand-made contexts, and the
tiny preset of the same builder through the serving driver on the CPU —
``correct`` against the plain reference (benchmarks/reference/keye.py)
with a probe whose queries choose among more positions than they keep."""

import os
import time

import pytest

from benchmarks.harness import program_readers, serve, sparse_readers, spec
from flexflow_tpu.obs.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BDIR = os.path.join(ROOT, "benchmarks")
CONFIG = "keye-vl-2.0-30b-a3b-serve"
CELL = "keye-vl-2.0-30b-a3b.serve-longctx-sparse"
SEED = 2 ** 31 + 43
NEW_METRICS = ("serve.sparse_keys_attended_share", "serve.indexer_time_share",
               "serve.indexer_roofline_share",
               "serve.sparse_attention_roofline_share")
CUT = ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size",
       "max_position_embeddings"]


@pytest.fixture(scope="module")
def config():
    return spec.load_json(os.path.join(BDIR, "configs", CONFIG + ".json"))


def test_the_file_is_the_published_config_but_for_the_cut(config):
    spec.check_against_source(config)
    assert sorted(config["reduced"]) == sorted(CUT)
    pub, kw = config["published"], config["builder_kwargs"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_local_experts"], config["vocab_size"],
            config["max_position_embeddings"]) == (8, 16, 16, 18992, 32768)
    assert pub["vocab_size"] // 8 == 18992
    # the indexer's widths are sa_config's, which is a width as a whole
    sa = pub["sa_config"]
    assert "sa_config" in config["widths"] and config["sa_config"] == sa
    assert (kw["index_heads"], kw["index_head_dim"], kw["index_topk"],
            kw["index_block"], kw["prefill_chunk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        sa["kv_chunk_size"], sa["q_chunk_size"])
    assert sa["indexer_num_kv_heads"] == 1  # one index key a position
    # the router keeps its published width; every layer is an expert one
    assert kw["n_routed_experts"] == pub["num_experts"] == 128
    assert pub["decoder_sparse_step"] == 1 and pub["mlp_only_layers"] == []
    assert pub["norm_topk_prob"] is True
    assert sum(pub["rope_scaling"]["mrope_section"]) == kw["head_dim"] // 2
    harness = config["harness"]
    assert harness["page_size"] * harness["pages_per_seq"] == harness[
        "context"] == config["max_position_embeddings"]
    assert "*/index_cache" in harness["kv_pools"]


def test_the_work_module_by_hand(config):
    work = spec.resolve_module(config["work"])
    # a layer: attention 2048 x (4096 + 512 + 512) + 4096 x 2048, the
    # indexer 2048 x (1024 + 64 + 16), the router 2048 x 128, 16 experts
    # of 3 x 2048 x 768; embedding and head 2 x 18,992 x 2048
    attention = 2048 * (4096 + 512 + 512) + 4096 * 2048
    indexer = 2048 * (1024 + 64 + 16)
    expert = 3 * 2048 * 768
    layer = attention + indexer + 2048 * 128 + 16 * expert
    assert layer == 96_894_976
    assert work.held_parameters(config) == 8 * layer + 2 * 18992 * 2048
    assert work.held_parameters(config) == 852_951_040
    # K and V 2 x 4 x 128, the index key 64: 2,176 B a token-layer in bf16
    assert work.cached_token_bytes(config, 2) == 8 * 2176
    # one token: 2 a multiplied weight (one expert of 16 held at the
    # share's expectation: 8 x 16 / 128), the indexer 2 x 16 x 64 against
    # every cached position, attention 4 x 4096 against min(context, 2048)
    multiplied = 8 * (attention + indexer + 2048 * 128 + expert)
    for context in (100, 9000):
        want = (2 * (multiplied + 2048 * 18992)
                + 8 * (2 * 1024 * context + 4 * 4096 * min(context, 2048)))
        assert work.served_token_flops(config, context) == pytest.approx(want)
    assert work.served_token_flops(config, 9000, logits=False) == \
        pytest.approx(work.served_token_flops(config, 9000)
                      - 2 * 2048 * 18992)
    # bytes of rows of 100 and 9,000 cached tokens: every index key, the
    # chosen rows' K and V
    assert work.index_key_bytes(config, [100, 9000], 2) == \
        9100 * 8 * 128
    assert work.selected_kv_bytes(config, [100, 9000], 2) == \
        (100 + 2048) * 8 * 2048
    assert work.attention_kernel_bytes(config, [100, 9000], 2) == \
        9100 * 8 * 128 + (100 + 2048) * 8 * 2048


def test_every_new_entry_is_found_by_name(named, config):
    bench = spec.load_benchmark(ROOT)
    entry = named(bench["configs"], CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == list(config["reduced"]) == CUT
    assert entry["source"] == config["source"]
    cell = named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed16-longctx-sparse", 1)
    for name in NEW_METRICS:
        metric = named(bench["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"]) == (
            "sparse attention", "serve_tokens_per_s")
    lists = bench["end_to_end"] + bench["per_layer"]
    assert CELL in named(lists, "serve_tokens_per_s")["workloads"]
    # the cell is in every serving list that holds the Trinity cell, but
    # for the lists that read only a window layer or a test pins to it
    trinity = "trinity-large-preview.serve-longctx-decode"
    pinned = {"serve.window_paged_roofline_share",
              "serve.kv_pages_skipped_share",
              "serve.moe_experts_touched_share", "serve.moe_dropped_share",
              "serve.moe_frame_time_share"}
    for m in lists:
        if trinity in m.get("workloads", []) and m["name"] not in pinned:
            assert CELL in m["workloads"], m["name"]
    for name in ("itl_p95_ms", "serve.live_page_share",
                 "serve.ragged_roofline_share"):
        assert CELL not in named(lists, name)["workloads"], name
    # the sparse op walks the chunk's key blocks as the grouped op does
    # (``chunk_walk``): the share of the table they cover has a reading
    assert CELL in named(lists, "serve.prefill_keys_walked_share")["workloads"]


def test_the_cell_resolves_with_its_metrics_and_traffic():
    cell = spec.resolve_cell(ROOT, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    traffic = cell.traffic
    assert traffic["clients"] == cell.config["slots"] == 16
    assert traffic["order"] == "fixed" and traffic["warmup_s"] == 20
    from benchmarks.harness import traffic as gen
    assert traffic["prompt_tokens"] == {"dist": "log_uniform", "lo": 8192,
                                        "hi": 24576}
    assert traffic["max_new_tokens"] == {"dist": "uniform", "lo": 1024,
                                         "hi": 2048}
    pool = gen.length_pool(traffic)
    assert len(pool) == 32 and len(set(pool)) == 32
    # every context passes the choice of 2,048 four times over; the
    # longest fits the context
    assert min(p for p, _ in pool) >= 4 * 2048
    assert max(p + n for p, n in pool) <= cell.config["harness"]["context"]


def ctx_of(facts, trace, config):
    cell = spec.Cell(name="handmade", chips=1, config=config, traffic={},
                     end_to_end=[], per_layer=[], run_seconds=1)
    return {"cell": cell, "facts": facts, "trace": trace,
            "device_kind": "TPU v5 lite"}


def test_the_roofline_readers_on_a_hand_made_trace(config):
    modules = [("jit_frame(1)", 0.0, 1.0), ("jit_fwd(2)", 1.0, 1.0)]
    ops = [("fusion.1", 0.1, 0.002),   # frame: the indexer's scores
           ("gather.2", 0.2, 0.004),   # frame: the chosen rows
           ("fusion.3", 0.3, 0.001),   # frame: anything else
           ("fusion.1", 1.1, 0.5)]     # the CHUNK's fusion.1: not read
    trace = {"devices": {"d": {"ops": ops, "modules": modules}}}
    facts = {"scopes": {
        "fusion.1": "jit(frame)/ff.attn.sparse/ff.sparse.index/dot",
        "gather.2": "jit(frame)/ff.attn.sparse/ff.sparse.attend/gather",
        "fusion.3": "jit(frame)/ff.moe.experts/dot"},
        "traced_live_seq_lens": [9000, 100], "pool_itemsize": 2}
    ctx = ctx_of(facts, trace, config)
    index = 9100 * 8 * 128
    chosen = (100 + 2048) * 8 * 2048
    assert sparse_readers.indexer_roofline_share(ctx) == pytest.approx(
        index / 0.002 / 819e9 * 100)
    assert sparse_readers.sparse_attention_roofline_share(ctx) == \
        pytest.approx(chosen / 0.004 / 819e9 * 100)
    # nothing to read: no scopes, no traced rows, a work module without
    # the function (another model's configuration)
    for bare in ({**facts, "scopes": {}},
                 {**facts, "traced_live_seq_lens": []}):
        assert sparse_readers.indexer_roofline_share(
            ctx_of(bare, trace, config)) is None
    trinity = spec.load_json(os.path.join(
        BDIR, "configs", "trinity-large-preview-serve.json"))
    assert sparse_readers.indexer_roofline_share(
        ctx_of(facts, trace, trinity)) is None


def test_the_counter_metric_on_hand_made_counters():
    metric = spec.load_json(os.path.join(
        BDIR, "layer_metrics", "serve.sparse_keys_attended_share.json"))
    read = spec.resolve_dotted(metric["reader"])
    ctx = {"registry": {"counters": {"sparse.keys_attended": 13,
                                     "sparse.keys_cached": 100}}}
    assert read(ctx, **metric["args"]) == pytest.approx(13.0)
    assert read({"registry": {"counters": {}}}, **metric["args"]) is None


# ---- the tiny preset through the serving driver ----------------------------
@pytest.fixture(scope="module")
def tiny_out():
    cell = spec.Cell(
        name="tiny-keye-serve.tiny-closed", chips=1,
        config=spec.load_json(os.path.join(BDIR, "configs",
                                           "tiny-keye-serve.json")),
        traffic=spec.load_json(os.path.join(BDIR, "traffic",
                                            "tiny-closed.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    spec.check_against_source(cell.config)
    before = dict(METRICS.snapshot()["counters"])
    out = serve.run(cell, SEED, 0.5, False, time.perf_counter(),
                    log=lambda *_: None)
    out["counters_before"] = before
    return out


def test_the_tiny_preset_is_correct_against_the_plain_reference(tiny_out):
    checks = tiny_out["facts"]["checks"]
    assert checks["logits_equal_reference"] and checks["tokens"]
    assert checks["tokens_are_the_references_best"]
    assert checks["attention_path"] and checks["nothing_expired"]
    assert tiny_out["correct"] and tiny_out["failed"] == 0
    gap = tiny_out["compared"]["probe_logit_gap"]
    assert 0 < gap["value"] <= gap["limit"] == serve.PROBE_LOGIT_ATOL


def test_the_tiny_run_counts_what_the_share_reads(tiny_out):
    assert tiny_out["facts"]["pool_itemsize"] == 4  # float32 pools
    before = tiny_out["counters_before"]
    after = METRICS.snapshot()["counters"]

    def counted(name):  # this run's own: the registry is the process's
        return after.get(name, 0) - before.get(name, 0)

    assert 0 < counted("sparse.keys_attended") < counted("sparse.keys_cached")
    assert set(tiny_out["end_to_end"]) >= {"serve_tokens_per_s", "setup_s"}
    # serve.prefill_keys_walked_share: the counters its entry reads
    entry = spec.load_json(os.path.join(
        BDIR, "layer_metrics", "serve.prefill_keys_walked_share.json"))
    assert program_readers.ratio({}, **entry["args"]) is not None
    args = entry["args"]
    assert 0 < counted(args["num"]) <= counted(args["den"])


def test_the_order_replay_prices_a_chunk_by_its_position():
    """tools/replay_order.py with a fourth cost — seconds a position
    behind a chunk, as this cell's chunk walks every key block behind it:
    deterministic, and dearer chunks leave fewer tokens in the window."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "replay_order.py")
    loaded = importlib.util.spec_from_file_location("replay_order", path)
    replay_order = importlib.util.module_from_spec(loaded)
    loaded.loader.exec_module(replay_order)
    cell = spec.Cell(
        name="tiny-keye-serve.tiny-closed", chips=1,
        config=spec.load_json(os.path.join(BDIR, "configs",
                                           "tiny-keye-serve.json")),
        traffic=dict(spec.load_json(os.path.join(BDIR, "traffic",
                                                 "tiny-closed.json")),
                     order="fixed"),
        end_to_end=[], per_layer=[], run_seconds=1)
    flat = (1e-3, 1e-9, 4e-3)
    by_position = flat + (2e-5,)
    first = replay_order.replay(cell, 3, by_position)
    assert first == replay_order.replay(cell, 3, by_position)
    assert replay_order.replay(cell, 3, flat + (0.0,)) == \
        replay_order.replay(cell, 3, flat)
    assert first["tokens"] < replay_order.replay(cell, 3, flat)["tokens"]
