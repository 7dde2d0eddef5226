"""The proof that the harness is pinned to no model: a second
architecture runs through both drivers by files alone.  In a temporary
copy of ``benchmarks/`` a later PR's move is made — new files and new
entries, no edit to a file that is there — with a language model that is
not ``build_gpt`` (other argument names, no attention op, no Mosaic
call; data/second_arch.py) through ``train.run``, and the decode model
behind a builder with other argument names through ``serve.run``.  Each
comes out ``correct`` against its plain reference at a tiny size on the
CPU — counts and values, never a speed."""

import json
import os
import shutil
import time

import pytest

from benchmarks.harness import serve, spec, train

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2 ** 31 + 29

NO_SOURCE = {"source": "none: a second architecture for the CPU tests",
             "preset": True, "published": {}, "widths": [], "reduced": {},
             "from_source": {"builder_kwargs": {}, "harness": {}},
             "loss": "sparse_categorical_crossentropy"}
GATED_LM = dict(
    NO_SOURCE, name="gated-lm", mode="train",
    builder="second_arch:build_gated_lm",
    builder_kwargs={"n_tokens": 96, "width": 32, "inner": 48, "context": 24},
    harness={"vocab": 96, "seq_len": 24, "layers": 1,
             "mosaic_calls": {"train_step": 0}, "attention_kernels": []},
    work="second_arch", reference="second_arch",
    ffconfig={"compute_dtype": "bfloat16", "num_devices": 1,
              "cost_cache_file": ""},
    optimizer={"type": "adam", "alpha": 0.001})
RENAMED_DECODE = dict(
    NO_SOURCE, name="renamed-decode", mode="serve",
    builder="second_arch:build_renamed_decode",
    builder_kwargs={"n_tokens": 96, "depth": 1, "width": 64, "heads": 2,
                    "inner": 96, "page": 8, "pages": 6},
    harness={"vocab": 96, "layers": 1, "context": 48, "page_size": 8,
             "pages_per_seq": 6, "kv_pools": ["*/k_cache", "*/v_cache"],
             "mosaic_calls": {"decode_frame": 1},
             "attention_kernels": ["ragged_paged_attention"]},
    work="second_arch_decode_work",
    reference="benchmarks.reference.opt_block",
    ffconfig={"num_devices": 1, "cost_cache_file": ""},
    slots=3, prefill_chunk=4, attention_path="pallas",
    probe={"prompt_tokens": 10, "max_new_tokens": 3})
GATED_EPOCHS = {"kind": "train_epochs", "batch": 2, "seq_len": 24,
                "batches_per_epoch": 2, "warmup_epochs": 1, "why": "test"}
WINDOW_GFLOP = {"name": "second.window_gflop", "unit": "GFLOP",
                "layer": "device program", "moves": "setup_s",
                "reader": "second_arch:window_gflop"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The temporary checkout with the later PR's files in it, and what
    every file of ``benchmarks/`` held before they were added."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmarks")):
        for name in files:
            with open(os.path.join(folder, name), "rb") as f:
                before[os.path.join(folder, name)] = f.read()

    def put(rel, obj):
        path = os.path.join(root, "benchmarks", rel)
        assert path not in before, "a later PR edits no file that is there"
        with open(path, "w") as f:
            json.dump(obj, f)

    put("configs/gated-lm.json", GATED_LM)
    put("configs/renamed-decode.json", RENAMED_DECODE)
    put("traffic/gated-epochs.json", GATED_EPOCHS)
    put("layer_metrics/second.window_gflop.json", WINDOW_GFLOP)
    bench = spec.load_benchmark(ROOT)
    for config in (GATED_LM, RENAMED_DECODE):
        bench["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"benchmarks/configs/{config['name']}.json",
            "reduced": [], "why": "test"})
    cells = {"gated-lm.gated-epochs": ("gated-lm", "gated-epochs"),
             "renamed-decode.tiny-closed": ("renamed-decode", "tiny-closed")}
    for name, (config, traffic) in cells.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    bench["per_layer"].append({
        "name": "second.window_gflop", "unit": "GFLOP", "better": "higher",
        "source": "host_clock", "layer": "device program",
        "moves": "setup_s", "workloads": list(cells)})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


@pytest.fixture(scope="module")
def on_path():
    """``tests/benchmarks/data`` importable, as ``benchmarks/`` is for the
    files a real PR adds there."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(os.path.join(HERE, "data"))
    yield
    mp.undo()


def run_cell(driver, root, name):
    cell = spec.resolve_cell(root, name)
    lines = []
    out = driver.run(cell, SEED, 0.3, False, time.perf_counter(),
                     log=lines.append)
    ctx = {"cell": cell, "facts": out["facts"], "trace": None,
           "device_kind": "TPU v5 lite"}
    metric = next(m for m in cell.per_layer
                  if m["name"] == "second.window_gflop")
    value = spec.resolve_dotted(metric["reader"])(ctx)
    return out, value, "\n".join(lines)


def test_a_model_that_is_not_build_gpt_trains_by_files_alone(copy, on_path):
    root, _ = copy
    out, gflop, log = run_cell(train, root, "gated-lm.gated-epochs")
    assert out["correct"], log
    assert out["facts"]["checks"]["step0_loss_equals_reference"]
    compared = out["compared"]
    assert 0 <= compared["step0_loss_rel_gap"]["value"] \
        <= compared["step0_loss_rel_gap"]["limit"]
    assert compared["mosaic_calls_in_step"] == {"value": 0, "limit": 0}
    # the driver priced the window by THIS model's work module: whole
    # epochs x 2 steps x 2 x 24 tokens x 6 x (3 x 32 x 48 + 32 x 96)
    epochs = len(out["facts"]["epoch_seconds"])
    assert epochs >= 1 and out["attempted"] == 2 * epochs
    assert gflop == pytest.approx(
        epochs * 2 * 2 * 24 * 6 * (3 * 32 * 48 + 32 * 96) / 1e9)
    assert "1 layers" in log and "second_arch:trained_token_flops" in log


def test_a_renamed_decode_builder_serves_by_files_alone(copy, on_path):
    root, _ = copy
    out, gflop, log = run_cell(serve, root, "renamed-decode.tiny-closed")
    assert out["correct"], log
    checks = out["facts"]["checks"]
    assert checks["logits_equal_reference"] and checks["tokens"]
    assert out["attempted"] >= 3 and out["failed"] == 0
    assert out["compared"]["probe_logit_gap"]["value"] \
        <= out["compared"]["probe_logit_gap"]["limit"]
    assert out["facts"]["pool_itemsize"] == 4 and gflop > 0


def test_no_file_that_was_there_changed_and_old_cells_resolve(copy):
    root, before = copy
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        old = spec.resolve_cell(root, w["name"])
        assert "second.window_gflop" not in [m["name"]
                                             for m in old.per_layer]
        assert old.config == spec.resolve_cell(ROOT, w["name"]).config
