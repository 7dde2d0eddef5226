"""flops.py against numbers worked out by hand."""

import json
import os

import pytest

from benchmarks.harness import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sizes_of(config_name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config_name + ".json")) as f:
        return json.load(f)["builder_kwargs"]


def test_opt_350m_train_flops_per_token():
    sizes = sizes_of("opt-350m-train")
    # per layer 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912 weights; x 24 =
    # 301,989,888; head 1024 x 50272 = 51,478,528; sum 353,468,416; x 6
    assert flops.matmul_params(sizes) == 353_468_416
    assert flops.train_matmul_flops_per_token(sizes) == pytest.approx(
        2.12e9, rel=2e-3)
    # causal attention: 6 x 2048 x 1024 a layer x 24 = 301,989,888
    assert flops.train_attention_flops_per_token(sizes, 2048) == 301_989_888
    assert flops.train_flops_per_token(sizes, 2048) == pytest.approx(
        2.42e9, rel=2e-3)
    # one step of 2 x 2048 tokens: the flash kernels' share
    assert flops.flash_flops_per_step(sizes, 2, 2048) == 301_989_888 * 4096


def test_opt_350m_serve_kv_bytes_per_token():
    sizes = sizes_of("opt-350m-serve")
    assert flops.kv_bytes_per_token(sizes, 4) == 196_608  # 24 x 2 x 1024 x 4
    assert flops.ragged_live_kv_bytes([10, 20], sizes, 4) == 30 * 196_608


def test_mfu_is_tokens_times_flops_over_peak():
    assert flops.mfu(30_000, 2.42e9, 1, 197e12) == pytest.approx(0.3685, rel=1e-3)
