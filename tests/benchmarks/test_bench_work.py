"""The ``work`` contract (what the algorithm needs, priced by the module
a configuration names) and the whole window's share of the peak that is
read through it: on hand-made facts, and on OPT's own two files by
numbers worked out by hand."""

import os
import re
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import mfu_readers, serve, spec, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
CONTRACT = ("trained_token_flops", "attention_kernel_flops",
            "served_token_flops", "cached_token_bytes")


@pytest.mark.parametrize("name", sorted(os.listdir(CONFIGS)))
def test_every_configuration_names_a_work_module_with_the_four(name):
    config = spec.load_json(os.path.join(CONFIGS, name))
    work = spec.resolve_module(config["work"])
    assert all(callable(getattr(work, f)) for f in CONTRACT)
    assert work.trained_token_flops(config, 16) > 0
    assert work.cached_token_bytes(config, 4) > 0
    # a served token's price rises with its context, and an array of
    # contexts gives an array of prices
    some = work.served_token_flops(config, np.asarray([1, 9]))
    assert some.shape == (2,) and some[1] > some[0] > 0
    assert work.served_token_flops(config, 9, logits=False) < some[1]


def test_opt_350m_through_the_contract():
    train = spec.load_json(os.path.join(CONFIGS, "opt-350m-train.json"))
    serve_ = spec.load_json(os.path.join(CONFIGS, "opt-350m-serve.json"))
    work = spec.resolve_module(train["work"])
    # 6 x 353,468,416 multiplied weights + 6 x 2048 x 1024 x 24 causal
    assert work.trained_token_flops(train, 2048) == 6 * 353_468_416 + \
        301_989_888
    assert work.attention_kernel_flops(train, 2, 2048) == 301_989_888 * 4096
    assert work.cached_token_bytes(serve_, 4) == 196_608
    # a generated token behind 100 cached: 2 a weight, QK^T and PV 2 x
    # 100 x 1024 each in 24 layers; a prompt token needs no 1024 x 50272
    # head
    assert work.served_token_flops(serve_, 100) == \
        2 * 353_468_416 + 4 * 100 * 1024 * 24
    assert work.served_token_flops(serve_, 100, logits=False) == \
        2 * (353_468_416 - 1024 * 50272) + 4 * 100 * 1024 * 24


def ctx_of(facts, chips=1):
    cell = spec.Cell(name="handmade", chips=chips, config={}, traffic={},
                     end_to_end=[], per_layer=[], run_seconds=1)
    return {"cell": cell, "facts": facts, "device_kind": "TPU v5 lite"}


def test_window_mfu_is_flops_over_seconds_chips_and_peak():
    # 19.7 TFLOP in 2 s on one chip of 197 TFLOP/s: 5 %
    assert mfu_readers.window_mfu(
        ctx_of({"window_flops": 19.7e12, "window_s": 2.0})) \
        == pytest.approx(5.0)
    assert mfu_readers.window_mfu(
        ctx_of({"window_flops": 19.7e12, "window_s": 2.0}, chips=4)) \
        == pytest.approx(1.25)
    assert mfu_readers.share_of_peak(197e12, 1.0, 1, 197e12) == 1.0


@pytest.mark.parametrize("facts", [
    {}, {"window_flops": 0.0, "window_s": 2.0},
    {"window_flops": 1e12, "window_s": 0.0}, {"window_s": 2.0}])
def test_window_mfu_with_nothing_to_read_is_none_never_zero(facts):
    assert mfu_readers.window_mfu(ctx_of(facts)) is None


def test_window_mfu_of_an_unknown_device_is_an_error():
    ctx = ctx_of({"window_flops": 1e12, "window_s": 1.0})
    ctx["device_kind"] = "TPU v99"
    with pytest.raises(KeyError):
        mfu_readers.window_mfu(ctx)


def test_the_ledger_gives_every_forward_pass_of_the_window_its_context():
    ledger = serve.Ledger()
    ledger.issue(traffic.Request("a", [1] * 5, 3), 9.0)
    ledger.issue(traffic.Request("b", [1] * 3, 2), 10.5)
    ledger.issue(traffic.Request("c", [1] * 4, 2), 11.0)   # never answered
    ledger.tokens["a"] = [9.5, 11.0, 12.0]   # first token before the window
    ledger.tokens["b"] = [11.0, 12.5]        # last token after it
    generated, prefilled = ledger.served_contexts(10.0, 12.0)
    # a: tokens 1 and 2 at contexts 5 + 1, 5 + 2; its prefill fell before
    # the window.  b: token 0 at context 3, and its 2 prompt tokens before
    # the last at contexts 1, 2
    assert sorted(generated.tolist()) == [3, 6, 7]
    assert prefilled.tolist() == [1, 2]
    empty = serve.Ledger().served_contexts(0.0, 1.0)
    assert [len(e) for e in empty] == [0, 0]


def test_the_serve_driver_prices_the_window_by_the_work_module(monkeypatch):
    """One price a generated token and another a prefilled one: the
    window's FLOPs are the two counts times their prices."""
    work = types.ModuleType("handmade_work")
    work.served_token_flops = (
        lambda config, context, logits=True:
        np.full(np.shape(context), 7.0 if logits else 2.0))
    monkeypatch.setitem(sys.modules, "handmade_work", work)
    bdir = os.path.join(ROOT, "benchmarks")
    config = dict(spec.load_json(os.path.join(CONFIGS, "tiny-serve.json")),
                  work="handmade_work")
    cell = spec.Cell(
        name="tiny", chips=1, config=config,
        traffic=spec.load_json(os.path.join(bdir, "traffic",
                                            "tiny-closed.json")),
        end_to_end=[], per_layer=[], run_seconds=1)
    lines = []
    out = serve.run(cell, 2 ** 31 + 5, 0.3, False, 0.0, log=lines.append)
    n_gen, n_pre = map(int, re.search(
        r"(\d+) tokens generated, (\d+) prefilled", "\n".join(
            l for l in lines if "the window's work" in l)).groups())
    assert n_gen > 0 and n_pre > 0
    assert out["facts"]["window_flops"] == 7.0 * n_gen + 2.0 * n_pre
    assert out["facts"]["window_s"] >= 0.3
