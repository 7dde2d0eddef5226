"""Model-zoo smoke tests: every family builds, compiles DP on the CPU
mesh, and completes a train step (role of reference
tests/multi_gpu_tests.sh — success = trains without crash)."""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.models import (
    build_alexnet_cifar10,
    build_bert,
    build_candle_uno,
    build_dlrm,
    build_inception_v3,
    build_mlp_unify,
    build_moe,
    build_ouro,
    build_resnext50,
    build_transformer,
)


def tiny_cfg(batch=8, **kw):
    return ff.FFConfig(batch_size=batch, epochs=1, num_devices=8,
                       only_data_parallel=True, compute_dtype="float32", **kw)


def fit_one(model, inputs, labels, loss="sparse_categorical_crossentropy",
            metrics=("accuracy",)):
    model.compile(optimizer=ff.SGDOptimizer(lr=0.01), loss_type=loss,
                  metrics=list(metrics))
    hist = model.fit(x=inputs, y=labels, verbose=False)
    assert hist and "samples" in hist[-1]
    return hist


def test_alexnet_cifar10():
    rng = np.random.default_rng(0)
    model = build_alexnet_cifar10(tiny_cfg())
    x = rng.normal(size=(16, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, 16).astype(np.int32)
    fit_one(model, x, y)


def test_transformer_tiny():
    rng = np.random.default_rng(1)
    model = build_transformer(tiny_cfg(), num_layers=2, hidden=32, num_heads=4,
                              ff_dim=64, seq_len=16)
    x = rng.normal(size=(16, 16, 32)).astype(np.float32)
    y = rng.normal(size=(16, 16, 32)).astype(np.float32)
    fit_one(model, x, y, loss="mean_squared_error", metrics=["mean_squared_error"])


def test_bert_tiny():
    rng = np.random.default_rng(2)
    model = build_bert(tiny_cfg(), vocab=100, num_layers=2, hidden=32,
                       num_heads=4, ff_dim=64, seq_len=16, num_classes=2,
                       dropout=0.0)
    ids = rng.integers(0, 100, size=(16, 16)).astype(np.int32)
    y = rng.integers(0, 2, 16).astype(np.int32)
    fit_one(model, ids, y)


def test_gpt_tiny_learns_and_is_causal():
    """Causal LM family (beyond the reference zoo): per-token sparse
    CCE on a deterministic next-token rule must LEARN (loss falls
    well below uniform), and causality must hold — perturbing the last
    input position cannot change earlier logits."""
    from flexflow_tpu.models import build_gpt

    vocab, seq = 64, 16
    model = build_gpt(tiny_cfg(), vocab=vocab, num_layers=2, hidden=32,
                      num_heads=4, ff_dim=64, seq_len=seq)
    model.compile(optimizer=ff.AdamOptimizer(alpha=3e-3),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=["accuracy", "sparse_categorical_crossentropy"])
    from examples.common import lm_sequence_data

    x, y = lm_sequence_data(64, seq, vocab, seed=4)
    hist = model.fit(x=x, y=y, epochs=8, verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.5, (
        hist[0]["loss"], hist[-1]["loss"])
    assert 0.0 <= hist[-1]["accuracy"] <= 1.0

    # strict causality: flip the LAST token; logits at positions < S-1
    # must be bit-identical
    fwd = model.compiled.forward_fn()
    x2 = x[:8].copy()
    x2[:, -1] = (x2[:, -1] + 1) % vocab
    l1 = np.asarray(fwd(model.params, model.state, [x[:8]]))
    l2 = np.asarray(fwd(model.params, model.state, [x2]))
    np.testing.assert_array_equal(l1[:, :-1], l2[:, :-1])
    assert np.abs(l1[:, -1] - l2[:, -1]).max() > 0


def test_dlrm_tiny():
    rng = np.random.default_rng(3)
    model = build_dlrm(tiny_cfg(), embedding_sizes=(1000, 1000), embedding_dim=16,
                       dense_dim=13, bot_mlp=(64, 16), top_mlp=(64, 1))
    dense = rng.normal(size=(16, 13)).astype(np.float32)
    s0 = rng.integers(0, 1000, size=(16, 1)).astype(np.int32)
    s1 = rng.integers(0, 1000, size=(16, 1)).astype(np.int32)
    y = rng.uniform(0, 1, (16, 1)).astype(np.float32)
    fit_one(model, [dense, s0, s1], y, loss="mean_squared_error",
            metrics=["mean_squared_error"])


def test_candle_uno_tiny():
    rng = np.random.default_rng(4)
    shapes = {"dose": 1, "cell.rnaseq": 32, "drug.descriptors": 24}
    feats = ["dose1", "cell.rnaseq", "drug1.descriptors"]
    model = build_candle_uno(tiny_cfg(), feature_shapes=shapes,
                             input_features=feats,
                             dense_layers=(32, 32), dense_feature_layers=(32,))
    xs = [rng.normal(size=(16, shapes[k])).astype(np.float32)
          for k in ["dose", "cell.rnaseq", "drug.descriptors"]]
    y = rng.uniform(0, 1, (16, 1)).astype(np.float32)
    fit_one(model, xs, y, loss="mean_squared_error", metrics=["mean_squared_error"])


def test_moe_tiny():
    rng = np.random.default_rng(5)
    model = build_moe(tiny_cfg(), in_dim=32, num_classes=4, num_exp=4,
                      num_select=2, hidden=16)
    x = rng.normal(size=(16, 32)).astype(np.float32)
    y = rng.integers(0, 4, 16).astype(np.int32)
    fit_one(model, x, y)
    # every expert has weights of its own
    fc1 = model.get_weight("expert_fc1", "kernel")
    assert fc1.shape == (4, 32, 16) and not np.allclose(fc1[0], fc1[1])


def test_ouro_tiny():
    """The looped language model under data parallelism on 8 devices:
    two layers run three times hold two layers' weights."""
    model = build_ouro(tiny_cfg(), vocab=64, num_layers=2, hidden=32,
                       num_heads=2, head_dim=16, ff_dim=48, loop_steps=3,
                       seq_len=16)
    x = np.random.default_rng(8).integers(0, 64, (16, 16)).astype(np.int32)
    fit_one(model, x, np.roll(x, -1, axis=1), metrics=())
    assert "loop2_layer0_attn" not in model.params
    assert model.get_weight("layer1_attn", "wq").shape == (32, 2, 16)
    assert model.get_weight("exit_gate", "kernel").shape == (32, 1)


def test_mlp_unify_tiny():
    rng = np.random.default_rng(6)
    model = build_mlp_unify(tiny_cfg(), in_dim=64, hidden=(64, 64), num_classes=4)
    x = rng.normal(size=(16, 64)).astype(np.float32)
    y = rng.integers(0, 4, 16).astype(np.int32)
    fit_one(model, x, y)


@pytest.mark.slow
def test_inception_builds():
    """Graph-build + shape check only (full compile is slow on CPU)."""
    model = build_inception_v3(tiny_cfg(batch=2), num_classes=10, image=299)
    assert model.graph.num_nodes > 100
    sink = model.graph.sinks()[-1]
    assert sink.op.output_shapes[0].sizes == (2, 10)


@pytest.mark.slow
def test_resnext_builds():
    model = build_resnext50(tiny_cfg(batch=2), num_classes=10, image=224)
    sink = model.graph.sinks()[-1]
    assert sink.op.output_shapes[0].sizes == (2, 10)
