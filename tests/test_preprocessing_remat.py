"""Keras preprocessing utilities + activation rematerialization."""

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.keras import preprocessing as pp


def test_pad_sequences():
    out = pp.pad_sequences([[1, 2], [3, 4, 5, 6], []], maxlen=3)
    np.testing.assert_array_equal(out, [[0, 1, 2], [4, 5, 6], [0, 0, 0]])
    out = pp.pad_sequences([[1, 2]], maxlen=3, padding="post")
    np.testing.assert_array_equal(out, [[1, 2, 0]])
    out = pp.pad_sequences([[1, 2, 3, 4]], maxlen=2, truncating="post")
    np.testing.assert_array_equal(out, [[1, 2]])


def test_tokenizer_roundtrip():
    tok = pp.Tokenizer(num_words=10, oov_token="<oov>")
    tok.fit_on_texts(["the cat sat", "the cat ran", "dogs run fast"])
    seqs = tok.texts_to_sequences(["the cat", "zebra the"])
    assert seqs[0][0] == tok.word_index["the"]
    assert seqs[1][0] == tok.word_index["<oov>"]  # unseen word -> oov
    m = tok.texts_to_matrix(["the the cat"], mode="count")
    assert m[0][tok.word_index["the"]] == 2.0


def test_skipgrams_labels():
    couples, labels = pp.skipgrams([1, 2, 3, 4], vocabulary_size=10,
                                   window_size=1, seed=1)
    assert len(couples) == len(labels)
    assert set(labels) == {0, 1}
    for (a, b), l in zip(couples, labels):
        if l == 1:
            assert abs([1, 2, 3, 4].index(a) - [1, 2, 3, 4].index(b)) <= 1


def _train(remat: bool):
    cfg = ff.FFConfig(batch_size=16, epochs=2, num_devices=8,
                      only_data_parallel=True, compute_dtype="float32",
                      remat=remat, seed=11)
    m = ff.FFModel(cfg)
    x = m.create_tensor([16, 8, 16])
    t = m.multihead_attention(x, x, x, embed_dim=16, num_heads=2, causal=True)
    t = m.dense(t, 32, activation="gelu")
    t = m.dense(t, 16)
    t = m.mean(t, dims=[1])
    t = m.dense(t, 4)
    m.compile(optimizer=ff.SGDOptimizer(lr=0.05),
              loss_type="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 8, 16)).astype(np.float32)
    ys = rng.integers(0, 4, 64).astype(np.int32)
    hist = m.fit(x=xs, y=ys, verbose=False)
    return [h["loss"] for h in hist]


def test_remat_matches_baseline_numerics():
    """jax.checkpoint recomputes the same values — losses identical."""
    base = _train(remat=False)
    remat = _train(remat=True)
    np.testing.assert_allclose(base, remat, rtol=1e-6)


def test_remat_pipeline():
    from flexflow_tpu.models import build_transformer
    from flexflow_tpu.parallel import PipelineConfig

    cfg = ff.FFConfig(batch_size=8, epochs=1, num_devices=8,
                      compute_dtype="float32", remat=True)
    m = build_transformer(cfg, num_layers=4, hidden=16, num_heads=2,
                          ff_dim=32, seq_len=8)
    m.compile(pipeline=PipelineConfig(num_stages=2, num_microbatches=4),
              loss_type="mean_squared_error", metrics=["mean_squared_error"])
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(16, 8, 16)).astype(np.float32)
    ys = rng.normal(size=(16, 8, 16)).astype(np.float32)
    hist = m.fit(x=xs, y=ys, verbose=False)
    assert np.isfinite(hist[-1]["loss"])
