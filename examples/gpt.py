#!/usr/bin/env python
"""GPT-style causal language model (beyond the reference zoo — its
Transformer example is a non-causal MSE proxy, transformer.cc:112-211).

Trains next-token prediction with per-token sparse CCE; the causal MHA
rides the Pallas flash kernel, and sharding the seq dim takes the
zigzag ring-attention path for long contexts.

Usage: python examples/gpt.py -b 8 -e 1
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import flexflow_tpu as ff
from examples.common import lm_sequence_data
from flexflow_tpu.models import build_gpt
from flexflow_tpu.runtime.compile_cache import place_compile_cache


def main():
    import jax

    place_compile_cache()
    config = ff.FFConfig.parse_args()
    platform = jax.devices()[0].platform
    if platform == "cpu":  # CI-sized
        vocab, layers, hidden, heads, ff_dim, seq = 512, 2, 64, 4, 128, 32
    else:
        vocab, layers, hidden, heads, ff_dim, seq = 32000, 12, 768, 12, 3072, 512
    print(f"[gpt] platform={platform}: {layers} layers x hidden {hidden}, "
          f"seq {seq}, vocab {vocab}")

    model = build_gpt(config, vocab=vocab, num_layers=layers, hidden=hidden,
                      num_heads=heads, ff_dim=ff_dim, seq_len=seq)
    model.compile(
        optimizer=ff.AdamOptimizer(alpha=3e-4),
        loss_type="sparse_categorical_crossentropy",
        metrics=["accuracy", "sparse_categorical_crossentropy"],
    )

    n = config.batch_size * 8
    x, y = lm_sequence_data(n, seq, vocab, seed=config.seed)
    model.fit(x=x, y=y, epochs=config.epochs)


if __name__ == "__main__":
    main()
