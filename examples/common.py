"""Shared runner for example scripts: synthetic data generation, train
loop, throughput report — the role of each reference example's
top_level_task + DataLoader (e.g. transformer.cc:112-211)."""

from __future__ import annotations

import time
from typing import List, Sequence

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.runtime.compile_cache import place_compile_cache


def synthetic_inputs(model: ff.FFModel, num_samples: int, seed: int = 0) -> List[np.ndarray]:
    """Generate arrays matching the model's input tensors (batch dim
    replaced by num_samples)."""
    rng = np.random.default_rng(seed)
    out = []
    for t in model._input_tensors:
        shape = (num_samples,) + tuple(t.sizes[1:])
        if t.dtype.value.startswith("int"):
            # embedding ids: stay in-range; find the consumer's vocab if any
            vocab = 1000
            node, _ = model._producer[t.guid]
            for e in model.graph.out_edges[node.guid]:
                consumer = model.graph.nodes[e.dst].op
                if "num_entries" in consumer.attrs:
                    vocab = consumer.attrs["num_entries"]
            out.append(rng.integers(0, vocab, size=shape).astype(np.int32))
        else:
            out.append(rng.normal(size=shape).astype(np.float32))
    return out


def lm_sequence_data(num_samples: int, seq_len: int, vocab: int, seed: int = 0):
    """(x, y) for next-token training on the deterministic rule
    token[j] = (token[j-1] * 3 + 1) mod vocab — learnable by a causal
    model; shared by examples/gpt.py and the zoo test so the asserted
    rule and the demonstrated rule cannot drift apart."""
    rng = np.random.default_rng(seed)
    x = np.empty((num_samples, seq_len), np.int32)
    x[:, 0] = rng.integers(0, vocab, num_samples)
    for j in range(1, seq_len):
        x[:, j] = (x[:, j - 1] * 3 + 1) % vocab
    return x, np.roll(x, -1, axis=1)


def synthetic_labels(model: ff.FFModel, num_samples: int, loss: str, seed: int = 1):
    rng = np.random.default_rng(seed)
    sink = model.graph.sinks()[-1]
    out_shape = sink.op.output_shapes[0].sizes
    if loss == "sparse_categorical_crossentropy":
        if len(out_shape) > 2:  # per-position logits (causal LM)
            return rng.integers(
                0, out_shape[-1], (num_samples,) + tuple(out_shape[1:-1])
            ).astype(np.int32)
        return rng.integers(0, out_shape[-1], num_samples).astype(np.int32)
    return rng.normal(size=(num_samples,) + tuple(out_shape[1:])).astype(np.float32)


def run_example(model: ff.FFModel, name: str, loss: str = "sparse_categorical_crossentropy",
                metrics: Sequence[str] = ("accuracy",), num_samples: int = 0,
                optimizer=None, recompile_state=None, skip_compile=False):
    place_compile_cache()
    cfg = model.config
    num_samples = num_samples or cfg.batch_size * 8
    if not skip_compile:
        t0 = time.perf_counter()
        model.compile(optimizer=optimizer, loss_type=loss, metrics=list(metrics))
        print(f"[{name}] compile (incl. strategy search): {time.perf_counter()-t0:.2f}s")
    xs = synthetic_inputs(model, num_samples)
    y = synthetic_labels(model, num_samples, loss)
    model.fit(x=xs if len(xs) > 1 else xs[0], y=y, recompile_state=recompile_state)
    thr = getattr(model, "last_throughput", None)
    if thr:
        print(f"[{name}] THROUGHPUT = {thr:.2f} samples/s")
    return model
