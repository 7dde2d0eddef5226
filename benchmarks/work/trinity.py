"""Operations and bytes the ALGORITHM of ``build_afmoe_decode`` needs —
the ``work`` contract (benchmarks/harness/flops.py states it) for one
chip's share of a mixture-of-experts model whose attention layers are
sliding-window or global: a window layer attends to at most ``window``
cached positions and its cache holds no more; the routed experts are
priced at the SHARE's expectation — of a token's ``experts_per_token``
choices among ``n_routed_experts``, ``experts_held`` / ``n_routed_experts``
fall on an expert held here.

Conventions as in flops.py: a multiply-add is 2 FLOPs, tables that are
looked up are not multiplied, norms, rotary and the gate's sigmoid are
not matmuls; the router is (every token is scored against its full
width).
"""

from __future__ import annotations

import numpy as np

SLIDING = "sliding_attention"


def _kinds(kw: dict):
    """(window layers, global layers, dense layers, expert layers)."""
    window = sum(t == SLIDING for t in kw["layer_types"])
    dense = min(kw["first_dense_layers"], len(kw["layer_types"]))
    return (window, len(kw["layer_types"]) - window, dense,
            len(kw["layer_types"]) - dense)


def attention_weights(kw: dict) -> int:
    """wq, wg [E, Hq·D], wk, wv [E, Hkv·D], wo [Hq·D, E]."""
    q = kw["num_heads"] * kw["head_dim"]
    kv = kw["num_kv_heads"] * kw["head_dim"]
    return kw["hidden"] * (3 * q + 2 * kv)


def gated_weights(kw: dict, width: int) -> int:
    return 3 * kw["hidden"] * width


def held_parameters(config: dict) -> int:
    """Matmul weights and the embedding table this chip HOLDS (norm
    gains left out): what ``param_dtype`` bytes are paid for."""
    kw = config["builder_kwargs"]
    _, _, dense, moe = _kinds(kw)
    layer = len(kw["layer_types"]) * attention_weights(kw)
    ffn = dense * gated_weights(kw, kw["dense_ff_dim"])
    expert = gated_weights(kw, kw["expert_ff_dim"])
    ffn += moe * (kw["hidden"] * kw["n_routed_experts"]
                  + (kw["n_shared_experts"] + kw["experts_held"]) * expert)
    return layer + ffn + 2 * kw["vocab"] * kw["hidden"]


def multiplied_weights(kw: dict, logits: bool = True) -> float:
    """Weights one token is multiplied with in a forward pass, the routed
    experts at the share's expectation."""
    _, _, dense, moe = _kinds(kw)
    routed = (kw["experts_per_token"] * kw["experts_held"]
              / kw["n_routed_experts"])
    expert = gated_weights(kw, kw["expert_ff_dim"])
    return (len(kw["layer_types"]) * attention_weights(kw)
            + dense * gated_weights(kw, kw["dense_ff_dim"])
            + moe * (kw["hidden"] * kw["n_routed_experts"]
                     + (kw["n_shared_experts"] + routed) * expert)
            + (kw["hidden"] * kw["vocab"] if logits else 0))


def attended(kw: dict, context):
    """Cached positions one token reads, summed over the layers: a window
    layer at most its window."""
    window, full, _, _ = _kinds(kw)
    context = np.asarray(context)
    return full * context + window * np.minimum(context, kw["window"])


# ---- the ``work`` contract ------------------------------------------------

def served_token_flops(config: dict, context, logits: bool = True):
    """One token's forward pass behind ``context`` cached tokens (its own
    among them): 2 a multiplied weight plus QK^T and PV against what each
    layer's window lets it see.  ``context`` may be an array."""
    kw = config["builder_kwargs"]
    return (2.0 * multiplied_weights(kw, logits)
            + 4.0 * attended(kw, context) * kw["num_heads"] * kw["head_dim"])


def cached_token_bytes(config: dict, itemsize: int) -> int:
    """K and V of one cached token over all layers, each holding it
    (a window layer drops it after ``window`` more)."""
    kw = config["builder_kwargs"]
    return (len(kw["layer_types"]) * 2 * kw["num_kv_heads"] * kw["head_dim"]
            * itemsize)


def attention_kernel_bytes(config: dict, lens, itemsize: int) -> float:
    """Bytes of K and V the decode kernels have to read for rows that
    attend to ``lens`` cached tokens each (a frame's, or many frames'):
    a global layer all of them, a window layer at most its window."""
    kw = config["builder_kwargs"]
    return (float(np.sum(attended(kw, np.asarray(lens, np.int64))))
            * 2 * kw["num_kv_heads"] * kw["head_dim"] * itemsize)


def trained_token_flops(config: dict, seq_len: int) -> float:
    """Forward + backward of one token in a sequence of ``seq_len``
    (causal: half the positions on average, a window layer no more than
    its window).  No cell trains this configuration; the contract asks."""
    kw = config["builder_kwargs"]
    mean_seen = float(np.mean(attended(kw, np.arange(1, seq_len + 1))))
    return (6.0 * multiplied_weights(kw)
            + 12.0 * mean_seen * kw["num_heads"] * kw["head_dim"])


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    kw = config["builder_kwargs"]
    seen = float(np.sum(attended(kw, np.arange(1, seq_len + 1))))
    return 12.0 * seen * kw["num_heads"] * kw["head_dim"] * batch
