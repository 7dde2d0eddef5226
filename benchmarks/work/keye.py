"""Operations and bytes the ALGORITHM of ``build_keye_vl_decode`` needs —
the ``work`` contract (benchmarks/harness/flops.py states it) for one
chip's share of a mixture-of-experts model whose attention reads only
the cached positions a learned indexer chooses: every layer scores ALL
of a token's cached positions with the indexer, then attends to at most
``index_topk`` of them; the routed experts are priced at the SHARE's
expectation — of a token's ``experts_per_token`` choices among
``n_routed_experts``, ``experts_held`` / ``n_routed_experts`` fall on an
expert held here.

Conventions as in flops.py: a multiply-add is 2 FLOPs, tables that are
looked up are not multiplied, norms, rotary, the indexer's ReLU and the
selection are not matmuls; the router and the indexer's projections are
(every token is scored against their full width).
"""

from __future__ import annotations

import numpy as np


def attention_weights(kw: dict) -> int:
    """wq [E, Hq·D], wk, wv [E, Hkv·D], wo [Hq·D, E]."""
    q = kw["num_heads"] * kw["head_dim"]
    kv = kw["num_kv_heads"] * kw["head_dim"]
    return kw["hidden"] * (2 * q + 2 * kv)


def indexer_weights(kw: dict) -> int:
    """WqI [E, Hi·Di], WkI [E, Di], Ww [E, Hi]."""
    hi, di = kw["index_heads"], kw["index_head_dim"]
    return kw["hidden"] * (hi * di + di + hi)


def expert_weights(kw: dict) -> int:
    return 3 * kw["hidden"] * kw["expert_ff_dim"]


def held_parameters(config: dict) -> int:
    """Matmul weights and the embedding table this chip HOLDS (norm gains
    left out): what ``param_dtype`` bytes are paid for."""
    kw = config["builder_kwargs"]
    layer = (attention_weights(kw) + indexer_weights(kw)
             + kw["hidden"] * kw["n_routed_experts"]
             + kw["experts_held"] * expert_weights(kw))
    return kw["num_layers"] * layer + 2 * kw["vocab"] * kw["hidden"]


def multiplied_weights(kw: dict, logits: bool = True) -> float:
    """Weights one token is multiplied with in a forward pass, the routed
    experts at the share's expectation."""
    routed = (kw["experts_per_token"] * kw["experts_held"]
              / kw["n_routed_experts"])
    layer = (attention_weights(kw) + indexer_weights(kw)
             + kw["hidden"] * kw["n_routed_experts"]
             + routed * expert_weights(kw))
    return (kw["num_layers"] * layer
            + (kw["hidden"] * kw["vocab"] if logits else 0))


def attended(kw: dict, context):
    """Cached positions one token's attention reads in a layer: at most
    ``index_topk``."""
    return np.minimum(np.asarray(context), kw["index_topk"])


# ---- the ``work`` contract ------------------------------------------------

def served_token_flops(config: dict, context, logits: bool = True):
    """One token's forward pass behind ``context`` cached tokens (its own
    among them): 2 a multiplied weight, the indexer's ``Hi`` dot products
    of ``Di`` against every cached index key, and QK^T and PV against the
    chosen positions, in every layer.  ``context`` may be an array."""
    kw = config["builder_kwargs"]
    context = np.asarray(context)
    index = 2.0 * kw["index_heads"] * kw["index_head_dim"] * context
    attend = 4.0 * attended(kw, context) * kw["num_heads"] * kw["head_dim"]
    return 2.0 * multiplied_weights(kw, logits) + kw["num_layers"] * (
        index + attend)


def cached_token_bytes(config: dict, itemsize: int) -> int:
    """K, V and the index key of one cached token over all layers."""
    kw = config["builder_kwargs"]
    return kw["num_layers"] * (2 * kw["num_kv_heads"] * kw["head_dim"]
                               + kw["index_head_dim"]) * itemsize


def index_key_bytes(config: dict, lens, itemsize: int) -> float:
    """Bytes of index keys the indexer reads for rows of ``lens`` cached
    tokens each: all of them, in every layer."""
    kw = config["builder_kwargs"]
    return (float(np.sum(np.asarray(lens, np.int64))) * kw["num_layers"]
            * kw["index_head_dim"] * itemsize)


def selected_kv_bytes(config: dict, lens, itemsize: int) -> float:
    """Bytes of K and V the attention reads for rows of ``lens`` cached
    tokens each: the chosen rows alone, at most ``index_topk``."""
    kw = config["builder_kwargs"]
    return (float(np.sum(attended(kw, np.asarray(lens, np.int64))))
            * kw["num_layers"] * 2 * kw["num_kv_heads"] * kw["head_dim"]
            * itemsize)


def attention_kernel_bytes(config: dict, lens, itemsize: int) -> float:
    """Bytes the decode attention has to read for rows of ``lens`` cached
    tokens each: the chosen rows' K/V and every cached index key."""
    return (selected_kv_bytes(config, lens, itemsize)
            + index_key_bytes(config, lens, itemsize))


def trained_token_flops(config: dict, seq_len: int) -> float:
    """Forward + backward of one token in a sequence of ``seq_len``
    (causal: the indexer over half the positions on average, attention
    over at most ``index_topk``).  No cell trains this configuration;
    the contract asks."""
    kw = config["builder_kwargs"]
    seen = np.arange(1, seq_len + 1)
    index = 2.0 * kw["index_heads"] * kw["index_head_dim"] * float(
        np.mean(seen))
    attend = 4.0 * float(np.mean(attended(kw, seen))) * kw["num_heads"] \
        * kw["head_dim"]
    return 3.0 * (2.0 * multiplied_weights(kw)
                  + kw["num_layers"] * (index + attend))


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    """Sparse attention's QK^T and PV over a batch, forward + backward."""
    kw = config["builder_kwargs"]
    seen = float(np.sum(attended(kw, np.arange(1, seq_len + 1))))
    return (12.0 * seen * kw["num_heads"] * kw["head_dim"] * batch
            * kw["num_layers"])
