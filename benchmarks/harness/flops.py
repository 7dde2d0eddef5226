"""Operations and bytes the ALGORITHM needs, from a cell's shapes — the
yardstick for MFU and roofline shares.  Never from the program's own
``op.flops()``: the program does not price itself.

Conventions: a multiply-add is 2 FLOPs; the backward pass of a matmul
costs twice its forward; causal attention is counted once (half of the
full score matrix); operations a kernel RE-computes (the flash backward
kernels rebuild the scores) do not count.
"""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Weights that are multiplied per token: the four attention
    projections and the two FFN matrices of every layer, and the output
    head.  Embedding tables are looked up, not multiplied."""
    h, ff = sizes["hidden"], sizes["ff_dim"]
    return (sizes["num_layers"] * (4 * h * h + 2 * h * ff)
            + h * sizes["vocab"])


def train_matmul_flops_per_token(sizes: dict) -> float:
    """Forward 2 FLOPs a weight, backward 4."""
    return 6.0 * matmul_params(sizes)


def train_attention_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Causal self-attention, forward + backward, all layers.  Forward:
    QK^T and PV are 2*S*h FLOPs a token each over the full square, half
    of that causal -> 2*S*h a token; backward twice that."""
    return 6.0 * seq_len * sizes["hidden"] * sizes["num_layers"]


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    return (train_matmul_flops_per_token(sizes)
            + train_attention_flops_per_token(sizes, seq_len))


def flash_flops_per_step(sizes: dict, batch: int, seq_len: int) -> float:
    """What the three flash kernels (fwd, dq, dkv) have to compute in one
    optimizer step: the attention share of ``train_flops_per_token``."""
    return train_attention_flops_per_token(sizes, seq_len) * batch * seq_len


def kv_bytes_per_token(sizes: dict, pool_itemsize: int = 4) -> int:
    """K and V of one cached token over all layers, in the pool's type."""
    return sizes["num_layers"] * 2 * sizes["hidden"] * pool_itemsize


def ragged_live_kv_bytes(seq_lens_read, sizes: dict,
                         pool_itemsize: int = 4) -> float:
    """Bytes of LIVE K and V the decode kernel had to read over a set of
    frames: ``seq_lens_read`` holds, for every live row of every frame,
    the number of cached tokens it attended to."""
    return float(sum(seq_lens_read)) * kv_bytes_per_token(sizes,
                                                           pool_itemsize)


def mfu(tokens_per_s: float, flops_per_token: float, chips: int,
        peak_flops_per_s: float) -> float:
    return tokens_per_s * flops_per_token / (chips * peak_flops_per_s)


# ---- the ``work`` contract ------------------------------------------------
# A configuration names a module under ``work``; the drivers and readers
# call these four on it, with the configuration's own dict, and nothing
# else.  This module is the one of the zoo's OPT-style block, so it knows
# what ``build_gpt`` and ``build_gpt_decode`` call their sizes.


def trained_token_flops(config: dict, seq_len: int) -> float:
    """FLOPs one trained token needs, forward + backward."""
    return train_flops_per_token(config["builder_kwargs"], seq_len)


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    """FLOPs the configuration's attention kernels (``harness.
    attention_kernels``) have to compute in one optimizer step."""
    return flash_flops_per_step(config["builder_kwargs"], batch, seq_len)


def served_token_flops(config: dict, context, logits: bool = True):
    """FLOPs of one token's forward pass behind ``context`` cached tokens
    (its own among them): 2 a multiplied weight, and QK^T and PV over the
    context, 2 * context * hidden each a layer.  A prompt token that is
    not the last needs no logits: ``logits=False`` leaves the head out.
    ``context`` may be an array; the result then has its shape."""
    sizes = config["builder_kwargs"]
    weights = matmul_params(sizes)
    if not logits:
        weights -= sizes["hidden"] * sizes["vocab"]
    return (2.0 * weights
            + 4.0 * context * sizes["hidden"] * sizes["num_layers"])


def cached_token_bytes(config: dict, itemsize: int) -> int:
    """K and V of one cached token over all layers."""
    return kv_bytes_per_token(config["builder_kwargs"], itemsize)
