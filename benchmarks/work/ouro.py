"""Operations and bytes the ALGORITHM of ``build_ouro`` needs — the
``work`` contract (benchmarks/harness/flops.py states it) for a looped
language model: ``num_layers`` layers applied ``loop_steps`` times over
the same weights, the head and the exit gate read once a loop step.

Conventions as in flops.py: a multiply-add is 2 FLOPs, a matmul's
backward costs twice its forward, causal attention is counted once (half
the score matrix), tables that are looked up are not multiplied, norms
and rotary are not matmuls.  A weight that is USED T times is multiplied
T times: the tie saves memory, not arithmetic.  What recomputation
(``FFConfig.remat``) runs a second time is the program's cost and not the
algorithm's: it is not counted, so ``train.mfu`` and
``train.flash_roofline_share`` fall under recomputation, as they should.
"""

from __future__ import annotations


def layer_weights(kw: dict) -> int:
    """Multiplied weights of one layer: the four attention projections
    and the three matrices of the gated feed-forward."""
    h = kw["hidden"]
    return 4 * h * kw["num_heads"] * kw["head_dim"] + 3 * h * kw["ff_dim"]


def multiplied_weights(kw: dict, logits: bool = True) -> int:
    """Weights one token is multiplied with in a forward pass through
    all loop steps: every layer, the head and the gate once a step."""
    exit_ = kw["hidden"] * ((kw["vocab"] if logits else 0) + 1)
    return kw["loop_steps"] * (kw["num_layers"] * layer_weights(kw) + exit_)


def attention_flops_per_token(kw: dict, seq_len: int) -> float:
    """Causal attention forward + backward over the T x L applications:
    forward ``seq_len * heads * 2 * head_dim`` a token — half of the two
    full products — and twice that backward."""
    return (3.0 * seq_len * kw["num_heads"] * 2 * kw["head_dim"]
            * kw["loop_steps"] * kw["num_layers"])


# ---- the ``work`` contract ------------------------------------------------

def trained_token_flops(config: dict, seq_len: int) -> float:
    """FLOPs one trained token needs, forward + backward, all exits."""
    kw = config["builder_kwargs"]
    return (6.0 * multiplied_weights(kw)
            + attention_flops_per_token(kw, seq_len))


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    """FLOPs the three flash kernels have to compute in one optimizer
    step (a forward kernel that recomputation launches again adds time,
    not work)."""
    return (attention_flops_per_token(config["builder_kwargs"], seq_len)
            * batch * seq_len)


def served_token_flops(config: dict, context, logits: bool = True):
    """One token's forward pass through every loop step behind
    ``context`` cached tokens a (step, layer): 2 a multiplied weight —
    with ``logits`` the head at every step, as an early-exit server
    would read it — plus QK^T and PV against the cache.  ``context`` may
    be an array."""
    kw = config["builder_kwargs"]
    return (2.0 * multiplied_weights(kw, logits=logits)
            + 4.0 * context * kw["num_heads"] * kw["head_dim"]
            * kw["loop_steps"] * kw["num_layers"])


def cached_token_bytes(config: dict, itemsize: int) -> int:
    """K and V of one token: a cached layer a (loop step, layer)."""
    kw = config["builder_kwargs"]
    return (2 * kw["num_heads"] * kw["head_dim"] * kw["loop_steps"]
            * kw["num_layers"] * itemsize)
