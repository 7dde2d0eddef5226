"""Operations and bytes the ALGORITHM of ``build_joyai_flash`` needs —
the ``work`` contract (benchmarks/harness/flops.py states it) for the
JoyAI-LLM-Flash block as one chip of an expert-parallel group runs it.

Conventions as in flops.py: a multiply-add is 2 FLOPs, a matmul's
backward costs twice its forward, causal attention is counted once (half
the score matrix), what a kernel re-computes does not count, tables that
are looked up are not multiplied, norms, rotary and routing top-k are
not matmuls.  The routed experts are priced at the SHARE's expectation:
a token sends ``experts_per_token`` assignments over ``n_routed_experts``
experts of which ``experts_held`` live here, so it meets
``experts_per_token * experts_held / n_routed_experts`` of them on
average — padding rows the program computes beyond that are not work.
"""

from __future__ import annotations


def attention_weights(kw: dict) -> int:
    """The five projections of one latent-attention block."""
    h, heads = kw["hidden"], kw["num_heads"]
    qk = kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
    return (h * kw["q_lora_rank"] + kw["q_lora_rank"] * heads * qk
            + h * (kw["kv_lora_rank"] + kw["qk_rope_head_dim"])
            + kw["kv_lora_rank"] * heads * (kw["qk_nope_head_dim"]
                                            + kw["v_head_dim"])
            + heads * kw["v_head_dim"] * h)


def router_weights(kw: dict) -> int:
    return kw["hidden"] * kw["n_routed_experts"]


def expert_block_weights(kw: dict) -> float:
    """Router + shared experts + the routed experts a token meets here."""
    one = 3 * kw["hidden"] * kw["expert_ff_dim"]
    met = (kw["experts_per_token"] * kw["experts_held"]
           / kw["n_routed_experts"])
    return (router_weights(kw) + kw.get("n_shared_experts", 1) * one
            + met * one)


def blocks(kw: dict):
    """(dense blocks, expert blocks of the trunk, MTP modules)."""
    dense = min(kw.get("first_dense_layers", 1), kw["num_layers"])
    return dense, kw["num_layers"] - dense, kw.get("mtp_layers", 1)


def multiplied_weights(kw: dict, mtp: bool = True, logits: bool = True) -> float:
    """Weights one token is multiplied with in a forward pass."""
    dense, expert, modules = blocks(kw)
    h = kw["hidden"]
    head = h * kw["vocab"] if logits else 0
    total = ((dense + expert) * attention_weights(kw)
             + dense * 3 * h * kw["dense_ff_dim"]
             + expert * expert_block_weights(kw) + head)
    if mtp and modules:
        # W_eh [2h -> h], one expert block, the shared head once more
        total += modules * (2 * h * h + attention_weights(kw)
                            + expert_block_weights(kw) + head)
    return total


def attention_flops_per_token(kw: dict, seq_len: int) -> float:
    """Causal attention forward + backward, every block (the MTP module's
    too): forward ``seq_len * heads * (qk width + v width)`` a token —
    half of the two full products — and twice that backward."""
    dense, expert, modules = blocks(kw)
    widths = (kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
              + kw["v_head_dim"])
    return 3.0 * seq_len * kw["num_heads"] * widths * (dense + expert + modules)


# ---- the ``work`` contract ------------------------------------------------

def trained_token_flops(config: dict, seq_len: int) -> float:
    """FLOPs one trained token needs, forward + backward, both losses.
    A router whose layer holds only some of its experts takes no
    gradient (``MoERouterOp``): its product is forward only, 2 a weight
    and not 6."""
    kw = config["builder_kwargs"]
    dense, expert, modules = blocks(kw)
    forward_only = (router_weights(kw) * (expert + modules)
                    if kw["experts_held"] < kw["n_routed_experts"] else 0)
    return (6.0 * multiplied_weights(kw) - 4.0 * forward_only
            + attention_flops_per_token(kw, seq_len))


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    """FLOPs the three flash kernels have to compute in one optimizer
    step, at q/k heads ``nope + rope`` wide and v heads ``v_head_dim``."""
    return (attention_flops_per_token(config["builder_kwargs"], seq_len)
            * batch * seq_len)


def served_token_flops(config: dict, context, logits: bool = True):
    """One token's forward pass behind ``context`` cached tokens in the
    LATENT form a server of this family keeps (no MTP module): 2 a
    multiplied weight; per layer and head the scores against the cached
    ``kv_lora_rank + rope`` and the values in the latent ``kv_lora_rank``.
    ``context`` may be an array."""
    kw = config["builder_kwargs"]
    latent = 2 * kw["kv_lora_rank"] + kw["qk_rope_head_dim"]
    return (2.0 * multiplied_weights(kw, mtp=False, logits=logits)
            + 2.0 * context * kw["num_heads"] * latent * kw["num_layers"])


def cached_token_bytes(config: dict, itemsize: int) -> int:
    """The latent cache of one token over all layers: c_kv and the one
    shared k_rope."""
    kw = config["builder_kwargs"]
    return ((kw["kv_lora_rank"] + kw["qk_rope_head_dim"]) * kw["num_layers"]
            * itemsize)
