"""A causal language model of the JoyAI-LLM-Flash / DeepSeek-V3 family,
as one chip of an expert-parallel group trains it.

    block:  h <- h + MLA(RMS(h));  h <- h + FFN(RMS(h))
    FFN of the leading dense layers: W_down(silu(W_gate x) * W_up x)
    FFN of the expert layers: sigmoid router over ALL ``n_routed_experts``
      (top ``experts_per_token`` of score + correction bias, weighed by
      the score, normalised, times ``routed_scaling_factor``; the bias is
      state, held constant, never moved by a gradient), the gated
      form of every CHOSEN AND HELD expert, plus the shared expert
    head:   logits = RMS(h_L) W_head
    MTP (depth 1): g_i = W_eh [RMS(Emb(t_{i+1})) ; RMS(h_i)], one expert
      block on g, logits' = RMS(.) W_head with the main model's Emb and
      W_head, target t_{i+2}; loss = L_main + mtp_lambda * L_mtp

The chip holds ``experts_held`` of every layer's routed experts, from
``expert_offset`` on; assignments to the others add nothing here (their
chips are not stood in for).  ``expert_rows`` bounds the assignments the
chip's experts take together in one layer-step and keeps the shapes
static (rows sorted by expert, one grouped product); an assignment past
it is counted (``moe.assignments_dropped``), and the bound is set so
that none is.
``vocab`` is this chip's slice of the vocabulary: ids, logits and both
losses are over the slice.  No bias anywhere; every norm an RMS norm.
"""

from __future__ import annotations

from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel


def gated_ffn(model, x, width: int, hidden: int, name: str,
              weights_of: str | None = None):
    """W_down(silu(W_gate x) * W_up x), no bias; ``weights_of`` names
    another gated feed-forward whose three kernels this one reads."""
    def of(part):
        return f"{weights_of}_{part}" if weights_of else None

    gate = model.dense(x, width, activation="silu", use_bias=False,
                       name=f"{name}_gate", weights_of=of("gate"))
    up = model.dense(x, width, use_bias=False, name=f"{name}_up",
                     weights_of=of("up"))
    return model.dense(model.multiply(gate, up, name=f"{name}_act"), hidden,
                       use_bias=False, name=f"{name}_down",
                       weights_of=of("down"))


def held_experts_ffn(model, x, name: str, *, hidden, expert_ff_dim,
                     n_routed_experts, experts_held, expert_offset,
                     experts_per_token, expert_rows, routed_scaling_factor):
    """The routed part of an expert layer: what the experts held here add
    for the tokens routed to them."""
    weights, experts = model.moe_router(
        x, n_routed_experts, experts_per_token, scale=routed_scaling_factor,
        experts_held=experts_held, name=f"{name}_router")
    rows, source, sizes = model.expert_dispatch(
        x, experts, n_routed_experts, experts_held, expert_rows,
        expert_offset=expert_offset, name=f"{name}_dispatch")
    gate = model.expert_linear(rows, expert_ff_dim, activation="silu",
                               sizes=sizes, name=f"{name}_experts_gate")
    up = model.expert_linear(rows, expert_ff_dim, sizes=sizes,
                             name=f"{name}_experts_up")
    down = model.expert_linear(
        model.multiply(gate, up, name=f"{name}_experts_act"), hidden,
        sizes=sizes, name=f"{name}_experts_down")
    return model.expert_combine(weights, source, down, name=f"{name}_combine")


def build_joyai_flash(
    config: FFConfig,
    vocab: int = 129280,
    num_layers: int = 40,
    hidden: int = 2048,
    num_heads: int = 32,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    dense_ff_dim: int = 7168,
    expert_ff_dim: int = 768,
    first_dense_layers: int = 1,
    n_routed_experts: int = 256,
    experts_held: int = 256,
    expert_offset: int = 0,
    experts_per_token: int = 8,
    n_shared_experts: int = 1,
    routed_scaling_factor: float = 2.5,
    expert_rows: int = 0,
    rope_theta: float = 32_000_000.0,
    rms_eps: float = 1e-6,
    seq_len: int = 4096,
    mtp_layers: int = 1,
    mtp_lambda: float = 0.3,
):
    """``expert_rows`` 0 means "never drop": room for every assignment of
    the batch (tokens x ``experts_per_token`` rows)."""
    assert mtp_layers in (0, 1), "one multi-token-prediction module at most"
    model = FFModel(config)
    b = config.batch_size
    tokens = b * seq_len
    rows = expert_rows or tokens * experts_per_token

    def block(h, name, dense: bool):
        a = model.latent_attention(
            model.rms_norm(h, eps=rms_eps, name=f"{name}_attn_norm"),
            num_heads=num_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, eps=rms_eps, name=f"{name}_mla")
        h = model.add(h, a, name=f"{name}_res1")
        x = model.rms_norm(h, eps=rms_eps, name=f"{name}_ffn_norm")
        if dense:
            f = gated_ffn(model, x, dense_ff_dim, hidden, f"{name}_ffn")
        else:
            f = held_experts_ffn(
                model, x, f"{name}_moe", hidden=hidden,
                expert_ff_dim=expert_ff_dim,
                n_routed_experts=n_routed_experts, experts_held=experts_held,
                expert_offset=expert_offset,
                experts_per_token=experts_per_token, expert_rows=rows,
                routed_scaling_factor=routed_scaling_factor)
            if n_shared_experts:
                shared = gated_ffn(model, x, expert_ff_dim * n_shared_experts,
                                   hidden, f"{name}_shared")
                f = model.add(f, shared, name=f"{name}_moe_sum")
        return model.add(h, f, name=f"{name}_res2")

    ids = model.create_tensor([b, seq_len], dtype="int32", name="input_ids")
    h = model.embedding(ids, vocab, hidden, aggr="none", name="tok_embed")
    for i in range(num_layers):
        h = block(h, f"layer{i}", dense=i < first_dense_layers)
    logits = model.dense(model.rms_norm(h, eps=rms_eps, name="final_norm"),
                         vocab, use_bias=False, name="lm_head")
    if mtp_layers:
        with model.block_scope("ff.mtp"):
            ahead = model.embedding(model.shift(ids, 1, name="mtp_next_ids"),
                                    vocab, hidden, aggr="none",
                                    weights_of="tok_embed", name="mtp_embed")
            g = model.concat(
                [model.rms_norm(ahead, eps=rms_eps, name="mtp_enorm"),
                 model.rms_norm(h, eps=rms_eps, name="mtp_hnorm")],
                axis=-1, name="mtp_concat")
            g = model.dense(g, hidden, use_bias=False, name="mtp_eh_proj")
            g = block(g, "mtp", dense=False)
            ahead_logits = model.dense(
                model.rms_norm(g, eps=rms_eps, name="mtp_final_norm"), vocab,
                use_bias=False, weights_of="lm_head", name="mtp_head")
            logits = model.next_token_loss(logits, ahead_logits, ids, shift=2,
                                           weight=mtp_lambda, name="mtp_loss")
    return model
