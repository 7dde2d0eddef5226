"""The decode graph of Keye-VL-2.0's language model (Kwai-Keye, the
Qwen3-VL-MoE lineage with DeepSeek-V3.2's sparse attention), text only,
as ONE CHIP of an expert-parallel group serves it.

    h0 = Emb[ids]                       (no scaling; untied from the head)
    layer:  h <- h + Attn(N1 h);  h <- h + MoE(N2 h)      (RMS norms)
    logits = N(h_L) W_head

    Attn(x) at position t:
      q = Nq(x Wq) [Hq, D], k = Nk(x Wk) [Hkv, D], v = x Wv [Hkv, D];
        q, k turned by half-split rotary at ``rope_theta`` (M-RoPE with
        t = h = w, as every text token has)
      indexer: qI = x WqI [Hi, Di];  kI = LN(x WkI) [Di], cached a
        position; rotary on the leading ``index_rope_dim`` of each;
        wI = (x Ww) [Hi] / sqrt(Hi)
        I[t, s] = sum_j wI[t, j] ReLU(qI[t, j] . kI[s]) / sqrt(Di), s <= t
      S_t = the ``index_topk`` s <= t of largest I[t, s] (all s <= t
        when fewer); one set for every head
      o_t = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s  (Hq / Hkv query
        heads share a key/value head);  Attn = o Wo
    MoE(x): p = softmax(x Wr) over all ``n_routed_experts`` (float32);
      E_t = the top ``experts_per_token`` of p; g_e = p_e / sum_{E_t} p
      (``norm_topk_prob``); MoE = sum over e in E_t AND held of
      g_e Wd_e(silu(Wg_e x) * Wu_e x); no shared expert

Every layer is alike (``decoder_sparse_step`` 1, no dense layer).  Three
inputs as every decode graph has (``token_ids`` [B, 1], ``page_table``
[B, pages_per_seq], ``seq_lens`` [B]); B = ``config.batch_size``
sequence slots.  The chip holds ``experts_held`` of every layer's routed
experts from ``expert_offset`` on (what the others would add is left
out) and ``vocab`` rows of the vocabulary.  Weights are declared in
``config.param_dtype``; the residual stream is float32.  Each layer
keeps three pools under the one page table — K, V and the indexer's
keys (ops/decode_attention.py ``SparseDecodeAttentionOp``) — and no
ring, so any page table serves.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.config import FFConfig
from flexflow_tpu.initializers import NormInitializer
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.joyai_flash import held_experts_ffn


def build_keye_vl_decode(
    config: FFConfig,
    vocab: int = 151936,
    num_layers: int = 48,
    hidden: int = 2048,
    num_heads: int = 32,
    num_kv_heads: int = 4,
    head_dim: int = 128,
    index_heads: int = 16,
    index_head_dim: int = 64,
    index_topk: int = 2048,
    index_block: int = 512,
    expert_ff_dim: int = 768,
    n_routed_experts: int = 128,
    experts_held: int = 128,
    expert_offset: int = 0,
    experts_per_token: int = 8,
    rope_theta: float = 10_000_000.0,
    rms_eps: float = 1e-6,
    page_size: int = 64,
    pages_per_seq: int = 512,
    prefill_chunk: int = 512,
    kv_dtype: str = "bf16",
    head_init_std: Optional[float] = None,
):
    """``index_block`` is the indexer's key block and the chunk's
    attention block (``sa_config``'s ``kv_chunk_size``);
    ``prefill_chunk`` the largest chunk the expert layers' row bound
    holds.  ``head_init_std``: how a seeded model's head starts, a
    normal initialisation at that scale instead of Glorot's."""
    model = FFModel(config)
    b = config.batch_size
    # a frame routes B tokens, a prefill chunk ``prefill_chunk``: the row
    # bound holds every assignment of either
    expert_rows = max(b, prefill_chunk) * experts_per_token

    ids = model.create_tensor([b, 1], dtype="int32", name="token_ids")
    page_table = model.create_tensor([b, pages_per_seq], dtype="int32",
                                     name="page_table")
    seq_lens = model.create_tensor([b], dtype="int32", name="seq_lens")

    def norm(x, name):
        return model.rms_norm(x, eps=rms_eps, name=name)

    h = model.embedding(ids, vocab, hidden, aggr="none", name="tok_embed")
    h = model.cast(h, "float32", name="embed_f32")
    for i in range(num_layers):
        name = f"layer{i}"
        a = model.sparse_decode_attention(
            norm(h, f"{name}_attn_norm"), page_table, seq_lens,
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, index_heads=index_heads,
            index_head_dim=index_head_dim, index_topk=index_topk,
            index_block=index_block, index_norm_eps=rms_eps,
            page_size=page_size, pages_per_seq=pages_per_seq,
            rope_theta=rope_theta, qk_norm_eps=rms_eps,
            kv_dtype=kv_dtype,
            name=f"{name}_attn")
        h = model.add(h, a, name=f"{name}_res1")
        f = held_experts_ffn(
            model, norm(h, f"{name}_ffn_norm"), f"{name}_moe",
            hidden=hidden, expert_ff_dim=expert_ff_dim,
            n_routed_experts=n_routed_experts, experts_held=experts_held,
            expert_offset=expert_offset, experts_per_token=experts_per_token,
            expert_rows=expert_rows, routed_scaling_factor=1.0,
            score="softmax")
        h = model.add(h, f, name=f"{name}_res2")
    head_init = NormInitializer(stddev=head_init_std) if head_init_std else None
    model.dense(norm(h, "final_norm"), vocab, use_bias=False,
                kernel_initializer=head_init, name="lm_head")
    return model
