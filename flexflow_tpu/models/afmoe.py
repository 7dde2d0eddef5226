"""The decode graph of a mixture-of-experts language model of the
``afmoe`` family (Arcee Trinity): grouped-query attention whose layers
are sliding-window or global by a published list, a gated attention
output, sandwich RMS norms, leading dense layers and then sigmoid-routed
experts beside a shared one — as ONE CHIP of an expert-parallel group
serves it.

    h0 = Emb[ids] * sqrt(hidden)
    layer: h <- h + N2(Attn(N1 h));  h <- h + N4(FFN(N3 h))
    Attn(x): q = Nq(x Wq) [Hq, D], k = Nk(x Wk), v = x Wv [Hkv, D];
      rotary (half-split) on q and k in SLIDING layers only — a full
      layer carries no position signal at all; causal softmax over the
      last ``window`` positions in a sliding layer, over all in a full
      one; output (sigmoid(x Wg) * o) Wo
    FFN, leading dense layers: Wdown(silu(Wgate x) * Wup x)
    FFN, expert layers: s = sigmoid(x Wr) over ALL ``n_routed_experts``;
      the top ``experts_per_token`` of s + b chosen, weighed by s over
      the sum of the chosen, times ``route_scale``; the gated form of
      every expert CHOSEN AND HELD here, plus the shared expert
    logits = N(h_L) W_head

Three inputs as every decode graph has (``token_ids`` [B, 1],
``page_table`` [B, pages_per_seq], ``seq_lens`` [B]); B =
``config.batch_size`` sequence slots.  The chip holds ``experts_held``
of every layer's routed experts from ``expert_offset`` on (what the
others would add is left out; nothing stands in for their chips) and
``vocab`` rows of the vocabulary.  Weights are declared in
``config.param_dtype``; the residual stream is float32.

Two kinds of KV page under one table: a full layer's pool holds
``pages_per_seq`` pages a sequence, a sliding layer's ``ceil((window +
prefill_chunk) / page_size) + 1`` used as a ring
(ops/decode_attention.py), which needs slot-aligned page tables: an
executor that hands out pages from the free list (an oversubscribed
pool, prefix sharing) refuses to be built over this model's step.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from flexflow_tpu.config import FFConfig
from flexflow_tpu.initializers import ConstantInitializer, NormInitializer
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.joyai_flash import gated_ffn, held_experts_ffn

SLIDING, FULL = "sliding_attention", "full_attention"


def ring_pages(window: int, prefill_chunk: int, page_size: int) -> int:
    """Pages a sequence of a sliding layer's ring: the window, the chunk
    written before its queries attend, and one page of misalignment."""
    return -(-(window + max(prefill_chunk, 1)) // page_size) + 1


def build_afmoe_decode(
    config: FFConfig,
    vocab: int = 200192,
    layer_types: Sequence[str] = (SLIDING, SLIDING, SLIDING, FULL),
    num_layers: Optional[int] = None,
    first_dense_layers: int = 1,
    hidden: int = 3072,
    num_heads: int = 48,
    num_kv_heads: int = 8,
    head_dim: int = 128,
    window: int = 4096,
    dense_ff_dim: int = 12288,
    expert_ff_dim: int = 3072,
    n_routed_experts: int = 256,
    experts_held: int = 256,
    expert_offset: int = 0,
    experts_per_token: int = 4,
    n_shared_experts: int = 1,
    route_scale: float = 2.448,
    rope_theta: float = 10000.0,
    rms_eps: float = 1e-5,
    page_size: int = 64,
    pages_per_seq: int = 256,
    prefill_chunk: int = 512,
    kv_dtype: str = "bf16",
    use_kernel: bool = True,
    qk_norm_init: float = 1.0,
    head_init_std: Optional[float] = None,
):
    """``prefill_chunk`` is the LARGEST chunk the sliding layers' rings
    are sized for (``compiled_decode_step`` refuses a larger one).
    How a seeded model starts: ``qk_norm_init``, the constant the q and
    k norms' gains are initialised at, and ``head_init_std``, a normal
    initialisation of the head at that scale instead of Glorot's."""
    assert all(t in (SLIDING, FULL) for t in layer_types), layer_types
    assert num_layers in (None, len(layer_types)), (
        f"num_layers {num_layers} but {len(layer_types)} layer_types")
    model = FFModel(config)
    b = config.batch_size
    ring = min(pages_per_seq, ring_pages(window, prefill_chunk, page_size))
    qk_init = ConstantInitializer(qk_norm_init)
    # a frame routes B tokens, a prefill chunk ``prefill_chunk``: the row
    # bound holds every assignment of either (the dispatch never takes
    # more rows than there are assignments)
    expert_rows = max(b, prefill_chunk) * experts_per_token

    ids = model.create_tensor([b, 1], dtype="int32", name="token_ids")
    page_table = model.create_tensor([b, pages_per_seq], dtype="int32",
                                     name="page_table")
    seq_lens = model.create_tensor([b], dtype="int32", name="seq_lens")

    def norm(x, name):
        return model.rms_norm(x, eps=rms_eps, name=name)

    def experts_ffn(x, name):
        f = held_experts_ffn(
            model, x, f"{name}_moe", hidden=hidden,
            expert_ff_dim=expert_ff_dim, n_routed_experts=n_routed_experts,
            experts_held=experts_held, expert_offset=expert_offset,
            experts_per_token=experts_per_token, expert_rows=expert_rows,
            routed_scaling_factor=route_scale)
        if n_shared_experts:
            shared = gated_ffn(model, x, expert_ff_dim * n_shared_experts,
                               hidden, f"{name}_shared")
            f = model.add(f, shared, name=f"{name}_moe_sum")
        return f

    h = model.embedding(ids, vocab, hidden, aggr="none", name="tok_embed")
    h = model.scalar_multiply(model.cast(h, "float32", name="embed_f32"),
                              math.sqrt(hidden), name="embed_scale")
    for i, kind in enumerate(layer_types):
        name = f"layer{i}"
        sliding = kind == SLIDING
        a = model.grouped_decode_attention(
            norm(h, f"{name}_attn_norm"), page_table, seq_lens,
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=page_size,
            pages_per_seq=pages_per_seq,
            window=window if sliding else 0,
            ring_pages=ring if sliding else 0,
            rope_theta=rope_theta if sliding else None,
            qk_norm_eps=rms_eps, qk_norm_initializer=qk_init, gated=True,
            use_kernel=use_kernel, kv_dtype=kv_dtype,
            name=f"{name}_attn_window" if sliding else f"{name}_attn_global")
        h = model.add(h, norm(a, f"{name}_attn_out_norm"),
                      name=f"{name}_res1")
        x = norm(h, f"{name}_ffn_norm")
        if i < first_dense_layers:
            f = gated_ffn(model, x, dense_ff_dim, hidden, f"{name}_ffn")
        else:
            f = experts_ffn(x, name)
        h = model.add(h, norm(f, f"{name}_ffn_out_norm"), name=f"{name}_res2")
    head_init = NormInitializer(stddev=head_init_std) if head_init_std else None
    model.dense(norm(h, "final_norm"), vocab, use_bias=False,
                kernel_initializer=head_init, name="lm_head")
    return model
