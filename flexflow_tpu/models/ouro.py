"""A looped ("universal-transformer") causal language model of the Ouro
family: ONE stack of ``num_layers`` layers run ``loop_steps`` times over
the same weights, an exit after every loop step, and the
entropy-regularised objective over the exits (ops/exit_loss.py).

    h = Emb[ids]
    for t in 1..T:                                   # the SAME weights at every t
        for l in 1..L:
            q, k, v = RMS(h; g1_l) (Wq_l, Wk_l, Wv_l);  q, k turn by their
              positions (half-split rotary);  o = causal attention, times Wo_l
            h = h + RMS(o; g2_l)                     # sandwich: a norm on the
            f = Wdown_l(silu(Wgate_l m) * Wup_l m),  m = RMS(h; g3_l)
            h = h + RMS(f; g4_l)                     #   branch's OUTPUT too
        h = RMS(h; g_final)                          # carried into step t + 1
        logits_t = h W_head;  z_t = h w_gate + b_gate

In the graph the loop is unrolled into T x L blocks; block (1, l) owns
the weights (``layer<l>_attn`` ...) and block (t, l) for t > 1
(``loop<t>_layer<l>_attn`` ...) is built with ``weights_of`` naming its
ops, so the parameter tree, the optimizer state and a checkpoint hold L
layers.
Loop step t lowers under the name scope ``ff.loop<t>``, the exits' head,
gate and objective under ``ff.exit``; every block is a
``FFModel.remat_block``.  No bias but the gate's; every norm an RMS norm.
"""

from __future__ import annotations

from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.joyai_flash import gated_ffn


def build_ouro(
    config: FFConfig,
    vocab: int = 49152,
    num_layers: int = 48,
    hidden: int = 2048,
    num_heads: int = 16,
    head_dim: int = 128,
    ff_dim: int = 5632,
    loop_steps: int = 4,
    rope_theta: float = 1_000_000.0,
    rms_eps: float = 1e-6,
    seq_len: int = 4096,
    exit_beta: float = 0.1,
):
    assert hidden == num_heads * head_dim, "plain multi-head: d = H x head_dim"
    assert loop_steps >= 2, "an exit distribution needs two exits"
    model = FFModel(config)

    def tied(part, t):
        """Names of a weighted op at loop step ``t``: step 1 owns the
        weights under the plain name, a later step reads them."""
        if t == 1:
            return dict(name=part)
        return dict(name=f"loop{t}_{part}", weights_of=part)

    def norm(x, part, t):
        return model.rms_norm(x, eps=rms_eps, **tied(part, t))

    def block(h, t, l):
        """Layer ``l`` at loop step ``t``."""
        layer = f"layer{l}"
        a = norm(h, f"{layer}_attn_norm", t)
        a = model.multihead_attention(
            a, a, a, hidden, num_heads, causal=True, rope_theta=rope_theta,
            **tied(f"{layer}_attn", t))
        h = model.add(h, norm(a, f"{layer}_attn_out_norm", t),
                      name=f"loop{t}_{layer}_res1")
        m = norm(h, f"{layer}_ffn_norm", t)
        f = gated_ffn(model, m, ff_dim, hidden, **tied(f"{layer}_ffn", t))
        return model.add(h, norm(f, f"{layer}_ffn_out_norm", t),
                         name=f"loop{t}_{layer}_res2")

    ids = model.create_tensor([config.batch_size, seq_len], dtype="int32",
                              name="input_ids")
    h = model.embedding(ids, vocab, hidden, aggr="none", name="tok_embed")
    logits, gates = [], []
    for t in range(1, loop_steps + 1):
        with model.block_scope(f"ff.loop{t}"):
            for l in range(num_layers):
                with model.remat_block():
                    h = block(h, t, l)
            h = norm(h, "final_norm", t)
        with model.block_scope("ff.exit"):
            logits.append(model.dense(h, vocab, use_bias=False,
                                      **tied("lm_head", t)))
            gates.append(model.dense(h, 1, **tied("exit_gate", t)))
    with model.block_scope("ff.exit"):
        model.exit_loss(logits, gates, ids, beta=exit_beta, name="exit_loss")
    return model
