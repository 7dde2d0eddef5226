"""Plain reference of the JoyAI-LLM-Flash block as the zoo's
``build_joyai_flash`` trains it on one chip of an expert-parallel group:
float32 throughout, ``default_matmul_precision("highest")``, no kernel,
no dispatch, no capacity — the published mathematics.

    block:  h <- h + MLA(RMS(h));  h <- h + FFN(RMS(h))
    RMS(x) = x * rsqrt(mean(x^2) + 1e-6) * gamma; no bias anywhere
    MLA:    c_q = RMS(x W_qa); q = c_q W_qb -> heads x [nope | rope];
            [c_kv | k_rope] = x W_kva; [k_nope | v] x heads = RMS(c_kv) W_kvb;
            rotary on q_rope (each head) and on the one shared k_rope,
            interleaved pairs (2i, 2i+1), theta 32e6, no scaling;
            softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope)), causal
    dense FFN:  W_down(silu(W_gate x) * W_up x)
    expert FFN: s = sigmoid(x W_r); the top 8 of s + b are chosen and
            weighed s_i / (sum of the chosen s + 1e-20) * 2.5;
            y = sum over experts CHOSEN AND HELD of w_i E_i(x) + E_shared(x)
    head:   RMS(h_L) W_head; loss = mean token cross-entropy
    MTP:    g_i = W_eh [RMS_e(Emb(t_{i+1})) ; RMS_h(h_i)] (h before the final
            norm), one expert block on g, logits' = RMS'(.) W_head with the
            main Emb and W_head, target t_{i+2}, the last two positions
            masked; loss = L_main + 0.3 L_mtp

The share: the experts' weights hold ``E_held`` experts, which are the
experts ``expert_offset .. expert_offset + E_held - 1`` of the router's
width; what the others would add is left out, here as in the program,
and where some are left out the routing weights carry no gradient.
Held experts are computed DENSELY for every token and masked by the
routing weight (one expert at a time, so 4,096 tokens fit); attention in
blocks of queries for the same reason.

It reads the system's parameters by op name (``layer{i}_mla`` ...).  What
the parameter shapes cannot tell is below, at the published values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOP_K = 8                 # num_experts_per_tok
ROUTED_SCALE = 2.5        # routed_scaling_factor
ROPE_THETA = 32_000_000.0
RMS_EPS = 1e-6
MTP_LAMBDA = 0.3          # assumed (the configuration file lists it)
Q_BLOCK = 512             # queries a block of the reference attention


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, gamma, eps=RMS_EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def rotary(x, theta=ROPE_THETA):
    """x [B, S, H, R] at positions 0..S-1; pair i = (x[2i], x[2i+1])
    turns by position * theta^(-2i/R)."""
    s, r = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v):
    """q, k [B, S, H, Dqk], v [B, S, H, Dv] -> [B, S, H, Dv]; a block of
    queries at a time, so the scores are [B, H, block, S]."""
    b, s, h, d = q.shape
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)

    def one(args):
        qb, start = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(float(d))
        rows = start + jnp.arange(block)
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    q_blocks = q.reshape(b, s // block, block, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (q_blocks, jnp.arange(0, s, block)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def mla(x, p):
    rkv = p["kv_norm"].shape[0]
    rope = p["w_kva"].shape[1] - rkv
    nope = p["w_qb"].shape[2] - rope
    heads = p["w_qb"].shape[1]
    q = jnp.einsum("bsr,rhd->bshd", rms(x @ p["w_qa"], p["q_norm"]), p["w_qb"])
    kv = x @ p["w_kva"]
    k_v = jnp.einsum("bsr,rhd->bshd", rms(kv[..., :rkv], p["kv_norm"]),
                     p["w_kvb"])
    k_rope = rotary(kv[..., None, rkv:])
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], axis=-1)
    k = jnp.concatenate(
        [k_v[..., :nope],
         jnp.broadcast_to(k_rope, k_rope.shape[:2] + (heads, rope))], axis=-1)
    out = causal_attention(q, k, k_v[..., nope:])
    return jnp.einsum("bshd,hde->bse", out, p["w_o"])


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing_weights(x, router, top_k=TOP_K, scale=ROUTED_SCALE):
    """[..., E]: each token's weight for every expert of the router's
    width, 0 where the expert is not among its ``top_k``."""
    s = jax.nn.sigmoid(x @ router["kernel"])
    # the correction bias is the system's STATE, zeros until the
    # balancing rule has moved it: parameters alone hold none
    _, chosen = jax.lax.top_k(s + router.get("bias", 0.0), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scale
    on = jax.nn.one_hot(chosen, s.shape[-1], dtype=w.dtype)  # [..., k, E]
    return jnp.einsum("...k,...ke->...e", w, on)


def routed_part(x, router, gate, up, down, expert_offset=0, top_k=TOP_K,
                scale=ROUTED_SCALE):
    """What the experts in ``gate`` / ``up`` / ``down`` ([E_held, ...]:
    experts ``expert_offset`` .. of the router's width) add: each one
    computed for every token, times the token's weight for it."""
    held = gate.shape[0]
    w = routing_weights(x, router, top_k, scale)
    if held < w.shape[-1]:
        # a share's partial sum is no signal to learn the routing from (it
        # pulls every token toward the experts that happen to be held):
        # the weights are handed on without a gradient, as in the program
        w = jax.lax.stop_gradient(w)
    w = jax.lax.dynamic_slice_in_dim(w, expert_offset, held, axis=-1)

    def add(y, args):
        w_e, g, u, d = args
        return y + w_e[..., None] * gated(x, g, u, d), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (jnp.moveaxis(w, -1, 0), gate, up, down))
    return y


@jax.jit
def _embed(tok, ids):
    return tok["table"][ids]


@jax.jit
def _dense_block(h, attn_norm, attn, ffn_norm, gate, up, down):
    h = h + mla(rms(h, attn_norm["gamma"]), attn)
    x = rms(h, ffn_norm["gamma"])
    return h + gated(x, gate["kernel"], up["kernel"], down["kernel"])


@functools.partial(jax.jit, static_argnames=("expert_offset",))
def _expert_block(h, attn_norm, attn, ffn_norm, router, e_gate, e_up, e_down,
                  s_gate, s_up, s_down, expert_offset=0):
    h = h + mla(rms(h, attn_norm["gamma"]), attn)
    x = rms(h, ffn_norm["gamma"])
    y = routed_part(x, router, e_gate["kernel"], e_up["kernel"],
                    e_down["kernel"], expert_offset)
    return h + y + gated(x, s_gate["kernel"], s_up["kernel"], s_down["kernel"])


@jax.jit
def _head(h, norm, head):
    return rms(h, norm["gamma"]) @ head["kernel"]


@jax.jit
def _mtp_input(ahead, h, enorm, hnorm, eh_proj):
    return jnp.concatenate([rms(ahead, enorm["gamma"]), rms(h, hnorm["gamma"])],
                           axis=-1) @ eh_proj["kernel"]


@jax.jit
def _mean_nll(logits, labels, counted):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(counted, nll, 0.0)) / jnp.sum(counted)


def _block(params, h, name, expert_offset):
    p = {k[len(name) + 1:]: _f32(v) for k, v in params.items()
         if k.startswith(name + "_")}
    common = (h, p["attn_norm"], p["mla"], p["ffn_norm"])
    if "ffn_gate" in p:
        return _dense_block(*common, p["ffn_gate"], p["ffn_up"], p["ffn_down"])
    return _expert_block(
        *common, p["moe_router"], p["moe_experts_gate"], p["moe_experts_up"],
        p["moe_experts_down"], p["shared_gate"], p["shared_up"],
        p["shared_down"], expert_offset=expert_offset)


def _trunk(params, ids, expert_offset):
    """(embeddings [B, S, D], h_L before the final norm)."""
    layers = sum(k.startswith("layer") and k.endswith("_mla") for k in params)
    emb = _embed(_f32(params["tok_embed"]), jnp.asarray(ids, jnp.int32))
    h = emb
    for i in range(layers):
        h = _block(params, h, f"layer{i}", expert_offset)
    return emb, h


def forward(params, ids, expert_offset: int = 0):
    """ids [B, S] int32 -> the main head's logits [B, S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        _, h = _trunk(params, ids, expert_offset)
        return _head(h, _f32(params["final_norm"]), _f32(params["lm_head"]))


def losses(params, ids, labels, expert_offset: int = 0):
    """(L_main, L_mtp): mean token cross-entropy of the main head against
    ``labels``, and of the MTP head against the ids two ahead (0.0 for
    parameters that hold no MTP module)."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        emb, h = _trunk(params, ids, expert_offset)
        head = _f32(params["lm_head"])
        everywhere = jnp.ones(ids.shape, bool)
        main = _mean_nll(_head(h, _f32(params["final_norm"]), head),
                         jnp.asarray(labels, jnp.int32), everywhere)
        if "mtp_eh_proj" not in params:
            return main, jnp.float32(0.0)
        # Emb(t_{i+1}): the embeddings one position ahead (the last row is
        # masked out of the loss below, whatever it holds)
        ahead = jnp.roll(emb, -1, axis=1)
        g = _mtp_input(ahead, h, *(_f32(params[n]) for n in
                                   ("mtp_enorm", "mtp_hnorm", "mtp_eh_proj")))
        g = _block(params, g, "mtp", expert_offset)
        seq = ids.shape[1]
        counted = jnp.broadcast_to(jnp.arange(seq) < seq - 2, ids.shape)
        mtp = _mean_nll(_head(g, _f32(params["mtp_final_norm"]), head),
                        jnp.roll(ids, -2, axis=1), counted)
        return main, mtp


def loss(params, ids, labels, expert_offset: int = 0):
    """L_main + 0.3 L_mtp, as ``fit`` trains."""
    main, mtp = losses(params, ids, labels, expert_offset)
    return main + MTP_LAMBDA * mtp
