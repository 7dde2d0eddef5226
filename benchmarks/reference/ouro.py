"""Plain reference of the looped language model the zoo's ``build_ouro``
trains: float32 throughout, ``default_matmul_precision("highest")``,
Python loops over loop steps and layers, no kernel, no checkpointing —
the published mathematics.

    h = Emb[ids]
    for t in 1..T:                          # T = 4; the SAME weights at every t
        for l in 1..L:
            a = RMS(h; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l     [B, S, H, D]
            q, k = rope(q), rope(k)   # half-split: for i < D/2 the pair
                                      # (x_i, x_{i+D/2}) turns by pos * theta^(-2i/D)
            o = causal_softmax(q k^T / sqrt(D)) v, times Wo_l
            h = h + RMS(o; g2_l)                 # a norm on the branch's OUTPUT too
            m = RMS(h; g3_l);  f = (silu(m Wgate_l) * (m Wup_l)) Wdown_l
            h = h + RMS(f; g4_l)
        h = RMS(h; g_final)                      # carried into step t + 1
        logits_t = h W_head;  lam_t = sigmoid(h w_gate + b_gate)
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j);  p_T = prod_{j<T}(1 - lam_j)
    objective = mean over positions [ sum_t p_t CE(logits_t, target)
                                      - beta H(p) ],  H(p) = -sum_t p_t ln p_t
    RMS(x; g) = x * rsqrt(mean(x^2) + 1e-6) * g; no bias but the gate's

The last position of a sequence has no target and is masked out of every
term.  Attention runs a block of queries at a time and each exit's
cross-entropy is reduced before the next exit's logits are made, so the
reference fits the chip beside the model at 4,096 tokens.

It reads the system's parameters by op name (``layer{l}_attn`` ...).
What the parameter shapes cannot tell is below, at the published values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# RMS norm (eps 1e-6) and causal attention a block of queries at a time:
# the same plain functions the other reference of this family of blocks has
from benchmarks.reference.joyai_flash import causal_attention, rms

LOOP_STEPS = 4            # total_ut_steps
ROPE_THETA = 1_000_000.0
EXIT_BETA = 0.1           # assumed (the configuration file lists it)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rotary(x, theta=ROPE_THETA):
    """x [B, S, H, D] at positions 0..S-1; pair i = (x[i], x[i + D/2])
    turns by position * theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, p, theta=ROPE_THETA):
    """Rotary causal self-attention of x [B, S, E] with the projections
    ``wq``, ``wk``, ``wv`` [E, H, D] and ``wo`` [H, D, E]."""
    q, k, v = (jnp.einsum("bse,ehd->bshd", x, p[n]) for n in ("wq", "wk", "wv"))
    out = causal_attention(rotary(q, theta), rotary(k, theta), v)
    return jnp.einsum("bshd,hde->bse", out, p["wo"])


@jax.jit
def _embed(tok, ids):
    return tok["table"][ids]


@jax.jit
def _layer(h, attn_norm, attn, attn_out_norm, ffn_norm, gate, up, down,
           ffn_out_norm):
    o = attention(rms(h, attn_norm["gamma"]), attn)
    h = h + rms(o, attn_out_norm["gamma"])
    m = rms(h, ffn_norm["gamma"])
    f = (jax.nn.silu(m @ gate["kernel"]) * (m @ up["kernel"])) @ down["kernel"]
    return h + rms(f, ffn_out_norm["gamma"])


@jax.jit
def _exit(h, final_norm, head, gate):
    """(the normed state, logits [B, S, V], lam [B, S])."""
    h = rms(h, final_norm["gamma"])
    z = (h @ gate["kernel"])[..., 0] + gate["bias"][0]
    return h, h @ head["kernel"], jax.nn.sigmoid(z)


@jax.jit
def _token_nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _steps(params, ids):
    """Yields (logits_t, lam_t) for t = 1..T."""
    p = _f32(params)
    layers = sum(k.startswith("layer") and k.endswith("_attn") for k in p)
    h = _embed(p["tok_embed"], jnp.asarray(ids, jnp.int32))
    for _ in range(LOOP_STEPS):
        for l in range(layers):
            h = _layer(h, *(p[f"layer{l}_{part}"] for part in (
                "attn_norm", "attn", "attn_out_norm", "ffn_norm", "ffn_gate",
                "ffn_up", "ffn_down", "ffn_out_norm")))
        h, logits, lam = _exit(h, p["final_norm"], p["lm_head"],
                               p["exit_gate"])
        yield logits, lam


def exit_distribution(lam):
    """lam [T, ...] -> p [T, ...]: the chance of leaving at each step;
    whoever has not left by the last step leaves there."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, stay[-1:]], axis=0)


def objective(nll, lam, counted, beta=EXIT_BETA):
    """nll, lam [T, B, S] -> the mean over the counted positions of the
    expected loss under the exit distribution less beta times its
    entropy."""
    p = exit_distribution(lam)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=0)
    per_position = jnp.sum(p * nll, axis=0) - beta * entropy
    return jnp.sum(jnp.where(counted, per_position, 0.0)) / jnp.sum(counted)


def exits(params, ids):
    """ids [B, S] -> (logits [T, B, S, V], lam [T, B, S]), float32.  All
    T logits at once: for the tests' sizes."""
    with jax.default_matmul_precision("highest"):
        logits, lam = zip(*_steps(params, ids))
        return jnp.stack(logits), jnp.stack(lam)


def forward(params, ids):
    """ids [B, S] int32 -> the last exit's logits [B, S, vocab] float32:
    what the model answers with at ``early_exit_threshold`` 1."""
    with jax.default_matmul_precision("highest"):
        for logits, _ in _steps(params, ids):
            pass
        return logits


def loss(params, ids, labels, beta=EXIT_BETA):
    """The objective ``fit`` trains: ``labels[:, i]`` is the target of
    position i; the last position is not counted."""
    labels = jnp.asarray(labels, jnp.int32)
    seq = labels.shape[1]
    counted = jnp.broadcast_to(jnp.arange(seq) < seq - 1, labels.shape)
    with jax.default_matmul_precision("highest"):
        nll, lam = zip(*((_token_nll(logits, labels), lam)
                         for logits, lam in _steps(params, ids)))
        return objective(jnp.stack(nll), jnp.stack(lam), counted, beta)
