"""Plain reference of the OPT-350m-style decoder the zoo's ``build_gpt``
and ``build_gpt_decode`` compute: token + learned absolute position
embeddings, N x (causal attention, add, LayerNorm, ReLU feed-forward,
add, LayerNorm) — the post-LN block of ``do_layer_norm_before: false``
— a final LayerNorm and an untied head.  float32 throughout,
``default_matmul_precision("highest")``, no kernel, cache or batching.
It reads the system's parameters by op name (``layer{i}_mha`` ...), so
the same file checks the trainer and the decode server.

Departures from the published OPT-350m that the SYSTEM makes (and this
file follows, since it is the system's reference): no 512<->1024
embedding projection and an untied head, a final LayerNorm, no position
offset of 2, no attention-projection biases, no dropout.

Layers run one jitted call each (all layers share shapes), so the
reference compiles one block, not N.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5  # flexflow_tpu.ops.norm.LayerNormOp's default


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["gamma"] + p["beta"]


@jax.jit
def _embed(tok, pos, ids):
    positions = jnp.arange(ids.shape[1])
    return tok["table"][ids] + pos["table"][positions][None]


@jax.jit
def _block(x, mha, ln1, ff1, ff2, ln2):
    s, d = x.shape[1], mha["wq"].shape[-1]
    q = jnp.einsum("bse,ehd->bshd", x, mha["wq"])
    k = jnp.einsum("bse,ehd->bshd", x, mha["wk"])
    v = jnp.einsum("bse,ehd->bshd", x, mha["wv"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    x = _layer_norm(x + jnp.einsum("bshd,hde->bse", a, mha["wo"]), ln1)
    f = jax.nn.relu(x @ ff1["kernel"] + ff1["bias"])
    return _layer_norm(x + f @ ff2["kernel"] + ff2["bias"], ln2)


@jax.jit
def _head(x, final_ln, lm_head):
    return _layer_norm(x, final_ln) @ lm_head["kernel"]


@jax.jit
def _sparse_cce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward(params, ids):
    """ids [B, S] int32 -> logits [B, S, vocab] float32."""
    num_layers = sum(k.endswith("_mha") for k in params)
    with jax.default_matmul_precision("highest"):
        x = _embed(_f32(params["tok_embed"]), _f32(params["pos_embed"]),
                   jnp.asarray(ids, jnp.int32))
        for i in range(num_layers):
            x = _block(x, *(_f32(params[f"layer{i}_{n}"])
                            for n in ("mha", "ln1", "ff1", "ff2", "ln2")))
        return _head(x, _f32(params["final_ln"]), _f32(params["lm_head"]))


def loss(params, ids, labels):
    """Mean per-token sparse categorical cross-entropy, as ``fit`` trains."""
    with jax.default_matmul_precision("highest"):
        return _sparse_cce(forward(params, ids),
                           jnp.asarray(labels, jnp.int32))
