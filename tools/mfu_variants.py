"""CHIP-TIME experiment: run on the TPU (one command per chip call).

    PYTHONPATH=. python tools/mfu_variants.py baseline
    PYTHONPATH=. python tools/mfu_variants.py flash
    PYTHONPATH=. python tools/mfu_variants.py bf16probs

Compares the bench workload's step time under: the shipped einsum path
(now the compact-VJP backward), the Pallas flash kernel forced on at
seq=256 (below the measured fwd-only dispatch threshold — training may
still favor it), and the bf16-probs prototype (now productized as the
compact VJP; kept for A/B reference).  Feed the winner back into the
ops/attention.py dispatch heuristic.
"""
import sys
import time
import numpy as np
import jax
import jax.numpy as jnp
import jax.random as jrandom

import flexflow_tpu as ff
from flexflow_tpu.models import build_transformer

VARIANT = sys.argv[1] if len(sys.argv) > 1 else "baseline"

if VARIANT == "bf16probs":
    # keep the einsum path but store only a bf16 probs residual for the
    # backward (custom_vjp): halves the dominant [B,H,S,S] HBM traffic
    import importlib
    fa = importlib.import_module(
        'flexflow_tpu.kernels.flash_attention')
    import jax as _jax
    import jax.numpy as _jnp

    @_jax.custom_vjp
    def _attn_core(q, k, v, scale):
        s = _jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=_jnp.float32) * scale
        p = _jax.nn.softmax(s, axis=-1)
        return _jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)

    def _fwd(q, k, v, scale):
        s = _jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=_jnp.float32) * scale
        p = _jax.nn.softmax(s, axis=-1).astype(q.dtype)  # bf16 residual
        out = _jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return out, (q, k, v, p, _jnp.float32(scale))

    def _bwd(res, g):
        q, k, v, p, scale = res
        pf = p.astype(_jnp.float32)
        gv = _jnp.einsum("bhqk,bqhd->bkhd", pf.astype(g.dtype), g)
        gp = _jnp.einsum("bqhd,bkhd->bhqk", g, v,
                         preferred_element_type=_jnp.float32)
        # softmax vjp from the (bf16-rounded) probs
        gs = pf * (gp - _jnp.sum(pf * gp, axis=-1, keepdims=True))
        gs = gs * scale
        gq = _jnp.einsum("bhqk,bkhd->bqhd", gs.astype(q.dtype), k)
        gk = _jnp.einsum("bhqk,bqhd->bkhd", gs.astype(q.dtype), q)
        return gq, gk, gv, None

    _attn_core.defvjp(_fwd, _bwd)

    def _bf16probs(q, k, v, causal, scale, dropout_rate=0.0,
                   dropout_rng=None):
        assert not causal and dropout_rate == 0.0
        return _attn_core(q, k, v, scale)

    fa._xla_attention = _bf16probs

if VARIANT == "flash":
    # route the einsum fallback through the Pallas flash kernel: at
    # S=256 the fwd einsum is fine but autodiff saves the f32 probs
    # [B,H,Sq,Sk] per layer as residuals; flash's recompute backward
    # never materializes them
    import importlib
    fa = importlib.import_module(
        'flexflow_tpu.kernels.flash_attention')
    _orig = fa._xla_attention
    def _forced(q, k, v, causal, scale, dropout_rate=0.0, dropout_rng=None):
        if dropout_rate > 0.0:
            return _orig(q, k, v, causal, scale, dropout_rate, dropout_rng)
        return fa.flash_attention(q, k, v, causal=causal, scale=scale)
    fa._xla_attention = _forced

batch, seq, hidden, layers, heads, ff_dim = 64, 256, 512, 6, 8, 2048
dtype = "bfloat16"

cfg = ff.FFConfig(batch_size=batch, epochs=1, num_devices=1,
                  only_data_parallel=True, compute_dtype=dtype)
model = build_transformer(cfg, num_layers=layers, hidden=hidden,
                          num_heads=heads, ff_dim=ff_dim, seq_len=seq,
                          dtype=dtype)
model.compile(optimizer=ff.AdamOptimizer(alpha=1e-4),
              loss_type="mean_squared_error",
              metrics=["mean_squared_error"])

rng = np.random.default_rng(0)
import ml_dtypes
in_np = np.dtype(getattr(ml_dtypes, dtype))
N = 10
xs = rng.normal(size=(N, batch, seq, hidden)).astype(in_np)
ys = rng.normal(size=(N, batch, seq, hidden)).astype(np.float32)
xs_d = jax.device_put(xs, model.compiled.stacked_input_sharding(0))
ys_d = jax.device_put(ys, model.compiled.stacked_batch_sharding())

comp = model.compiled
params, opt_state, state = model.params, model.opt_state, model.state

for i in range(3):
    params, opt_state, state, losses, m = comp.train_steps(
        params, opt_state, state, jrandom.key(1000 + i), [xs_d], ys_d)
float(losses[-1])

times = []
for b in range(5):
    t0 = time.perf_counter()
    for i in range(3):
        params, opt_state, state, losses, m = comp.train_steps(
            params, opt_state, state, jrandom.key(b * 3 + i), [xs_d], ys_d)
    float(losses[-1])
    times.append((time.perf_counter() - t0) / (3 * N))

step = float(np.median(times))
fwd_flops = sum(n.op.flops() for n in model.graph.nodes.values())
peak = 1.97e14
print(f"{VARIANT}: {step*1e3:.3f} ms/step  "
      f"throughput={batch/step:.1f} samples/s  "
      f"MFU={3*fwd_flops/step/peak:.4f}")
