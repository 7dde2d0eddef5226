#!/usr/bin/env python
"""Long-context attention benchmark artifact (writes BENCH_LONGCTX.*).

The reference cannot partition MHA's sequence dimension at all
(SURVEY.md §5: no ring/blockwise attention — its cuDNN MHA kernel
materializes the [Sq, Sk] scores), so long-context is a new capability
of this framework: the Pallas flash kernel keeps HBM at O(S·block)
single-chip, and ring attention (parallel/ring_attention.py) spreads S
across the mesh's seq axis for multi-chip.

This artifact measures, on the live accelerator:
  * flash-attention fwd+bwd wall time vs the materializing XLA path
    across sequence lengths (the XLA path falls off a memory cliff
    around S=8k on a 16G chip and OOMs after);
  * a full causal-transformer training step at long S through the
    ordinary FFModel.compile()/train path.

Timing notes: each measurement scans `iters` iterations inside ONE
jitted call, so dispatch cost is paid once, and a scalar readback
fences the clock.
"""

from __future__ import annotations

import argparse
import json
import time


def _fence_timer(f, *args, iters=8):
    """Seconds per application of f, with f applied `iters` times
    inside one jitted scan (serial data dependence via the carry)."""
    import jax
    import jax.numpy as jnp

    def many(*a):
        def body(c, _):
            o = f(a[0] + c, *a[1:])
            return o.reshape(-1)[0].astype(jnp.bfloat16), None

        c, _ = jax.lax.scan(body, jnp.bfloat16(0), None, length=iters)
        return c

    j = jax.jit(many)
    float(j(*args))  # compile + settle
    t0 = time.perf_counter()
    float(j(*args))
    float(j(*args))
    return (time.perf_counter() - t0) / (2 * iters)


def attention_rows(seqs, heads, head_dim, tokens):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import (
        _xla_attention,
        flash_attention,
    )

    key = jax.random.key(0)
    scale = 1.0 / head_dim**0.5
    rows = []
    for s in seqs:
        b = max(1, tokens // s)
        shape = (b, s, heads, head_dim)
        q = jax.random.normal(key, shape, jnp.bfloat16)
        k = jax.random.normal(key, shape, jnp.bfloat16)
        v = jax.random.normal(key, shape, jnp.bfloat16)

        def fl_loss(q, k, v):
            return flash_attention(q, k, v, causal=True, scale=scale)

        def xla_loss(q, k, v):
            return _xla_attention(q, k, v, True, scale)

        def grad_of(f):
            def g(q, k, v):
                return jax.grad(
                    lambda q, k, v: f(q, k, v).astype(jnp.float32).mean(),
                    argnums=(0, 1, 2),
                )(q, k, v)[0]

            return g

        row = {"seq": s, "batch": b}
        row["flash_ms"] = round(_fence_timer(grad_of(fl_loss), q, k, v) * 1e3, 3)
        # the einsum path still materializes the [Sq,Sk] block per
        # layer: fp32 scores transiently in the forward (4 B/elt) plus
        # the compact VJP's probs-at-stream-dtype residual (2 B/elt in
        # bf16 — the fp32 logits+probs RESIDUALS are gone since the
        # compact backward); past the cliff it OOMs — record that
        logits_gb = b * heads * s * s * (4 + 2) / 1e9
        if logits_gb <= 8.0:
            try:
                row["xla_ms"] = round(
                    _fence_timer(grad_of(xla_loss), q, k, v) * 1e3, 3)
                row["ratio"] = round(row["xla_ms"] / row["flash_ms"], 2)
            except Exception as e:
                row["xla_ms"] = f"OOM ({type(e).__name__})"
        else:
            row["xla_ms"] = f"skipped ({logits_gb:.0f} GB logits)"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def train_step_row(seq, hidden, heads, layers):
    """Full causal-transformer training step at long S through the
    ordinary compile/fit path (trace_steps amortizes dispatch)."""
    import numpy as np

    import jax
    import jax.random as jrandom

    import flexflow_tpu as ff
    from flexflow_tpu.models import build_transformer

    cfg = ff.FFConfig(batch_size=1, num_devices=1, only_data_parallel=True,
                      compute_dtype="bfloat16")
    model = build_transformer(cfg, num_layers=layers, hidden=hidden,
                              num_heads=heads, ff_dim=4 * hidden,
                              seq_len=seq, layer_norm=True, causal=True)
    model.compile(optimizer=ff.AdamOptimizer(alpha=1e-4),
                  loss_type="mean_squared_error", metrics=[])
    rng = np.random.default_rng(0)
    n_tr = 4
    xs = rng.normal(size=(n_tr, 1, seq, hidden)).astype(np.float32)
    ys = rng.normal(size=(n_tr, 1, seq, hidden)).astype(np.float32)
    xs_d = jax.device_put(xs, model.compiled.stacked_input_sharding(0))
    ys_d = jax.device_put(ys, model.compiled.stacked_batch_sharding())
    params, opt_state, state = model.params, model.opt_state, model.state
    for i in range(2):
        params, opt_state, state, losses, _ = model.compiled.train_steps(
            params, opt_state, state, jrandom.key(i), [xs_d], ys_d)
    float(losses[-1])
    t0 = time.perf_counter()
    reps = 3
    for i in range(reps):
        params, opt_state, state, losses, _ = model.compiled.train_steps(
            params, opt_state, state, jrandom.key(9 + i), [xs_d], ys_d)
    float(losses[-1])
    sec = (time.perf_counter() - t0) / (reps * n_tr)
    row = {
        "model": f"{layers}L causal transformer h{hidden}",
        "seq": seq,
        "step_ms": round(sec * 1e3, 1),
        "tokens_per_s": round(seq / sec),
    }
    print(json.dumps(row), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096,8192,16384,32768")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16384,
                    help="tokens per measured batch (batch = tokens/seq)")
    ap.add_argument("--train-seq", type=int, default=16384)
    args = ap.parse_args()

    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    place_compile_cache()
    import jax

    backend = jax.devices()[0].platform
    seqs = [int(s) for s in args.seqs.split(",")]
    rows = attention_rows(seqs, args.heads, args.head_dim, args.tokens)
    train = train_step_row(args.train_seq, hidden=args.heads * args.head_dim,
                           heads=args.heads, layers=2)

    report = {"backend": backend, "heads": args.heads,
              "head_dim": args.head_dim, "attention": rows, "train": train}
    with open("BENCH_LONGCTX.json", "w") as f:
        json.dump(report, f, indent=1)
    lines = [
        "# BENCH_LONGCTX — long-context attention on the live chip",
        "",
        "The reference's MHA cannot split or block the sequence dim "
        "(SURVEY.md §5); its kernel materializes [Sq,Sk].  Rows compare "
        "this framework's Pallas flash kernel (O(S*block) memory) with "
        "the materializing XLA path, causal, fwd+bwd, bf16, "
        f"{args.heads} heads x {args.head_dim}.",
        "",
        "| seq | batch | flash fwd+bwd ms | materializing ms | ratio |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(f"| {r['seq']} | {r['batch']} | {r['flash_ms']} | "
                     f"{r['xla_ms']} | {r.get('ratio', '—')} |")
    lines += [
        "",
        f"Full training step, {train['model']}, S={train['seq']}: "
        f"{train['step_ms']} ms/step ({train['tokens_per_s']} tokens/s) "
        f"on {backend}.",
        "",
        "Multi-chip sequence parallelism — ring attention over the mesh "
        "seq axis, and the Ulysses all-to-all head exchange "
        "(sp_mode=\"ulysses\") — is exercised by tests/test_kernels.py "
        "and __graft_entry__.dryrun_multichip on the 8-device mesh.",
    ]
    with open("BENCH_LONGCTX.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print("# wrote BENCH_LONGCTX.json / BENCH_LONGCTX.md")


if __name__ == "__main__":
    main()
