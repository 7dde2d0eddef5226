#!/usr/bin/env python
"""Benchmark entry point — ONE process that measures and prints ONE
JSON line.

Measures training throughput (samples/s) and MFU of the flagship model
(Transformer encoder, the reference's examples/cpp/Transformer workload:
transformer.cc:112-211 self-reports THROUGHPUT the same way) on the
accelerator jax finds.  The reference repo publishes no absolute
numbers (BASELINE.md), so vs_baseline reports delivered MFU against a
0.40 good-utilization bar for this workload.

A chip belongs to one process, so there is no probe and no child: this
process initializes jax, measures, prints.  Without a TPU it exits
non-zero; it never prints a number it did not just measure.  Only an
explicit ``JAX_PLATFORMS=cpu`` runs a CPU dry run of the same code at a
tiny size — its record says ``platform: "cpu"``, goes under its own
metric name and carries no MFU, because a CPU timing is not a device
metric.
"""

import json
import os
import sys
import time

import numpy as np

# bf16 matmul peak FLOP/s of one chip, keyed by jax's ``device_kind``
# (Google Cloud TPU documentation, per-chip figures).  A kind that is
# not here is an error, not a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 2.75e14,
    "TPU v5 lite": 1.97e14,  # v5e
    "TPU v5": 4.59e14,  # v5p
    "TPU v6 lite": 9.18e14,  # v6e
}


def main():
    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    place_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    kind = devices[0].device_kind
    if platform == "tpu":
        if kind not in PEAK_BF16_FLOPS:
            sys.exit(f"bench: no peak recorded for device_kind {kind!r}; "
                     f"add it to PEAK_BF16_FLOPS with its source")
        batch, seq, hidden, layers, heads, ff_dim = 64, 256, 512, 6, 8, 2048
        steps, trace_n, warmup, blocks = 30, 10, 3, 3
        dtype = "bfloat16"
    elif platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        batch, seq, hidden, layers, heads, ff_dim = 8, 32, 64, 2, 4, 128
        steps, trace_n, warmup, blocks = 5, 5, 1, 1
        dtype = "float32"
    else:
        sys.exit(f"bench: no TPU found (jax platform {platform!r}); set "
                 f"JAX_PLATFORMS=cpu to ask for the CPU dry run")

    import flexflow_tpu as ff
    from flexflow_tpu.models import build_transformer

    cfg = ff.FFConfig(
        batch_size=batch,
        epochs=1,
        num_devices=len(devices),
        only_data_parallel=len(devices) == 1,
        compute_dtype=dtype,
    )
    # bf16 activation stream on TPU: ops cast outputs back to the input
    # tensor's dtype, so a bf16 input keeps every inter-op activation at
    # 2 bytes (half the HBM traffic); matmuls still accumulate f32 and
    # loss/metrics upcast internally
    model = build_transformer(
        cfg, num_layers=layers, hidden=hidden, num_heads=heads,
        ff_dim=ff_dim, seq_len=seq, dtype=dtype,
    )
    model.compile(
        optimizer=ff.AdamOptimizer(alpha=1e-4),
        loss_type="mean_squared_error",
        metrics=["mean_squared_error"],
    )

    rng = np.random.default_rng(0)
    # N distinct batches stacked on a leading step axis: one
    # train_steps() call scans all N inside a single compiled program —
    # the XLA analogue of the reference's Legion iteration tracing
    # (flexflow_cffi.py:1867-1874), amortizing per-call dispatch
    import ml_dtypes

    in_np = np.float32 if dtype == "float32" else np.dtype(
        getattr(ml_dtypes, dtype))
    xs = rng.normal(size=(trace_n, batch, seq, hidden)).astype(in_np)
    ys = rng.normal(size=(trace_n, batch, seq, hidden)).astype(np.float32)
    xs_d = jax.device_put(xs, model.compiled.stacked_input_sharding(0))
    ys_d = jax.device_put(ys, model.compiled.stacked_batch_sharding())

    import jax.random as jrandom

    # warmup: the first call compiles
    params, opt_state, state = model.params, model.opt_state, model.state
    t0 = time.perf_counter()
    for i in range(warmup):
        params, opt_state, state, losses, m = model.compiled.train_steps(
            params, opt_state, state, jrandom.key(1000 + i), [xs_d], ys_d
        )
    float(losses[-1])  # host readback: the work is done
    warmup_s = time.perf_counter() - t0

    # Timed block: reps calls dispatched back-to-back (async dispatch
    # keeps the device pipelined, as a real training loop would), one
    # readback fence at the end.  The block repeats and the MEDIAN block
    # time is reported.  Per-call fencing would serialize the pipeline
    # and measure round-trips, not training.
    reps = max(1, steps // trace_n)
    block_times = []
    for _ in range(blocks):
        t0 = time.perf_counter()
        for i in range(reps):
            params, opt_state, state, losses, m = model.compiled.train_steps(
                params, opt_state, state, jrandom.key(i + 1), [xs_d], ys_d
            )
        float(losses[-1])
        block_times.append(time.perf_counter() - t0)
    elapsed = float(np.median(block_times))
    steps = reps * trace_n
    throughput = steps * batch / elapsed

    record = {
        "metric": "transformer_train_throughput",
        "value": round(throughput, 2),
        "unit": "samples/s",
        "platform": platform,
        "device_kind": kind,
        "device_count": len(devices),
        "warmup_s": round(warmup_s, 2),  # set-up: compile included
    }
    if platform == "tpu":
        # MFU = model FLOPs actually trained / elapsed / chip peak.
        # Forward FLOPs come from the PCG's own per-op estimates (the
        # same numbers the cost model ranks strategies with); training
        # ≈ 3x forward (bwd does the two grad matmuls per fwd matmul).
        fwd_flops = sum(n.op.flops() for n in model.graph.nodes.values())
        peak = PEAK_BF16_FLOPS[kind] * len(devices)
        mfu = 3.0 * fwd_flops * steps / elapsed / peak
        record["mfu"] = round(mfu, 4)
        # vs_baseline: the reference publishes no absolute numbers
        # (BASELINE.md); its per-chip contract is utilization, so report
        # the ratio of delivered MFU to a 40% good-MFU bar.
        record["vs_baseline"] = round(mfu / 0.40, 3)
    else:
        record["metric"] = "transformer_train_cpu_dry_run"
    print(json.dumps(record))


if __name__ == "__main__":
    main()
