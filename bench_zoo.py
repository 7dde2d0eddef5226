#!/usr/bin/env python
"""Model-zoo training-throughput artifact (writes BENCH_ZOO.json/md).

The reference's baseline contract row 1 (BASELINE.md) is the
self-reported `THROUGHPUT = %.2f samples/s` every C++ example prints
after timed epochs (transformer.cc:208-210, resnet.cc:159,
inception.cc:226, resnext.cc:135, dlrm.cc, xdl.cc:197,
candle_uno.cc:173, mlp.cc:88, moe.cc:216).  This runs each model
family of the zoo on the live accelerator at the reference example's
default shapes and records the same number.

Usage: python bench_zoo.py [--models a,b,...] [--out-prefix BENCH_ZOO]
"""

from __future__ import annotations

import argparse
import json
import time


def _zoo():
    from flexflow_tpu.models import (
        build_alexnet_cifar10,
        build_candle_uno,
        build_dlrm,
        build_gpt,
        build_inception_v3,
        build_mlp_unify,
        build_moe,
        build_resnet,
        build_resnext50,
        build_transformer,
        build_xdl,
    )

    # batch sizes follow the reference example defaults / osdi22ae runs
    return {
        "alexnet": dict(build=build_alexnet_cifar10, batch=64,
                        loss="sparse_categorical_crossentropy"),
        "resnet": dict(build=build_resnet, batch=64,
                       loss="sparse_categorical_crossentropy"),
        "resnext50": dict(build=build_resnext50, batch=16,
                          loss="sparse_categorical_crossentropy"),
        "inception": dict(build=build_inception_v3, batch=64,
                          loss="sparse_categorical_crossentropy"),
        "transformer": dict(
            build=lambda cfg: build_transformer(
                cfg, num_layers=12, hidden=512, num_heads=8, ff_dim=2048,
                seq_len=256),
            batch=64, loss="mean_squared_error"),
        "gpt": dict(
            build=lambda cfg: build_gpt(
                cfg, vocab=32000, num_layers=12, hidden=768, num_heads=12,
                ff_dim=3072, seq_len=512),
            batch=8, loss="sparse_categorical_crossentropy"),
        "dlrm": dict(
            # reference default is 8x 1M-row tables; 4x 1M keeps the f32
            # weight+grad+Adam footprint inside one chip's HBM
            build=lambda cfg: build_dlrm(cfg, embedding_sizes=(1000000,) * 4),
            batch=64, loss="mean_squared_error"),
        "xdl": dict(build=build_xdl, batch=64, loss="mean_squared_error"),
        "candle_uno": dict(build=build_candle_uno, batch=64,
                           loss="mean_squared_error"),
        "mlp": dict(build=build_mlp_unify, batch=64,
                    loss="sparse_categorical_crossentropy"),
        "moe": dict(build=build_moe, batch=64,
                    loss="sparse_categorical_crossentropy"),
    }


def bench_model(name, spec):
    """Steady-state samples/s of the compiled train step.

    Data is pre-staged on device once and trace_n optimizer steps run
    per compiled call — the role the reference's DataLoader plays
    (whole array into zero-copy memory once, then on-node per-batch
    copies); per-batch host->device uploads would measure the host
    link, not the chip."""
    import jax
    import jax.random as jrandom
    import numpy as np

    import flexflow_tpu as ff
    from examples.common import synthetic_inputs, synthetic_labels

    on_tpu = jax.devices()[0].platform != "cpu"
    cfg = ff.FFConfig(
        batch_size=spec["batch"],
        num_devices=1,
        only_data_parallel=True,
        compute_dtype="bfloat16" if on_tpu else "float32",
    )
    t0 = time.perf_counter()
    model = spec["build"](cfg)
    model.compile(optimizer=ff.AdamOptimizer(alpha=1e-4),
                  loss_type=spec["loss"], metrics=[])
    compile_s = time.perf_counter() - t0

    trace_n = 8
    b = cfg.batch_size
    xs = synthetic_inputs(model, trace_n * b)
    y = synthetic_labels(model, trace_n * b, spec["loss"])
    compiled = model.compiled
    xs_d = [
        jax.device_put(x.reshape((trace_n, b) + x.shape[1:]),
                       compiled.stacked_input_sharding(i))
        for i, x in enumerate(xs)
    ]
    y_d = jax.device_put(y.reshape((trace_n, b) + y.shape[1:]),
                         compiled.stacked_batch_sharding())
    params, opt_state, state = model.params, model.opt_state, model.state
    for i in range(2):  # compile the scanned program + settle
        params, opt_state, state, losses, _ = compiled.train_steps(
            params, opt_state, state, jrandom.key(i), xs_d, y_d)
    float(losses[-1])  # host readback: warm-up is done
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        params, opt_state, state, losses, _ = compiled.train_steps(
            params, opt_state, state, jrandom.key(10 + i), xs_d, y_d)
        float(losses[-1])
        times.append(time.perf_counter() - t0)
    step_s = float(np.median(times)) / trace_n
    return {
        "batch": b,
        "backend": jax.devices()[0].platform,
        "compile_s": round(compile_s, 1),
        "step_ms": round(step_s * 1e3, 3),
        "throughput_samples_s": round(b / step_s, 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default=",".join(_zoo().keys()))
    ap.add_argument("--out-prefix", default="BENCH_ZOO")
    args = ap.parse_args()

    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    place_compile_cache()
    zoo = _zoo()
    names = [n for n in args.models.split(",") if n]
    unknown = [n for n in names if n not in zoo]
    if unknown:
        ap.error(f"unknown models {unknown}; valid: {sorted(zoo)}")
    report = {}
    for name in names:
        try:
            row = bench_model(name, zoo[name])
        except Exception as e:  # honest artifact: record the failure
            row = {"error": f"{type(e).__name__}: {e}"}
        report[name] = row
        print(json.dumps({"model": name, **row}), flush=True)
        # incremental write: a long run killed mid-way keeps its rows
        with open(f"{args.out_prefix}.json", "w") as f:
            json.dump(report, f, indent=1)
    lines = [
        f"# {args.out_prefix} — model-zoo training throughput on the live chip",
        "",
        "The reference contract: every C++ example self-reports "
        "`THROUGHPUT = %.2f samples/s` after timed epochs "
        "(BASELINE.md row 1; transformer.cc:208-210 and 9 siblings).  "
        "Same models, same default shapes, one chip, Adam, bf16 compute, "
        "synthetic data, first (compiling) step excluded.",
        "",
        "| model | batch | compile s | step ms | samples/s |",
        "|---|---|---|---|---|",
    ]
    for name, r in report.items():
        if "error" in r:
            lines.append(f"| {name} | — | — | — | ERROR: {r['error']} |")
        else:
            lines.append(
                f"| {name} | {r['batch']} | {r['compile_s']} | "
                f"{r['step_ms']} | {r['throughput_samples_s']} |")
    with open(f"{args.out_prefix}.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# wrote {args.out_prefix}.json / {args.out_prefix}.md")


if __name__ == "__main__":
    main()
