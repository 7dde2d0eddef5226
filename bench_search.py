#!/usr/bin/env python
"""Searched-vs-DP benchmark artifact (reference: the OSDI'22 Unity
artifact scripts, scripts/osdi22ae/{bert,dlrm,candle_uno,inception}.sh —
each runs an example twice, searched vs --only-data-parallel, and
compares throughput).

For each model this reports:
  * simulated 8-device cost of the searched strategy vs pure data
    parallelism (full-size model, the TPU machine model), and
  * a REAL executed step-time ratio for the same two strategies on the
    available mesh (>=8 devices required; sizes are scaled down when
    executing on a CPU mesh and recorded as such — honest numbers,
    clearly labeled).

Writes BENCH_SEARCH.json and BENCH_SEARCH.md.

Usage:
  python bench_search.py [--models bert,dlrm,candle_uno,inception]
                         [--calibrate] [--steps N]
"""

from __future__ import annotations

import argparse
import json
import time

# The sync-bound transformer regime (osdi22ae/bert.sh scaled to the
# CPU mesh): per-device batch 1, full hidden/ff widths — DP's weight
# allreduce dominates and the searched TP strategy wins at EXECUTION.
# Shared with tests/test_search_exec_coherence.py so the benchmark and
# the CI gate measure the SAME program pair.
SYNC_BOUND_BERT_KW = dict(num_layers=2, hidden=512, num_heads=4,
                          ff_dim=2048, seq_len=16)


def _model_specs():
    """Per-model configs mirror the osdi22ae scripts (bert.sh: batch 8,
    budget 30; dlrm.sh/candle_uno.sh: budget 20; inception.sh: batch 64,
    budget 10)."""
    from flexflow_tpu.models import (
        build_alexnet,
        build_alexnet_cifar10,
        build_candle_uno,
        build_dlrm,
        build_gpt,
        build_inception_v3,
        build_mlp_unify,
        build_resnext50,
        build_transformer,
        build_xdl,
    )

    return {
        "alexnet": dict(
            # the 5th BASELINE.json target config (AlexNet/CIFAR-10):
            # sim at full ImageNet size, exec at the native CIFAR size
            build=lambda cfg: build_alexnet(cfg),
            batch=64, budget=10, loss="sparse_categorical_crossentropy",
            exec_build=lambda cfg: build_alexnet_cifar10(cfg),
            exec_batch=16,
        ),
        "bert": dict(
            build=lambda cfg: build_transformer(
                cfg, num_layers=12, hidden=512, num_heads=8, ff_dim=2048,
                seq_len=512),
            batch=8, budget=30, loss="mean_squared_error",
            # exec tier keeps the full hidden/ff widths at short seq:
            # the per-device batch is 1, so DP's weight allreduce
            # dominates and the search's TP strategy wins at EXECUTION
            # (the osdi22ae/bert.sh regime; measured 3.7x on the CPU
            # mesh) — a narrowed exec model collapses to DP and the
            # two-program comparison degenerates.  The coherence CI
            # gates THE SAME spec (SYNC_BOUND_BERT_KW).
            exec_build=lambda cfg: build_transformer(
                cfg, **SYNC_BOUND_BERT_KW),
            exec_batch=8,
        ),
        "gpt": dict(
            # causal LM (beyond the reference's workload set): the
            # 32k-vocab lm_head is the largest weight — the search
            # row-splits it instead of paying its gradient allreduce
            build=lambda cfg: build_gpt(
                cfg, vocab=32000, num_layers=8, hidden=512, num_heads=8,
                ff_dim=2048, seq_len=512),
            batch=8, budget=30, loss="sparse_categorical_crossentropy",
            exec_build=lambda cfg: build_gpt(
                cfg, vocab=2048, num_layers=2, hidden=128, num_heads=4,
                ff_dim=256, seq_len=64),
            exec_batch=8,
        ),
        "dlrm": dict(
            build=lambda cfg: build_dlrm(cfg),
            batch=64, budget=20, loss="mean_squared_error",
            exec_build=lambda cfg: build_dlrm(
                cfg, embedding_sizes=(100000,) * 4, embedding_dim=32,
                bot_mlp=(64, 32), top_mlp=(64, 1)),
            exec_batch=64,
        ),
        "candle_uno": dict(
            build=lambda cfg: build_candle_uno(cfg),
            batch=64, budget=20, loss="mean_squared_error",
            exec_build=lambda cfg: build_candle_uno(cfg),
            exec_batch=32,
        ),
        "inception": dict(
            build=lambda cfg: build_inception_v3(cfg),
            batch=64, budget=10, loss="sparse_categorical_crossentropy",
            # 75x75 is InceptionV3's minimum input: ~10 s/step on the
            # CPU mesh — slow but real; the 299x299 full size stays
            # sim-only (hours per artifact run)
            exec_build=lambda cfg: build_inception_v3(
                cfg, num_classes=100, image=75),
            exec_batch=4,
        ),
        # the remaining osdi22ae scripts: resnext-50.sh, xdl.sh, mlp.sh
        "resnext50": dict(
            build=lambda cfg: build_resnext50(cfg),
            batch=64, budget=10, loss="sparse_categorical_crossentropy",
            # 32x32 is the executable floor for the grouped-conv stack
            # on a CPU mesh (~45 s/step at batch 4; batch 2 halves it);
            # the 224x224 full size stays sim-only
            exec_build=lambda cfg: build_resnext50(
                cfg, num_classes=10, image=32),
            exec_batch=2,
        ),
        "xdl": dict(
            build=lambda cfg: build_xdl(cfg),
            batch=64, budget=20, loss="mean_squared_error",
            exec_build=lambda cfg: build_xdl(
                cfg, num_tables=8, vocab=20000, embedding_dim=16,
                mlp=(64, 32, 1)),
            exec_batch=64,
        ),
        "mlp": dict(
            build=lambda cfg: build_mlp_unify(cfg),
            batch=64, budget=20, loss="sparse_categorical_crossentropy",
            exec_build=lambda cfg: build_mlp_unify(
                cfg, in_dim=512, hidden=(512, 512, 512)),
            exec_batch=32,
        ),
    }


def simulate_pair(name, spec, n_devices, calibration=None,
                  calibration_file=None, cost_cache_file=None,
                  verify=False, slice_levels=None):
    import flexflow_tpu as ff
    from flexflow_tpu.analysis import CHECK_STATS
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.driver import LAST_SEARCH_STATS, optimize_strategy
    from flexflow_tpu.search.simulator import Simulator

    cfg = ff.FFConfig(batch_size=spec["batch"], num_devices=n_devices,
                      search_budget=spec["budget"],
                      # the SEARCH must rank with the measured table too,
                      # or it optimizes the roofline and the calibrated
                      # re-simulation below exposes a bad pick
                      calibration_file=calibration_file,
                      cost_cache_file=cost_cache_file,
                      # multi-slice hierarchy for the sim tier (FFConfig
                      # layers it over the machine spec, PR 6)
                      slice_levels=slice_levels)
    model = spec["build"](cfg)
    g = model.graph
    if calibration is not None and (
            calibration.backend not in (None, cfg.machine_spec.platform)):
        print(f"# {name}: calibration probed on {calibration.backend!r} is "
              f"incoherent with machine model {cfg.machine_spec.name!r}; "
              "simulating with the roofline")
        calibration = None
    sim = Simulator(cfg.machine_spec, num_devices=n_devices,
                    calibration=calibration)
    c_dp = sim.simulate(g, data_parallel_strategy(g, n_devices))
    verify_before = dict(CHECK_STATS)
    t0 = time.monotonic()
    best_graph, strategy = optimize_strategy(g, cfg, return_graph=True)
    search_s = time.monotonic() - t0
    stats = dict(LAST_SEARCH_STATS)
    verify_stats = None
    if verify:
        # per-model verifier overhead: wall seconds spent inside the
        # invariant checker during THIS search (the measured cost of
        # always-on checking, not a guess)
        verify_stats = {
            "verify_checks": int(
                CHECK_STATS["checks"] - verify_before["checks"]),
            "verify_seconds": round(
                CHECK_STATS["seconds"] - verify_before["seconds"], 4),
        }
    c_se = Simulator(cfg.machine_spec, num_devices=n_devices,
                     calibration=calibration).simulate(best_graph, strategy)
    d, f = stats.get("delta_sims", 0), stats.get("full_sims", 0)
    rh = stats.get("cache_row_hits", 0)
    rm = stats.get("cache_row_misses", 0)
    return {
        "nodes": g.num_nodes,
        # whether THIS model's sim numbers actually consulted measured
        # records (False when the table was discarded as incoherent
        # with the machine model above)
        "sim_calibrated": calibration is not None,
        "sim_dp_ms": round(c_dp * 1e3, 4),
        "sim_searched_ms": round(c_se * 1e3, 4),
        "sim_ratio": round(c_dp / c_se, 3) if c_se > 0 else None,
        # split timing (was one conflated search_seconds): any
        # compile-time calibration probing is reported separately
        "search_seconds": round(stats.get("search_seconds", search_s), 2),
        "calibration_seconds": round(stats.get("calibration_seconds", 0.0),
                                     2),
        # delta-simulation and persistent-cache effectiveness — the
        # tracked trajectory numbers for search throughput
        "delta_sims": d,
        "full_sims": f,
        "delta_hit_rate": round(d / (d + f), 3) if (d + f) else None,
        "cost_cache_row_hit_rate": (
            round(rh / (rh + rm), 3) if (rh + rm) else None),
        "cost_cache_result_hit": bool(stats.get("result_cache_hit")),
        **(verify_stats or {}),
    }


def _steady_step_seconds(model, xs, y, steps, blocks: int = 5):
    """Median-of-blocks step time: single-core hosts jitter 8-18%
    between consecutive blocks (observed), which is larger than the
    effects being measured — the median of several short blocks is
    stable to ~2-3%."""
    import statistics

    import jax
    import jax.random as jrandom

    compiled = model.compiled
    loader_inputs = [
        jax.device_put(x, compiled.input_sharding(i)) for i, x in enumerate(xs)
    ]
    labels = jax.device_put(y, compiled.batch_sharding())
    params, opt_state, state = model.params, model.opt_state, model.state
    for i in range(3):  # compile + settle
        params, opt_state, state, loss, _ = compiled.train_step(
            params, opt_state, state, jrandom.key(i), loader_inputs, labels)
    float(loss)
    times = []
    for b in range(blocks):
        t0 = time.perf_counter()
        for i in range(steps):
            params, opt_state, state, loss, _ = compiled.train_step(
                params, opt_state, state, jrandom.key(100 + b * steps + i),
                loader_inputs, labels)
        float(loss)
        times.append((time.perf_counter() - t0) / steps)
    return statistics.median(times)


def _exec_cfg_kwargs(n_devices, on_cpu):
    """The live-mesh execution recipe SHARED by execute_pair and the
    sync-precision sweep, so the two 'executed' measurements in one
    artifact can never diverge in methodology: on a CPU mesh rank with
    the CPU machine model in float32; on the real accelerator keep the
    TPU model and bfloat16."""
    from flexflow_tpu.core.machine import MachineSpec

    return dict(
        num_devices=n_devices,
        compute_dtype="float32" if on_cpu else "bfloat16",
        machine_spec=MachineSpec.host_cpu(n_devices) if on_cpu else None,
    )


def execute_pair(name, spec, n_devices, steps, calibration_file=None,
                 obs=False, out_prefix="BENCH_SEARCH",
                 drift_threshold=0.5):
    """Measure real per-step seconds for DP vs searched strategies on
    the live mesh.  Returns None when the model has no executable
    reduced config.  With ``obs`` the unified telemetry rides along:
    a per-strategy DriftReport (simulated prediction vs the measured
    steady step, per phase) lands in the returned row, and the
    searched strategy's PREDICTED timeline is written as
    Perfetto-loadable Chrome-trace JSON."""
    if spec["exec_build"] is None:
        return None
    import os

    import jax

    import flexflow_tpu as ff
    from examples.common import synthetic_inputs, synthetic_labels
    from flexflow_tpu.compiler.lowering import data_parallel_strategy

    on_cpu = jax.devices()[0].platform == "cpu"

    results = {}
    programs = {}  # mode -> (graph, strategy, cfg, executor) for obs
    searched_is_dp = False
    for mode in ("dp", "searched"):
        # the osdi22ae contract runs searched-vs-DP on the SAME hardware,
        # with the search targeting that hardware — on a CPU mesh the
        # search must rank with the CPU machine model, not the TPU one
        # (a TPU-optimal strategy can be a CPU pessimization); on the
        # real accelerator the search gets the calibration file too, so
        # the executed strategy is the one the calibrated sim ranked
        cfg = ff.FFConfig(batch_size=spec["exec_batch"],
                          search_budget=spec["budget"],
                          calibration_file=(None if on_cpu
                                            else calibration_file),
                          only_data_parallel=(mode == "dp"),
                          **_exec_cfg_kwargs(n_devices, on_cpu))
        model = spec["exec_build"](cfg)
        if mode == "dp":
            strategy = data_parallel_strategy(model.graph, n_devices)
            model.compile(loss_type=spec["loss"], metrics=[], strategy=strategy)
        else:
            model.compile(loss_type=spec["loss"], metrics=[])  # joint search
            # did the search's champion-vs-DP floor keep plain DP?  Then
            # both compiled programs are identical and the measured
            # ratio is pure timing noise around 1.0 — record that.
            searched_is_dp = (
                model.strategy == data_parallel_strategy(model.graph, n_devices)
            )
        xs = synthetic_inputs(model, cfg.batch_size)
        y = synthetic_labels(model, cfg.batch_size, spec["loss"])
        results[mode] = _steady_step_seconds(model, xs, y, steps)
        if obs:
            programs[mode] = (
                model.graph,
                model.strategy if mode == "searched" else strategy,
                cfg, type(model.compiled).__name__,
            )
    obs_row = {}
    if obs:
        from flexflow_tpu.obs.drift import build_drift_report
        from flexflow_tpu.search.driver import coherent_calibration
        from flexflow_tpu.search.simulator import Simulator

        drift = {}
        for mode, (g, strat, cfg_m, executor) in programs.items():
            # predict with the same table the search ranked with — a
            # roofline prediction labeled "calibrated" would flag the
            # calibration table stale for drift it never caused
            cal = coherent_calibration(cfg_m)
            sim = Simulator.for_config(cfg_m, calibration=cal)
            bd = {}
            schedule, comm = [], []
            sim.simulate(g, strat, breakdown=bd, schedule=schedule,
                         comm_schedule=comm)
            rep = build_drift_report(
                bd, measured_step_s=results[mode],
                threshold=drift_threshold,
                calibrated=cal is not None,
            )
            if rep is not None:
                d = rep.to_dict()
                d["executor"] = executor
                drift[mode] = d
            if mode == "searched":
                trace_path = f"{out_prefix}_timeline_{name}.json"
                sim.export_chrome_trace(
                    g, strat, trace_path,
                    label=f"predicted ({name}, searched)",
                    schedule=schedule, comm_schedule=comm,
                    total_s=bd.get("total_s"))
                obs_row["predicted_timeline"] = trace_path
        if drift:
            obs_row["drift"] = drift
    return {
        **obs_row,
        "searched_is_dp": searched_is_dp,
        "exec_backend": jax.devices()[0].platform,
        "exec_devices": n_devices,
        # virtual devices share the host's physical cores: when cores <
        # devices, per-device compute serializes and compute-parallel
        # strategies cannot win — only work/communication-avoiding wins
        # (DLRM-style) are observable on such a host
        "exec_host_cores": os.cpu_count(),
        "exec_scale": "reduced" if on_cpu else "full",
        "exec_dp_ms": round(results["dp"] * 1e3, 3),
        "exec_searched_ms": round(results["searched"] * 1e3, 3),
        "exec_ratio": round(results["dp"] / results["searched"], 3),
    }


def sync_precision_sweep(n_devices, steps, precisions):
    """The --sync-precision sweep: gradient-sync wire precision as a
    strategy dimension (comm/quantized.py, EQuARX arXiv:2506.17615) on
    the sync-bound BERT config (SYNC_BOUND_BERT_KW — per-device batch
    1, full widths, where DP's weight allreduce dominates).

    Simulated: the DP strategy's weight-sync (allreduce) term and full
    step cost under the TPU machine model, per precision.  Executed:
    real CPU-mesh step time running the SAME per-weight-group map the
    TPU pricing chooses — on a CPU mesh there is no fat wire to save,
    so the executed ratio measures the quantize round-trip OVERHEAD
    honestly (the win is the simulated number); the map is forced
    because the CPU machine model itself declines to compress."""
    import jax

    import flexflow_tpu as ff
    from examples.common import synthetic_inputs, synthetic_labels
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.search.sync_precision import choose_sync_precision

    on_cpu = jax.devices()[0].platform == "cpu"
    can_exec = len(jax.devices()) >= n_devices

    sweep = {
        "model": "bert",
        "config": dict(SYNC_BOUND_BERT_KW),
        "batch": 8,
        "note": (
            "simulated numbers price the wire win on the TPU machine "
            "model; executed numbers run the TPU-chosen compression map "
            "on the live mesh — on a CPU mesh that measures the "
            "quantize round-trip overhead with no wire to save, so "
            "exec_ratio <= 1.0 there is expected and honest"
        ),
        "rows": {},
    }
    from flexflow_tpu.models import build_transformer

    for prec in precisions:
        cfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                          sync_precision=prec)
        g = build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph
        sim = Simulator(cfg.machine_spec, num_devices=n_devices,
                        sync_precision=prec)
        dp = data_parallel_strategy(g, n_devices)
        step_s = sim.simulate(g, dp)
        sync_s = sum(
            sim.cost.sync_cost(node.op, dp[node.guid])
            for node in g.topo_order()
        )
        groups = choose_sync_precision(g, dp, sim.cost)
        row = {
            "sim_allreduce_ms": round(sync_s * 1e3, 4),
            "sim_step_ms": round(step_s * 1e3, 4),
            "compressed_groups": len(groups),
        }
        if can_exec:
            cfg_x = ff.FFConfig(
                batch_size=8, only_data_parallel=True,
                **_exec_cfg_kwargs(n_devices, on_cpu))
            m = build_transformer(cfg_x, **SYNC_BOUND_BERT_KW)
            dp_x = data_parallel_strategy(m.graph, n_devices)
            m.compile(loss_type="mean_squared_error", metrics=[],
                      strategy=dp_x)
            # force the TPU-chosen map (see docstring): the compiled
            # step is lazily jitted, so setting the map here is enough
            m.compiled.sync_precision = dict(
                choose_sync_precision(m.graph, dp_x, sim.cost, mode=prec)
            )
            xs = synthetic_inputs(m, cfg_x.batch_size)
            y = synthetic_labels(m, cfg_x.batch_size, "mean_squared_error")
            row["exec_ms"] = round(
                _steady_step_seconds(m, xs, y, steps) * 1e3, 3)
            row["exec_backend"] = jax.devices()[0].platform
        sweep["rows"][prec] = row
        print(json.dumps({"sync_precision": prec, **row}))
    base = sweep["rows"].get("fp32")
    if base:
        for prec, row in sweep["rows"].items():
            if row.get("sim_allreduce_ms"):
                row["sim_allreduce_ratio_vs_fp32"] = round(
                    base["sim_allreduce_ms"] / row["sim_allreduce_ms"], 3)
                row["sim_step_ratio_vs_fp32"] = round(
                    base["sim_step_ms"] / row["sim_step_ms"], 3)
            if row.get("exec_ms") and base.get("exec_ms"):
                row["exec_ratio_vs_fp32"] = round(
                    base["exec_ms"] / row["exec_ms"], 3)
    return sweep


def sync_schedule_sweep(n_devices, steps, drift_threshold=0.5):
    """The --sync-schedule sweep: the gradient-sync SCHEDULE as a
    searched comm plan (search/sync_schedule.py) on the sync-bound BERT
    config, per sync-precision mode.

    Simulated (TPU machine model): the DP strategy's step under the
    MONOLITHIC schedule (one post-backward fused sync — the executed
    status quo) vs the SEARCHED bucketed schedule, with the exposed
    sync tail and per-bucket lanes recorded — the acceptance number is
    scheduled < monolithic.  Executed (live mesh): the same two
    programs run for real — monolithic ``_sync_grads`` vs the bucketed
    executor (comm/bucketed.py) — each with a DriftReport carrying the
    per-bucket predicted-exposed rows.  On a CPU mesh fp32 buckets are
    value-identity barriers and there is no fat wire, so the executed
    ratio measures the anchoring/quantize overhead honestly; the
    overlap win is the simulated number, falsifiable on real ICI."""
    import math

    import jax

    import flexflow_tpu as ff
    from examples.common import synthetic_inputs, synthetic_labels
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.obs.drift import build_drift_report
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.search.sync_precision import choose_sync_precision
    from flexflow_tpu.search.sync_schedule import (
        build_bucketed_schedule,
        choose_sync_schedule,
        synced_weight_groups,
    )
    from flexflow_tpu.models import build_transformer

    on_cpu = jax.devices()[0].platform == "cpu"
    can_exec = len(jax.devices()) >= n_devices

    sweep = {
        "model": "bert",
        "config": dict(SYNC_BOUND_BERT_KW),
        "batch": 8,
        "note": (
            "simulated numbers price overlap on the TPU machine model "
            "(monolithic = one post-backward fused sync, scheduled = "
            "searched issue-ordered buckets); executed numbers run both "
            "programs for real — on a CPU mesh fp32 buckets are "
            "value-identity barriers with no wire to save, so "
            "exec_ratio ~= 1.0 there is expected and honest, and the "
            "per-bucket drift rows stay predicted-side only (one fused "
            "XLA program has no per-bucket host timer)"
        ),
        "rows": {},
    }
    for prec_mode in ("fp32", "search"):
        cfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                          sync_precision=prec_mode, sync_schedule="search")
        g = build_transformer(cfg, **SYNC_BOUND_BERT_KW).graph
        sim = Simulator(cfg.machine_spec, num_devices=n_devices,
                        sync_precision=prec_mode)
        dp = data_parallel_strategy(g, n_devices)
        pmap = (choose_sync_precision(g, dp, sim.cost)
                if prec_mode != "fp32" else {})
        synced = synced_weight_groups(g, dp, sim.cost)
        mono = build_bucketed_schedule(synced, pmap, math.inf)
        bd_mono = {}
        sim.simulate(g, dp, breakdown=bd_mono, sync_schedule=mono)
        sched, info = choose_sync_schedule(g, dp, sim, pmap, cfg)
        row = {
            "sim_monolithic_ms": round(bd_mono["total_s"] * 1e3, 4),
            "sim_exposed_monolithic_ms": round(
                bd_mono["sync_exposed_s"] * 1e3, 4),
            "buckets": info.get("buckets", 0),
            "compressed_groups": len(pmap),
        }
        if sched is not None:
            bd_s = {}
            sim.simulate(g, dp, breakdown=bd_s, sync_schedule=sched)
            row["sim_scheduled_ms"] = round(bd_s["total_s"] * 1e3, 4)
            row["sim_exposed_scheduled_ms"] = round(
                bd_s["sync_exposed_s"] * 1e3, 4)
            row["sim_step_ratio"] = round(
                bd_mono["total_s"] / bd_s["total_s"], 3)
            row["bucket_lanes"] = bd_s.get("sync_buckets", [])
        if can_exec and sched is not None:
            drift = {}
            execd = {}
            for mode, use_sched in (("monolithic", None),
                                    ("scheduled", sched)):
                cfg_x = ff.FFConfig(
                    batch_size=8, only_data_parallel=True,
                    **_exec_cfg_kwargs(n_devices, on_cpu))
                m = build_transformer(cfg_x, **SYNC_BOUND_BERT_KW)
                dp_x = data_parallel_strategy(m.graph, n_devices)
                m.compile(loss_type="mean_squared_error", metrics=[],
                          strategy=dp_x)
                # force the TPU-chosen artifacts (see docstring): the
                # compiled step is lazily jitted, so setting them here
                # is enough — same discipline as the precision sweep
                m.compiled.sync_precision = dict(pmap)
                m.compiled.sync_schedule = use_sched
                xs = synthetic_inputs(m, cfg_x.batch_size)
                y = synthetic_labels(m, cfg_x.batch_size,
                                     "mean_squared_error")
                execd[mode] = _steady_step_seconds(m, xs, y, steps)
                bd = bd_s if use_sched is not None else bd_mono
                rep = build_drift_report(
                    bd, measured_step_s=execd[mode],
                    threshold=drift_threshold)
                if rep is not None:
                    drift[mode] = rep.to_dict()
            row["exec_monolithic_ms"] = round(execd["monolithic"] * 1e3, 3)
            row["exec_scheduled_ms"] = round(execd["scheduled"] * 1e3, 3)
            row["exec_ratio"] = round(
                execd["monolithic"] / execd["scheduled"], 3)
            row["exec_backend"] = jax.devices()[0].platform
            if drift:
                row["drift"] = drift
        sweep["rows"][prec_mode] = row
        print(json.dumps({"sync_schedule": prec_mode, **{
            k: v for k, v in row.items()
            if k not in ("bucket_lanes", "drift")}}))
    return sweep


def topology_sweep(n_devices):
    """The --topology sweep: hierarchical machine topologies as a
    pricing + search dimension (search/machine_model.py link levels +
    search/reduction_plan.py staged reduction plans).

    Simulated only, deliberately: a CPU mesh has no slice boundary, so
    executed numbers could not show a DCN win — the contract numbers
    are the machine-model sync terms, falsifiable on a real multislice
    pod.  For flat vs 2-slice vs 4-slice variants of the TPU machine
    (10x ICI/DCN bandwidth gap, the production-typical ratio), each
    model records the DP strategy's flat-ring sync term, the searched
    staged-plan sync term, and the chosen per-bucket reduction plans
    (the acceptance number: staged beats flat >= 2x on the sync term
    for the sync-bound BERT)."""
    import dataclasses
    import math

    import flexflow_tpu as ff
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.models import (
        build_dlrm,
        build_mlp_unify,
        build_transformer,
    )
    from flexflow_tpu.search.simulator import Simulator
    from flexflow_tpu.search.sync_schedule import (
        build_bucketed_schedule,
        choose_sync_schedule,
        synced_weight_groups,
    )

    builders = {
        "bert": (8, lambda cfg: build_transformer(
            cfg, **SYNC_BOUND_BERT_KW)),
        "dlrm": (64, lambda cfg: build_dlrm(cfg)),
        "mlp": (64, lambda cfg: build_mlp_unify(cfg)),
    }
    base_spec = ff.FFConfig(batch_size=8,
                            num_devices=n_devices).machine_spec
    gap = 10.0
    topologies = {"flat": base_spec}
    for k in (2, 4):
        # a k-slice variant needs k even slices of >= 2 devices each —
        # degenerate counts (--devices 2 with 4 slices) would build a
        # spec with devices_per_host 0
        if n_devices % k == 0 and n_devices // k >= 2:
            topologies[f"{k}slice"] = dataclasses.replace(
                base_spec, devices_per_host=n_devices // k,
                dcn_bandwidth=base_spec.ici_bandwidth / gap)
        else:
            print(f"# topology sweep: skipping {k}slice "
                  f"(needs {k} even slices of >=2 of {n_devices} devices)")
    sweep = {
        "devices": n_devices,
        "ici_dcn_gap": gap,
        "note": (
            "simulated on the TPU machine model (a CPU mesh has no "
            "slice boundary to execute across); sync terms are the DP "
            "strategy's weight-gradient reduction priced flat (one "
            "ring over every link class) vs with the searched staged "
            "reduction plans (RS within slice, cross-slice exchange of "
            "the shard, AG within slice)"
        ),
        "models": {},
    }
    for name, (batch, build) in builders.items():
        cfg = ff.FFConfig(batch_size=batch, num_devices=n_devices)
        g = build(cfg).graph
        dp = data_parallel_strategy(g, n_devices)
        rows = {}
        for topo, spec in topologies.items():
            sim = Simulator(spec, num_devices=n_devices)
            synced = synced_weight_groups(g, dp, sim.cost)
            mono = build_bucketed_schedule(synced, {}, math.inf)
            bd = {}
            sim.simulate(g, dp, breakdown=bd, sync_schedule=mono)
            sched, info = choose_sync_schedule(g, dp, sim, {}, cfg)
            row = {
                "sim_flat_step_ms": round(bd["total_s"] * 1e3, 4),
                "sim_flat_sync_ms": round(bd["sync_total_s"] * 1e3, 4),
                "buckets": info.get("buckets", 0),
                "staged_buckets": info.get("staged_buckets", 0),
                "plans": {},
            }
            if sched is not None:
                bd_s = {}
                sim.simulate(g, dp, breakdown=bd_s, sync_schedule=sched)
                row["sim_planned_step_ms"] = round(
                    bd_s["total_s"] * 1e3, 4)
                row["sim_planned_sync_ms"] = round(
                    bd_s["sync_total_s"] * 1e3, 4)
                row["sync_levels_ms"] = {
                    k: round(v * 1e3, 4)
                    for k, v in (bd_s.get("sync_levels_s") or {}).items()}
                row["plans"] = {
                    b.name: b.plan.name for b in sched.buckets
                    if b.plan is not None}
                if row["sim_planned_sync_ms"]:
                    row["sync_ratio_flat_over_planned"] = round(
                        row["sim_flat_sync_ms"]
                        / row["sim_planned_sync_ms"], 3)
            rows[topo] = row
            print(json.dumps({"topology": topo, "model": name, **{
                k: v for k, v in row.items() if k != "plans"}}))
        sweep["models"][name] = rows
    return sweep


def serve_sweep(n_devices):
    """The --serve sweep: throughput (objective=train) vs p99-latency
    (objective=serve) strategies for the DECODE zoo (models/decode.py)
    on the flat and 2-slice machine variants — ROADMAP item 4's
    "serving wants a different Pareto point" claim as a recorded
    artifact.

    For each decode config both objectives run the full search; the
    two results are then scored in BOTH currencies — mean step (train)
    and the serving arrival model's p50/p90/p99 (search/serving.py) —
    plus per-device KV residency, so the table compares strategies,
    not scorers.  Simulated only, deliberately: a CPU mesh can execute
    the decode graph (tests do) but cannot exhibit the HBM-bandwidth
    cache-streaming ratios the machine model prices; the contract
    numbers are falsifiable on a real chip via --calibrate.  A prefill
    row records the compute-bound phase for contrast (no decode ops —
    the serve objective degenerates to train pricing there by
    design)."""
    import dataclasses

    import flexflow_tpu as ff
    from flexflow_tpu.core.optype import OperatorType
    from flexflow_tpu.models import (
        GPT_DECODE_KW,
        GPT_DECODE_SERVE_KW,
        SERVE_FRAME_SLOTS,
        build_gpt_decode,
        build_gpt_prefill,
    )
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.serving import (
        kv_residency_bytes,
        serve_latency_quantiles,
    )
    from flexflow_tpu.search.simulator import Simulator

    base_spec = ff.FFConfig(batch_size=8,
                            num_devices=n_devices).machine_spec
    gap = 10.0
    topologies = {"flat": base_spec}
    if n_devices % 2 == 0 and n_devices // 2 >= 2:
        topologies["2slice"] = dataclasses.replace(
            base_spec, devices_per_host=n_devices // 2,
            dcn_bandwidth=base_spec.ici_bandwidth / gap)
    configs = {
        # the serving-regime geometry (long ragged caches, modest
        # width): where throughput and p99 provably part ways
        "gpt_decode_serve": (SERVE_FRAME_SLOTS, GPT_DECODE_SERVE_KW),
        # the small executor-tested config for contrast (cache too
        # small for the ragged term to dominate — the objectives are
        # allowed to agree here; the row proves the sweep does not
        # manufacture divergence)
        "gpt_decode_s": (16, GPT_DECODE_KW),
    }
    sweep = {
        "devices": n_devices,
        "note": (
            "simulated on the TPU machine model (CPU execution cannot "
            "exhibit HBM cache-streaming ratios); p50/p90/p99 are the "
            "serving arrival model's quantile currencies "
            "(search/serving.py), mean is the train currency; both "
            "strategies scored in both, so the rows compare "
            "strategies, not scorers"
        ),
        "models": {},
    }

    def _decode_views(g, s):
        return [
            {"op": n.op.name, "dims": list(s[n.guid].dim_degrees),
             "replica": s[n.guid].replica_degree}
            for n in g.topo_order()
            if n.op.op_type == OperatorType.DECODE_ATTENTION
        ]

    def _named(g, s):
        return {
            n.op.name: (tuple(s[n.guid].dim_degrees),
                        s[n.guid].replica_degree, s[n.guid].start_part)
            for n in g.topo_order() if n.guid in s
        }

    for name, (batch, kw) in configs.items():
        rows = {}
        for topo, spec in topologies.items():
            out = {}
            results = {}
            for obj in ("train", "serve"):
                cfg = ff.FFConfig(
                    batch_size=batch, num_devices=n_devices,
                    machine_spec=spec, search_budget=8,
                    search_timeout_s=60.0, objective=obj,
                    comp_mode="inference", cost_cache_file="",
                )
                m = build_gpt_decode(cfg, **kw)
                t0 = time.monotonic()
                g, s = optimize_strategy(m.graph, cfg, return_graph=True)
                results[obj] = (cfg, g, s)
                out[f"{obj}_search_seconds"] = round(
                    time.monotonic() - t0, 2)
                out[f"{obj}_decode_views"] = _decode_views(g, s)
                out[f"{obj}_kv_mb_per_device"] = round(
                    kv_residency_bytes(g, s, n_devices) / 1e6, 2)
            cfg_serve = results["serve"][0]
            for obj in ("train", "serve"):
                _cfg, g, s = results[obj]
                q = serve_latency_quantiles(g, s, cfg_serve)
                for k, v in q.items():
                    out[f"{obj}_sim_{k}_ms"] = round(v * 1e3, 4)
                mean_sim = Simulator(spec, num_devices=n_devices,
                                     inference=True)
                out[f"{obj}_sim_mean_ms"] = round(
                    mean_sim.simulate(g, s) * 1e3, 4)
            out["strategies_differ"] = (
                _named(*results["train"][1:]) != _named(*results["serve"][1:]))
            if out["serve_sim_p99_ms"]:
                out["p99_win_ratio"] = round(
                    out["train_sim_p99_ms"] / out["serve_sim_p99_ms"], 3)
            rows[topo] = out
            print(json.dumps({
                "serve_sweep": name, "topology": topo,
                **{k: v for k, v in out.items()
                   if not k.endswith("decode_views")}}))
        sweep["models"][name] = rows

    # prefill contrast row: the compute-bound serving phase — plain
    # causal forward, searched under inference mode (train currency;
    # no decode ops, so no serve Pareto exists by construction)
    cfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                      search_budget=8, search_timeout_s=45.0,
                      comp_mode="inference", cost_cache_file="")
    m = build_gpt_prefill(cfg, **{k: v for k, v in GPT_DECODE_KW.items()
                                  if k not in ("page_size",
                                               "pages_per_seq")},
                          seq_len=256)
    t0 = time.monotonic()
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    sim = Simulator(cfg.machine_spec, num_devices=n_devices,
                    inference=True)
    sweep["prefill"] = {
        "config": "gpt_prefill (GPT_DECODE_KW widths, seq 256)",
        "sim_mean_ms": round(sim.simulate(g, s) * 1e3, 4),
        "search_seconds": round(time.monotonic() - t0, 2),
        "nodes": g.num_nodes,
    }
    print(json.dumps({"serve_sweep": "prefill", **sweep["prefill"]}))
    return sweep


def _serve_sweep_md_lines(sweep):
    lines = [
        "",
        "## Inference serving (decode zoo: train vs serve objective)",
        "",
        sweep.get("note", ""),
        "",
        "| config | topology | objective | decode views | sim mean ms | "
        "sim p50 ms | sim p90 ms | sim p99 ms | KV MB/dev | differ | "
        "p99 win |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for name, rows in sweep.get("models", {}).items():
        for topo, r in rows.items():
            for obj in ("train", "serve"):
                views = "; ".join(
                    f"{v['dims']}r{v['replica']}"
                    for v in r.get(f"{obj}_decode_views", [])[:2])
                lines.append(
                    f"| {name} | {topo} | {obj} | {views} | "
                    f"{r.get(f'{obj}_sim_mean_ms')} | "
                    f"{r.get(f'{obj}_sim_p50_ms')} | "
                    f"{r.get(f'{obj}_sim_p90_ms')} | "
                    f"{r.get(f'{obj}_sim_p99_ms')} | "
                    f"{r.get(f'{obj}_kv_mb_per_device')} | "
                    f"{'yes' if r.get('strategies_differ') else 'no'} | "
                    f"{r.get('p99_win_ratio', '—') if obj == 'serve' else ''} |")
    pre = sweep.get("prefill")
    if pre:
        lines += [
            "",
            f"Prefill contrast ({pre['config']}): "
            f"{pre['sim_mean_ms']} ms simulated forward, "
            f"{pre['nodes']} nodes — the compute-bound phase keeps the "
            f"train currency (no decode ops, nothing ragged).",
        ]
    lines += [
        "",
        "p99 win = serve-objective strategy's simulated p99 advantage "
        "over the throughput strategy's, both scored in the SAME "
        "arrival-model currency.  'differ' marks the configs where the "
        "two objectives select different strategies — the serving "
        "Pareto point (ragged max-shard imbalance vs the head-split's "
        "partial-sum tax) is real, not asserted.",
    ]
    return lines


# the short-prompt interactive decode config where disaggregation
# genuinely pays on the stock machine model: the batch-1 prefill pass
# is weight-streaming-bound (short prompts amortize the weight stream
# over few tokens), so a prompt's KV handoff is cheap relative to the
# phase interference colocation pays — the regime arXiv:2110.10548's
# placement synthesis targets.  The long-cache GPT_DECODE_SERVE_KW
# config honestly stays colocated (its handoff is fat, its decode
# phase wants every device).
GPT_DECODE_CHAT_KW = dict(vocab=4096, num_layers=2, hidden=2048,
                          num_heads=16, ff_dim=4096, page_size=16,
                          pages_per_seq=32)
CHAT_ARRIVAL = dict(serve_prompt_tokens_mean=128,
                    serve_decode_tokens_mean=32)


def disagg_sweep(n_devices):
    """The --disagg sweep, two legs:

    (1) SIMULATED prefill/decode disaggregation (search/
    disaggregation.py): for each decode config, the serve-objective
    search runs, then the disaggregation proposal prices colocated vs
    two-block placement in the serve currency (seconds per decode
    frame, phase-split arrival load, KV handoff as a cross-block
    transfer).  The chat config adopts; the long-cache serve config
    records an honest zero.

    (2) MEASURED chunked-prefill TTFT on the 8-dev CPU host mesh: the
    SAME searched decode model serves the SAME seeded ragged request
    set twice — prefill-via-decode (one frame per prompt token) vs the
    chunked lane (runtime/prefill.py) — token-identity asserted, TTFT
    p50/p99 recorded for both.  CPU-mesh honesty: the measured win is
    frame dispatch + batched math (the real chunking win on any
    backend); HBM cache-streaming ratios stay simulated until a TPU
    run."""
    import os
    import tempfile

    import numpy as np

    import flexflow_tpu as ff
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import (
        GPT_DECODE_SERVE_KW,
        SERVE_FRAME_SLOTS,
        build_gpt_decode,
    )
    from flexflow_tpu.obs.events import BUS
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )
    from flexflow_tpu.search.disaggregation import propose_disaggregation
    from flexflow_tpu.search.driver import optimize_strategy

    sweep = {
        "devices": n_devices,
        "note": (
            "disaggregation leg simulated on the TPU machine model "
            "(phase-split serve currency: seconds per decode frame "
            "incl. the arriving prompts' prefill share; KV handoff "
            "priced at the boundary link); TTFT leg MEASURED on the "
            "CPU host mesh — the chunked win there is frame dispatch "
            "+ batched prompt math, the part of the win a CPU can "
            "exhibit"),
        "models": {},
    }

    configs = {
        "gpt_decode_chat": (32, GPT_DECODE_CHAT_KW, CHAT_ARRIVAL),
        "gpt_decode_serve": (SERVE_FRAME_SLOTS, GPT_DECODE_SERVE_KW, {}),
    }
    for name, (batch, kw, arrival) in configs.items():
        cfg = ff.FFConfig(
            batch_size=batch, num_devices=n_devices, search_budget=8,
            search_timeout_s=60.0, objective="serve",
            comp_mode="inference", cost_cache_file="", **arrival)
        m = build_gpt_decode(cfg, **kw)
        t0 = time.monotonic()
        g, s = optimize_strategy(m.graph, cfg, return_graph=True)
        prop = propose_disaggregation(
            g, s, cfg, base_graph=m.graph if g is not m.graph else None)
        row = {"search_seconds": round(time.monotonic() - t0, 2),
               "arrival": arrival or "defaults"}
        if prop is None:
            row["proposal"] = None
        else:
            row.update({
                "colocated_step_ms": round(prop.colocated_step_s * 1e3, 4),
                "disagg_step_ms": round(prop.disagg_step_s * 1e3, 4),
                "handoff_ms": round(prop.handoff_s * 1e3, 4),
                "prefill_devices": prop.prefill_devices,
                "decode_devices": prop.decode_devices,
                "prefill_tokens_per_frame": prop.prefill_tokens_per_frame,
                "spans_dcn": prop.spans_dcn,
                "adopted": prop.adopted,
                "win_ratio": round(
                    prop.colocated_step_s / prop.disagg_step_s, 3),
            })
        sweep["models"][name] = row
        print(json.dumps({"disagg_sweep": name, **row}))

    # ---- measured TTFT: chunked prefill vs prefill-via-decode ---------
    kw = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
              ff_dim=128, page_size=8, pages_per_seq=8)
    chunk = 8
    rng0 = np.random.default_rng(7)
    prompts = [list(map(int, rng0.integers(1, 255, size=int(L))))
               for L in rng0.integers(4, 49, size=12)]

    def _measured(use_chunk):
        cfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                          search_budget=4, search_timeout_s=30.0,
                          cost_cache_file="",
                          machine_spec=MachineSpec.host_cpu(n_devices))
        m = build_gpt_decode(cfg, **kw)
        m.compile(loss_type="sparse_categorical_crossentropy",
                  metrics=[], comp_mode="inference")
        step = compiled_decode_step(
            m, prefill_chunk=chunk if use_chunk else 0)
        ex = ContinuousBatchingExecutor(
            step, max_seqs=8, page_size=8, pages_per_seq=8,
            prefill_fn=getattr(step, "prefill", None),
            prefill_chunk=chunk if use_chunk else 0)
        reqs = [DecodeRequest(rid=f"r{i}", prompt=list(p),
                              max_new_tokens=8)
                for i, p in enumerate(prompts)]
        log = tempfile.mktemp(suffix=".jsonl")
        BUS.configure(log)
        try:
            # warm the jitted programs so TTFT measures steady state,
            # not compile (a production server pays compile once)
            warm = ContinuousBatchingExecutor(
                step, max_seqs=8, page_size=8, pages_per_seq=8,
                prefill_fn=getattr(step, "prefill", None),
                prefill_chunk=chunk if use_chunk else 0)
            warm.run([DecodeRequest(rid="w", prompt=[1] * (chunk + 3),
                                    max_new_tokens=2)], max_frames=60)
            out = ex.run(reqs, max_frames=2000)
        finally:
            BUS.close()
            os.remove(log)
        summ = ex.summary()
        return out, {
            "frames": summ["frames"],
            "prefill_chunks": summ["prefill_chunks"],
            "ttft_p50_ms": round((summ.get("ttft_p50_s") or 0) * 1e3, 3),
            "ttft_p99_ms": round((summ.get("ttft_p99_s") or 0) * 1e3, 3),
            "prefill_p50_ms": round(
                (summ.get("prefill_p50_s") or 0) * 1e3, 3),
            "queue_p50_ms": round(
                (summ.get("queue_p50_s") or 0) * 1e3, 3),
        }

    out_oracle, row_oracle = _measured(False)
    out_chunk, row_chunk = _measured(True)
    token_identical = out_oracle == out_chunk
    ttft = {
        "config": "gpt_decode small (2L, h64, 12 ragged prompts of "
                  "4..48 tokens, chunk 8, searched strategy, host mesh)",
        "token_identical": token_identical,
        "via_decode": row_oracle,
        "chunked": row_chunk,
        "ttft_p50_win": round(
            row_oracle["ttft_p50_ms"]
            / max(row_chunk["ttft_p50_ms"], 1e-9), 2),
        "ttft_p99_win": round(
            row_oracle["ttft_p99_ms"]
            / max(row_chunk["ttft_p99_ms"], 1e-9), 2),
    }
    if not token_identical:
        ttft["note"] = "TOKEN MISMATCH — the chunked lane is broken"
    sweep["measured_ttft"] = ttft
    print(json.dumps({"disagg_sweep": "measured_ttft", **ttft}))
    return sweep


def _disagg_sweep_md_lines(sweep):
    lines = [
        "",
        "## Prefill/decode disaggregation & chunked prefill",
        "",
        sweep.get("note", ""),
        "",
        "| config | coloc ms/frame | disagg ms/frame | handoff ms | "
        "split | pre tok/frame | adopted | win |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for name, r in sweep.get("models", {}).items():
        if r.get("proposal", "x") is None:
            lines.append(f"| {name} | — | — | — | — | — | no | — |")
            continue
        lines.append(
            f"| {name} | {r.get('colocated_step_ms')} | "
            f"{r.get('disagg_step_ms')} | {r.get('handoff_ms')} | "
            f"{r.get('prefill_devices')}/{r.get('decode_devices')} | "
            f"{r.get('prefill_tokens_per_frame')} | "
            f"{'YES' if r.get('adopted') else 'no'} | "
            f"{r.get('win_ratio')}x |")
    t = sweep.get("measured_ttft")
    if t:
        o, c = t["via_decode"], t["chunked"]
        lines += [
            "",
            f"Measured chunked-prefill TTFT ({t['config']}): "
            f"token-identical {'YES' if t['token_identical'] else 'NO'}.",
            "",
            "| lane | frames | prefill chunks | TTFT p50 ms | "
            "TTFT p99 ms |",
            "|---|---|---|---|---|",
            f"| prefill-via-decode | {o['frames']} | — | "
            f"{o['ttft_p50_ms']} | {o['ttft_p99_ms']} |",
            f"| chunked prefill | {c['frames']} | "
            f"{c['prefill_chunks']} | {c['ttft_p50_ms']} | "
            f"{c['ttft_p99_ms']} |",
            "",
            f"TTFT win: {t['ttft_p50_win']}x p50 / "
            f"{t['ttft_p99_win']}x p99 — measured, the chunked output "
            f"token-identical to the token-by-token oracle.",
        ]
    lines += [
        "",
        "Disaggregation is the searched two-block placement "
        "(search/disaggregation.py): prefill and decode graphs on "
        "disjoint submeshes, phases overlapped, the admitted prompts' "
        "KV pages priced as a cross-block transfer.  The chat config "
        "(short prompts — the weight-streaming-bound prefill regime) "
        "adopts; the long-cache config's honest zero shows colocation "
        "winning where the decode phase wants every device.",
    ]
    return lines


def kv_sweep(n_devices):
    """The --kv sweep, two legs (ISSUE 18 — KV memory as a searched
    resource):

    (1) SEARCHED KV-cache precision (simulated, TPU machine model):
    the gpt_decode_chat serve-objective search runs with
    ``kv_precision="search"`` + 2 shared prefix pages/seq; the driver
    prices fp32/bf16/int8 pool clones in the serve currency (decode
    stream + quantize-overhead passes, residency discounted by the
    shared factor) and the winning ``__meta__.kv`` is recorded —
    chosen dtype, per-dtype predicted p99, pool bytes/device.

    (2) MEASURED radix prefix sharing on the CPU host mesh: eight
    seeded requests share a 48-token system prompt with divergent
    tails (one diverging MID-page to exercise copy-on-write); the SAME
    request set serves through a FIXED 29-page pool with sharing off
    vs on — peak concurrent sequences, shared/private page claims,
    prompt tokens skipped at prefill, CoW copies, and token-identity
    vs solo single-request runs all recorded.  Plus the accuracy
    contract at op level: int8/bf16 pool drift vs the fp32 attention
    path and quant-kernel-vs-XLA agreement on seeded pages.
    CPU-mesh honesty: the dequant overhead and sharing concurrency are
    measured for real; HBM cache-stream ratios stay simulated until a
    TPU run."""
    import numpy as np

    import flexflow_tpu as ff
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )
    from flexflow_tpu.search import driver as _driver
    from flexflow_tpu.search.driver import optimize_strategy

    sweep = {
        "devices": n_devices,
        "note": (
            "precision leg simulated on the TPU machine model (serve "
            "currency: p99 seconds/frame incl. KV_QUANT_PASSES write "
            "overhead; residency discounted by the shared-prefix "
            "factor); sharing + drift legs MEASURED on the CPU host "
            "mesh — concurrency and dequant drift are real there, HBM "
            "stream ratios are not"),
    }

    # ---- leg 1: searched pool precision (simulated) -------------------
    cfg = ff.FFConfig(
        batch_size=32, num_devices=n_devices, search_budget=8,
        search_timeout_s=60.0, objective="serve",
        comp_mode="inference", cost_cache_file="",
        kv_precision="search", serve_shared_prefix_pages=2,
        **CHAT_ARRIVAL)
    m = build_gpt_decode(cfg, **GPT_DECODE_CHAT_KW)
    t0 = time.monotonic()
    optimize_strategy(m.graph, cfg)
    meta = dict(_driver.LAST_KV_META or {})
    p99 = meta.get("predicted_p99_step_ms") or {}
    chosen = meta.get("dtype")
    searched = {
        "config": "gpt_decode_chat (serve objective, kv_precision="
                  "search, 2 shared prefix pages/seq)",
        "search_seconds": round(time.monotonic() - t0, 2),
        "dtype": chosen,
        "predicted_p99_step_ms": p99,
        "p99_win_vs_fp32": (
            round(p99["fp32"] / p99[chosen], 4)
            if chosen in p99 and p99.get("fp32") else None),
        "kv_bytes_per_device": meta.get("kv_bytes_per_device"),
        "shared_prefix_pages": meta.get("shared_prefix_pages"),
        "shared_residency_factor": meta.get("shared_residency_factor"),
    }
    sweep["searched_precision"] = searched
    print(json.dumps({"kv_sweep": "searched_precision", **searched}))

    # ---- leg 2a: measured prefix sharing (CPU host mesh) --------------
    kw = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
              ff_dim=128, page_size=8, pages_per_seq=10)
    page_bytes = 2 * 8 * 64 * 4  # K+V, page_size x hidden, fp32
    rng = np.random.default_rng(7)
    sys_prompt = list(map(int, rng.integers(1, 255, size=48)))
    # r0 carries a 10-token tail so its page 6 (tokens 48..55) fills
    # and registers; rc agrees with r0 for 4 tokens past the page-6
    # boundary then diverges MID-page — the copy-on-write case; the
    # rest diverge exactly at the boundary (pure refcount claims)
    tails = [list(map(int, rng.integers(1, 255, size=int(L))))
             for L in [10, 4, 4, 5, 5, 6, 6]]
    prompts = [sys_prompt + t for t in tails]
    prompts.append(sys_prompt + tails[0][:4]
                   + list(map(int, rng.integers(1, 255, size=3))))
    scfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                       search_budget=4, search_timeout_s=30.0,
                       cost_cache_file="",
                       machine_spec=MachineSpec.host_cpu(n_devices))
    sm = build_gpt_decode(scfg, **kw)
    sm.compile(loss_type="sparse_categorical_crossentropy",
               metrics=[], comp_mode="inference")
    step = compiled_decode_step(sm, prefill_chunk=8)

    def _serve(sharing, num_pages, reqs):
        ex = ContinuousBatchingExecutor(
            step, max_seqs=8, page_size=8, pages_per_seq=10,
            num_pages=num_pages,
            prefill_fn=getattr(step, "prefill", None), prefill_chunk=8,
            prefix_sharing=sharing,
            copy_page_fn=step.copy_page if sharing else None)
        ex.submit(reqs)
        peak = 0
        while ex.queue or any(s is not None for s in ex.slots):
            if ex.frame >= 2000:
                raise RuntimeError("kv sweep decode run stuck")
            ex.step()
            peak = max(peak, sum(s is not None for s in ex.slots))
        return dict(ex.finished), peak, ex.summary()

    def _reqs():
        return [DecodeRequest(rid=f"r{i}", prompt=list(p),
                              max_new_tokens=8)
                for i, p in enumerate(prompts)]

    pool = 29  # FIXED pool: 1 scratch + 2 full allotments with change
    out_off, peak_off, _ = _serve(False, pool, _reqs())
    out_on, peak_on, summ_on = _serve(True, pool, _reqs())
    solo = {}
    for i, p in enumerate(prompts):
        one, _, _ = _serve(False, 0, [DecodeRequest(
            rid=f"r{i}", prompt=list(p), max_new_tokens=8)])
        solo.update(one)
    sharing = {
        "config": "gpt_decode small (2L, h64, 8 requests over a "
                  "48-token shared system prompt, fixed 29-page pool, "
                  "chunk-8 prefill, host mesh)",
        "pool_pages": pool,
        "kv_pool_bytes": pool * page_bytes,
        "max_concurrent_off": peak_off,
        "max_concurrent": peak_on,
        "concurrency_win": round(peak_on / max(peak_off, 1), 2),
        "token_identical_batched_vs_solo": (out_on == solo
                                            and out_off == solo),
        "prefix_hits": summ_on.get("prefix_hits"),
        "shared_pages": summ_on.get("shared_pages"),
        "private_pages": summ_on.get("private_pages"),
        "cow_copies": summ_on.get("cow_copies"),
        "prefix_tokens": summ_on.get("prefix_tokens"),
        "kv_shared_bytes": summ_on.get("shared_pages", 0) * page_bytes,
    }
    if not sharing["token_identical_batched_vs_solo"]:
        sharing["note"] = ("TOKEN MISMATCH — shared pages corrupted a "
                           "sibling's stream")
    sweep["measured_sharing"] = sharing
    print(json.dumps({"kv_sweep": "measured_sharing", **sharing}))

    # ---- leg 2b: accuracy contract (measured, op level) ---------------
    import math

    import jax.numpy as jnp

    from flexflow_tpu.kernels.ragged_paged_attention import (
        _xla_ragged_paged_quant,
        ragged_paged_attention,
        ragged_paged_attention_quant,
    )
    from flexflow_tpu.ops.decode_attention import _quantize_kv

    P, ps, H, D, B, pps = 16, 8, 4, 16, 4, 4
    k = jnp.asarray(rng.normal(size=(P, ps, H * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, ps, H * D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(P)[:B * pps].reshape(B, pps), jnp.int32)
    lens = jnp.asarray(rng.integers(ps, ps * pps, size=B), jnp.int32)
    ref = ragged_paged_attention(q, k, v, table, lens)
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    got8 = ragged_paged_attention_quant(q, kq, vq, ks, vs, table, lens)
    xla8 = _xla_ragged_paged_quant(q, kq, vq, ks, vs, table, lens,
                                   1.0 / math.sqrt(D))
    gotbf = ragged_paged_attention(
        q, k.astype(jnp.bfloat16).astype(jnp.float32),
        v.astype(jnp.bfloat16).astype(jnp.float32), table, lens)
    drift = {
        "int8_max_abs_drift": float(jnp.max(jnp.abs(got8 - ref))),
        "bf16_max_abs_drift": float(jnp.max(jnp.abs(gotbf - ref))),
        "int8_kernel_vs_xla": float(jnp.max(jnp.abs(got8 - xla8))),
        "contract_bound": 0.05,
    }
    drift["within_contract"] = (
        drift["int8_max_abs_drift"] < drift["contract_bound"])
    sweep["accuracy_contract"] = drift
    print(json.dumps({"kv_sweep": "accuracy_contract", **drift}))
    return sweep


def _kv_sweep_md_lines(sweep):
    lines = [
        "",
        "## KV memory as a searched resource "
        "(prefix sharing + pool precision)",
        "",
        sweep.get("note", ""),
    ]
    s = sweep.get("searched_precision")
    if s:
        p99 = s.get("predicted_p99_step_ms") or {}
        lines += [
            "",
            f"Searched pool precision ({s['config']}): the lane chose "
            f"**{s.get('dtype')}** in {s.get('search_seconds')}s.",
            "",
            "| pool dtype | predicted p99 ms/frame |",
            "|---|---|",
        ] + [f"| {d}{' (chosen)' if d == s.get('dtype') else ''} | "
             f"{p99[d]} |" for d in ("fp32", "bf16", "int8") if d in p99]
        if s.get("p99_win_vs_fp32") is not None:
            lines += [
                "",
                f"p99 win vs fp32: {s['p99_win_vs_fp32']}x at "
                f"{s.get('kv_bytes_per_device')} pool bytes/device; "
                f"{s.get('shared_prefix_pages')} shared prefix "
                f"page(s)/seq discount residency to "
                f"{s.get('shared_residency_factor')} of the private "
                f"pool (stream is never discounted — every sequence "
                f"still reads its own prefix).",
            ]
    m = sweep.get("measured_sharing")
    if m:
        lines += [
            "",
            f"Measured radix prefix sharing ({m['config']}): "
            f"token-identical to solo "
            f"{'YES' if m['token_identical_batched_vs_solo'] else 'NO'}.",
            "",
            "| lane | peak concurrent seqs | shared pages | "
            "private pages | CoW copies | prompt tokens skipped |",
            "|---|---|---|---|---|---|",
            f"| sharing off | {m['max_concurrent_off']} | — | — | — | "
            f"— |",
            f"| sharing on | {m['max_concurrent']} | "
            f"{m['shared_pages']} | {m['private_pages']} | "
            f"{m['cow_copies']} | {m['prefix_tokens']} |",
            "",
            f"Concurrency win at a fixed {m['pool_pages']}-page pool "
            f"({m['kv_pool_bytes']} bytes): {m['concurrency_win']}x — "
            f"measured, {m['prefix_hits']} of the admissions claimed "
            f"cached prefix pages by refcount instead of allocating.",
        ]
    d = sweep.get("accuracy_contract")
    if d:
        lines += [
            "",
            f"Accuracy contract (seeded pages, op level): int8 pool "
            f"max-abs drift {d['int8_max_abs_drift']:.2e} vs fp32 "
            f"(bound {d['contract_bound']}, "
            f"{'WITHIN' if d['within_contract'] else 'EXCEEDED'}), "
            f"bf16 {d['bf16_max_abs_drift']:.2e}, quant kernel vs XLA "
            f"fallback {d['int8_kernel_vs_xla']:.2e}.",
        ]
    return lines


# the mixed-SLO class table every fleet leg shares: an interactive
# trickle (1/8 of arrivals, priority 2, 64-frame deadline), a standard
# stream (2/8), and a batch flood (5/8 of arrivals, watched at p90) —
# the weighted-arrival shape where per-class routing has something to
# decide (equal-weight classes make uniform routing trivially optimal)
FLEET_SLO = ("interactive:2:64:0.99:1,standard:1:0:0.99:2,"
             "batch:0:0:0.9:5")


def fleet_sweep(n_devices):
    """The --fleet sweep, two legs:

    (1) SIMULATED fleet search (search/fleet.py) on the chat decode
    config and the TPU machine model: ``propose_fleet`` enumerates
    replica-block partitions x per-SLO-class routing policies, each
    block's strategy re-searched at its own width, every candidate
    priced by the phase-split serving simulator in per-class p99
    currency.  Recorded at nominal offered load, then re-searched at
    1.8x — the drift episode: the controller's re-search re-sizes the
    fleet (more, narrower replicas once queueing dominates).

    (2) MEASURED mixed-SLO serving on the CPU host mesh: the fleet the
    search picks FOR THE HOST MACHINE MODEL (max_replicas=3 so the
    partition space holds unequal widths) serves a seeded 32-request
    interactive/standard/batch trace against the single-replica and
    naive uniform-fleet (even halving, uniform routing) baselines —
    same compiled frames, same trace, token-identity asserted,
    per-class TTFT/e2e p99 measured via the fleet roll-up
    (runtime/fleet.py)."""
    import os
    import random
    import tempfile

    import flexflow_tpu as ff
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.obs.events import BUS
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        SLOClass,
        compiled_decode_step,
    )
    from flexflow_tpu.runtime.fleet import FleetExecutor
    from flexflow_tpu.search.driver import optimize_strategy
    from flexflow_tpu.search.fleet import propose_fleet

    sweep = {
        "devices": n_devices,
        "slo_classes": FLEET_SLO,
        "note": (
            "fleet leg simulated on the TPU machine model (per-class "
            "p99 currency: each replica block's searched strategy "
            "re-simulated at its routed share's occupancy, priority-"
            "aware queueing per class); serving leg MEASURED on the "
            "CPU host mesh — the fleet the search picks for the HOST "
            "machine model serves a seeded mixed-SLO trace against "
            "single-replica and uniform-fleet baselines"),
    }

    def _prop_row(prop):
        if prop is None:
            return {"proposal": None}
        return {
            "replicas": [r.devices for r in prop.replicas],
            "routing_policy": prop.routing_policy,
            "routing": {c: [round(f, 3) for f in fr]
                        for c, fr in sorted(prop.routing.items())},
            "single_ms": round(prop.single_cost_s * 1e3, 4),
            "fleet_ms": round(prop.fleet_cost_s * 1e3, 4),
            "per_class_p99_ms": {
                c: round(v * 1e3, 4)
                for c, v in sorted(prop.per_class_p99_s.items())},
            "adopted": prop.adopted,
            "win_ratio": round(
                prop.single_cost_s / max(prop.fleet_cost_s, 1e-12), 3),
        }

    # ---- (1) simulated: searched fleet + drift-episode re-size -------
    cfg = ff.FFConfig(
        batch_size=8, num_devices=n_devices, search_budget=8,
        search_timeout_s=60.0, objective="serve",
        comp_mode="inference", cost_cache_file="",
        serve_slo_classes=FLEET_SLO, **CHAT_ARRIVAL)
    m = build_gpt_decode(cfg, **GPT_DECODE_CHAT_KW)
    t0 = time.monotonic()
    g, s = optimize_strategy(m.graph, cfg, return_graph=True)
    base = m.graph if g is not m.graph else None
    nominal = propose_fleet(g, s, cfg, base_graph=base)
    drift = propose_fleet(g, s, cfg, base_graph=base, load_scale=1.8)
    sim = {
        "config": "gpt_decode_chat (2L, h2048) on the TPU machine "
                  "model, serve objective, chat arrival",
        "search_seconds": round(time.monotonic() - t0, 2),
        "nominal": _prop_row(nominal),
        "drift": {"load_scale": 1.8, **_prop_row(drift)},
    }
    if nominal is not None and drift is not None:
        sim["drift"]["resized"] = (
            len(drift.replicas) != len(nominal.replicas))
    sweep["simulated"] = sim
    print(json.dumps({"fleet_sweep": "simulated", **sim}))

    # ---- (2) measured: searched fleet vs baselines on the host mesh --
    kw = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
              ff_dim=128, page_size=8, pages_per_seq=8)
    cfg_h = ff.FFConfig(
        batch_size=8, num_devices=n_devices, search_budget=4,
        search_timeout_s=30.0, objective="serve",
        comp_mode="inference", cost_cache_file="",
        serve_slo_classes=FLEET_SLO, serve_fleet_max_replicas=3,
        machine_spec=MachineSpec.host_cpu(n_devices))
    m_h = build_gpt_decode(cfg_h, **kw)
    g_h, s_h = optimize_strategy(m_h.graph, cfg_h, return_graph=True)
    prop_h = propose_fleet(
        g_h, s_h, cfg_h,
        base_graph=m_h.graph if g_h is not m_h.graph else None)
    measured = {
        "config": "gpt_decode small (2L, h64) on the CPU host mesh, "
                  "32-request seeded interactive/standard/batch trace "
                  "(seed 7, arrival weights 1:2:5)",
        "host_search": _prop_row(prop_h),
    }

    classes = [SLOClass(name=c["name"], priority=c["priority"],
                        deadline_frames=c["deadline_frames"],
                        quantile=c["quantile"])
               for c in cfg_h.serve_slo_classes]
    class_names = [c.name for c in classes]

    rng = random.Random(7)
    trace = []
    for i in range(32):
        slo = rng.choices(class_names, weights=[1, 2, 5])[0]
        plen = rng.randint(4, 32)
        trace.append(DecodeRequest(
            rid=f"r{i:02d}",
            prompt=[rng.randrange(2, 250) for _ in range(plen)],
            max_new_tokens=rng.randint(4, 12), slo=slo))

    # one compiled decode frame per replica width, shared across the
    # variants (fresh executors each run; the frames are stateless)
    steps = {}

    def _step_for(width):
        if width not in steps:
            c = ff.FFConfig(batch_size=8, num_devices=width,
                            comp_mode="inference", cost_cache_file="",
                            machine_spec=MachineSpec.host_cpu(width))
            mm = build_gpt_decode(c, **kw)
            mm.compile(loss_type="sparse_categorical_crossentropy",
                       metrics=[], comp_mode="inference")
            step = compiled_decode_step(mm)
            # jit-warm outside timing (a server pays compile once)
            ContinuousBatchingExecutor(
                step, max_seqs=8, page_size=8, pages_per_seq=8).run(
                [DecodeRequest(rid="w", prompt=[1, 2, 3],
                               max_new_tokens=2)], max_frames=20)
            steps[width] = step
        return steps[width]

    def _measure(widths, routing):
        reps = [ContinuousBatchingExecutor(
                    _step_for(w), max_seqs=8, page_size=8,
                    pages_per_seq=8, slo_classes=classes,
                    replica_label=str(i))
                for i, w in enumerate(widths)]
        fl = FleetExecutor(reps, routing, slo_classes=classes, seed=7)
        t0 = time.monotonic()
        out = fl.run(trace)
        wall = time.monotonic() - t0
        summ = fl.summary()
        row = {"replicas": list(widths), "wall_s": round(wall, 2),
               "per_class": {}}
        for name, d in sorted(summ["slo_classes"].items()):
            row["per_class"][name] = {
                "completed": d["completed"],
                "ttft_p99_ms": round((d["ttft_p99_s"] or 0) * 1e3, 1),
                "e2e_p99_ms": round((d["e2e_p99_s"] or 0) * 1e3, 1),
            }
        toks = {k: tuple(v) for k, v in out.items()
                if not k.startswith("w")}
        return row, toks

    half = max(1, n_devices // 2)
    variants = {
        "single_replica": ([n_devices],
                           {c: [1.0] for c in class_names}),
        "uniform_fleet": ([half, half],
                          {c: [0.5, 0.5] for c in class_names}),
    }
    if prop_h is not None and len(prop_h.replicas) > 1:
        variants["searched_fleet"] = (
            [r.devices for r in prop_h.replicas], prop_h.routing)
    else:
        measured["note"] = ("host search kept a single replica — no "
                            "searched variant to measure")

    # the roll-up percentiles need the request records, which only
    # stamp while the obs bus is armed; borrow a scratch log when the
    # caller has not configured one (and leave theirs alone when it has)
    scratch = None
    if not BUS.enabled:
        scratch = tempfile.mktemp(suffix=".jsonl")
        BUS.configure(scratch)
    try:
        tok_sets = []
        for vname, (widths, routing) in variants.items():
            row, toks = _measure(widths, routing)
            measured[vname] = row
            tok_sets.append(toks)
            print(json.dumps({"fleet_sweep": vname, **row}))
    finally:
        if scratch is not None:
            BUS.close()
            if os.path.exists(scratch):
                os.remove(scratch)
    measured["token_identical"] = all(
        t == tok_sets[0] for t in tok_sets[1:])
    if not measured["token_identical"]:
        measured["note"] = ("TOKEN MISMATCH across fleet variants — "
                            "routing must not change what is generated")
    sweep["measured"] = measured
    print(json.dumps({"fleet_sweep": "token_identical",
                      "value": measured["token_identical"]}))
    return sweep


def _fleet_sweep_md_lines(sweep):
    lines = [
        "",
        "## Serving fleet",
        "",
        sweep.get("note", ""),
        "",
    ]
    sim = sweep.get("simulated") or {}
    nom = sim.get("nominal") or {}
    dri = sim.get("drift") or {}

    def _sim_row(tag, r, ls):
        if not r or r.get("proposal", "x") is None:
            return f"| {tag} | {ls} | — | — | — | — | — | no |"
        pc = "; ".join(f"{c} {v}" for c, v in
                       (r.get("per_class_p99_ms") or {}).items())
        return (f"| {tag} | {ls} | {r.get('replicas')} | "
                f"{r.get('routing_policy')} | {r.get('single_ms')} | "
                f"{r.get('fleet_ms')} | {pc} | "
                f"{'YES' if r.get('adopted') else 'no'} |")

    lines += [
        f"Simulated fleet search ({sim.get('config', '')}):",
        "",
        "| episode | load | replicas | routing | single ms | fleet ms "
        "| per-class p99 ms | adopted |",
        "|---|---|---|---|---|---|---|---|",
        _sim_row("nominal", nom, 1.0),
        _sim_row("drift re-search", dri, dri.get("load_scale", "—")),
    ]
    if nom.get("replicas") and dri.get("replicas"):
        k0, k1 = len(nom["replicas"]), len(dri["replicas"])
        lines += [
            "",
            f"Drift episode: offered load x{dri.get('load_scale')} "
            f"re-sizes the fleet {k0} -> {k1} replicas "
            f"({'RESIZED' if k0 != k1 else 'shape held'}) — queueing "
            f"dominance pushes the search toward more, narrower "
            f"blocks; the controller applies the same re-search live "
            f"on measured per-class p99 drift "
            f"(runtime/controller.py observe_fleet).",
        ]
    meas = sweep.get("measured") or {}
    if meas:
        hs = meas.get("host_search") or {}
        names = []
        for v in ("single_replica", "uniform_fleet", "searched_fleet"):
            for c in (meas.get(v) or {}).get("per_class", {}):
                if c not in names:
                    names.append(c)
        lines += [
            "",
            f"Measured mixed-SLO serving ({meas.get('config', '')}); "
            f"host-model search picked {hs.get('replicas')} with "
            f"'{hs.get('routing_policy')}' routing; token-identical "
            f"{'YES' if meas.get('token_identical') else 'NO'}.",
            "",
            "| fleet | replicas | wall s | "
            + " | ".join(f"{c} TTFT/e2e p99 ms" for c in names)
            + " |",
            "|---|---|---|" + "---|" * len(names),
        ]
        for v in ("single_replica", "uniform_fleet", "searched_fleet"):
            r = meas.get(v)
            if not r:
                continue
            cells = []
            for c in names:
                d = r["per_class"].get(c)
                cells.append(f"{d['ttft_p99_ms']} / {d['e2e_p99_ms']}"
                             if d else "—")
            lines.append(f"| {v.replace('_', ' ')} | {r['replicas']} | "
                         f"{r['wall_s']} | " + " | ".join(cells) + " |")
    lines += [
        "",
        "The fleet is the searched N-block serving placement "
        "(search/fleet.py): each replica block gets its own rewriting "
        "search at its width, candidate fleets are priced in per-class "
        "p99 currency with per-SLO-class routing fractions as decision "
        "variables, and runtime/fleet.py executes the winner — N "
        "continuous-batching replicas behind a deficit router honoring "
        "the searched fractions.  The measured leg keeps all variants "
        "token-identical: routing decides WHERE a request queues, "
        "never what it generates.",
    ]
    return lines


def request_trace_sweep(n_devices, out_prefix="BENCH_SEARCH"):
    """The --request-trace sweep, three legs (obs/tracing.py,
    obs/flight.py, obs/slo.py):

    (1) MEASURED request tracing on the CPU host mesh: a 2-replica
    fleet serves the seeded 32-request mixed-SLO trace with the tracer
    armed; every request's span tree is validated (single root, no
    orphans, children nest inside parents, queue+prefill+decode phase
    durations reproduce the measured e2e within tolerance) and the
    whole forest is exported as ``<prefix>_request_traces.json`` —
    Chrome trace-event format, loaded back and structure-checked so
    the artifact provably opens in Perfetto.

    (2) fault post-mortem: a replica is stepped with requests still in
    flight, then a scheduled ``p99_drift`` fault fires — the injection
    dumps the always-on flight ring, and the dump is asserted to hold
    the last-N bus events PLUS the in-flight requests' open spans
    (copied to ``<prefix>_flight_dump.jsonl`` for inspection).

    (3) burn-vs-p99 replay: ``first_fire_indices`` replays latency
    streams and records the completion index at which the multi-window
    burn-rate trigger vs the raw p99-drift trigger first fires — the
    burn signal catches a load ramp earlier and catches a persistent
    moderate (1.3x) violation that p99-drift never sees at all."""
    import os
    import random
    import shutil
    import tempfile

    import flexflow_tpu as ff
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.obs.events import BUS
    from flexflow_tpu.obs.flight import FLIGHT
    from flexflow_tpu.obs.slo import first_fire_indices
    from flexflow_tpu.obs.tracing import TRACER
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        SLOClass,
        compiled_decode_step,
    )
    from flexflow_tpu.runtime.faults import FaultPlan
    from flexflow_tpu.runtime.fleet import FleetExecutor

    sweep = {
        "devices": n_devices,
        "note": (
            "request-scoped tracing MEASURED on the CPU host mesh: a "
            "2-replica fleet serves the seeded 32-request mixed-SLO "
            "trace with the tracer armed; every span tree is "
            "validated and exported as a Chrome/Perfetto trace; a "
            "p99_drift fault injection exercises the always-on flight "
            "ring's post-mortem dump; burn-rate vs p99-drift trigger "
            "ordering is replayed on synthetic latency streams"),
    }

    kw = dict(vocab=256, num_layers=2, hidden=64, num_heads=4,
              ff_dim=128, page_size=8, pages_per_seq=8)
    cfg = ff.FFConfig(
        batch_size=8, num_devices=n_devices, comp_mode="inference",
        cost_cache_file="", serve_slo_classes=FLEET_SLO,
        machine_spec=MachineSpec.host_cpu(n_devices))
    classes = [SLOClass(name=c["name"], priority=c["priority"],
                        deadline_frames=c["deadline_frames"],
                        quantile=c["quantile"])
               for c in cfg.serve_slo_classes]
    class_names = [c.name for c in classes]

    rng = random.Random(7)
    trace = []
    for i in range(32):
        slo = rng.choices(class_names, weights=[1, 2, 5])[0]
        plen = rng.randint(4, 32)
        trace.append(DecodeRequest(
            rid=f"r{i:02d}",
            prompt=[rng.randrange(2, 250) for _ in range(plen)],
            max_new_tokens=rng.randint(4, 12), slo=slo))

    half = max(1, n_devices // 2)
    c_h = ff.FFConfig(batch_size=8, num_devices=half,
                      comp_mode="inference", cost_cache_file="",
                      machine_spec=MachineSpec.host_cpu(half))
    m_h = build_gpt_decode(c_h, **kw)
    m_h.compile(loss_type="sparse_categorical_crossentropy",
                metrics=[], comp_mode="inference")
    step = compiled_decode_step(m_h)
    # jit-warm BEFORE the tracer arms: the warm-up request is not part
    # of the measured forest
    ContinuousBatchingExecutor(
        step, max_seqs=8, page_size=8, pages_per_seq=8).run(
        [DecodeRequest(rid="w", prompt=[1, 2, 3], max_new_tokens=2)],
        max_frames=20)

    def _replicas():
        return [ContinuousBatchingExecutor(
                    step, max_seqs=8, page_size=8, pages_per_seq=8,
                    slo_classes=classes, replica_label=str(i))
                for i in range(2)]

    # the tracer, the obs bus and the flight ring are process globals:
    # borrow them only when the caller has not armed them, and put
    # every knob back afterwards (same discipline as fleet_sweep's
    # scratch bus)
    scratch = None
    if not BUS.enabled:
        scratch = tempfile.mktemp(suffix=".jsonl")
        BUS.configure(scratch)
    tracer_was = TRACER.enabled
    prev_dump_dir = FLIGHT.dump_dir
    tmp = tempfile.mkdtemp(prefix="ff_flight_")
    TRACER.reset()
    TRACER.enabled = True
    FLIGHT.reset()
    FLIGHT.configure(dump_dir=tmp)
    try:
        # ---- (1) traced fleet serve + validation + chrome export -----
        fl = FleetExecutor(_replicas(),
                           {c: [0.5, 0.5] for c in class_names},
                           slo_classes=classes, seed=7)
        t0 = time.monotonic()
        fl.run(trace)
        wall = time.monotonic() - t0
        recs = {r["rid"]: r for r in fl.request_records
                if r.get("phase") == "finish"}
        problems = []
        validated = 0
        for tid in TRACER.trace_ids():
            rec = recs.get(tid.split("#", 1)[0])
            if rec is None:
                continue
            validated += 1
            problems += TRACER.validate_trace(tid, e2e_s=rec["e2e_s"])
        from flexflow_tpu.obs.tracing import forest_stats, span_forest

        forest = span_forest(
            dict(s.to_jsonable(), kind="trace.span")
            for tid in TRACER.trace_ids()
            for s in TRACER.trace_spans(tid))
        total, max_depth, orphans = forest_stats(forest)
        chrome_path = f"{out_prefix}_request_traces.json"
        n_events = TRACER.export_chrome_trace(chrome_path)
        with open(chrome_path) as f:
            doc = json.load(f)
        evs = doc.get("traceEvents", [])
        slices = [e for e in evs if e.get("ph") == "X"]
        chrome_ok = (
            isinstance(evs, list) and len(slices) == n_events
            and all(e.get("ph") in ("X", "M") and "pid" in e
                    and "tid" in e and "name" in e for e in evs)
            and all(e.get("ts", -1) >= 0 and e.get("dur", 0) > 0
                    for e in slices))
        leg = {
            "completed": len(recs),
            "traces_validated": validated,
            "spans": total,
            "max_depth": max_depth,
            "orphans": orphans,
            "open_spans_left": len(TRACER.open_spans()),
            "validation_problems": problems[:8],
            "valid": (not problems and orphans == 0
                      and validated == len(trace)),
            "wall_s": round(wall, 2),
            "chrome_trace": {"path": chrome_path, "events": n_events,
                             "well_formed": chrome_ok},
        }
        sweep["traced_serve"] = leg
        print(json.dumps({"request_trace_sweep": "traced_serve",
                          **{k: v for k, v in leg.items()
                             if k != "validation_problems"}}))

        # ---- (2) fault injection -> flight post-mortem dump ----------
        ex = ContinuousBatchingExecutor(
            step, max_seqs=8, page_size=8, pages_per_seq=8,
            slo_classes=classes, replica_label="pm")
        live_reqs = [DecodeRequest(
            rid=f"pm{i}", prompt=[5 + i, 6 + i, 7 + i],
            max_new_tokens=32, slo="standard") for i in range(3)]
        ex.submit(live_reqs)
        for _ in range(3):
            ex.step()  # admit + a few decode frames; requests stay live
        plan = FaultPlan.parse("p99_drift@0", seed=7)
        fault = plan.due(0)[0]
        ratio = plan.inject_p99_drift(fault)
        dump_path = FLIGHT.last_dump_path
        dump_rows = []
        if dump_path and os.path.exists(dump_path):
            with open(dump_path) as f:
                dump_rows = [json.loads(ln) for ln in f if ln.strip()]
        meta = dump_rows[0] if dump_rows else {}
        open_rows = [r for r in dump_rows
                     if r.get("kind") == "trace.open"]
        live_rids = {r.rid for r in live_reqs}
        covered = {r["trace_id"].split("#", 1)[0] for r in open_rows
                   if "#" in r.get("trace_id", "")} & live_rids
        kept = None
        if dump_path and os.path.exists(dump_path):
            kept = f"{out_prefix}_flight_dump.jsonl"
            shutil.copyfile(dump_path, kept)
        pm = {
            "fault": "p99_drift@0",
            "drift_ratio": round(ratio, 3),
            "dump": kept,
            "meta_reason": meta.get("reason"),
            "ring_events": meta.get("events"),
            "open_spans_in_dump": len(open_rows),
            "live_requests_covered": sorted(covered),
            "post_mortem_ok": (
                meta.get("kind") == "flight.meta"
                and (meta.get("events") or 0) > 0
                and covered == live_rids),
        }
        sweep["fault_post_mortem"] = pm
        print(json.dumps({"request_trace_sweep": "fault_post_mortem",
                          **pm}))
    finally:
        TRACER.reset()
        TRACER.enabled = tracer_was
        FLIGHT.dump_dir = prev_dump_dir
        FLIGHT.reset()
        shutil.rmtree(tmp, ignore_errors=True)
        if scratch is not None:
            BUS.close()
            if os.path.exists(scratch):
                os.remove(scratch)

    # ---- (3) burn-rate vs raw p99-drift trigger ordering -------------
    target = 0.1
    ramp = [0.08 + i * (0.12 / 47.0) for i in range(48)]
    persistent = [0.13] * 48
    scenarios = {}
    for name, lat in (("load_ramp", ramp),
                      ("persistent_1.3x", persistent)):
        burn_at, drift_at = first_fire_indices(lat, target)
        scenarios[name] = {
            "completions": len(lat),
            "burn_fires_at": burn_at,
            "p99_drift_fires_at": drift_at,
            "burn_leads": (drift_at is None
                           or (burn_at is not None
                               and burn_at < drift_at)),
        }
    sweep["burn_vs_p99"] = {
        "target_s": target,
        "scenarios": scenarios,
        "burn_always_leads": all(s["burn_leads"]
                                 for s in scenarios.values()),
    }
    print(json.dumps({"request_trace_sweep": "burn_vs_p99",
                      **sweep["burn_vs_p99"]}))
    return sweep


def _request_trace_md_lines(sweep):
    lines = [
        "",
        "## Observability: request tracing",
        "",
        sweep.get("note", ""),
        "",
    ]
    ts = sweep.get("traced_serve") or {}
    ch = ts.get("chrome_trace") or {}
    lines += [
        "| leg | result |",
        "|---|---|",
        f"| traced serve | {ts.get('completed')} completed, "
        f"{ts.get('traces_validated')} span trees validated "
        f"({'VALID' if ts.get('valid') else 'INVALID'}), "
        f"{ts.get('spans')} spans, depth {ts.get('max_depth')}, "
        f"{ts.get('orphans')} orphans, "
        f"{ts.get('open_spans_left')} left open |",
        f"| Chrome trace | {ch.get('path')}: {ch.get('events')} "
        f"events, well-formed "
        f"{'YES' if ch.get('well_formed') else 'NO'} "
        f"(loads in Perfetto / chrome://tracing) |",
    ]
    pm = sweep.get("fault_post_mortem") or {}
    if pm:
        lines += [
            f"| fault post-mortem | {pm.get('fault')} (ratio "
            f"{pm.get('drift_ratio')}x) dumped {pm.get('ring_events')} "
            f"ring events + {pm.get('open_spans_in_dump')} open spans; "
            f"in-flight requests covered: "
            f"{', '.join(pm.get('live_requests_covered') or []) or '—'} "
            f"({'OK' if pm.get('post_mortem_ok') else 'MISSING'}) |",
        ]
    bp = sweep.get("burn_vs_p99") or {}
    for name, s in sorted((bp.get("scenarios") or {}).items()):
        drift = s.get("p99_drift_fires_at")
        lines += [
            f"| burn vs p99-drift: {name} | burn fires at completion "
            f"{s.get('burn_fires_at')}, p99-drift at "
            f"{drift if drift is not None else 'NEVER'} "
            f"({'burn leads' if s.get('burn_leads') else 'NO LEAD'}) |",
        ]
    lines += [
        "",
        "Every request carries a span tree — route decision, queue "
        "wait, chunked prefill, decode residency, preemption re-queues "
        "— minted at the router and validated against the measured "
        "e2e (obs/tracing.py; render with `tools/ffobs.py trace`).  "
        "The flight ring records the last-N events even while the bus "
        "is off, and fault injections / controller fallbacks dump it "
        "with the in-flight requests' open spans (obs/flight.py).  "
        "The multi-window burn-rate computer (obs/slo.py) gives the "
        "controller an earlier, noise-robust re-search trigger than "
        "raw p99 drift: it catches slow SLO bleed the p99 watch never "
        "sees.",
    ]
    return lines


def co_search_sweep(n_devices):
    """The --co-search sweep: sequential (strategy→plan) vs JOINT
    strategy x comm-plan pricing (search/comm_plan.py, ROADMAP item 2).

    For each sync-bound zoo config (bert/dlrm/mlp) on the flat and
    2-slice topologies, both pipelines run the full substitution
    search — sequential picks the strategy under the legacy per-node
    overlap credit and fits the comm plan afterwards; joint prices
    every candidate with its best plan (sync schedule + per-group wire
    precision + staged reductions + per-group ZeRO) — and both final
    results are then scored in the SAME joint currency (best plan +
    zero credit, exposed-comm simulation), so the step numbers compare
    the strategies, not the scoring.  Also records the joint search's
    wall-clock overhead vs sequential (inception + gpt_xl carry the
    ≤1.5x acceptance) and the comm-plan memo serve rate (≥80%
    acceptance).  Simulated only, deliberately: the priced wins are
    exposed-comm + update-shard terms a CPU mesh cannot exhibit."""
    import dataclasses
    import time as _time

    import flexflow_tpu as ff
    from flexflow_tpu.models import (
        build_dlrm,
        build_gpt_xl,
        build_inception_v3,
        build_mlp_unify,
        build_transformer,
    )
    from flexflow_tpu.search import driver as _driver
    from flexflow_tpu.search.comm_plan import JointPricer
    from flexflow_tpu.search.driver import (
        LAST_SEARCH_STATS,
        optimize_strategy,
    )
    from flexflow_tpu.search.simulator import Simulator

    builders = {
        # bert at batch 64 (per-device 8) with the full sync-bound
        # widths: enough compute that the legacy per-node overlap
        # credit HIDES most of DP's weight sync — the regime where the
        # sequential pipeline's ranking flips vs the exposed-comm joint
        # currency (at per-device batch 1 both pipelines find the same
        # TP strategy and the comparison degenerates to 1.0x)
        "bert": (64, 30, lambda cfg: build_transformer(
            cfg, **SYNC_BOUND_BERT_KW)),
        "dlrm": (64, 20, lambda cfg: build_dlrm(cfg)),
        "mlp": (64, 20, lambda cfg: build_mlp_unify(cfg)),
    }
    base_spec = ff.FFConfig(batch_size=8,
                            num_devices=n_devices).machine_spec
    gap = 10.0
    topologies = {"flat": base_spec}
    if n_devices % 2 == 0 and n_devices // 2 >= 2:
        topologies["2slice"] = dataclasses.replace(
            base_spec, devices_per_host=n_devices // 2,
            dcn_bandwidth=base_spec.ici_bandwidth / gap)

    def _cfg(batch, bud, spec, co):
        return ff.FFConfig(
            batch_size=batch, num_devices=n_devices, search_budget=bud,
            machine_spec=spec, cost_cache_file="",  # each run cold: the
            # comparison is search-vs-search, not cache-vs-cache
            sync_precision="search", sync_schedule="search",
            co_search=co)

    def _joint_price(cfg_joint, g, s):
        """Both pipelines' results scored in the joint currency —
        through Simulator.for_config, the ONE place config-derived
        cost flags are threaded (a hand-built Simulator would silently
        miss the next flag the way sync_ef was nearly missed)."""
        sim = Simulator.for_config(cfg_joint)
        return JointPricer(cfg_joint).price(sim, g, s)

    sweep = {
        "devices": n_devices,
        "ici_dcn_gap": gap,
        "note": (
            "simulated on the TPU machine model; both pipelines' final "
            "(graph, strategy) results are re-scored in the joint "
            "currency (best comm plan via the exposed-comm simulation "
            "minus the per-group ZeRO update credit), so step ratios "
            "compare strategies under one scoring rule"
        ),
        "models": {},
        "overhead": {},
    }
    for name, (batch, bud, build) in builders.items():
        rows = {}
        for topo, spec in topologies.items():
            cfg_seq = _cfg(batch, bud, spec, co=False)
            g0 = build(cfg_seq).graph
            t0 = _time.monotonic()
            g_seq, s_seq = optimize_strategy(g0, cfg_seq,
                                             return_graph=True)
            t_seq = _time.monotonic() - t0

            cfg_joint = _cfg(batch, bud, spec, co=True)
            g1 = build(cfg_joint).graph
            t0 = _time.monotonic()
            g_j, s_j = optimize_strategy(g1, cfg_joint, return_graph=True)
            t_joint = _time.monotonic() - t0
            serves = LAST_SEARCH_STATS.get("comm_plan_serves", 0)
            searches = LAST_SEARCH_STATS.get("comm_plan_searches", 0)
            # every candidate the search evaluated (tier-1 estimates +
            # tier-2/merge/floor groundings): the depth-gated design
            # ranks interiors in the bounded scalar currency and
            # grounds winners jointly, so a candidate evaluation pays
            # a comm-plan SEARCH only when its top-level grounding hits
            # a never-seen synced-group signature — the serve-rate
            # acceptance reads plan_search_free_rate (fraction of
            # candidate evaluations served without re-searching a
            # plan); comm_plan_serve_rate is the stricter repeat rate
            # at the pricer itself
            evals = (LAST_SEARCH_STATS.get("full_sims", 0)
                     + LAST_SEARCH_STATS.get("delta_sims", 0))

            c_seq = _joint_price(cfg_joint, g_seq, s_seq)
            c_j = _joint_price(cfg_joint, g_j, s_j)
            row = {
                "sequential_step_ms": round(c_seq * 1e3, 4),
                "joint_step_ms": round(c_j * 1e3, 4),
                "step_win": round(c_seq / c_j, 4) if c_j else None,
                "sequential_search_s": round(t_seq, 3),
                "joint_search_s": round(t_joint, 3),
                "search_overhead": round(t_joint / max(t_seq, 1e-9), 3),
                "comm_plan_serves": serves,
                "comm_plan_searches": searches,
                "comm_plan_serve_rate": round(
                    serves / max(1, serves + searches), 4),
                "candidate_evals": evals,
                "plan_search_free_rate": round(
                    1.0 - searches / max(1, evals), 4),
                "zero_groups": len(_driver.LAST_ZERO_GROUPS),
            }
            rows[topo] = row
            print(json.dumps({"co_search": topo, "model": name, **row}))
        sweep["models"][name] = rows

    # wall-clock overhead acceptance rows (search only, flat machine):
    # the two biggest zoo graphs, joint/sequential ≤ 1.5x
    overhead_models = {
        "inception": (64, 10, lambda cfg: build_inception_v3(cfg)),
        "gpt_xl": (8, 16, lambda cfg: build_gpt_xl(cfg)),
    }
    for name, (batch, bud, build) in overhead_models.items():
        cfg_seq = _cfg(batch, bud, base_spec, co=False)
        g0 = build(cfg_seq).graph
        t0 = _time.monotonic()
        optimize_strategy(g0, cfg_seq, return_graph=True)
        t_seq = _time.monotonic() - t0
        cfg_joint = _cfg(batch, bud, base_spec, co=True)
        g1 = build(cfg_joint).graph
        t0 = _time.monotonic()
        optimize_strategy(g1, cfg_joint, return_graph=True)
        t_joint = _time.monotonic() - t0
        serves = LAST_SEARCH_STATS.get("comm_plan_serves", 0)
        searches = LAST_SEARCH_STATS.get("comm_plan_searches", 0)
        evals = (LAST_SEARCH_STATS.get("full_sims", 0)
                 + LAST_SEARCH_STATS.get("delta_sims", 0))
        row = {
            "nodes": g1.num_nodes,
            "sequential_search_s": round(t_seq, 3),
            "joint_search_s": round(t_joint, 3),
            "search_overhead": round(t_joint / max(t_seq, 1e-9), 3),
            "comm_plan_serve_rate": round(
                serves / max(1, serves + searches), 4),
            "plan_search_free_rate": round(
                1.0 - searches / max(1, evals), 4),
        }
        sweep["overhead"][name] = row
        print(json.dumps({"co_search_overhead": name, **row}))
    return sweep


def _co_search_sweep_md_lines(sweep):
    lines = [
        "",
        "## Joint comm-plan co-search (sequential strategy→plan vs "
        "joint pricing, "
        f"{sweep['devices']} devices)",
        "",
        sweep["note"],
        "",
        "| model | topology | sequential ms | joint ms | step win | "
        "search overhead | plan-search-free evals | memo repeat rate | "
        "zero groups |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for name, rows in sweep["models"].items():
        for topo, r in rows.items():
            lines.append(
                f"| {name} | {topo} | {r['sequential_step_ms']} | "
                f"{r['joint_step_ms']} | "
                f"{r['step_win']}x | {r['search_overhead']}x | "
                f"{r.get('plan_search_free_rate', 0):.1%} | "
                f"{r['comm_plan_serve_rate']:.0%} | "
                f"{r['zero_groups']} |")
    lines += [
        "",
        "plan-search-free evals = candidate evaluations served without "
        "re-searching a comm plan (the depth-gated design grounds "
        "interior winners against memoized plans); memo repeat rate = "
        "served/(served+searched) at the pricer itself.",
        "",
        "| overhead model | nodes | sequential s | joint s | overhead | "
        "plan-search-free evals |",
        "|---|---|---|---|---|---|",
    ]
    for name, r in sweep.get("overhead", {}).items():
        lines.append(
            f"| {name} | {r['nodes']} | {r['sequential_search_s']} | "
            f"{r['joint_search_s']} | {r['search_overhead']}x | "
            f"{r.get('plan_search_free_rate', 0):.1%} |")
    lines.append("")
    return lines


def scale_sweep(n_devices, budget=16):
    """The --scale sweep: production-graph search throughput (ROADMAP
    item 3 / PR 7).  gpt_xl (models/transformer.py GPT_XL_KW, ~1015
    PCG nodes — 10-50x the rest of the zoo) searched three ways against
    the inception reference (the previous biggest-graph wall-clock):

      * COLD  — fresh cost cache: the k-way chain decomposition +
        isomorphic segment STAMPING carry the whole win (a transformer
        stack is ~N identical layers: solve one, stamp N);
      * WARM/result — identical re-search: the PR 3 whole-result layer;
      * WARM/rows — the search knobs changed (budget+1), so the result
        layer misses and tier-2 DP segments are served from the
        PERSISTED memo rows under process-stable digests.

    Also records the serve rate — the fraction of tier-2 segment
    solves answered by stamping or persisted rows instead of running
    the DP — and the incremental-ctx patch rate for the solves that do
    run."""
    import os
    import tempfile

    import flexflow_tpu as ff
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.models import build_gpt_xl, build_inception_v3
    from flexflow_tpu.search.driver import LAST_SEARCH_STATS, optimize_strategy
    from flexflow_tpu.search.simulator import Simulator

    def one(tag, build, batch, cache, budget_=None):
        cfg = ff.FFConfig(batch_size=batch, num_devices=n_devices,
                          search_budget=budget_ or budget,
                          cost_cache_file=cache)
        g = build(cfg).graph
        t0 = time.monotonic()
        bg, strat = optimize_strategy(g, cfg, return_graph=True)
        wall = time.monotonic() - t0
        stats = dict(LAST_SEARCH_STATS)
        sim = Simulator(cfg.machine_spec, num_devices=n_devices)
        c_dp = sim.simulate(g, data_parallel_strategy(g, n_devices))
        c_se = sim.simulate(bg, strat)
        stamped = stats.get("segments_stamped", 0)
        served = stats.get("dp_rows_served", 0)
        solves = stats.get("ctx_patch_hits", 0) + stats.get(
            "ctx_rebuilds", 0)
        row = {
            "nodes": g.num_nodes,
            "search_seconds": round(wall, 2),
            "sim_dp_ms": round(c_dp * 1e3, 4),
            "sim_searched_ms": round(c_se * 1e3, 4),
            "sim_ratio": round(c_dp / c_se, 3) if c_se > 0 else None,
            "segments_stamped": stamped,
            "dp_rows_served": served,
            "ctx_patch_hits": stats.get("ctx_patch_hits", 0),
            "ctx_rebuilds": stats.get("ctx_rebuilds", 0),
            "ctx_patch_rate": (
                round(stats.get("ctx_patch_hits", 0) / solves, 3)
                if solves else None),
            # fraction of tier-2 segment solves answered WITHOUT
            # running the DP (stamped from an isomorphic sibling or
            # served from a persisted memo row)
            "serve_rate": (
                round((stamped + served) / (stamped + served + solves), 3)
                if stamped + served + solves else None),
            "result_cache_hit": bool(stats.get("result_cache_hit")),
        }
        print(json.dumps({"scale": tag, **row}))
        return row

    tmp = tempfile.mkdtemp(prefix="ff_scale_")
    cache = os.path.join(tmp, "scale_cache.json")
    sweep = {
        "devices": n_devices,
        "budget": budget,
        "note": (
            "cold = fresh cost cache (chain decomposition + segment "
            "stamping only); warm_result = identical re-search served "
            "by the whole-result cache layer; warm_rows = search "
            "budget changed so the result layer misses and tier-2 DP "
            "segments are served from the persisted memo rows under "
            "process-stable digests; serve_rate = (stamped + rows "
            "served) / (stamped + rows served + DP solves)"
        ),
    }
    # inception reference: cold, no cache — today's biggest-zoo-graph
    # wall-clock, the acceptance yardstick
    sweep["inception_ref"] = one("inception_ref", build_inception_v3,
                                 64, "")
    sweep["gpt_xl_cold"] = one("gpt_xl_cold", build_gpt_xl, 8, cache)
    sweep["gpt_xl_warm_result"] = one("gpt_xl_warm_result", build_gpt_xl,
                                      8, cache)
    # knobs changed => the whole-result layer misses; the dp-row layer
    # must carry the warm win on its own
    sweep["gpt_xl_warm_rows"] = one("gpt_xl_warm_rows", build_gpt_xl,
                                    8, cache, budget_=budget + 1)
    ref = sweep["inception_ref"]["search_seconds"]
    if ref > 0:
        sweep["cold_vs_inception"] = round(
            sweep["gpt_xl_cold"]["search_seconds"] / ref, 3)
        sweep["warm_vs_inception"] = round(
            sweep["gpt_xl_warm_result"]["search_seconds"] / ref, 3)
    for f in (cache, cache + ".results.pkl"):
        if os.path.exists(f):
            os.remove(f)
    os.rmdir(tmp)
    return sweep


def sp_scale_sweep(n_devices, budget=16):
    """The --sp-scale sweep: series-parallel decomposition on ARBITRARY
    graph shapes (ROADMAP item 4 / PR 12).  The non-chain synthetic
    families (models/synthetic.py — a persistent-skip MoE trunk and a
    multi-tower multibranch, both bottleneck-free at depth) searched
    cold at 1k and 10k nodes against the gpt_xl chain reference; the
    acceptance gate is the 10k-node cold search within 5x of gpt_xl's
    cold wall-clock.  Also records the decomposition provenance
    (mode/cuts/width), the matcher node-visit reduction (seed-index +
    vectorized-filter skips), and the warm re-search where the
    whole-result layer misses — a DIFFERENT trunk depth changes the
    graph digest while the search knobs stay IDENTICAL (search_budget
    is part of the sp-row key) — so the sp-segment memo rows carry
    the win alone."""
    import os
    import tempfile

    import flexflow_tpu as ff
    from flexflow_tpu.compiler.lowering import data_parallel_strategy
    from flexflow_tpu.models import (
        build_gpt_xl,
        build_moe_trunk,
        build_multibranch,
    )
    from flexflow_tpu.search.driver import LAST_SEARCH_STATS, optimize_strategy
    from flexflow_tpu.search.simulator import Simulator

    def one(tag, build, kw, batch, cache, budget_=None, timeout=900.0):
        cfg = ff.FFConfig(batch_size=batch, num_devices=n_devices,
                          search_budget=budget_ or budget,
                          search_timeout_s=timeout,
                          cost_cache_file=cache)
        g = build(cfg, **kw).graph
        t0 = time.monotonic()
        bg, strat = optimize_strategy(g, cfg, return_graph=True)
        wall = time.monotonic() - t0
        stats = dict(LAST_SEARCH_STATS)
        sim = Simulator(cfg.machine_spec, num_devices=n_devices)
        c_dp = sim.simulate(g, data_parallel_strategy(g, n_devices))
        c_se = sim.simulate(bg, strat)
        row = {
            "nodes": g.num_nodes,
            "search_seconds": round(wall, 2),
            "sim_dp_ms": round(c_dp * 1e3, 4),
            "sim_searched_ms": round(c_se * 1e3, 4),
            "sim_ratio": round(c_dp / c_se, 3) if c_se > 0 else None,
            "decompose_mode": stats.get("decompose_mode"),
            "decompose_cuts": stats.get("decompose_cuts", 0),
            "decompose_max_width": stats.get("decompose_max_width", 0),
            "sp_segments": stats.get("sp_segments", 0),
            "segments_stamped": stats.get("segments_stamped", 0),
            "sp_rows_served": stats.get("sp_rows_served", 0),
            "dp_rows_served": stats.get("dp_rows_served", 0),
            # matcher node-visit reduction: calls skipped by the
            # per-op-type seed index + the vectorized predicate filters
            "match_index_skips": stats.get("match_index_skips", 0),
            "match_vec_skips": stats.get("match_vec_skips", 0),
            "match_worker_batches": stats.get("match_worker_batches", 0),
            "result_cache_hit": bool(stats.get("result_cache_hit")),
        }
        print(json.dumps({"sp_scale": tag, **row}))
        return row

    tmp = tempfile.mkdtemp(prefix="ff_sp_scale_")
    cache = os.path.join(tmp, "sp_cache.json")
    sweep = {
        "devices": n_devices,
        "budget": budget,
        "note": (
            "moe_trunk = persistent-skip dense-mixture trunk "
            "(bottleneck-free: the input skip bypasses every block); "
            "multibranch = independent towers concatenated once; both "
            "searched COLD (fresh cache) through the series-parallel "
            "frontier-cut decomposition — pre-PR these fell back to "
            "binary recursion, which degenerates to a whole-graph "
            "greedy past the native-DP ceiling.  gpt_xl_ref = the "
            "chain-shaped acceptance yardstick (routes through the "
            "same sp path as the width-1 degenerate case).  "
            "warm_rows = a DIFFERENT (800-block) trunk over the 770-"
            "block run's cache: the whole-result layer misses on the "
            "new graph digest and the guid-free sp-segment memo rows "
            "carry the warm win alone"
        ),
    }
    sweep["gpt_xl_ref"] = one("gpt_xl_ref", build_gpt_xl, {}, 8, "")
    sweep["multibranch_1k"] = one(
        "multibranch_1k", build_multibranch,
        dict(num_branches=6, depth=170), 8, "")
    sweep["moe_trunk_1k"] = one(
        "moe_trunk_1k", build_moe_trunk, dict(num_blocks=80), 8, "")
    sweep["moe_trunk_10k"] = one(
        "moe_trunk_10k", build_moe_trunk, dict(num_blocks=770), 8, cache)
    # a DIFFERENT graph with isomorphic segments: the whole-result
    # layer misses (different graph digest) and the sp-segment rows
    # must carry the warm win on their own
    sweep["moe_trunk_10k_warm_rows"] = one(
        "moe_trunk_10k_warm_rows", build_moe_trunk,
        dict(num_blocks=800), 8, cache)
    ref = sweep["gpt_xl_ref"]["search_seconds"]
    if ref > 0:
        sweep["sp10k_vs_gpt_xl"] = round(
            sweep["moe_trunk_10k"]["search_seconds"] / ref, 3)
    for f in (cache, cache + ".results.pkl"):
        if os.path.exists(f):
            os.remove(f)
    os.rmdir(tmp)
    return sweep


def _sp_scale_sweep_md_lines(sweep):
    lines = [
        "",
        "## Series-parallel search on arbitrary graph shapes "
        "(--sp-scale)",
        "",
        "Generalized decomposition (ROADMAP item 4 / PR 12, "
        "`search/decompose.py`): bounded-width frontier cuts instead "
        "of single bottlenecks, segment solves per boundary-view "
        "TUPLE stamped across isomorphism classes, persisted as "
        "guid-free sp-memo rows; matching moved off the critical "
        "path (vectorized predicate filters + opt-in match-worker "
        "pool).  Chain-shaped graphs route through the same path as "
        "the width-1 degenerate case, bit-identity test-enforced.",
        "",
        "| run | nodes | mode | cuts (max w) | search s | vs gpt_xl | "
        "sim ratio | stamped | sp rows | match skips (idx+vec) |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    ref = sweep["gpt_xl_ref"]["search_seconds"]
    for tag in ("gpt_xl_ref", "multibranch_1k", "moe_trunk_1k",
                "moe_trunk_10k", "moe_trunk_10k_warm_rows"):
        r = sweep.get(tag)
        if r is None:
            continue
        vs = round(r["search_seconds"] / ref, 2) if ref > 0 else "—"
        lines.append(
            f"| {tag} | {r['nodes']} | {r.get('decompose_mode')} | "
            f"{r.get('decompose_cuts', 0)} "
            f"({r.get('decompose_max_width', 0)}) | "
            f"{r['search_seconds']} | {vs}x | "
            f"{r.get('sim_ratio', '—')} | "
            f"{r.get('segments_stamped', 0)} | "
            f"{r.get('sp_rows_served', 0)} | "
            f"{r.get('match_index_skips', 0)}+"
            f"{r.get('match_vec_skips', 0)} |")
    if "sp10k_vs_gpt_xl" in sweep:
        lines += [
            "",
            f"10k-node non-chain cold search = "
            f"{sweep['sp10k_vs_gpt_xl']}x gpt_xl's cold wall-clock "
            f"(acceptance gate: <= 5x).",
        ]
    lines += ["", f"Methodology: {sweep['note']}."]
    return lines


def _scale_sweep_md_lines(sweep):
    lines = [
        "",
        "## Production-scale search (gpt_xl, segment reuse)",
        "",
        "Scaling `optimize_strategy` to thousand-node graphs (ROADMAP "
        "item 3): the k-way chain decomposition cuts the stack at "
        "bottlenecks, tier-2 DP runs once per isomorphism class x "
        "boundary pair and is STAMPED onto the repeated layers "
        "(lint-gated), the native-DP ctx is patched incrementally from "
        "the substitution's changed-guid sets, and solved segments "
        "persist as guid-free DP memo rows under process-stable "
        "digests.",
        "",
        "| run | nodes | search s | vs inception | sim ratio | "
        "stamped | rows served | ctx patch rate | serve rate |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    ref = sweep["inception_ref"]["search_seconds"]
    for tag in ("inception_ref", "gpt_xl_cold", "gpt_xl_warm_result",
                "gpt_xl_warm_rows"):
        r = sweep.get(tag)
        if r is None:
            continue
        vs = round(r["search_seconds"] / ref, 2) if ref > 0 else "—"

        def cell(key):
            v = r.get(key)
            return "—" if v is None else v

        lines.append(
            f"| {tag} | {r['nodes']} | {r['search_seconds']} | {vs}x | "
            f"{cell('sim_ratio')} | {r.get('segments_stamped', 0)} "
            f"| {r.get('dp_rows_served', 0)} | {cell('ctx_patch_rate')} | "
            f"{cell('serve_rate')} |")
    lines += ["", f"Methodology: {sweep['note']}."]
    return lines


def _topology_sweep_md_lines(sweep):
    lines = [
        "",
        "## Hierarchical topology sweep (flat vs multi-slice, "
        f"{sweep['ici_dcn_gap']:.0f}x ICI/DCN gap)",
        "",
        "The machine model's link hierarchy as a search dimension "
        "(search/machine_model.py levels + search/reduction_plan.py): "
        "on multi-slice topologies the search synthesizes staged "
        "per-group reduction plans — reduce-scatter within each slice, "
        "a cross-slice exchange of the 1/n shard, all-gather within "
        "the slice — instead of dragging the full gradient around the "
        "slow DCN ring.",
        "",
        "| model | topology | flat sync ms | planned sync ms | "
        "sync ratio | flat step ms | planned step ms | staged buckets | "
        "plans |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for name, rows in sweep["models"].items():
        for topo, r in rows.items():
            plans = ",".join(sorted(set(r.get("plans", {}).values()))) \
                or "—"
            lines.append(
                f"| {name} | {topo} | {r.get('sim_flat_sync_ms', '—')} | "
                f"{r.get('sim_planned_sync_ms', '—')} | "
                f"{r.get('sync_ratio_flat_over_planned', '—')} | "
                f"{r.get('sim_flat_step_ms', '—')} | "
                f"{r.get('sim_planned_step_ms', '—')} | "
                f"{r.get('staged_buckets', 0)} | {plans} |")
    lines += [
        "",
        f"Honesty note: {sweep['note']}.",
    ]
    return lines


def _schedule_sweep_md_lines(sweep):
    lines = [
        "",
        "## Overlap-aware sync schedule (sync-bound BERT, "
        "SYNC_BOUND_BERT_KW)",
        "",
        "The gradient-sync schedule as a searched comm plan "
        "(search/sync_schedule.py): issue-ordered buckets overlap the "
        "backward, coalescing amortizes collective latency; the "
        "simulator prices the EXPOSED sync tail and the lowering "
        "executes the buckets (comm/bucketed.py).  'monolithic' is the "
        "one-post-backward-sync status quo in the same pricing "
        "currency.",
        "",
        "| precision mode | sim monolithic ms | sim scheduled ms | "
        "sim ratio | exposed mono ms | exposed sched ms | buckets | "
        "exec mono ms | exec sched ms | exec ratio |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for mode, r in sweep["rows"].items():
        lines.append(
            f"| {mode} | {r.get('sim_monolithic_ms', '—')} | "
            f"{r.get('sim_scheduled_ms', '—')} | "
            f"{r.get('sim_step_ratio', '—')} | "
            f"{r.get('sim_exposed_monolithic_ms', '—')} | "
            f"{r.get('sim_exposed_scheduled_ms', '—')} | "
            f"{r.get('buckets', '—')} | "
            f"{r.get('exec_monolithic_ms', '—')} | "
            f"{r.get('exec_scheduled_ms', '—')} | "
            f"{r.get('exec_ratio', '—')} |")
    lines += [
        "",
        f"Honesty note: {sweep['note']}.",
    ]
    return lines


def _sweep_md_lines(sweep):
    lines = [
        "",
        "## Sync-precision sweep (sync-bound BERT, SYNC_BOUND_BERT_KW)",
        "",
        "Gradient-sync wire precision as a searchable strategy dimension "
        "(EQuARX-style quantized allreduce, comm/quantized.py).  "
        "Simulated columns price the DP weight-allreduce term on the "
        "TPU machine model; exec columns run the TPU-chosen "
        "per-weight-group map for real on the live mesh.",
        "",
        "| precision | sim allreduce ms | sim step ms | sim allreduce "
        "ratio | sim step ratio | exec ms | exec ratio | groups |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for prec, r in sweep["rows"].items():
        lines.append(
            f"| {prec} | {r.get('sim_allreduce_ms', '—')} | "
            f"{r.get('sim_step_ms', '—')} | "
            f"{r.get('sim_allreduce_ratio_vs_fp32', '—')} | "
            f"{r.get('sim_step_ratio_vs_fp32', '—')} | "
            f"{r.get('exec_ms', '—')} | "
            f"{r.get('exec_ratio_vs_fp32', '—')} | "
            f"{r.get('compressed_groups', '—')} |")
    lines += [
        "",
        f"Honesty note: {sweep['note']}.",
    ]
    return lines


def always_on_sweep(n_devices):
    """The always-on controller scenario (runtime/controller.py): one
    calibrated run with an injected calibration drift (re-probe →
    signature rotation → live re-search → hot swap between steps) and
    one run with an injected device loss (elastic re-search + state
    re-homing onto the surviving mesh).  Reports measured swap latency,
    recovery wall-clock, and the warm-search fraction (mid-run
    re-search seconds / initial compile-time search seconds) on the CPU
    mesh — simulated faults via the seeded harness, labeled so.  The
    bit-exactness of the swap itself is tier-1-enforced
    (tests/test_controller.py), not re-proven here."""
    import os
    import tempfile
    import time as _time

    import numpy as np

    import flexflow_tpu as ff
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.runtime import FaultPlan, TrainingController
    from flexflow_tpu.search import driver as _driver
    from flexflow_tpu.search.calibration import (
        CalibrationTable,
        calibrate_graph,
    )

    rng = np.random.RandomState(0)
    X = rng.randn(64, 128).astype(np.float32)
    Y = rng.randint(0, 8, size=(64,)).astype(np.int32)

    LAYERS, WIDTH = 10, 512  # big enough that search wall-clock is
    # signal, not timer noise (a 3-layer toy searches in ~0.05s and the
    # warm fraction becomes a coin flip)

    def build(cal_file, num=n_devices):
        cfg = ff.FFConfig(
            batch_size=16, num_devices=num,
            machine_spec=MachineSpec.host_cpu(num),
            calibration_file=cal_file, calibration_budget_s=5.0,
            search_budget=16, search_timeout_s=30.0, cost_cache_file="")
        m = ff.FFModel(cfg)
        x = m.create_tensor([16, 128])
        t = x
        for i in range(LAYERS):
            t = m.dense(t, WIDTH, activation="relu", name=f"fc{i}")
        m.dense(t, 8, name="head")
        t0 = _time.perf_counter()
        m.compile(optimizer=ff.SGDOptimizer(lr=1e-2),
                  loss_type="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        return m, _time.perf_counter() - t0

    out = {"devices": n_devices, "simulated_faults": True, "steps": 10}

    # -- scenario 1: calibration drift → re-probe → re-search → swap ----
    with tempfile.TemporaryDirectory(prefix="ffa_") as tmp:
        cal = os.path.join(tmp, "CALIBRATION.json")
        table = CalibrationTable()
        # pre-probe so the compile-time search is genuinely calibrated
        pre_cfg = ff.FFConfig(batch_size=16, num_devices=n_devices,
                              machine_spec=MachineSpec.host_cpu(
                                  n_devices))
        pre = ff.FFModel(pre_cfg)
        x = pre.create_tensor([16, 128])
        t = x
        for i in range(LAYERS):
            t = pre.dense(t, WIDTH, activation="relu", name=f"fc{i}")
        pre.dense(t, 8, name="head")
        calibrate_graph(pre.graph, n_devices, table, time_budget_s=5.0)
        table.save(cal)
        m, compile_s = build(cal)
        initial = dict(_driver.LAST_SEARCH_STATS)
        ctl = TrainingController(
            m, faults=FaultPlan.parse("calibration_drift@3", seed=7))
        ctl.run(X, Y, steps=10)
        init_s = float(initial.get("search_seconds") or 0.0)
        detail = (ctl.stats["research_detail"] or [{}])[0]
        re_s = float(detail.get("search_s") or 0.0)
        out["drift"] = {
            "initial_search_s": round(init_s, 3),
            "compile_s": round(compile_s, 3),
            # a re-search episode may span TWO searches: when the swap
            # gate refuses the rewritten winner (fusion renames weighted
            # ops), a strategy-only search on the live graph follows —
            # research_search_s sums both, honestly
            "searches": detail.get("searches"),
            "research_search_s": round(re_s, 3),
            "research_reprobe_s": round(float(
                detail.get("calibration_s") or 0.0), 3),
            "research_wall_s": round(float(detail.get("wall_s") or 0.0),
                                     3),
            "swap_latency_s": round(
                float(ctl.stats["swap_seconds"][0]), 3)
            if ctl.stats["swap_seconds"] else None,
            "warm_fraction": round(re_s / init_s, 3) if init_s else None,
            "swaps": ctl.stats["swaps"],
        }

    # -- scenario 2: device loss → elastic re-search + recovery ----------
    m, _ = build(None)
    survivors = max(1, n_devices // 2)
    ctl = TrainingController(
        m, faults=FaultPlan.parse(f"device_loss@3:{survivors}", seed=7))
    t0 = _time.perf_counter()
    run = ctl.run(X, Y, steps=10)
    wall = _time.perf_counter() - t0
    out["device_loss"] = {
        "survivors": survivors,
        "research_s": round(float(ctl.stats["research_seconds"][0]), 3)
        if ctl.stats["research_seconds"] else None,
        "swap_latency_s": round(float(ctl.stats["swap_seconds"][0]), 3)
        if ctl.stats["swap_seconds"] else None,
        "recovery_wall_s": round(
            float((ctl.stats["research_seconds"] or [0])[0])
            + float((ctl.stats["swap_seconds"] or [0])[0]), 3),
        "run_wall_s": round(wall, 3),
        "final_loss": round(float(run["history"][-1]["loss"]), 6),
        "recoveries": ctl.stats["recoveries"],
    }
    return out


def _always_on_md_lines(sweep):
    drift, loss = sweep.get("drift", {}), sweep.get("device_loss", {})
    # recovery wall = research wall (incl. re-probe) + swap, the SAME
    # basis as the device-loss row's recovery_wall_s
    drift_recovery_s = round((drift.get("research_wall_s") or 0)
                             + (drift.get("swap_latency_s") or 0), 3)
    lines = [
        "",
        "## Always-on controller (drift swap + elastic recovery)",
        "",
        f"Simulated faults (seeded harness, runtime/faults.py) on the "
        f"{sweep.get('devices')}-device CPU mesh, "
        f"{sweep.get('steps')} controller steps; swap bit-exactness is "
        f"tier-1-enforced (tests/test_controller.py).",
        "",
        "| scenario | search s | swap latency s | recovery wall s | "
        "warm fraction |",
        "|---|---|---|---|---|",
        f"| initial compile search | {drift.get('initial_search_s')} | "
        f"— | — | 1.0 (cold) |",
        f"| drift → re-search + hot swap | "
        f"{drift.get('research_search_s')} "
        f"({drift.get('searches')} search(es) — the swap gate may "
        f"refuse a rewritten winner and re-search strategy-only — "
        f"+{drift.get('research_reprobe_s')} re-probe) | "
        f"{drift.get('swap_latency_s')} | "
        f"{drift_recovery_s} | "
        f"{drift.get('warm_fraction')} |",
        f"| device loss → {loss.get('survivors')} survivors | "
        f"{loss.get('research_s')} | {loss.get('swap_latency_s')} | "
        f"{loss.get('recovery_wall_s')} | — |",
    ]
    return lines


def obs_lanes_sweep(n_devices, drift_threshold=0.5, obs_log=None):
    """The --obs measured-side sweep (the layer every on-TPU sweep
    will read its numbers through): (1) a sync-scheduled fit on the
    live mesh captured under ``jax.profiler`` (device_trace_dir), the
    capture ingested and TAG-matched into per-bucket lane-drift rows
    — predicted vs measured issue time and duration per sync lane
    (obs/trace_ingest.py); (2) a compiled decode serve with
    per-request telemetry, recording measured TTFT/TPOT/frame-p99
    against the serving arrival model's predicted p99.

    Honesty: on a CPU mesh the capture carries HOST-observed lane
    markers (dispatch + virtual-device compute — no ICI/DCN wire), so
    the absolute measured/predicted ratios price the machine-model
    gap, not a win; the step-relative lane fractions are the drift
    signal.  The same sweep on a TPU yields real wire lanes."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    import flexflow_tpu as ff
    from flexflow_tpu.models import build_transformer

    on_cpu = jax.devices()[0].platform == "cpu"
    # the per-request spans are bus-gated (one-check-per-frame
    # contract), so a standalone --obs-lanes-only run arms the bus to
    # the artifact log the full --obs path would have used
    from flexflow_tpu.obs.events import BUS as _bus

    if not _bus.enabled and obs_log:
        _bus.configure(obs_log)
    sweep = {
        "devices": n_devices,
        "backend": jax.devices()[0].platform,
        "source": "host_trace" if on_cpu else "device_trace",
        "note": (
            "lane rows are host-trace-derived on a CPU mesh: the "
            "markers bracket each bucket's collectives in the host "
            "timeline (dispatch + serialized virtual-device compute); "
            "ICI/DCN wire behavior stays simulated until this sweep "
            "runs on a TPU.  Matching is by stable lane id "
            "(bucket:<name>:sync), never kernel names.  fp32 buckets' "
            "lanes bracket grad-readiness + the ordering barrier "
            "(their wire is GSPMD's own backward psum); compressed "
            "buckets bracket the real quantized collective."),
    }

    # -- (1) lane drift: sync-scheduled fit under a real capture --------
    tdir = tempfile.mkdtemp(prefix="ff_lane_trace_")
    try:
        cfg = ff.FFConfig(batch_size=8, epochs=2,
                          only_data_parallel=True,
                          sync_schedule="search", profiling=True,
                          device_trace_dir=tdir, cost_cache_file="",
                          drift_threshold=drift_threshold,
                          **_exec_cfg_kwargs(n_devices, on_cpu))
        m = build_transformer(cfg, **SYNC_BOUND_BERT_KW)
        m.compile(loss_type="mean_squared_error", metrics=[])
        kw = SYNC_BOUND_BERT_KW
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, kw["seq_len"], kw["hidden"])
                       ).astype(np.float32)
        m.fit(x=x, y=x, verbose=False, shuffle=False)
        rep = m.lane_drift_report
        drift = m.drift_report
        prec = {b["lane"]: b.get("precision")
                for b in (drift.sync_buckets if drift else [])}
        lanes = {
            "config": ("sync-bound BERT (SYNC_BOUND_BERT_KW), DP "
                       "strategy + searched sync schedule, "
                       f"{'CPU' if on_cpu else 'TPU'} mesh"),
            "buckets": len(m.sync_schedule.buckets)
            if m.sync_schedule else 0,
        }
        if rep is not None:
            lanes.update(
                steps_captured=rep.steps,
                matched_all=rep.matched_all,
                matched=rep.matched,
                predicted_step_ms=round(rep.predicted_total_s * 1e3, 4),
                measured_step_ms=round(rep.measured_step_s * 1e3, 3),
                unmatched_predicted=rep.unmatched_predicted,
                rows=[{
                    "lane": r["lane"],
                    "precision": prec.get(r["lane"]),
                    "samples": r["samples"],
                    "predicted_issue_ms": round(
                        (r["predicted_issue_s"] or 0) * 1e3, 4),
                    "measured_issue_ms": round(
                        (r["measured_issue_s"] or 0) * 1e3, 3),
                    "predicted_sync_ms": round(
                        (r["predicted_sync_s"] or 0) * 1e3, 4),
                    "measured_sync_ms": round(
                        (r["measured_sync_s"] or 0) * 1e3, 3),
                    "predicted_issue_frac": round(
                        r["predicted_issue_frac"] or 0, 3),
                    "measured_issue_frac": round(
                        r["measured_issue_frac"] or 0, 3),
                    "sync_frac_ratio": (
                        round(r["sync_frac_ratio"], 4)
                        if r["sync_frac_ratio"] is not None else None),
                } for r in rep.lanes],
            )
        else:
            lanes["error"] = "capture did not ingest"
        sweep["lanes"] = lanes
        print(json.dumps({"obs_lanes": {
            k: v for k, v in lanes.items() if k != "rows"}}))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    # -- (2) serving telemetry: compiled decode serve, measured vs
    #    predicted p99 + per-request TTFT/TPOT --------------------------
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import (
        ContinuousBatchingExecutor,
        DecodeRequest,
        compiled_decode_step,
    )
    from flexflow_tpu.search.serving import serve_latency_quantiles

    kw = dict(vocab=256, num_layers=1, hidden=64, num_heads=4,
              ff_dim=64, page_size=4, pages_per_seq=4)
    cfg = ff.FFConfig(batch_size=8, num_devices=n_devices,
                      search_budget=4, search_timeout_s=30.0,
                      cost_cache_file="", comp_mode="inference",
                      objective="serve",
                      machine_spec=MachineSpec.host_cpu(n_devices)
                      if on_cpu else None)
    m = build_gpt_decode(cfg, **kw)
    m.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
              comp_mode="inference")
    q = serve_latency_quantiles(m.graph, m.strategy, cfg)
    step_fn = compiled_decode_step(m)
    # jit-warm the decode frame with a throwaway request so the
    # telemetry run measures steady-state serving, not XLA compile
    # (a production server's first request pays it once per process)
    ContinuousBatchingExecutor(
        step_fn, max_seqs=8, page_size=4, pages_per_seq=4).run(
        [DecodeRequest(rid="warmup", prompt=[1], max_new_tokens=1)],
        max_frames=10)
    ex = ContinuousBatchingExecutor(
        step_fn, max_seqs=8, page_size=4,
        pages_per_seq=4, predicted_step_s=q["p99"])
    reqs = [DecodeRequest(rid=f"r{i}", prompt=[3 + i, 11, 2 * i + 1],
                          max_new_tokens=3 + (i % 3))
            for i in range(12)]
    ex.run(reqs, max_frames=400)
    ex.decode_drift_report(threshold=drift_threshold)
    s = ex.summary()

    def _ms(v):
        return round(v * 1e3, 3) if v is not None else None

    serving = {
        "config": ("gpt_decode (1 layer, 64 hidden) searched under "
                   "objective=serve, 12 ragged requests over 8 slots "
                   f"on the live {'CPU' if on_cpu else 'TPU'} mesh"),
        "requests": len(reqs),
        "frames": s["frames"],
        "predicted_p99_ms": _ms(q["p99"]),
        "measured_frame_p50_ms": _ms(s["measured_p50_s"]),
        "measured_frame_p99_ms": _ms(s["measured_p99_s"]),
        "measured_vs_predicted_p99": (
            round(s["measured_p99_s"] / q["p99"], 2) if q["p99"] else None),
        "ttft_p50_ms": _ms(s.get("ttft_p50_s")),
        "ttft_p99_ms": _ms(s.get("ttft_p99_s")),
        "tpot_p50_ms": _ms(s.get("tpot_p50_s")),
        "tpot_p99_ms": _ms(s.get("tpot_p99_s")),
        "e2e_p99_ms": _ms(s.get("e2e_p99_s")),
        "queue_p99_ms": _ms(s.get("queue_p99_s")),
        "note": ("measured on the host mesh (dispatch + virtual-device "
                 "compute); the predicted side is the serving arrival "
                 "model's machine-model p99 — the ratio prices the "
                 "model gap, not a win" if on_cpu else
                 "measured on the live accelerator"),
    }
    sweep["serving"] = serving
    print(json.dumps({"obs_serving": serving}))
    return sweep


def _obs_lanes_md_lines(sweep):
    lanes = sweep.get("lanes") or {}
    serving = sweep.get("serving") or {}
    lines = [
        "",
        "## Measured lanes & request telemetry (--obs)",
        "",
        f"Source: {sweep.get('source')} on {sweep.get('devices')} "
        f"{sweep.get('backend')} device(s).  {sweep.get('note')}",
        "",
    ]
    if lanes.get("rows"):
        lines.append(
            f"Lane drift — {lanes.get('config')}: "
            f"{lanes.get('matched')}/{len(lanes['rows'])} lanes "
            f"tag-matched over {lanes.get('steps_captured')} captured "
            f"step(s); predicted step "
            f"{lanes.get('predicted_step_ms')} ms vs measured "
            f"{lanes.get('measured_step_ms')} ms (host wall).")
        lines.append("")
        lines.append(
            "| lane | precision | samples | pred issue ms | "
            "meas issue ms | pred sync ms | meas sync ms | "
            "pred issue frac | meas issue frac | sync-share ratio |")
        lines.append("|---|---|---|---|---|---|---|---|---|---|")
        for r in lanes["rows"]:
            lines.append(
                f"| {r['lane']} | {r.get('precision') or '—'} | "
                f"{r['samples']} | {r['predicted_issue_ms']} | "
                f"{r['measured_issue_ms']} | {r['predicted_sync_ms']} | "
                f"{r['measured_sync_ms']} | {r['predicted_issue_frac']} "
                f"| {r['measured_issue_frac']} | "
                f"{r['sync_frac_ratio'] if r['sync_frac_ratio'] is not None else '—'} |")
    elif lanes:
        lines.append(f"Lane drift: {lanes.get('error', 'no rows')}")
    if serving:
        lines += [
            "",
            f"Serving telemetry — {serving.get('config')}:",
            "",
            "| requests | frames | predicted p99 ms | measured frame "
            "p50/p99 ms | TTFT p50/p99 ms | TPOT p50/p99 ms | "
            "e2e p99 ms | queue p99 ms |",
            "|---|---|---|---|---|---|---|---|",
            f"| {serving.get('requests')} | {serving.get('frames')} | "
            f"{serving.get('predicted_p99_ms')} | "
            f"{serving.get('measured_frame_p50_ms')}/"
            f"{serving.get('measured_frame_p99_ms')} | "
            f"{serving.get('ttft_p50_ms')}/{serving.get('ttft_p99_ms')} | "
            f"{serving.get('tpot_p50_ms')}/{serving.get('tpot_p99_ms')} | "
            f"{serving.get('e2e_p99_ms')} | "
            f"{serving.get('queue_p99_ms')} |",
            "",
            f"({serving.get('note')})",
        ]
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--models",
        default="alexnet,bert,gpt,dlrm,candle_uno,inception,resnext50,"
                "xdl,mlp")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--cpu-mesh", action="store_true",
                    help="run on a virtual CPU mesh of --devices devices "
                         "(jax may be pre-imported with another platform, "
                         "so env vars alone can be too late)")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure per-(op,view) costs on the live backend "
                         "first (search/calibration.py) and rank with them")
    ap.add_argument("--calibrate-only", action="store_true",
                    help="save the calibration table and exit without "
                         "touching the BENCH_SEARCH artifacts — the "
                         "on-TPU half of the calibrate-on-TPU / "
                         "execute-on-CPU-mesh split")
    ap.add_argument("--calibrate-budget", type=float, default=120.0,
                    help="per-model probe wall budget in seconds")
    ap.add_argument("--load-calibration", action="store_true",
                    help="rank with an existing --calibration-file (e.g. "
                         "measured earlier on the real TPU) instead of "
                         "probing the live backend — the way to combine "
                         "TPU-calibrated sim ratios with CPU-mesh "
                         "executed ratios")
    ap.add_argument("--calibration-file", default="CALIBRATION.json")
    ap.add_argument("--out-prefix", default="BENCH_SEARCH",
                    help="artifact file prefix — point smoke runs at a "
                         "scratch prefix so they never overwrite the "
                         "committed full artifact")
    ap.add_argument("--sim-only", action="store_true",
                    help="skip the executed-step tier even when enough "
                         "devices are visible — the search-throughput "
                         "measurement mode (cold vs warm cost cache)")
    ap.add_argument("--cost-cache-file", default="COST_CACHE.json",
                    help="persistent cost cache (search/cost_cache.py): "
                         "per-(op, view) cost rows + finished search "
                         "results keyed by graph digest x machine view x "
                         "calibration signature; repeat sweeps start warm")
    ap.add_argument("--no-cost-cache", action="store_true",
                    help="bypass the persistent cost cache (cold-cache "
                         "run)")
    ap.add_argument("--sync-precision", default="fp32,bf16,int8",
                    help="comma list of gradient-sync wire precisions to "
                         "sweep on the sync-bound BERT config (simulated "
                         "allreduce term + executed step time per "
                         "precision); empty disables the sweep")
    ap.add_argument("--sync-sweep-only", action="store_true",
                    help="run ONLY the sync-precision sweep and merge it "
                         "into the existing artifact, leaving every "
                         "model row untouched")
    ap.add_argument("--sync-schedule", action="store_true",
                    help="also sweep the gradient-sync SCHEDULE on the "
                         "sync-bound BERT config: searched issue-ordered "
                         "buckets vs the monolithic post-backward sync, "
                         "simulated (exposed-comm pricing) + executed, "
                         "with per-bucket DriftReports")
    ap.add_argument("--sync-schedule-only", action="store_true",
                    help="run ONLY the sync-schedule sweep and merge it "
                         "into the existing artifact, leaving every "
                         "model row untouched")
    ap.add_argument("--co-search", action="store_true",
                    help="also run the joint strategy x comm-plan "
                         "co-search sweep (sequential strategy→plan vs "
                         "joint pricing on the sync-bound zoo configs, "
                         "flat + 2-slice; search/comm_plan.py)")
    ap.add_argument("--co-search-only", action="store_true",
                    help="run ONLY the co-search sweep and merge it "
                         "into existing BENCH_SEARCH artifacts")
    ap.add_argument("--topology", action="store_true",
                    help="also sweep hierarchical machine topologies "
                         "(flat vs 2-slice vs 4-slice, 10x ICI/DCN "
                         "gap): per-model chosen reduction plans + "
                         "the flat-vs-staged DP sync term, simulated")
    ap.add_argument("--topology-only", action="store_true",
                    help="run ONLY the topology sweep and merge it "
                         "into the existing artifact, leaving every "
                         "model row untouched")
    ap.add_argument("--scale", action="store_true",
                    help="also sweep production-graph search "
                         "throughput: gpt_xl (~1015 nodes) cold / "
                         "warm-result / warm-rows vs the inception "
                         "reference, with segment-stamping and "
                         "persisted-DP-memo serve rates")
    ap.add_argument("--sp-scale", action="store_true",
                    help="also run the series-parallel scale sweep "
                    "(models/synthetic.py non-chain families at 1k/10k "
                    "nodes vs the gpt_xl chain reference; records "
                    "decompose + matcher counters)")
    ap.add_argument("--sp-scale-only", action="store_true",
                    help="run ONLY the sp-scale sweep and merge it "
                    "into an existing report")
    ap.add_argument("--scale-only", action="store_true",
                    help="run ONLY the scale sweep and merge it into "
                         "the existing artifact, leaving every model "
                         "row untouched")
    ap.add_argument("--serve", action="store_true",
                    help="also run the inference-serving sweep: decode "
                         "zoo x flat/2-slice, throughput-objective vs "
                         "serve-objective strategies with simulated "
                         "p50/p90/p99 + KV-residency columns "
                         "(search/serving.py)")
    ap.add_argument("--serve-only", action="store_true",
                    help="run ONLY the serving sweep and merge it into "
                         "existing BENCH_SEARCH artifacts")
    ap.add_argument("--disagg", action="store_true",
                    help="also run the disaggregation sweep: searched "
                         "prefill/decode two-block placement scored in "
                         "the phase-split serve currency, plus MEASURED "
                         "chunked-prefill vs prefill-via-decode TTFT on "
                         "the CPU host mesh (search/disaggregation.py, "
                         "runtime/prefill.py)")
    ap.add_argument("--disagg-only", action="store_true",
                    help="run ONLY the disaggregation sweep and merge "
                         "it into existing BENCH_SEARCH artifacts")
    ap.add_argument("--kv", action="store_true",
                    help="also run the KV-memory sweep: searched pool "
                         "precision (fp32/bf16/int8 priced in the "
                         "serve currency, kv_precision=search), "
                         "MEASURED radix prefix sharing at a fixed "
                         "pool (peak concurrency, CoW, token identity "
                         "vs solo) and the int8/bf16 accuracy "
                         "contract (runtime/decode.py, "
                         "ops/decode_attention.py)")
    ap.add_argument("--kv-only", action="store_true",
                    help="run ONLY the KV-memory sweep and merge it "
                         "into existing BENCH_SEARCH artifacts")
    ap.add_argument("--fleet", action="store_true",
                    help="also run the serving-fleet sweep: searched "
                         "N-replica-block fleets with per-SLO-class "
                         "routing priced in per-class p99 currency "
                         "(incl. a drift-episode re-size), plus "
                         "MEASURED mixed-SLO serving on the CPU host "
                         "mesh — searched fleet vs single-replica and "
                         "uniform-fleet baselines (search/fleet.py, "
                         "runtime/fleet.py)")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run ONLY the serving-fleet sweep and merge "
                         "it into existing BENCH_SEARCH artifacts")
    ap.add_argument("--request-trace", action="store_true",
                    help="also run the request-tracing sweep: a "
                         "2-replica fleet serves the seeded mixed-SLO "
                         "trace with the tracer armed — span trees "
                         "validated against measured e2e, Chrome/"
                         "Perfetto trace exported, a p99_drift fault "
                         "exercises the flight-ring post-mortem dump, "
                         "and burn-rate vs p99-drift trigger ordering "
                         "is replayed (obs/tracing.py, obs/flight.py, "
                         "obs/slo.py)")
    ap.add_argument("--request-trace-only", action="store_true",
                    help="run ONLY the request-tracing sweep and merge "
                         "it into existing BENCH_SEARCH artifacts")
    ap.add_argument("--always-on", action="store_true",
                    help="also run the always-on controller scenario: "
                         "injected calibration drift (re-search + hot "
                         "swap) and device loss (elastic recovery) with "
                         "measured swap latency / recovery wall-clock / "
                         "warm-search fraction (runtime/controller.py)")
    ap.add_argument("--always-on-only", action="store_true",
                    help="run ONLY the always-on controller scenario "
                         "and merge it into existing BENCH_SEARCH "
                         "artifacts")
    ap.add_argument("--slice-levels", default=None,
                    help="multi-slice link hierarchy above ICI for the "
                         "sim tier, without a machine file: comma list "
                         "of span:bandwidth:latency triples (FFConfig "
                         "--slice-levels; e.g. '16:3.1e9:1e-5')")
    ap.add_argument("--verify", action="store_true",
                    help="arm the static-analysis verifier "
                         "(flexflow_tpu/analysis, FLEXFLOW_TPU_VERIFY "
                         "semantics) during the searches and record "
                         "per-model verifier overhead "
                         "(verify_checks/verify_seconds) in each row")
    ap.add_argument("--obs", action="store_true",
                    help="unified telemetry: JSONL event log "
                         "(<prefix>_obs.jsonl), per-model "
                         "predicted-timeline Chrome-trace JSON, a "
                         "per-strategy DriftReport in every executed "
                         "row, an ffobs strategy-explanation report "
                         "(<prefix>_report.md), plus the measured-"
                         "lanes sweep: a device-trace capture tag-"
                         "matched into per-bucket lane-drift rows and "
                         "a decode serve with TTFT/TPOT/p99 measured-"
                         "vs-predicted columns")
    ap.add_argument("--obs-lanes-only", action="store_true",
                    help="run ONLY the measured-lanes + serving-"
                         "telemetry sweep (device-trace capture -> "
                         "lane-drift rows, decode TTFT/TPOT/p99) and "
                         "merge it into existing BENCH_SEARCH "
                         "artifacts")
    ap.add_argument("--drift-threshold", type=float, default=0.5,
                    help="predicted-vs-measured ratio beyond which a "
                         "DriftReport flags staleness")
    args = ap.parse_args()

    import os

    import jax

    if args.cpu_mesh or os.environ.get("JAX_PLATFORMS") == "cpu":
        from flexflow_tpu.comm.compat import force_cpu_devices

        force_cpu_devices(args.devices)

    obs_log = None
    if args.obs:
        from flexflow_tpu.obs.events import BUS

        obs_log = f"{args.out_prefix}_obs.jsonl"
        # fresh log per run: the report renders THIS run's decisions.
        # Close first — FLEXFLOW_TPU_OBS may have bound the bus to this
        # very path at import, and removing a file an open sink holds
        # would silently strand every later event on the unlinked inode
        BUS.close()
        if os.path.exists(obs_log):
            os.remove(obs_log)
        BUS.configure(obs_log)

    sweep_precisions = [p for p in args.sync_precision.split(",") if p]
    if args.obs_lanes_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["obs_lanes"] = obs_lanes_sweep(
            args.devices, drift_threshold=args.drift_threshold,
            obs_log=f"{args.out_prefix}_obs.jsonl")
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous measured-lanes section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Measured lanes & request telemetry"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_obs_lanes_md_lines(report["obs_lanes"]))
                    + "\n" + tail)
        print(f"# merged measured-lanes sweep into {path} / {md}")
        return
    if args.always_on_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["always_on"] = always_on_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous always-on section (same merge
            # discipline as the other --*-only modes)
            marker = "\n## Always-on controller"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_always_on_md_lines(report["always_on"]))
                    + "\n" + tail)
        print(f"# merged always-on controller sweep into {path} / {md}")
        return
    if args.serve_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["serve_sweep"] = serve_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous serving section (same merge
            # discipline as the other --*-only modes)
            marker = "\n## Inference serving"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_serve_sweep_md_lines(
                        report["serve_sweep"]))
                    + "\n" + tail)
        print(f"# merged serving sweep into {path} / {md}")
        return
    if args.disagg_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["disagg_sweep"] = disagg_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous disaggregation section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Prefill/decode disaggregation"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_disagg_sweep_md_lines(
                        report["disagg_sweep"]))
                    + "\n" + tail)
        print(f"# merged disaggregation sweep into {path} / {md}")
        return
    if args.kv_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["kv_sweep"] = kv_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous KV-memory section (same merge
            # discipline as the other --*-only modes)
            marker = "\n## KV memory as a searched resource"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_kv_sweep_md_lines(report["kv_sweep"]))
                    + "\n" + tail)
        print(f"# merged KV-memory sweep into {path} / {md}")
        return
    if args.fleet_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["fleet_sweep"] = fleet_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous serving-fleet section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Serving fleet"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_fleet_sweep_md_lines(
                        report["fleet_sweep"]))
                    + "\n" + tail)
        print(f"# merged serving-fleet sweep into {path} / {md}")
        return
    if args.request_trace_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["request_trace_sweep"] = request_trace_sweep(
            args.devices, args.out_prefix)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous request-tracing section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Observability: request tracing"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_request_trace_md_lines(
                        report["request_trace_sweep"]))
                    + "\n" + tail)
        print(f"# merged request-tracing sweep into {path} / {md}")
        return
    if args.scale_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["scale_sweep"] = scale_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous scale-sweep section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Production-scale search"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_scale_sweep_md_lines(
                        report["scale_sweep"]))
                    + "\n" + tail)
        print(f"# merged scale sweep into {path} / {md}")
        return
    if args.sp_scale_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["sp_scale_sweep"] = sp_scale_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous sp-scale section (same merge
            # discipline as the other --*-only modes)
            marker = "\n## Series-parallel search on arbitrary"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_sp_scale_sweep_md_lines(
                        report["sp_scale_sweep"]))
                    + "\n" + tail)
        print(f"# merged sp-scale sweep into {path} / {md}")
        return
    if args.co_search_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["co_search_sweep"] = co_search_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous co-search section (same merge
            # discipline as the other --*-only modes)
            marker = "\n## Joint comm-plan co-search"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_co_search_sweep_md_lines(
                        report["co_search_sweep"]))
                    + "\n" + tail)
        print(f"# merged co-search sweep into {path} / {md}")
        return
    if args.topology_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["topology_sweep"] = topology_sweep(args.devices)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous topology-sweep section (same
            # merge discipline as the other --*-only modes)
            marker = "\n## Hierarchical topology sweep"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_topology_sweep_md_lines(
                        report["topology_sweep"]))
                    + "\n" + tail)
        print(f"# merged topology sweep into {path} / {md}")
        return
    if args.sync_schedule_only:
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["sync_schedule_sweep"] = sync_schedule_sweep(
            args.devices, args.steps,
            drift_threshold=args.drift_threshold)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous schedule-sweep section (same
            # merge discipline as --sync-sweep-only)
            marker = "\n## Overlap-aware sync schedule"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_schedule_sweep_md_lines(
                        report["sync_schedule_sweep"]))
                    + "\n" + tail)
        print(f"# merged sync-schedule sweep into {path} / {md}")
        return
    if args.sync_sweep_only:
        if not sweep_precisions:
            ap.error("--sync-sweep-only needs a non-empty --sync-precision "
                     "list (empty means 'sweep disabled')")
        path = f"{args.out_prefix}.json"
        if os.path.exists(path):
            with open(path) as f:
                report = json.load(f)
        else:
            report = {"devices": args.devices,
                      "backend": jax.devices()[0].platform,
                      "calibrated": False, "calibration_backend": None,
                      "models": {}}
        report["sync_precision_sweep"] = sync_precision_sweep(
            args.devices, args.steps, sweep_precisions)
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        md = f"{args.out_prefix}.md"
        head, tail = "", ""
        if os.path.exists(md):
            with open(md) as f:
                head = f.read()
            # splice out ONLY a previous sweep section: everything from
            # its marker to the next "## " heading (or EOF) — later
            # sections survive the merge
            marker = "\n## Sync-precision sweep"
            at = head.find(marker)
            if at >= 0:
                nxt = head.find("\n## ", at + 1)
                tail = head[nxt:] if nxt >= 0 else ""
                head = head[:at]
        with open(md, "w") as f:
            f.write(head.rstrip("\n") + "\n"
                    + "\n".join(_sweep_md_lines(report["sync_precision_sweep"]))
                    + "\n" + tail)
        print(f"# merged sync-precision sweep into {path} / {md}")
        return

    specs = _model_specs()
    names = [n for n in args.models.split(",") if n in specs]
    if args.calibrate_only:
        args.calibrate = True
    calibration = None
    bench_cal = {}  # per-model seconds spent in the bench's own probe
    # loop — reported as calibration_seconds, never folded into
    # search_seconds (the satellite split)
    if args.load_calibration:
        from flexflow_tpu.search.calibration import CalibrationTable

        if args.calibrate:
            print("# --load-calibration takes precedence over --calibrate: "
                  "using the existing file, no new probes")
        if not os.path.exists(args.calibration_file):
            ap.error(f"--load-calibration: {args.calibration_file} does not "
                     "exist (run with --calibrate first, e.g. on the TPU)")
        calibration = CalibrationTable.load(args.calibration_file)
        print(f"# loaded {len(calibration)} calibration records from "
              f"{args.calibration_file}")
    elif args.calibrate:
        from flexflow_tpu.search.calibration import (
            CalibrationTable,
            calibrate_graph,
        )

        import flexflow_tpu as ff

        def _coverage_graph():
            """Ops the zoo's calibrate sweep misses or under-reaches
            (the reference measures every op kind it runs,
            simulator.cc:515): dropout, batch_matmul, pooling, and the
            MoE dispatch chain (top_k/group_by/aggregate)."""
            cfg = ff.FFConfig(batch_size=32, num_devices=args.devices)
            m = ff.FFModel(cfg)
            x = m.create_tensor([32, 64, 64], name="cal_x")
            a = m.dropout(x, rate=0.1, name="cal_dropout")
            bmm = m.batch_matmul(a, x, name="cal_bmm")
            pooled = m.mean(bmm, dims=[1], name="cal_mean")
            img = m.create_tensor([32, 16, 16, 8], name="cal_img")
            p = m.pool2d(img, 2, 2, stride_h=2, stride_w=2, name="cal_pool")
            pf = m.flat(p, name="cal_flat")
            gate_in = m.dense(pooled, 8, name="cal_gate")
            gates = m.softmax(gate_in, name="cal_gates")
            tg, ti = m.top_k(gates, 2, name="cal_topk")
            grouped = m.group_by(pf, ti, 8, name="cal_groupby")
            experts = [m.dense(g, 16, name=f"cal_exp{i}")
                       for i, g in enumerate(grouped[:2])]
            del experts
            return m.graph

        live = jax.devices()[0].platform
        if os.path.exists(args.calibration_file):
            calibration = CalibrationTable.load(args.calibration_file)
            if calibration.backend not in (None, live):
                # mixing probes from different backends would mislabel
                # the table's provenance — start fresh on this backend
                print(f"# existing calibration is from "
                      f"{calibration.backend!r}, live backend is {live!r}: "
                      f"recalibrating from scratch")
                calibration = CalibrationTable()
            else:
                print(f"# resuming calibration: {len(calibration)} existing "
                      f"records")
        else:
            calibration = CalibrationTable()
        for n in names:
            cfg = ff.FFConfig(batch_size=specs[n]["batch"],
                              num_devices=args.devices)
            t0 = time.monotonic()
            calibrate_graph(specs[n]["build"](cfg).graph, args.devices,
                            calibration,
                            time_budget_s=args.calibrate_budget)
            bench_cal[n] = time.monotonic() - t0
            print(f"# calibration after {n}: {len(calibration)} records, "
                  f"{calibration.num_clusters} clusters")
        calibrate_graph(_coverage_graph(), args.devices, calibration,
                        time_budget_s=args.calibrate_budget / 2)
        # the full MoE dispatch chain (group_by/aggregate/cache) probes
        # from the zoo's MoE builder (reference: moe.cc self-reports
        # throughput the same way the other examples do)
        from flexflow_tpu.models import build_moe

        calibrate_graph(
            build_moe(ff.FFConfig(batch_size=32,
                                  num_devices=args.devices)).graph,
            args.devices, calibration,
            time_budget_s=args.calibrate_budget / 2)
        calibration.save(args.calibration_file)
        print(f"# calibrated {len(calibration)} (op, view) records + "
              f"{calibration.num_clusters} fusion clusters "
              f"on {jax.devices()[0].platform}")
    if args.calibrate_only:
        # applies to the --load-calibration combination too: the flag's
        # contract is "never touch the BENCH_SEARCH artifacts"
        return

    cost_cache = None if args.no_cost_cache else args.cost_cache_file
    report = {"devices": args.devices,
              "calibrated": bool(calibration) and len(calibration) > 0,
              "calibration_backend": getattr(calibration, "backend", None)
              if calibration else None,
              "backend": jax.devices()[0].platform,
              "cost_cache": cost_cache,
              "models": {}}
    can_exec = len(jax.devices()) >= args.devices and not args.sim_only
    cal_file = args.calibration_file if calibration is not None else None
    if args.verify:
        from flexflow_tpu.analysis import set_verify

        set_verify(True)
    for n in names:
        row = simulate_pair(n, specs[n], args.devices, calibration,
                            calibration_file=cal_file,
                            cost_cache_file=cost_cache or "",
                            verify=args.verify,
                            slice_levels=args.slice_levels)
        row["calibration_seconds"] = round(
            row.get("calibration_seconds", 0.0) + bench_cal.get(n, 0.0), 2)
        if can_exec:
            try:
                ex = execute_pair(n, specs[n], args.devices, args.steps,
                                  calibration_file=cal_file,
                                  obs=args.obs, out_prefix=args.out_prefix,
                                  drift_threshold=args.drift_threshold)
            except Exception as e:  # honest artifact: record the failure
                ex = {"exec_error": f"{type(e).__name__}: {e}"}
            if ex:
                row.update(ex)
        report["models"][n] = row
        print(json.dumps({"model": n, **row}))
    # "calibrated" must mean the sims CONSULTED measurements, not merely
    # that a table object existed (it may have been discarded per-model
    # as incoherent with the machine model)
    report["calibrated"] = any(
        r.get("sim_calibrated") for r in report["models"].values())
    if sweep_precisions:
        report["sync_precision_sweep"] = sync_precision_sweep(
            args.devices, args.steps, sweep_precisions)
    if args.sync_schedule:
        report["sync_schedule_sweep"] = sync_schedule_sweep(
            args.devices, args.steps,
            drift_threshold=args.drift_threshold)
    if args.topology:
        report["topology_sweep"] = topology_sweep(args.devices)
    if args.co_search:
        report["co_search_sweep"] = co_search_sweep(args.devices)
    if args.scale:
        report["scale_sweep"] = scale_sweep(args.devices)
    if args.sp_scale:
        report["sp_scale_sweep"] = sp_scale_sweep(args.devices)
    if args.serve:
        report["serve_sweep"] = serve_sweep(args.devices)
    if args.disagg:
        report["disagg_sweep"] = disagg_sweep(args.devices)
    if args.kv:
        report["kv_sweep"] = kv_sweep(args.devices)
    if args.fleet:
        report["fleet_sweep"] = fleet_sweep(args.devices)
    if args.request_trace:
        report["request_trace_sweep"] = request_trace_sweep(
            args.devices, args.out_prefix)
    if args.always_on:
        report["always_on"] = always_on_sweep(args.devices)
    if args.obs:
        report["obs_lanes"] = obs_lanes_sweep(
            args.devices, drift_threshold=args.drift_threshold)

    with open(f"{args.out_prefix}.json", "w") as f:
        json.dump(report, f, indent=1)
    lines = [
        f"# {args.out_prefix} — searched strategy vs pure data parallelism",
        "",
        "Reference contract: scripts/osdi22ae/*.sh (searched vs "
        "`--only-data-parallel`, same hardware).  Simulated costs are for "
        f"the full-size models on the {args.devices}-device TPU machine "
        "model; executed ratios run BOTH strategies for real on the "
        "available mesh (scaled-down model sizes when the mesh is CPU — "
        "see exec_scale).",
        "",
        "| model | nodes | sim DP ms | sim searched ms | sim ratio | "
        "exec ratio | exec backend/scale | cal s | search s | "
        "delta hit | cache |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for n, r in report["models"].items():
        cache_cell = ("result" if r.get("cost_cache_result_hit")
                      else (f"rows {r['cost_cache_row_hit_rate']:.0%}"
                            if r.get("cost_cache_row_hit_rate") is not None
                            else "—"))
        lines.append(
            f"| {n} | {r['nodes']} | {r['sim_dp_ms']} | "
            f"{r['sim_searched_ms']} | {r['sim_ratio']} | "
            f"{r.get('exec_ratio', '—')} | "
            f"{r.get('exec_backend', '—')}/{r.get('exec_scale', '—')} | "
            f"{r.get('calibration_seconds', 0.0)} | {r['search_seconds']} | "
            f"{r.get('delta_hit_rate', '—')} | {cache_cell} |")
    cal_note = (
        f"Calibrated cost model: {report['calibrated']}"
        + (f" (probes measured on {report['calibration_backend']})."
           if report.get("calibration_backend") else ".")
    )
    # honesty notes derived from THIS run's numbers — a hardcoded list
    # of winners goes stale (and self-contradictory) on regeneration
    exec_rows = {
        k: v["exec_ratio"] for k, v in report["models"].items()
        if isinstance(v.get("exec_ratio"), (int, float))
    }
    won = sorted(k for k, r in exec_rows.items() if r > 1.0)
    lost = sorted(k for k, r in exec_rows.items() if r <= 1.0)
    kept_dp = sorted(
        k for k, v in report["models"].items() if v.get("searched_is_dp"))
    lines += [
        "",
        cal_note,
        "Honesty notes: the simulator's DLRM DP cost is dominated by the "
        "full-table gradient allreduce (the real phenomenon Unity "
        "exploits, dlrm.cc + osdi22ae/dlrm.sh).  Executed ratios on a CPU "
        "mesh are bounded by the host: with fewer physical cores than "
        "virtual devices (see exec_host_cores) per-device compute "
        "serializes, so work/communication-AVOIDING strategies can show "
        "real wins there while compute-parallel ones also pay GSPMD "
        "resharding copies; single-core timing jitter moves ratios near "
        "1.0 between runs.  "
        f"In this run the searched strategy won at execution for "
        f"{', '.join(won) or 'none'} and did not for "
        f"{', '.join(lost) or 'none'}.  "
        + (f"For {', '.join(kept_dp)} the search's champion-vs-DP floor "
           "kept plain data parallelism (predicted win below the "
           "uncertainty margin), so both executed programs are "
           "IDENTICAL and the measured ratio is timing noise around "
           "1.0.  " if kept_dp else "")
        + "The contract number for "
        "compute-parallel strategies is the TPU-machine-model sim "
        "ratio, which the calibrated table makes falsifiable.",
    ]
    if report.get("sync_precision_sweep"):
        lines += _sweep_md_lines(report["sync_precision_sweep"])
    if report.get("sync_schedule_sweep"):
        lines += _schedule_sweep_md_lines(report["sync_schedule_sweep"])
    if report.get("topology_sweep"):
        lines += _topology_sweep_md_lines(report["topology_sweep"])
    if report.get("co_search_sweep"):
        lines += _co_search_sweep_md_lines(report["co_search_sweep"])
    if report.get("scale_sweep"):
        lines += _scale_sweep_md_lines(report["scale_sweep"])
    if report.get("sp_scale_sweep"):
        lines += _sp_scale_sweep_md_lines(report["sp_scale_sweep"])
    if report.get("serve_sweep"):
        lines += _serve_sweep_md_lines(report["serve_sweep"])
    if report.get("disagg_sweep"):
        lines += _disagg_sweep_md_lines(report["disagg_sweep"])
    if report.get("kv_sweep"):
        lines += _kv_sweep_md_lines(report["kv_sweep"])
    if report.get("fleet_sweep"):
        lines += _fleet_sweep_md_lines(report["fleet_sweep"])
    if report.get("request_trace_sweep"):
        lines += _request_trace_md_lines(report["request_trace_sweep"])
    if report.get("always_on"):
        lines += _always_on_md_lines(report["always_on"])
    if report.get("obs_lanes"):
        lines += _obs_lanes_md_lines(report["obs_lanes"])
    with open(f"{args.out_prefix}.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# wrote {args.out_prefix}.json / {args.out_prefix}.md")

    if args.obs and obs_log and os.path.exists(obs_log):
        # render the strategy-explanation report from this run's event
        # log (tools/ffobs.py is stdlib-only, so the subprocess is fast)
        import subprocess
        import sys as _sys

        from flexflow_tpu.obs.events import BUS

        BUS.flush()
        ffobs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "ffobs.py")
        proc = subprocess.run(
            [_sys.executable, ffobs, "report", obs_log],
            capture_output=True, text=True)
        if proc.returncode == 0:
            with open(f"{args.out_prefix}_report.md", "w") as f:
                f.write(proc.stdout)
            print(f"# wrote {args.out_prefix}_report.md (telemetry: "
                  f"{obs_log})")
        else:
            print(f"# ffobs report failed: {proc.stderr.strip()}")


if __name__ == "__main__":
    main()
