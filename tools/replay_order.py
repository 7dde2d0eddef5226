"""Pick the ``order_seed`` of a serving cell whose traffic file says
``"order": "fixed"`` by REPLAY: the benchmark's own driver
(``benchmarks/harness/serve.py drive``) and the real
``ContinuousBatchingExecutor`` under a virtual clock, over a step function
that computes nothing and costs what the chip measured.  No chip, no
model; a replay of a 50 s timeline takes about 3 s.

A cell of few, long admissions reads differently by the ORDER of the same
requests alone (which of them straddle the window's edges), and a run
differs from the next by whether an admission crosses an edge.  So:
replay ``--orders`` orders, keep those between the 30th and the 70th
percentile of tokens/s, sweep each over the frame's and the chunk's speed
(what seeds and later changes do to the timeline), and rank them by how
far the reading leaves a smooth plane.  The costs are a cell's own, read
from a chip run (PERF.md section 4 has the long-context cell's):

    python tools/replay_order.py trinity-large-preview.serve-longctx-decode \\
        --frame-ms 3.9 --kv-gbps 500 --chunk-ms 28

A frame costs ``frame_ms`` + the K/V bytes its rows read (the
configuration's ``work.attention_kernel_bytes``) at ``kv_gbps``; a prefill
chunk ``chunk_ms`` + ``chunk_ms_per_kkey`` for every 1,024 positions
behind its last one (0 by default: a window layer's chunk walks a fixed
count; a global or sparse layer's walks every key block behind it).
Numbers printed here are a replay's, never a device's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from multiprocessing import Pool

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FRAME_SWEEP = (0.99, 0.995, 1.0, 1.005, 1.01)
CHUNK_SWEEP = (0.97, 0.98, 0.99, 1.0, 1.01, 1.02, 1.03, 1.04, 1.05)


class CostedStep:
    """The step and prefill functions an executor drives, advancing a
    virtual clock instead of computing."""

    attention_path = "replay"

    def __init__(self, config, frame_s, s_per_kv_byte, chunk_s,
                 chunk_s_per_key=0.0):
        from benchmarks.harness.spec import resolve_module

        self.config, self.now = config, 0.0
        self.work = resolve_module(config["work"])
        self.frame_s, self.s_per_byte, self.chunk_s = (
            frame_s, s_per_kv_byte, chunk_s)
        self.chunk_s_per_key = chunk_s_per_key
        self.frames = self.chunks = 0

    def clock(self):
        return self.now

    def __call__(self, ids, table, lens):
        rows = np.asarray(lens, np.int64) + 1  # the fresh token too
        self.now += self.frame_s + self.s_per_byte * (
            self.work.attention_kernel_bytes(self.config, rows, 2))
        self.frames += 1
        logits = np.zeros((len(rows), 1, 2), np.float32)
        logits[:, 0, 1] = 1.0  # never an end-of-sequence id
        return logits

    def prefill(self, ids, positions, table):
        # one call is sent a prompt's whole run of chunks
        c = self.config["prefill_chunk"]
        n = np.shape(ids)[1] // c
        behind = np.asarray(positions).reshape(-1, n, c)[0, :, -1] + 1
        self.now += self.chunk_s * n + self.chunk_s_per_key * float(
            behind.sum())
        self.chunks += n


def replay(cell, order_seed, costs, frame_scale=1.0, chunk_scale=1.0):
    """One run of the cell's traffic in the order ``order_seed`` gives;
    returns the window's reading and where the run stood at its end."""
    from benchmarks.harness import serve

    # (frame, a K/V byte, a chunk[, a position behind a chunk]): seconds
    frame_s, s_per_byte, chunk_s, chunk_per_key = (tuple(costs) + (0.0,))[:4]
    step = CostedStep(cell.config, frame_s * frame_scale,
                      s_per_byte * frame_scale, chunk_s * chunk_scale,
                      chunk_per_key * chunk_scale)
    ex = serve.new_executor(cell.config, step)
    run = serve.drive(ex, dict(cell.traffic, order_seed=order_seed), 2, 0,
                      cell.run_seconds, clock=step.clock)
    window = run["ledger"].window(run["t_start"], run["t_end"])
    seconds = run["t_end"] - run["t_start"]
    return {"order_seed": order_seed, "tokens_per_s": window["tokens"] / seconds,
            "tokens": window["tokens"], "window_s": seconds,
            "completed": window["completed"], "warmup_s": run["t_start"],
            "frames": step.frames, "chunks": step.chunks}


def _one(args):
    return replay(*args)


def rank(cell, orders, costs, workers=4):
    """(summary of the orders' nominal readings, the near-median orders
    ranked by the largest residual off a plane over the two sweeps)."""
    with Pool(workers) as pool:
        nominal = pool.map(_one, [(cell, o, costs) for o in range(orders)])
        tps = np.array([r["tokens_per_s"] for r in nominal])
        lo, hi = np.percentile(tps, [30, 70])
        near = [r["order_seed"] for r in nominal
                if lo <= r["tokens_per_s"] <= hi]
        grid = [(f, c) for f in FRAME_SWEEP for c in CHUNK_SWEEP]
        swept = pool.map(_one, [(cell, o, costs, f, c)
                                for o in near for f, c in grid])
    median = float(np.median(tps))
    rows = []
    for k, o in enumerate(near):
        y = np.array([r["tokens_per_s"]
                      for r in swept[k * len(grid):(k + 1) * len(grid)]])
        x = np.array([[1.0, f, c] for f, c in grid])
        coef, *_ = np.linalg.lstsq(x, y, rcond=None)
        resid = (y - x @ coef) / y.mean() * 100.0
        q1, q3 = np.percentile(resid, [25, 75])
        rows.append(dict(nominal[o], off_median_pct=100 * (tps[o] - median)
                         / median, resid_max_pct=float(np.abs(resid).max()),
                         resid_iqr_pct=float(q3 - q1)))
    rows.sort(key=lambda r: r["resid_max_pct"])
    return {"orders": orders, "min": float(tps.min()), "median": median,
            "max": float(tps.max()), "near_median": len(near)}, rows


def main(argv=None):
    from benchmarks.harness import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--orders", type=int, default=128)
    ap.add_argument("--frame-ms", type=float, required=True)
    ap.add_argument("--kv-gbps", type=float, required=True)
    ap.add_argument("--chunk-ms", type=float, required=True)
    ap.add_argument("--chunk-ms-per-kkey", type=float, default=0.0)
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(ROOT, args.workload)
    if cell.traffic.get("order") != "fixed":
        raise SystemExit(f"{args.workload}'s traffic draws its order from "
                         "the run's seed: there is no order_seed to pick")
    costs = (args.frame_ms * 1e-3, 1e-9 / args.kv_gbps, args.chunk_ms * 1e-3,
             args.chunk_ms_per_kkey * 1e-3 / 1024)
    summary, rows = rank(cell, args.orders, costs, args.workers)
    print(json.dumps(summary))
    for row in rows:
        print(json.dumps(row))


if __name__ == "__main__":
    main()
