#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process, no children, every visible chip.  Drives the two main
paths through the entry points a user calls, at the full width of
models the repo supports, with seeded random weights:

* train: ``build_gpt`` at its default width (vocab 32000, 12 layers,
  hidden 768, 12 heads, ff 3072, seq 1024), batch 8, bf16, Adam, sparse
  CCE — ``FFModel.compile()`` runs the strategy search, ``fit`` takes 8
  steps.  Right = every loss finite, the last below the first, and the
  compiled step holds the flash kernel's 3 x 12 Mosaic calls.  On more
  than one chip also: the searched strategy splits some op, and every
  device holds memory.
* serve: ``build_gpt_decode`` at ``GPT_DECODE_SERVE_KW`` on ONE chip,
  chunked prefill + ``ContinuousBatchingExecutor`` over 8 seeded
  requests.  Right = all complete, the tokens equal the same requests
  served through the XLA gather path (``use_kernel=False``, the
  reference, invoked on purpose), the frame holds the Mosaic call; then
  once more over an int8 page pool, whose logits on one fixed frame
  stay within 0.05 of the fp32 pool's.

Any failed check raises and the run exits non-zero: nothing catches a
leg's failure and carries on.  Without a TPU (or without the rest of the
repo next to this file) the script exits non-zero and prints no result.

The LAST line of stdout is the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it (``"ok": false`` when a leg failed).
The line before it is one JSON object of facts about the run (losses,
Mosaic-call counts, strategy views, and a ``setup`` block of compile
seconds, step/frame milliseconds and peak bytes) — information, not a
result: nothing here is a benchmark.
"""

import collections
import gc
import importlib.metadata
import json
import sys
import time

import numpy as np

SEED = 0
TRAIN_BATCH = 8
TRAIN_STEPS = 8
SERVE_REQUESTS = 8
SERVE_NEW_TOKENS = 16
PREFILL_CHUNK = 64
INT8_LOGIT_BOUND = 0.05  # tests/test_kv.py holds the kernel to the same
FIXED_FRAME_PROMPTS = (64, 200, 333, 600)  # tokens per live row
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: FAILED — {what}")


def mosaic_calls(jitted, *args) -> int:
    """Mosaic (Pallas TPU) custom calls in the COMPILED program of
    ``jitted`` at ``args`` — what proves a kernel ran compiled rather
    than interpreted or replaced by an XLA path.  The second compile of
    a program this process just ran is a persistent-cache hit."""
    return jitted.lower(*args).compile().as_text().count(MOSAIC_TARGET)


def device_memory(key: str):
    """``memory_stats()[key]`` of every device, in device order."""
    import jax

    return [d.memory_stats()[key] for d in jax.devices()]


def train_leg(gpt_kw=None, steps=TRAIN_STEPS, batch=TRAIN_BATCH):
    """Default-width GPT through compile() (search included) and fit().
    ``gpt_kw`` shrinks the model for a CPU rehearsal of this function;
    the smoke itself passes none."""
    import jax

    import flexflow_tpu as ff
    from examples.common import lm_sequence_data
    from flexflow_tpu.keras.callbacks import Callback
    from flexflow_tpu.models import build_gpt

    class EpochClock(Callback):
        """Wall time at the start of training and the end of every
        epoch (fit reads the loss back each epoch, so the device work
        is done by then)."""

        def __init__(self):
            self.stamps = []

        def on_train_begin(self):
            self.stamps.append(time.perf_counter())

        def on_epoch_end(self, epoch, logs):
            self.stamps.append(time.perf_counter())

    cfg = ff.FFConfig(batch_size=batch, epochs=steps, num_devices=0,
                      compute_dtype="bfloat16", seed=SEED,
                      cost_cache_file="")
    model = build_gpt(cfg, **(gpt_kw or {}))
    ids = model._input_tensors[0]
    seq_len = ids.sizes[1]
    vocab = model.graph.sinks()[-1].op.output_shapes[0].sizes[-1]
    layers = sum(n.op.op_type.name == "MULTIHEAD_ATTENTION"
                 for n in model.graph.nodes.values())

    t0 = time.perf_counter()
    model.compile(optimizer=ff.AdamOptimizer(alpha=3e-4),
                  loss_type="sparse_categorical_crossentropy", metrics=[])
    search_s = time.perf_counter() - t0
    strategy = model.compiled.strategy
    graph = model.compiled.graph
    views = dict(collections.Counter(str(mv) for mv in strategy.values()))
    print(f"[train] compile() incl. search {search_s:.1f}s, executor "
          f"{type(model.compiled).__name__}, views {views}")

    # one batch, one step per epoch: fit's history then carries the loss
    # of every step, and the one-chip and four-chip runs see the same
    # global batch
    x, y = lm_sequence_data(batch, seq_len, vocab, seed=SEED)
    clock = EpochClock()
    history = model.fit(x=x, y=y, epochs=steps, shuffle=False,
                        verbose=False, callbacks=[clock])
    losses = [float(h["loss"]) for h in history]
    print(f"[train] losses {[round(v, 4) for v in losses]}")
    check(len(losses) == steps, f"fit ran {len(losses)} steps, not {steps}")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")

    step_s = np.diff(clock.stamps)
    compiled = model.compiled
    calls = mosaic_calls(
        compiled._train_step_fn, model.params, model.opt_state,
        model.state, jax.random.key(0),
        [jax.device_put(x, compiled.input_sharding(0))],
        jax.device_put(y, compiled.batch_sharding()))
    check(calls == 3 * layers,
          f"train step holds {calls} Mosaic calls, expected 3 x {layers} "
          f"(flash fwd + dq + dkv per layer)")

    devices = jax.devices()
    split_ops = sum(mv.num_parts > 1 for mv in strategy.values())
    in_use = device_memory("bytes_in_use")
    if len(devices) > 1:
        check(split_ops > 0,
              "multi-chip strategy has no op with a view of > 1 part")
        check(all(b > 0 for b in in_use),
              f"a device holds no memory: bytes_in_use {in_use}")
    facts = {
        "model": f"gpt {layers}L seq{seq_len} vocab{vocab} batch{batch}",
        "losses": losses,
        "mosaic_calls": calls,
        "ops": len(graph.nodes),
        "split_ops": split_ops,
        "views": views,
        "bytes_in_use": in_use,
    }
    setup = {
        "search_and_lower_s": round(search_s, 2),
        "first_step_s": round(float(step_s[0]), 2),  # compile included
        "step_ms": round(float(np.median(step_s[1:])) * 1e3, 2),
        "peak_bytes_in_use": device_memory("peak_bytes_in_use"),
    }
    return facts, setup


def build_serve_model(serve_kw, slots, use_kernel=True, kv_precision="off"):
    """The decode model on ONE chip, compiled for inference.  A pinned
    pool dtype rides the serve objective's KV lane
    (FFConfig.kv_precision), as a user would arm it."""
    import flexflow_tpu as ff
    from flexflow_tpu.models import build_gpt_decode

    lane = ({} if kv_precision == "off"
            else dict(objective="serve", kv_precision=kv_precision))
    cfg = ff.FFConfig(batch_size=slots, num_devices=1, seed=SEED,
                      cost_cache_file="", **lane)
    model = build_gpt_decode(cfg, use_kernel=use_kernel, **serve_kw)
    model.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
                  comp_mode="inference")
    return model


class Server:
    """One compiled decode model behind the continuous-batching
    executor, warmed up before it takes requests."""

    def __init__(self, model, serve_kw, slots, chunk=PREFILL_CHUNK):
        from flexflow_tpu.runtime.decode import compiled_decode_step

        self.model = model
        self.slots = slots
        self.chunk = chunk
        self.page_size = serve_kw["page_size"]
        self.pps = serve_kw["pages_per_seq"]
        self.vocab = serve_kw["vocab"]
        self.step = compiled_decode_step(model, prefill_chunk=chunk)
        # warm-up = compile: one prefill chunk and one all-idle frame.
        # Both write only where no live sequence reads — slot 0's own
        # pages at positions a later prefill rewrites, and each idle
        # row's own range (runtime/decode.py frame contract).
        t0 = time.perf_counter()
        self.step.prefill(np.zeros((1, chunk), np.int32),
                          np.arange(chunk, dtype=np.int32)[None, :],
                          self.idle_frame()[1][:1])
        np.asarray(self.step(*self.idle_frame()))
        self.compile_s = time.perf_counter() - t0

    def idle_frame(self):
        table = np.arange(self.slots * self.pps,
                          dtype=np.int32).reshape(self.slots, self.pps)
        return (np.zeros((self.slots, 1), np.int32), table,
                np.zeros((self.slots,), np.int32))

    def serve(self, requests):
        from flexflow_tpu.runtime.decode import ContinuousBatchingExecutor

        ex = ContinuousBatchingExecutor(
            self.step, max_seqs=self.slots, page_size=self.page_size,
            pages_per_seq=self.pps, prefill_fn=self.step.prefill,
            prefill_chunk=self.chunk)
        return ex.run(requests), ex.summary()

    def frame_mosaic_calls(self) -> int:
        ids, table, lens = self.idle_frame()
        # the program the server RUNS takes the served tree
        return mosaic_calls(self.step.frame_fn, self.step.weights,
                            self.step.state["state"], [ids, table, lens])

    def fixed_frame_logits(self, prompt_lens=FIXED_FRAME_PROMPTS):
        """Logits of one fixed decode frame: seeded prompts prefilled
        into the first slots' own page ranges, every other row idle,
        then ONE frame feeding each prompt's last token."""
        from flexflow_tpu.runtime.prefill import run_chunked_prefill

        rng = np.random.default_rng(SEED + 1)
        ids, table, lens = self.idle_frame()
        for row, n in enumerate(prompt_lens):
            tokens = rng.integers(1, self.vocab, size=n).tolist()
            run_chunked_prefill(self.step.prefill, tokens, table[row],
                                chunk=self.chunk,
                                cap=self.page_size * self.pps)
            ids[row, 0] = tokens[-1]
            lens[row] = n - 1
        logits = np.asarray(self.step(ids, table, lens), np.float32)
        return logits[:len(prompt_lens), 0]


def serve_requests(vocab, n=SERVE_REQUESTS, lo=17, hi=600,
                   new_tokens=SERVE_NEW_TOKENS):
    from flexflow_tpu.runtime.decode import DecodeRequest

    rng = np.random.default_rng(SEED)
    lens = rng.integers(lo, hi + 1, size=n)
    lens[0], lens[-1] = lo, hi  # both ends of the range, every run
    return [
        DecodeRequest(rid=f"r{i}",
                      prompt=rng.integers(1, vocab, size=int(k)).tolist(),
                      max_new_tokens=new_tokens)
        for i, k in enumerate(lens)]


def serve_leg(serve_kw=None, slots=None, prompt_hi=600,
              fixed_prompts=FIXED_FRAME_PROMPTS):
    """Kernel server vs the XLA-path reference, then the int8 pool.
    ``serve_kw``/``slots`` shrink the model for a CPU rehearsal of this
    function; the smoke itself passes none."""
    from flexflow_tpu.models import GPT_DECODE_SERVE_KW, SERVE_FRAME_SLOTS

    serve_kw = serve_kw or GPT_DECODE_SERVE_KW
    slots = slots or SERVE_FRAME_SLOTS
    layers = serve_kw["num_layers"]

    def requests():
        return serve_requests(serve_kw["vocab"], hi=prompt_hi)

    def complete(name, out):
        check(len(out) == SERVE_REQUESTS
              and all(len(v) == SERVE_NEW_TOKENS for v in out.values()),
              f"{name}: {len(out)}/{SERVE_REQUESTS} requests completed "
              f"with {SERVE_NEW_TOKENS} tokens")

    # one server at a time: each holds its page pools (1 GB per fp32
    # model at the serve width) until it is dropped
    kernel = Server(build_serve_model(serve_kw, slots), serve_kw, slots)
    got, summary = kernel.serve(requests())
    complete("kernel server", got)
    check(summary["attention_path"] == "pallas",
          f"kernel server ran the {summary['attention_path']} path")
    calls = kernel.frame_mosaic_calls()
    check(calls == layers,
          f"decode frame holds {calls} Mosaic calls, expected {layers}")
    fp32_logits = kernel.fixed_frame_logits(fixed_prompts)
    kernel_compile_s = kernel.compile_s
    del kernel
    gc.collect()
    print(f"[serve] kernel server done; peak bytes "
          f"{device_memory('peak_bytes_in_use')}")

    reference = Server(
        build_serve_model(serve_kw, slots, use_kernel=False),
        serve_kw, slots)
    want, ref_summary = reference.serve(requests())
    complete("reference server", want)
    check(ref_summary["attention_path"] == "xla"
          and reference.frame_mosaic_calls() == 0,
          "the use_kernel=False reference did not take the XLA path")
    diverged = {rid: (got[rid], want[rid])
                for rid in want if got[rid] != want[rid]}
    check(not diverged,
          f"kernel tokens differ from the XLA reference: {diverged}")
    ref_logits = reference.fixed_frame_logits(fixed_prompts)
    del reference
    gc.collect()
    print(f"[serve] reference server done; peak bytes "
          f"{device_memory('peak_bytes_in_use')}")

    int8 = Server(
        build_serve_model(serve_kw, slots, kv_precision="int8"),
        serve_kw, slots)
    state = int8.step.state["state"]
    check(any(v.dtype == np.int8 for v in state.values()),
          "kv_precision='int8' did not produce an int8 page pool")
    got8, summary8 = int8.serve(requests())
    complete("int8 server", got8)
    calls8 = int8.frame_mosaic_calls()
    check(summary8["attention_path"] == "pallas" and calls8 == layers,
          f"int8 decode frame: path {summary8['attention_path']}, "
          f"{calls8} Mosaic calls, expected {layers}")
    int8_logits = int8.fixed_frame_logits(fixed_prompts)
    check(np.all(np.isfinite(int8_logits)), "int8 logits not finite")
    drift = float(np.max(np.abs(int8_logits - fp32_logits)))
    check(drift < INT8_LOGIT_BOUND,
          f"int8 pool logits drift {drift} >= {INT8_LOGIT_BOUND}")

    print(f"[serve] 8/8 requests x3 servers; int8 drift {drift:.4g}")
    facts = {
        "model": (f"gpt_decode {layers}L hidden{serve_kw['hidden']} "
                  f"page{serve_kw['page_size']}x"
                  f"{serve_kw['pages_per_seq']} slots{slots}"),
        "completed": len(got),
        "tokens_equal_xla_reference": True,
        "mosaic_calls": calls,
        "attention_path": summary["attention_path"],
        "frames": summary["frames"],
        "prefill_chunks": summary["prefill_chunks"],
        "kernel_vs_xla_logit_maxabs": float(
            np.max(np.abs(fp32_logits - ref_logits))),
        "int8": {
            "completed": len(got8),
            "mosaic_calls": calls8,
            "logit_maxabs_vs_fp32_pool": drift,
            "bound": INT8_LOGIT_BOUND,
            "tokens_equal_fp32_pool": got8 == got,
        },
    }
    setup = {
        "compile_s": {"kernel": round(kernel_compile_s, 2),
                      "int8": round(int8.compile_s, 2)},
        "frame_ms_p50": {
            "kernel": round(summary["measured_p50_s"] * 1e3, 2),
            "xla_reference": round(ref_summary["measured_p50_s"] * 1e3, 2),
            "int8": round(summary8["measured_p50_s"] * 1e3, 2)},
    }
    return facts, setup


def main() -> int:
    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax
    import jaxlib

    t_start = time.perf_counter()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found — jax reports platform "
              f"{platform!r} ({len(devices)} device(s)); nothing was run",
              file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    from flexflow_tpu import native

    engine = native.engine_status()
    print(f"[start] {device} {versions}")
    print(f"[start] search engine: {engine}")
    print(f"[start] compile cache: {cache_dir}")

    try:
        train, train_setup = train_leg()
        gc.collect()
        print(f"[train] done; bytes in use {device_memory('bytes_in_use')}, "
              f"peak {device_memory('peak_bytes_in_use')}")
        serve, serve_setup = serve_leg()
    except BaseException:
        # the result line says so; the failure itself still ends the run
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise

    print(json.dumps({
        "versions": versions,
        "search_engine": engine,
        "compile_cache": cache_dir,
        "train": train,
        "serve": serve,
        "setup": {"train": train_setup, "serve": serve_setup,
                  "peak_bytes_in_use": device_memory("peak_bytes_in_use"),
                  "wall_s": round(time.perf_counter() - t_start, 1)},
    }))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
