"""The serving driver: a configuration's decode model behind
``compiled_decode_step`` + ``ContinuousBatchingExecutor`` (chunked
prefill, continuous batching), loaded by the benchmark's own clients and
timed from outside on the host clock.

The driver calls ``ex.submit()`` and ``ex.step()`` itself.  A token
exists when ``ex.step()`` has returned (it ends in ``np.asarray``, which
blocks); after each step the driver reads ``ex.slots[i].generated`` and
``ex.finished`` to stamp every request's new tokens.  TTFT runs from the
instant the client issued the request — the ``submit`` call of a closed
loop, the DUE time of an open loop — not from admission.

Closed loop: ``clients`` callers, each submitting its next request the
instant its last one finished.  The first request of client i asks for
(i+1)/clients of its tokens, so the streams start out of step, as a
server that has been up for a while finds them.  Open loop: requests
arrive on the traffic file's schedule whatever the server does; how late
the generator ran is printed.

Of the model the driver knows what the configuration's ``harness`` block
says by the harness's own names — ``vocab`` (ids are drawn below it),
``context`` = ``page_size`` x ``pages_per_seq`` tokens a sequence,
``layers``, ``kv_pools`` (patterns of the state keys that are KV pools),
``mosaic_calls.decode_frame`` — and the modules it names under
``reference`` and ``work``.  The builder is called with
``**builder_kwargs`` and nothing else looks inside them.
"""

from __future__ import annotations

import fnmatch
import itertools
import math
import time

import numpy as np

from benchmarks.harness import device, traffic as traffic_gen
from benchmarks.harness.spec import resolve_dotted, resolve_module

# bf16 compute against a float32 "highest" reference.  Logits of the
# seeded, untrained model have a standard deviation of 0.2 (largest about
# 1); every layer rounds its matmul inputs to 8 bits of mantissa and
# LayerNorm renormalises, so the error does not grow with depth: the chip
# showed 0.0081..0.0087 at most.  0.03 is three and a half times that and
# a seventh of the logits' own spread, which a wrong cache position, a
# dropped token or a wrong page moves a logit by.
PROBE_LOGIT_ATOL = 0.03
# A served token is the largest of the program's logits; where those lie
# within PROBE_LOGIT_ATOL of the reference's, the reference's logit of
# that token lies within twice that of the reference's best.  A token
# altered after the logits (sampling, harvest, the stream handed back)
# lies about a whole logit (1.0) below it.
PROBE_TOKEN_GAP = 2 * PROBE_LOGIT_ATOL
TRACE_FOR_S = 2.5  # the traced tail after the window: some tens of frames


class StepTap:
    """The step function as the executor calls it, with taps: the
    ``seq_lens`` of every frame (what the ragged kernel had to read) and,
    for the probe, slot 0's logits."""

    def __init__(self, step):
        self.step = step
        self.attention_path = step.attention_path
        self.seq_lens = None
        self.logits = None

    def __call__(self, ids, table, lens):
        if self.seq_lens is not None:
            self.seq_lens.append(lens.copy())
        out = self.step(ids, table, lens)
        if self.logits is not None:
            self.logits.append(np.asarray(out, np.float32)[0, 0])
        return out


def build_server(config: dict, seed: int):
    """The decode model compiled for inference and its warmed step
    function; returns (model, step, compile seconds, warm-up seconds)."""
    import flexflow_tpu as ff
    from flexflow_tpu.runtime.decode import compiled_decode_step

    slots, chunk = config["slots"], config["prefill_chunk"]
    ffc = ff.FFConfig(batch_size=slots, seed=seed, **config["ffconfig"])
    model = resolve_dotted(config["builder"])(ffc,
                                              **config["builder_kwargs"])
    t0 = time.perf_counter()
    model.compile(loss_type=config["loss"], metrics=[],
                  comp_mode="inference")
    compile_s = time.perf_counter() - t0
    step = compiled_decode_step(model, prefill_chunk=chunk)
    # warm-up = compile, of this cell's two shapes and no others: one
    # prefill chunk and one all-idle frame.  Both write only where no
    # live sequence reads (slot 0's own pages; each idle row's own range)
    t0 = time.perf_counter()
    ids, table, lens = idle_frame(slots, config["harness"]["pages_per_seq"])
    step.prefill(np.zeros((1, chunk), np.int32),
                 np.arange(chunk, dtype=np.int32)[None, :], table[:1])
    np.asarray(step(ids, table, lens))
    return model, step, compile_s, time.perf_counter() - t0


def idle_frame(slots: int, pages_per_seq: int):
    table = np.arange(slots * pages_per_seq,
                      dtype=np.int32).reshape(slots, pages_per_seq)
    return (np.zeros((slots, 1), np.int32), table,
            np.zeros((slots,), np.int32))


def new_executor(config: dict, step, step_fn=None):
    from flexflow_tpu.runtime.decode import ContinuousBatchingExecutor

    harness = config["harness"]
    extra = {}
    if config.get("prefix_sharing"):
        extra = dict(prefix_sharing=True, copy_page_fn=step.copy_page)
    return ContinuousBatchingExecutor(
        step_fn or step, max_seqs=config["slots"],
        page_size=harness["page_size"],
        pages_per_seq=harness["pages_per_seq"],
        prefill_fn=step.prefill, prefill_chunk=config["prefill_chunk"],
        **extra)


def probe(config: dict, model, step, seed: int, log):
    """One seeded request through chunked prefill and the executor; the
    logits of its decode frames against the plain float32 reference's
    full forward at those positions, and each token the request was
    handed against the reference's best at its position (not token for
    token: with random weights the largest logit changes on rounding).
    Returns the two widest gaps and the probe's checks."""
    from flexflow_tpu.runtime.decode import DecodeRequest

    reference = resolve_module(config["reference"])
    n_prompt = config["probe"]["prompt_tokens"]
    n_new = config["probe"]["max_new_tokens"]
    rng = np.random.default_rng(seed + 7)
    prompt = rng.integers(1, config["harness"]["vocab"],
                          size=n_prompt).tolist()
    tap = StepTap(step)
    tap.logits = []
    ex = new_executor(config, step, tap)
    out = ex.run([DecodeRequest(rid="probe", prompt=prompt,
                                max_new_tokens=n_new)])["probe"]
    got = np.stack(tap.logits)                        # [n_new, vocab]
    ids = np.asarray([prompt + out[:-1]], np.int32)   # what the frames saw
    want = np.asarray(reference.forward(model.params, ids))[
        0, n_prompt - 1:n_prompt - 1 + n_new]
    gap = float(np.max(np.abs(got - want)))
    token_gap = float(np.max(want.max(axis=-1)
                             - want[np.arange(len(want)), out[:len(want)]]))
    log(f"[serve] probe: {n_prompt}-token prompt, {len(out)} tokens; decode-"
        f"frame logits vs float32 reference max|diff| {gap:.4g} (bound "
        f"{PROBE_LOGIT_ATOL}; reference std {float(want.std()):.3g}, "
        f"max|.| {float(np.abs(want).max()):.3g}); the served tokens lie "
        f"at most {token_gap:.4g} under the reference's best (bound "
        f"{PROBE_TOKEN_GAP}); path {ex.summary()['attention_path']}")
    return (gap, token_gap), {
        "tokens": len(out) == n_new, "frames": len(got) == n_new,
        "logits_equal_reference": gap <= PROBE_LOGIT_ATOL,
        "tokens_are_the_references_best": token_gap <= PROBE_TOKEN_GAP,
        "attention_path": (ex.summary()["attention_path"]
                           == config.get("attention_path", "pallas"))}


class Ledger:
    """Per-request bookkeeping on the benchmark's clock."""

    def __init__(self):
        self.issued = {}    # rid -> when the client issued it
        self.prompt = {}    # rid -> prompt tokens
        self.asked = {}     # rid -> max_new_tokens
        self.tokens = {}    # rid -> stamps of its generated tokens
        self.finished = {}  # rid -> (when, tokens delivered)

    def issue(self, req, when: float) -> None:
        self.issued[req.rid] = when
        self.prompt[req.rid] = len(req.prompt)
        self.asked[req.rid] = req.max_new_tokens
        self.tokens[req.rid] = []

    def harvest(self, ex, inflight: set, now: float) -> list:
        """Stamp the tokens the last step produced; returns the rids that
        finished in it."""
        for live in ex.slots:
            if live is not None:
                stamps = self.tokens[live.req.rid]
                stamps.extend([now] * (live.generated - len(stamps)))
        done = [rid for rid in inflight if rid in ex.finished]
        for rid in done:
            stamps = self.tokens[rid]
            stamps.extend([now] * (len(ex.finished[rid]) - len(stamps)))
            self.finished[rid] = (now, len(ex.finished[rid]))
        return done

    def window(self, t0: float, t1: float) -> dict:
        """What happened in (t0, t1]: tokens, inter-token gaps with both
        ends inside, TTFT of requests issued and first answered inside,
        and the requests that finished inside."""
        tokens, gaps, ttft = 0, [], []
        for rid, stamps in self.tokens.items():
            inside = [t for t in stamps if t0 < t <= t1]
            tokens += len(inside)
            gaps.extend(b - a for a, b in zip(stamps, stamps[1:])
                        if a >= t0 and b <= t1)
            if stamps and self.issued[rid] >= t0 and stamps[0] <= t1:
                ttft.append(stamps[0] - self.issued[rid])
        done = [rid for rid, (t, _) in self.finished.items() if t0 < t <= t1]
        short = [rid for rid in done
                 if self.finished[rid][1] != self.asked[rid]]
        return {"tokens": tokens, "gaps": gaps, "ttft": ttft,
                "completed": len(done), "short": len(short)}

    def served_contexts(self, t0: float, t1: float):
        """The forward passes the window's tokens needed, as two arrays
        of contexts (cached tokens attended to, the token's own among
        them).  Generated: token k of a prompt of n comes from a pass at
        context n + k (the first from the prompt's LAST token).
        Prefilled: the n - 1 prompt tokens before the last, contexts
        1..n-1, none of which needs logits; they count for the window
        that holds the request's first token.  A shared prefix served
        from cache still counts: this is the algorithm's need."""
        generated, prefilled = [], []
        for rid, stamps in self.tokens.items():
            n = self.prompt[rid]
            inside = [k for k, t in enumerate(stamps) if t0 < t <= t1]
            generated.append(n + np.asarray(inside, np.int64))
            if inside and inside[0] == 0:
                prefilled.append(np.arange(1, n, dtype=np.int64))
        none = [np.zeros(0, np.int64)]
        return (np.concatenate(generated or none),
                np.concatenate(prefilled or none))


def drive(ex, traffic: dict, vocab: int, seed: int, seconds: float,
          tracer=None, tap=None, clock=time.perf_counter) -> dict:
    """Warm-up, then the window, then (with a tracer) a traced tail of the
    same traffic.  Returns the ledger, the window's bounds and frames, and
    the ``seq_lens`` of the traced frames."""
    from flexflow_tpu.runtime.decode import DecodeRequest

    def submit(req, when):
        ledger.issue(req, when)
        inflight.add(req.rid)
        with device.annotation("bench.submit", phase == "traced"):
            ex.submit([DecodeRequest(rid=req.rid, prompt=req.prompt,
                                     max_new_tokens=req.max_new_tokens)])

    phase = "warmup"
    stream = traffic_gen.requests(traffic, vocab, seed)
    ledger, inflight, late = Ledger(), set(), []
    closed = traffic["kind"] == "closed_loop"
    t_begin = clock()
    if closed:
        clients = traffic["clients"]
        for i, req in enumerate(itertools.islice(stream, clients)):
            req.max_new_tokens = max(1, math.ceil(
                req.max_new_tokens * (i + 1) / clients))
            submit(req, clock())
    else:
        due = (t_begin + t for t in traffic_gen.arrivals(traffic, seed))
        next_due = next(due)
    t_start = t_end = None
    while True:
        if not closed:
            now = clock()
            while next_due <= now:
                late.append(now - next_due)
                submit(next(stream), next_due)
                next_due = next(due)
            if not inflight:  # nothing to serve: wait for the next arrival
                time.sleep(max(0.0, min(next_due - now, 0.05)))
        if inflight:
            with device.annotation("bench.executor_step", phase == "traced"):
                ex.step()
        now = clock()
        for rid in ledger.harvest(ex, inflight, now):
            inflight.discard(rid)
            if closed:
                submit(next(stream), clock())
        if phase == "warmup" and now - t_begin >= traffic["warmup_s"]:
            phase, t_start, first_frame = "window", now, ex.frame
        elif phase == "window" and now - t_start >= seconds:
            t_end, last_frame = now, ex.frame
            if tracer is None:
                break
            # the traced tail: the same traffic goes on under the
            # profiler AFTER the window, so that neither the tracer's
            # start nor its slow stop falls inside it
            phase, tap.seq_lens = "traced", []
            tracer.start()
            t_trace = clock()
        elif phase == "traced" and now - t_trace >= TRACE_FOR_S:
            tracer.stop()
            break
    return {"ledger": ledger, "t_start": t_start, "t_end": t_end,
            "frames": ex.frame_seconds[first_frame:last_frame], "late": late,
            "traced_seq_lens": tap.seq_lens if tracer is not None else []}


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        log=print) -> dict:
    import jax

    config, traffic = cell.config, cell.traffic
    harness = config["harness"]
    cap = harness["context"]
    if harness["page_size"] * harness["pages_per_seq"] != cap:
        raise ValueError(f"the configuration's context {cap} is not its "
                         f"page_size x pages_per_seq")
    work = resolve_module(config["work"])
    longest = max(p + n for p, n in traffic_gen.length_pool(traffic))
    longest += int(traffic.get("shared_prefix_tokens", 0))
    if longest > cap:
        raise ValueError(f"traffic asks for {longest} tokens a sequence, the "
                         f"configuration caps at {cap}")

    t_run = time.perf_counter()
    model, step, compile_s, warm_s = build_server(config, seed)
    t_built = time.perf_counter()
    (probe_gap, token_gap), checks = probe(config, model, step, seed, log)
    t_probed = time.perf_counter()

    tracer = device.Tracer() if trace else None
    tap = StepTap(step) if trace else None
    ex = new_executor(config, step, tap)
    run_ = drive(ex, traffic, harness["vocab"], seed, seconds, tracer, tap)
    setup_s = run_["t_start"] - t_proc0
    window_s = run_["t_end"] - run_["t_start"]
    w = run_["ledger"].window(run_["t_start"], run_["t_end"])

    log(f"[serve] {harness['layers']} layers, {config['slots']} slots x "
        f"{cap} tokens; set-up "
        f"{setup_s:.2f}s: start + imports {t_run - t_proc0:.2f}, compile() "
        f"{compile_s:.2f}, chunk + frame (program load or compile) "
        f"{warm_s:.2f}, rest of the build {t_built - t_run - compile_s - warm_s:.2f}, "
        f"probe + reference {t_probed - t_built:.2f}, warm-up traffic "
        f"{run_['t_start'] - t_probed:.2f}")

    # ---- after the window: facts and checks that need no timing --------
    summary = ex.summary()
    log(f"[serve] window {window_s:.3f}s: {w['tokens']} tokens generated, "
        f"{w['completed']} requests completed ({w['short']} short), "
        f"{len(w['gaps'])} inter-token gaps, {len(w['ttft'])} TTFT samples, "
        f"{len(run_['frames'])} frames; whole run: {summary['frames']} "
        f"frames, {summary['prefill_chunks']} prefill chunks, path "
        f"{summary['attention_path']}")
    if run_["late"]:
        log(f"[serve] generator lateness: median "
            f"{np.median(run_['late']) * 1e3:.3f} ms, max "
            f"{max(run_['late']) * 1e3:.3f} ms over {len(run_['late'])}")
    ids, table, lens = idle_frame(config["slots"], harness["pages_per_seq"])
    # the frame as the window ran it, over the served weight tree: the
    # second compile of a program this process ran is a cache hit
    frame = step.frame_fn.lower(step.weights, step.state["state"],
                                [ids, table, lens]).compile()
    calls = device.mosaic_calls(frame)
    expect_calls = harness["mosaic_calls"]["decode_frame"]
    log(f"[serve] Mosaic calls in the frame: {calls} (expected "
        f"{expect_calls}); frame memory_analysis "
        f"{device.memory_analysis_bytes(frame)}; memory_stats "
        f"{device.memory_stats()}")
    pool_itemsizes = {v.dtype.itemsize for k, v in step.state["state"].items()
                      if any(fnmatch.fnmatchcase(k, pattern)
                             for pattern in harness["kv_pools"])}
    if len(pool_itemsizes) != 1:
        raise ValueError(f"the state keys {harness['kv_pools']} name no KV "
                         f"pool, or pools of several types: "
                         f"{sorted(step.state['state'])[:4]}...")
    generated, prefilled = run_["ledger"].served_contexts(run_["t_start"],
                                                          run_["t_end"])
    window_flops = float(
        np.sum(work.served_token_flops(config, generated))
        + np.sum(work.served_token_flops(config, prefilled, logits=False)))
    log(f"[serve] the window's work: {len(generated)} tokens generated, "
        f"{len(prefilled)} prefilled, {window_flops / 1e12:.4f} TFLOP "
        f"({config['work']}:served_token_flops)")
    on_tpu = jax.devices()[0].platform == "tpu"
    checks.update({
        "mosaic_calls": calls == expect_calls or not on_tpu,
        "requests_completed": w["completed"] > 0,
        "every_finished_request_has_its_tokens": w["short"] == 0,
        "nothing_expired": summary["expired"] == 0,
    })
    log(f"[serve] checks {checks}")

    latency = {}  # ms; a statistic with no sample is left out
    for name, samples in (("itl", w["gaps"]), ("ttft", w["ttft"])):
        if samples:
            latency[f"{name}_p50_ms"] = float(np.median(samples)) * 1e3
            latency[f"{name}_p95_ms"] = float(np.percentile(samples, 95)) * 1e3
    end_to_end = {"serve_tokens_per_s": w["tokens"] / window_s,
                  "setup_s": setup_s,
                  **{k: v for k, v in latency.items() if "_p95_" in k}}
    log("[serve] " + ", ".join(f"{k} {v:.4f}" for k, v in
                               {**end_to_end, **latency}.items()))
    return {
        "correct": all(checks.values()),
        "compared": {
            "probe_logit_gap": {"value": probe_gap,
                                "limit": PROBE_LOGIT_ATOL},
            "probe_token_under_best": {"value": token_gap,
                                       "limit": PROBE_TOKEN_GAP},
            "mosaic_calls_in_frame": {"value": calls, "limit": expect_calls},
            "requests_short_of_their_tokens": {"value": w["short"],
                                               "limit": 0},
        },
        "attempted": w["completed"],
        "failed": w["short"],
        "end_to_end": end_to_end,
        "facts": {
            "compile_s": compile_s,
            "window_frame_seconds": run_["frames"],
            "traced_live_seq_lens": [int(n) + 1 for frame_lens in
                                     run_["traced_seq_lens"]
                                     for n in frame_lens if n > 0],
            "pool_itemsize": pool_itemsizes.pop(),
            "window_flops": window_flops, "window_s": window_s,
            "latency_ms": latency, "checks": checks,
            # the FRAME's instructions alone: the traced tail also holds
            # the prefill chunk's, under names of its own numbering
            **(device.scope_facts(frame) if trace else {}),
        },
        "trace": tracer.reduce() if tracer is not None else None,
    }
