"""The training driver: a configuration's model through
``FFModel.compile()`` (search included) and ``FFModel.fit()``, timed
from outside by epoch-end stamps (``fit`` reads the loss back at every
epoch end, so the device work is done by then).

Traffic kind ``train_epochs``: ``batch`` x the model's ``seq_len``
tokens a step, a dataset of ``batches_per_epoch`` distinct batches made
from the seed, ``warmup_epochs`` whole epochs before the window.  The
window starts at the last warm-up epoch's end and closes at the first
epoch end past ``seconds``; only whole epochs count.

Of the model the driver knows what the configuration's ``harness`` block
says by the harness's own names — ``vocab`` (ids are drawn below it),
``seq_len``, ``layers``, ``mosaic_calls.train_step`` (Mosaic calls the
compiled step must hold) — and the modules it names under ``reference``
and ``work``.  The builder is called with ``**builder_kwargs`` and
nothing else looks inside them.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import device, mfu_readers
from benchmarks.harness.spec import resolve_dotted, resolve_module

# bf16 compute against a float32 "highest" reference.  The step-0 loss of
# the seeded, untrained model is ln(vocab) plus a model-dependent part of
# about 3e-2 (2.5e-3 relative); bf16 rounding errors average out over the
# 4096 tokens of the batch, and the chip showed gaps of 4e-7..2e-6
# relative.  1e-4 is fifty times that and resolves 4 % of the
# model-dependent part, which a wrong mask, a missing layer or a dropped
# LayerNorm changes as a whole.
STEP0_LOSS_RTOL = 1e-4


def lm_sequence_data(num_samples: int, seq_len: int, vocab: int, seed: int):
    """(x, y) for next-token training on the rule token[j] =
    (token[j-1] * 3 + 1) mod vocab — learnable by a causal model.  The
    rule of examples/common.py, copied so the yardstick cannot move."""
    rng = np.random.default_rng(seed)
    x = np.empty((num_samples, seq_len), np.int32)
    x[:, 0] = rng.integers(0, vocab, num_samples)
    for j in range(1, seq_len):
        x[:, j] = (x[:, j - 1] * 3 + 1) % vocab
    return x, np.roll(x, -1, axis=1)


class EpochClock:
    """fit's callback: stamps every epoch end, opens the window after the
    warm-up epochs, and stops fit at the first epoch end past
    ``seconds``."""

    def __init__(self, seconds: float, warmup_epochs: int):
        self.seconds = seconds
        self.warmup_epochs = warmup_epochs
        self.stamps = []   # wall time at each epoch's end
        self.losses = []   # loss of each epoch's last step
        self.t_start = None

    def on_train_begin(self):
        pass

    def on_train_end(self):
        pass

    def on_epoch_begin(self, epoch):
        pass

    def on_epoch_end(self, epoch, logs):
        now = time.perf_counter()
        self.stamps.append(now)
        self.losses.append(float(logs["loss"]))
        if epoch + 1 == self.warmup_epochs:
            self.t_start = now
        if self.t_start is not None and now - self.t_start >= self.seconds:
            return False
        return None

    def window_epochs(self) -> np.ndarray:
        """Seconds of each whole epoch inside the window."""
        return np.diff(self.stamps[self.warmup_epochs - 1:])


def build_model(config: dict, batch: int, seed: int):
    import flexflow_tpu as ff

    ffc = ff.FFConfig(batch_size=batch, seed=seed, **config["ffconfig"])
    return resolve_dotted(config["builder"])(ffc, **config["builder_kwargs"])


def compile_for_training(model, config: dict) -> float:
    """``FFModel.compile()`` with the configuration's optimizer and loss;
    returns its seconds (search + lowering + parameter init)."""
    import flexflow_tpu as ff

    opt = dict(config["optimizer"])
    optimizer = {"adam": ff.AdamOptimizer, "sgd": ff.SGDOptimizer}[
        opt.pop("type")](**opt)
    t0 = time.perf_counter()
    model.compile(optimizer=optimizer, loss_type=config["loss"], metrics=[])
    return time.perf_counter() - t0


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        log=print) -> dict:
    import jax

    t_run = time.perf_counter()
    config, traffic = cell.config, cell.traffic
    harness = config["harness"]
    seq_len, vocab = harness["seq_len"], harness["vocab"]
    batch, nb = traffic["batch"], traffic["batches_per_epoch"]
    if traffic.get("seq_len", seq_len) != seq_len:
        raise ValueError(f"traffic seq_len {traffic['seq_len']} != the "
                         f"configuration's {seq_len}")
    reference = resolve_module(config["reference"])
    work = resolve_module(config["work"])

    model = build_model(config, batch, seed)
    compile_s = compile_for_training(model, config)
    n_params = sum(int(np.prod(w.shape)) for ws in model.params.values()
                   for w in ws.values())
    log(f"[train] compile() incl. search {compile_s:.2f}s; "
        f"{harness['layers']} layers, {n_params / 1e6:.1f} M parameters, "
        f"executor {type(model.compiled).__name__}")

    x, y = lm_sequence_data(batch * nb, seq_len, vocab, seed)
    t_data = time.perf_counter()
    # step 0 against the plain reference: same weights, same batch.  The
    # reference runs FIRST (the train step donates the parameters).
    ref_loss = float(reference.loss(model.params, x[:batch], y[:batch]))
    t_ref = time.perf_counter()
    step0 = model.fit(x=x[:batch], y=y[:batch], epochs=1, shuffle=False,
                      verbose=False)
    step0_loss = float(step0[0]["loss"])
    rel = abs(step0_loss - ref_loss) / abs(ref_loss)
    log(f"[train] step-0 loss {step0_loss:.6f}, float32 reference "
        f"{ref_loss:.6f}, relative gap {rel:.2e} (bound {STEP0_LOSS_RTOL})")

    clock = EpochClock(seconds, traffic["warmup_epochs"])
    t_fit = time.perf_counter()
    model.fit(x=x, y=y, epochs=10 ** 9, shuffle=False, verbose=False,
              callbacks=[clock])
    setup_s = clock.t_start - t_proc0
    epochs_s = clock.window_epochs()
    window_s = float(clock.stamps[-1] - clock.t_start)
    tokens = len(epochs_s) * nb * batch * seq_len
    tokens_per_s = tokens / window_s

    log(f"[train] set-up {setup_s:.2f}s: start + imports {t_run - t_proc0:.2f}, "
        f"compile() {compile_s:.2f}, data {t_data - t_run - compile_s:.2f}, "
        f"reference {t_ref - t_data:.2f}, first step (program load or "
        f"compile) {t_fit - t_ref:.2f}, warm-up epochs "
        f"{clock.t_start - t_fit:.2f}")

    # ---- after the window: the traced tail, facts, checks ---------------
    tracer = None
    if trace:
        # one more whole epoch under the profiler, so that neither the
        # tracer's start nor its slow stop falls inside the window
        tracer = device.Tracer()
        tracer.start()
        with device.annotation("bench.fit_epoch", True):
            model.fit(x=x, y=y, epochs=1, shuffle=False, verbose=False)
        tracer.stop()
    kind = jax.devices()[0].device_kind
    flops_per_token = work.trained_token_flops(config, seq_len)
    window_flops = tokens * flops_per_token
    log(f"[train] window {window_s:.3f}s, {len(epochs_s)} whole epochs x "
        f"{nb} steps x {batch * seq_len} tokens; losses "
        f"{clock.losses[0]:.4f} -> {clock.losses[-1]:.4f}")
    log(f"[train] flops/token {flops_per_token / 1e9:.4f} G "
        f"({config['work']}:trained_token_flops)")
    if jax.devices()[0].platform == "tpu":
        from benchmarks.harness.peaks import peaks_for

        peak = peaks_for(kind)["flops_bf16_per_s"]
        share = mfu_readers.share_of_peak(window_flops, window_s, cell.chips,
                                          peak)
        log(f"[train] MFU {share:.4f} = {tokens_per_s:.1f} tokens/s x "
            f"flops/token / ({cell.chips} x {peak:.3g})")
    compiled = model.compiled
    # the second compile of a program this process ran is a cache hit
    program = compiled._train_step_fn.lower(
        model.params, model.opt_state, model.state, jax.random.key(0),
        [jax.device_put(x[:batch], compiled.input_sharding(0))],
        jax.device_put(y[:batch], compiled.batch_sharding())).compile()
    calls = device.mosaic_calls(program)
    expect_calls = harness["mosaic_calls"]["train_step"]
    log(f"[train] Mosaic calls in the step: {calls} (expected {expect_calls}); "
        f"memory_analysis {device.memory_analysis_bytes(program)}; "
        f"memory_stats {device.memory_stats()}")

    losses = clock.losses
    checks = {
        "step0_loss_equals_reference": rel <= STEP0_LOSS_RTOL,
        "losses_finite": bool(np.all(np.isfinite(losses))),
        # Adam without warm-up first drives this post-LN stack's loss UP
        # (10.84 at step 0, up to 11.4 by the warm-up epoch's end), so
        # "fell" is the last epoch against the first, not against step 0
        "loss_fell": losses[-1] < losses[0],
        "mosaic_calls": (calls == expect_calls
                         or jax.devices()[0].platform != "tpu"),
        "whole_epochs_in_window": len(epochs_s) >= 1,
    }
    log(f"[train] checks {checks}")
    steps = len(epochs_s) * nb
    return {
        "correct": all(checks.values()),
        "compared": {
            "step0_loss_rel_gap": {"value": rel, "limit": STEP0_LOSS_RTOL},
            "mosaic_calls_in_step": {"value": calls, "limit": expect_calls},
        },
        "attempted": steps,
        "failed": 0 if checks["losses_finite"] else steps,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "facts": {"compile_s": compile_s, "epoch_seconds": epochs_s.tolist(),
                  "steps_per_epoch": nb, "batch": batch, "seq_len": seq_len,
                  "window_flops": window_flops,
                  "window_s": window_s, "checks": checks,
                  "traced_steps": nb if trace else 0,
                  **(device.scope_facts(program) if trace else {})},
        "trace": tracer.reduce() if tracer is not None else None,
    }
