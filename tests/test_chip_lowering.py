"""What the CPU can prove about the chip path before a chip is spent.

``chip_smoke.py`` is the proof that the system runs on the TPU; these
are the refusals it met on the way, held down from the CPU: every
Pallas entry cross-lowers for the TPU (and, where libtpu serves a
compile-only topology, compiles under Mosaic) at the smoke's shapes;
the flash kernel lowers under ``shard_map`` on a multi-device mesh; no
catch-all hides a kernel failure; the smoke refuses to run without a
TPU; the compile cache lands where it was placed; and the searched
multi-chip GPT is still the model the user built.  Named to sort early:
the suite is cut off by a wall clock.
"""

import ast
import collections
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the package re-exports functions under the modules' own names
fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
rpa = importlib.import_module("flexflow_tpu.kernels.ragged_paged_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MOSAIC = "tpu_custom_call"
S = jax.ShapeDtypeStruct

# chip_smoke.py's shapes: default-width GPT attention at batch 8, and
# the GPT_DECODE_SERVE_KW frame (32 slots, 8 heads x 64, page 32 x 128)
QKV = S((8, 1024, 12, 64), jnp.bfloat16)
BQ, BK = 512, 1024
B, H, D, PAGE, PPS = 32, 8, 64, 32, 128
SCALE = 0.125


def _flash_entries():
    lse = S((8 * 12, 1024, 1), jnp.float32)
    return {
        "flash_fwd": (
            lambda q, k, v: fa._flash_forward(
                q, k, v, True, SCALE, BQ, BK, False, save_lse=True),
            (QKV, QKV, QKV)),
        "flash_dq_dkv": (
            lambda q, k, v, o, lse, do: fa._flash_backward(
                q, k, v, o, lse, do, True, SCALE, BQ, BK, False),
            (QKV, QKV, QKV, QKV, lse, QKV)),
        "flash_partial": (
            lambda q, k, v: fa._flash_forward_partial(
                q, k, v, True, SCALE, BQ, BK, False),
            (QKV, QKV, QKV)),
    }


def _paged_entries():
    """The ragged kernel at the smoke's frame and, ``*_cell``, at the
    serving cell's (16 slots, 16 heads x 64, page 32 x 32 pages a
    sequence), over fp32, bf16 and int8 pools."""
    def call(q, k, v, t, n, *scales):
        return rpa._pallas_ragged_paged(q, k, v, t, n, SCALE, False, *scales)

    out = {}
    for tag, (b, h, pps) in (("", (B, H, PPS)), ("_cell", (16, 16, 32))):
        q = S((b, h, D), jnp.float32)
        table, lens = S((b, pps), jnp.int32), S((b,), jnp.int32)
        scales = S((b * pps, PAGE), jnp.float32)
        for name, dt in (("fp32", jnp.float32), ("bf16", jnp.bfloat16),
                         ("int8", jnp.int8)):
            pool = S((b * pps, PAGE, h * D), dt)
            out[f"paged_{name}{tag}"] = (
                call, (q, pool, pool, table, lens)
                + ((scales, scales) if name == "int8" else ()))
    return out


ENTRIES = {**_flash_entries(), **_paged_entries()}
# Mosaic calls each entry must hold (the backward is dq + dkv)
CALLS = {name: 2 if name == "flash_dq_dkv" else 1 for name in ENTRIES}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_pallas_entry_cross_lowers_for_tpu(name):
    """The Pallas→Mosaic lowering (block-shape rule, scalar prefetch,
    kernel body) accepts the entry with interpret mode OFF."""
    fn, args = ENTRIES[name]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count(MOSAIC) == CALLS[name], name


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a compile-only v5e topology — libtpu serves it
    with no hardware.  Skips where it cannot."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu / no compile-only support here
        pytest.skip(f"no compile-only TPU topology: {e!r}")
    return topo.devices[0]


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_pallas_entry_compiles_under_mosaic(name, v5e_device):
    """The chip's own compiler (Mosaic inside XLA:TPU) takes the entry:
    what cross-lowering cannot see — layouts, relayouts, VMEM/SMEM
    budgets."""
    fn, args = ENTRIES[name]
    sh = jax.sharding.SingleDeviceSharding(v5e_device)
    args = [S(a.shape, a.dtype, sharding=sh) for a in args]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count(f'custom_call_target="{MOSAIC}"') == CALLS[name]


def test_flash_kernels_compile_at_192_beside_128(v5e_device):
    """Latent attention's head widths — query/key 192, value 128 — bf16,
    the blocks the training cell uses: Mosaic takes the forward, dq and
    dkv kernels as they are, nothing padded."""
    import math

    b, s, h, d, d_v = 1, 1024, 2, 192, 128
    sh = jax.sharding.SingleDeviceSharding(v5e_device)

    def spec(width):
        return S((b, s, h, width), jnp.bfloat16, sharding=sh)

    def fwd_bwd(q, k, v, g):
        scale = 1.0 / math.sqrt(d)
        out, lse = fa._flash_forward(q, k, v, True, scale, 512, 1024, False,
                                     save_lse=True)
        return fa._flash_backward(q, k, v, out, lse, g, True, scale, 512,
                                  1024, False)

    hlo = jax.jit(fwd_bwd).lower(spec(d), spec(d), spec(d_v),
                                 spec(d_v)).compile().as_text()
    assert hlo.count(f'custom_call_target="{MOSAIC}"') == 3


def test_grouped_expert_products_compile_as_kernels_at_the_cells_widths(
        v5e_device):
    """``ExpertLinearOp`` on rows sorted by expert is ``jax.lax.ragged_dot``;
    XLA:TPU runs it as Mosaic kernels of its own (it shows as
    ``ragged-dot-*`` in a trace).  One gated expert block at the training
    cell's widths, forward and backward: 3 + 6 products and two metadata
    calls — the 11 an expert block adds to ``harness.mosaic_calls`` in
    benchmarks/configs/joyai-llm-flash-train.json."""
    from flexflow_tpu.core.ptensor import ParallelTensorShape
    from flexflow_tpu.ops import ExpertLinearOp, LoweringContext

    rows, held, d, ff = 8192, 16, 2048, 768
    sh = jax.sharding.SingleDeviceSharding(v5e_device)
    sizes_shape = ParallelTensorShape.make((held,), "int32")

    def op(name, width, out, activation=None):
        return ExpertLinearOp(
            name, [ParallelTensorShape.make((rows, width), "float32"),
                   sizes_shape], out_dim=out, activation=activation)

    gate, up, down = op("g", d, ff, "silu"), op("u", d, ff), op("w", ff, d)
    ctx = LoweringContext(compute_dtype=jnp.bfloat16, train=True, state_in={})

    def block(x, sizes, wg, wu, wd):
        h = (gate.forward(ctx, [x, sizes], {"kernel": wg})[0]
             * up.forward(ctx, [x, sizes], {"kernel": wu})[0])
        return jnp.sum(down.forward(ctx, [h, sizes], {"kernel": wd})[0] ** 2)

    hlo = jax.jit(jax.grad(block, argnums=(0, 2, 3, 4))).lower(
        S((rows, d), jnp.float32, sharding=sh),
        S((held,), jnp.int32, sharding=sh),
        S((held, d, ff), jnp.float32, sharding=sh),
        S((held, d, ff), jnp.float32, sharding=sh),
        S((held, ff, d), jnp.float32, sharding=sh)).compile().as_text()
    assert hlo.count(f'custom_call_target="{MOSAIC}"') == 9 + 2


def test_remat_by_block_shrinks_a_looped_models_temporaries(v5e_device):
    """A looped model (one stack run four times over tied weights)
    compiled for the described v5e: recomputation by block
    (``FFModel.remat_block``) holds little more than the residual stream
    at each block's edge.  Recomputation per op does NOT shrink this
    model (every large activation of a gated feed-forward is some
    weighted op's input, which a per-op checkpoint saves): at the
    cell's size it read 17.15 GB against 17.05 GB without and 12.70 GB
    by block (PERF.md section 4) — that it covers the tied ops at all
    is held by tests/test_ouro.py."""
    import flexflow_tpu as ff
    from flexflow_tpu.models import build_ouro

    kw = dict(vocab=256, num_layers=2, hidden=256, num_heads=2, head_dim=128,
              ff_dim=512, loop_steps=4, seq_len=256)
    sh = jax.sharding.SingleDeviceSharding(v5e_device)
    temps = {}
    for name, remat in (("none", False), ("op", True), ("block", True)):
        cfg = ff.FFConfig(batch_size=4, compute_dtype="bfloat16",
                          num_devices=1, cost_cache_file="", remat=remat)
        model = build_ouro(cfg, **kw)
        if name == "op":
            for node in model.graph.nodes.values():
                node.op.remat_block = None
        model.compile(optimizer=ff.AdamOptimizer(alpha=1e-3),
                      loss_type="sparse_categorical_crossentropy", metrics=[])
        compiled = model.compiled

        def loss(params, ids):
            logits, state = compiled.apply(params, model.state, [ids], None,
                                           train=True)
            return compiled._loss_from(logits, ids, state)

        params = jax.tree.map(
            lambda a: S(a.shape, a.dtype, sharding=sh), model.params)
        program = jax.jit(jax.grad(loss)).lower(
            params, S((4, 256), jnp.int32, sharding=sh)).compile()
        temps[name] = program.memory_analysis().temp_size_in_bytes
    assert temps["block"] < 0.5 * min(temps["op"], temps["none"]), temps


def _pool_sized_producers(hlo: str, pool_elems: int):
    """HLO instructions of a compiled program whose result is an array
    of at least a pool leaf's element count, as (opcode, jax op name)."""
    found = []
    for line in hlo.splitlines():
        m = re.search(
            r"= [a-z0-9]+\[([0-9,]+)\][^ ]* ([a-z][a-z0-9-]*)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= pool_elems:
            name = re.search(r'op_name="([^"]*)"', line)
            found.append((m.group(2), name.group(1) if name else ""))
    return found


def test_decode_frame_and_chunk_update_the_pool_in_place(
        v5e_device, monkeypatch):
    """THE structural guard of the in-place KV pool: at the serving
    cell's head geometry (16 x 64, page 32, 16 slots x 32 pages) the
    frame and the prefill program (a loop over a prompt's chunks),
    compiled for the described v5e, alias every pool leaf to its
    output, hold no copy / transpose / convert (or any other op but the
    scatter's in-place update) that produces a pool-sized array, and
    need less than one pool leaf of temporaries — the loop no more than
    the program of one chunk.  A [P, page, H, 64] pool failed all three: XLA:TPU
    stores it page-minor and transposes it in and out on every call."""
    import flexflow_tpu as ff
    from flexflow_tpu.models import build_gpt_decode
    from flexflow_tpu.runtime.decode import compiled_decode_step
    from flexflow_tpu.runtime.prefill import build_chunk_forward

    slots, chunk, layers = 16, 64, 2
    kw = dict(vocab=512, num_layers=layers, hidden=1024, num_heads=16,
              ff_dim=256, page_size=32, pages_per_seq=32)
    cfg = ff.FFConfig(batch_size=slots, num_devices=1, cost_cache_file="")
    model = build_gpt_decode(cfg, **kw)
    model.compile(loss_type="sparse_categorical_crossentropy", metrics=[],
                  comp_mode="inference")
    step = compiled_decode_step(model, prefill_chunk=chunk)
    assert step.attention_path == "pallas"

    sh = jax.sharding.SingleDeviceSharding(v5e_device)

    def described(tree):
        return jax.tree.map(
            lambda a: S(a.shape, a.dtype, sharding=sh), tree)

    def ints(*shape):
        return S(shape, jnp.int32, sharding=sh)

    state = described(model.state)
    pools = sorted(k for k in state if k.endswith(("/k_cache", "/v_cache")))
    assert len(pools) == 2 * layers
    leaf = state[pools[0]]
    assert leaf.shape == (slots * 32, 32, 16 * 64)
    # the kernel picks interpreter mode off-TPU by the default backend;
    # this compile is FOR the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the programs the server runs take ``step.weights``; the same two
    # over ``model.params`` (the fp32 tree, converted in the call) are
    # what the harness and the smoke lower for their Mosaic count
    programs = {}
    for tag, tree in (("", step.weights), (" over model.params",
                                           model.params)):
        params = described(tree)
        programs["frame" + tag] = step.frame_fn.lower(
            params, state, [ints(slots, 1), ints(slots, 32), ints(slots)])
        # the prefill program: a prompt's chunks in one loop, over runs
        # padded to the context (32 chunks of 64), the count traced
        programs["chunk" + tag] = step.chunk_fn.lower(
            params, state, ints(1, 1024), ints(1, 1024), ints(1, 32),
            ints())
    pool_elems = int(np.prod(leaf.shape))
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        # an fp32 matmul weight is read (and converted) only where the
        # program was handed the fp32 tree
        assert ("f32[1024,16,64]" in hlo) == name.endswith("model.params")
        # the compiled program numbers only the arguments it kept (the
        # chunk reads no lm_head): find the pool leaves by their type
        entry = hlo[hlo.index("ENTRY "):]
        pool_type = f"f32[{','.join(map(str, leaf.shape))}]"
        pool_params = {int(i) for i in re.findall(
            re.escape(pool_type) + r"\S* parameter\((\d+)\)", entry)}
        assert len(pool_params) == len(pools), (name, pool_params)
        alias = hlo[hlo.index("input_output_alias={"):]
        alias = alias[:alias.index("\n")]
        aliased = {int(i) for i in re.findall(r"\((\d+), \{\}", alias)}
        assert pool_params <= aliased, (name, pool_params, alias[:400])
        # what may produce a pool-sized array: the argument itself and
        # the scatter's in-place update of it (a fusion around it on
        # the TPU) — by row in the frame, by page in the chunk, whose
        # ``cond`` (a contiguous run or not) and key-block ``while``
        # hand the pool on as a tuple's element.  A convert here would
        # be XLA:TPU retyping the loop's pool operand to bf16 (the
        # chunk's attention rounds the block it gathered, not the pool)
        made = [m for m in _pool_sized_producers(hlo, pool_elems)
                if m[0] not in ("parameter", "get-tuple-element")]
        assert made and all(op in ("scatter", "fusion") and jax_op.endswith("/scatter")
                   for op, jax_op in made), (name, made)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < pool_elems * leaf.dtype.itemsize, (name, temp)
        if name.startswith("frame"):
            assert hlo.count(f'custom_call_target="{MOSAIC}"') == layers
        if name == "chunk":
            # the loop over a prompt's chunks holds what ONE chunk's
            # program holds: arguments and temporaries within 1 %
            one = jax.jit(build_chunk_forward(
                model.graph, model.compiled.compute_dtype),
                donate_argnums=(1,)).lower(
                    described(step.weights), state, ints(1, chunk),
                    ints(1, chunk), ints(1, 32)).compile()
            held = [c.memory_analysis() for c in (compiled, one)]
            loop_b, one_b = (m.argument_size_in_bytes + m.temp_size_in_bytes
                             for m in held)
            assert loop_b <= 1.01 * one_b, (loop_b, one_b)


def test_sharded_flash_lowers_and_matches_on_cpu_mesh(mesh8, monkeypatch):
    """GSPMD refuses a bare Mosaic call on a multi-device mesh; under
    shard_map (batch over two axes, heads over the third) the wrapper
    lowers for the TPU with the kernel inside, and on the CPU mesh its
    values and gradients equal the unsharded kernel's."""
    batch_axes, head_axes = mesh8.axis_names[:2], mesh8.axis_names[2:]

    def sharded(q, k, v):
        return fa.flash_attention_sharded(
            q, k, v, mesh8, batch_axes=batch_axes, head_axes=head_axes,
            causal=True)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 512, 2, 16)), jnp.float32)
               for _ in range(3))
    plain = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)  # noqa: E731
    np.testing.assert_allclose(jax.jit(sharded)(q, k, v), plain(q, k, v),
                               atol=1e-5)
    got = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2))).trace(
        QKV, QKV, QKV).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count(MOSAIC) == 3  # fwd + dq + dkv, inside shard_map


def _broad(handler: ast.ExceptHandler) -> bool:
    types = (handler.type.elts if isinstance(handler.type, ast.Tuple)
             else [handler.type])
    return handler.type is None or any(
        isinstance(t, ast.Name) and t.id in ("Exception", "BaseException")
        for t in types)


def _catch_alls_around_kernel_calls(repo):
    roots = [os.path.join(repo, "flexflow_tpu"),
             os.path.join(repo, "examples")]
    files = [os.path.join(repo, f) for f in os.listdir(repo)
             if f.endswith(".py")]
    for root in roots:
        for d, _dirs, names in os.walk(root):
            files += [os.path.join(d, f) for f in names if f.endswith(".py")]
    bad = []
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        kernel_names = {
            a.asname or a.name
            for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
            and (n.module or "").startswith("flexflow_tpu.kernels")
            for a in n.names}
        if os.path.basename(os.path.dirname(path)) == "kernels":
            # inside the package every function of the module counts
            kernel_names |= {n.name for n in tree.body
                             if isinstance(n, ast.FunctionDef)}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Try)
                    and any(_broad(h) for h in node.handlers)):
                continue
            for inner in (x for stmt in node.body for x in ast.walk(stmt)):
                hit = (
                    isinstance(inner, ast.ImportFrom)
                    and (inner.module or "").startswith(
                        "flexflow_tpu.kernels")
                ) or (
                    isinstance(inner, ast.Call)
                    and isinstance(inner.func, ast.Name)
                    and inner.func.id in kernel_names)
                if hit:
                    bad.append(f"{os.path.relpath(path, repo)}:"
                               f"{inner.lineno}")
    return bad


def test_no_catch_all_around_a_kernel_call():
    """A kernel is chosen by a visible rule (shape, mesh size), never by
    a caught exception: no bare ``except`` / ``except Exception`` may
    enclose an import of, or a call into, ``flexflow_tpu.kernels``."""
    bad = _catch_alls_around_kernel_calls(REPO)
    assert not bad, "catch-all around a kernel call: " + ", ".join(bad)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=REPO)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout  # no result line


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=tmp_path)
    assert r.returncode != 0
    assert "flexflow_tpu" in r.stderr and r.stdout.strip() == ""


def test_chip_smoke_result_line_is_exactly_the_contract(monkeypatch, capsys):
    """The last line of stdout is ``{"ok", "device": {"platform", "kind",
    "count"}}`` and nothing else; the facts ride the line before it.  The
    legs are stubbed — the line's shape is what is under test."""
    import types

    from flexflow_tpu.runtime import compile_cache

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                 memory_stats=lambda: collections.defaultdict(int))
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    monkeypatch.setattr(compile_cache, "place_compile_cache", lambda: "/x")
    monkeypatch.setattr(chip_smoke, "train_leg",
                        lambda: ({"losses": [2.0, 1.0]}, {}))
    monkeypatch.setattr(chip_smoke, "serve_leg", lambda: ({}, {}))
    want = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": want}
    assert json.loads(lines[-2])["train"]["losses"] == [2.0, 1.0]

    def failing_leg():
        raise RuntimeError("a leg failed")

    monkeypatch.setattr(chip_smoke, "serve_leg", failing_leg)
    with pytest.raises(RuntimeError, match="a leg failed"):
        chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": False, "device": want}


def test_compile_cache_is_placed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is touched; unset, the
    cache is the fixed in-checkout directory."""
    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    updates = []  # recorded, not applied: the suite runs without a cache
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert place_compile_cache() == "/x" and updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert place_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_searched_multichip_gpt_is_the_model_the_user_built():
    """On four chips the search solves one transformer layer and stamps
    the solution onto its eleven siblings.  The stamped graph must keep
    every layer's OWN ops: the lowering keys weights by op name, and a
    stamp that carried the donor's names trained a weight-tied model
    (found by chip_smoke's four-chip loss trajectory)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models import build_gpt
    from flexflow_tpu.search.driver import optimize_strategy

    cfg = ff.FFConfig(batch_size=8, num_devices=4, cost_cache_file="")
    model = build_gpt(cfg)
    weighted = sorted(n.op.name for n in model.graph.nodes.values()
                      if n.op._weight_specs)
    graph, strategy = optimize_strategy(model.graph, cfg, return_graph=True)
    assert any(mv.num_parts > 1 for mv in strategy.values())
    names = [n.op.name for n in graph.nodes.values()]
    assert len(names) == len(set(names)), "duplicate op names after search"
    assert sorted(n.op.name for n in graph.nodes.values()
                  if n.op._weight_specs) == weighted
    by_name = {n.op.name: n.op for n in model.graph.nodes.values()}
    assert all(n.op is by_name[n.op.name] for n in graph.nodes.values()
               if n.op.name in by_name)


def test_bench_names_its_device_and_refuses_an_unknown_chip():
    """bench.py is one process: a CPU dry run is labelled as one (own
    metric name, platform cpu, no MFU) and no peak is ever assumed."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert "TPU v5 lite" in bench.PEAK_BF16_FLOPS
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "subprocess" not in src and "LASTGOOD" not in src
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=240,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert "mfu" not in rec and rec["metric"].endswith("cpu_dry_run")
