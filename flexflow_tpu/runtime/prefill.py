"""Chunked prefill: the prompt path off the decode loop (ROADMAP
item 2 — "the serving tier's prompt path stops riding the decode
frame").

PR 10's executor admitted prompts token-by-token through the decode
graph: one full decode frame per prompt token, so TTFT paid
``len(prompt)`` frame dispatches.  This module builds the batched KV
writer: the prompt's causal forward runs once per C-token CHUNK (C a
config knob, ``FFConfig.prefill_chunk``) and scatters the chunk's K/V
directly into the sequence's page-pool pages — all of a prompt's chunks
in ONE program call, a loop over them on the device
(``build_run_forward``), so the host pays one dispatch an admission and
not one a chunk — after which the sequence
joins the decode loop at its LAST prompt token — the first generated
token still comes out of the decode graph, so the chunked path is
token-identical to the prefill-via-decode oracle (test-enforced across
ragged prompt lengths).

The chunk program is derived FROM THE DECODE GRAPH itself, not from a
separately-built prefill model: every decode-family op has a natural
C-token semantics (embeddings/dense/LN/add are position-wise;
``DecodeAttentionOp.forward_chunk`` is the prefix+causal-chunk
attention with the batched scatter), so prefill and decode trivially
share ONE parameter set — the decode model's params — and the caches
are populated under whatever sharding the strategy's
``state_shardings`` placed them with (the chunk update is a jitted
function of the placed state, so XLA keeps the pool's sharding).  The
separately-searched ``build_gpt_prefill`` graph is what the
DISAGGREGATION search places (search/disaggregation.py);
``prefill_weight_bridge`` proves its parameter set corresponds
name-for-name (and shape-for-shape) to the decode graph's, which is
what lets that placement claim a shared parameter set too (SHD165).

Positions past the prompt (the fixed-shape chunk's pad tail) are
clamped into the sequence's own page allotment: a pad write lands at a
FUTURE position, and the decode loop rewrites every position in the
frame that first reads it, so pad garbage is dead by construction — no
masking, no dynamic shapes, one compiled program per chunk size (the
run is padded to the context and its chunk count is a traced scalar).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from flexflow_tpu.core.optype import OperatorType
from flexflow_tpu.obs.annotate import PREFILL_PHASE, phase_span
from flexflow_tpu.ops.base import LoweringContext
from flexflow_tpu.ops.inout import InputOp


def run_chunked_prefill(prefill_fn: Callable, tokens: Sequence[int],
                        pages: Sequence[int], *, chunk: int, cap: int,
                        start: int = 0) -> int:
    """Drive the chunk writer over a prompt: write ``tokens[start:-1]``
    into the sequence's pages in ``ceil((len-1-start)/chunk)``
    fixed-shape chunks, all of them sent in ONE ``prefill_fn`` call (the
    decode loop then starts at the LAST token).  Returns the number of
    chunks.

    ``start`` is the prefix-sharing skip-ahead (runtime/decode.py):
    the first ``start`` tokens already live in pages the admission
    claimed from the trie (or copied on divergence), so the writer
    begins at the first divergent token — its chunk windows simply
    shift, positions stay absolute, and the already-cached pages are
    never touched.

    The call is sent a RUN of n chunks: ids and positions ``[1, n·C]``,
    the positions ``c0 … c0 + n·C − 1`` clamped at ``cap − 1``, the
    prompt's tokens first and the pad tail after them.  Each C-slice of
    the run is exactly what ``DecodeAttentionOp.forward_chunk`` states
    its contract on — the one place it is written: ONE contiguous run
    ``c0 … c0 + C − 1`` clamped at ``cap − 1`` — which is what lets the
    op write the slice page by page and walk its keys only to the
    slice's last position.  The writer runs the slices in order, slice
    i + 1 attending to slice i's K/V (``build_run_forward``).

    The call is one ``ff.phase/prefill_chunk`` span of the program's
    timeline (``obs/annotate.py``), under the ``ff.phase/serve.admit``
    of the frame that admitted the request."""
    n_pre = len(tokens) - 1
    if n_pre - start <= 0:
        return 0
    chunks = -(-(n_pre - start) // chunk)
    ids = np.zeros((1, chunks * chunk), np.int32)
    ids[0, :n_pre - start] = tokens[start:n_pre]
    pos = np.minimum(start + np.arange(chunks * chunk), cap - 1)
    table = np.asarray(pages, np.int32)[None, :]  # [1, P]
    with phase_span(PREFILL_PHASE):  # the run's dispatch
        prefill_fn(ids, pos[None, :].astype(np.int32), table)
    return chunks


def _decode_guids(graph) -> List[int]:
    return [n.guid for n in graph.topo_order()
            if n.op.op_type == OperatorType.DECODE_ATTENTION]


def prefill_io_nodes(graph) -> Tuple[int, int, int]:
    """(token_ids, page_table, seq_lens) InputOp guids of a
    decode-family graph, identified structurally from the first decode
    op's own bindings (input 1 = page_table, input 2 = seq_lens) —
    never by name."""
    dec = _decode_guids(graph)
    if not dec:
        raise ValueError("graph has no DecodeAttentionOp — not a "
                         "decode-family graph")
    by_idx = {e.dst_idx: e.src for e in graph.in_edges[dec[0]]}
    pt_guid, sl_guid = by_idx[1], by_idx[2]
    inputs = [n.guid for n in graph.topo_order()
              if isinstance(n.op, InputOp)]
    tok = [g for g in inputs if g not in (pt_guid, sl_guid)]
    if len(tok) != 1:
        raise ValueError(
            f"decode-family graph must have exactly 3 inputs "
            f"(token_ids, page_table, seq_lens); found {len(inputs)}")
    return tok[0], pt_guid, sl_guid


def build_chunk_forward(graph, compute_dtype) -> Callable:
    """A pure function ``(params, state, ids [B, C], positions [B, C],
    page_table [B, P]) -> new_state`` lowering the decode graph for a
    C-token chunk.  Position-wise ops run their ordinary ``forward``;
    the seq_lens->pos_ids reshape becomes identity (positions already
    arrive [B, C]); decode attention takes its chunk twin.  Prefill
    produces STATE, not logits: only the ops a state write depends on
    run, so nothing past the last one (the last layer's tail, the final
    LN, lm_head) is traced or handed its weights — also inside
    ``build_run_forward``'s loop, whose body XLA would not prune of
    them."""
    tok_guid, pt_guid, sl_guid = prefill_io_nodes(graph)
    dec_guids = set(_decode_guids(graph))
    needed = set()  # an op that may write state, and what it reads
    for node in reversed(graph.topo_order()):
        if (node.guid in needed or node.op.writes_state
                or getattr(node.op, "state_specs", None) is not None):
            needed.add(node.guid)
            needed.update(e.src for e in graph.in_edges[node.guid])
    topo = [n for n in graph.topo_order() if n.guid in needed]
    for node in topo:  # fail at build time, not inside the jit
        ot = node.op.op_type
        if ot == OperatorType.RESHAPE:
            srcs = {e.src for e in graph.in_edges[node.guid]}
            if srcs != {sl_guid}:
                raise NotImplementedError(
                    f"chunked prefill only supports the seq_lens "
                    f"pos_ids reshape; {node.op.name!r} reshapes "
                    f"something else")

    def fwd(params, state, ids, positions, page_table):
        ctx = LoweringContext(compute_dtype=compute_dtype, train=False,
                              state_in=state)
        values: Dict[Tuple[int, int], object] = {}
        for node in topo:
            op = node.op
            if isinstance(op, InputOp):
                values[(node.guid, 0)] = {
                    tok_guid: ids, pt_guid: page_table,
                    sl_guid: positions}[node.guid]
                continue
            edges = sorted(graph.in_edges[node.guid],
                           key=lambda e: e.dst_idx)
            ins = [values[(e.src, e.src_idx)] for e in edges]
            weights = params.get(op.name, {})
            if node.guid in dec_guids:
                outs = op.forward_chunk(ctx, ins, weights)
            elif op.op_type == OperatorType.RESHAPE:
                outs = [ins[0]]  # positions already [B, C]
            else:
                outs = op.forward(ctx, ins, weights)
            for i, y in enumerate(outs):
                values[(node.guid, i)] = y
        new_state = dict(state)
        new_state.update(ctx.state_out)
        return new_state

    return fwd


def build_run_forward(graph, compute_dtype, chunk: int) -> Callable:
    """A pure function ``(params, state, ids [B, L], positions [B, L],
    page_table [B, P], n_chunks) -> new_state`` that runs the first
    ``n_chunks`` C-slices of a run (``run_chunked_prefill``'s) in ONE
    program: a device-side loop whose trip count is traced, each pass
    ``build_chunk_forward``'s chunk on slice i, the state carried — so
    slice i + 1 attends to slice i's K/V exactly as a second call of
    the chunk would, and the state after the loop is, bit for bit, the
    state after n such calls.  The caller fixes L (a multiple of C at
    least the context), so one program serves every prompt length."""
    import jax

    chunk_fwd = build_chunk_forward(graph, compute_dtype)

    # named ``fwd``: a device trace shows the program as ``jit_fwd(..)``,
    # the module the benchmark's readers look for
    def fwd(params, state, ids, positions, page_table, n_chunks):
        def one(i, state):
            return chunk_fwd(
                params, state,
                jax.lax.dynamic_slice_in_dim(ids, i * chunk, chunk, axis=1),
                jax.lax.dynamic_slice_in_dim(positions, i * chunk, chunk,
                                             axis=1),
                page_table)

        return jax.lax.fori_loop(0, n_chunks, one, state)

    return fwd


def prefill_weight_bridge(prefill_graph, decode_graph) -> Dict[str, str]:
    """The weight-correspondence bridge: prove the separately-built
    prefill graph (models/decode.py ``build_gpt_prefill``) and the
    decode graph share ONE parameter set, weight for weight.  Returns
    ``{"prefill_op/w": "decode_op/w"}`` for every prefill weight, or
    raises ``ValueError`` naming the first break.

    The rule is name correspondence under shape agreement — the same
    rule ``weight_fold_key`` initializes by, so a bridged pair draws
    IDENTICAL values for the same seed.  One deliberate exception: the
    positional table, where the prefill graph's ``seq_len`` rows are a
    PREFIX of the decode graph's ``max_seq_len`` rows (positions are
    positions); the bridge accepts ``prefill_rows <= decode_rows`` with
    agreeing trailing dims there, and exact shape equality everywhere
    else.  The disaggregation lint (SHD165) runs this to refuse
    placements whose two blocks would not actually share parameters."""
    dec_ops = {n.op.name: n.op for n in decode_graph.topo_order()
               if n.op._weight_specs}
    # the decode side's position count — the ONLY row count the prefix
    # rule may target (a vocab mismatch must stay a hard error)
    dec_nodes = [decode_graph.nodes[g] for g in _decode_guids(decode_graph)]
    pos_rows = {n.op.max_seq_len for n in dec_nodes}
    bridge: Dict[str, str] = {}
    for node in prefill_graph.topo_order():
        op = node.op
        if not op._weight_specs:
            continue
        twin = dec_ops.get(op.name)
        if twin is None:
            raise ValueError(
                f"prefill op {op.name!r} has no same-named decode twin "
                f"— the graphs cannot share a parameter set")
        dec_ws = {w.name: w for w in twin._weight_specs}
        for ws in op._weight_specs:
            tw = dec_ws.get(ws.name)
            if tw is None:
                raise ValueError(
                    f"prefill weight {op.name}/{ws.name} missing on the "
                    f"decode twin")
            ok = tuple(ws.shape) == tuple(tw.shape)
            if not ok and len(ws.shape) == len(tw.shape) == 2 \
                    and ws.shape[1] == tw.shape[1] \
                    and ws.shape[0] <= tw.shape[0] \
                    and tw.shape[0] in pos_rows:
                # positional-table prefix rule (see docstring): only a
                # decode-side table with exactly max_seq_len rows
                # qualifies — a vocab mismatch stays a hard error
                ok = True
            if not ok:
                raise ValueError(
                    f"weight {op.name}/{ws.name} shape mismatch: "
                    f"prefill {tuple(ws.shape)} vs decode "
                    f"{tuple(tw.shape)}")
            bridge[f"{op.name}/{ws.name}"] = f"{twin.name}/{ws.name}"
    return bridge
