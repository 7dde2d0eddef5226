"""Serving-phase zoo: prefill + single-token decode variants of the
GPT configs (ROADMAP item 4 — "open the inference/serving workload").

A serving step has two phases with OPPOSITE cost shapes:

* **prefill** — the prompt's full forward pass: compute-bound causal
  attention over the whole prompt, exactly the training-side GPT graph
  minus the loss.  ``build_gpt_prefill`` reuses the causal encoder
  stack (models/transformer.py) so the strategy search prices it with
  everything it already knows (flash attention, ring/ulysses SP).
* **decode** — one token per live sequence per step: memory-bound
  streaming of the RAGGED paged KV cache.  ``build_gpt_decode`` builds
  the decode-frame graph whose attention ops are
  ``DecodeAttentionOp`` — explicit KV-cache state (page-pool indexed),
  ``page_table``/``seq_lens`` frame inputs, ragged paged attention
  kernel lowering.

The decode graph's batch dim is the frame's SEQUENCE-SLOT count
(``max_seqs``), fixed so the compiled program never re-specializes;
the continuous-batching executor (runtime/decode.py) composes ragged
requests into frames of this exact shape.
"""

from __future__ import annotations

from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel

# the canonical small decode config the executor tests lower and run
# on the CPU mesh: 2 layers deep enough to exercise cache state
# threading, small enough to compile in seconds
GPT_DECODE_KW = dict(vocab=2048, num_layers=2, hidden=256, num_heads=8,
                     ff_dim=512, page_size=16, pages_per_seq=16)

# the serving-regime decode config the serve bench + objective tests
# SEARCH (never lowered on the CPU mesh): long caches at modest width,
# the window where the ragged-KV stream dominates the step — per
# sequence 4096 cached tokens x 4 KB/token, 32-slot frames = 1 GB of
# pool per layer — so the batch-split max-shard imbalance the serve
# objective prices is the first-order term, while the weight stream
# (4 MB/layer of projections) is small enough that the train (mean
# step) objective still prefers the pure batch split.  This is the
# configuration where throughput and p99 provably part ways
# (tests/test_serving.py, simulated).
GPT_DECODE_SERVE_KW = dict(vocab=4096, num_layers=2, hidden=512,
                           num_heads=8, ff_dim=1024, page_size=32,
                           pages_per_seq=128)
SERVE_FRAME_SLOTS = 32  # config.batch_size the serve sweep uses


def decode_layer(model, t, page_table, seq_lens, hidden, num_heads,
                 ff_dim, name, page_size, pages_per_seq, num_pages=0,
                 layer_norm=True, use_kernel=True):
    """One decode-step transformer layer: paged-cache attention +
    residual + LN + FFN (the decode twin of transformer.encoder_layer,
    which this must mirror so prefill/decode weights correspond
    layer-for-layer)."""
    a = model.decode_attention(
        t, page_table, seq_lens, embed_dim=hidden, num_heads=num_heads,
        page_size=page_size, pages_per_seq=pages_per_seq,
        num_pages=num_pages, use_kernel=use_kernel, name=f"{name}_mha",
    )
    t = model.add(a, t, name=f"{name}_res1")
    if layer_norm:
        t = model.layer_norm(t, name=f"{name}_ln1")
    f = model.dense(t, ff_dim, activation="relu", name=f"{name}_ff1")
    f = model.dense(f, hidden, name=f"{name}_ff2")
    t = model.add(f, t, name=f"{name}_res2")
    if layer_norm:
        t = model.layer_norm(t, name=f"{name}_ln2")
    return t


def build_gpt_decode(config: FFConfig, vocab: int = 2048,
                     num_layers: int = 2, hidden: int = 256,
                     num_heads: int = 8, ff_dim: int = 512,
                     page_size: int = 16, pages_per_seq: int = 16,
                     num_pages: int = 0, use_kernel: bool = True):
    """The single-token decode-step graph: token ids [B, 1] -> next-token
    logits [B, 1, vocab], where B = config.batch_size is the decode
    frame's sequence-slot count (max concurrent sequences).

    Inputs, in binding order: ``token_ids`` [B, 1] i32, ``page_table``
    [B, pages_per_seq] i32, ``seq_lens`` [B] i32.  Every layer's
    attention reads/writes its OWN page-pool KV cache (model state);
    all layers share one page-table geometry, so one allocator serves
    the whole stack.  ``use_kernel=False`` lowers every layer's
    attention through the XLA gather path instead of the Pallas kernel
    — the reference a kernel run is compared against."""
    model = FFModel(config)
    b = config.batch_size
    ids = model.create_tensor([b, 1], dtype="int32", name="token_ids")
    page_table = model.create_tensor([b, pages_per_seq], dtype="int32",
                                     name="page_table")
    seq_lens = model.create_tensor([b], dtype="int32", name="seq_lens")
    t = model.embedding(ids, vocab, hidden, aggr="none", name="tok_embed")
    # learned positional embedding indexed by the token's position
    # (= seq_lens): the decode twin of build_gpt's positional table
    pos = model.reshape(seq_lens, [b, 1], name="pos_ids")
    p = model.embedding(pos, page_size * pages_per_seq, hidden,
                        aggr="none", name="pos_embed")
    t = model.add(t, p, name="embed_sum")
    for i in range(num_layers):
        t = decode_layer(
            model, t, page_table, seq_lens, hidden, num_heads, ff_dim,
            f"layer{i}", page_size=page_size, pages_per_seq=pages_per_seq,
            num_pages=num_pages, layer_norm=True, use_kernel=use_kernel,
        )
    t = model.layer_norm(t, name="final_ln")
    t = model.dense(t, vocab, use_bias=False, name="lm_head")
    return model


def build_gpt_prefill(config: FFConfig, vocab: int = 2048,
                      num_layers: int = 2, hidden: int = 256,
                      num_heads: int = 8, ff_dim: int = 512,
                      seq_len: int = 256):
    """The prompt-phase graph: the causal GPT forward at prompt length
    (compute-bound, seq-parallelizable — the training-side strategy
    machinery applies unchanged).  Searched under
    ``comp_mode="inference"`` it ranks by forward latency.  Cache
    POPULATION runs through the chunked-prefill lane
    (runtime/prefill.py): the prompt's causal forward once per chunk,
    K/V scattered straight into the page pool, token-identical to the
    prefill-via-decode fallback.  This graph is also what the
    DISAGGREGATION search places on its own submesh
    (search/disaggregation.py) — ``prefill_weight_bridge`` proves its
    parameter set corresponds weight-for-weight to the decode
    graph's."""
    from flexflow_tpu.models.transformer import build_gpt

    return build_gpt(config, vocab=vocab, num_layers=num_layers,
                     hidden=hidden, num_heads=num_heads, ff_dim=ff_dim,
                     seq_len=seq_len)


def derive_prefill_model(decode_graph, config, seq_len: int):
    """Build the prefill twin of an existing DECODE graph by reading
    the family widths off the graph itself (vocab/hidden from the
    token embedding, heads/embed from the decode ops, ff width from
    the FFN denses) — the disaggregation search derives the prompt
    graph it places from the deployment's own decode graph instead of
    trusting a caller to pass a matching one.  Returns ``(model,
    prefill_config)``; the prefill config prices one prompt at a time
    (batch 1 — the chunked lane's per-sequence pass), everything else
    inherited.  ``prefill_weight_bridge`` (runtime/prefill.py) then
    proves the two graphs share one parameter set."""
    import dataclasses

    from flexflow_tpu.core.optype import OperatorType
    from flexflow_tpu.runtime.prefill import prefill_io_nodes

    tok_guid, _, _ = prefill_io_nodes(decode_graph)
    dec_ops = [n.op for n in decode_graph.topo_order()
               if n.op.op_type == OperatorType.DECODE_ATTENTION]
    tok_embed = next(
        n.op for n in decode_graph.topo_order()
        if n.op.op_type == OperatorType.EMBEDDING
        and any(e.src == tok_guid
                for e in decode_graph.in_edges[n.guid]))
    vocab = tok_embed.attrs["num_entries"]
    hidden = tok_embed.attrs["out_dim"]
    first = dec_ops[0]
    num_heads = first.attrs["num_heads"]
    # ff1 is the dense that feeds another dense DIRECTLY (ff1 -> ff2);
    # out_dim sets can't disambiguate it — ff_dim may collide with
    # vocab or hidden
    ff_dim = hidden
    for n in decode_graph.topo_order():
        if n.op.op_type != OperatorType.LINEAR:
            continue
        feeds_dense = any(
            decode_graph.nodes[e.dst].op.op_type == OperatorType.LINEAR
            for e in decode_graph.out_edges[n.guid])
        if feeds_dense:
            ff_dim = n.op.attrs["out_dim"]
            break
    cfg = dataclasses.replace(config, batch_size=1)
    model = build_gpt_prefill(
        cfg, vocab=vocab, num_layers=len(dec_ops), hidden=hidden,
        num_heads=num_heads, ff_dim=ff_dim, seq_len=seq_len)
    return model, cfg
