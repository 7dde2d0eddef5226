"""Pipeline parallelism — first-class, microbatched, over a ``pp`` mesh axis.

The reference *declares* pipeline parallelism (OP_PIPELINE,
reference: include/flexflow/ffconst.h:148, task ids model.h:184-186) but
never implements it — no pipeline.cc exists, and its DP search only
approximates inter-op parallelism by placing subgraphs on disjoint
device sets with no microbatch schedule (reference: graph.cc:180-205).
This module supplies the real thing, TPU-style.

Design (collective / looped pipeline, the idiomatic TPU formulation):
all ``S`` stages are *isomorphic* subgraphs whose parameters are stacked
along a leading stage axis sharded over the mesh's ``pp`` axis.  One
``lax.scan`` runs ``M + S - 1`` ticks; at every tick each device runs
its stage on its current microbatch and hands the activation to its ICI
neighbour via ``lax.ppermute``.  Every device computes at every tick
(modulo the (S-1)/(M+S-1) pipeline-fill bubble), activations only ever
move one hop over ICI, and the whole schedule — forward *and* the
reversed backward pass — is differentiable, so ``jax.grad`` of the
scanned program yields the classic GPipe backward schedule for free.

The pipeline shard_map is *partial-manual*: only the ``pp`` axis is
manual; data/tensor-parallel axes remain visible to GSPMD inside the
stage body, so pp composes freely with dp/tp/sp strategies.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PipelineConfig:
    """User-facing pipeline request (FFModel.compile(pipeline=...)).

    ``num_stages`` devices along the ``pp`` mesh axis each own
    ``layers/num_stages`` of the repeated block stack;
    ``num_microbatches`` must be >= num_stages to keep the bubble small
    (bubble fraction = (S-1)/(M+S-1))."""

    num_stages: int
    num_microbatches: int
    axis_name: str = "pp"


def pipeline_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_microbatches: jax.Array,
    *,
    mesh,
    axis_name: str = "pp",
    x_const: Any = None,
):
    """Run ``stage_fn`` as an S-stage circular pipeline over microbatches.

    Args:
      stage_fn: ``(params, x[, const][, mb_index]) -> y`` for ONE stage
        (arity picked by whether ``x_const`` is passed; ``mb_index`` is
        the traced index of the microbatch being processed — fold it
        into rng keys so stochastic ops draw fresh randomness per
        microbatch).  ``y`` must have ``x``'s shape/dtype (homogeneous
        stages — the transformer block case).  ``params`` keeps a
        leading *local-block* axis of size L/S (a stage owning several
        consecutive blocks scans over it).  Called under partial-manual
        shard_map: collectives over non-pp axes and GSPMD shardings
        still work inside.
      stage_params: pytree whose leaves have leading axis L (total
        blocks, L divisible by S), sharded over ``axis_name``.
      x_microbatches: [M, ...microbatch...] input, replicated over pp.
      x_const: optional pytree of per-tick-invariant side inputs passed
        through to every stage (e.g. rng keys, attention masks),
        replicated.

    Returns [M, ...microbatch...] outputs (replicated over pp).
    """
    S = mesh.shape[axis_name]
    M = x_microbatches.shape[0]

    def call_stage(p, x, const, mb_index):
        if x_const is None:
            return stage_fn(p, x, mb_index)
        return stage_fn(p, x, const, mb_index)

    if S == 1:
        return jax.lax.map(
            lambda xi: call_stage(stage_params, xi[0], x_const, xi[1]),
            (x_microbatches, jnp.arange(M)),
        )
    assert M >= 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def local(params_l, x_l, const_l):
        # params_l leaves: [L/S, ...] — this stage's block slices.
        p = params_l
        s = jax.lax.axis_index(axis_name)
        zero = jnp.zeros(x_l.shape[1:], x_l.dtype)
        outbuf = jnp.zeros((M,) + x_l.shape[1:], x_l.dtype)

        def tick(carry, t):
            recv, outbuf = carry
            mb = x_l[jnp.clip(t, 0, M - 1)]
            xin = jnp.where(s == 0, mb, recv)
            # stage s processes microbatch t - s at tick t
            mb_index = jnp.clip(t - s, 0, M - 1)
            y = call_stage(p, xin, const_l, mb_index)
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(s == S - 1, t >= S - 1)
            prev = jax.lax.dynamic_index_in_dim(outbuf, oidx, 0, keepdims=False)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(valid, y, prev), oidx, 0
            )
            recv = jax.lax.ppermute(y, axis_name, perm)
            return (recv, outbuf), None

        (_, outbuf), _ = jax.lax.scan(
            tick, (zero, outbuf), jnp.arange(M + S - 1)
        )
        # real outputs live on the last stage only; stream them down the
        # chain S-1 -> S-2 -> ... -> 0, one microbatch-chunk per tick
        # (pipelined chain broadcast).  Each link carries the N-byte
        # buffer exactly once ((S-1)·N aggregate, vs ~2(S-1)·N for a ring
        # allreduce of the masked buffer) and chunk pipelining keeps the
        # latency at ~N·(1+(S-2)/M)/BW, below the allreduce's
        # ~2N·(S-1)/S/BW for M >= 2(S-2).
        back = [(r + 1, r) for r in range(S - 1)]
        acc0 = jnp.where(s == S - 1, outbuf, jnp.zeros_like(outbuf))

        def bcast_tick(carry, t):
            acc, cur = carry
            send = jnp.where(s == S - 1, outbuf[jnp.clip(t, 0, M - 1)], cur)
            recv = jax.lax.ppermute(send, axis_name, back)
            c = t - (S - 2 - s)  # chunk arriving at this rank this tick
            valid = jnp.logical_and(s < S - 1,
                                    jnp.logical_and(c >= 0, c < M))
            cidx = jnp.clip(c, 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(acc, cidx, 0, keepdims=False)
            acc = jax.lax.dynamic_update_index_in_dim(
                acc, jnp.where(valid, recv, prev), cidx, 0
            )
            return (acc, recv), None

        (acc, _), _ = jax.lax.scan(
            bcast_tick,
            (acc0, jnp.zeros(outbuf.shape[1:], outbuf.dtype)),
            jnp.arange(M + S - 2),
        )
        return acc

    ndim_x = x_microbatches.ndim
    param_specs = jax.tree.map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stage_params
    )
    x_spec = P(*([None] * ndim_x))
    const_specs = (
        jax.tree.map(lambda a: P(*([None] * jnp.ndim(a))), x_const)
        if x_const is not None
        else None
    )
    from flexflow_tpu.comm.compat import shard_map

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec, const_specs),
        out_specs=x_spec,
        axis_names={axis_name},
    )(stage_params, x_microbatches, x_const)


def split_microbatches(x: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...] (batch must divide evenly)."""
    B = x.shape[0]
    assert B % num_microbatches == 0, (
        f"batch {B} not divisible by {num_microbatches} microbatches"
    )
    return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])


def merge_microbatches(y: jax.Array) -> jax.Array:
    """[M, B/M, ...] -> [B, ...]."""
    return y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
