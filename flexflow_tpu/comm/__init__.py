"""Collective-communication subsystem.

Two roles:

* ``compat`` — ``jax.shard_map`` with the replication checker off by
  default, and the virtual-CPU-mesh switch.  Every explicit-SPMD
  lowering in the tree imports shard_map from here.
* ``quantized`` — EQuARX-style compressed gradient collectives
  (arXiv:2506.17615): per-chunk-scaled int8 (and bf16) quantize →
  reduce-scatter → requantize → all-gather, with an exact-fp32 psum
  fallback and an error-bound unit contract.  The search prices these
  (search/machine_model.py ``allreduce(precision=...)``) and the
  lowering executes them (compiler/lowering.py ``_sync_grads``).
* ``bucketed`` — the searched gradient-sync SCHEDULE's executor
  (search/sync_schedule.py): member grads of a bucket flatten into one
  fused wire payload, buckets chain through ``optimization_barrier``
  so collectives issue in backward grad-readiness order (overlap-aware
  bucketed sync; GSPMD async collectives, arXiv:2105.04663).
* ``hierarchical`` — staged execution of the searched reduction PLANs
  (search/reduction_plan.py) on multi-slice topologies: exact fp32
  reduce-scatter/all-gather within each slice around a compressed
  cross-slice exchange (arXiv:2110.10548's staged shape).
"""

from flexflow_tpu.comm.bucketed import bucketed_grad_sync
from flexflow_tpu.comm.compat import force_cpu_devices, shard_map
from flexflow_tpu.comm.hierarchical import (
    plan_axis_groups,
    staged_allreduce,
)
from flexflow_tpu.comm.quantized import (
    DEFAULT_CHUNK,
    MIN_COMPRESS_ELEMS,
    SYNC_PRECISIONS,
    allreduce_error_bound,
    dequantize_chunked,
    quantize_chunked,
    quantized_allreduce,
    quantized_allreduce_ef,
    quantized_grad_sync,
    replication_axes,
)

__all__ = [
    "DEFAULT_CHUNK",
    "MIN_COMPRESS_ELEMS",
    "SYNC_PRECISIONS",
    "allreduce_error_bound",
    "bucketed_grad_sync",
    "dequantize_chunked",
    "force_cpu_devices",
    "plan_axis_groups",
    "quantize_chunked",
    "staged_allreduce",
    "quantized_allreduce",
    "quantized_allreduce_ef",
    "quantized_grad_sync",
    "replication_axes",
    "shard_map",
]
