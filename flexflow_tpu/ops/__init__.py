"""Operator library: every dense op of the reference's src/ops/* with
TPU-native lowering (see ops.base for the contract)."""

from flexflow_tpu.ops.base import (
    LoweringContext,
    Operator,
    OpSharding,
    OP_REGISTRY,
    ShardAnnot,
    WeightSpec,
    register_op,
)
from flexflow_tpu.ops.inout import ConstantOp, InputOp, NoOp
from flexflow_tpu.ops.elementwise import ElementBinaryOp, ElementUnaryOp
from flexflow_tpu.ops.linear import LinearOp
from flexflow_tpu.ops.shape_ops import (
    CastOp,
    ConcatOp,
    FlatOp,
    ReshapeOp,
    ReverseOp,
    SplitOp,
    TransposeOp,
)
from flexflow_tpu.ops.norm import (
    BatchNormOp,
    DropoutOp,
    LayerNormOp,
    RMSNormOp,
    SoftmaxOp,
)
from flexflow_tpu.ops.conv import Conv2DOp, Pool2DOp
from flexflow_tpu.ops.embedding import EmbeddingOp
from flexflow_tpu.ops.attention import BatchMatmulOp, MultiHeadAttentionOp
from flexflow_tpu.ops.decode_attention import (
    DecodeAttentionOp,
    GroupedDecodeAttentionOp,
    SparseDecodeAttentionOp,
)
from flexflow_tpu.ops.reductions import GatherOp, MeanOp, TopKOp
from flexflow_tpu.ops.latent_attention import LatentAttentionOp
from flexflow_tpu.ops.moe import (
    AggregateOp,
    AggregateSpecOp,
    CacheOp,
    ExpertCombineOp,
    ExpertDispatchOp,
    ExpertLinearOp,
    GroupByOp,
    MoERouterOp,
)
from flexflow_tpu.ops.exit_loss import ExitLossOp
from flexflow_tpu.ops.mtp import NextTokenLossOp, ShiftOp

__all__ = [
    "LoweringContext",
    "Operator",
    "OpSharding",
    "OP_REGISTRY",
    "ShardAnnot",
    "WeightSpec",
    "register_op",
    "ConstantOp",
    "InputOp",
    "NoOp",
    "ElementBinaryOp",
    "ElementUnaryOp",
    "LinearOp",
    "CastOp",
    "ConcatOp",
    "FlatOp",
    "ReshapeOp",
    "ReverseOp",
    "SplitOp",
    "TransposeOp",
    "BatchNormOp",
    "DropoutOp",
    "LayerNormOp",
    "RMSNormOp",
    "LatentAttentionOp",
    "MoERouterOp",
    "ExpertDispatchOp",
    "ExpertLinearOp",
    "ExpertCombineOp",
    "ShiftOp",
    "NextTokenLossOp",
    "ExitLossOp",
    "SoftmaxOp",
    "Conv2DOp",
    "Pool2DOp",
    "EmbeddingOp",
    "BatchMatmulOp",
    "DecodeAttentionOp",
    "GroupedDecodeAttentionOp",
    "SparseDecodeAttentionOp",
    "MultiHeadAttentionOp",
    "GatherOp",
    "MeanOp",
    "TopKOp",
    "AggregateOp",
    "AggregateSpecOp",
    "CacheOp",
    "GroupByOp",
]
