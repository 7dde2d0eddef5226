"""Model zoo — TPU-native builds of every model family the reference
ships as examples (reference: examples/cpp/*, SURVEY.md §2.6)."""

from flexflow_tpu.models.alexnet import build_alexnet, build_alexnet_cifar10
from flexflow_tpu.models.resnet import build_resnet, build_resnext50
from flexflow_tpu.models.inception import build_inception_v3
from flexflow_tpu.models.transformer import (
    build_bert,
    build_gpt,
    build_gpt_xl,
    build_transformer,
)
from flexflow_tpu.models.decode import (
    GPT_DECODE_KW,
    GPT_DECODE_SERVE_KW,
    SERVE_FRAME_SLOTS,
    build_gpt_decode,
    build_gpt_prefill,
    derive_prefill_model,
)
from flexflow_tpu.models.dlrm import build_dlrm
from flexflow_tpu.models.xdl import build_xdl
from flexflow_tpu.models.candle_uno import build_candle_uno
from flexflow_tpu.models.moe import build_moe
from flexflow_tpu.models.joyai_flash import build_joyai_flash
from flexflow_tpu.models.ouro import build_ouro
from flexflow_tpu.models.afmoe import build_afmoe_decode
from flexflow_tpu.models.mlp import build_mlp_unify
from flexflow_tpu.models.synthetic import build_moe_trunk, build_multibranch

__all__ = [
    "build_alexnet",
    "build_alexnet_cifar10",
    "build_resnet",
    "build_resnext50",
    "build_inception_v3",
    "build_transformer",
    "build_bert",
    "build_gpt",
    "build_gpt_decode",
    "build_gpt_prefill",
    "derive_prefill_model",
    "build_gpt_xl",
    "GPT_DECODE_KW",
    "GPT_DECODE_SERVE_KW",
    "SERVE_FRAME_SLOTS",
    "build_dlrm",
    "build_xdl",
    "build_candle_uno",
    "build_moe",
    "build_joyai_flash",
    "build_ouro",
    "build_afmoe_decode",
    "build_moe_trunk",
    "build_multibranch",
    "build_mlp_unify",
]
