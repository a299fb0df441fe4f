"""Model zoo used by examples, tests and benchmarks: ResNet, BERT, MLP,
VGG, Inception V3 — the reference's benchmark families
(reference: docs/benchmarks.rst:13-14 benchmarks Inception V3 / ResNet-101
/ VGG-16)."""

from .bert import bert_base, bert_large, bert_tiny  # noqa: F401
from .gpt import (GPT, gpt_medium, gpt_small, gpt_tiny,  # noqa: F401
                  init_kv_cache, param_bytes, pipeline_fns, rope,
                  stack_stage_params)
from .inception import InceptionV3  # noqa: F401
from .looplm import LoopLM, exit_log_distribution, looplm_loss  # noqa: F401
from .solar import SolarLM, solar_loss  # noqa: F401
from .lfm2 import Lfm2LM, lfm2_loss  # noqa: F401
from .sdar import SdarLM, block_noise, sdar_loss  # noqa: F401
from .laguna import LagunaLM, laguna_loss  # noqa: F401
from .granite import GraniteHybridLM, Mamba2Mixer, granite_loss  # noqa: F401
from .olmo_hybrid import (GatedDeltaNet, OlmoHybridLM,  # noqa: F401
                          olmo_hybrid_loss)
from .mlp import MLP, ConvNet  # noqa: F401
from .resnet import ResNet, ResNet50, ResNet101, ResNet152  # noqa: F401
from .vgg import VGG, VGG11, VGG13, VGG16, VGG19  # noqa: F401
from .vit import ViT, vit_base, vit_tiny  # noqa: F401
