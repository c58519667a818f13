from deepspeed_tpu.ops import adam
from deepspeed_tpu.ops import lamb
from deepspeed_tpu.ops import retention
from deepspeed_tpu.ops import sequence
from deepspeed_tpu.ops import sparse_attention
from deepspeed_tpu.ops import transformer

from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerLayer,
                                           DeepSpeedTransformerConfig)
from deepspeed_tpu.ops.module_inject import replace_module

__all__ = ["DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig",
           "replace_module", "adam", "lamb", "retention", "sequence",
           "sparse_attention", "transformer"]
