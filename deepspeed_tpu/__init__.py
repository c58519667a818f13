"""deepspeed_tpu — a TPU-native training framework with the capabilities of
DeepSpeed (reference v0.3.11), built on JAX/XLA/Pallas.

Public API parity with `deepspeed/__init__.py`:
    initialize(), add_config_arguments(), init_distributed,
    DeepSpeedTransformerLayer/Config re-exports, PipelineModule re-export,
    checkpointing module.
"""

import argparse

from deepspeed_tpu.version import __version__
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments
from deepspeed_tpu.utils.distributed import init_distributed
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.runtime.correctness import (ABCorrectnessChecker,
                                               DivergenceError)

__version_info__ = tuple(int(p) for p in __version__.split("."))
__git_hash__ = "unknown"
__git_branch__ = "unknown"


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               mesh=None):
    """Initialize the DeepSpeed-TPU engine (ref `__init__.py:50`).

    Returns a tuple of ``(engine, optimizer, training_dataloader,
    lr_scheduler)`` — same shape as the reference. If the model is a
    PipelineModule, a PipelineEngine is constructed instead
    (ref `__init__.py:109-131`).
    """
    log_dist(f"DeepSpeed-TPU info: version={__version__}", ranks=[0])

    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    is_pipelined_protocol = hasattr(model, "stage_module") and \
        hasattr(model, "loss_fn")
    if isinstance(model, PipelineModule) or is_pipelined_protocol:
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(args=args,
                                model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mpu=model.mpu() if hasattr(model, "mpu")
                                else mpu,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn,
                                config=config,
                                config_params=config_params,
                                mesh=mesh)
    else:
        engine = DeepSpeedEngine(args=args,
                                 model=model,
                                 optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data,
                                 lr_scheduler=lr_scheduler,
                                 mpu=mpu,
                                 dist_init_required=dist_init_required,
                                 collate_fn=collate_fn,
                                 config=config,
                                 config_params=config_params,
                                 mesh=mesh)

    return_items = [
        engine, engine.optimizer, engine.training_dataloader,
        engine.lr_scheduler
    ]
    return tuple(return_items)


def _add_core_arguments(parser):
    """--deepspeed family of args (ref `__init__.py:142-175`)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to user code)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Discover launch info from MPI environment")
    return parser


def add_config_arguments(parser):
    """Update an argument parser with DeepSpeed's args (ref
    `__init__.py:193`)."""
    parser = _add_core_arguments(parser)
    return parser


# Top-level re-exports (ref `__init__.py`: DeepSpeedTransformerLayer and
# DeepSpeedTransformerConfig live at package root).
from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerLayer,
                                           DeepSpeedTransformerConfig)
# `deepspeed.checkpointing` module alias (ref exposes the activation-
# checkpointing module at package level).
from deepspeed_tpu.runtime.activation_checkpointing import \
    checkpointing  # noqa: F401

# Backwards compatibility with the old `deepspeed.pt` module structure
# (ref `__init__.py:37-47`): alias runtime modules under a dummy `pt`
# submodule so `import deepspeed_tpu.pt.deepspeed_utils` etc. resolve.
import sys as _sys
import types as _types

from deepspeed_tpu.runtime import config as _config_mod
from deepspeed_tpu.runtime import utils as _utils_mod
from deepspeed_tpu.runtime.fp16 import loss_scaler as _loss_scaler_mod

pt = _types.ModuleType("pt", "dummy pt module for backwards compatability")
pt.deepspeed_utils = _utils_mod
pt.deepspeed_config = _config_mod
pt.loss_scaler = _loss_scaler_mod
_sys.modules[__name__ + ".pt"] = pt
_sys.modules[__name__ + ".pt.deepspeed_utils"] = _utils_mod
_sys.modules[__name__ + ".pt.deepspeed_config"] = _config_mod
_sys.modules[__name__ + ".pt.loss_scaler"] = _loss_scaler_mod
