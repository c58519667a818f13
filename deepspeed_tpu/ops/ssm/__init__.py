"""Mamba-2's state-space mixer (arXiv:2405.21060): the chunked scan of
a prefill chunk, the one-token step of decode and the causal depthwise
convolution in front of both (`mamba2.py`)."""

from deepspeed_tpu.ops.ssm.mamba2 import (causal_conv, split_xbc,  # noqa: F401
                                          ssd_chunked, ssm_step)
