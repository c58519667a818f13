"""State-space mixers as a serving engine needs them: the scan of a
prefill chunk, the one-token step of decode and the causal depthwise
convolution in front of both. Mamba-2's (arXiv:2405.21060,
`mamba2.py`): one scalar decay a head, so a chunk is matrix products
(`ssd_chunked`, `ssm_step`). Mamba-1's (arXiv:2312.00752, `mamba1.py`):
a decay for every (channel, state) pair, so a chunk is a scan with the
state kept on chip (`selective_scan_chunk`, `selective_step`)."""

from deepspeed_tpu.ops.ssm.mamba1 import (selective_scan_chunk,  # noqa: F401
                                          selective_step)
from deepspeed_tpu.ops.ssm.mamba2 import (causal_conv, split_xbc,  # noqa: F401
                                          ssd_chunked, ssm_step)
