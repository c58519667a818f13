from deepspeed_tpu.ops.retention.retention import (
    phi, retention_chunked, retention_step, state_dim)
from deepspeed_tpu.ops.retention.decode import (
    retention_decode, retention_decode_kernel)
from deepspeed_tpu.ops.retention.prefill import (
    retention_prefill, retention_prefill_kernel)

__all__ = ["phi", "retention_chunked", "retention_decode",
           "retention_decode_kernel", "retention_prefill",
           "retention_prefill_kernel", "retention_step", "state_dim"]
