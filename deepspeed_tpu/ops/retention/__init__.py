from deepspeed_tpu.ops.retention.retention import (
    phi, retention_chunked, retention_step, state_dim)

__all__ = ["phi", "retention_chunked", "retention_step", "state_dim"]
