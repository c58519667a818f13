"""deepspeed_tpu.inference — the serving engine (docs/inference.md).

  * InferenceEngine (engine.py): AOT-compiled prefill + single-token
    decode programs, device-side sampling, zero per-token host sync.
  * PagedKVCache (kv_cache.py): fixed-size pages in one preallocated
    device pool, per-request page tables, host-side alloc/free at
    serving fences, `kv_cache` memory-ledger category.
  * RecurrentStateCache (kv_cache.py): the second kind of slot state,
    a fixed block of recurrent state per slot for a model whose
    layers are retention (models/brumby.py); same interface towards
    the scheduler, `recurrent_state` ledger category.
  * ServingLoop / Request / serve_sequential (scheduler.py):
    iteration-level continuous batching with chunked prefill
    interleaving and EOS/max-tokens eviction.
  * InferenceConfig (config.py): the `inference` config block.
  * int8 weight-only quantization (engine.quantize_param_tree over
    ops/transformer/quantized_matmul.py): the projection kernels a
    model names, quantized once at load; dequant-in-matmul epilogue.
  * serving observability (monitor/serving.py, ISSUE 14): with a
    `monitor` block enabled, a ServingTracker stamps each request's
    lifecycle at the serving fences — per-slot Perfetto timeline,
    per-fence `serving_slo` SLO events, live request table in flight
    dumps (`inference.observability`; docs/monitoring.md).
"""

from deepspeed_tpu.inference.config import (InferenceConfig,
                                            InferenceConfigError)
from deepspeed_tpu.inference.engine import InferenceEngine
# the kind "paged+latent" registers itself in `engine.KINDS`
from deepspeed_tpu.inference import latent_kind  # noqa: F401
# and so does "state+window+shared"
from deepspeed_tpu.inference import hybrid_kind  # noqa: F401
# and "paged|state"
from deepspeed_tpu.inference import layered_kind  # noqa: F401
from deepspeed_tpu.inference.kv_cache import (PagedKVCache,
                                              RecurrentStateCache)
from deepspeed_tpu.inference.scheduler import (Request, ServingLoop,
                                               serve_sequential)

__all__ = [
    "InferenceEngine", "PagedKVCache", "RecurrentStateCache", "ServingLoop",
    "Request",
    "serve_sequential", "InferenceConfig", "InferenceConfigError",
]
