"""The regions of the serving programs (`jax.named_scope`: metadata on
the HLO, no instruction changes). A device profile's operations are
joined to these through `monitor/programs.py::op_scopes`.

Time in SCOPE_LAYERS outside every inner region is the layer scan
itself: the loop and the slicing of each layer's weights out of the
stacked tree. The model's cache rides in the scan's carry whole
(`engine.scan_layers`) and is touched only in the regions named for it:
SCOPE_KV_WRITE, SCOPE_KV_GATHER and, in the programs that attend
through the decode kernel, SCOPE_ATTN for the K/V page pools of a
paged model, SCOPE_STATE_RESET, SCOPE_RETENTION_CHUNK and SCOPE_STATE_UPDATE
for the state of a recurrent one; a model that keeps both in every
layer (SCOPES_PAGED_STATE) has the paged regions and, for its
state-space mixer, SCOPE_STATE_RESET, SCOPE_SSM_CONV, SCOPE_SSM_CHUNK
and SCOPE_STATE_UPDATE. A cache-sized copy showing up under
SCOPE_LAYERS alone is a regression.

A model's block (`models/gpt2.py`, `models/brumby.py`,
`models/falcon_h1.py`, `models/trinity.py`, `models/sarvam_mla.py`,
`models/phi4flash.py`, `models/nemotron_h.py`) and the engine
(`inference/engine.py`, which re-exports them) both take the names
from here: neither the models nor the ops import the serving code.
"""

SCOPE_EMBED = "embed"
SCOPE_LAYERS = "layers"            # round the lax.scan call, nothing else
SCOPE_ATTN_QKV = "attn_qkv"        # inside a layer: the norm + the q/k/v
#                                    (and gate) projections, q/k-norm, rotary
SCOPE_KV_WRITE = "kv_write"        # the chunk's K/V into the page pool
SCOPE_KV_GATHER = "kv_gather"      # a prefill chunk's live pages through its
#                                    table row, a block of pages at a time
#                                    (decode gathers nothing)
SCOPE_ATTN = "attn"                # a few rows a slot: the kernel that reads
#                                    the pages where they lie; a prefill
#                                    chunk: paged_prefill_attention's products
#                                    and online softmax, a block at a time
SCOPE_ATTN_OUT = "attn_out"        # output projection + residual
SCOPE_MLP = "mlp"                  # norm, feed-forward, residual
SCOPE_HEAD = "head"                # final norm + output head (tied to the
#                                    embedding or its own matrix)
SCOPE_SAMPLE = "sample"
SCOPE_BOOKKEEPING = "bookkeeping"  # the slot state update
SCOPES_IN_LAYER = (SCOPE_ATTN_QKV, SCOPE_KV_WRITE, SCOPE_KV_GATHER,
                   SCOPE_ATTN, SCOPE_ATTN_OUT, SCOPE_MLP)
SCOPES = (SCOPE_EMBED, SCOPE_LAYERS) + SCOPES_IN_LAYER + \
    (SCOPE_HEAD, SCOPE_SAMPLE, SCOPE_BOOKKEEPING)

# a model whose cache is recurrent state (retention): no pages, no
# attention over a window; the state is zeroed, advanced a chunk
# (prefill) or advanced one token and read (decode)
SCOPE_STATE_RESET = "state_reset"          # a reused slot starts from zero
SCOPE_RETENTION_CHUNK = "retention_chunk"  # prefill: the chunked form
SCOPE_STATE_UPDATE = "state_update"        # decode: update + read-out
SCOPES_STATE = (SCOPE_STATE_RESET, SCOPE_RETENTION_CHUNK,
                SCOPE_STATE_UPDATE)
SCOPES_IN_LAYER_RECURRENT = (SCOPE_ATTN_QKV,) + SCOPES_STATE + \
    (SCOPE_ATTN_OUT, SCOPE_MLP)
SCOPES_RECURRENT = (SCOPE_EMBED, SCOPE_LAYERS) + \
    SCOPES_IN_LAYER_RECURRENT + (SCOPE_HEAD, SCOPE_SAMPLE,
                                 SCOPE_BOOKKEEPING)

# a model whose every layer keeps BOTH: K/V pages for its attention
# branch and, for a state-space mixer beside it, a state matrix and
# the rows its causal convolution carries (`models/falcon_h1.py`).
# Both branches' input projections stand under SCOPE_ATTN_QKV, both
# output projections and the residual under SCOPE_ATTN_OUT; the paged
# regions keep their meaning; SCOPE_STATE_RESET and SCOPE_STATE_UPDATE
# keep theirs (decode: every slot's state advanced a token and read)
SCOPE_SSM_CONV = "ssm_conv"        # the convolution and its carried rows
SCOPE_SSM_CHUNK = "ssm_chunk"      # prefill: the chunked scan, the slot's
#                                    state read and written back
SCOPES_SSM = (SCOPE_STATE_RESET, SCOPE_SSM_CONV, SCOPE_SSM_CHUNK,
              SCOPE_STATE_UPDATE)
SCOPES_IN_LAYER_PAGED_STATE = (
    SCOPE_ATTN_QKV, SCOPE_KV_WRITE, SCOPE_KV_GATHER, SCOPE_ATTN) + \
    SCOPES_SSM + (SCOPE_ATTN_OUT, SCOPE_MLP)
SCOPES_PAGED_STATE = (SCOPE_EMBED, SCOPE_LAYERS) + \
    SCOPES_IN_LAYER_PAGED_STATE + (SCOPE_HEAD, SCOPE_SAMPLE,
                                   SCOPE_BOOKKEEPING)

# a model whose layers keep K/V pages in two geometries (a sliding
# window's ring and whole histories) and whose feed-forward is an
# expert layer (`models/trinity.py`, `moe/serving.py`). The paged
# regions keep their names and meaning, and so does SCOPE_MLP (the
# norms round the feed-forward, a dense layer's feed-forward, the
# residual); the expert layer's parts stand inside it under their own
SCOPE_MOE_ROUTER = "moe_router"      # sigmoid scores, top-k, weights
SCOPE_MOE_DISPATCH = "moe_dispatch"  # rows sorted by expert: the counting
#                                      sort and the gather of the rows
SCOPE_MOE_EXPERTS = "moe_experts"    # the grouped products and their gate
SCOPE_MOE_SHARED = "moe_shared"      # the shared expert
SCOPE_MOE_COMBINE = "moe_combine"    # gathered back, weighted, summed
SCOPES_MOE = (SCOPE_MOE_ROUTER, SCOPE_MOE_DISPATCH, SCOPE_MOE_EXPERTS,
              SCOPE_MOE_SHARED, SCOPE_MOE_COMBINE)
SCOPES_IN_LAYER_PAGED_MOE = SCOPES_IN_LAYER + SCOPES_MOE
SCOPES_PAGED_MOE = (SCOPE_EMBED, SCOPE_LAYERS) + \
    SCOPES_IN_LAYER_PAGED_MOE + (SCOPE_HEAD, SCOPE_SAMPLE,
                                 SCOPE_BOOKKEEPING)

# a model that attends by multi-head latent attention over ONE pool of
# latent rows and feeds forward through experts (`models/sarvam_mla.py`).
# The paged regions keep their names and meaning over the one pool
# (SCOPE_KV_WRITE: the row [c~ ; k_rope]; SCOPE_KV_GATHER: a prefill
# chunk's rows through the slot's table, a block of pages at a time;
# SCOPE_ATTN: the decode kernel, or the chunk's products), and so do
# the expert layer's. What is new stands inside SCOPE_ATTN_QKV and
# SCOPE_ATTN_OUT under its own name
SCOPE_MLA_ABSORB = "mla_absorb"      # the two per-head products with W_kvb:
#                                      W^K into the query, W^V out of the
#                                      attended latent rows
SCOPES_IN_LAYER_LATENT_MOE = SCOPES_IN_LAYER_PAGED_MOE + (SCOPE_MLA_ABSORB,)
SCOPES_LATENT_MOE = (SCOPE_EMBED, SCOPE_LAYERS) + \
    SCOPES_IN_LAYER_LATENT_MOE + (SCOPE_HEAD, SCOPE_SAMPLE,
                                  SCOPE_BOOKKEEPING)

# a model whose layers keep DIFFERENT things (`models/phi4flash.py`,
# kind "state+window+shared"): a Mamba-1 state in some, a ring of
# pages in others, one layer of pages that several layers read, and
# nothing in the rest. The state-space regions keep their names over
# the Mamba-1 scan (SCOPE_SSM_CONV, SCOPE_SSM_CHUNK: the chunk's
# selective scan; SCOPE_STATE_UPDATE: decode's step) and the paged
# regions theirs over the rings and the shared pool. What is new: the
# attention over the SHARED pool stands inside SCOPE_ATTN under its own
# name (a ring's attention is SCOPE_ATTN alone), and the gated memory
# unit, which keeps nothing, under its own
SCOPE_SHARED_KV = "shared_kv"        # inside attn: a reading layer's walk
#                                      over the pages another layer wrote
SCOPE_GMU = "gmu"                    # the memory unit: its gate inside
#                                      attn_qkv, product and out projection
#                                      inside attn_out
SCOPES_IN_LAYER_HYBRID = SCOPES_IN_LAYER_PAGED_STATE + (SCOPE_SHARED_KV,
                                                        SCOPE_GMU)
SCOPES_HYBRID = (SCOPE_EMBED, SCOPE_LAYERS) + SCOPES_IN_LAYER_HYBRID + \
    (SCOPE_HEAD, SCOPE_SAMPLE, SCOPE_BOOKKEEPING)

# a model whose every layer is ONE thing (`models/nemotron_h.py`, kind
# "paged|state"): a Mamba-2 mixer that keeps a state, attention that
# keeps K/V pages, or a layer of experts that keeps nothing. No region
# is new: the state-space regions over the M layers and the paged ones
# over the `*` layers keep the names and meaning they have where both
# stand in every layer (SCOPES_PAGED_STATE), each layer's norm and input
# projection under SCOPE_ATTN_QKV, its output projection and residual
# under SCOPE_ATTN_OUT; an expert layer IS the feed-forward, its norm
# and residual under SCOPE_MLP and its parts under their own inside it
SCOPES_IN_LAYER_LAYERED = SCOPES_IN_LAYER_PAGED_STATE + SCOPES_MOE
SCOPES_LAYERED = (SCOPE_EMBED, SCOPE_LAYERS) + SCOPES_IN_LAYER_LAYERED + \
    (SCOPE_HEAD, SCOPE_SAMPLE, SCOPE_BOOKKEEPING)
