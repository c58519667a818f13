"""Operations and bytes that latent attention and the held share of an
expert layer need, computed from shapes and from the program's own
counters (beside `kernel_costs.py` and `moe_costs.py`, and for the
same reason: the yardstick stays with the benchmark). `sizes` is a
configuration file of the `sarvam_mla` family: its `num_experts`
counts the routed experts HELD, its `first_k_dense_replace` the
leading dense layers.

Also the vocabulary of regions of a model that keeps ONE pool of
latent rows and feeds forward through experts (the benchmark's own
copy of `deepspeed_tpu/utils/scopes.py`'s `SCOPES_LATENT_MOE`; a test
holds the two equal), for `region_join.seconds`.
"""

MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
       "moe_combine")
ABSORB = ("mla_absorb",)
LATENT_MOE = ("embed", "layers", "attn_qkv", "kv_write", "kv_gather", "attn",
              "attn_out", "mlp") + MOE + ABSORB + ("head", "sample",
                                                   "bookkeeping")


def latent_row_values(sizes):
    """What a token leaves in a layer's cache: [c~ ; k_rope]."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def decode_attention_cost(sizes, cached_tokens, bytes_per_el=2):
    """(flops, bytes) of ONE layer's decode attention over
    `cached_tokens` cached tokens (summed over the slots): every
    token's PUBLISHED row read once, not the padded lanes; a head's
    score against the whole row and its weighted sum of the row's
    first kv_lora_rank values, two operations a product. The queries
    in and the attended rows out are not counted: 64 rows a slot
    against thousands of keys."""
    row, heads = latent_row_values(sizes), sizes["num_attention_heads"]
    flops = 2 * heads * (row + sizes["kv_lora_rank"]) * cached_tokens
    return flops, cached_tokens * row * bytes_per_el


def expert_bytes(sizes, bytes_per_el=2):
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] * \
        bytes_per_el


def expert_layers(sizes):
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def experts_held(sizes):
    """Routed experts this chip holds, over its expert layers."""
    return sizes["num_experts"] * expert_layers(sizes)


def experts_traffic_bytes(sizes, experts_touched, rows, bytes_per_el=2):
    """The least the grouped products of some launches move:
    `experts_touched` held experts' matrices read once (the program's
    count: distinct experts of the share with at least one row, summed
    over the expert layers and the launches), and the `rows` (token,
    pick) rows of the share in and out at the hidden width."""
    return experts_touched * expert_bytes(sizes, bytes_per_el) + \
        2 * rows * sizes["hidden_size"] * bytes_per_el
