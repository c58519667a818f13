"""Bytes that a dropless expert layer's grouped products need,
computed from shapes and from the program's own count of the experts
touched (beside `kernel_costs.py`, `retention_costs.py` and
`ssm_costs.py`, and for the same reason: the yardstick stays with the
benchmark). `sizes` is a configuration file of the `afmoe` family.

Also the vocabulary of regions of a model that keeps K/V pages in two
geometries and feeds forward through experts (the benchmark's own
copy of `deepspeed_tpu/utils/scopes.py`'s `SCOPES_PAGED_MOE`; a test
holds the two equal), for `region_join.seconds`.
"""

MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
       "moe_combine")
PAGED_MOE = ("embed", "layers", "attn_qkv", "kv_write", "kv_gather", "attn",
             "attn_out", "mlp") + MOE + ("head", "sample", "bookkeeping")


def expert_bytes(sizes, bytes_per_el=2):
    """One expert's three matrices (gate, up, down)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] * \
        bytes_per_el


def expert_layers(sizes):
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def experts_traffic_bytes(sizes, experts_touched, rows, bytes_per_el=2):
    """The least the grouped products of some launches move:
    `experts_touched` experts' matrices read once (the program's
    count: distinct experts with at least one row, summed over the
    expert layers and the launches), and the `rows` (token, pick) rows
    in and out at the hidden width. The gate's rows between the two
    products are not counted: a fused form would never write them."""
    return experts_touched * expert_bytes(sizes, bytes_per_el) + \
        2 * rows * sizes["hidden_size"] * bytes_per_el
