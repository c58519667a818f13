"""Bytes that the held share of a Nemotron-H expert layer and its
Mamba-2 layers' state need, computed from shapes and from the
program's own counters (beside `moe_costs.py`, `mla_costs.py` and
`ssm_costs.py`, and for the same reason: the yardstick stays with the
benchmark). `sizes` is a configuration file of the `nemotron_h`
family: its `n_routed_experts` counts the routed experts HELD, its
`hybrid_override_pattern` spells the layers held (`M` keeps a state,
`E` routes, `*` attends), and an expert is TWO matrices (up, down: no
gate) at `moe_intermediate_size`, or at `program.expert_width_stored`
where the file says the experts are stored wider.

Also the vocabulary of regions of a model whose every layer is one of
those three (the benchmark's own copy of
`deepspeed_tpu/utils/scopes.py`'s `SCOPES_LAYERED`, made of the copies
it has of the state-space and the expert regions; a test holds the two
equal), for `region_join.seconds`.
"""


from benchmark.moe_costs import MOE
from benchmark.region_join import PAGED_STATE, SSM  # noqa: F401 (readers)

LAYERED = PAGED_STATE[:-3] + MOE + PAGED_STATE[-3:]


def layers_of(sizes, letter):
    return sizes["hybrid_override_pattern"].count(letter)


def expert_width(sizes):
    """The columns an expert's W_up is stored at."""
    return sizes["program"].get("expert_width_stored",
                                sizes["moe_intermediate_size"])


def expert_bytes(sizes, bytes_per_el=2):
    """One routed expert's two matrices (up, down) as stored."""
    return 2 * sizes["hidden_size"] * expert_width(sizes) * bytes_per_el


def experts_held(sizes):
    """Routed experts this chip holds, over its expert layers."""
    return sizes["n_routed_experts"] * layers_of(sizes, "E")


def experts_traffic_bytes(sizes, experts_touched, rows, bytes_per_el=2):
    """The least the grouped products of some launches move:
    `experts_touched` held experts' matrices read once (the program's
    count: distinct experts of the share with at least one row, summed
    over the expert layers and the launches), and the `rows` (token,
    pick) rows of the share in and out at the hidden width. The rows
    between the two products are not counted: a fused form would never
    write them."""
    return experts_touched * expert_bytes(sizes, bytes_per_el) + \
        2 * rows * sizes["hidden_size"] * bytes_per_el


def state_bytes(sizes, slots, bytes_per_el=4):
    """Bytes of the state matrices [heads, P, N] of `slots` slots over
    the layers that keep one (the `M` layers alone; the convolution's
    three carried rows, 37 KB a slot and layer, are not counted)."""
    return (layers_of(sizes, "M") * slots * sizes["mamba_num_heads"] *
            sizes["mamba_head_dim"] * sizes["ssm_state_size"] * bytes_per_el)


def decode_state_traffic_bytes(sizes, slots, bytes_per_el=4):
    """The least a decode launch moves for the state: every slot's
    state of every `M` layer read once and written once. Slots that
    are idle are counted too: the program touches them."""
    return 2 * state_bytes(sizes, slots, bytes_per_el)
