"""Bytes and operations that Phi-4-mini-flash-reasoning's new kernels
need, computed from shapes (beside `kernel_costs.py`, `ssm_costs.py`
and `mla_costs.py`, and for the same reason: the yardstick stays with
the benchmark). `sizes` is a configuration file of the `phi4flash`
family. Whatever implements a kernel, these count the same work.
"""


def geometry(sizes):
    """(d_inner, state, conv width, layers that keep a Mamba state,
    layers that read the shared pool, K or V values a token)."""
    a = sizes["assumed"]
    quarter = sizes["num_hidden_layers"] // 4
    row = sizes["num_key_value_heads"] * (
        sizes["hidden_size"] // sizes["num_attention_heads"])
    return (a["mamba_expand"] * sizes["hidden_size"], a["mamba_d_state"],
            a["mamba_d_conv"], quarter + 1, quarter, row)


def shared_readers(sizes):
    return geometry(sizes)[4]


def shared_decode_bytes(sizes, page_reads, page, bytes_per_el=2):
    """The least the attention over the shared pool moves for
    `page_reads` reads of a page (a live slot's page, once a reading
    layer and launch): the page's K and V, a token's PUBLISHED row of
    n_kv_head x head_dim values in each (1,280: whole lane tiles, so
    nothing is padded). Both softmaxes of every pair come from that
    one read. The query rows and the output are a few KB a slot and
    not counted."""
    return page_reads * page * geometry(sizes)[5] * bytes_per_el * 2


def cache_bytes_a_token(sizes, bytes_per_el=2):
    """What a token costs in the shared pool: K and V of ONE layer."""
    return 2 * geometry(sizes)[5] * bytes_per_el


def decode_state_traffic_bytes(sizes, slots, state_bytes_per_el=4):
    """The least a decode launch moves for the Mamba-1 states: every
    slot's [d_inner, N] state of every layer that keeps one read once
    and written once (update and read-out in one pass). Idle slots are
    counted too: the program touches them. The convolution's carried
    rows (3 x d_inner x 2 bytes a slot and layer, 1.8% of the state)
    are not counted."""
    di, n, _, layers, _, _ = geometry(sizes)
    return 2 * layers * slots * di * n * state_bytes_per_el


def prefill_scan_cost(sizes, tokens, state_bytes_per_el=4):
    """(flops, bytes) of the selective scan over one prefill launch of
    `tokens` tokens of one slot, every layer that keeps a state. For
    each (token, channel, state index): the decay's exponent, the
    state's multiply-add, the input's product and the read-out's
    multiply-add: 6 operations (the exponential itself not counted).
    Bytes: c in and y out (2 bytes a value), dt in (float32), B and C,
    and the slot's state read once and written once a launch."""
    di, n, _, layers, _, _ = geometry(sizes)
    flops = 6 * tokens * di * n
    nbytes = tokens * di * (2 + 4 + 2) + tokens * 2 * n * 2 + \
        2 * di * n * state_bytes_per_el
    return layers * flops, layers * nbytes
