"""Operations and bytes that gated power retention of degree 2 needs,
computed from shapes (beside `kernel_costs.py`, and for the same
reason: the yardstick stays with the benchmark). `sizes` is a
configuration file of the Brumby family; D = head_dim (head_dim + 1) / 2
rows of state per key/value head.
"""


def state_rows(sizes):
    d = sizes["head_dim"]
    return d * (d + 1) // 2


def state_bytes(sizes, slots, bytes_per_el=4):
    """Bytes of the state (matrix [D, head_dim] and normaliser [D] per
    key/value head) of `slots` slots over every layer held here."""
    per_head = state_rows(sizes) * (sizes["head_dim"] + 1)
    return (sizes["num_hidden_layers"] * slots *
            sizes["num_key_value_heads"] * per_head * bytes_per_el)


def decode_state_traffic_bytes(sizes, slots, bytes_per_el=4):
    """The least a decode launch moves for the state: every slot's
    state of every layer read once and written once (update and
    read-out in one pass). Slots that are idle are counted too: the
    program touches them."""
    return 2 * state_bytes(sizes, slots, bytes_per_el)


def prefill_chunk_cost(sizes, tokens, pairs_chunk, bytes_per_el=4):
    """(flops, bytes) of retention over one prefill launch of `tokens`
    tokens of one slot, every layer held here, in the chunked form
    with `pairs_chunk` tokens' pairs taken directly:

      * inside a chunk the causal half of the pairs, twice (the
        weights (q.k)^2 and their product with v): 2 matmuls of
        c (c + 1) / 2 x head_dim per query head;
      * phi(K)^T V into the state and phi(Q) S, phi(Q) z out of it:
        matmuls over D rows per key/value head and per query head;
      * forming phi for q and k: two multiplications a value.

    Bytes: the slot's state read once and written once per launch, and
    q, k, v in and o out (2 bytes a value). The one-hot products that
    this program forms phi with are its own choice and not counted."""
    L, hq, hk, d = (sizes["num_hidden_layers"], sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    D = state_rows(sizes)
    c = min(pairs_chunk, tokens)
    chunks = -(-tokens // c)
    pairs = 2 * 2 * hq * (c * (c + 1) // 2) * d * chunks
    into_state = 2 * hk * tokens * D * (d + 1)
    out_of_state = 2 * hq * tokens * D * (d + 1)
    forming_phi = 2 * (hq + hk) * tokens * D
    flops = L * (pairs + into_state + out_of_state + forming_phi)
    nbytes = 2 * state_bytes(sizes, 1, bytes_per_el) + \
        L * tokens * (2 * hq + 2 * hk) * d * 2
    return flops, nbytes
