"""Operations and bytes that a Mamba-2 state-space mixer needs,
computed from shapes (beside `kernel_costs.py` and
`retention_costs.py`, and for the same reason: the yardstick stays
with the benchmark). `sizes` is a configuration file of the Falcon-H1
family: `mamba_n_heads` heads of `mamba_d_head` (P) with a state of
[P, N] each, N = `mamba_d_state`, in `mamba_n_groups` groups that
share B and C.
"""


def state_bytes(sizes, slots, bytes_per_el=4):
    """Bytes of the state matrices [heads, P, N] of `slots` slots over
    every layer held here (the convolution's three carried rows, 30 KB
    a slot and layer, are not counted)."""
    return (sizes["num_hidden_layers"] * slots * sizes["mamba_n_heads"] *
            sizes["mamba_d_head"] * sizes["mamba_d_state"] * bytes_per_el)


def decode_state_traffic_bytes(sizes, slots, bytes_per_el=4):
    """The least a decode launch moves for the state: every slot's
    state of every layer read once and written once (update and
    read-out in one pass). Slots that are idle are counted too: the
    program touches them."""
    return 2 * state_bytes(sizes, slots, bytes_per_el)


def prefill_chunk_cost(sizes, tokens, chunk, bytes_per_el=4):
    """(flops, bytes) of the chunked scan over one prefill launch of
    `tokens` tokens of one slot, every layer held here, `chunk` tokens
    at a time (the state-space dual form):

      * inside a chunk the causal half of the pairs: C.B^T per group
        (contraction N) and the weighted pairs times x per head
        (contraction over the chunk's tokens);
      * the state's read-out C.H and its update x^T B, both
        [P, N] a head and token.

    Decays, dt, D x and the convolution before the scan are a few
    operations a value and not counted. Bytes: the slot's state read
    once and written once per launch, and x, B, C in and y out (2
    bytes a value)."""
    L, nh, p, n, g = (sizes["num_hidden_layers"], sizes["mamba_n_heads"],
                      sizes["mamba_d_head"], sizes["mamba_d_state"],
                      sizes["mamba_n_groups"])
    c = min(chunk, tokens)
    chunks = -(-tokens // c)
    half = c * (c + 1) // 2
    pairs = 2 * (g * half * n + nh * half * p) * chunks
    state = 2 * 2 * nh * tokens * p * n
    nbytes = 2 * state_bytes(sizes, 1, bytes_per_el) + \
        L * tokens * (2 * nh * p + 2 * g * n) * 2
    return L * (pairs + state), nbytes
