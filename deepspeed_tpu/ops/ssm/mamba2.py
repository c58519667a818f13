"""Mamba-2's state-space mixer (arXiv:2405.21060) for serving: a state
matrix per head with ONE SCALAR decay a head and token, which is what
lets a chunk be matrix products. Mamba-1's (`mamba1.py`) decays every
(channel, state) pair apart: neither function here can express it.

For one head of width P with state width N, its group's B_t, C_t [N],
the token's step dt_t > 0 and the head's A < 0, D:

    a_t = exp(dt_t A)
    H_t = a_t H_{t-1} + dt_t x_t B_t^T          H [P, N]
    y_t = H_t C_t + D x_t

`ssm_step` is that recurrence for one token of every slot.
`ssd_chunked` is the same sum taken `chunk` tokens at a time (the
paper's state-space dual form): with cs_t the running sum of dt A
inside a chunk,

    y_t  = sum_{s <= t} exp(cs_t - cs_s) dt_s (C_t . B_s) x_s   the pairs
           + exp(cs_t) H_in C_t                                 the state
    H_out = exp(cs_Q) H_in + sum_s exp(cs_Q - cs_s) dt_s x_s B_s^T

so a chunk costs three small matrix products a group and the state is
touched once a chunk. Heads h share the B and C of group
h // (heads / groups).

In front of both stands a causal depthwise convolution of width K over
the concatenated x | B | C channels, which needs the K - 1 rows before
the first token: `causal_conv` takes them in and hands the next ones on.

Precision: the state is float32 unless the caller keeps it in another
type (read as float32, written back in its own); dt, A, the decays and
every sum into or out of the state are float32; x, B, C and y are the
caller's compute type, and their products accumulate in float32.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _precision(dtype):
    """float32 operands need every pass of the matrix unit to stay
    float32; bfloat16 operands are exact in one."""
    return HIGHEST if dtype == f32 else None


def causal_conv(x, w, b, carried, n_valid=None):
    """silu(causal depthwise conv1d) over x [..., T, C] with the
    weights w [C, K] (w[:, K - 1] meets the token itself, as
    `torch.nn.Conv1d` lays them out) and bias b [C]. `carried`
    [..., K - 1, C] are the rows before x's first (zeros at a
    sequence's start). Returns (y [..., T, C] in x's type, the rows to
    carry on [..., K - 1, C] in `carried`'s type): the last K - 1 of
    the first `n_valid` rows (a traced scalar; default T), so that pad
    rows behind a prefill chunk's tokens are never carried."""
    t = x.shape[-2]
    k = w.shape[-1]
    window = jnp.concatenate([carried.astype(x.dtype), x], axis=-2)
    w32 = w.astype(f32)
    y = b.astype(f32)
    for i in range(k):
        y = y + window[..., i:i + t, :].astype(f32) * w32[:, i]
    at = t if n_valid is None else n_valid
    nxt = jax.lax.dynamic_slice_in_dim(window, at, k - 1, axis=-2)
    return jax.nn.silu(y).astype(x.dtype), nxt.astype(carried.dtype)


def split_xbc(xbc, n_heads, head_dim, d_state):
    """[..., d_ssm + 2 G N] -> x [..., heads, P], B, C [..., G, N],
    for a state of [heads, P, N] a slot; what is left of the width
    after d_ssm = heads x P tells the number of groups."""
    d_ssm = n_heads * head_dim
    gn = (xbc.shape[-1] - d_ssm) // 2
    n_groups = gn // d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :d_ssm].reshape(lead + (n_heads, head_dim))
    B = xbc[..., d_ssm:d_ssm + gn].reshape(lead + (n_groups, d_state))
    C = xbc[..., d_ssm + gn:].reshape(lead + (n_groups, d_state))
    return x, B, C


def ssd_chunked(xs, dt, A, B, C, D, H0, valid=None, chunk=128):
    """A run of tokens of ONE sequence through the chunked form.

    xs [T, heads, P]; dt [T, heads] float32 (after softplus); A, D
    [heads] float32; B, C [T, G, N]; H0 [heads, P, N], the state the
    run starts from; `valid` [T] bool (default: all): a row that is
    not valid (the pad behind a prefill chunk's tokens) takes a step
    of dt = 0, which leaves the state alone. Returns (y [T, heads, P]
    in xs' type, H1 in H0's type): H1 is the state after the last
    valid row. T need not be a multiple of `chunk`."""
    t, nh, p = xs.shape
    g, n = B.shape[-2:]
    e = nh // g                                 # heads a group
    prec = _precision(xs.dtype)
    dt = dt.astype(f32)
    if valid is not None:
        dt = jnp.where(valid[:, None], dt, 0.0)
    pad = -t % chunk
    if pad:
        xs, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                        for a in (xs, dt, B, C))
    nc = (t + pad) // chunk
    chunks = (xs.reshape(nc, chunk, g, e, p), dt.reshape(nc, chunk, g, e),
              B.reshape(nc, chunk, g, n), C.reshape(nc, chunk, g, n))
    A = A.astype(f32).reshape(g, e)
    D = D.astype(f32).reshape(g, e)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]

    def one(H, c):
        x, d, b, c_ = c
        cs = jnp.cumsum(d * A, axis=0)                       # [Q, G, E] <= 0
        # the pairs inside the chunk
        cb = jnp.einsum("tgn,sgn->tsg", c_, b, precision=prec,
                        preferred_element_type=f32)
        decay = jnp.exp(jnp.where(seen, cs[:, None] - cs[None, :],
                                  -jnp.inf))                 # [t, s, G, E]
        m = cb[..., None] * decay * d[None]
        y = jnp.einsum("tsge,sgep->tgep", m.astype(x.dtype), x,
                       precision=prec, preferred_element_type=f32)
        # what the state the chunk starts from adds
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "tgn,gepn->tgep", c_.astype(f32), H, precision=HIGHEST)
        y = y + D[..., None] * x.astype(f32)
        # the state handed on
        w = jnp.exp(cs[-1][None] - cs) * d                   # [Q, G, E]
        H = jnp.exp(cs[-1])[..., None, None] * H + jnp.einsum(
            "sgep,sgn->gepn", (w[..., None] * x.astype(f32)).astype(x.dtype),
            b, precision=prec, preferred_element_type=f32)
        return H, y.astype(x.dtype)

    H1, y = jax.lax.scan(one, H0.astype(f32).reshape(g, e, p, n), chunks)
    return (y.reshape(nc * chunk, nh, p)[:t],
            H1.reshape(nh, p, n).astype(H0.dtype))


def ssm_step(xs, dt, A, B, C, D, H, li, keep, fresh):
    """One token of every slot, on layer `li` of the WHOLE state array
    H [L, S, heads, P, N] (it rides in the layer scan's carry; the
    layer's part is updated in place): xs [S, heads, P]; dt [S, heads]
    float32; B, C [S, G, N]; A, D [heads]. A slot with `fresh` [S]
    starts from zero state (a one-token prompt's first step), a slot
    with `keep` [S] (not live) keeps the state it has. Returns (y [S,
    heads, P] in xs' type, H)."""
    s, nh, p = xs.shape
    g, n = B.shape[-2:]
    e = nh // g
    old = jax.lax.dynamic_index_in_dim(H, li, 0, keepdims=False)
    H0 = jnp.where(fresh[:, None, None, None], 0.0, old.astype(f32))
    dt = dt.astype(f32)
    a = jnp.exp(dt * A.astype(f32))
    heads = lambda v: jnp.broadcast_to(
        v.astype(f32)[:, :, None], (s, g, e, n)).reshape(s, nh, 1, n)
    x32 = xs.astype(f32)
    H1 = a[..., None, None] * H0 + \
        (dt[..., None] * x32)[..., None] * heads(B)
    y = (H1 * heads(C)).sum(-1) + D.astype(f32)[:, None] * x32
    H1 = jnp.where(keep[:, None, None, None], old, H1.astype(H.dtype))
    H = jax.lax.dynamic_update_index_in_dim(H, H1, li, 0)
    return y.astype(xs.dtype), H
