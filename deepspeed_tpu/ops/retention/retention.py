"""Gated power retention (degree 2): attention whose memory is a
fixed-size matrix per key/value head instead of a list of keys and
values (Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239).

For one key/value head, with s the scale inside the power, lg_t the
log of the token's gate and p = 2:

    a[t, i] = (s * q_t . k_i)^2 * exp(lg_{i+1} + ... + lg_t)    i <= t
    o_t     = sum_i a[t, i] v_i / (sum_i a[t, i] + eps)

phi(u) is the symmetric square of u: the d(d+1)/2 values
c_ij u_i u_j, i <= j, c_ii = 1, c_ij = sqrt(2), so that
phi(u).phi(w) = (u.w)^2, in D rows (`state_dim`: the pairs, and where
a head fills the lanes rows of padding that keep every run of fixed i
on a tile boundary). With it the sum over i folds into a state:

    S_t = g_t S_{t-1} + phi(sqrt(s) k_t) v_t^T    [D, d]
    z_t = g_t z_{t-1} + phi(sqrt(s) k_t)          [D]
    o_t = phi(sqrt(s) q_t)^T S_t / (phi(sqrt(s) q_t)^T z_t + eps)

`retention_step` is that recurrence for one token of every row
(decode). `retention_chunked` is the same sum taken a chunk at a time
(prefill): inside a chunk every pair directly, (q.k)^2 with no phi;
across chunks the state. Several query heads read one key/value head's
state (grouped-query): query head j reads head j // (Hq // Hk).

Everything summed into the state is float32, and so is the state
unless the caller keeps it in another type (it is read as float32 and
written back in its own). phi is formed
without a gather: u times two one-hot [d, D] matrices picks u_i and
u_j, which the MXU does exactly for bfloat16 operands in one pass.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


LANE = 128
# rows of the state that lie side by side in one of the chip's float32
# tiles: where a head fills the lanes, the rows of its state come in
# runs that start on such a boundary (see `state_dim`)
RUN_ALIGN = 8


def _runs(head_dim, align):
    """[(i, the j at which run i starts)]: run i holds the rows (i, j)
    from j = align (i // align) up; those with j < i are padding."""
    return [(i, i // align * align) for i in range(head_dim)]


def state_dim(head_dim, align=None):
    """Rows of one head's state: the pairs (i, j), i <= j, in runs of
    fixed i with j ascending. With `align` 1 they are the d (d + 1) / 2
    pairs and nothing else. With `align` 8 run i starts at j = 8
    (i // 8): its rows with j < i are padding (phi is 0 there and the
    state stays 0), every run starts on an 8-row boundary in phase
    with j, and row j of a [d, d] tile lines up with the run's rows in
    the chip's float32 tiling, which is what lets one kernel pass over
    the state in decode (`decode.retention_decode`); 8,256 pairs in 8,704
    rows at head width 128. By default a head that fills the lanes
    (the kernel's case) is aligned and a narrower one is not. A state
    array tells its layout by its row count, and `phi`,
    `retention_step` and `retention_chunked` follow it."""
    if align is None:
        align = RUN_ALIGN if head_dim % LANE == 0 else 1
    return sum(head_dim - j0 for _, j0 in _runs(head_dim, align))


def align_of(head_dim, rows):
    """The alignment of a state of `rows` rows at this head width."""
    for align in (RUN_ALIGN, 1):
        if rows == state_dim(head_dim, align):
            return align
    raise ValueError(f"a state of {rows} rows is no layout of head width "
                     f"{head_dim} (`state_dim`)")


@functools.lru_cache(maxsize=None)
def _pairs(head_dim, align):
    """(one-hot [d, D] of i, one-hot [d, D] of j, c_ij [D]), rows in
    the order of `state_dim`; a padding row's columns and coefficient
    are zero."""
    runs = _runs(head_dim, align)
    i = np.concatenate([np.full(head_dim - j0, a) for a, j0 in runs])
    j = np.concatenate([np.arange(j0, head_dim) for _, j0 in runs])
    rows = np.arange(len(i))
    real = j >= i
    first = np.zeros((head_dim, len(i)), np.float32)
    first[i[real], rows[real]] = 1.0
    second = np.zeros((head_dim, len(i)), np.float32)
    second[j[real], rows[real]] = 1.0
    coeff = np.where(i == j, 1.0, np.sqrt(2.0)) * real
    return first, second, coeff.astype(np.float32)


def phi(u, scale, rows=None):
    """[..., d] -> [..., D] float32: phi(sqrt(scale) u), in the layout
    of a state of `rows` rows (default: `state_dim` of d)."""
    d = u.shape[-1]
    first, second, coeff = _pairs(
        d, align_of(d, state_dim(d) if rows is None else rows))
    # a one-hot product is exact in the operand's own type; float32
    # operands need every pass of the MXU to stay so
    precision = HIGHEST if u.dtype == f32 else None
    pick = lambda onehot: jnp.dot(
        u, jnp.asarray(onehot, u.dtype), precision=precision,
        preferred_element_type=f32)
    return pick(first) * pick(second) * (coeff * f32(scale))


def read_out(qg, k, v, g, num, den, scale, eps):
    """The read-out of the NEW state, taken from the old one's
    products (num = phi(q).S0 [B, Hk, G, d], den = phi(q).z0 [B, Hk,
    G]) and the token's own pair: phi(q).S1 = g phi(q).S0 + (s q.k)^2
    v. Both passes over the state then read the same array (measured
    on the v5e: 2.41 ms a layer against 2.63 with the product on S1),
    and a kernel can make them one. qg [B, Hk, G, d]; k, v [B, Hk, d];
    g [B, Hk] float32 gates. Returns o [B, Hk, G, d] float32."""
    own = (f32(scale) * jnp.einsum(
        "bhgd,bhd->bhg", qg, k, preferred_element_type=f32,
        precision=HIGHEST if qg.dtype == f32 else None)) ** 2
    num = g[..., None, None] * num + \
        own[..., None] * v.astype(f32)[:, :, None, :]
    den = g[..., None] * den + own
    return num / (den[..., None] + f32(eps))


def retention_step(q, k, v, lg, S, z, scale, eps, keep=None, fresh=None):
    """One token of every row. q [B, Hq, d]; k, v [B, Hk, d]; lg
    [B, Hk] float32 log-gates; S [B, Hk, D, d] and z [B, Hk, D]
    float32. Rows with `fresh` start from zero state; rows with `keep`
    leave theirs as it was (their output is not meant to be read).
    Returns (o [B, Hq, d] float32, S, z)."""
    b, hq, d = q.shape
    hk = k.shape[1]
    S0, z0 = S.astype(f32), z.astype(f32)
    if fresh is not None:
        S0 = jnp.where(fresh[:, None, None, None], f32(0), S0)
        z0 = jnp.where(fresh[:, None, None], f32(0), z0)
    g = jnp.exp(lg.astype(f32))
    v = v.astype(f32)
    qg = q.reshape(b, hk, hq // hk, d)
    phik = phi(k, scale, S.shape[-2])                    # [B, Hk, D]
    phiq = phi(qg, scale, S.shape[-2])                   # [B, Hk, G, D]
    S1 = g[..., None, None] * S0 + phik[..., None] * v[:, :, None, :]
    z1 = g[..., None] * z0 + phik
    o = read_out(
        qg, k, v, g,
        jnp.einsum("bhgD,bhDd->bhgd", phiq, S0, precision=HIGHEST),
        jnp.einsum("bhgD,bhD->bhg", phiq, z0, precision=HIGHEST),
        scale, eps).reshape(b, hq, d)
    S1, z1 = S1.astype(S.dtype), z1.astype(z.dtype)
    if keep is not None:
        S1 = jnp.where(keep[:, None, None, None], S, S1)
        z1 = jnp.where(keep[:, None, None], z, z1)
    return o, S1, z1


def _chunk(q, k, v, lg, valid, S, z, scale, eps):
    """One chunk of C tokens from state (S, z): q [B, C, Hk, G, d],
    k, v [B, C, Hk, d], lg [B, C, Hk], valid [B, C]."""
    c = q.shape[1]
    S_in, z_in = S, z
    S, z = S.astype(f32), z.astype(f32)
    lg = jnp.where(valid[..., None], lg, f32(0))
    cum = jnp.cumsum(lg, axis=1)                         # [B, C, Hk]
    total = cum[:, -1]                                   # [B, Hk]
    # inside the chunk: every pair, no phi
    scores = jnp.einsum("bthgd,bihd->bhgti", q, k,
                        preferred_element_type=f32,
                        precision=HIGHEST if q.dtype == f32 else None)
    decay = cum.transpose(0, 2, 1)[:, :, :, None] - \
        cum.transpose(0, 2, 1)[:, :, None, :]            # [B, Hk, t, i]
    seen = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]) & \
        valid[:, None, None, :]
    decay = jnp.exp(jnp.where(seen, decay, -jnp.inf))
    a = (f32(scale) * scores) ** 2 * decay[:, :, None]
    num = jnp.einsum("bhgti,bihd->bthgd", a, v.astype(f32),
                     precision=HIGHEST)
    den = a.sum(-1).transpose(0, 3, 1, 2)                # [B, t, Hk, G]
    # what came before the chunk: the state
    phiq = phi(q, scale, S.shape[-2]) * jnp.exp(cum)[:, :, :, None, None]
    num = num + jnp.einsum("bthgD,bhDd->bthgd", phiq, S,
                           precision=HIGHEST)
    den = den + jnp.einsum("bthgD,bhD->bthg", phiq, z, precision=HIGHEST)
    o = num / (den[..., None] + f32(eps))
    # the chunk into the state
    left = jnp.where(valid[..., None], jnp.exp(total[:, None] - cum),
                     f32(0))                             # [B, C, Hk]
    phik = phi(k, scale, S.shape[-2]) * left[..., None]  # [B, C, Hk, D]
    carry = jnp.exp(total)
    S1 = carry[..., None, None] * S + jnp.einsum(
        "bihD,bihd->bhDd", phik, v.astype(f32), precision=HIGHEST)
    z1 = carry[..., None] * z + phik.sum(1)
    return o, S1.astype(S_in.dtype), z1.astype(z_in.dtype)


def retention_chunked(q, k, v, lg, S, z, scale, eps, chunk, valid=None):
    """T tokens of every row, `chunk` at a time, from state (S, z).
    q [B, T, Hq, d]; k, v [B, T, Hk, d]; lg [B, T, Hk]; S [B, Hk, D, d],
    z [B, Hk, D] float32; `valid` [B, T] marks the real tokens (a row's
    padding leaves the state as it was). phi(Q) exists for one chunk at
    a time. Returns (o [B, T, Hq, d] float32, S, z)."""
    b, t, hq, d = q.shape
    hk = k.shape[2]
    if valid is None:
        valid = jnp.ones((b, t), bool)
    n = -(-t // chunk)
    pad = n * chunk - t

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(
            x.reshape((b, n, chunk) + x.shape[2:]), 1, 0)

    def step(carry, xs):
        o, S, z = _chunk(*xs, *carry, scale, eps)
        return (S, z), o

    (S, z), o = jax.lax.scan(
        step, (S, z),
        (chunks(q.reshape(b, t, hk, hq // hk, d)), chunks(k), chunks(v),
         chunks(lg.astype(f32)), chunks(valid)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * chunk, hq, d)
    return o[:, :t], S, z
