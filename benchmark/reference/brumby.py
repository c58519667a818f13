"""The plain reference: the Brumby decoder (Manifest AI; the block its
`config.json` spells out, with gated power retention of degree 2 in
place of softmax attention, arXiv:2507.04239) in `jax.numpy` and
float32 at matmul precision "highest". No kernels, no state, no
chunks, and no import from the program.

The gate is lg = log_sigmoid(h Wg + bg), one a key/value head and
token (bg is the model's one bias: see `assumed` in the configuration
file). Retention is written in its all-pairs form. For query head j, which
reads key/value head j // (Hq // Hk), with s = 1 / head_dim:

    a[t, i] = (s * q_t . k_i)^2 * exp(lg_{i+1} + ... + lg_t)    i <= t
    o_t     = sum_i a[t, i] v_i / (sum_i a[t, i] + eps)

The program keeps the same sum as a recurrent state (one token at a
time at decode, a chunk at a time at prefill); that the two agree is a
test of how it carries that state, not of one formula against itself.
The pairs are taken a block of query rows and one key/value head at a
time, so that 8,192 tokens fit on one chip ([40, 8192, 8192] float32
scores are 10.7 GB whole).

Weights come as the flat dict of `benchmark/weights_brumby.py` (`h.*`
leaves stacked `[n_layer, ...]`, any dtype: read as float32). One
sequence at a time: `hidden` gives the last layer's output [T, H],
`logits_of` the logits of chosen rows (the whole [T, V] is 5 GB at
8,192 x 151,936).

Departure from the published inference code, which keeps keys and
values up to a switch-over length and folds them into the state
afterwards: none in the mathematics; the reference has no state at all.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32
PROJECTIONS = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")


def rounded_to(dtype):
    """Operands of the layer's eight projections rounded to `dtype`
    and read back as float32: the reference computed in a lower
    precision, which is what a control is."""
    return lambda x: x.astype(dtype).astype(f32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, d] at positions 0..T-1: the two halves of a head
    rotated against each other."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(t, dtype=f32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def retention_all_pairs(q, k, v, lg, scale, eps, rows=1024):
    """q [T, Hq, d]; k, v [T, Hk, d]; lg [T, Hk] log-gates -> [T, Hq,
    d]. Every pair i <= t, a block of `rows` query rows and one
    key/value head (with the query heads that read it) at a time."""
    t, hq, d = q.shape
    hk = k.shape[1]
    rows = min(rows, t)
    n = -(-t // rows)
    pad = n * rows - t
    cum = jnp.cumsum(lg, axis=0)                         # [T, Hk]
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n, rows, hk, hq // hk, d)
    cb = jnp.pad(cum, ((0, pad), (0, 0))).reshape(n, rows, hk)
    at = jnp.arange(n * rows).reshape(n, rows)

    def one_head(h):
        kh, vh, ch = k[:, h], v[:, h], cum[:, h]

        def one_block(xs):
            qr, cr, tr = xs                              # [rows, G, d] ..
            scores = scale * jnp.einsum("tgd,id->gti", qr, kh)
            seen = tr[:, None] >= jnp.arange(t)[None, :]
            decay = jnp.exp(jnp.where(seen, cr[:, None] - ch[None, :],
                                      -jnp.inf))
            a = scores ** 2 * decay
            return jnp.einsum("gti,id->tgd", a, vh) / \
                (a.sum(-1).T[..., None] + eps)           # [rows, G, d]

        return jax.lax.map(one_block, (qb[:, :, h], cb[:, :, h], at))

    o = jax.lax.map(one_head, jnp.arange(hk))            # [Hk, n, rows, G, d]
    return o.transpose(1, 2, 0, 3, 4).reshape(n * rows, hq, d)[:t]


def _leaves(lp, cast):
    """This layer's leaves as float32, the projections' rounded under
    a control, and what rounds an activation."""
    lp = {k: v.astype(f32) for k, v in lp.items()}
    if cast is None:
        return lp, lambda y: y
    return {k: cast(v) if k in PROJECTIONS else v
            for k, v in lp.items()}, cast


def _qkv(lp, act, x, sizes):
    """x [T, H] -> q [T, Hq, d], k, v [T, Hk, d], lg [T, Hk]."""
    t = x.shape[0]
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    h = act(_rms(x, lp["norm_in"], eps))
    q = (h @ lp["wq"]).reshape(t, hq, d)
    k = (h @ lp["wk"]).reshape(t, hk, d)
    v = (h @ lp["wv"]).reshape(t, hk, d)
    lg = jax.nn.log_sigmoid(h @ lp["wg"] + lp["bg"])
    q = _rope(_rms(q, lp["q_norm"], eps), sizes["rope_theta"])
    k = _rope(_rms(k, lp["k_norm"], eps), sizes["rope_theta"])
    return q, k, v, lg


def layer(lp, x, sizes, cast=None):
    """One block on x [T, H]. lp: this layer's leaves. `cast` rounds
    both operands of every projection (controls)."""
    lp, act = _leaves(lp, cast)
    t = x.shape[0]
    hq, d = sizes["num_attention_heads"], sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    ret = sizes["assumed"]["retention"]
    q, k, v, lg = _qkv(lp, act, x, sizes)
    o = retention_all_pairs(q, k, v, lg, 1.0 / d, ret["eps"])
    x = x + act(o.reshape(t, hq * d)) @ lp["wo"]
    m = act(_rms(x, lp["norm_post"], eps))
    y = act(jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"]))
    return x + y @ lp["w_down"]


def split(flat):
    """(top-level leaves, stacked block leaves with the "h." cut)."""
    top = {k: v for k, v in flat.items() if not k.startswith("h.")}
    blocks = {k[2:]: v for k, v in flat.items() if k.startswith("h.")}
    return top, blocks


def hidden(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, H], the last layer's output, one layer at a
    time."""
    if sizes["assumed"]["retention"]["degree"] != 2:
        raise ValueError("the reference states retention of degree 2")
    with jax.default_matmul_precision("highest"):
        top, blocks = split(flat)

        def body(x, lp):
            return layer(lp, x, sizes, cast), None

        x, _ = jax.lax.scan(body, top["embed"][ids].astype(f32), blocks)
        return x


def state_rows(flat, ids, n, sizes, dirs, at_layer, cast=None):
    """What a program that folds the sum into a state must hold for
    layer `at_layer` once it has taken in the first `n` of the tokens
    `ids` [T], read along the directions `dirs` [P, d] in a query's
    place: for every key/value head, with s = 1 / head_dim,

        num[p] = sum_{i < n} (s u_p . k_i)^2 exp(lg_{i+1} + .. + lg_{n-1}) v_i
        den[p] = the same sum without v_i

    the numerator and the normaliser of the all-pairs form above for a
    query u_p at the last token, each pair written out, no state.
    Returns (num [Hk, P, d], den [Hk, P])."""
    with jax.default_matmul_precision("highest"):
        top, blocks = split(flat)
        x = top["embed"][ids].astype(f32)
        below = jax.tree_util.tree_map(lambda w: w[:at_layer], blocks)
        x, _ = jax.lax.scan(lambda x, lp: (layer(lp, x, sizes, cast), None),
                            x, below)
        lp, act = _leaves({k: w[at_layer] for k, w in blocks.items()}, cast)
        _, k, v, lg = _qkv(lp, act, x, sizes)
        seen = jnp.arange(ids.shape[0]) < n
        cum = jnp.cumsum(jnp.where(seen[:, None], lg, 0.0), axis=0)
        decay = jnp.where(seen[:, None], jnp.exp(cum[-1] - cum), 0.0)
        a = (jnp.einsum("pd,ihd->hpi", dirs, k) / sizes["head_dim"]) ** 2 * \
            decay.T[:, None, :]
        return jnp.einsum("hpi,ihd->hpd", a, v), a.sum(-1)


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the head (untied from the embedding)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, flat["norm_f"].astype(f32), sizes["rms_norm_eps"])
        return x @ flat["head"].astype(f32)


def logits(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, V]: for small sizes."""
    return logits_of(flat, hidden(flat, ids, sizes, cast), sizes)
