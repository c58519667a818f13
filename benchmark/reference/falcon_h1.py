"""The plain reference: the Falcon-H1 decoder (TII; the block its
`config.json` spells out, `model_type: falcon_h1`: a Mamba-2
state-space mixer, arXiv:2405.21060, and softmax attention with
grouped-query heads in parallel on one normed input, then a gated SiLU
feed-forward, with the config's muP multipliers) in `jax.numpy` and
float32 at matmul precision "highest". No kernels, no cache, no state
kept between calls, no chunks, and no import from the program.

Per layer, on x [T, H], every multiplier a key of the configuration:

    h  = RMSNorm(x; norm_in)
    u  = ((h * ssm_in_multiplier) W_in) * m     m = ssm_multipliers over
                                                z | xs | B | C | dt
    xBC = silu(conv1d_causal([xs|B|C]; conv_w [C, 4], conv_b))
    dt_t = softplus(dt_t + dt_bias_h);  a_t = exp(dt_t A_h),  A_h = -exp(A_log_h)
    H_t = a_t H_{t-1} + dt_t xs_t B_t^T      per head H [P, N]; B, C of
    y_t = H_t C_t + D_h xs_t                 the head's group
    y  = RMSNorm per group(y * silu(z); ssm_norm)
    o_ssm = (y W_out) * ssm_out_multiplier
    q = (h * attention_in_multiplier) Wq;  v likewise
    k = ((h * attention_in_multiplier) Wk) * key_multiplier
    o_att = (softmax_causal(RoPE(q) RoPE(k)^T / sqrt(d)) v) Wo
            * attention_out_multiplier
    x  = x + o_ssm + o_att
    m  = RMSNorm(x; norm_ff)
    x  = x + ((silu((m W_gate) * mlp_multipliers[0]) * (m W_up)) W_down)
             * mlp_multipliers[1]

The state-space mixer is written as the plain recurrence, one token
after another from a zero state (`lax.scan` over the tokens; the
program takes 128 tokens at a time in the dual form and carries the
state between launches, which is what the comparison tests).
Attention is all pairs, a block of query rows at a time so that 16,384
tokens fit ([20, 16384, 16384] float32 scores are 21 GB whole).
`ssm_state` gives what a program that keeps a state must hold after n
tokens as the direct sum over those tokens, with no recurrence at all.

Weights come as the flat dict of `benchmark/weights_falcon_h1.py`
(`h.*` leaves stacked `[n_layer, ...]`, any dtype: read as float32).
One sequence at a time: `hidden` gives the last layer's output [T, H],
`logits_of` the logits of chosen rows (the whole [T, V] is 17 GB at
16,384 x 261,120).

Departures from the published description: none in the mathematics.
The published code clamps dt to `time_step_limit`, whose default is
(0, inf): no clamp. The gated norm is taken per group
(`mamba_n_groups`), as the `falcon_h1` model code does when
`mamba_rms_norm` is set with `norm_before_gate` false.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32
PROJECTIONS = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up",
               "w_down")


def rounded_to(dtype):
    """Operands of the layer's nine projections rounded to `dtype`
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
    freq = float(theta) ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(t, dtype=f32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention_all_pairs(q, k, v, rows=512):
    """q [T, Hq, d]; k, v [T, Hk, d] -> [T, Hq, d]: causal softmax
    attention, query head j against key/value head j // (Hq // Hk),
    every pair, `rows` query rows at a time."""
    t, hq, d = q.shape
    hk = k.shape[1]
    rows = min(rows, t)
    n = -(-t // rows)
    qb = jnp.pad(q, ((0, n * rows - t), (0, 0), (0, 0))).reshape(
        n, rows, hk, hq // hk, d)
    at = jnp.arange(n * rows).reshape(n, rows)

    def one_block(xs):
        qr, tr = xs
        scores = jnp.einsum("thgd,ihd->hgti", qr, k) / jnp.sqrt(f32(d))
        seen = tr[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgti,ihd->thgd", p, v)

    o = jax.lax.map(one_block, (qb, at))
    return o.reshape(n * rows, hq, d)[:t]


def causal_conv(x, w, b):
    """x [T, C], w [C, K], b [C]: y_t = b + sum_i w[:, i] x_{t-K+1+i},
    zeros before the first token (`torch.nn.Conv1d` with groups = C
    and padding K - 1, cut to T)."""
    t, k = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return b + sum(padded[i:i + t] * w[:, i] for i in range(k))


def ssm_recurrence(xs, dt, A, B, C, D):
    """xs [T, heads, P]; dt [T, heads]; A, D [heads]; B, C [T, G, N]
    -> y [T, heads, P]: the recurrence of the module's docstring, from
    a zero state, one token after another."""
    t, nh, p = xs.shape
    g, n = B.shape[1:]
    of_head = jnp.arange(nh) // (nh // g)

    def step(H, tok):
        x, d, b, c = tok
        H = jnp.exp(d * A)[:, None, None] * H + \
            (d[:, None] * x)[:, :, None] * b[of_head][:, None, :]
        return H, (H * c[of_head][:, None, :]).sum(-1) + D[:, None] * x

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), f32), (xs, dt, B, C))
    return y


def _leaves(lp, cast):
    """This layer's leaves as float32, the projections' rounded under
    a control, and what rounds an activation."""
    lp = {k: v.astype(f32) for k, v in lp.items()}
    if cast is None:
        return lp, lambda y: y
    return {k: cast(v) if k in PROJECTIONS else v
            for k, v in lp.items()}, cast


def _ssm_inputs(lp, act, h, sizes):
    """h [T, H] (normed) -> z [T, d_ssm], xs [T, heads, P], B, C [T,
    G, N], dt [T, heads], A [heads]."""
    t = h.shape[0]
    d_ssm, nh, p = (sizes["mamba_d_ssm"], sizes["mamba_n_heads"],
                    sizes["mamba_d_head"])
    g, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    widths = (d_ssm, d_ssm, g * n, g * n, nh)
    m = jnp.concatenate([jnp.full((w,), s, f32) for w, s in
                         zip(widths, sizes["ssm_multipliers"])])
    u = (act(h * sizes["ssm_in_multiplier"]) @ lp["w_in"]) * m
    z, xbc, dt = (u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * g * n],
                  u[:, 2 * d_ssm + 2 * g * n:])
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs = xbc[:, :d_ssm].reshape(t, nh, p)
    B = xbc[:, d_ssm:d_ssm + g * n].reshape(t, g, n)
    C = xbc[:, d_ssm + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    return z, xs, B, C, dt, -jnp.exp(lp["A_log"])


def layer(lp, x, sizes, cast=None):
    """One block on x [T, H]. lp: this layer's leaves. `cast` rounds
    both operands of every projection (controls)."""
    lp, act = _leaves(lp, cast)
    t = x.shape[0]
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps, g = sizes["rms_norm_eps"], sizes["mamba_n_groups"]
    h = _rms(x, lp["norm_in"], eps)
    z, xs, B, C, dt, A = _ssm_inputs(lp, act, h, sizes)
    y = ssm_recurrence(xs, dt, A, B, C, lp["D"]).reshape(t, -1)
    y = y * jax.nn.silu(z)
    y = _rms(y.reshape(t, g, -1), lp["ssm_norm"].reshape(g, -1),
             eps).reshape(t, -1)
    o_ssm = (act(y) @ lp["w_out"]) * sizes["ssm_out_multiplier"]
    ha = act(h * sizes["attention_in_multiplier"])
    q = (ha @ lp["wq"]).reshape(t, hq, d)
    k = ((ha @ lp["wk"]) * sizes["key_multiplier"]).reshape(t, hk, d)
    v = (ha @ lp["wv"]).reshape(t, hk, d)
    o = attention_all_pairs(_rope(q, sizes["rope_theta"]),
                            _rope(k, sizes["rope_theta"]), v)
    o_att = (act(o.reshape(t, hq * d)) @ lp["wo"]) * \
        sizes["attention_out_multiplier"]
    x = x + o_ssm + o_att
    m = act(_rms(x, lp["norm_ff"], eps))
    up, down = sizes["mlp_multipliers"]
    y = act(jax.nn.silu((m @ lp["w_gate"]) * up) * (m @ lp["w_up"]))
    return x + (y @ lp["w_down"]) * down


def split(flat):
    """(top-level leaves, stacked block leaves with the "h." cut)."""
    top = {k: v for k, v in flat.items() if not k.startswith("h.")}
    blocks = {k[2:]: v for k, v in flat.items() if k.startswith("h.")}
    return top, blocks


def _embedded(top, ids, sizes):
    return top["embed"][ids].astype(f32) * sizes["embedding_multiplier"]


def hidden(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, H], the last layer's output, one layer at a
    time."""
    with jax.default_matmul_precision("highest"):
        top, blocks = split(flat)

        def body(x, lp):
            return layer(lp, x, sizes, cast), None

        x, _ = jax.lax.scan(body, _embedded(top, ids, sizes), blocks)
        return x


def ssm_state(flat, ids, n, sizes, at_layer, cast=None):
    """What a program that keeps the state-space mixer's state must
    hold for layer `at_layer` once it has taken in the first `n` of
    the tokens `ids` [T]: for every head, with a_r = exp(dt_r A),

        H = sum_{s < n} (prod_{s < r < n} a_r) dt_s xs_s B_s^T

    each token's term written out, no recurrence. Returns [heads, P,
    N] float32."""
    with jax.default_matmul_precision("highest"):
        top, blocks = split(flat)
        x = _embedded(top, ids, sizes)
        below = jax.tree_util.tree_map(lambda w: w[:at_layer], blocks)
        x, _ = jax.lax.scan(lambda x, lp: (layer(lp, x, sizes, cast), None),
                            x, below)
        lp, act = _leaves({k: w[at_layer] for k, w in blocks.items()}, cast)
        h = _rms(x, lp["norm_in"], sizes["rms_norm_eps"])
        _, xs, B, _, dt, A = _ssm_inputs(lp, act, h, sizes)
        seen = (jnp.arange(ids.shape[0]) < n)[:, None]
        cum = jnp.cumsum(jnp.where(seen, dt * A, 0.0), axis=0)
        w = jnp.where(seen, jnp.exp(cum[-1] - cum) * dt, 0.0)   # [T, heads]
        nh, g = xs.shape[1], B.shape[1]
        H = jnp.einsum("sgep,sgn->gepn",
                       (w[..., None] * xs).reshape(-1, g, nh // g,
                                                   xs.shape[-1]), B)
        return H.reshape(nh, xs.shape[-1], B.shape[-1])


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the head (untied from the embedding)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, flat["norm_f"].astype(f32), sizes["rms_norm_eps"])
        return (x @ flat["head"].astype(f32)) * sizes["lm_head_multiplier"]


def logits(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, V]: for small sizes."""
    return logits_of(flat, hidden(flat, ids, sizes, cast), sizes)
