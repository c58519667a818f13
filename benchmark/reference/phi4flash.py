"""The plain reference: Phi-4-mini-flash-reasoning's decoder (Microsoft,
`model_type: phi4flash`; the decoder-hybrid-decoder of
arXiv:2507.06607) in `jax.numpy` and float32 at matmul precision
"highest". No kernels, no cache, no state kept between calls, no
skipped prefill: every layer runs on every token of one sequence. No
import from the program.

Every layer l (0-based) on x [T, H] (LayerNorm with bias, eps
`layer_norm_eps`):

    h = LayerNorm(x; w, b);  x = x + Mixer_l(h)
    m = LayerNorm(x; w', b'); x = x + (silu(m W_gate) * (m W_up)) W_down

with, by `mb_per_layer` 2 and L layers:

    l even, l <= L/2: Mamba-1 (arXiv:2312.00752)
        u, z = split(h W_in);  c = silu(conv1d_causal(u; conv_w, conv_b))
        dtl, B, C = split(c W_x);  dt = softplus(dtl W_dt + dt_bias)
        S_t = exp(dt_t A) * S_{t-1} + (dt_t c_t) B_t^T
                                                  A = -exp(A_log) [Di, N]
        y_t = S_t C_t + D c_t;  out = (y * silu(z)) W_out
        (written as the plain recurrence, one token after another from a
        zero state; layer L/2's y, before the gate, is the memory `mem`)
    l even, l > L/2: gated memory unit, out = (mem * silu(h W_g)) W_o
    l odd: differential attention (arXiv:2410.05258), pair j of query
    heads (2j, 2j + 1) over pair j // 2 of key/value heads:
        a_i = softmax(q_i k_i^T / sqrt(d)) [v_1 ; v_2]   causal; i = 1, 2
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
        lam0 = 0.8 - 0.6 exp(-0.3 l)
        o_j = RMSNorm_2d(a_1 - lam a_2; g) * (1 - lam0)
        out = concat(o) W_o + b_o
      l < L/2: q, k, v = split(h W_qkv + b); keys t - window + 1 .. t
      l = L/2 + 1: the same over every key; its k and v are THE cache
      l > L/2 + 1: q = h W_q + b_q alone; k, v are layer L/2 + 1's

logits = LayerNorm(x_L; w_f, b_f) E^T, E the embedding. All four
products of a pair are written out over all pairs of positions, a block
of query rows at a time so that 18,432 tokens fit. `scan_state` gives
what a program that keeps a Mamba state must hold after n tokens as a
direct sum over those tokens (no recurrence), and `shared_rows` the K
and V that layer L/2 + 1 leaves.

Weights come as the flat dict of `benchmark/weights_phi4flash.py`
(stacks `s.`, `m.`, `c.` of periods, `a.` the even layer and `b.` the
odd one; any dtype: read as float32; `A_log_t` lies [N, Di]).

Departures from the published description: none known in the
mathematics; what `config.json` does not carry is under the
configuration file's `assumed`.
"""

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
PROJECTIONS = ("w_in", "w_x", "w_dt", "w_out", "w_g", "w_o", "wqkv", "wq",
               "wo", "w_gu", "w_down")


def rounded_to(dtype):
    """Operands of every projection rounded to `dtype` and read back as
    float32: the reference computed in a lower precision, which is
    what a control is."""
    return lambda x: x.astype(dtype).astype(f32)


def _ln(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _leaves(lp, cast):
    """A layer's leaves as float32, the projections' rounded under a
    control, and what rounds an activation."""
    lp = {k: v.astype(f32) for k, v in lp.items()}
    if cast is None:
        return lp, lambda y: y
    return {k: cast(v) if k in PROJECTIONS else v
            for k, v in lp.items()}, cast


def lam0(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, f32))


def causal_conv(x, w, b):
    """x [T, C], w [C, K], b [C]: y_t = b + sum_i w[:, i] x_{t-K+1+i},
    zeros before the first token."""
    t, k = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return b + sum(padded[i:i + t] * w[:, i] for i in range(k))


def mamba_inputs(lp, act, h, sizes):
    """h [T, H] (normed) -> z, c [T, Di], dt [T, Di], B, C [T, N], A
    [Di, N]."""
    a = sizes["assumed"]
    di = a["mamba_expand"] * sizes["hidden_size"]
    n, r = a["mamba_d_state"], a["mamba_dt_rank"]
    uz = act(h) @ lp["w_in"]
    u, z = uz[:, :di], uz[:, di:]
    c = jax.nn.silu(causal_conv(u, lp["conv_w"], lp["conv_b"]))
    dbc = act(c) @ lp["w_x"]
    dt = jax.nn.softplus(act(dbc[:, :r]) @ lp["w_dt"] + lp["dt_bias"])
    return (z, c, dt, dbc[:, r:r + n], dbc[:, r + n:],
            -jnp.exp(lp["A_log_t"].T))


def selective_recurrence(c, dt, A, B, C, D):
    """c, dt [T, Di]; A [Di, N]; B, C [T, N]; D [Di] -> y [T, Di]: the
    recurrence from a zero state, one token after another."""
    def step(S, tok):
        c_t, dt_t, b_t, c_out = tok
        S = jnp.exp(dt_t[:, None] * A) * S + \
            (dt_t * c_t)[:, None] * b_t[None, :]
        return S, S @ c_out + D * c_t

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, f32), (c, dt, B, C))
    return y


def differential_attention(q, k, v, window=None, rows=256):
    """q [T, Hq, d]; k, v [T, Hk, d] -> a [T, Hq / 2, 2, 2 d]: for pair
    j and i = 1, 2, softmax(q_{2j+i-1} k_{2 (j // 2) + i - 1}^T /
    sqrt(d)) [v_{2 (j // 2)} ; v_{2 (j // 2) + 1}], causal and, with
    `window`, over keys t - window + 1 .. t; `rows` query rows at a
    time."""
    t, hq, d = q.shape
    pairs = hq // 2
    of_pair = jnp.arange(pairs) // 2
    kk = k.reshape(t, -1, 2, d)[:, of_pair]               # [T, pairs, 2, d]
    vv = v.reshape(t, -1, 2 * d)[:, of_pair]              # [T, pairs, 2 d]
    rows = min(rows, t)
    n = -(-t // rows)
    qb = jnp.pad(q, ((0, n * rows - t), (0, 0), (0, 0))).reshape(
        n, rows, pairs, 2, d)
    at = jnp.arange(n * rows).reshape(n, rows)

    def one_block(xs):
        qr, tr = xs
        scores = jnp.einsum("tjid,sjid->jits", qr, kk) / math.sqrt(d)
        keys = jnp.arange(t)[None, :]
        seen = keys <= tr[:, None]
        if window is not None:
            seen = seen & (keys > tr[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("jits,sje->tjie", p, vv)

    a = jax.lax.map(one_block, (qb, at))
    return a.reshape(n * rows, pairs, 2, 2 * d)[:t]


def attention_out(lp, act, a, layer, sizes):
    """a [T, pairs, 2, 2 d] -> the layer's attention output [T, H]."""
    t = a.shape[0]
    l0 = lam0(layer)
    lam = jnp.exp(lp["lq1"] @ lp["lk1"]) - jnp.exp(lp["lq2"] @ lp["lk2"]) + l0
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) +
                     sizes["assumed"]["subnorm_eps"]) * lp["subnorm"]
    return act((o * (1.0 - l0)).reshape(t, -1)) @ lp["wo"] + lp["bo"]


def heads_of(lp, act, h, sizes):
    """h [T, H] (normed) -> q [T, Hq, d], k, v [T, Hk, d] of a layer
    that projects all three."""
    t = h.shape[0]
    hq, hk = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["hidden_size"] // hq
    qkv = act(h) @ lp["wqkv"] + lp["bqkv"]
    return (qkv[:, :hq * d].reshape(t, hq, d),
            qkv[:, hq * d:(hq + hk) * d].reshape(t, hk, d),
            qkv[:, (hq + hk) * d:].reshape(t, hk, d))


def feed_forward(lp, act, x, sizes):
    f = sizes["intermediate_size"]
    m = act(_ln(x, lp["ffn_norm_w"], lp["ffn_norm_b"],
                sizes["layer_norm_eps"]))
    gu = m @ lp["w_gu"]
    return x + act(jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ lp["w_down"]


def period(lp, x, mem, kv, which, layer, sizes, cast=None):
    """The even layer `layer` and the odd one after it on x [T, H];
    `which`: "self" (below the middle), "middle" or "cross". lp: {"a":
    the even layer's leaves, "b": the odd one's}. `mem` and `kv` are
    what the middle period made (None before it). `layer` may be
    traced: only lam0 reads it. Returns (x, mem, kv)."""
    eps = sizes["layer_norm_eps"]
    la, act = _leaves(lp["a"], cast)
    lb, _ = _leaves(lp["b"], cast)
    t = x.shape[0]
    h = _ln(x, la["norm_w"], la["norm_b"], eps)
    if which == "cross":
        x = x + act(mem * jax.nn.silu(act(h) @ la["w_g"])) @ la["w_o"]
    else:
        z, c, dt, B, C, A = mamba_inputs(la, act, h, sizes)
        y = selective_recurrence(c, dt, A, B, C, la["D"])
        if which == "middle":
            mem = y
        x = x + act(y * jax.nn.silu(z)) @ la["w_out"]
    x = feed_forward(la, act, x, sizes)
    h = _ln(x, lb["norm_w"], lb["norm_b"], eps)
    if which == "cross":
        hq = sizes["num_attention_heads"]
        q = (act(h) @ lb["wq"] + lb["bq"]).reshape(t, hq, -1)
        a = differential_attention(q, *kv)
    else:
        q, k, v = heads_of(lb, act, h, sizes)
        if which == "middle":
            kv = (k, v)
        a = differential_attention(
            q, k, v, window=sizes["sliding_window"] if which == "self"
            else None)
    x = x + attention_out(lb, act, a, layer + 1, sizes)
    return feed_forward(lb, act, x, sizes), mem, kv


def stack_of(flat, short):
    """{"a": .., "b": ..} of the stack `short` ("s", "m" or "c"), the
    names cut."""
    return {half: {k.split(".", 2)[2]: v for k, v in flat.items()
                   if k.startswith(f"{short}.{half}.")}
            for half in ("a", "b")}


def _through(flat, ids, sizes, cast, stop_after_middle=False):
    """(x, mem, kv) after every period, or after the middle one; the
    periods of a stack one after another (a scan: they differ in their
    weights and in the layer number that lam0 reads)."""
    quarter = sizes["num_hidden_layers"] // 4
    x = flat["embed"][ids].astype(f32)

    def self_period(x, xs):
        lp, i = xs
        return period(lp, x, None, None, "self", 2 * i, sizes, cast)[0], None

    x, _ = jax.lax.scan(self_period, x,
                        (stack_of(flat, "s"), jnp.arange(quarter)))
    middle = jax.tree_util.tree_map(lambda w: w[0], stack_of(flat, "m"))
    x, mem, kv = period(middle, x, None, None, "middle", 2 * quarter, sizes,
                        cast)
    if stop_after_middle:
        return x, mem, kv

    def cross_period(x, xs):
        lp, i = xs
        return period(lp, x, mem, kv, "cross", 2 * (quarter + 1 + i), sizes,
                      cast)[0], None

    x, _ = jax.lax.scan(cross_period, x,
                        (stack_of(flat, "c"), jnp.arange(quarter - 1)))
    return x, mem, kv


def hidden(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, H], the last layer's output."""
    with jax.default_matmul_precision("highest"):
        return _through(flat, ids, sizes, cast)[0]


def shared_rows(flat, ids, sizes, cast=None):
    """[T] tokens -> (K, V) [T, Hk d] that layer L/2 + 1 leaves for the
    cross-decoder: what a program's shared cache must hold."""
    with jax.default_matmul_precision("highest"):
        k, v = _through(flat, ids, sizes, cast, stop_after_middle=True)[2]
        return k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)


def scan_state(flat, ids, n, sizes, cast=None, block=512):
    """What a program that keeps layer 0's Mamba state must hold once
    it has taken in the first `n` of the tokens `ids` [T]: with cum_s
    the running sum of dt up to and including token s,

        S[d, m] = sum_{s < n} exp(A[d, m] (cum_{n-1}[d] - cum_s[d]))
                              dt_s[d] c_s[d] B_s[m]

    each token's term written out, no recurrence (`block` tokens'
    terms at a time). Returns [Di, N] float32."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree_util.tree_map(lambda w: w[0], stack_of(flat, "s")["a"])
        lp, act = _leaves(lp, cast)
        x = flat["embed"][ids].astype(f32)
        h = _ln(x, lp["norm_w"], lp["norm_b"], sizes["layer_norm_eps"])
        _, c, dt, B, _, A = mamba_inputs(lp, act, h, sizes)
        t = ids.shape[0]
        seen = (jnp.arange(t) < n)[:, None]
        dt = jnp.where(seen, dt, 0.0)
        cum = jnp.cumsum(dt, axis=0)
        left = cum[-1][None, :] - cum                          # [T, Di]
        pad = -t % block
        blocks = lambda a: jnp.pad(a, ((0, pad), (0, 0))).reshape(
            -1, block, a.shape[1])

        def add(S, xs):
            left_b, w_b, b_b = xs
            terms = jnp.exp(left_b[:, :, None] * A[None]) * \
                w_b[:, :, None] * b_b[:, None, :]
            return S + terms.sum(0), None

        S, _ = jax.lax.scan(add, jnp.zeros(A.shape, f32),
                            (blocks(left), blocks(dt * c), blocks(B)))
        return S


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the embedding."""
    with jax.default_matmul_precision("highest"):
        x = _ln(x, flat["norm_f.w"].astype(f32), flat["norm_f.b"].astype(f32),
                sizes["layer_norm_eps"])
        return jnp.einsum("rh,vh->rv", x, flat["embed"].astype(f32))


def logits(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, V]: for small sizes."""
    return logits_of(flat, hidden(flat, ids, sizes, cast), sizes)
