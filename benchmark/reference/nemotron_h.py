"""The plain reference: the Nemotron-H decoder (NVIDIA; the layers its
`config.json` spells out, `model_type: nemotron_h`: every layer ONE of
a Mamba-2 state-space mixer, arXiv:2405.21060, a layer of ungated
relu^2 experts behind a sigmoid router, or softmax attention with
grouped-query heads, in the order `hybrid_override_pattern` gives) in
`jax.numpy` and float32 at matmul precision "highest". No kernels, no
cache, no state kept between calls, no chunks, no sort, no grouped
product, and no import from the program.

Every layer, on x [T, H] (RMSNorm: w * x / rms(x), eps
`layer_norm_epsilon`): x = x + Mixer(RMSNorm(x; norm)), Mixer by the
layer's letter:

    M   z | xBC | dt = h W_in        widths d_ssm | d_ssm + 2 G N | heads
        xs | B | C = silu(conv1d_causal(xBC; conv_w [C, K], conv_b))
        dt_t = softplus(dt_t + dt_bias_h);  a_t = exp(dt_t A_h),  A_h = -exp(A_log_h)
        H_t = a_t H_{t-1} + dt_t xs_t B_t^T     per head H [P, N]; B, C of
        y_t = H_t C_t + D_h xs_t                the head's group
        out = RMSNorm per group(y * silu(z); ssm_norm) W_out
    E   s = sigmoid(h W_r); picks = the `num_experts_per_tok` experts of
        largest s + expert_bias; w = routed_scaling_factor * s[picks] /
        sum(s[picks]);
        out = Shared(h) + sum over every HELD expert e of
              [e in picks] w_e relu(h W_up_e)^2 W_down_e
        Shared(h) = relu(h W_su)^2 W_sd (added by the share that holds
        expert 0)
    *   q = h W_q, k = h W_k, v = h W_v; query head j against key/value
        head j // (Hq / Hk); out = softmax_causal(q k^T / sqrt(d)) v W_o;
        no rotation, no positions

logits = RMSNorm(x_L; norm_f) W_head, the head untied.

The state-space mixer is the plain recurrence, one token after another
from a zero state (`lax.scan` over the tokens; the program takes 128
tokens at a time in the dual form and carries the state between
launches, which is what the comparison tests). Attention is all pairs,
a block of query rows at a time. Every held expert is computed for
every token and its result multiplied by the token's weight for it
(zero where the token did not pick it), ONE EXPERT'S matrices read at
a time out of the benchmark's own stacked arrays, cut to the PUBLISHED
width (`moe_intermediate_size`: where the arrays are stored wider, the
columns past it are not read). `ssm_state` gives what a program that
keeps a state must hold after n tokens as the direct sum over those
tokens, with no recurrence at all.

The chip's share: the configuration's `n_routed_experts` counts the
experts HELD (`first_expert` on); the router's width is the arrays'.

Weights come as the flat dict of `benchmark/weights_nemotron_h.py`:
the leaves of the layers a RUN of the pattern holds are stacked under
`r<run>.<letter>.<leaf>` (`runs`: the pattern read from the left as
runs of `EM` pairs and, where no pair begins, of one letter), every
expert layer's routed experts under `x.w_up`, `x.w_down`; any dtype:
read as float32. One sequence at a time: `hidden` gives the last
layer's output [T, H], `logits_of` the logits of chosen rows,
`router_picks` the experts every expert layer picks for one row.

Departures from the published code: none in the mathematics. The
`nemotron_h` modelling code builds no rotary embedding although
`rope_theta` stands in the config: nothing rotates here. The gated
norm is taken per group (`n_groups`), `norm_before_gate` false. dt is
not clamped (`time_step_limit` defaults to (0, inf)). The published
code keeps the router in float32; everything here is float32.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32
PROJECTIONS = ("w_in", "w_out", "wq", "wk", "wv", "wo", "shared_up",
               "shared_down")
EXPERTS = ("w_up", "w_down")         # of the routed experts


def rounded_to(dtype):
    """Operands of every projection (a Mamba-2 layer's two,
    attention's four, every expert's and the shared expert's two)
    rounded to `dtype` and read back as float32: the reference
    computed in a lower precision, which is what a control is. The
    router stays float32, as the published code keeps it."""
    return lambda x: x.astype(dtype).astype(f32)


def runs(pattern):
    """[(unit, steps)]: the pattern read from the left as runs of `EM`
    pairs and, where no pair begins, of one letter (how the weights
    are stacked)."""
    out, i = [], 0
    while i < len(pattern):
        unit = "EM" if pattern.startswith("EM", i) else pattern[i]
        n = 1
        while pattern.startswith(unit, i + n * len(unit)):
            n += 1
        out.append((unit, n))
        i += n * len(unit)
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _relu2(m, w_up, w_down, act):
    return act(jnp.square(jax.nn.relu(m @ w_up))) @ w_down


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
    zeros before the first token."""
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
    """This layer's leaves as float32 (the routed experts' own stacks
    and the layer's index among them stay as they are), the
    projections' rounded under a control, and what rounds an
    activation."""
    lp = {k: v if k in EXPERTS + ("layer",) else v.astype(f32)
          for k, v in lp.items()}
    if cast is None:
        return lp, lambda y: y
    return {k: cast(v) if k in PROJECTIONS else v
            for k, v in lp.items()}, cast


def _ssm_inputs(lp, act, h, sizes):
    """h [T, H] (normed) -> z [T, d_ssm], xs [T, heads, P], B, C [T,
    G, N], dt [T, heads], A [heads]."""
    t = h.shape[0]
    nh, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    d_ssm = nh * p
    u = act(h) @ lp["w_in"]
    z, xbc, dt = (u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * g * n],
                  u[:, 2 * d_ssm + 2 * g * n:])
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs = xbc[:, :d_ssm].reshape(t, nh, p)
    B = xbc[:, d_ssm:d_ssm + g * n].reshape(t, g, n)
    C = xbc[:, d_ssm + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    return z, xs, B, C, dt, -jnp.exp(lp["A_log"])


def mamba(lp, x, sizes, cast=None):
    """An `M` layer on x [T, H]."""
    lp, act = _leaves(lp, cast)
    t, g, eps = x.shape[0], sizes["n_groups"], sizes["layer_norm_epsilon"]
    z, xs, B, C, dt, A = _ssm_inputs(lp, act, _rms(x, lp["norm"], eps),
                                     sizes)
    y = ssm_recurrence(xs, dt, A, B, C, lp["D"]).reshape(t, -1)
    y = y * jax.nn.silu(z)
    y = _rms(y.reshape(t, g, -1), lp["ssm_norm"].reshape(g, -1),
             eps).reshape(t, -1)
    return x + act(y) @ lp["w_out"]


def attention(lp, x, sizes, cast=None):
    """A `*` layer on x [T, H]."""
    lp, act = _leaves(lp, cast)
    t = x.shape[0]
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    h = act(_rms(x, lp["norm"], sizes["layer_norm_epsilon"]))
    o = attention_all_pairs((h @ lp["wq"]).reshape(t, hq, d),
                            (h @ lp["wk"]).reshape(t, hk, d),
                            (h @ lp["wv"]).reshape(t, hk, d))
    return x + act(o.reshape(t, hq * d)) @ lp["wo"]


def router_scores(lp, x, sizes):
    """x [T, H] -> sigmoid scores [T, E] of an `E` layer's router."""
    return jax.nn.sigmoid(_rms(x, lp["norm"].astype(f32),
                               sizes["layer_norm_epsilon"]) @
                          lp["router"].astype(f32))


def route(scores, bias, sizes):
    """scores [T, E] -> (picks [T, k], weight of EVERY expert [T, E],
    zero where not picked)."""
    _, picks = jax.lax.top_k(scores + bias, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    w = sizes["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(scores.shape[0])[:, None]
    return picks, jnp.zeros_like(scores).at[rows, picks].set(w)


def held_experts(m, lp, weights, sizes, cast):
    """sum over the HELD experts e of weights[:, e] * Expert_e(m), one
    expert's two matrices read at a time, at the published width. lp
    holds EVERY expert layer's matrices `[L, held, ...]` as the
    benchmark made them and `layer`, which of them this is."""
    act = (lambda y: y) if cast is None else cast
    rnd = (lambda w: w.astype(f32)) if cast is None else \
        (lambda w: cast(w.astype(f32)))
    m_in, layer = act(m), lp["layer"]
    width = sizes["moe_intermediate_size"]
    first, held = sizes.get("first_expert", 0), lp["w_up"].shape[1]

    def one(total, xs):
        e, share = xs
        w_up = rnd(lp["w_up"][layer, e][:, :width])
        w_down = rnd(lp["w_down"][layer, e][:width])
        return total + share[:, None] * _relu2(m_in, w_up, w_down, act), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(held), weights[:, first:first + held].T))
    return total


def experts(lp, x, sizes, cast=None):
    """(an `E` layer on x [T, H], its picks [T, k])."""
    scores = router_scores(lp, x, sizes)
    lp, act = _leaves(lp, cast)
    m = _rms(x, lp["norm"], sizes["layer_norm_epsilon"])
    picks, weights = route(scores, lp["expert_bias"], sizes)
    y = held_experts(m, lp, weights, sizes, cast)
    if sizes.get("first_expert", 0) == 0:
        y = y + _relu2(act(m), lp["shared_up"], lp["shared_down"], act)
    return x + y, picks


def split(flat, sizes):
    """(top-level leaves, [(letter, one layer's leaves)] in the
    pattern's order; an `E` layer's hold the routed experts' matrices
    of EVERY expert layer, whole, and `layer`, its index among
    them)."""
    top = {k: v for k, v in flat.items() if "." not in k}
    whole = {k: flat["x." + k] for k in EXPERTS}
    layers, n_experts = [], 0
    for i, (unit, n) in enumerate(runs(sizes["hybrid_override_pattern"])):
        for j in range(n):
            for letter in unit:
                prefix = f"r{i:02d}.{letter}."
                lp = {k[len(prefix):]: v[j] for k, v in flat.items()
                      if k.startswith(prefix)}
                if letter == "E":
                    lp.update(whole, layer=n_experts)
                    n_experts += 1
                layers.append((letter, lp))
    return top, layers


def _through(flat, ids, sizes, cast, upto=None):
    """(hidden [T, H] after the layers before layer `upto` (default:
    all), [picks [T, k]] of the expert layers among them)."""
    top, layers = split(flat, sizes)
    x, picked = top["embed"][ids].astype(f32), []
    for letter, lp in layers[:upto]:
        if letter == "M":
            x = mamba(lp, x, sizes, cast)
        elif letter == "*":
            x = attention(lp, x, sizes, cast)
        else:
            x, picks = experts(lp, x, sizes, cast)
            picked.append(picks)
    return x, picked


def hidden(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, H], the last layer's output."""
    with jax.default_matmul_precision("highest"):
        return _through(flat, ids, sizes, cast)[0]


def router_picks(flat, ids, row, sizes, cast=None):
    """The experts that every expert layer picks for row `row` of the
    tokens `ids` [T]: [expert layers, k] int32."""
    with jax.default_matmul_precision("highest"):
        picked = _through(flat, ids, sizes, cast)[1]
        return jnp.stack([p[row] for p in picked]).astype(jnp.int32)


def ssm_state(flat, ids, n, sizes, at_layer, cast=None):
    """What a program that keeps the state-space mixer's state must
    hold for the `at_layer`-th `M` layer once it has taken in the
    first `n` of the tokens `ids` [T]: for every head, with a_r =
    exp(dt_r A),

        H = sum_{s < n} (prod_{s < r < n} a_r) dt_s xs_s B_s^T

    each token's term written out, no recurrence. Returns [heads, P,
    N] float32."""
    with jax.default_matmul_precision("highest"):
        layers = split(flat, sizes)[1]
        index = [i for i, (letter, _) in enumerate(layers)
                 if letter == "M"][at_layer]
        x = _through(flat, ids, sizes, cast, upto=index)[0]
        lp, act = _leaves(layers[index][1], cast)
        h = _rms(x, lp["norm"], sizes["layer_norm_epsilon"])
        _, xs, B, _, dt, A = _ssm_inputs(lp, act, h, sizes)
        seen = (jnp.arange(ids.shape[0]) < n)[:, None]
        cum = jnp.cumsum(jnp.where(seen, dt * A, 0.0), axis=0)
        w = jnp.where(seen, jnp.exp(cum[-1] - cum) * dt, 0.0)   # [T, heads]
        nh, g = xs.shape[1], B.shape[1]
        H = jnp.einsum("sgep,sgn->gepn",
                       (w[..., None] * xs).reshape(-1, g, nh // g,
                                                   xs.shape[-1]), B)
        return H.reshape(nh, xs.shape[-1], B.shape[-1])


def _column_blocks(v, most=16384):
    """The fewest equal blocks of at most `most` columns that `v`
    columns divide into (1 where none does)."""
    return next((n for n in range(-(-v // most), v // 128 + 1)
                 if v % n == 0), 1)


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the head (untied from the embedding), a block of
    the head's columns at a time."""
    head = flat["head"]
    h, v = head.shape
    n = _column_blocks(v)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, flat["norm_f"].astype(f32), sizes["layer_norm_epsilon"])
        blocks = jax.lax.map(
            lambda i: x @ jax.lax.dynamic_slice(
                head, (0, i * (v // n)), (h, v // n)).astype(f32),
            jnp.arange(n))
        return jnp.moveaxis(blocks, 0, 1).reshape(x.shape[0], v)


def logits(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, V]: for small sizes."""
    return logits_of(flat, hidden(flat, ids, sizes, cast), sizes)
