"""The plain reference: the `afmoe` decoder (Arcee Trinity; the
`afmoe` model code of `transformers` beside its `config.json`) in
`jax.numpy` and float32 at matmul precision "highest". All-pairs
attention under the band mask, a loop over the experts, no cache, no
sort, no grouped product, no kernels, and no import from the program.

Per layer, on x [T, H] (RMSNorm: w * x / rms(x), eps `rms_norm_eps`):

    h = norm_in(x)
    q = q_norm(h W_q), k = k_norm(h W_k) per head over head_dim; v = h W_v
    g = h W_g
    a layer of type "sliding_attention": q, k under rotary positions
    (theta `rope_theta`, the two halves of a head rotated against each
    other), query t sees keys (t - sliding_window, t];
    "full_attention": no rotation, keys [0, t]
    a = x + norm_post_attn((softmax(q k^T / sqrt(d)) v * sigmoid(g)) W_o)
    m = norm_pre_mlp(a)
    a dense layer:   y = (silu(m W_gate) * (m W_up)) W_down
    an expert layer: s = sigmoid(m W_r); picks = the
        `num_experts_per_tok` experts of largest s + expert_bias;
        w = route_scale * s[picks] / sum(s[picks]);
        y = Shared(m) + sum over EVERY expert e of
            [e in picks] w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e
    x = a + norm_post_mlp(y)

The embedding is multiplied by sqrt(H) (`mup_enabled`); logits are
norm_f(x) W_head, the head untied.

Every expert is computed for every token and its result multiplied by
the token's weight for it (zero where the token did not pick it): 16
times the program's work, and no row is ever moved. The experts'
matrices are read ONE EXPERT AT A TIME (`lax.scan` over the experts,
each indexed out of the benchmark's own stacked arrays), so that
neither a layer's float32 copy (3.2 GB) nor a layer sliced out of the
stack (1.6 GB) ever exists beside the weights. Attention takes a block of
query rows at a time.

Which layers are held is the configuration's: `kept_layers` lists the
published layers kept (their types from the published `layer_types`),
the first `num_dense_layers` of them dense.

Weights come as the flat dict of `benchmark/weights_afmoe.py` (`d.*`
the dense layers' leaves, `h.*` the expert layers', stacked; any
dtype: read as float32). One sequence at a time: `hidden` gives the
last layer's output [T, H], `logits_of` the logits of chosen rows,
`router_picks` the experts every expert layer picks for one row.

Departures from the published code: none in the mathematics. The
published router adds 1e-20 to the sum of the picked scores; so does
this. `n_group`, `topk_group`, `num_expert_groups` are 1 in the
configuration: no grouped selection. The published code keeps the
router in float32 and the rest in the checkpoint's type; everything
here is float32.
"""

import jax
import jax.numpy as jnp

f32 = jnp.float32
ATTENTION = ("wq", "wk", "wv", "wg", "wo")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
EXPERTS = ("w_gate", "w_up", "w_down")      # of the routed experts


def rounded_to(dtype):
    """Operands of every projection (attention's five, a dense
    layer's three, every expert's and the shared expert's three)
    rounded to `dtype` and read back as float32: the reference
    computed in a lower precision, which is what a control is. The
    router stays float32, as the published code keeps it."""
    return lambda x: x.astype(dtype).astype(f32)


def layer_kinds(sizes):
    """[(slides, dense)] of the layers held, in order."""
    kept = sizes.get("kept_layers", range(sizes["num_hidden_layers"]))
    types = [sizes["layer_types"][i] for i in kept]
    return [(t == "sliding_attention", i < sizes["num_dense_layers"])
            for i, t in enumerate(types)]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, d] at positions 0..T-1."""
    t, _, d = x.shape
    half = d // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(t, dtype=f32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention_all_pairs(q, k, v, window=None, rows=512):
    """q [T, Hq, d]; k, v [T, Hk, d] -> [T, Hq, d]: softmax attention,
    query head j against key/value head j // (Hq // Hk), query t over
    keys [0, t] or, with a window, (t - window, t]; every pair, `rows`
    query rows at a time."""
    t, hq, d = q.shape
    hk = k.shape[1]
    rows = min(rows, t)
    n = -(-t // rows)
    qb = jnp.pad(q, ((0, n * rows - t), (0, 0), (0, 0))).reshape(
        n, rows, hk, hq // hk, d)
    at = jnp.arange(n * rows).reshape(n, rows)
    key_at = jnp.arange(t)[None, :]

    def one_block(xs):
        qr, tr = xs
        scores = jnp.einsum("thgd,ihd->hgti", qr, k) / jnp.sqrt(f32(d))
        seen = tr[:, None] >= key_at
        if window is not None:
            seen = seen & (key_at > tr[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgti,ihd->thgd", p, v)

    o = jax.lax.map(one_block, (qb, at))
    return o.reshape(n * rows, hq, d)[:t]


def _gated(m, w_gate, w_up, w_down, act):
    return act(jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route(m, router, bias, sizes):
    """m [T, H] -> (picks [T, k], weight of EVERY expert [T, E], zero
    where not picked)."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ router)
    _, picks = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, picks, axis=-1)
    w = sizes["route_scale"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(m.shape[0])[:, None]
    return picks, jnp.zeros_like(s).at[rows, picks].set(w)


def experts(m, lp, weights, cast):
    """sum over every expert e of weights[:, e] * Expert_e(m), one
    expert's matrices read at a time. lp holds EVERY expert layer's
    matrices `[L, E, ...]` as the benchmark made them and `layer`,
    which of them this is: a layer sliced out of the stack would be a
    copy (1.6 GB at the published sizes, and one for each layer at
    once)."""
    act = (lambda y: y) if cast is None else cast
    rnd = (lambda w: w.astype(f32)) if cast is None else \
        (lambda w: cast(w.astype(f32)))
    m_in, layer = act(m), lp["layer"]

    def one(total, xs):
        e, share = xs
        w_gate, w_up, w_down = (rnd(lp[k][layer, e]) for k in EXPERTS)
        y = _gated(m_in, w_gate, w_up, w_down, act)
        return total + share[:, None] * y, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(weights.shape[1]), weights.T))
    return total


def _small(lp, cast, names):
    """The layer's leaves as float32 except the experts' own
    matrices, the projections in `names` rounded under a control."""
    out = {k: v if k in EXPERTS + ("layer",) and "router" in lp
           else v.astype(f32) for k, v in lp.items()}
    if cast is None:
        return out, lambda y: y
    return {k: cast(v) if k in names else v for k, v in out.items()}, cast


def attend(lp, x, sizes, slides, cast=None):
    """The attention half: x -> a. lp: one layer's leaves."""
    lp, act = _small(lp, cast, ATTENTION)
    t = x.shape[0]
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    h = act(_rms(x, lp["norm_in"], eps))
    q = _rms((h @ lp["wq"]).reshape(t, hq, d), lp["q_norm"], eps)
    k = _rms((h @ lp["wk"]).reshape(t, hk, d), lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(t, hk, d)
    if slides:
        q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    o = attention_all_pairs(q, k, v,
                            sizes["sliding_window"] if slides else None)
    o = o.reshape(t, hq * d) * jax.nn.sigmoid(h @ lp["wg"])
    return x + _rms(act(o) @ lp["wo"], lp["norm_post_attn"], eps)


def feed_forward(lp, a, sizes, dense, cast=None):
    """(a -> x, the picks [T, k] of an expert layer or None)."""
    eps = sizes["rms_norm_eps"]
    lp, act = _small(lp, cast, DENSE if dense else SHARED)
    m = _rms(a, lp["norm_pre_mlp"], eps)
    if dense:
        y, picks = _gated(act(m), lp["w_gate"], lp["w_up"], lp["w_down"],
                          act), None
    else:
        picks, weights = route(m, lp["router"], lp["expert_bias"], sizes)
        y = _gated(act(m), lp["shared_gate"], lp["shared_up"],
                   lp["shared_down"], act) + experts(m, lp, weights, cast)
    return a + _rms(y, lp["norm_post_mlp"], eps), picks


def split(flat, sizes):
    """(top-level leaves, [one layer's leaves] in the order held; an
    expert layer's hold the routed experts' matrices of EVERY expert
    layer, whole, and `layer`, its index among them)."""
    top = {k: v for k, v in flat.items() if k[:2] not in ("d.", "h.")}
    nd = sizes["num_dense_layers"]
    whole = {k: flat["h." + k] for k in EXPERTS}
    return top, [
        {k[2:]: v[i] for k, v in flat.items() if k[:2] == "d."} if dense
        else dict({k[2:]: v[i - nd] for k, v in flat.items()
                   if k[:2] == "h." and k[2:] not in EXPERTS},
                  layer=i - nd, **whole)
        for i, (_, dense) in enumerate(layer_kinds(sizes))]


def _embedded(top, ids, sizes):
    x = top["embed"][ids].astype(f32)
    return x * sizes["hidden_size"] ** 0.5 if sizes["mup_enabled"] else x


def _through(flat, ids, sizes, cast):
    """(hidden [T, H], [picks [T, k]] of the expert layers)."""
    top, blocks = split(flat, sizes)
    x, picked = _embedded(top, ids, sizes), []
    for lp, (slides, dense) in zip(blocks, layer_kinds(sizes)):
        a = attend(lp, x, sizes, slides, cast)
        x, picks = feed_forward(lp, a, sizes, dense, cast)
        if picks is not None:
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


def _column_blocks(v, most=16384):
    """The fewest equal blocks of at most `most` columns that `v`
    columns divide into (1 where none does)."""
    return next((n for n in range(-(-v // most), v // 128 + 1)
                 if v % n == 0), 1)


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the head (untied from the embedding), a block of
    the head's columns at a time: its float32 copy (1.6 GB at the
    published vocabulary) never exists whole."""
    head = flat["head"]
    h, v = head.shape
    n = _column_blocks(v)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, flat["norm_f"].astype(f32), sizes["rms_norm_eps"])
        blocks = jax.lax.map(
            lambda i: x @ jax.lax.dynamic_slice(
                head, (0, i * (v // n)), (h, v // n)).astype(f32),
            jnp.arange(n))
        return jnp.moveaxis(blocks, 0, 1).reshape(x.shape[0], v)


def logits(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, V]: for small sizes."""
    return logits_of(flat, hidden(flat, ids, sizes, cast), sizes)
