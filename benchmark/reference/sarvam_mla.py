"""The plain reference: the `sarvam_mla` decoder (Sarvam-105B;
multi-head latent attention as the DeepSeek-V2/V3 model code of
`transformers` has it, which the family's `config.json` keys name) in
`jax.numpy` and float32 at matmul precision "highest". The EXPANDED
form only: every head's keys and values are made from the compressed
vector through W_kvb and attended to all-pairs under the causal mask.
No absorption, no cache, no sort, no grouped product, no kernels, and
no import from the program.

Per layer, on x [T, H] (RMSNorm: w * x / rms(x), eps `rms_norm_eps`;
Hq heads, a query head `qk_nope_head_dim` values without position and
`qk_rope_head_dim` rotary):

    h = norm_in(x)
    q = q_norm(h W_q) per head over q_head_dim -> (q_nope, q_rope)
    (c, r) = h W_kva;  c~ = kv_norm(c) over kv_lora_rank
    k_rope = RoPE(r), one for all heads;  q_rope = RoPE(q_rope)
    (k_nope_i, v_i) = c~ W_kvb,i a head
    s_i(t, s) = (q_nope_i . k_nope_i,s + q_rope_i . k_rope,s) * scale,
        keys [0, t];  scale = q_head_dim^-0.5 * mscale^2,
        mscale = 0.1 mscale_all_dim ln(factor) + 1
    a = x + concat_i(softmax(s_i) v_i) W_o
    m = norm_mlp(a)
    a dense layer (the first `first_k_dense_replace`):
        y = (silu(m W_gate) * (m W_up)) W_down
    an expert layer: s = sigmoid(m W_r); picks = the
        `num_experts_per_tok` experts of largest s + expert_bias;
        w = routed_scaling_factor * s[picks] / (sum(s[picks]) + 1e-20);
        y = Shared(m) + sum over every expert e HELD of
            [e in picks] w_e (silu(m W_gate_e) * (m W_up_e)) W_down_e
    x' = a + y

RoPE: the frequencies of YaRN (`rope_scaling`, type deepseek_yarn):
frequency j of qk_rope_head_dim / 2 is theta^(-2j/d) below the lower
correction dim, that / factor above the upper, a linear blend between;
mscale = mscale_all_dim leaves cos and sin unscaled; the two halves of
the rotary part rotated against each other. Embeddings are unscaled;
logits are norm_f(x) W_head, the head untied.

**The share.** The configuration's `num_experts` experts from
`first_expert` (0 where the file has no such key) are held; the router
scores `published.num_experts` and every row keeps its
`num_experts_per_tok` picks; what the experts not held would add is
left out (the shared expert is added by the share that holds expert
0), and that partial result goes on to the next layer. The vocabulary
is the slice the weights hold.

Every held expert is computed for every token and its result
multiplied by the token's weight for it (zero where the token did not
pick it), ONE EXPERT AT A TIME (`lax.scan` over the held experts, each
indexed out of the benchmark's own stacked arrays): neither a layer's
float32 copy nor a layer sliced out of the stack ever exists beside
the weights. Attention takes a block of query rows at a time.

Weights come as the flat dict of `benchmark/weights_sarvam_mla.py`
(`d.*` the dense layers' leaves, `h.*` the expert layers', stacked;
any dtype: read as float32). One sequence at a time: `hidden` gives
the last layer's output [T, H], `logits_of` the logits of chosen rows,
`router_picks` the experts every expert layer picks for one row,
`latent_rows` what a cache of latent rows would hold of chosen layers:
[c~ ; k_rope] [T, kv_lora_rank + qk_rope_head_dim].

Departures from the published description: none known in the
mathematics; what `config.json` does not carry as an equation is
listed under `assumed` in the configuration file (pre-norm residuals,
the norms that `use_qk_norm` names, the rotation by halves, the
router).
"""

import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
ATTENTION = ("wq", "w_kva", "w_kvb", "wo")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
EXPERTS = ("w_gate", "w_up", "w_down")      # of the routed experts


def rounded_to(dtype):
    """Operands of every projection (attention's four, the dense
    layer's three, every expert's and the shared expert's three)
    rounded to `dtype` and read back as float32: the reference
    computed in a lower precision, which is what a control is. The
    router stays float32."""
    return lambda x: x.astype(dtype).astype(f32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def yarn_frequencies(sizes):
    """[qk_rope_head_dim / 2] float32."""
    d, theta = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    y = sizes["rope_scaling"]
    j = jnp.arange(d // 2, dtype=f32)
    plain = theta ** (-2.0 * j / d)

    def correction_dim(turns):
        return d * math.log(y["original_max_position_embeddings"] /
                            (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), d - 1)
    ramp = jnp.clip((j - low) / (high - low if high > low else 1e-3), 0, 1)
    return plain / y["factor"] * ramp + plain * (1 - ramp)


def softmax_scale(sizes):
    y = sizes["rope_scaling"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0 \
        if y["factor"] > 1 else 1.0
    return sizes["q_head_dim"] ** -0.5 * m * m


def _rope(x, freq):
    """x [T, heads, d] at positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=f32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention_all_pairs(q_nope, q_rope, k_nope, k_rope, v, scale, rows=128):
    """q_nope [T, Hq, n], q_rope [T, Hq, r]; k_nope [T, Hq, n], k_rope
    [T, r] (one for all heads), v [T, Hq, dv] -> [T, Hq, dv]: softmax
    attention of query t over keys [0, t]; every pair, `rows` query
    rows at a time."""
    t = q_nope.shape[0]
    rows = min(rows, t)
    n = -(-t // rows)
    pad = lambda x: jnp.pad(x, ((0, n * rows - t), (0, 0), (0, 0))).reshape(
        (n, rows) + x.shape[1:])
    at = jnp.arange(n * rows).reshape(n, rows)
    key_at = jnp.arange(t)[None, :]

    def one_block(xs):
        qn, qr, tr = xs
        scores = (jnp.einsum("thn,shn->hts", qn, k_nope) +
                  jnp.einsum("thr,sr->hts", qr, k_rope)) * scale
        p = jax.nn.softmax(
            jnp.where(tr[:, None] >= key_at, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shv->thv", p, v)

    o = jax.lax.map(one_block, (pad(q_nope), pad(q_rope), at))
    return o.reshape((n * rows,) + v.shape[1:])[:t]


def _gated(m, w_gate, w_up, w_down, act):
    return act(jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def router_scores(lp, a, sizes):
    """An expert layer's scores [T, E] of the attended rows a [T, H]:
    what `route` picks by, for `weights_sarvam_mla.balanced_bias`."""
    m = _rms(a, lp["norm_mlp"].astype(f32), sizes["rms_norm_eps"])
    return jax.nn.sigmoid(m @ lp["router"].astype(f32))


def route(m, router, bias, sizes):
    """m [T, H] -> (picks [T, k], weight of EVERY expert the router
    scores [T, E], zero where not picked)."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(m @ router)
    _, picks = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, picks, axis=-1)
    w = sizes["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(m.shape[0])[:, None]
    return picks, jnp.zeros_like(s).at[rows, picks].set(w)


def experts(m, lp, weights, cast):
    """sum over every HELD expert e of weights[:, e] * Expert_e(m),
    one expert's matrices read at a time. lp holds EVERY expert
    layer's held matrices `[L, held, ...]` as the benchmark made them,
    `layer`, which of them this is, and `first_expert`, the expert
    that the first of the held is."""
    act = (lambda y: y) if cast is None else cast
    rnd = (lambda w: w.astype(f32)) if cast is None else \
        (lambda w: cast(w.astype(f32)))
    m_in, layer, first = act(m), lp["layer"], lp["first_expert"]
    held = lp[EXPERTS[0]].shape[1]

    def one(total, xs):
        e, share = xs
        w_gate, w_up, w_down = (rnd(lp[k][layer, e]) for k in EXPERTS)
        y = _gated(m_in, w_gate, w_up, w_down, act)
        return total + share[:, None] * y, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(held), weights.T[first:first + held]))
    return total


def _small(lp, cast, names):
    """The layer's leaves as float32 except the experts' own
    matrices, the projections in `names` rounded under a control."""
    keep = EXPERTS + ("layer", "first_expert")
    out = {k: v if k in keep and "router" in lp else v.astype(f32)
           for k, v in lp.items()}
    if cast is None:
        return out, lambda y: y
    return {k: cast(v) if k in names else v for k, v in out.items()}, cast


def attend(lp, x, sizes, cast=None):
    """The attention half: x -> (a, the latent rows [c~ ; k_rope]
    [T, rank + r]). lp: one layer's leaves."""
    lp, act = _small(lp, cast, ATTENTION)
    t = x.shape[0]
    hq, n, r, dv, rank = (
        sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"],
        sizes["kv_lora_rank"])
    eps, freq = sizes["rms_norm_eps"], yarn_frequencies(sizes)
    h = act(_rms(x, lp["norm_in"], eps))
    q = _rms((h @ lp["wq"]).reshape(t, hq, n + r), lp["q_norm"], eps)
    kva = h @ lp["w_kva"]
    c = _rms(kva[:, :rank], lp["kv_norm"], eps)
    k_rope = _rope(kva[:, None, rank:], freq)[:, 0]
    kv = (act(c) @ lp["w_kvb"]).reshape(t, hq, n + dv)
    o = attention_all_pairs(
        act(q[..., :n]), act(_rope(q[..., n:], freq)), act(kv[..., :n]),
        act(k_rope), act(kv[..., n:]), softmax_scale(sizes))
    return (x + act(o.reshape(t, hq * dv)) @ lp["wo"],
            jnp.concatenate([c, k_rope], -1))


def feed_forward(lp, a, sizes, dense, cast=None):
    """(a -> x, the picks [T, k] of an expert layer or None)."""
    lp, act = _small(lp, cast, DENSE if dense else SHARED)
    m = _rms(a, lp["norm_mlp"], sizes["rms_norm_eps"])
    if dense:
        return a + _gated(act(m), lp["w_gate"], lp["w_up"], lp["w_down"],
                          act), None
    picks, weights = route(m, lp["router"], lp["expert_bias"], sizes)
    y = experts(m, lp, weights, cast)
    if lp["first_expert"] == 0:
        y = y + _gated(act(m), lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"], act)
    return a + y, picks


def split(flat, sizes):
    """(top-level leaves, [(one layer's leaves, whether it is dense)]
    in order; an expert layer's hold the held experts' matrices of
    EVERY expert layer, whole, `layer`, its index among them, and
    `first_expert`)."""
    top = {k: v for k, v in flat.items() if k[:2] not in ("d.", "h.")}
    nd = sizes["first_k_dense_replace"]
    whole = dict({k: flat["h." + k] for k in EXPERTS},
                 first_expert=int(sizes.get("first_expert", 0)))
    return top, [
        ({k[2:]: v[i] for k, v in flat.items() if k[:2] == "d."}, True)
        if i < nd else
        (dict({k[2:]: v[i - nd] for k, v in flat.items()
               if k[:2] == "h." and k[2:] not in EXPERTS},
              layer=i - nd, **whole), False)
        for i in range(sizes["num_hidden_layers"])]


def _through(flat, ids, sizes, cast):
    """(hidden [T, H], [picks [T, k]] of the expert layers, [latent
    rows [T, rank + r]] of every layer)."""
    top, blocks = split(flat, sizes)
    x, picked, latent = top["embed"][ids].astype(f32), [], []
    for lp, dense in blocks:
        a, rows = attend(lp, x, sizes, cast)
        x, picks = feed_forward(lp, a, sizes, dense, cast)
        latent.append(rows)
        if picks is not None:
            picked.append(picks)
    return x, picked, latent


def hidden(flat, ids, sizes, cast=None):
    """[T] tokens -> [T, H], the last layer's output."""
    with jax.default_matmul_precision("highest"):
        return _through(flat, ids, sizes, cast)[0]


def router_picks(flat, ids, row, sizes, cast=None):
    """The experts that every expert layer picks for row `row` of the
    tokens `ids` [T]: [expert layers, k] int32."""
    return picks_and_latent_rows(flat, ids, row, (), sizes, cast)[0]


def latent_rows(flat, ids, layers, sizes, cast=None):
    """[c~ ; k_rope] of every token of `ids` in each of `layers`:
    [len(layers), T, rank + r] float32."""
    return picks_and_latent_rows(flat, ids, 0, layers, sizes, cast)[1]


def picks_and_latent_rows(flat, ids, row, layers, sizes, cast=None):
    """`router_picks` of row `row` and `latent_rows` of `layers` from
    one pass."""
    with jax.default_matmul_precision("highest"):
        _, picked, latent = _through(flat, ids, sizes, cast)
        return (jnp.stack([p[row] for p in picked]).astype(jnp.int32),
                jnp.stack([latent[i] for i in layers]) if layers else None)


def _column_blocks(v, most=16384):
    """The fewest equal blocks of at most `most` columns that `v`
    columns divide into (1 where none does)."""
    return next((n for n in range(-(-v // most), v // 128 + 1)
                 if v % n == 0), 1)


def logits_of(flat, x, sizes):
    """Rows x [R, H] of `hidden` -> [R, V] float32 logits through the
    final norm and the head (untied from the embedding), a block of
    the head's columns at a time: its float32 copy never exists
    whole."""
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
