"""Sarvam-105B (`model_type: sarvam_mla`): a decoder of one dense
layer and then expert layers, every layer attending by multi-head
LATENT attention: keys and values are expanded per head from one
compressed vector a token, and a rotary part that all heads share
rides beside it. Serving only: there is no training path here.

Per layer, on hidden x [B, T, H] (RMSNorm: w * x / rms(x), eps 1e-6;
64 heads; a query head is 128 values without position + 64 rotary):

    h  = norm_in(x)
    q  = q_norm(h W_q) per head over its 192  -> (q_nope [64, 128],
                                                  q_rope [64, 64])
    (c, r) = h W_kva  [512 + 64];  c~ = kv_norm(c);  k_rope = RoPE(r),
                                   ONE for all heads; q_rope = RoPE(q_rope)
    (k_nope_i, v_i) = c~ W_kvb,i   [128 + 128] a head
    s_i(t, s) = (q_nope_i . k_nope_i,s + q_rope_i . k_rope,s) * scale
    o_i = sum_s softmax_s(s_i) v_i,s;   a = x + concat_i(o_i) W_o
    m  = norm_mlp(a)
    the dense layer (the first `first_k_dense_replace`):
        y = (silu(m W_gate) * (m W_up)) W_down      width intermediate_size
    an expert layer (`moe/serving.py::expert_layer`):
        s = sigmoid(m W_r) in float32; the k experts of largest
        s + expert_bias; weights routed_scaling_factor * s / sum(s)
        over the picks; y = Shared(m) + sum_j w_j Expert_j(m), every
        one a gated SiLU MLP of width moe_intermediate_size
    x' = a + y

RoPE is YaRN's (`rope_scaling` deepseek_yarn, `yarn_frequencies`):
each of the 32 frequencies a blend of theta^(-2j/64) and the same /
factor; mscale = mscale_all_dim, so cos/sin are unscaled and scale =
192^-0.5 * (0.1 ln(factor) + 1)^2. Embeddings are unscaled; logits are
norm_f(x) W_head, the head untied; no projection has a bias.

**Absorbed.** The same numbers without ever expanding a key: with
q^_i = W_kvb,i^K q_nope_i in R^512,

    s_i = (q^_i . c~_s + q_rope_i . k_rope,s) * scale
    o_i = W_kvb,i^V^T (sum_s p_s c~_s)

so a token's cache row in a layer is [c~ ; k_rope], `latent_row` = 576
values for all 64 heads, and it is key and value at once. `block`
does that: it projects, absorbs W_kvb^K into the query (SCOPE_MLA_ABSORB),
hands its `mixer` the 64 scaled query rows of 576 and the ONE latent
row a token, `mixer(q [B, T, 64, 576], row [B, T, 576], cache) ->
(o [B, T, 64, 512], cache, live [B, T] bool or None)`, and applies
W_kvb^V (SCOPE_MLA_ABSORB again) and W_o. `live` says which rows are
a request's (an idle slot's and a chunk's pad rows are not; None:
every row is): the others are kept out of the experts' products
(`moe/serving.py`, "Rows of no request"). It knows nothing of pages, tables or slots: the
serving engine (`inference/latent_kind.py`, kind "paged+latent", which
composes `embed`, `block`, `head` and `stacks` and imports nothing from
here) hands it the mixer over the latent page pool. This module's own
full-sequence `forward` uses the EXPANDED form (`attend_expanded`:
dense attention under the causal mask over per-head keys and values).

**The chip's share.** `num_experts` is the router's width, whole
everywhere; `experts_held` of them from `first_expert` are held here
(`moe/serving.py` has the contract: rows routed elsewhere contribute
zero, the share holding expert 0 adds the shared expert).

The dense layers and the expert layers are two stacks (`stacks`) that
the one `engine.scan_layers` runs one after the other; the experts'
own matrices are not scanned over (`lp["experts"]`, whole, with the
layer's index among them). The block returns a third value, what its
expert layer counted (`COUNTERS`; zeros from a dense layer), and a
fourth, what it read off every row (`ROW_READINGS`: the experts the
row picked; -1 from a dense layer).

Parameters are a plain dict, a stack's leaves stacked [n, ...]:

    embed [V, H]   head [H, V]   norm_f [H]
    dense, layers:  norm_in, norm_mlp [n, H]  q_norm [n, 192]
                    kv_norm [n, 512]  wq [n, H, 64 192]
                    w_kva [n, H, 576]  w_kvb [n, 512, 64 256] (a head's
                    128 key columns, then its 128 value columns)
                    wo [n, 64 128, H]
    dense:   w_gate, w_up [n, H, F]   w_down [n, F, H]
    layers:  router [n, H, E]   expert_bias [n, E] float32
             w_gate, w_up [n, held, H, I]   w_down [n, held, I, H]
             shared_gate, shared_up [n, H, Is]   shared_down [n, Is, H]

wq, whose output is split into heads, is read where it lies in both
stacks, through `brumby.head_projection` (it has why). w_kvb is itself
split into heads, and the absorbed products batch over them: the chip's
compiler still re-lays a layer's 16.8 MB of it, heads outermost, in
every launch (PERF.md section 7, from PR 40).
"""

import dataclasses
import functools
import math
import sys
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.brumby import head_projection, rms_norm
from deepspeed_tpu.moe import serving as moe
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLA_ABSORB, SCOPE_MLP)

f32 = jnp.float32
# what `block` counts a launch, summed over its expert layers
COUNTERS = moe.COUNTERS
# what `block` reads off every row, a layer: the k experts it picked
ROW_READINGS = ("moe_picks",)
YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 40), ("mscale", 1),
        ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096),
        ("type", "deepseek_yarn"))


@dataclasses.dataclass(frozen=True)
class SarvamMLAConfig:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/sarvamai/sarvam-105b), then the chip's
    share, then what the config does not carry and this program
    assumes (see `benchmark/configs/sarvam-105b.json`, `assumed`)."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 32
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_head_dim: int = 192
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    head_dim: int = 576
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # `rope_scaling` as sorted (key, value) pairs: a frozen config
    rope_scaling: Tuple[Tuple[str, Any], ...] = YARN
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    moe_router_enable_expert_bias: bool = True
    use_qk_norm: bool = True
    # the chip's share of every expert layer: None, all of them
    experts_held: int = None
    first_expert: int = 0
    # assumed
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # what `InferenceEngine` reads off every model config
    cache_kind = "paged+latent"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        if not (self.use_qk_norm and self.moe_router_enable_expert_bias):
            raise ValueError(
                "use_qk_norm and moe_router_enable_expert_bias are true in "
                "the published config and the block has no other path: the "
                "query heads and the latent are normed, the selection bias "
                "is added")
        if self.q_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim \
                or self.head_dim != self.latent_row:
            raise ValueError(
                f"q_head_dim {self.q_head_dim} is not qk_nope_head_dim + "
                f"qk_rope_head_dim, or head_dim {self.head_dim} not "
                f"kv_lora_rank + qk_rope_head_dim = {self.latent_row}: "
                "the one cached row")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"{self.first_k_dense_replace} dense layers of "
                f"{self.num_hidden_layers}: no expert layer is left")
        if not 0 <= self.first_expert <= \
                self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert} .. {self.first_expert} + "
                f"{self.experts_held} are not among {self.num_experts}")

    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    n_head = property(lambda self: self.num_attention_heads)
    # one token's cache row in a layer: [c~ ; k_rope]
    latent_row = property(lambda self: self.kv_lora_rank +
                          self.qk_rope_head_dim)
    shared_width = property(lambda self: self.num_shared_experts *
                            self.moe_intermediate_size)

    @property
    def softmax_scale(self):
        """q_head_dim^-0.5 * mscale^2, mscale = 0.1 mscale_all_dim
        ln(factor) + 1 (YaRN's correction of the softmax's
        temperature at the stretched positions)."""
        yarn = dict(self.rope_scaling)
        m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0 \
            if yarn["factor"] > 1 else 1.0
        return self.q_head_dim ** -0.5 * m * m


def yarn_frequencies(dim, theta, factor, original, beta_fast, beta_slow):
    """The dim / 2 rotary frequencies of YaRN (`deepseek_yarn`):
    frequency j is theta^(-2j/dim) where a period fits more than
    `beta_fast` times into the `original` positions (j below the lower
    correction dim), that / factor where it fits fewer than
    `beta_slow` times (above the upper), and a linear blend of the two
    between. float64 numpy: constants of the program."""
    j = np.arange(dim // 2, dtype=np.float64)
    plain = float(theta) ** (-2.0 * j / dim)
    turns_at = lambda turns: dim * math.log(
        original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def frequencies(cfg):
    yarn = dict(cfg.rope_scaling)
    return yarn_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta, yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"])


def rope(x, positions, freq):
    """Rotary positions on x [B, T, heads, d] at the d / 2 frequencies
    `freq`, the two halves of a head rotated against each other;
    angles in float32."""
    half = x.shape[-1] // 2
    ang = positions.astype(f32)[..., None, None] * jnp.asarray(freq, f32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def init_params(cfg, key):
    """Normal(initializer_range) projections, the residual
    projections scaled by 1/sqrt(2 L), norm weights 1, the selection
    bias 0."""
    H, F, I, E = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.moe_intermediate_size, cfg.num_experts)
    held, hq = cfg.experts_held, cfg.num_attention_heads
    r = cfg.initializer_range
    rs = r / (2 * cfg.num_hidden_layers) ** 0.5
    nd = cfg.first_k_dense_replace
    ne = cfg.num_hidden_layers - nd
    draw = lambda k, shape, std: (std * jax.random.normal(
        k, shape, f32)).astype(cfg.param_dtype)

    def stack(key, n, feed_forward):
        shapes = {"wq": ((n, H, hq * cfg.q_head_dim), r),
                  "w_kva": ((n, H, cfg.latent_row), r),
                  "w_kvb": ((n, cfg.kv_lora_rank, hq * (
                      cfg.qk_nope_head_dim + cfg.v_head_dim)), r),
                  "wo": ((n, hq * cfg.v_head_dim, H), rs), **feed_forward}
        keys = jax.random.split(key, len(shapes))
        out = {name: draw(k, *spec) for k, (name, spec) in
               zip(keys, sorted(shapes.items()))}
        ones = lambda *shape: jnp.ones(shape, cfg.param_dtype)
        out.update(norm_in=ones(n, H), norm_mlp=ones(n, H),
                   q_norm=ones(n, cfg.q_head_dim),
                   kv_norm=ones(n, cfg.kv_lora_rank))
        return out

    kd, ke, k1, k2 = jax.random.split(key, 4)
    dense = stack(kd, nd, {"w_gate": ((nd, H, F), r), "w_up": ((nd, H, F), r),
                           "w_down": ((nd, F, H), rs)})
    Is = cfg.shared_width
    experts = stack(ke, ne, {
        "router": ((ne, H, E), r),
        "w_gate": ((ne, held, H, I), r), "w_up": ((ne, held, H, I), r),
        "w_down": ((ne, held, I, H), rs),
        "shared_gate": ((ne, H, Is), r), "shared_up": ((ne, H, Is), r),
        "shared_down": ((ne, Is, H), rs)})
    experts["expert_bias"] = jnp.zeros((ne, E), f32)
    return {"embed": draw(k1, (cfg.vocab_size, H), r),
            "head": draw(k2, (H, cfg.vocab_size), r),
            "norm_f": jnp.ones((H,), cfg.param_dtype),
            "dense": dense, "layers": experts}


def project(cfg, lp, hidden, positions):
    """What both forms of attention start from: (q_nope [B, T, Hq,
    128], q_rope [B, T, Hq, 64] rotated, c~ [B, T, 512], k_rope [B, T,
    64] rotated), in the compute type."""
    b, t, _ = hidden.shape
    hq, eps, dtype = cfg.num_attention_heads, cfg.rms_norm_eps, cfg.dtype
    h = rms_norm(hidden, lp["norm_in"], eps).astype(dtype)
    q = head_projection(h, lp["wq"].astype(dtype)).reshape(
        b, t, hq, cfg.q_head_dim)
    q = rms_norm(q, lp["q_norm"], eps)
    kva = h @ lp["w_kva"].astype(dtype)
    c = rms_norm(kva[..., :cfg.kv_lora_rank], lp["kv_norm"], eps)
    r = kva[..., cfg.kv_lora_rank:]
    freq = frequencies(cfg)
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], positions, freq)
    k_rope = rope(r[:, :, None, :], positions, freq)[:, :, 0]
    return q[..., :cfg.qk_nope_head_dim], q_rope, c, k_rope


def expansion(cfg, lp):
    """W_kvb as (W^K [512, Hq, 128], W^V [512, Hq, 128])."""
    w = lp["w_kvb"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def attend(cfg, lp, hidden, positions, mixer, cache):
    """The attention half of a layer, absorbed: x -> a = x + Attn(
    norm_in(x)) W_o through the mixer over latent rows. Returns (a,
    cache, live: the mixer's)."""
    b, t, _ = hidden.shape
    dtype = cfg.dtype
    with jax.named_scope(SCOPE_ATTN_QKV):
        q_nope, q_rope, c, k_rope = project(cfg, lp, hidden, positions)
        with jax.named_scope(SCOPE_MLA_ABSORB):
            q_abs = jnp.einsum("bthn,chn->bthc", q_nope,
                               expansion(cfg, lp)[0],
                               preferred_element_type=f32)
        q = jnp.concatenate([q_abs, q_rope.astype(f32)], -1) * \
            cfg.softmax_scale
        row = jnp.concatenate([c, k_rope], -1)
    o, cache, live = mixer(q.astype(dtype), row, cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        with jax.named_scope(SCOPE_MLA_ABSORB):
            o = jnp.einsum("bthc,chv->bthv", o.astype(dtype),
                           expansion(cfg, lp)[1],
                           preferred_element_type=f32).astype(dtype)
        return hidden + o.reshape(b, t, -1) @ lp["wo"].astype(dtype), \
            cache, live


def attend_expanded(cfg, lp, hidden, positions):
    """The same half, expanded: every head's keys and values from c~
    through W_kvb, dense attention under the causal mask. x -> a."""
    b, t, _ = hidden.shape
    dtype = cfg.dtype
    w_k, w_v = expansion(cfg, lp)
    q_nope, q_rope, c, k_rope = project(cfg, lp, hidden, positions)
    k_nope = jnp.einsum("bsc,chn->bshn", c, w_k)
    v = jnp.einsum("bsc,chv->bshv", c, w_v)
    scores = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope).astype(f32) +
              jnp.einsum("bthr,bsr->bhts", q_rope, k_rope).astype(f32)) * \
        cfg.softmax_scale
    seen = positions[:, None, :, None] >= positions[:, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    o = jnp.einsum("bhts,bshv->bthv", p.astype(dtype), v)
    return hidden + o.reshape(b, t, -1) @ lp["wo"].astype(dtype)


def feed_forward(cfg, lp, a, live=None):
    """The other half: a -> a + FF(norm_mlp(a)), FF the dense gated
    MLP or the expert layer by the weights `lp` holds; rows that are
    no request's (`live` [B, T] false) go to no routed expert. Returns
    (hidden, counts int32 [len(COUNTERS)], picks int32 [B T, k]: zeros
    and -1 from a dense layer)."""
    b, t, H = a.shape
    with jax.named_scope(SCOPE_MLP):
        m = rms_norm(a, lp["norm_mlp"], cfg.rms_norm_eps).astype(cfg.dtype)
        if "router" in lp:
            y, counts, picks = moe.expert_layer(
                m.reshape(b * t, H), lp, lp["experts"], lp["expert_layer"],
                cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                first_expert=cfg.first_expert,
                live=None if live is None else live.reshape(b * t))
            y = y.reshape(b, t, H)
        else:
            w = lambda name: lp[name].astype(cfg.dtype)
            y = moe.gated_mlp(m, w("w_gate"), w("w_up"), w("w_down"))
            counts = jnp.zeros((len(COUNTERS),), jnp.int32)
            picks = jnp.full((b * t, cfg.num_experts_per_tok), -1, jnp.int32)
        return a + y, counts, picks


def block(cfg, lp, hidden, positions, mixer, cache):
    """One layer on hidden [B, T, H] at `positions` [B, T]. Returns
    (hidden, cache, counts int32 [len(COUNTERS)], (picks int32 [B T,
    k],): `ROW_READINGS`)."""
    a, cache, live = attend(cfg, lp, hidden, positions, mixer, cache)
    hidden, counts, picks = feed_forward(cfg, lp, a, live)
    return hidden, cache, counts, (picks,)


def embed(cfg, params, tokens, positions):
    """Positions are rotary, applied in `block`: not read here."""
    return params["embed"][tokens].astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits in the compute type."""
    x = rms_norm(hidden, params["norm_f"], cfg.rms_norm_eps)
    return x.astype(cfg.dtype) @ params["head"].astype(cfg.dtype)


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def stacks(cfg, params):
    """[(scanned, whole)] in order, the dense layers and then the
    expert layers: `block` is scanned over `scanned` (a layer's
    weights and, for an expert layer, its index `expert_layer` among
    them) and takes `whole` as it is beside each layer's slice: the
    held experts' own matrices of EVERY expert layer (`experts`),
    which the grouped product reads where they lie."""
    nd = cfg.first_k_dense_replace
    rest = {k: v for k, v in params["layers"].items()
            if k not in EXPERT_LEAVES}
    both = (
        (params["dense"], {}),
        (dict(rest, expert_layer=jnp.arange(cfg.num_hidden_layers - nd,
                                            dtype=jnp.int32)),
         {"experts": {k: params["layers"][k] for k in EXPERT_LEAVES}}))
    return both[0 if nd else 1:]


def layers(params):
    """The expert layers' stacked [n, ...] leaves (`stacks` has every
    layer)."""
    return params["layers"]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: the expanded form, nothing
    kept."""
    b, t = ids.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    def layer(whole, hidden, lp):
        lp = {**lp, **whole}
        a = attend_expanded(cfg, lp, hidden, positions)
        return feed_forward(cfg, lp, a)[0], None

    hidden = embed(cfg, params, ids, positions)
    for scanned, whole in stacks(cfg, params):
        hidden, _ = jax.lax.scan(functools.partial(layer, whole), hidden,
                                 scanned)
    return head(cfg, params, hidden)
