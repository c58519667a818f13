"""Trinity (Arcee, `model_type: afmoe`): a decoder of a few dense
layers and then expert layers, three layers of sliding-window
attention with rotary positions to every layer of full attention
WITHOUT positional rotation, grouped-query heads with a norm on every
query and key head and a sigmoid gate on the attention output, sandwich
norms round both halves of a layer. Serving only: there is no training
path here.

Per layer, on hidden x [B, T, H] (RMSNorm: w * x / rms(x), eps 1e-5):

    h = norm_in(x)
    q = q_norm(h W_q)  k = k_norm(h W_k)  per head over d;  v = h W_v
    g = h W_g                                        [Hq d]
    a sliding layer: q, k take rotary positions (theta 10,000) and a
    query at t sees keys in (t - window, t]; a full layer: no rotation,
    keys [0, t]
    o = mixer: softmax(q k^T / sqrt(d)) v
    a = x + norm_post_attn((o * sigmoid(g)) W_o)
    m = norm_pre_mlp(a)
    a dense layer (the first `num_dense_layers`):
        y = (silu(m W_gate) * (m W_up)) W_down       width intermediate_size
    an expert layer (`moe/serving.py::expert_layer`):
        s = sigmoid(m W_r) in float32; the k experts of largest
        s + expert_bias; weights route_scale * s / sum(s) over the picks;
        y = Shared(m) + sum_j w_j Expert_j(m), every one a gated SiLU
        MLP of width moe_intermediate_size; no token dropped
    x = a + norm_post_mlp(y)

Embeddings are multiplied by sqrt(H) (`mup_enabled`); logits are
norm_f(x) W_head, the head untied; no projection has a bias.

ONE functional `block` holds that. Whether a layer slides is an
OPERAND (`lp["sliding"]`, one flag a layer in the stack it is scanned
over), not a Python branch a layer; whether it is dense or an expert
layer is the shape of its weights, so the dense layers and the expert
layers are two stacks (`stacks`) that the one `engine.scan_layers`
runs one after the other. The experts' own matrices are not scanned
over: `block` takes every expert layer's whole (`lp["experts"]`) with
the layer's index among them, and the grouped product reads that
layer's where they lie. The block hands its `mixer` the rows' q, k,
v, `mixer(q, k, v, cache) -> (o [B, T, Hq d], cache)`, and knows
nothing of pages, tables or slots: this module's own full-sequence
`forward` hands it dense attention under the band mask, the serving
engine (`inference/engine.py`, kind "paged+window", which composes
`embed`, `block`, `head` and `stacks` and imports nothing from here)
the mixer over two page pools. The block returns a third value, what
its expert layer counted (`COUNTERS`; zeros from a dense layer), and a
fourth, what it read off every row (`ROW_READINGS`: the experts the
row picked; -1 from a dense layer).

Parameters are a plain dict, a stack's leaves stacked [n, ...]:

    embed [V, H]   head [H, V]   norm_f [H]
    dense, layers:  norm_in, norm_post_attn, norm_pre_mlp,
                    norm_post_mlp [n, H]   q_norm, k_norm [n, d]
                    wq, wg [n, H, Hq d]  wk, wv [n, H, Hk d]  wo [n, Hq d, H]
    dense:   w_gate, w_up [n, H, F]   w_down [n, F, H]
    layers:  router [n, H, E]   expert_bias [n, E] float32
             w_gate, w_up [n, E, H, I]   w_down [n, E, I, H]
             shared_gate, shared_up [n, H, Is]   shared_down [n, Is, H]

wq and wk, whose output is split into heads for the norm, are read
where they lie in both stacks, through `brumby.head_projection` (it
has why).
"""

import dataclasses
import functools
import sys
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.brumby import head_projection, rms_norm, rope
from deepspeed_tpu.moe import serving as moe
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)

f32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
# what `block` counts a launch, summed over its expert layers
COUNTERS = moe.COUNTERS
# what `block` reads off every row, a layer: the k experts it picked
ROW_READINGS = ("moe_picks",)


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/arcee-ai/Trinity-Mini), then what the
    config does not carry and this program assumes (see
    `benchmark/configs/trinity-mini.json`, `assumed`)."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_scale: float = 2.826
    mup_enabled: bool = True
    # one entry a layer held; None: the published pattern, every
    # fourth layer full
    layer_types: Tuple[str, ...] = None
    # assumed
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # what `InferenceEngine` reads off every model config
    cache_kind = "paged+window"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if (i + 1) % 4 == 0 else SLIDING
                for i in range(self.num_hidden_layers)))
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types {self.layer_types} does not name the kind "
                f"of each of {self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key/value heads")
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError(
                f"{self.num_dense_layers} dense layers of "
                f"{self.num_hidden_layers}: no expert layer is left")

    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    shared_width = property(lambda self: self.num_shared_experts *
                            self.moe_intermediate_size)


def init_params(cfg, key):
    """Normal(initializer_range) projections, the residual
    projections scaled by 1/sqrt(2 L), norm weights 1, the selection
    bias 0."""
    H, F, I, E = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.moe_intermediate_size, cfg.num_experts)
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    r = cfg.initializer_range
    rs = r / (2 * cfg.num_hidden_layers) ** 0.5
    nd, ne = cfg.num_dense_layers, cfg.num_hidden_layers - cfg.num_dense_layers
    draw = lambda k, shape, std: (std * jax.random.normal(
        k, shape, f32)).astype(cfg.param_dtype)

    def stack(key, n, feed_forward):
        shapes = {"wq": ((n, H, hq * d), r), "wg": ((n, H, hq * d), r),
                  "wk": ((n, H, hk * d), r), "wv": ((n, H, hk * d), r),
                  "wo": ((n, hq * d, H), rs), **feed_forward}
        keys = jax.random.split(key, len(shapes))
        out = {name: draw(k, *spec) for k, (name, spec) in
               zip(keys, sorted(shapes.items()))}
        ones = lambda *shape: jnp.ones(shape, cfg.param_dtype)
        out.update({name: ones(n, H) for name in (
            "norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")},
            q_norm=ones(n, d), k_norm=ones(n, d))
        return out

    kd, ke, k1, k2 = jax.random.split(key, 4)
    dense = stack(kd, nd, {"w_gate": ((nd, H, F), r), "w_up": ((nd, H, F), r),
                           "w_down": ((nd, F, H), rs)})
    Is = cfg.shared_width
    experts = stack(ke, ne, {
        "router": ((ne, H, E), r),
        "w_gate": ((ne, E, H, I), r), "w_up": ((ne, E, H, I), r),
        "w_down": ((ne, E, I, H), rs),
        "shared_gate": ((ne, H, Is), r), "shared_up": ((ne, H, Is), r),
        "shared_down": ((ne, Is, H), rs)})
    experts["expert_bias"] = jnp.zeros((ne, E), f32)
    return {"embed": draw(k1, (cfg.vocab_size, H), r),
            "head": draw(k2, (H, cfg.vocab_size), r),
            "norm_f": jnp.ones((H,), cfg.param_dtype),
            "dense": dense, "layers": experts}


def attend(cfg, lp, hidden, positions, mixer, cache):
    """The attention half of a layer: x -> a = x + norm_post_attn(
    gated attention of norm_in(x)). Returns (a, cache)."""
    b, t, _ = hidden.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    eps, dtype = cfg.rms_norm_eps, cfg.dtype
    w = lambda name: lp[name].astype(dtype)
    with jax.named_scope(SCOPE_ATTN_QKV):
        h = rms_norm(hidden, lp["norm_in"], eps).astype(dtype)
        q = rms_norm(head_projection(h, w("wq")).reshape(b, t, hq, d),
                     lp["q_norm"], eps)
        k = rms_norm(head_projection(h, w("wk")).reshape(b, t, hk, d),
                     lp["k_norm"], eps)
        v, gate = h @ w("wv"), h @ w("wg")
        theta = float(cfg.rope_theta)
        q = jnp.where(lp["sliding"], rope(q, positions, theta), q)
        k = jnp.where(lp["sliding"], rope(k, positions, theta), k)
    o, cache = mixer(q.reshape(b, t, hq * d), k.reshape(b, t, hk * d), v,
                     cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        o = o.astype(dtype) * jax.nn.sigmoid(gate.astype(f32)).astype(dtype)
        return hidden + rms_norm(o @ w("wo"), lp["norm_post_attn"],
                                 eps), cache


def feed_forward_input(cfg, lp, a):
    """What a layer's feed-forward (and an expert layer's router)
    reads: norm_pre_mlp(a) in the compute type."""
    return rms_norm(a, lp["norm_pre_mlp"], cfg.rms_norm_eps) \
        .astype(cfg.dtype)


def feed_forward(cfg, lp, a):
    """The other half: a -> a + norm_post_mlp(FF(norm_pre_mlp(a))),
    FF the dense gated MLP or the expert layer by the weights `lp`
    holds. Returns (hidden, counts int32 [len(COUNTERS)], picks int32
    [B T, k]: zeros and -1 from a dense layer)."""
    b, t, H = a.shape
    with jax.named_scope(SCOPE_MLP):
        m = feed_forward_input(cfg, lp, a)
        if "router" in lp:
            y, counts, picks = moe.expert_layer(
                m.reshape(b * t, H), lp, lp["experts"], lp["expert_layer"],
                cfg.num_experts_per_tok, cfg.route_scale)
            y = y.reshape(b, t, H)
        else:
            w = lambda name: lp[name].astype(cfg.dtype)
            y = moe.gated_mlp(m, w("w_gate"), w("w_up"), w("w_down"))
            counts = jnp.zeros((len(COUNTERS),), jnp.int32)
            picks = jnp.full((b * t, cfg.num_experts_per_tok), -1, jnp.int32)
        return (a + rms_norm(y, lp["norm_post_mlp"], cfg.rms_norm_eps),
                counts, picks)


def block(cfg, lp, hidden, positions, mixer, cache):
    """One layer on hidden [B, T, H] at `positions` [B, T]; lp: the
    layer's weights and `sliding`, whether it attends over the
    window. Returns (hidden, cache, counts int32 [len(COUNTERS)],
    (picks int32 [B T, k],): `ROW_READINGS`)."""
    a, cache = attend(cfg, lp, hidden, positions, mixer, cache)
    hidden, counts, picks = feed_forward(cfg, lp, a)
    return hidden, cache, counts, (picks,)


def embed(cfg, params, tokens, positions):
    """Positions are rotary, applied in `block`: not read here."""
    x = params["embed"][tokens].astype(f32)
    if cfg.mup_enabled:
        x = x * cfg.hidden_size ** 0.5
    return x.astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits in the compute type."""
    x = rms_norm(hidden, params["norm_f"], cfg.rms_norm_eps)
    return x.astype(cfg.dtype) @ params["head"].astype(cfg.dtype)


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def stacks(cfg, params):
    """[(scanned, whole)] in order, the dense layers and then the
    expert layers: `block` is scanned over `scanned` (a layer's
    weights, its `sliding` flag and, for an expert layer, its index
    `expert_layer` among them) and takes `whole` as it is beside each
    layer's slice: the experts' own matrices of EVERY expert layer
    (`experts`), which the grouped product reads where they lie."""
    slides = np.asarray([kind == SLIDING for kind in cfg.layer_types])
    nd = cfg.num_dense_layers
    rest = {k: v for k, v in params["layers"].items()
            if k not in EXPERT_LEAVES}
    both = (
        (dict(params["dense"], sliding=jnp.asarray(slides[:nd])), {}),
        (dict(rest, sliding=jnp.asarray(slides[nd:]),
              expert_layer=jnp.arange(len(slides) - nd, dtype=jnp.int32)),
         {"experts": {k: params["layers"][k] for k in EXPERT_LEAVES}}))
    return both[0 if nd else 1:]


def layers(params):
    """The expert layers' stacked [n, ...] leaves (`stacks` has every
    layer)."""
    return params["layers"]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def band_attention(q, k, v, slides, window):
    """q [B, T, Hq, d]; k, v [B, T, Hk, d]: softmax attention of
    query t over keys [0, t], or (t - window, t] where `slides`."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    qg = q.reshape(b, t, -1, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(f32) / d ** 0.5
    at = jnp.arange(t)
    seen = (at[None, :] <= at[:, None]) & \
        (~slides | (at[None, :] > at[:, None] - window))
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v).reshape(
        b, t, hq * d)


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: dense attention under the
    band mask, nothing kept."""
    b, t = ids.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    def layer(whole, hidden, lp):
        def mixer(q, k, v, cache):
            return band_attention(
                q.reshape(b, t, hq, d), k.reshape(b, t, hk, d),
                v.reshape(b, t, hk, d), lp["sliding"],
                cfg.sliding_window), cache
        return block(cfg, {**lp, **whole}, hidden, positions, mixer,
                     None)[0], None

    hidden = embed(cfg, params, ids, positions)
    for scanned, whole in stacks(cfg, params):
        hidden, _ = jax.lax.scan(functools.partial(layer, whole), hidden,
                                 scanned)
    return head(cfg, params, hidden)
