"""Brumby (Manifest AI, `model_type: brumby`): the rotary / RMSNorm /
grouped-query / gated-SiLU decoder block, with softmax attention
replaced in every layer by gated power retention
(`deepspeed_tpu/ops/retention`). Serving only: there is no backward of
the chunked scan and no training path.

Per layer, on hidden x [B, T, H] (pre-norm residual block):

    h  = RMSNorm(x; norm_in)
    q, k, v = h Wq, h Wk, h Wv        -> heads of head_dim
    q  = RoPE(RMSNorm_head(q; q_norm), pos)     k likewise (k_norm)
    lg = log_sigmoid(h Wg + bg)       float32, one gate a key/value
                                      head and token
    o  = mixer(q, k, v, lg, state)    retention over the tokens so far
    x  = x + o Wo
    m  = RMSNorm(x; norm_post)
    x  = x + (silu(m Wgate) * (m Wup)) Wdown

and after the last layer logits = RMSNorm(x; norm_f) W_head, the head
untied from the embedding. No projection has a bias; the gate's bg
[Hk] is the model's one bias (what sets how long a head remembers).

ONE functional `block` holds that. Its `mixer` is the retention call:
the model's own full-sequence `forward` hands it `retention_chunked`
from zero state, the serving engine's prefill program the same from
the slot's state, its decode program `retention_decode` (one token of
every slot: a kernel on the chip, `retention_step` elsewhere; the
recurrent kind's two mixers in `inference/engine.py`, which composes `embed`,
`block`, `head` and `layers` and imports nothing from here). There is
no second copy of the block.

Parameters are a plain dict, the layers' leaves stacked [n_layer, ...]
under "layers" (what `engine.scan_layers` scans over):

    embed [V, H]   head [H, V]   norm_f [H]
    layers: norm_in [L, H]  wq [L, H, Hq*d]  wk, wv [L, H, Hk*d]
            wg [L, H, Hk]   bg [L, Hk]   q_norm, k_norm [L, d]
            wo [L, Hq*d, H]
            norm_post [L, H]  w_gate, w_up [L, H, F]  w_down [L, F, H]

wq, wk and wv are read where they lie, through `head_projection`: the
chip's compiler would otherwise slice each out of the stack and
transpose it in every layer of every launch, because their output is
split into heads.
"""

import dataclasses
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from deepspeed_tpu.ops.retention import retention_chunked
from deepspeed_tpu.ops.retention import state_dim as _state_dim
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/manifestai/Brumby-14B-Base), then what the
    config does not carry and this program assumes (see
    `benchmark/configs/brumby-14b.json`, `assumed`)."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    gate_bias_init: float = 4.0
    # assumed
    retention_degree: int = 2
    retention_eps: float = 1e-6     # guard on the normaliser
    retention_chunk: int = 128      # tokens whose pairs are taken directly
    dtype: Any = jnp.bfloat16       # compute dtype
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the state and its normaliser

    # what `InferenceEngine` reads off every model config: the kind of
    # cache the layers keep, and the module whose `embed`, `block`,
    # `head` and `layers` it composes with that kind's mixers
    cache_kind = "recurrent"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        if self.retention_degree != 2:
            raise ValueError("power retention is implemented for degree 2 "
                             f"only, got {self.retention_degree}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key/value heads")

    # the names the serving engine reads off every model config
    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    retention_scale = property(lambda self: 1.0 / self.head_dim)
    state_dim = property(lambda self: _state_dim(self.head_dim))

    @property
    def state_slot_shapes(self):
        """((shape, dtype), ...) of ONE slot's state in ONE layer: the
        matrix and its normaliser per key/value head."""
        lead = (self.num_key_value_heads, self.state_dim)
        dtype = np.dtype(self.state_dtype)
        return ((lead + (self.head_dim,), dtype), (lead, dtype))


def init_params(cfg, key):
    """Normal(initializer_range) projections, the two residual
    projections scaled by 1/sqrt(2 L), norm weights 1, the gate's bias
    at `gate_bias_init` (gates near 1: a memory of about e^bias
    tokens)."""
    L, H, F = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    r = cfg.initializer_range
    rs = r / (2 * L) ** 0.5
    shapes = {"wq": ((L, H, hq * d), r), "wk": ((L, H, hk * d), r),
              "wv": ((L, H, hk * d), r), "wg": ((L, H, hk), r),
              "wo": ((L, hq * d, H), rs), "w_gate": ((L, H, F), r),
              "w_up": ((L, H, F), r), "w_down": ((L, F, H), rs)}
    keys = jax.random.split(key, len(shapes) + 2)
    draw = lambda k, shape, std: (std * jax.random.normal(
        k, shape, f32)).astype(cfg.param_dtype)
    layers = {name: draw(keys[i], *spec)
              for i, (name, spec) in enumerate(sorted(shapes.items()))}
    ones = lambda *shape: jnp.ones(shape, cfg.param_dtype)
    layers.update(norm_in=ones(L, H), norm_post=ones(L, H),
                  q_norm=ones(L, d), k_norm=ones(L, d),
                  bg=cfg.gate_bias_init * ones(L, hk))
    return {"embed": draw(keys[-2], (cfg.vocab_size, H), r),
            "head": draw(keys[-1], (H, cfg.vocab_size), r),
            "norm_f": ones(H), "layers": layers}


def rms_norm(x, weight, eps):
    """float32 statistics, the result in x's type."""
    x32 = x.astype(f32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight.astype(f32)).astype(x.dtype)


def head_projection(x, w):
    """x [..., in] @ w [in, out], for a product whose output the
    caller splits into heads: the output is pinned row-major (`out`
    minor), so that the split re-lays the ACTIVATION. Left to itself
    the chip's compiler makes the split free by producing the heads
    apart, which wants `w` with `in` minor: it then writes a layer's
    `w` out of the scanned stack and transposes it, in every layer of
    every launch (117 MB a layer of Sarvam-105B, a tenth of that cell's
    device time: PERF.md section 6, PR 40). Pinned, the product reads
    the layer's `w` out of the stack where it lies."""
    return with_layout_constraint(
        x @ w, Layout(major_to_minor=tuple(range(x.ndim))))


def rope(x, positions, theta):
    """Rotary positions on x [B, T, heads, d], the two halves of a
    head rotated against each other (the source family's
    `rotate_half`); angles in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = positions.astype(f32)[..., None, None] * freq      # [B, T, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def block(cfg, lp, hidden, positions, mixer, state):
    """One layer on hidden [B, T, H] at `positions` [B, T]. `mixer(q,
    k, v, lg, state) -> (o [B, T, Hq, d], state)` is the retention
    call, and `state` whatever it keeps between calls."""
    b, t, _ = hidden.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    eps = cfg.rms_norm_eps
    with jax.named_scope(SCOPE_ATTN_QKV):
        h = rms_norm(hidden, lp["norm_in"], eps).astype(cfg.dtype)
        heads = lambda name, n: head_projection(
            h, lp[name].astype(cfg.dtype)).reshape(b, t, n, d)
        q, k, v = heads("wq", hq), heads("wk", hk), heads("wv", hk)
        lg = jax.nn.log_sigmoid(jnp.dot(
            h, lp["wg"].astype(cfg.dtype), preferred_element_type=f32) +
            lp["bg"].astype(f32))
        q = rope(rms_norm(q, lp["q_norm"], eps), positions, cfg.rope_theta)
        k = rope(rms_norm(k, lp["k_norm"], eps), positions, cfg.rope_theta)
    o, state = mixer(q, k, v, lg, state)
    with jax.named_scope(SCOPE_ATTN_OUT):
        o = o.astype(cfg.dtype).reshape(b, t, hq * d)
        hidden = hidden + o @ lp["wo"].astype(cfg.dtype)
    with jax.named_scope(SCOPE_MLP):
        m = rms_norm(hidden, lp["norm_post"], eps).astype(cfg.dtype)
        y = jax.nn.silu(m @ lp["w_gate"].astype(cfg.dtype)) * \
            (m @ lp["w_up"].astype(cfg.dtype))
        hidden = hidden + y @ lp["w_down"].astype(cfg.dtype)
    return hidden, state


def embed(cfg, params, tokens, positions):
    """Positions are rotary, applied in `block`: not read here."""
    return params["embed"][tokens].astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits in the compute type."""
    x = rms_norm(hidden, params["norm_f"], cfg.rms_norm_eps)
    return x.astype(cfg.dtype) @ params["head"].astype(cfg.dtype)


def layers(params):
    """The stacked [n_layer, ...] leaves `block` takes one layer of."""
    return params["layers"]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def zero_state(cfg, rows):
    """(S [rows, Hk, D, d], z [rows, Hk, D]) of one layer, zero."""
    hk, d = cfg.num_key_value_heads, cfg.head_dim
    return (jnp.zeros((rows, hk, cfg.state_dim, d), cfg.state_dtype),
            jnp.zeros((rows, hk, cfg.state_dim), cfg.state_dtype))


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: every layer's retention in
    its chunked form from zero state, nothing kept."""
    b, t = ids.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    def mixer(q, k, v, lg, state):
        o, _, _ = retention_chunked(
            q, k, v, lg, *zero_state(cfg, b), cfg.retention_scale,
            cfg.retention_eps, cfg.retention_chunk)
        return o, state

    def layer(hidden, lp):
        return block(cfg, lp, hidden, positions, mixer, None)[0], None

    hidden, _ = jax.lax.scan(layer, embed(cfg, params, ids, positions),
                             layers(params))
    return head(cfg, params, hidden)
