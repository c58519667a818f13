"""Falcon-H1 (TII, `model_type: falcon_h1`): a decoder whose every
layer runs a Mamba-2 state-space mixer and softmax attention with
grouped-query heads IN PARALLEL on one normed input, adds both to the
residual and follows them with a gated SiLU feed-forward; muP
multipliers from the config scale nearly every projection. Serving
only: there is no backward of the chunked scan and no training path.

Per layer, on hidden x [B, T, H] (eps 1e-5 throughout):

    h   = RMSNorm(x; norm_in)
    # the state-space branch (arXiv:2405.21060): d_ssm = heads x P,
    # state width N, G groups of heads sharing B and C, conv width K
    u   = ((h * ssm_in_multiplier) W_in) * m      m: ssm_multipliers
                                                  over z | xs | B | C | dt
    z, xBC, dt = split(u)
    dt  = softplus(dt + dt_bias)   A = -exp(A_log)          float32
    y   = mixer: silu(causal_conv(xBC)) -> xs, B, C;
          H_t = exp(dt_t A) H_{t-1} + dt_t xs_t B_t^T;  y_t = H_t C_t + D xs_t
    y   = GroupRMSNorm(y * silu(z); ssm_norm, G groups)
    o_ssm = (y W_out) * ssm_out_multiplier
    # the attention branch: Hq query heads over Hk key/value heads
    q   = (h * attention_in_multiplier) Wq      v likewise
    k   = ((h * attention_in_multiplier) Wk) * key_multiplier
    o   = mixer: softmax_causal(RoPE(q) RoPE(k)^T / sqrt(d)) v
    o_att = (o Wo) * attention_out_multiplier
    x   = x + o_ssm + o_att
    m   = RMSNorm(x; norm_ff)
    x   = x + ((silu((m W_gate) * mlp_multipliers[0]) * (m W_up)) W_down)
              * mlp_multipliers[1]

Embeddings are multiplied by `embedding_multiplier`; logits are
RMSNorm(x; norm_f) W_head * lm_head_multiplier, the head untied. No
projection has a bias; the convolution has one.

ONE functional `block` holds that. It hands its `mixer` BOTH branches'
projections in one call, `mixer((q, k, v), (xBC, dt, A, D, conv_w,
conv_b), cache) -> ((o [B, T, Hq*d], y [B, T, d_ssm]), cache)`, and
knows nothing of pages, tables, slots or state arrays: the model's own
full-sequence `forward` hands it dense causal attention and the chunked
scan from zero state; the serving engine's composite kind
(`inference/engine.py`, which composes `embed`, `block`, `head` and
`layers` and imports nothing from here) the paged mixer beside a
mixer over (convolution rows, state matrix).

Parameters are a plain dict, the layers' leaves stacked [n_layer, ...]
under "layers" (what `engine.scan_layers` scans over):

    embed [V, H]   head [H, V]   norm_f [H]
    layers: norm_in [L, H]
            w_in [L, H, 2 d_ssm + 2 G N + heads]
            conv_w [L, d_ssm + 2 G N, K]   conv_b [L, d_ssm + 2 G N]
            dt_bias, A_log, D [L, heads]   ssm_norm [L, d_ssm]
            w_out [L, d_ssm, H]
            wq [L, H, Hq*d]  wk, wv [L, H, Hk*d]  wo [L, Hq*d, H]
            norm_ff [L, H]  w_gate, w_up [L, H, F]  w_down [L, F, H]

wq and wk, whose output is split into heads for the rotation, are read
where they lie, through `brumby.head_projection` (it has why).
"""

import dataclasses
import sys
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.brumby import head_projection, rms_norm, rope
from deepspeed_tpu.ops.ssm import causal_conv, split_xbc, ssd_chunked
from deepspeed_tpu.ops.transformer.flash_attention import dense_attention
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct), then what
    the config does not carry and this program assumes (see
    `benchmark/configs/falcon-h1-34b.json`, `assumed`)."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    # assumed
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16           # compute dtype; the conv's rows
    param_dtype: Any = jnp.bfloat16
    ssm_state_dtype: Any = jnp.float32  # the state matrix H

    # what `InferenceEngine` reads off every model config: the kind of
    # cache the layers keep, and the module whose `embed`, `block`,
    # `head` and `layers` it composes with that kind's mixers
    cache_kind = "paged+state"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide "
                f"over {self.num_key_value_heads} key/value heads")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm or \
                self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"{self.mamba_n_heads} state-space heads of "
                f"{self.mamba_d_head} in {self.mamba_n_groups} groups do "
                f"not make up d_ssm {self.mamba_d_ssm}")

    # the names the serving engine reads off every model config
    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    # x | B | C: what the convolution runs over
    conv_dim = property(lambda self: self.mamba_d_ssm + 2 *
                        self.mamba_n_groups * self.mamba_d_state)
    # z | x | B | C | dt: the state-space branch's input projection
    segments = property(lambda self: (
        self.mamba_d_ssm, self.mamba_d_ssm,
        self.mamba_n_groups * self.mamba_d_state,
        self.mamba_n_groups * self.mamba_d_state, self.mamba_n_heads))

    @property
    def state_slot_shapes(self):
        """((shape, dtype), ...) of ONE slot's state in ONE layer, in
        the order the engine's state kind keeps them: the
        convolution's carried rows, the state matrix."""
        return (((self.mamba_d_conv - 1, self.conv_dim),
                 np.dtype(self.dtype)),
                ((self.mamba_n_heads, self.mamba_d_head,
                  self.mamba_d_state), np.dtype(self.ssm_state_dtype)))


def init_params(cfg, key):
    """Normal(initializer_range) projections, the three residual
    projections scaled by 1/sqrt(2 L), norm weights 1; the state-space
    scalars by Mamba-2's published initialisation: A_log = log U[1,
    16], dt_bias the inverse softplus of a log-uniform dt in [0.001,
    0.1], D = 1; the convolution uniform in +-1/sqrt(K) like
    `torch.nn.Conv1d`."""
    L, H, F = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    nh, K = cfg.mamba_n_heads, cfg.mamba_d_conv
    r = cfg.initializer_range
    rs = r / (2 * L) ** 0.5
    shapes = {"w_in": ((L, H, sum(cfg.segments)), r),
              "w_out": ((L, cfg.mamba_d_ssm, H), rs),
              "wq": ((L, H, hq * d), r), "wk": ((L, H, hk * d), r),
              "wv": ((L, H, hk * d), r), "wo": ((L, hq * d, H), rs),
              "w_gate": ((L, H, F), r), "w_up": ((L, H, F), r),
              "w_down": ((L, F, H), rs)}
    keys = jax.random.split(key, len(shapes) + 6)
    draw = lambda k, shape, std: (std * jax.random.normal(
        k, shape, f32)).astype(cfg.param_dtype)
    layers = {name: draw(keys[i], *spec)
              for i, (name, spec) in enumerate(sorted(shapes.items()))}
    ones = lambda *shape: jnp.ones(shape, cfg.param_dtype)
    uniform = lambda k, shape, lo, hi: jax.random.uniform(
        k, shape, f32, lo, hi)
    dt = jnp.exp(uniform(keys[-6], (L, nh), np.log(1e-3), np.log(1e-1)))
    layers.update(
        norm_in=ones(L, H), norm_ff=ones(L, H),
        ssm_norm=ones(L, cfg.mamba_d_ssm),
        conv_w=uniform(keys[-5], (L, cfg.conv_dim, K), -K ** -0.5,
                       K ** -0.5).astype(cfg.param_dtype),
        conv_b=uniform(keys[-4], (L, cfg.conv_dim), -K ** -0.5,
                       K ** -0.5).astype(cfg.param_dtype),
        A_log=jnp.log(uniform(keys[-3], (L, nh), 1.0, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)), D=jnp.ones((L, nh), f32))
    return {"embed": draw(keys[-2], (cfg.vocab_size, H), r),
            "head": draw(keys[-1], (H, cfg.vocab_size), r),
            "norm_f": ones(H), "layers": layers}


def group_rms_norm(x, weight, groups, eps):
    """RMSNorm over each of `groups` equal parts of the last axis
    (Mamba-2's gated norm with `norm_before_gate` false: the caller
    has already multiplied the gate in); float32 statistics, the
    result in x's type."""
    parts = x.astype(f32).reshape(x.shape[:-1] + (groups, -1))
    y = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, -1, keepdims=True) + eps)
    return (y.reshape(x.shape) * weight.astype(f32)).astype(x.dtype)


def mup_vector(cfg):
    """[sum(segments)] float32: `ssm_multipliers` laid over the input
    projection's five segments."""
    return np.concatenate([np.full(n, m, np.float32) for n, m in
                           zip(cfg.segments, cfg.ssm_multipliers)])


def block(cfg, lp, hidden, positions, mixer, cache):
    """One layer on hidden [B, T, H] at `positions` [B, T]. `mixer`
    takes both branches' inputs at once (the module's docstring) and
    `cache` is whatever it keeps between calls."""
    b, t, _ = hidden.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    eps, dtype = cfg.rms_norm_eps, cfg.dtype
    w = lambda name: lp[name].astype(dtype)
    with jax.named_scope(SCOPE_ATTN_QKV):
        h = rms_norm(hidden, lp["norm_in"], eps).astype(dtype)
        u = ((h * cfg.ssm_in_multiplier) @ w("w_in")) * \
            jnp.asarray(mup_vector(cfg), dtype)
        d_ssm = cfg.mamba_d_ssm
        z, xbc = u[..., :d_ssm], u[..., d_ssm:d_ssm + cfg.conv_dim]
        dt = jax.nn.softplus(u[..., d_ssm + cfg.conv_dim:].astype(f32) +
                             lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
        ha = h * cfg.attention_in_multiplier
        q = head_projection(ha, w("wq")).reshape(b, t, hq, d)
        k = (head_projection(ha, w("wk")) *
             cfg.key_multiplier).reshape(b, t, hk, d)
        v = ha @ w("wv")
        # the config's theta (1e11) is a whole number past 32 bits
        theta = float(cfg.rope_theta)
        q = rope(q, positions, theta).reshape(b, t, hq * d)
        k = rope(k, positions, theta).reshape(b, t, hk * d)
    (o, y), cache = mixer(
        (q, k, v), (xbc, dt, A, lp["D"], lp["conv_w"], lp["conv_b"]), cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        y = group_rms_norm(y.astype(dtype) * jax.nn.silu(z), lp["ssm_norm"],
                           cfg.mamba_n_groups, eps)
        hidden = hidden + (y @ w("w_out")) * cfg.ssm_out_multiplier + \
            (o.astype(dtype) @ w("wo")) * cfg.attention_out_multiplier
    with jax.named_scope(SCOPE_MLP):
        m = rms_norm(hidden, lp["norm_ff"], eps).astype(dtype)
        g = jax.nn.silu((m @ w("w_gate")) * cfg.mlp_multipliers[0])
        hidden = hidden + ((g * (m @ w("w_up"))) @ w("w_down")) * \
            cfg.mlp_multipliers[1]
    return hidden, cache


def embed(cfg, params, tokens, positions):
    """Positions are rotary, applied in `block`: not read here."""
    return (params["embed"][tokens].astype(f32) *
            cfg.embedding_multiplier).astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits in the compute type."""
    x = rms_norm(hidden, params["norm_f"], cfg.rms_norm_eps)
    return (x.astype(cfg.dtype) @ params["head"].astype(cfg.dtype)) * \
        cfg.lm_head_multiplier


def layers(params):
    """The stacked [n_layer, ...] leaves `block` takes one layer of."""
    return params["layers"]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: dense causal attention and
    the chunked scan from zero state, nothing kept."""
    b, t = ids.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    (rows, _), (state, _) = cfg.state_slot_shapes

    def mixer(attn_in, ssm_in, cache):
        q, k, v = attn_in
        kv = lambda x: jnp.repeat(x.reshape(b, t, hk, d), hq // hk, axis=2)
        o = dense_attention(q.reshape(b, t, hq, d), kv(k), kv(v),
                            causal=True).reshape(b, t, hq * d)
        xbc, dt, A, D, conv_w, conv_b = ssm_in
        xbc, _ = causal_conv(xbc, conv_w, conv_b,
                             jnp.zeros((b,) + rows, xbc.dtype))
        xs, B, C = split_xbc(xbc, *state)
        y, _ = jax.vmap(lambda xs, dt, B, C: ssd_chunked(
            xs, dt, A, B, C, D, jnp.zeros(state, f32),
            chunk=cfg.mamba_chunk_size))(xs, dt, B, C)
        return (o, y.reshape(b, t, cfg.mamba_d_ssm)), cache

    def layer(hidden, lp):
        return block(cfg, lp, hidden, positions, mixer, None)[0], None

    hidden, _ = jax.lax.scan(layer, embed(cfg, params, ids, positions),
                             layers(params))
    return head(cfg, params, hidden)
