"""Phi-4-mini-flash-reasoning (Microsoft, `model_type: phi4flash`; the
decoder-hybrid-decoder "SambaY" of arXiv:2507.06607): a decoder whose
even layers are Mamba-shaped and whose odd layers attend, in two
halves. The first half (the self-decoder) alternates Mamba-1 and
differential attention over a sliding window; layer L/2 is one more
Mamba-1 whose scan output `mem` goes on, layer L/2 + 1 attends over
everything and writes THE K/V cache; every later pair (the
cross-decoder) is a gated memory unit on `mem` and a differential
cross-attention whose queries are its own and whose keys and values
are layer L/2 + 1's. Serving only: there is no training path here.

Every layer l (0-based), on x [B, T, H] (LayerNorm with bias, eps
1e-5; no positional encoding anywhere):

    h = LayerNorm(x; w, b)
    x = x + Mixer_l(h)
    m = LayerNorm(x; w', b')
    x = x + (silu(m W_gate) * (m W_up)) W_down      W_gate | W_up fused
    logits = LayerNorm(x_L; w_f, b_f) E^T           E the embedding

    Mamba-1 (arXiv:2312.00752; d_inner Di, state N, conv K, rank R):
        u, z = split(h W_in)
        c    = silu(causal_conv(u; conv_w [Di, K], conv_b))
        dtl, B, C = split(c W_x)                    R | N | N
        dt   = softplus(dtl W_dt + dt_bias)         float32
        S_t  = exp(dt_t A) * S_{t-1} + (dt_t c_t) B_t^T,  A = -exp(A_log)
        y_t  = S_t C_t + D c_t
        out  = (y * silu(z)) W_out                  layer L/2: mem = y
    gated memory unit: out = (mem * silu(h W_g)) W_o
    differential attention (arXiv:2410.05258): query heads (2j, 2j + 1)
    over key/value heads (2p, 2p + 1), p = j // 2:
        a_i  = softmax_causal(q_i k_i^T / sqrt(d)) [v_1 ; v_2]   i = 1, 2
        lam  = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
        o_j  = RMSNorm_2d(a_1 - lam a_2; g) * (1 - lam0(l))
        out  = concat_j(o_j) W_o + b_o,  lam0(l) = 0.8 - 0.6 exp(-0.3 l)
    a window layer sees keys p - window + 1 .. p; a cross layer
    computes q alone and takes k, v as layer L/2 + 1 wrote them.

The layers are scanned two at a time, a PERIOD (an even layer and the
odd one after it), in three stacks (`stacks`): the self-decoder's
periods, the one period in the middle, the cross-decoder's. ONE
functional `block` holds a period of any of them; which it is comes
as a word beside the period's weights (`lp["stack"]`), so that no
branch is an operand. Beside the hidden state the scans carry `mem`
(`enter`, `leave`): made in the middle period, read by every later
one, dropped when the launch ends. The block calls its `mixer` once
for each thing a layer keeps or reads between tokens, by role:

    mixer(CONV, u, conv_w, conv_b, cache)        -> (c, cache)
    mixer(SCAN, c, dt, A_t, B, C, D, cache)      -> (y, cache)
    mixer(WINDOW | FULL, q, k, v, cache)         -> (a [B, T, H, 2 d], cache)
    mixer(WRITE, k, v, cache)                    -> cache
    mixer(SHARED, q, cache)                      -> a

and knows nothing of pages, rings, tables, slots or state arrays: this
module's own `forward` hands it dense forms from a zero state, the
serving engine (`inference/hybrid_kind.py`, kind
"state+window+shared") mixers over a state, a ring of pages and one
layer of pages that eight layers read. A launch that yields no logits
(the engine's prefill) needs only what later tokens read:
`stacks(caching=True)` gives the self-decoder and the middle period
with the word WRITE, under which layer L/2 + 1 leaves its K and V and
nothing else runs after the middle Mamba's feed-forward.

Parameters are a plain dict; a stack's leaves are stacked [periods,
...] with the even layer's under "a" and the odd one's under "b":

    embed [V, H]   norm_f: w, b [H]
    self, bridge, cross: a, b: norm_w, norm_b, ffn_norm_w, ffn_norm_b [n, H]
                               w_gu [n, H, 2 F]   w_down [n, F, H]
    Mamba (self.a, bridge.a): w_in [n, H, 2 Di]  conv_w [n, Di, K]  conv_b
        [n, Di]  w_x [n, Di, R + 2 N]  w_dt [n, R, Di]  dt_bias, D [n, Di]
        A_log_t [n, N, Di] (transposed: the state is held [N, Di])
        w_out [n, Di, H]
    memory unit (cross.a): w_g [n, H, Di]  w_o [n, Di, H]
    attention (self.b, bridge.b): wqkv [n, H, (Hq + 2 Hk) d]  bqkv
        lq1, lk1, lq2, lk2 [n, d] float32  subnorm [n, 2 d]
        wo [n, Hq d, H]  bo [n, H]
    cross-attention (cross.b): the same with wq [n, H, Hq d], bq
"""

import dataclasses
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.brumby import head_projection
from deepspeed_tpu.ops.ssm import (causal_conv, selective_scan_chunk)
from deepspeed_tpu.ops.transformer.diff_decode_attention import \
    diff_attention
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_GMU, SCOPE_MLP)

f32 = jnp.float32
# the stacks, and what a middle period is in a launch that only caches
SELF, BRIDGE, CROSS, WRITE_ONLY = "self", "bridge", "cross", "write_only"
# the mixer's roles
CONV, SCAN, WINDOW, FULL, WRITE, SHARED = (
    "conv", "scan", "window", "full", "write", "shared")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning),
    then what the config does not carry and this program assumes (see
    `benchmark/configs/phi-4-mini-flash.json`, `assumed`)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    sliding_window: int = 512
    mb_per_layer: int = 2
    # assumed
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    subnorm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16           # compute type; rings, pool, conv rows
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32      # the scan's state

    # what `InferenceEngine` reads off every model config
    cache_kind = "state+window+shared"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.mb_per_layer != 2 or L % 4 or L < 8:
            raise ValueError(
                f"{L} layers with mb_per_layer {self.mb_per_layer}: the "
                "layers come in periods of two, as many before the middle "
                "period as with it and after")
        if self.num_attention_heads != 2 * self.num_key_value_heads or \
                self.num_key_value_heads % 2 or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads of width "
                f"{self.hidden_size}: differential attention takes two "
                "query pairs to every key/value pair")

    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size //
                        self.num_attention_heads)
    d_inner = property(lambda self: self.mamba_expand * self.hidden_size)
    self_periods = property(lambda self: self.num_hidden_layers // 4)
    cross_periods = property(lambda self: self.num_hidden_layers // 4 - 1)
    # what each part of a slot's cache spans: a state in the
    # self-decoder's Mamba layers and the middle one, a ring in the
    # window layers, ONE layer of pages; the layers that read it (the
    # middle period's and every cross layer), and the feed-forwards a
    # launch that only caches runs
    state_layers = property(lambda self: self.self_periods + 1)
    window_layers = property(lambda self: self.self_periods)
    shared_readers = property(lambda self: self.cross_periods + 1)
    caching_layers = property(lambda self: 2 * self.self_periods + 1)

    @property
    def state_slot_shapes(self):
        """((shape, dtype), ...) of ONE slot's state in ONE Mamba
        layer: the convolution's carried rows, the scan's state
        (transposed: `ops/ssm/mamba1.py` has why)."""
        return (((self.mamba_d_conv - 1, self.d_inner),
                 np.dtype(self.dtype)),
                ((self.mamba_d_state, self.d_inner),
                 np.dtype(self.state_dtype)))


def lam0(layers):
    """lambda_init of the 0-based layers `layers`, float32."""
    return jnp.asarray(0.8 - 0.6 * np.exp(-0.3 * np.asarray(layers, float)),
                       f32)


def init_params(cfg, key):
    """Normal(initializer_range) projections, the residual projections
    scaled by 1/sqrt(2 L), biases 0, norm weights 1; Mamba's published
    initialisation (A_log = log(1..N) along N, dt_bias the inverse
    softplus of a log-uniform dt in [0.001, 0.1], D = 1; W_dt uniform
    in +-R^-0.5, the convolution in +-K^-0.5 like `torch.nn.Conv1d`);
    the four lambda vectors normal(0.1)."""
    H, F, Di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    hq, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    r = cfg.initializer_range
    rs = r / (2 * cfg.num_hidden_layers) ** 0.5
    keys = iter(jax.random.split(key, 256))
    dtype = cfg.param_dtype
    draw = lambda shape, std: (std * jax.random.normal(
        next(keys), shape, f32)).astype(dtype)
    uniform = lambda shape, lo, hi: jax.random.uniform(
        next(keys), shape, f32, lo, hi)
    ones = lambda *shape: jnp.ones(shape, dtype)
    zeros = lambda *shape: jnp.zeros(shape, dtype)

    def common(n):
        return dict(norm_w=ones(n, H), norm_b=zeros(n, H),
                    ffn_norm_w=ones(n, H), ffn_norm_b=zeros(n, H),
                    w_gu=draw((n, H, 2 * F), r), w_down=draw((n, F, H), rs))

    def mamba(n):
        dt = jnp.exp(uniform((n, Di), np.log(1e-3), np.log(1e-1)))
        return dict(
            common(n), w_in=draw((n, H, 2 * Di), r),
            conv_w=uniform((n, Di, K), -K ** -0.5, K ** -0.5).astype(dtype),
            conv_b=uniform((n, Di), -K ** -0.5, K ** -0.5).astype(dtype),
            w_x=draw((n, Di, R + 2 * N), r),
            w_dt=uniform((n, R, Di), -R ** -0.5, R ** -0.5).astype(dtype),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log_t=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=f32))[None, :, None], (n, N, Di)),
            D=jnp.ones((n, Di), f32), w_out=draw((n, Di, H), rs))

    def memory_unit(n):
        return dict(common(n), w_g=draw((n, H, Di), r),
                    w_o=draw((n, Di, H), rs))

    def attention(n, cross):
        lam = lambda: 0.1 * jax.random.normal(next(keys), (n, d), f32)
        q = dict(wq=draw((n, H, hq * d), r), bq=zeros(n, hq * d)) if cross \
            else dict(wqkv=draw((n, H, (hq + 2 * hk) * d), r),
                      bqkv=zeros(n, (hq + 2 * hk) * d))
        return dict(common(n), **q, lq1=lam(), lk1=lam(), lq2=lam(),
                    lk2=lam(), subnorm=ones(n, 2 * d),
                    wo=draw((n, hq * d, H), rs), bo=zeros(n, H))

    ns, nc = cfg.self_periods, cfg.cross_periods
    return {"embed": draw((cfg.vocab_size, H), r),
            "norm_f": {"w": ones(H), "b": zeros(H)},
            "self": {"a": mamba(ns), "b": attention(ns, False)},
            "bridge": {"a": mamba(1), "b": attention(1, False)},
            "cross": {"a": memory_unit(nc), "b": attention(nc, True)}}


def layer_norm(x, w, b, eps):
    """`torch.nn.LayerNorm`; float32 statistics, the result in x's
    type."""
    x32 = x.astype(f32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(f32) + b.astype(f32)).astype(x.dtype)


def feed_forward(cfg, lp, x):
    """x + the gated SiLU feed-forward of LayerNorm(x)."""
    F, dtype = cfg.intermediate_size, cfg.dtype
    with jax.named_scope(SCOPE_MLP):
        m = layer_norm(x, lp["ffn_norm_w"], lp["ffn_norm_b"],
                       cfg.layer_norm_eps).astype(dtype)
        gu = head_projection(m, lp["w_gu"].astype(dtype))
        y = jax.nn.silu(gu[..., :F]) * gu[..., F:]
        return x + y @ lp["w_down"].astype(dtype)


def normed(cfg, lp, x):
    return layer_norm(x, lp["norm_w"], lp["norm_b"],
                      cfg.layer_norm_eps).astype(cfg.dtype)


def memory_of(y, gated):
    """What the middle Mamba layer hands the memory units: the scan's
    output BEFORE the gate."""
    return y


def mamba(cfg, lp, x, mixer, cache):
    """A Mamba-1 layer's mixer half: (x + out, the memory, cache)."""
    Di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    dtype = cfg.dtype
    w = lambda name: lp[name].astype(dtype)
    with jax.named_scope(SCOPE_ATTN_QKV):
        uz = head_projection(normed(cfg, lp, x), w("w_in"))
        u, z = uz[..., :Di], uz[..., Di:]
    c, cache = mixer(CONV, u, lp["conv_w"], lp["conv_b"], cache)
    with jax.named_scope(SCOPE_ATTN_QKV):
        dbc = head_projection(c, w("w_x"))
        dt = jax.nn.softplus(
            (dbc[..., :R] @ w("w_dt")).astype(f32) +
            lp["dt_bias"].astype(f32))
        A_t = -jnp.exp(lp["A_log_t"].astype(f32))
    y, cache = mixer(SCAN, c, dt, A_t, dbc[..., R:R + N], dbc[..., R + N:],
                     lp["D"], cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        y = y.astype(dtype)
        gated = y * jax.nn.silu(z)
        return x + gated @ w("w_out"), memory_of(y, gated), cache


def gated_memory(cfg, lp, x, mem):
    """A gated memory unit's mixer half: x + (mem * silu(h W_g)) W_o."""
    dtype = cfg.dtype
    with jax.named_scope(SCOPE_ATTN_QKV), jax.named_scope(SCOPE_GMU):
        g = jax.nn.silu(normed(cfg, lp, x) @ lp["w_g"].astype(dtype))
    with jax.named_scope(SCOPE_ATTN_OUT), jax.named_scope(SCOPE_GMU):
        return x + (mem.astype(dtype) * g) @ lp["w_o"].astype(dtype)


def lam_of(lp):
    """A layer's lambda: exp(lq1 . lk1) - exp(lq2 . lk2) + lam0."""
    dot = lambda a, b: jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))
    return jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + \
        lp["lam0"].astype(f32)


def sub_norm(cfg, o, weight):
    """RMSNorm over a pair's 2 d values, float32."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) +
                             cfg.subnorm_eps) * weight.astype(f32)


def subtracted(cfg, lp, a):
    """a [B, T, H, 2 d], each query head's softmax over the pair's
    values -> [B, T, H d]: a pair's rows subtracted, normed, scaled."""
    b, t, h, wide = a.shape
    pairs = a.astype(f32).reshape(b, t, h // 2, 2, wide)
    o = sub_norm(cfg, pairs[..., 0, :] - lam_of(lp) * pairs[..., 1, :],
                 lp["subnorm"])
    return (o * (1.0 - lp["lam0"].astype(f32))).reshape(
        b, t, h * wide // 2).astype(cfg.dtype)


def attention(cfg, lp, x, role, mixer, cache):
    """A differential attention layer's mixer half, `role` WINDOW,
    FULL or SHARED: (x + out, cache)."""
    b, t, _ = x.shape
    hq, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dtype = cfg.dtype
    with jax.named_scope(SCOPE_ATTN_QKV):
        h = normed(cfg, lp, x)
        if role == SHARED:
            q = head_projection(h, lp["wq"].astype(dtype)) + \
                lp["bq"].astype(dtype)
        else:
            qkv = head_projection(h, lp["wqkv"].astype(dtype)) + \
                lp["bqkv"].astype(dtype)
            q, k, v = (qkv[..., :hq * d], qkv[..., hq * d:(hq + hk) * d],
                       qkv[..., (hq + hk) * d:])
    if role == SHARED:
        a = mixer(SHARED, q, cache)
    else:
        a, cache = mixer(role, q, k, v, cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        o = subtracted(cfg, lp, a.reshape(b, t, hq, 2 * d))
        return x + o @ lp["wo"].astype(dtype) + lp["bo"].astype(dtype), cache


def write_only(cfg, lp, x, mixer, cache):
    """What a launch that yields no logits runs of the layer that
    writes the shared cache: the norm, W_k, W_v, the write."""
    hq, dtype = cfg.n_head * cfg.head_dim, cfg.dtype
    with jax.named_scope(SCOPE_ATTN_QKV):
        kv = head_projection(normed(cfg, lp, x),
                             lp["wqkv"][:, hq:].astype(dtype)) + \
            lp["bqkv"][hq:].astype(dtype)
        k, v = jnp.split(kv, 2, axis=-1)
    return mixer(WRITE, k, v, cache)


def block(cfg, lp, carry, positions, mixer, cache):
    """One period (an even layer and the odd one after it) on carry =
    (hidden [B, T, H], mem [B, T, Di]); `lp["stack"]` says which
    stack's. Returns (carry, cache). No layer reads `positions`."""
    x, mem = carry
    stack = lp["stack"]
    if stack == CROSS:
        x = gated_memory(cfg, lp["a"], x, mem)
    else:
        x, y, cache = mamba(cfg, lp["a"], x, mixer, cache)
        if stack != SELF:
            mem = y
    x = feed_forward(cfg, lp["a"], x)
    if stack == WRITE_ONLY:
        return (x, mem), write_only(cfg, lp["b"], x, mixer, cache)
    role = {SELF: WINDOW, BRIDGE: FULL, CROSS: SHARED}[stack]
    x, cache = attention(cfg, dict(lp["b"], lam0=lp["lam0"]), x, role,
                         mixer, cache)
    return (feed_forward(cfg, lp["b"], x), mem), cache


def stacks(cfg, params, caching=False):
    """[(scanned, whole)] in order: `block` is scanned over `scanned`
    (a period's weights and its attention layer's `lam0`) and takes
    `whole` (the stack's word) beside each period's slice. With
    `caching`, only the stacks that write what later tokens read, the
    middle period under the word WRITE_ONLY."""
    ns, nc = cfg.self_periods, cfg.cross_periods
    odd = lambda first, n: lam0(2 * (first + np.arange(n)) + 1)
    out = [(dict(params["self"], lam0=odd(0, ns)), {"stack": SELF}),
           (dict(params["bridge"], lam0=odd(ns, 1)),
            {"stack": WRITE_ONLY if caching else BRIDGE})]
    if not caching:
        out.append((dict(params["cross"], lam0=odd(ns + 1, nc)),
                    {"stack": CROSS}))
    return out


def enter(cfg, hidden):
    """What the layer scans carry: the hidden state and `mem`, zeros
    until the middle period makes it."""
    return hidden, jnp.zeros(hidden.shape[:-1] + (cfg.d_inner,), cfg.dtype)


def leave(cfg, carry):
    """`mem` is dropped when the launch ends."""
    return carry[0]


def embed(cfg, params, tokens, positions):
    """No positions of any kind."""
    return params["embed"][tokens].astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits: the final norm and the embedding,
    read [V, H] where it lies."""
    x = layer_norm(hidden, params["norm_f"]["w"], params["norm_f"]["b"],
                   cfg.layer_norm_eps).astype(cfg.dtype)
    return jax.lax.dot_general(
        x, params["embed"].astype(cfg.dtype),
        (((x.ndim - 1,), (1,)), ((), ())))


def layers(params):
    """The self-decoder's stacked leaves (`stacks` has every layer)."""
    return params["self"]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: every layer on every token,
    dense attention under the band mask, the scan from a zero state,
    nothing kept (the `cache` of the mixer here is the middle period's
    K and V on their way to the cross layers)."""
    b, t = ids.shape
    hq, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    at = jnp.arange(t)
    causal = at[None, :] <= at[:, None]
    band = causal & (at[None, :] > at[:, None] - cfg.sliding_window)
    heads = lambda x, n: x.reshape(b, t, n, d)
    attend = lambda q, kv, seen: diff_attention(
        heads(q, hq), heads(kv[0], hk), heads(kv[1], hk),
        jnp.broadcast_to(seen, (b, t, t)))

    def mixer(role, *args):
        *args, cache = args
        if role == CONV:
            u, conv_w, conv_b = args
            rows = jnp.zeros((b, cfg.mamba_d_conv - 1, u.shape[-1]), u.dtype)
            return causal_conv(u, conv_w, conv_b, rows)[0], cache
        if role == SCAN:
            c, dt, A_t, B, C, D = args
            S0 = jnp.zeros(A_t.shape, f32)
            y, _ = jax.vmap(lambda c, dt, B, C: selective_scan_chunk(
                c, dt, A_t, B, C, D, S0))(c, dt, B, C)
            return y, cache
        if role == SHARED:
            return attend(args[0], cache, causal)
        q, k, v = args
        if role == WINDOW:
            return attend(q, (k, v), band), cache
        return attend(q, (k, v), causal), (k, v)

    positions = jnp.broadcast_to(at, (b, t))
    carry = enter(cfg, embed(cfg, params, ids, positions))
    cache = (jnp.zeros((b, t, hk * d), cfg.dtype),) * 2
    for scanned, whole in stacks(cfg, params):
        def period(state, lp, whole=whole):
            return block(cfg, {**lp, **whole}, state[0], positions, mixer,
                         state[1]), None
        (carry, cache), _ = jax.lax.scan(period, (carry, cache), scanned)
    return head(cfg, params, leave(cfg, carry))
