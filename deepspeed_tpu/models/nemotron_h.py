"""Nemotron-H (NVIDIA, `model_type: nemotron_h`; NVIDIA-Nemotron-3-Nano-
30B-A3B): a decoder whose every layer is ONE thing, a Mamba-2
state-space mixer, a layer of ungated experts or softmax attention
with grouped-query heads, in the order that a pattern STRING gives
(`hybrid_override_pattern`: `M`, `E`, `*`). There is no separate
feed-forward half: the expert layers ARE the feed-forwards. Serving
only: there is no backward of the chunked scan or of the grouped
product, and no training path.

Every layer, on x [B, T, H] (RMSNorm eps `layer_norm_epsilon`; no bias
but the convolution's; no positions anywhere: order comes from the
Mamba-2 layers):

    x = x + Mixer_l(RMSNorm(x; norm))

    M  Mamba-2 (arXiv:2405.21060; `mamba_num_heads` heads of
       `mamba_head_dim` = d_ssm, `n_groups` groups that share B and C,
       state width `ssm_state_size` N, conv width `conv_kernel` K):
           z | xBC | dt = h W_in            d_ssm | d_ssm + 2 G N | heads
           xs, B, C = split(silu(causal_conv(xBC; conv_w, conv_b)))
           dt = softplus(dt + dt_bias)      A = -exp(A_log)     float32
           H_t = exp(dt_t A) H_{t-1} + dt_t xs_t B_t^T;  y_t = H_t C_t + D xs_t
           out = GroupRMSNorm(y * silu(z); ssm_norm, G groups) W_out
    E  experts (`moe/serving.py::expert_layer`, form RELU2):
           s = sigmoid(h W_r) float32; picks = the `num_experts_per_tok`
           experts of largest s + expert_bias (one group);
           w = routed_scaling_factor * s[picks] / sum(s[picks])
           out = Shared(h) + sum_j w_j Expert_{picks_j}(h)
           Expert(h) = relu(h W_up)^2 W_down      no gate matrix; width
           `moe_intermediate_size`, the shared one
           `moe_shared_expert_intermediate_size`
    *  attention: `num_attention_heads` query heads over
       `num_key_value_heads` key/value heads of `head_dim`, causal
       softmax(q k^T / sqrt(d)) v, no rotation; out = o W_o

logits = RMSNorm(x_L; norm_f) W_head, the head untied.

The layers are scanned in RUNS (`runs`): the pattern is read from the
left as runs of `EM` pairs (a step of a scan is an expert layer and the
Mamba-2 layer after it) and, where no pair begins, runs of one letter;
the published 52 layers are M, 2 pairs, then six times `*` and 3, 3,
3, 3, 4 and 4 pairs, and a last E. ONE functional `block` holds a step
of any run; which it is comes as a word beside the step's weights
(`lp["unit"]`), so that no branch is an operand. The block calls its
`mixer` by role for what a layer keeps between tokens, and names the
layer by its index AMONG THE LAYERS OF ITS OWN KIND (the stacks carry
it beside the weights, as they carry an expert layer's index among the
expert layers):

    mixer(STATE, at, xBC, dt, A, D, conv_w, conv_b, cache) -> (y, cache)
    mixer(PAGES, at, q, k, v, cache)                       -> (o, cache)
    mixer(LIVE)     -> [B, T] bool, the rows that are a request's (or
                       None: all), which an expert layer keeps its
                       routed experts to; it touches no cache

and knows nothing of pages, tables, slots or state arrays: this
module's own `forward` hands it dense attention and the chunked scan
from a zero state, the serving engine (`inference/layered_kind.py`,
kind "paged|state") a state in the M layers and pages in the `*`
layers, counted apart. The block returns a third value, what its
expert layer counted (`COUNTERS`; zeros from a step without one), and
a fourth, what it read off every row (`ROW_READINGS`: the experts the
row picked; -1 from a step without an expert layer).

The chip's share: `experts_held` of the `n_routed_experts` from
`first_expert` on (the router, its bias and the picks are whole), and
`vocab_size` rows of the vocabulary.

Parameters are a plain dict. The routed experts' own matrices of EVERY
expert layer are one stack (the grouped product reads a layer of them
where they lie); everything else is held a run, each kind's leaves
stacked [steps of the run, ...], so that no run's weights are sliced
out of a longer stack:

    embed [V, H]   head [H, V]   norm_f [H]
    experts: w_up [nE, held, H, I']   w_down [nE, held, I', H]
             (I' = `expert_width_stored` >= I, zeros past I)
    runs: [ {M: .., E: .., "*": ..} ]   one a run, the kinds of its unit
      M: norm [n, H]  w_in [n, H, 2 d_ssm + 2 G N + heads]
         conv_w [n, d_ssm + 2 G N, K]  conv_b [n, d_ssm + 2 G N]
         dt_bias, A_log, D [n, heads] float32  ssm_norm [n, d_ssm]
         w_out [n, d_ssm, H]
      E: norm [n, H]  router [n, H, E]  expert_bias [n, E] float32
         shared_up [n, H, Is]  shared_down [n, Is, H]
      *: norm [n, H]  wq [n, H, Hq d]  wk, wv [n, H, Hk d]  wo [n, Hq d, H]
"""

import dataclasses
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.brumby import head_projection, rms_norm
from deepspeed_tpu.models.falcon_h1 import group_rms_norm
from deepspeed_tpu.moe import serving as moe
from deepspeed_tpu.ops.ssm import causal_conv, split_xbc, ssd_chunked
from deepspeed_tpu.ops.transformer.flash_attention import dense_attention
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)

f32 = jnp.float32
# a layer's kinds as the pattern spells them, and the mixer's roles
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
STATE, PAGES, LIVE = "state", "pages", "live"
# what `block` counts a launch, summed over its expert layers
COUNTERS = moe.COUNTERS
# what `block` reads off every row, a step: the k experts it picked
ROW_READINGS = ("moe_picks",)
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def runs(pattern):
    """[(unit, steps)] in order: the pattern read from the left as
    runs of `EM` pairs and, where no pair begins, of one letter."""
    out, i = [], 0
    while i < len(pattern):
        unit = EXPERTS + MAMBA if pattern.startswith(EXPERTS + MAMBA, i) \
            else pattern[i]
        n = 1
        while pattern.startswith(unit, i + n * len(unit)):
            n += 1
        out.append((unit, n))
        i += n * len(unit)
    return out


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The source's `config.json` keys at the published values
    (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16),
    then the chip's share, then what the config does not carry and
    this program assumes (see
    `benchmark/configs/nemotron-3-nano-30b.json`, `assumed`)."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    max_position_embeddings: int = 262144
    layer_norm_epsilon: float = 1e-5
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    # the chip's share of every expert layer: None, all of them
    experts_held: int = None
    first_expert: int = 0
    # the columns a routed expert's W_up (and rows of its W_down) are
    # STORED at, zeros past moe_intermediate_size: None, that width.
    # The published 1856 is 14.5 of the chip's lane tiles, and the
    # grouped product's kernel wants whole ones (`moe/serving.py`)
    expert_width_stored: int = None
    # assumed
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16           # compute dtype; pages, conv rows
    param_dtype: Any = jnp.bfloat16
    ssm_state_dtype: Any = jnp.float32  # the state matrix H

    # what `InferenceEngine` reads off every model config
    cache_kind = "paged|state"
    serving_module = property(lambda self: sys.modules[__name__])

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if self.expert_width_stored is None:
            object.__setattr__(self, "expert_width_stored",
                               self.moe_intermediate_size)
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or \
                set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not spell "
                f"{self.num_hidden_layers} layers in M, E and *")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"{self.num_attention_heads} query heads over "
                f"{self.num_key_value_heads} key/value heads, or "
                f"{self.mamba_num_heads} state-space heads in "
                f"{self.n_groups} groups: neither divides")
        if not (self.n_group == self.topk_group == self.n_shared_experts
                == 1 and self.norm_topk_prob):
            raise ValueError(
                "n_group, topk_group and n_shared_experts are 1 and "
                "norm_topk_prob true in the published config, and the "
                "expert layer has no other path: one group, one shared "
                "expert, the picked scores normalised")
        if not 0 <= self.first_expert <= \
                self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert} .. {self.first_expert} + "
                f"{self.experts_held} are not among {self.n_routed_experts}")

    # the names the serving engine reads off every model config
    n_layer = property(lambda self: self.num_hidden_layers)
    n_positions = property(lambda self: self.max_position_embeddings)
    n_head = property(lambda self: self.num_attention_heads)
    n_kv_head = property(lambda self: self.num_key_value_heads)
    mamba_chunk_size = property(lambda self: self.chunk_size)
    d_ssm = property(lambda self: self.mamba_num_heads * self.mamba_head_dim)
    # x | B | C: what the convolution runs over
    conv_dim = property(lambda self: self.d_ssm + 2 * self.n_groups *
                        self.ssm_state_size)
    # how many layers keep a state, pages, or route: the pattern's
    state_layers = property(
        lambda self: self.hybrid_override_pattern.count(MAMBA))
    paged_layers = property(
        lambda self: self.hybrid_override_pattern.count(ATTENTION))
    expert_layers = property(
        lambda self: self.hybrid_override_pattern.count(EXPERTS))

    @property
    def state_slot_shapes(self):
        """((shape, dtype), ...) of ONE slot's state in ONE Mamba-2
        layer: the convolution's carried rows, the state matrix."""
        return (((self.conv_kernel - 1, self.conv_dim),
                 np.dtype(self.dtype)),
                ((self.mamba_num_heads, self.mamba_head_dim,
                  self.ssm_state_size), np.dtype(self.ssm_state_dtype)))


def init_params(cfg, key):
    """Normal(initializer_range) projections and router, the residual
    projections (W_out, W_o, every W_down) scaled by 1/sqrt(L)
    (`rescale_prenorm_residual`: one residual branch a layer), norm
    weights 1, the selection bias 0; the state-space scalars by
    Mamba-2's published initialisation: A_log = log U[1, 16], dt_bias
    the inverse softplus of a log-uniform dt in [time_step_min,
    time_step_max] (no less than time_step_floor), D = 1; the
    convolution uniform in +-1/sqrt(K) like `torch.nn.Conv1d`."""
    H, I, Is = (cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size)
    hq, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    nh, K, E = cfg.mamba_num_heads, cfg.conv_kernel, cfg.n_routed_experts
    r = cfg.initializer_range
    rs = r / cfg.num_hidden_layers ** 0.5
    keys = iter(jax.random.split(key, 16 * len(runs(
        cfg.hybrid_override_pattern)) + 8))
    draw = lambda shape, std: (std * jax.random.normal(
        next(keys), shape, f32)).astype(cfg.param_dtype)
    uniform = lambda shape, lo, hi: jax.random.uniform(
        next(keys), shape, f32, lo, hi)
    ones = lambda *shape: jnp.ones(shape, cfg.param_dtype)

    def mamba(n):
        dt = jnp.maximum(jnp.exp(uniform(
            (n, nh), np.log(cfg.time_step_min), np.log(cfg.time_step_max))),
            cfg.time_step_floor)
        return dict(
            norm=ones(n, H),
            w_in=draw((n, H, cfg.d_ssm + cfg.conv_dim + nh), r),
            conv_w=uniform((n, cfg.conv_dim, K), -K ** -0.5,
                           K ** -0.5).astype(cfg.param_dtype),
            conv_b=uniform((n, cfg.conv_dim), -K ** -0.5,
                           K ** -0.5).astype(cfg.param_dtype),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(uniform((n, nh), 1.0, 16.0)),
            D=jnp.ones((n, nh), f32), ssm_norm=ones(n, cfg.d_ssm),
            w_out=draw((n, cfg.d_ssm, H), rs))

    def experts(n):
        return dict(norm=ones(n, H), router=draw((n, H, E), r),
                    expert_bias=jnp.zeros((n, E), f32),
                    shared_up=draw((n, H, Is), r),
                    shared_down=draw((n, Is, H), rs))

    def attention(n):
        return dict(norm=ones(n, H), wq=draw((n, H, hq * d), r),
                    wk=draw((n, H, hk * d), r), wv=draw((n, H, hk * d), r),
                    wo=draw((n, hq * d, H), rs))

    make = {MAMBA: mamba, EXPERTS: experts, ATTENTION: attention}
    ne, held = cfg.expert_layers, cfg.experts_held
    pad = cfg.expert_width_stored - I
    return {"embed": draw((cfg.vocab_size, H), r),
            "head": draw((H, cfg.vocab_size), r), "norm_f": ones(H),
            "experts": {
                "w_up": jnp.pad(draw((ne, held, H, I), r),
                                ((0, 0),) * 3 + ((0, pad),)),
                "w_down": jnp.pad(draw((ne, held, I, H), rs),
                                  ((0, 0),) * 2 + ((0, pad), (0, 0)))},
            "runs": [{kind: make[kind](n) for kind in unit}
                     for unit, n in runs(cfg.hybrid_override_pattern)]}


def normed(cfg, lp, x):
    return rms_norm(x, lp["norm"], cfg.layer_norm_epsilon).astype(cfg.dtype)


def mamba_layer(cfg, lp, x, mixer, cache):
    """A Mamba-2 layer: (x + out, cache). `lp["at"]`: the layer's
    index among the layers that keep a state."""
    d_ssm, dtype = cfg.d_ssm, cfg.dtype
    w = lambda name: lp[name].astype(dtype)
    with jax.named_scope(SCOPE_ATTN_QKV):
        u = head_projection(normed(cfg, lp, x), w("w_in"))
        z, xbc = u[..., :d_ssm], u[..., d_ssm:d_ssm + cfg.conv_dim]
        dt = jax.nn.softplus(u[..., d_ssm + cfg.conv_dim:].astype(f32) +
                             lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
    y, cache = mixer(STATE, lp["at"], xbc, dt, A, lp["D"], lp["conv_w"],
                     lp["conv_b"], cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        y = group_rms_norm(y.astype(dtype) * jax.nn.silu(z), lp["ssm_norm"],
                           cfg.n_groups, cfg.layer_norm_epsilon)
        return x + y @ w("w_out"), cache


def attention_layer(cfg, lp, x, mixer, cache):
    """An attention layer: (x + out, cache). `lp["at"]`: the layer's
    index among the layers that keep pages. No rotation: the keys the
    pages hold are h W_k as it is."""
    dtype = cfg.dtype
    w = lambda name: lp[name].astype(dtype)
    with jax.named_scope(SCOPE_ATTN_QKV):
        h = normed(cfg, lp, x)
        q, k = head_projection(h, w("wq")), head_projection(h, w("wk"))
        v = h @ w("wv")
    o, cache = mixer(PAGES, lp["at"], q, k, v, cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        return x + o.astype(dtype) @ w("wo"), cache


def expert_layer(cfg, lp, x, live):
    """An expert layer: (x + out, counts int32 [len(COUNTERS)], picks
    int32 [B T, k]). `lp["at"]`: the layer's index among the expert
    layers, `lp["experts"]` every expert layer's routed experts; rows
    that are no request's (`live` [B, T] false) go to no routed
    expert."""
    b, t, H = x.shape
    with jax.named_scope(SCOPE_MLP):
        y, counts, picks = moe.expert_layer(
            normed(cfg, lp, x).reshape(b * t, H), lp, lp["experts"],
            lp["at"], cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            first_expert=cfg.first_expert,
            live=None if live is None else live.reshape(b * t),
            form=moe.RELU2)
        return x + y.reshape(b, t, H), counts, picks


def block(cfg, lp, hidden, positions, mixer, cache):
    """One step of a run (`lp["unit"]`: one layer, or an expert layer
    and the Mamba-2 layer after it) on hidden [B, T, H]; no layer
    reads `positions`. Returns (hidden, cache, counts int32
    [len(COUNTERS)], (picks int32 [B T, k],): `ROW_READINGS`)."""
    b, t, _ = hidden.shape
    counts = jnp.zeros((len(COUNTERS),), jnp.int32)
    picks = jnp.full((b * t, cfg.num_experts_per_tok), -1, jnp.int32)
    for kind in lp["unit"]:
        if kind == EXPERTS:
            hidden, counts, picks = expert_layer(
                cfg, dict(lp[kind], experts=lp["experts"]), hidden,
                mixer(LIVE))
        elif kind == MAMBA:
            hidden, cache = mamba_layer(cfg, lp[kind], hidden, mixer, cache)
        else:
            hidden, cache = attention_layer(cfg, lp[kind], hidden, mixer,
                                            cache)
    return hidden, cache, counts, (picks,)


def stacks(cfg, params):
    """[(scanned, whole)] in order, one a run of the pattern: `block`
    is scanned over `scanned` (a step's weights and, beside each
    kind's, `at`: the layer's index among the layers of its kind) and
    takes `whole` as it is beside each step's slice: the run's unit,
    and the routed experts' own matrices of EVERY expert layer, which
    the grouped product reads where they lie."""
    seen = {MAMBA: 0, EXPERTS: 0, ATTENTION: 0}
    out = []
    for (unit, n), held in zip(runs(cfg.hybrid_override_pattern),
                               params["runs"]):
        scanned = {}
        for kind in unit:
            scanned[kind] = dict(held[kind], at=seen[kind] +
                                 jnp.arange(n, dtype=jnp.int32))
            seen[kind] += n
        whole = {"unit": unit}
        if EXPERTS in unit:
            whole["experts"] = params["experts"]
        out.append((scanned, whole))
    return out


def embed(cfg, params, tokens, positions):
    """No positions of any kind."""
    return params["embed"][tokens].astype(cfg.dtype)


def head(cfg, params, hidden):
    """[..., H] -> [..., V] logits in the compute type."""
    x = rms_norm(hidden, params["norm_f"], cfg.layer_norm_epsilon)
    return x.astype(cfg.dtype) @ params["head"].astype(cfg.dtype)


def layers(params):
    """The first run's stacked leaves (`stacks` has every layer)."""
    return params["runs"][0]


# no projection an int8 load may quantise: this model has no int8 path
QUANT_KERNEL_MODULES = ()


def forward(cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits: dense causal attention and
    the chunked scan from zero state, every row a request's, nothing
    kept."""
    b, t = ids.shape
    hq, hk, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    (rows, _), (state, _) = cfg.state_slot_shapes

    def mixer(role, *args):
        if role == LIVE:
            return None
        _, *args, cache = args
        if role == PAGES:
            q, k, v = args
            kv = lambda x: jnp.repeat(x.reshape(b, t, hk, d), hq // hk,
                                      axis=2)
            return dense_attention(q.reshape(b, t, hq, d), kv(k), kv(v),
                                   causal=True).reshape(b, t, hq * d), cache
        xbc, dt, A, D, conv_w, conv_b = args
        xbc, _ = causal_conv(xbc, conv_w, conv_b,
                             jnp.zeros((b,) + rows, xbc.dtype))
        xs, B, C = split_xbc(xbc, *state)
        y, _ = jax.vmap(lambda xs, dt, B, C: ssd_chunked(
            xs, dt, A, B, C, D, jnp.zeros(state, f32),
            chunk=cfg.chunk_size))(xs, dt, B, C)
        return y.reshape(b, t, cfg.d_ssm), cache

    hidden = embed(cfg, params, ids, positions)
    for scanned, whole in stacks(cfg, params):
        def step(hidden, lp, whole=whole):
            return block(cfg, {**lp, **whole}, hidden, positions, mixer,
                         None)[0], None
        hidden, _ = jax.lax.scan(step, hidden, scanned)
    return head(cfg, params, hidden)
