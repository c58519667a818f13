"""The benchmark's weights for the Falcon-H1 family: made on the device
from the seed in the type the cell serves them in, one small jitted
program per leaf, a leaf made alone bit for bit the leaf made with the
rest (`benchmark/weights.py`'s convention).

The plain reference and the program both get these arrays. They are a
flat dict keyed by the reference's names (`h.*` leaves are stacked
`[n_layer, ...]`); `to_program_tree` lays the same arrays out as the
program's parameter tree.

What is drawn how (the configuration file's `assumed` has the reasons):

  * every projection normal with spread r / (the muP multipliers that
    scale its output), r = 0.02: the multipliers of the published
    config are made for trained weights; with seeded weights at one
    spread they would leave the attention scores flat (key_multiplier
    0.011) and both mixers a hundredth of the residual, and no fault in
    either branch could show. Divided out, every branch reaches the
    residual as a 0.02 initialisation gives it, and a multiplier left
    out or applied twice shows as a factor of 4 to 128. The three
    residual projections (W_out, W_o, W_down) carry 1 / sqrt(2 x the
    PUBLISHED depth) besides. W_in has a spread a segment (z | xs | B |
    C | dt), and its dt segment alone keeps the plain 0.02: divided
    out it would add noise of spread 1.4 to dt before the softplus and
    cut the heads' memories from 0.6 .. 1,000 tokens to 0.5 .. 260
    (my chip run, PR 31: `memory_lengths` at every build);
  * the state-space scalars by Mamba-2's published initialisation:
    A_log = log U[1, 16], dt_bias the inverse softplus of a log-uniform
    dt in [0.001, 0.1], D = 1, all float32; the convolution's weights
    and bias uniform in +-1/sqrt(K) (`torch.nn.Conv1d`'s default);
  * norm weights round 1 (0.1) so that a fault in a norm's weight path
    shows.

`memory_lengths` gives what the seeded state-space heads remember,
1 / (mean dt x |A|) tokens.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, key_from_seed

A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
FLOAT32_LEAVES = ("h.A_log", "h.dt_bias", "h.D")


def segments(sizes):
    """Widths of W_in's five segments z | xs | B | C | dt."""
    gn = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return (sizes["mamba_d_ssm"], sizes["mamba_d_ssm"], gn, gn,
            sizes["mamba_n_heads"])


def weight_shapes(sizes):
    """{name: (how, shape, a, b)}: `normal` (spread a, centre b),
    `columns` (a: the spread of every segment of the last axis),
    `uniform` on [a, b], `log_uniform` exp(U[log a, log b]),
    `dt_bias` (the inverse softplus of a log-uniform dt in [a, b])."""
    L, H, F = (sizes["num_hidden_layers"], sizes["hidden_size"],
               sizes["intermediate_size"])
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    V, nh, K = sizes["vocab_size"], sizes["mamba_n_heads"], \
        sizes["mamba_d_conv"]
    conv = sizes["mamba_d_ssm"] + 2 * sizes["mamba_n_groups"] * \
        sizes["mamba_d_state"]
    r = sizes["assumed"]["initializer_range"]
    published = sizes.get("published", {}).get("num_hidden_layers", L)
    rs = r / math.sqrt(2 * published)
    up, down = sizes["mlp_multipliers"]
    w_in = tuple(r / (sizes["ssm_in_multiplier"] * m)
                 for m in sizes["ssm_multipliers"][:-1]) + (r,)
    bound = 1.0 / math.sqrt(K)
    return {
        "embed": ("normal", (V, H), r / sizes["embedding_multiplier"], 0.0),
        "head": ("normal", (H, V), r / sizes["lm_head_multiplier"], 0.0),
        "norm_f": ("normal", (H,), 0.1, 1.0),
        "h.norm_in": ("normal", (L, H), 0.1, 1.0),
        "h.w_in": ("columns", (L, H, sum(segments(sizes))), w_in, None),
        "h.conv_w": ("uniform", (L, conv, K), -bound, bound),
        "h.conv_b": ("uniform", (L, conv), -bound, bound),
        "h.dt_bias": ("dt_bias", (L, nh), *DT_RANGE),
        "h.A_log": ("log_uniform", (L, nh), *A_RANGE),
        "h.D": ("uniform", (L, nh), 1.0, 1.0),
        "h.ssm_norm": ("normal", (L, sizes["mamba_d_ssm"]), 0.1, 1.0),
        "h.w_out": ("normal", (L, sizes["mamba_d_ssm"], H),
                    rs / sizes["ssm_out_multiplier"], 0.0),
        "h.wq": ("normal", (L, H, hq * d),
                 r / sizes["attention_in_multiplier"], 0.0),
        "h.wk": ("normal", (L, H, hk * d), r / (
            sizes["attention_in_multiplier"] * sizes["key_multiplier"]), 0.0),
        "h.wv": ("normal", (L, H, hk * d),
                 r / sizes["attention_in_multiplier"], 0.0),
        "h.wo": ("normal", (L, hq * d, H),
                 rs / sizes["attention_out_multiplier"], 0.0),
        "h.norm_ff": ("normal", (L, H), 0.1, 1.0),
        "h.w_gate": ("normal", (L, H, F), r / up, 0.0),
        "h.w_up": ("normal", (L, H, F), r, 0.0),
        "h.w_down": ("normal", (L, F, H), rs / down, 0.0),
    }


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi", "dtype",
                                             "how"))
def _uniform_leaf(key, shape, lo, hi, dtype, how="uniform"):
    if how == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, lo, hi) \
            .astype(dtype)
    x = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(lo),
                                   math.log(hi)))
    if how == "log_uniform":          # A; the leaf is its logarithm
        return jnp.log(x).astype(dtype)
    return (x + jnp.log(-jnp.expm1(-x))).astype(dtype)   # softplus^-1(dt)


@functools.partial(jax.jit, static_argnames=("shape", "widths", "stds",
                                             "dtype"))
def _columns_leaf(key, shape, widths, stds, dtype):
    spread = jnp.concatenate([jnp.full((w,), s, jnp.float32)
                              for w, s in zip(widths, stds)])
    return (spread * jax.random.normal(key, shape, jnp.float32)) \
        .astype(dtype)


def make_weights(sizes, seed, dtype, only=None):
    """{name: array} for every leaf, or for the leaves named in `only`."""
    shapes = weight_shapes(sizes)
    names = sorted(shapes)
    keys = jax.random.split(key_from_seed(seed, stream=1), len(names))
    out = {}
    for i, name in enumerate(names):
        if only is not None and name not in only:
            continue
        how, shape, a, b = shapes[name]
        kind = "float32" if name in FLOAT32_LEAVES else jnp.dtype(dtype).name
        if how == "normal":
            out[name] = _leaf(keys[i], shape, a, b, kind)
        elif how == "columns":
            out[name] = _columns_leaf(keys[i], shape, segments(sizes), a,
                                      kind)
        else:
            out[name] = _uniform_leaf(keys[i], shape, a, b, kind, how)
    return out


def to_program_tree(flat):
    """`flat` laid out as `models/falcon_h1.py`'s parameter tree."""
    tree = {k: v for k, v in flat.items() if not k.startswith("h.")}
    tree["layers"] = {k[2:]: v for k, v in flat.items()
                      if k.startswith("h.")}
    return tree


def memory_lengths(sizes, seed, flat=None, tokens=1024):
    """[n_layer, heads] effective memory in tokens of the seeded
    state-space heads, 1 / (mean_t(dt_t) |A|), with rows of unit
    normal noise standing in for every layer's input (each is
    RMS-normed before the projection, so only its direction matters)."""
    names = ("h.norm_in", "h.w_in", "h.dt_bias", "h.A_log")
    if flat is None:
        flat = make_weights(sizes, seed, jnp.float32, only=names)
    f32 = lambda k: flat[k].astype(jnp.float32)
    nh = sizes["mamba_n_heads"]
    x = jax.random.normal(key_from_seed(seed, stream=2),
                          (tokens, sizes["hidden_size"]), jnp.float32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) +
                          sizes["rms_norm_eps"])
    raw = jnp.einsum("th,lh,lhk->ltk", x * sizes["ssm_in_multiplier"],
                     f32("h.norm_in"), f32("h.w_in")[..., -nh:],
                     precision="highest") * sizes["ssm_multipliers"][-1]
    dt = jax.nn.softplus(raw + f32("h.dt_bias")[:, None, :])
    return 1.0 / (dt.mean(1) * jnp.exp(f32("h.A_log")))
