"""The benchmark's weights for Phi-4-mini-flash-reasoning
(`program.architecture: phi4flash`): made on the device from the seed
in the type the cell serves them in, one small jitted program per leaf,
a leaf made alone bit for bit the leaf made with the rest
(`benchmark/weights.py`'s convention).

The plain reference and the program both get these arrays: a flat dict
keyed by the reference's names. The layers lie in three stacks of
PERIODS (an even layer and the odd one after it): `s.` the
self-decoder's (layers 0-15), `m.` the middle period (16, 17), `c.` the
cross-decoder's (18-31); under each, `a.` the even layer's leaves and
`b.` the odd one's, stacked [periods, ...]. `to_program_tree` lays the
same arrays out as `models/phi4flash.py`'s parameter tree.

What is drawn how (the configuration file's `assumed` has the
reasons): projections normal 0.02, the residual projections scaled by
1 / sqrt(2 x layers); biases normal 0.02 and norm weights normal 0.1
round 1, so that a fault in their paths shows; the four lambda vectors
of an attention layer normal 0.1, float32; Mamba-1's published
initialisation (`A_log` = log(1..N) along the state, held transposed
[N, d_inner] as the program holds the state; `dt_bias` the inverse
softplus of a log-uniform dt in [0.001, 0.1]; D = 1; W_dt uniform in
+-rank^-0.5; the convolution uniform in +-1/sqrt(K)), float32 where
the program computes in float32.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, key_from_seed
from benchmark.weights_falcon_h1 import _uniform_leaf

DT_RANGE = (1e-3, 1e-1)
FLOAT32_LEAVES = ("A_log_t", "dt_bias", "D", "lq1", "lk1", "lq2", "lk2")
STACKS = {"s": "self", "m": "bridge", "c": "cross"}


def assumed(sizes):
    """(d_inner, state, conv width, dt rank) from the file's `assumed`."""
    a = sizes["assumed"]
    return (a["mamba_expand"] * sizes["hidden_size"], a["mamba_d_state"],
            a["mamba_d_conv"], a["mamba_dt_rank"])


def periods(sizes):
    """{stack: its periods}."""
    quarter = sizes["num_hidden_layers"] // 4
    return {"s": quarter, "m": 1, "c": quarter - 1}


def weight_shapes(sizes):
    """{name: (how, shape, a, b)}: `normal` (spread a, centre b),
    `uniform` on [a, b], `dt_bias` (the inverse softplus of a
    log-uniform dt in [a, b]), `A_log` (log(1..N) along axis 1)."""
    H, F, V = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["vocab_size"])
    hq, hk = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = H // hq
    Di, N, K, R = assumed(sizes)
    r = sizes["assumed"]["initializer_range"]
    rs = r / math.sqrt(2 * sizes["num_hidden_layers"])
    out = {"embed": ("normal", (V, H), r, 0.0),
           "norm_f.w": ("normal", (H,), 0.1, 1.0),
           "norm_f.b": ("normal", (H,), r, 0.0)}

    def common(n):
        return {"norm_w": ("normal", (n, H), 0.1, 1.0),
                "norm_b": ("normal", (n, H), r, 0.0),
                "ffn_norm_w": ("normal", (n, H), 0.1, 1.0),
                "ffn_norm_b": ("normal", (n, H), r, 0.0),
                "w_gu": ("normal", (n, H, 2 * F), r, 0.0),
                "w_down": ("normal", (n, F, H), rs, 0.0)}

    def mamba(n):
        return dict(
            common(n), w_in=("normal", (n, H, 2 * Di), r, 0.0),
            conv_w=("uniform", (n, Di, K), -K ** -0.5, K ** -0.5),
            conv_b=("uniform", (n, Di), -K ** -0.5, K ** -0.5),
            w_x=("normal", (n, Di, R + 2 * N), r, 0.0),
            w_dt=("uniform", (n, R, Di), -R ** -0.5, R ** -0.5),
            dt_bias=("dt_bias", (n, Di), *DT_RANGE),
            A_log_t=("A_log", (n, N, Di), None, None),
            D=("uniform", (n, Di), 1.0, 1.0),
            w_out=("normal", (n, Di, H), rs, 0.0))

    def memory_unit(n):
        return dict(common(n), w_g=("normal", (n, H, Di), r, 0.0),
                    w_o=("normal", (n, Di, H), rs, 0.0))

    def attention(n, cross):
        q = {"wq": ("normal", (n, H, hq * d), r, 0.0),
             "bq": ("normal", (n, hq * d), r, 0.0)} if cross else \
            {"wqkv": ("normal", (n, H, (hq + 2 * hk) * d), r, 0.0),
             "bqkv": ("normal", (n, (hq + 2 * hk) * d), r, 0.0)}
        lam = {k: ("normal", (n, d), 0.1, 0.0)
               for k in ("lq1", "lk1", "lq2", "lk2")}
        return dict(common(n), **q, **lam,
                    subnorm=("normal", (n, 2 * d), 0.1, 1.0),
                    wo=("normal", (n, hq * d, H), rs, 0.0),
                    bo=("normal", (n, H), r, 0.0))

    for stack, n in periods(sizes).items():
        even = memory_unit(n) if stack == "c" else mamba(n)
        odd = attention(n, cross=stack == "c")
        out.update({f"{stack}.a.{k}": v for k, v in even.items()})
        out.update({f"{stack}.b.{k}": v for k, v in odd.items()})
    return out


@functools.partial(jax.jit, static_argnames=("shape",))
def _a_log(shape):
    n = shape[1]
    return jnp.broadcast_to(jnp.log(jnp.arange(
        1, n + 1, dtype=jnp.float32))[None, :, None], shape)


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
        kind = "float32" if name.rsplit(".", 1)[-1] in FLOAT32_LEAVES \
            else jnp.dtype(dtype).name
        if how == "normal":
            out[name] = _leaf(keys[i], shape, a, b, kind)
        elif how == "A_log":
            out[name] = _a_log(shape)
        else:
            out[name] = _uniform_leaf(keys[i], shape, a, b, kind, how)
    return out


def to_program_tree(flat):
    """`flat` laid out as `models/phi4flash.py`'s parameter tree."""
    tree = {"embed": flat["embed"],
            "norm_f": {"w": flat["norm_f.w"], "b": flat["norm_f.b"]}}
    for short, stack in STACKS.items():
        tree[stack] = {half: {k.split(".", 2)[2]: v for k, v in flat.items()
                              if k.startswith(f"{short}.{half}.")}
                       for half in ("a", "b")}
    return tree
