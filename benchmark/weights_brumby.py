"""The benchmark's weights for the Brumby family: made on the device
from the seed in the type the cell serves them in, one small jitted
program per leaf, a leaf made alone bit for bit the leaf made with the
rest (`benchmark/weights.py`'s convention and its `_leaf`).

The plain reference and the program both get these arrays. They are a
flat dict keyed by the reference's names (`h.*` leaves are stacked
`[n_layer, ...]`); `to_program_tree` lays the same arrays out as the
program's parameter tree.

Normal 0.02 for every projection, the two residual projections scaled
by 1/sqrt(2 x the PUBLISHED depth). Norm weights are drawn round 1
(0.1) so that a fault in a norm's weight path shows. The gate: `h.wg`
at 0.02 and the bias `h.bg` uniform in [2, 9] per layer and key/value
head. A bias-free gate would give a log-gate that is zero-mean over
tokens, a mean log-gate below -0.7 at any spread, and a state that
forgets within two tokens: nothing a fault in carrying state across
chunks could show in. With the bias a head's memory, 1 / -mean(lg),
runs from a few tokens to thousands (`memory_lengths`).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, key_from_seed

GATE_BIAS = (2.0, 9.0)


def weight_shapes(sizes):
    """{name: (shape, spread, centre)}: normal(centre, spread), but
    `h.bg` uniform on [centre - spread, centre + spread]."""
    L, H, F = (sizes["num_hidden_layers"], sizes["hidden_size"],
               sizes["intermediate_size"])
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    V = sizes["vocab_size"]
    r = sizes["assumed"]["initializer_range"]
    published = sizes.get("published", {}).get("num_hidden_layers", L)
    rs = r / math.sqrt(2 * published)
    lo, hi = GATE_BIAS
    return {
        "embed": ((V, H), r, 0.0),
        "head": ((H, V), r, 0.0),
        "norm_f": ((H,), 0.1, 1.0),
        "h.norm_in": ((L, H), 0.1, 1.0),
        "h.wq": ((L, H, hq * d), r, 0.0),
        "h.wk": ((L, H, hk * d), r, 0.0),
        "h.wv": ((L, H, hk * d), r, 0.0),
        "h.wg": ((L, H, hk), r, 0.0),
        "h.bg": ((L, hk), (hi - lo) / 2, (hi + lo) / 2),
        "h.q_norm": ((L, d), 0.1, 1.0),
        "h.k_norm": ((L, d), 0.1, 1.0),
        "h.wo": ((L, hq * d, H), rs, 0.0),
        "h.norm_post": ((L, H), 0.1, 1.0),
        "h.w_gate": ((L, H, F), r, 0.0),
        "h.w_up": ((L, H, F), r, 0.0),
        "h.w_down": ((L, F, H), rs, 0.0),
    }


def _uniform_leaf(key, shape, half, centre, dtype):
    return (centre + half * jax.random.uniform(
        key, shape, jnp.float32, -1.0, 1.0)).astype(dtype)


def make_weights(sizes, seed, dtype, only=None):
    """{name: array} for every leaf, or for the leaves named in `only`."""
    shapes = weight_shapes(sizes)
    names = sorted(shapes)
    keys = jax.random.split(key_from_seed(seed, stream=1), len(names))
    name_of = jnp.dtype(dtype).name
    return {name: (_uniform_leaf if name == "h.bg" else _leaf)(
                keys[i], *shapes[name], name_of)
            for i, name in enumerate(names)
            if only is None or name in only}


def to_program_tree(flat):
    """`flat` laid out as `models/brumby.py`'s parameter tree."""
    tree = {k: v for k, v in flat.items() if not k.startswith("h.")}
    tree["layers"] = {k[2:]: v for k, v in flat.items()
                      if k.startswith("h.")}
    return tree


def memory_lengths(sizes, seed, tokens=4096):
    """[n_layer, n_kv_head] effective memory in tokens of the seeded
    gates, 1 / -mean_t(lg_t), with embedding rows of random tokens
    standing in for every layer's input (each is RMS-normed before the
    gate, so only its direction matters)."""
    flat = make_weights(sizes, seed, jnp.float32,
                        only=("embed", "h.norm_in", "h.wg", "h.bg"))
    ids = jax.random.randint(key_from_seed(seed, stream=2), (tokens,), 0,
                             sizes["vocab_size"])
    x = flat["embed"][ids]
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) +
                          sizes["rms_norm_eps"])
    lg = jax.nn.log_sigmoid(
        jnp.einsum("th,lh,lhk->ltk", x, flat["h.norm_in"], flat["h.wg"],
                   precision="highest") + flat["h.bg"][:, None, :])
    return -1.0 / lg.mean(1)
