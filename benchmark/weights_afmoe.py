"""The benchmark's weights for the `afmoe` family (Trinity): made on
the device from the seed in the type the cell serves them in, one
small jitted program per leaf, a leaf made alone bit for bit the leaf
made with the rest (`benchmark/weights.py`'s convention).

The plain reference and the program both get these arrays. They are a
flat dict keyed by the reference's names: `d.*` leaves are the dense
layers' stacked `[num_dense_layers, ...]`, `h.*` the expert layers'
stacked `[expert layers, ...]`; `to_program_tree` lays the same arrays
out as the program's parameter tree.

What is drawn how (the configuration file's `assumed` has the reasons):

  * every projection, the router and the embedding normal with spread
    r = 0.02, as the other architectures' files draw theirs; the three
    residual projections (W_o, W_down, the experts' and the shared
    expert's W_down) carry 1 / sqrt(2 x the PUBLISHED depth) besides.
    Every branch's output passes a norm before it reaches the residual
    (the sandwich norms), so these spreads set no magnitude there; the
    router's sets how far apart the scores lie (logits of spread 0.9:
    scores in 0.2 .. 0.8, the 8th and 9th of 128 about 0.06 apart in
    the logit);
  * norm weights round 1 (0.1), the query and key heads' too, so that
    a fault in a norm's weight path shows;
  * `expert_bias` normal round 0 with spread 0.02, float32: a tenth of
    the scores' own spread, so that it decides a pick here and there
    and a selection that left it out, or a weight that took it in,
    shows.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, key_from_seed

FLOAT32_LEAVES = ("h.expert_bias",)
NORMS = ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")
EXPERT_BIAS_SPREAD = 0.02


def weight_shapes(sizes):
    """{name: (shape, spread, centre)}."""
    H, F, I, E = (sizes["hidden_size"], sizes["intermediate_size"],
                  sizes["moe_intermediate_size"], sizes["num_experts"])
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    V, nd = sizes["vocab_size"], sizes["num_dense_layers"]
    ne = sizes["num_hidden_layers"] - nd
    Is = sizes["num_shared_experts"] * I
    r = sizes["assumed"]["initializer_range"]
    published = sizes.get("published", {}).get("num_hidden_layers",
                                               sizes["num_hidden_layers"])
    rs = r / math.sqrt(2 * published)
    out = {"embed": ((V, H), r, 0.0), "head": ((H, V), r, 0.0),
           "norm_f": ((H,), 0.1, 1.0)}
    for p, n in (("d.", nd), ("h.", ne)):
        out.update({p + name: ((n, H), 0.1, 1.0) for name in NORMS})
        out.update({
            p + "q_norm": ((n, d), 0.1, 1.0),
            p + "k_norm": ((n, d), 0.1, 1.0),
            p + "wq": ((n, H, hq * d), r, 0.0),
            p + "wg": ((n, H, hq * d), r, 0.0),
            p + "wk": ((n, H, hk * d), r, 0.0),
            p + "wv": ((n, H, hk * d), r, 0.0),
            p + "wo": ((n, hq * d, H), rs, 0.0)})
    out.update({
        "d.w_gate": ((nd, H, F), r, 0.0), "d.w_up": ((nd, H, F), r, 0.0),
        "d.w_down": ((nd, F, H), rs, 0.0),
        "h.router": ((ne, H, E), r, 0.0),
        "h.expert_bias": ((ne, E), EXPERT_BIAS_SPREAD, 0.0),
        "h.w_gate": ((ne, E, H, I), r, 0.0),
        "h.w_up": ((ne, E, H, I), r, 0.0),
        "h.w_down": ((ne, E, I, H), rs, 0.0),
        "h.shared_gate": ((ne, H, Is), r, 0.0),
        "h.shared_up": ((ne, H, Is), r, 0.0),
        "h.shared_down": ((ne, Is, H), rs, 0.0)})
    return out


def make_weights(sizes, seed, dtype, only=None):
    """{name: array} for every leaf, or for the leaves named in `only`."""
    shapes = weight_shapes(sizes)
    names = sorted(shapes)
    keys = jax.random.split(key_from_seed(seed, stream=1), len(names))
    return {name: _leaf(keys[i], *shapes[name],
                        "float32" if name in FLOAT32_LEAVES
                        else jnp.dtype(dtype).name)
            for i, name in enumerate(names)
            if only is None or name in only}


def to_program_tree(flat):
    """`flat` laid out as `models/trinity.py`'s parameter tree."""
    tree = {k: v for k, v in flat.items() if k[:2] not in ("d.", "h.")}
    tree["dense"] = {k[2:]: v for k, v in flat.items() if k[:2] == "d."}
    tree["layers"] = {k[2:]: v for k, v in flat.items() if k[:2] == "h."}
    return tree
