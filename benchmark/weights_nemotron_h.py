"""The benchmark's weights for the `nemotron_h` family (NVIDIA
Nemotron-3-Nano): made on the device from the seed in the type the
cell serves them in, one small jitted program per leaf, a leaf made
alone bit for bit the leaf made with the rest (`benchmark/weights.py`'s
convention).

The plain reference and the program both get these arrays. They are a
flat dict keyed by the reference's names: the pattern is read as RUNS
(`reference/nemotron_h.py::runs`: `EM` pairs, else one letter) and
`r<run>.<letter>.<leaf>` holds that leaf of the run's layers of that
letter, stacked `[steps, ...]`; `x.w_up`, `x.w_down` are the routed
experts' own matrices of EVERY expert layer, `[expert layers, held,
...]`; `to_program_tree` lays the same arrays out as the program's
parameter tree.

The chip's share: the configuration's `n_routed_experts` counts the
routed experts HELD; the router and its selection bias keep the
PUBLISHED width (`published.n_routed_experts`), and `vocab_size` is
the slice's. Where `program.expert_width_stored` is wider than
`moe_intermediate_size` the routed experts' matrices are made at the
stored width with ZEROS past the published one (relu(0)^2 = 0: exact).

What is drawn how (the configuration file's `assumed` has the reasons):

  * every projection, the router and the embedding normal with spread
    r = 0.02; the residual projections (W_out, W_o, every W_down) carry
    1 / sqrt(the PUBLISHED depth) besides (one branch a layer:
    `rescale_prenorm_residual`). W_in's dt segment alone is drawn at
    r / 8: at r the seeded projection adds noise of spread 1.04 to dt
    before the softplus (there is no multiplier in front of it here)
    and shortens every head's memory by e^0.5 on average, at r / 8 the
    noise is 0.13 as in Falcon-H1's file;
  * the state-space scalars by Mamba-2's published initialisation:
    A_log = log U[1, 16], dt_bias the inverse softplus of a log-uniform
    dt in [time_step_min, time_step_max], D = 1, all float32; the
    convolution's weights and bias uniform in +-1/sqrt(K);
  * norm weights round 1 (0.1) so that a fault in a norm's weight path
    shows;
  * `expert_bias` normal round 0 with spread 0.02, float32, as
    Trinity's and Sarvam-105B's files draw it, and then BALANCED
    (`balanced_bias`) for Sarvam-105B's reason: this chip holds a SHARE
    of the experts (half), and which of a seed's experts are in favour
    must not decide how many of the held ones a step touches.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.nemotron_h import runs
from benchmark.weights import _leaf, key_from_seed
from benchmark.weights_afmoe import EXPERT_BIAS_SPREAD
from benchmark.weights_falcon_h1 import (A_RANGE, _columns_leaf,
                                         _uniform_leaf)
from benchmark.weights_sarvam_mla import (BALANCE_ROUNDS, BALANCE_SEQUENCES,
                                          BALANCE_TOKENS)

DT_SPREAD_CUT = 8.0


def float32_leaf(name):
    return name.rsplit(".", 1)[-1] in ("A_log", "dt_bias", "D",
                                       "expert_bias")


def router_width(sizes):
    """The routed experts the router scores: the published count."""
    return sizes.get("published", {}).get("n_routed_experts",
                                          sizes["n_routed_experts"])


def stored_width(sizes):
    """The columns a routed expert's W_up is stored at."""
    return sizes["program"].get("expert_width_stored",
                                sizes["moe_intermediate_size"])


def segments(sizes):
    """Widths of W_in's three segments z | xBC | dt."""
    d_ssm = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    gn = sizes["n_groups"] * sizes["ssm_state_size"]
    return (d_ssm, d_ssm + 2 * gn, sizes["mamba_num_heads"])


def weight_shapes(sizes):
    """{name: (how, shape, a, b)}: `normal` (spread a, centre b),
    `columns` (a: the spread of every segment of the last axis),
    `uniform` on [a, b], `log_uniform` exp(U[log a, log b]),
    `dt_bias` (the inverse softplus of a log-uniform dt in [a, b]),
    `experts` (normal with spread a, a layer at a time; b = (axis,
    width): zeros from `width` on along `axis`, where the stored
    width lies)."""
    H, I, Is = (sizes["hidden_size"], sizes["moe_intermediate_size"],
                sizes["moe_shared_expert_intermediate_size"])
    hq, hk, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    nh, K = sizes["mamba_num_heads"], sizes["conv_kernel"]
    V, held, E = sizes["vocab_size"], sizes["n_routed_experts"], \
        router_width(sizes)
    d_ssm, conv, _ = segments(sizes)
    r = sizes["assumed"]["initializer_range"]
    published = sizes.get("published", {}).get("num_hidden_layers",
                                               sizes["num_hidden_layers"])
    rs = r / math.sqrt(published)
    bound = 1.0 / math.sqrt(K)
    pattern = sizes["hybrid_override_pattern"]
    ne, wide = pattern.count("E"), stored_width(sizes)
    out = {"embed": ("normal", (V, H), r, 0.0),
           "head": ("normal", (H, V), r, 0.0),
           "norm_f": ("normal", (H,), 0.1, 1.0),
           "x.w_up": ("experts", (ne, held, H, wide), r, (3, I)),
           "x.w_down": ("experts", (ne, held, wide, H), rs, (2, I))}
    for i, (unit, n) in enumerate(runs(pattern)):
        for letter in unit:
            p = f"r{i:02d}.{letter}."
            out[p + "norm"] = ("normal", (n, H), 0.1, 1.0)
            if letter == "M":
                out.update({
                    p + "w_in": ("columns", (n, H, sum(segments(sizes))),
                                 (r, r, r / DT_SPREAD_CUT), None),
                    p + "conv_w": ("uniform", (n, conv, K), -bound, bound),
                    p + "conv_b": ("uniform", (n, conv), -bound, bound),
                    p + "dt_bias": ("dt_bias", (n, nh),
                                    sizes["time_step_min"],
                                    sizes["time_step_max"]),
                    p + "A_log": ("log_uniform", (n, nh), *A_RANGE),
                    p + "D": ("uniform", (n, nh), 1.0, 1.0),
                    p + "ssm_norm": ("normal", (n, d_ssm), 0.1, 1.0),
                    p + "w_out": ("normal", (n, d_ssm, H), rs, 0.0)})
            elif letter == "E":
                out.update({
                    p + "router": ("normal", (n, H, E), r, 0.0),
                    p + "expert_bias": ("normal", (n, E),
                                        EXPERT_BIAS_SPREAD, 0.0),
                    p + "shared_up": ("normal", (n, H, Is), r, 0.0),
                    p + "shared_down": ("normal", (n, Is, H), rs, 0.0)})
            else:
                out.update({
                    p + "wq": ("normal", (n, H, hq * d), r, 0.0),
                    p + "wk": ("normal", (n, H, hk * d), r, 0.0),
                    p + "wv": ("normal", (n, H, hk * d), r, 0.0),
                    p + "wo": ("normal", (n, hq * d, H), rs, 0.0)})
    return out


@functools.partial(jax.jit, static_argnames=("shape", "std", "axis",
                                             "width", "dtype"))
def _experts_leaf(key, shape, std, axis, width, dtype):
    """[layers, held, ., .] normal with spread `std`, zero from
    `width` on along `axis` (where the stored width lies), made a
    layer at a time: the draw's float32 and its bits never exist for
    the whole stack."""
    keep = (jnp.arange(shape[axis]) < width).reshape(
        [-1 if i == axis else 1 for i in range(1, len(shape))])
    return jax.lax.map(
        lambda k: (std * jax.random.normal(k, shape[1:], jnp.float32) *
                   keep).astype(dtype),
        jax.random.split(key, shape[0]))


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
        kind = "float32" if float32_leaf(name) else jnp.dtype(dtype).name
        if how == "normal":
            out[name] = _leaf(keys[i], shape, a, b, kind)
        elif how == "columns":
            out[name] = _columns_leaf(keys[i], shape, segments(sizes), a,
                                      kind)
        elif how == "experts":
            out[name] = _experts_leaf(keys[i], shape, a, *b, kind)
        else:
            out[name] = _uniform_leaf(keys[i], shape, a, b, kind, how)
    return out


def to_program_tree(flat, sizes):
    """`flat` laid out as `models/nemotron_h.py`'s parameter tree."""
    tree = {k: v for k, v in flat.items() if "." not in k}
    tree["experts"] = {k[2:]: v for k, v in flat.items()
                       if k.startswith("x.")}
    tree["runs"] = [
        {letter: {k.rsplit(".", 1)[-1]: v for k, v in flat.items()
                  if k.startswith(f"r{i:02d}.{letter}.")}
         for letter in unit}
        for i, (unit, _) in enumerate(runs(sizes["hybrid_override_pattern"]))]
    return tree


def bias_names(sizes):
    """The `expert_bias` leaves, in the pattern's order."""
    return [f"r{i:02d}.E.expert_bias" for i, (unit, _) in enumerate(
        runs(sizes["hybrid_override_pattern"])) if "E" in unit]


def balance_program(sizes, reference):
    """(flat weights, ids [sequences, T]) -> {name of an `expert_bias`
    leaf: the balanced bias [steps, E]}: see `balanced_bias`."""
    k = sizes["num_experts_per_tok"]

    def balanced(scores, bias):
        def one_round(r, bias):
            _, picks = jax.lax.top_k(scores + bias, k)
            load = jnp.zeros_like(bias).at[picks.ravel()].add(1.0)
            step = EXPERT_BIAS_SPREAD * (1 - r / BALANCE_ROUNDS * 39 / 40)
            return bias + step * jnp.sign(load.mean() - load)
        bias = jax.lax.fori_loop(0, BALANCE_ROUNDS, one_round, bias)
        return bias - bias.mean()

    def through(flat, ids):
        top, layers = reference.split(flat, sizes)
        xs, out = top["embed"][ids].astype(jnp.float32), []
        # a sequence at a time: four at once would put 5.5 GB of
        # temporaries beside the 10.9 GB of weights (the compiler's
        # count for a described v5e)
        each = lambda layer: lambda xs: jax.lax.map(layer, xs)
        for letter, lp in layers:
            if letter == "M":
                xs = each(lambda x: reference.mamba(lp, x, sizes))(xs)
            elif letter == "*":
                xs = each(lambda x: reference.attention(lp, x, sizes))(xs)
            else:
                scores = each(lambda x: reference.router_scores(
                    lp, x, sizes))(xs)
                out.append(balanced(scores.reshape(-1, scores.shape[-1]),
                                    lp["expert_bias"].astype(jnp.float32)))
                lp = dict(lp, expert_bias=out[-1])
                xs = each(lambda x: reference.experts(lp, x, sizes)[0])(xs)
        # back into the leaves' stacks, a run's steps together
        stacked, at = {}, 0
        for name in bias_names(sizes):
            n = flat[name].shape[0]
            stacked[name] = jnp.stack(out[at:at + n])
            at += n
        return stacked

    return jax.jit(through)


def balanced_bias(flat, sizes, seed, reference):
    """{name: `expert_bias` [steps, E] float32} after the published
    load-balancing rule, as `weights_sarvam_mla.balanced_bias` has it
    (its rounds, its step, its rows: `BALANCE_SEQUENCES` sequences of
    seeded uniform tokens through the plain reference layer after
    layer, each expert layer with the bias just balanced for it); one
    program, at the default precision (the loads are counts)."""
    ids = jax.random.randint(
        key_from_seed(seed, stream=2),
        (BALANCE_SEQUENCES,
         min(BALANCE_TOKENS, sizes["max_position_embeddings"])),
        0, sizes["vocab_size"])
    return balance_program(sizes, reference)(flat, ids)
