"""The benchmark's weights for the `sarvam_mla` family (Sarvam-105B):
made on the device from the seed in the type the cell serves them in,
one small jitted program per leaf, a leaf made alone bit for bit the
leaf made with the rest (`benchmark/weights.py`'s convention).

The plain reference and the program both get these arrays. They are a
flat dict keyed by the reference's names: `d.*` leaves are the dense
layers' stacked `[first_k_dense_replace, ...]`, `h.*` the expert
layers' stacked `[expert layers, ...]`; `to_program_tree` lays the
same arrays out as the program's parameter tree.

The chip's share: the configuration's `num_experts` counts the routed
experts HELD (their matrices `[expert layers, held, ...]`); the router
and its selection bias keep the PUBLISHED width
(`published.num_experts`), and `vocab_size` is the slice's.

What is drawn how (the configuration file's `assumed` has the reasons):

  * every projection, the router and the embedding normal with spread
    r = 0.02, as the other architectures' files draw theirs; the
    residual projections (W_o, W_down, the experts' and the shared
    expert's W_down) carry 1 / sqrt(2 x the PUBLISHED depth) besides.
    There is no norm between a branch and the residual here, so the
    stream grows from the embedding's 0.02 a value as branches add to
    it; every branch reads it through a norm;
  * norm weights round 1 (0.1), the query heads' and the latent's too,
    so that a fault in a norm's weight path shows;
  * `expert_bias` normal round 0 with spread 0.02, float32, as
    Trinity's file draws it and for its reason: a tenth of the scores'
    own spread, so that it decides a pick here and there and a
    selection that left it out, or a weight that took it in, shows;
  * and then BALANCED (`balanced_bias`), because a chip here holds a
    SHARE of the experts. A bias of 0.02 moves an expert's load by
    30% (the 8th of 128 sigmoid scores lies at 0.88, where a score
    moves 0.11 a unit of its logit and the tail's odds 2 a unit of
    spread), so which of a seed's experts are in favour decides how
    many of the 32 held a step of 32 rows touches, and with them the
    bytes it reads: 84.0% and 85.7% on two seeds (my CPU reading of
    the picks of 2,400 decode rows a seed, PR 39), and itl_mean_ms
    followed that share at 1% a point on the chip. The published
    model's bias is what its training's load-balancing rule left: each
    expert's load equal. So the drawn bias is run through that rule
    (raise the bias of an expert under the mean load, lower that of
    one over it) on the rows of seeded uniform tokens, layer after
    layer through the plain reference, until the loads are even.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.weights import _leaf, key_from_seed
# the selection bias's type and spread, and the program's tree of two
# stacks (`dense`, `layers`), are the `afmoe` family's
from benchmark.weights_afmoe import (EXPERT_BIAS_SPREAD,  # noqa: F401
                                     FLOAT32_LEAVES, to_program_tree)


def router_width(sizes):
    """The routed experts the router scores: the published count."""
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def weight_shapes(sizes):
    """{name: (shape, spread, centre)}."""
    H, F, I = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["moe_intermediate_size"])
    held, E = sizes["num_experts"], router_width(sizes)
    hq, dq = sizes["num_attention_heads"], sizes["q_head_dim"]
    rank, row = sizes["kv_lora_rank"], sizes["head_dim"]
    both = sizes["qk_nope_head_dim"] + sizes["v_head_dim"]
    V, nd = sizes["vocab_size"], sizes["first_k_dense_replace"]
    ne = sizes["num_hidden_layers"] - nd
    Is = sizes["num_shared_experts"] * I
    r = sizes["assumed"]["initializer_range"]
    published = sizes.get("published", {}).get("num_hidden_layers",
                                               sizes["num_hidden_layers"])
    rs = r / math.sqrt(2 * published)
    out = {"embed": ((V, H), r, 0.0), "head": ((H, V), r, 0.0),
           "norm_f": ((H,), 0.1, 1.0)}
    for p, n in (("d.", nd), ("h.", ne)):
        out.update({
            p + "norm_in": ((n, H), 0.1, 1.0),
            p + "norm_mlp": ((n, H), 0.1, 1.0),
            p + "q_norm": ((n, dq), 0.1, 1.0),
            p + "kv_norm": ((n, rank), 0.1, 1.0),
            p + "wq": ((n, H, hq * dq), r, 0.0),
            p + "w_kva": ((n, H, row), r, 0.0),
            p + "w_kvb": ((n, rank, hq * both), r, 0.0),
            p + "wo": ((n, hq * sizes["v_head_dim"], H), rs, 0.0)})
    out.update({
        "d.w_gate": ((nd, H, F), r, 0.0), "d.w_up": ((nd, H, F), r, 0.0),
        "d.w_down": ((nd, F, H), rs, 0.0),
        "h.router": ((ne, H, E), r, 0.0),
        "h.expert_bias": ((ne, E), EXPERT_BIAS_SPREAD, 0.0),
        "h.w_gate": ((ne, held, H, I), r, 0.0),
        "h.w_up": ((ne, held, H, I), r, 0.0),
        "h.w_down": ((ne, held, I, H), rs, 0.0),
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


# the sequences of uniform tokens the bias is balanced on, and the
# rounds of the rule: 8,192 rows put 512 a round on every expert, so
# the loads that come out even are even to 4% on other rows
BALANCE_SEQUENCES, BALANCE_TOKENS, BALANCE_ROUNDS = 4, 2048, 300


def balance_program(sizes, reference):
    """(flat weights, ids [sequences, T]) -> the balanced bias [expert
    layers, E]: see `balanced_bias`."""
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
        for lp, dense in layers:
            xs = jax.vmap(lambda x: reference.attend(lp, x, sizes)[0])(xs)
            if not dense:
                scores = jax.vmap(lambda a: reference.router_scores(
                    lp, a, sizes))(xs)
                out.append(balanced(scores.reshape(-1, scores.shape[-1]),
                                    lp["expert_bias"]))
                lp = dict(lp, expert_bias=out[-1])
            xs = jax.vmap(lambda a: reference.feed_forward(
                lp, a, sizes, dense)[0])(xs)
        return jnp.stack(out)

    return jax.jit(through)


def balanced_bias(flat, sizes, seed, reference):
    """`h.expert_bias` [expert layers, E] float32 after the published
    load-balancing rule: from the drawn bias, a round raises the bias
    of every expert that fewer than the mean of the rows picked and
    lowers that of every one that more did, by a step that falls from
    the bias's own spread to a fortieth of it. The rows are those of
    `BALANCE_SEQUENCES` sequences of seeded uniform tokens, computed
    by `reference` (the plain one: `split`, `attend`, `router_scores`,
    `feed_forward`) layer after layer, each expert layer with the bias
    just balanced for it; one program, at the default precision (the
    loads are counts)."""
    ids = jax.random.randint(
        key_from_seed(seed, stream=2),
        (BALANCE_SEQUENCES,
         min(BALANCE_TOKENS, sizes["max_position_embeddings"])),
        0, sizes["vocab_size"])
    return balance_program(sizes, reference)(flat, ids)
