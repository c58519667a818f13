"""The benchmark's own weights: made on the device from the seed, in
the type the cell trains or serves them in, one small jitted program
per leaf (16 launches; a leaf made alone later is bit for bit the leaf
made with the rest, which lets a check read a leaf's starting value
again without holding a second copy of the model).

The plain reference and the program both get these arrays, so neither
takes anything the other has made. They are kept as a flat dict keyed
by the reference's names (`h.*` leaves are stacked `[n_layer, ...]`);
`to_program_tree` lays the same arrays out as the program's parameter
tree, whose shape it reads from `jax.eval_shape(model.init)`.

GPT-2's published initialisation (normal 0.02, the two residual
projections scaled by 1/sqrt(2 * n_layer)), with one departure: biases
and LayerNorm parameters are drawn too (normal 0.02 around 0 and 1)
instead of being constant, so a fault in a bias or LayerNorm path
shows in the comparison with the reference.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed, stream=0):
    """A PRNG key from any whole number (the driver's seeds pass
    2**31), and a stream number for independent uses of one seed."""
    words = np.random.SeedSequence([int(seed), int(stream)]) \
        .generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def weight_shapes(sizes):
    """{name: (shape, std, mean)} for a GPT-2 of `sizes` (keys n_layer,
    n_embd, n_head, vocab_size, n_positions)."""
    L, H = sizes["n_layer"], sizes["n_embd"]
    V, P = sizes["vocab_size"], sizes["n_positions"]
    r = 0.02
    rs = r / math.sqrt(2 * L)
    return {
        "wte": ((V, H), r, 0.0),
        "wpe": ((P, H), r, 0.0),
        "ln_f.scale": ((H,), r, 1.0),
        "ln_f.bias": ((H,), r, 0.0),
        "h.ln_1.scale": ((L, H), r, 1.0),
        "h.ln_1.bias": ((L, H), r, 0.0),
        "h.c_attn.kernel": ((L, H, 3 * H), r, 0.0),
        "h.c_attn.bias": ((L, 3 * H), r, 0.0),
        "h.c_proj.kernel": ((L, H, H), rs, 0.0),
        "h.c_proj.bias": ((L, H), r, 0.0),
        "h.ln_2.scale": ((L, H), r, 1.0),
        "h.ln_2.bias": ((L, H), r, 0.0),
        "h.c_fc.kernel": ((L, H, 4 * H), r, 0.0),
        "h.c_fc.bias": ((L, 4 * H), r, 0.0),
        "h.mlp_c_proj.kernel": ((L, 4 * H, H), rs, 0.0),
        "h.mlp_c_proj.bias": ((L, H), r, 0.0),
    }


@functools.partial(jax.jit, static_argnames=("shape", "std", "mean", "dtype"))
def _leaf(key, shape, std, mean, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)) \
        .astype(dtype)


def make_weights(sizes, seed, dtype, only=None):
    """{name: array} for every leaf, or for the leaves named in `only`."""
    shapes = weight_shapes(sizes)
    names = sorted(shapes)
    keys = jax.random.split(key_from_seed(seed, stream=1), len(names))
    return {name: _leaf(keys[i], *shapes[name], jnp.dtype(dtype).name)
            for i, name in enumerate(names)
            if only is None or name in only}


def to_program_tree(flat, template):
    """`flat` laid out as the program's parameter tree. `template` is
    that tree's structure (`jax.eval_shape` of the model's init): the
    one auto-named child under "h" holds the stacked blocks."""
    (cell_name, cell), = template["h"].items()
    blocks = {mod: {leaf: flat[f"h.{mod}.{leaf}"] for leaf in leaves}
              for mod, leaves in cell.items()}
    tree = {"h": {cell_name: blocks},
            "ln_f": {k: flat[f"ln_f.{k}"] for k in template["ln_f"]},
            "wte": flat["wte"], "wpe": flat["wpe"]}
    want = jax.tree_util.tree_map(lambda x: x.shape, template)
    got = jax.tree_util.tree_map(lambda x: x.shape, tree)
    if want != got:
        raise ValueError("the benchmark's weights do not fit the "
                         f"program's parameter tree: {got} vs {want}")
    return tree


def from_program_tree(tree):
    """A program-layout tree (parameters, moments, gradients) as the
    flat dict of reference names."""
    (_, cell), = tree["h"].items()
    flat = {f"h.{mod}.{leaf}": x for mod, leaves in cell.items()
            for leaf, x in leaves.items()}
    flat.update({f"ln_f.{k}": v for k, v in tree["ln_f"].items()})
    flat["wte"], flat["wpe"] = tree["wte"], tree["wpe"]
    return flat
