"""The expert layer as it is SERVED: dropless top-k routing over
experts that are all held (or the share of them this chip is told it
holds), as plain functions on plain arrays. The training subsystem
beside it (`router.py`, `dispatch.py`, `experts.py`, `layer.py`) is a
different contract: a softmax gate, a capacity factor and dropped
tokens, bias + GeLU experts, flax modules, GSPMD all-to-alls. Nothing
here imports it and no serving `block` calls it.

One layer on rows x [T, H] (a decode launch: one row a slot; a prefill
launch: the chunk's rows), E experts, k picks a token:

    s      = sigmoid(x W_r)                       float32 [T, E]
    picks  = the k experts of largest s + bias    (the bias selects
                                                   and is NOT in the weight)
    w      = scale * s[picks] / sum(s[picks])     float32 [T, k]
    y      = Shared(x) + sum_j w_j Expert_{picks_j}(x)

An expert's FORM is the caller's (`form`), for the routed experts and
the shared one alike, at width I:

    GATED_SILU  Expert(x) = (silu(x W_gate) * (x W_up)) W_down
                (three matrices: Trinity, Sarvam-105B)
    RELU2       Expert(x) = relu(x W_up)^2 W_down
                (two matrices and NO gate: Nemotron-H; there is no
                `w_gate`, no `shared_gate` and no third product)

No token is dropped and there is no capacity: the T * k (token, pick)
rows are laid out sorted by expert (a counting sort: one cumulative
sum over a [T k, E] indicator, no comparison sort), each expert's
rows are multiplied by that expert's matrices in ONE grouped product
a projection (`grouped_product`), and the results are gathered back
and summed under their weights.

**Experts held.** The layer is told `first_expert`; the expert
weights it is handed are those of experts first_expert ..
first_expert + held (their second axis: the first is the layer, see
`grouped_product`). It routes over ALL E experts
(the router and its bias are whole everywhere), computes its own
experts' rows and returns its part of the sum: rows routed elsewhere
contribute zero here, and the share that holds expert 0 adds the
shared expert, so the parts of a set of shares that cover the experts
add up to the whole layer. With (0, E) that is the whole layer with no
exchange; no code here stands in for chips that are absent.

The grouped product: on a TPU `jax.experimental.pallas.ops.tpu
.megablox.gmm` (a Pallas kernel that visits the row tiles of each
non-empty expert and streams that expert's matrix once; it is given
every layer's experts as one run of groups, all empty but this
layer's, so nothing is sliced), elsewhere `jax.lax.ragged_dot`. The
kernel's tiling is the SHAPES' (`gmm_tiling`): of the contraction and
of the columns the largest whole number of 128-lane tiles, at most
1024, that divides the dimension. A width that is no whole number of
lane tiles is the caller's to avoid: Mosaic takes a block that spans
such a dimension, but the chip's compiler then re-lays the WHOLE stack
of matrices for the kernel in every launch (Nemotron-H's experts are
1856 = 14.5 lane tiles wide: 3 copies of 4.5 GB in a decode launch's
program, found by compiling for a described v5e), so that family's
experts are STORED at 1920 columns, the last 64 zero (relu(0)^2 = 0:
exact).

Regions (`utils/scopes.py`): `moe_router`, `moe_dispatch` (the
counting sort and the gather of the rows), `moe_experts` (the grouped
products and what stands between them), `moe_shared`,
`moe_combine`. Counters, int32 [3] a call, `COUNTERS` names them:
experts of this share with at least one row, (token, pick) rows of
this share, rows of its busiest expert. Every row the launch computes
is counted, an idle slot's or a pad row's too: those rows are routed
and multiplied like any other, so this is what the product reads.

**Rows of no request.** A caller that knows which rows are a
request's hands `expert_layer` `live` [T] bool: the others are sorted
past every expert's rows (a group E of their own), so that they are in
no expert's product and in no count. Without it 60 idle slots of 96,
all one row, put 60 rows on each of the same k experts in every
launch: experts no request picked are read, and a group of 60 rows
lies across a row tile's edge of the grouped product, whose two tiles
each stream that expert's matrices (PERF.md section 6, PR 39). Their
rows of y are the shared expert's alone.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.flash_attention import _on_tpu
from deepspeed_tpu.utils.scopes import (SCOPE_MOE_COMBINE,
                                        SCOPE_MOE_DISPATCH,
                                        SCOPE_MOE_EXPERTS,
                                        SCOPE_MOE_ROUTER, SCOPE_MOE_SHARED)

f32 = jnp.float32
COUNTERS = ("moe_experts_touched", "moe_rows", "moe_rows_max_expert")
# an expert's forms (the module's docstring)
GATED_SILU, RELU2 = "gated_silu", "relu2"
# rows of a tile of the grouped product on the chip, and the most of
# the contraction or of the columns that a tile takes
GMM_ROWS, GMM_MOST = 128, 1024


def gmm_tiling(k, n):
    """(rows, contraction, columns) of a tile of the grouped product
    of rows [M, k] with matrices [k, n]: of k and of n the largest
    whole number of 128-lane tiles, at most `GMM_MOST`, that divides
    it (a [2048, 1024] matrix is streamed in two tiles of 1024 x
    1024, a [2688, 1920] one in nine of 896 x 640). A dimension that
    no such tile divides is one tile (the module's docstring has what
    that costs on the chip)."""
    def tile(dim):
        fits = [t for t in range(128, GMM_MOST + 1, 128) if dim % t == 0]
        return max(fits, default=dim)
    return GMM_ROWS, tile(k), tile(n)


def route(x, w_router, expert_bias, top_k, route_scale):
    """x [T, H] -> (picks [T, k] int32, weights [T, k] float32,
    scores [T, E] float32)."""
    with jax.named_scope(SCOPE_MOE_ROUTER):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(f32), w_router.astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        _, picks = jax.lax.top_k(scores + expert_bias.astype(f32), top_k)
        picked = jnp.take_along_axis(scores, picks, axis=-1)
        weights = route_scale * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)
    return picks.astype(jnp.int32), weights, scores


def sorted_by_expert(picks, n_experts):
    """picks [T, k] -> (where [T k] int32: the row of the sorted
    layout that (token, pick) r = t k + j goes to; order [T k]: its
    inverse; sizes [E] int32: rows an expert). Rows of one expert keep
    the order of their tokens."""
    flat = picks.reshape(-1)
    mine = flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)
    rank = jnp.cumsum(mine.astype(jnp.int32), axis=0)
    sizes = rank[-1]
    before = jnp.cumsum(sizes) - sizes
    where = before[flat] + jnp.take_along_axis(
        rank, flat[:, None], axis=1)[:, 0] - 1
    order = jnp.zeros_like(where).at[where].set(
        jnp.arange(where.shape[0], dtype=where.dtype))
    return where, order, sizes


def grouped_product(rows, weights, layer, sizes, first_expert=0,
                    use_gmm=None, interpret=False):
    """rows [M, K] sorted by expert over ALL experts, sizes [E] rows
    an expert, weights [L, held, K, N]: every layer's matrices of the
    experts first_expert .. first_expert + held, WHOLE (a kernel reads
    layer `layer` of them where they lie: sliced out for it, a layer's
    matrices would be copied every launch) -> [M, N] in rows' type:
    each held expert's rows times its matrix of that layer, float32
    accumulation; rows of experts not held come out zero. On the chip
    the matrices are streamed in tiles that follow their shape
    (`gmm_tiling`)."""
    m = rows.shape[0]
    n_layers, held = weights.shape[:2]
    every = held == sizes.shape[0]
    if use_gmm is None:
        use_gmm = _on_tpu()
    if every:
        mine = sizes
    else:
        # the held experts' rows first: the product counts rows from 0
        first = jnp.asarray(first_expert, jnp.int32)
        start = jnp.cumsum(sizes)[first] - sizes[first]
        mine = jax.lax.dynamic_slice(sizes, (first,), (held,))
        rows = jnp.roll(rows, -start, axis=0)
    if use_gmm:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        # the stack as L x held groups, all empty but this layer's
        groups = jnp.zeros((n_layers, held), jnp.int32).at[layer].set(mine)
        out = gmm(jnp.pad(rows, ((0, -m % GMM_ROWS), (0, 0))),
                  weights.reshape((n_layers * held,) + weights.shape[2:]),
                  groups.reshape(-1), preferred_element_type=rows.dtype,
                  tiling=gmm_tiling(*weights.shape[2:]),
                  interpret=interpret)[:m]
    else:
        out = jax.lax.ragged_dot(
            rows, jax.lax.dynamic_index_in_dim(weights, layer, 0, False),
            mine, preferred_element_type=f32).astype(rows.dtype)
    if every:
        return out
    out = jnp.where((jnp.arange(m) < mine.sum())[:, None], out, 0)
    return jnp.roll(out, start, axis=0)


def expert_hidden(form, project):
    """What an expert of `form` hands its W_down, float32:
    `project(name)` is the expert's input times its matrix `name`
    ("gate", "up"). A form without a gate asks for none."""
    if form == GATED_SILU:
        return jax.nn.silu(project("gate").astype(f32)) * \
            project("up").astype(f32)
    if form == RELU2:
        return jnp.square(jax.nn.relu(project("up").astype(f32)))
    raise ValueError(f"an expert's form is {GATED_SILU!r} or {RELU2!r}, "
                     f"not {form!r}")


def gated_mlp(x, w_gate, w_up, w_down):
    """(silu(x W_gate) * (x W_up)) W_down, the gate in float32."""
    g = expert_hidden(GATED_SILU, {"gate": x @ w_gate, "up": x @ w_up}.get)
    return g.astype(x.dtype) @ w_down


def expert_layer(x, lp, experts, layer, top_k, route_scale, first_expert=0,
                 use_gmm=None, live=None, form=GATED_SILU):
    """x [T, H] in the compute type -> (y [T, H], counts int32 [3] in
    the order of `COUNTERS`, picks int32 [T, k]: the experts every row
    was routed to). lp, this layer's: `router` [H, E],
    `expert_bias` [E], `shared_up` [H, Is], `shared_down` [Is, H] and,
    for a form with a gate, `shared_gate` [H, Is]. experts, EVERY
    expert layer's, of which this is layer `layer`: the held experts'
    `w_up` [L, held, H, I], `w_down` [L, held, I, H] and, with a gate,
    `w_gate` [L, held, H, I]. `form`: what an expert computes, routed
    and shared alike (`GATED_SILU`, `RELU2`). Every matrix is read in
    x's type. `live` [T] bool, where given: the rows that are a
    request's; the others go to no expert (their picks read E) and are
    not counted."""
    t, dtype = x.shape[0], x.dtype
    w = lambda name: lp[name].astype(dtype)
    n_experts, held = lp["router"].shape[-1], experts["w_up"].shape[1]
    picks, weights, _ = route(x, lp["router"], lp["expert_bias"], top_k,
                              route_scale)
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        if live is None:
            where, order, sizes = sorted_by_expert(picks, n_experts)
        else:
            picks = jnp.where(live[:, None], picks, n_experts)
            where, order, sizes = sorted_by_expert(picks, n_experts + 1)
            sizes = sizes[:n_experts]
        rows = x[order // top_k]
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        product = lambda a, m: grouped_product(
            a, experts[m].astype(dtype), layer, sizes, first_expert, use_gmm)
        gate = expert_hidden(form, lambda m: product(rows, "w_" + m))
        out = product(gate.astype(dtype), "w_down")
        if live is not None and held == n_experts:
            # the kernel leaves rows past the last group unwritten
            out = jnp.where((jnp.arange(t * top_k) < sizes.sum())[:, None],
                            out, 0)
    with jax.named_scope(SCOPE_MOE_SHARED):
        shared = expert_hidden(form, lambda m: x @ w("shared_" + m)) \
            .astype(dtype) @ w("shared_down")
        shared = shared * (jnp.asarray(first_expert) == 0).astype(dtype)
    with jax.named_scope(SCOPE_MOE_COMBINE):
        picked = out[where].reshape(t, top_k, -1).astype(f32)
        y = shared.astype(f32) + (picked * weights[..., None]).sum(1)
        mine = jax.lax.dynamic_slice(
            sizes, (jnp.asarray(first_expert, jnp.int32),), (held,))
        counts = jnp.stack([(mine > 0).sum(), mine.sum(), mine.max()]) \
            .astype(jnp.int32)
    return y.astype(dtype), counts, picks
