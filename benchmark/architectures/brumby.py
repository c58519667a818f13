"""Architecture "brumby" (a configuration's `program.architecture`)
for the kinds that build the program from that name
(`kinds/serve_open_arch.py`): the model config, the seeded weights
(`weights_brumby.py`) laid out both ways, and the plain reference
(`reference/brumby.py`). A kind imports this module by the name; a new
architecture is a new file here with the same `build`.

The model keeps recurrent state, so the module also brings the
comparison of that state (optional for an architecture): `live_state`
reads, when the window closes, what the program holds for the slots
then live, and `state_checks` holds it against the reference's
all-pairs sum.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_brumby
from benchmark.reference import brumby as reference

KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "max_position_embeddings", "rms_norm_eps", "rope_theta")
STATE_LAYER = 0     # the layer whose state is compared


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module). `overrides` lays `model` keys of a
    control over the model config (a lower-precision path of the
    program's own)."""
    try:
        from deepspeed_tpu.models.brumby import BrumbyConfig
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture 'brumby': {e}")
    ret = sizes["assumed"]["retention"]
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    settings = dict(
        {k: sizes[k] for k in KEYS}, retention_degree=ret["degree"],
        retention_eps=ret["eps"], retention_chunk=ret["chunk"],
        state_dtype=jnp.dtype(ret["state_dtype"]), dtype=dtype,
        param_dtype=dtype)
    for k, v in (overrides or {}).items():
        settings[k] = jnp.dtype(v) if k.endswith("dtype") else v
    flat = weights_brumby.make_weights(sizes, seed, dtype)
    return (BrumbyConfig(**settings), flat,
            weights_brumby.to_program_tree(flat), reference)


def probes(head_dim):
    """[2 d, d] float32 directions the state is read along: every unit
    vector, and every sum of a unit vector and the one half a head
    away. Under the symmetric square a unit vector picks ONE row of a
    head's state and such a sum three, so a reading is those rows
    themselves: nothing is averaged over the state's 8,256 rows, as a
    real query's read-out is (which is why the logits cannot tell a
    bfloat16 state from a float32 one)."""
    eye = np.eye(head_dim, dtype=np.float32)
    return np.concatenate(
        [eye, (eye + np.roll(eye, head_dim // 2 + 1, axis=1)) /
         np.sqrt(2.0)]) * np.sqrt(head_dim)


@functools.partial(jax.jit, static_argnames=("scale",))
def _read(S, z, at, dirs, scale):
    from deepspeed_tpu.ops.retention import phi
    feat = phi(dirs, scale)
    with jax.default_matmul_precision("highest"):
        return (jnp.einsum("pD,shDd->shpd", feat,
                           S[STATE_LAYER, at].astype(jnp.float32)),
                jnp.einsum("pD,shD->shp", feat,
                           z[STATE_LAYER, at].astype(jnp.float32)))


def live_state(engine, slots, width):
    """What the program holds of layer STATE_LAYER for `slots` (at most
    `width` of them: one compiled shape, so a call before the window
    opens leaves nothing to compile at its close), read along `probes`
    with the program's own feature map, so that nothing here knows how
    the state's rows are ordered:
    [{"num": [Hk, P, d], "den": [Hk, P], "dtype": name}] a slot."""
    mc = engine.model_config
    S, z = engine.cache_arrays()
    at = np.zeros((width,), np.int32)
    at[:len(slots)] = slots
    num, den = _read(S, z, jnp.asarray(at),
                     jnp.asarray(probes(mc.head_dim)),
                     scale=mc.retention_scale)
    num, den = np.asarray(num), np.asarray(den)
    return [{"num": num[i], "den": den[i], "dtype": str(S.dtype)}
            for i in range(len(slots))]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(tokens the state has taken in, `live_state`'s
    reading)]. `state_rows_rel`: the widest distance of a reading from
    the reference's all-pairs sum over the same tokens
    (`reference.state_rows`), as a share of that head's largest, over
    slots, heads, numerators and normalisers. `state_dtype_differs`: 1
    where the program holds the state in another type than the
    configuration's `state_dtype`. Under a reference control the
    reference in the lower precision stands in the program's place."""
    if not live:
        return []
    dirs = jnp.asarray(probes(sizes["head_dim"]))

    def rows_from(cast):
        f = jax.jit(lambda flat, ids, n: reference.state_rows(
            flat, ids, n, sizes, dirs, STATE_LAYER, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            num, den = f(flat, jnp.asarray(ids),
                         jnp.asarray(len(seq), jnp.int32))
            return np.asarray(num), np.asarray(den)
        return of

    want_of = rows_from(None)
    lower = None if control_cast is None else rows_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    far = []
    for seq, got in live:
        want_num, want_den = want_of(seq)
        got_num, got_den = (got["num"], got["den"]) if lower is None \
            else lower(seq)
        for got_x, want_x in ((got_num, want_num), (got_den, want_den)):
            axes = tuple(range(1, want_x.ndim))
            far.append(float((np.abs(got_x - want_x).max(axes) /
                              np.abs(want_x).max(axes)).max()))
    configured = str(jnp.dtype(sizes["assumed"]["retention"]["state_dtype"]))
    differs = float(any(got["dtype"] != configured for _, got in live))
    harness.say("reference: layer", STATE_LAYER, "state of", len(live),
                "live slots along", dirs.shape[0], "directions; off by",
                " ".join(f"{x:.5f}" for x in far), "; held as",
                live[0][1]["dtype"])
    return [{"name": "state_rows_rel", "value": max(far),
             "limit": limits["state_rows_rel"],
             "ok": max(far) <= limits["state_rows_rel"]},
            {"name": "state_dtype_differs", "value": differs, "limit": 0.0,
             "ok": differs == 0.0}]
