"""Architecture "falcon_h1" (a configuration's `program.architecture`)
for the kinds that build the program from that name
(`kinds/serve_open_arch.py`): the model config, the seeded weights
(`weights_falcon_h1.py`) laid out both ways, and the plain reference
(`reference/falcon_h1.py`).

Every layer keeps a state-space mixer's state beside its K/V pages, so
the module also brings the comparison of that state: `live_state`
reads, when the window closes, the state matrix the program holds for
the slots then live, and `state_checks` holds it, element for element,
against the reference's direct sum over the slot's tokens. PR 26 found
that logits cannot tell a state kept in bfloat16 from a float32 one
(a read-out sums over the state's rows and their roundings average
out); an element of the state itself can.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_falcon_h1
from benchmark.reference import falcon_h1 as reference

STATE_LAYER = 0     # the layer whose state is compared
STATE_KEY = "ssm_state"


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module). `overrides` lays `model` keys of a
    control over the model config (a lower-precision path of the
    program's own)."""
    try:
        from deepspeed_tpu.models.falcon_h1 import FalconH1Config
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture 'falcon_h1': {e}")
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    # every key of the configuration that the program's config has,
    # at the file's value (a list is a tuple there)
    settings = {f.name: tuple(v) if isinstance(v, list) else v
                for f in dataclasses.fields(FalconH1Config)
                for v in [sizes.get(f.name)] if v is not None}
    settings.update(
        initializer_range=sizes["assumed"]["initializer_range"],
        ssm_state_dtype=jnp.dtype(sizes["assumed"]["ssm_state_dtype"]),
        dtype=dtype, param_dtype=dtype)
    for k, v in (overrides or {}).items():
        settings[k] = jnp.dtype(v) if k.endswith("dtype") else v
    flat = weights_falcon_h1.make_weights(sizes, seed, dtype)
    memory = np.asarray(weights_falcon_h1.memory_lengths(sizes, seed, flat))
    harness.say("weights: the state-space heads remember",
                " / ".join(f"{x:.3g}" for x in np.percentile(
                    memory, [0, 10, 50, 90, 100])),
                "tokens (min / p10 / median / p90 / max over",
                memory.size, "layer-heads)")
    return (FalconH1Config(**settings), flat,
            weights_falcon_h1.to_program_tree(flat), reference)


def live_state(engine, slots, width):
    """What the program holds of layer STATE_LAYER's state matrix for
    `slots` (at most `width` of them: one shape, so a call before the
    window opens leaves nothing to compile at its close), as it lies:
    [{"H": [heads, P, N] float32, "dtype": the held type's name}] a
    slot."""
    H = engine.cache_arrays()[engine.serving.cache_keys.index(STATE_KEY)]
    at = np.zeros((width,), np.int32)
    at[:len(slots)] = slots
    got = np.asarray(H[STATE_LAYER][jnp.asarray(at)].astype(jnp.float32))
    return [{"H": got[i], "dtype": str(H.dtype)} for i in range(len(slots))]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(tokens the state has taken in, `live_state`'s
    reading)]. `ssm_state_rel`: the widest distance of an element of
    the held state from the reference's direct sum over the same
    tokens (`reference.ssm_state`), as a share of that head's largest,
    over slots and heads. `state_dtype_differs`: 1 where the program
    holds the state in another type than the configuration's
    `ssm_state_dtype`. Under a reference control the reference in the
    lower precision stands in the program's place."""
    if not live:
        return []

    def state_from(cast):
        f = jax.jit(lambda flat, ids, n: reference.ssm_state(
            flat, ids, n, sizes, STATE_LAYER, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            return np.asarray(f(flat, jnp.asarray(ids),
                                jnp.asarray(len(seq), jnp.int32)))
        return of

    want_of = state_from(None)
    lower = None if control_cast is None else state_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    far = []
    for seq, got in live:
        want = want_of(seq)
        held = got["H"] if lower is None else lower(seq)
        far.append(float((np.abs(held - want).max((1, 2)) /
                          np.abs(want).max((1, 2))).max()))
    configured = str(jnp.dtype(sizes["assumed"]["ssm_state_dtype"]))
    differs = float(any(got["dtype"] != configured for _, got in live))
    harness.say("reference: layer", STATE_LAYER, "state matrix of",
                len(live), "live slots, element for element; off by",
                " ".join(f"{x:.5f}" for x in far), "; held as",
                live[0][1]["dtype"])
    return [{"name": "ssm_state_rel", "value": max(far),
             "limit": limits["ssm_state_rel"],
             "ok": max(far) <= limits["ssm_state_rel"]},
            {"name": "state_dtype_differs", "value": differs, "limit": 0.0,
             "ok": differs == 0.0}]
