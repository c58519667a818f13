"""Architecture "nemotron_h" (a configuration's `program.architecture`;
NVIDIA Nemotron-3-Nano-30B-A3B) for the kinds that build the program
from that name (`kinds/serve_open_arch.py`): the model config, the
seeded weights (`weights_nemotron_h.py`) laid out both ways, and the
plain reference (`reference/nemotron_h.py`).

The chip's share: the configuration's `n_routed_experts` is the routed
experts HELD and goes to the model config as `experts_held`
(`first_expert` 0 unless the file says otherwise); the router's width
is `published.n_routed_experts`.

Two comparisons beside the kind's three of the logits (`live_state` +
`state_checks`), both of what the window's own programs left:

  * `ssm_state_rel` and `state_dtype_differs`, as Falcon-H1's builder
    has them and for its reason (logits cannot tell a state kept in
    bfloat16 from a float32 one): the state matrix the program holds
    of the FIRST Mamba-2 layer for the slots live when the window
    closes, element for element against the reference's direct sum
    over the slot's tokens;
  * `router_picks_agree`, as Trinity's and Sarvam-105B's builders have
    it: the experts the window's decode program picked in every expert
    layer for the row whose logits are compared
    (`engine.last_row_readings()`), against the reference's picks for
    the same row; held from below.

The module also keeps the serving loop's `decode_batch` events for this
PR's program-counter metrics, as `afmoe.py` does and for its reason
(the sink's class and `touched_share` are that module's, `per_launch`
Sarvam-105B's builder's):
`fence_rows()` gives them to the readers under `benchmark/metrics/`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_nemotron_h
from benchmark.architectures.afmoe import (FenceRows,  # noqa: F401
                                            touched_share)
from benchmark.architectures.sarvam_mla import per_launch  # noqa: F401
from benchmark.reference import nemotron_h as reference

NAME = "nemotron_h"
STATE_LAYER = 0     # the Mamba-2 layer, of those held, whose state is compared
STATE_KEY = "ssm_state"


_fences = FenceRows()        # of the run in this process, for the readers


def fence_rows(ctx):
    """The `decode_batch` rows of the run in this process, the
    pre-roll's too (`loop_s` reads 0 when the window opens); [] for a
    cell of another architecture."""
    program = ctx.get("cell", {}).get("sizes", {}).get("program", {})
    if program.get("architecture") != NAME:
        return []
    return list(_fences.rows)


def window_rows(ctx):
    """The fence rows of the timed window: the first
    `fences_in_window` (the kind's own count) from clock 0 on."""
    rows = [row for row in fence_rows(ctx) if row["loop_s"] >= 0.0]
    return rows[:int(ctx.get("fences_in_window") or 0)]


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module). `overrides` lays `model` keys of a
    control over the model config. The selection bias is the drawn one
    run through the published load-balancing rule
    (`weights_nemotron_h.balanced_bias`)."""
    try:
        from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture {NAME!r}: {e}")
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    settings = {f.name: sizes[f.name]
                for f in dataclasses.fields(NemotronHConfig)
                if f.name in sizes}
    settings.update(
        n_routed_experts=weights_nemotron_h.router_width(sizes),
        experts_held=sizes["n_routed_experts"],
        first_expert=int(sizes.get("first_expert", 0)),
        expert_width_stored=weights_nemotron_h.stored_width(sizes),
        initializer_range=sizes["assumed"]["initializer_range"],
        ssm_state_dtype=jnp.dtype(sizes["assumed"]["ssm_state_dtype"]),
        dtype=dtype, param_dtype=dtype)
    for k, v in (overrides or {}).items():
        settings[k] = jnp.dtype(v) if k.endswith("dtype") else v
    global _fences
    _fences = FenceRows()            # a run's own rows
    flat = weights_nemotron_h.make_weights(sizes, seed, dtype)
    flat.update(weights_nemotron_h.balanced_bias(flat, sizes, seed,
                                                 reference))
    return (NemotronHConfig(**settings), flat,
            weights_nemotron_h.to_program_tree(flat, sizes), reference)


def live_state(engine, slots, width):
    """For each of `slots` (at most `width`: one compiled shape, so
    the kind's call before the window opens leaves nothing to compile
    at its close, and that call is where this module attaches its
    sink): what the program holds of Mamba-2 layer STATE_LAYER's state
    matrix, as it lies, and the experts the window's decode program
    picked in every expert layer for the slot's row of its last
    launch. [{"H": [heads, P, N] float32, "dtype": the held type's
    name, "picks": [expert layers, k] or None before any launch}]."""
    if _fences not in engine.monitor.sinks:
        engine.monitor.attach_sink(_fences)
    H = engine.cache_arrays()[engine.serving.cache_keys.index(STATE_KEY)]
    at = np.zeros((width,), np.int32)
    at[:len(slots)] = slots
    got = np.asarray(H[STATE_LAYER][jnp.asarray(at)].astype(jnp.float32))
    picks = engine.last_row_readings().get("moe_picks")
    if picks is not None:
        picks = np.asarray(picks)
        picks = picks[picks[:, 0, 0] >= 0]   # a step without an expert layer
        mc = engine.model_config
        since = [r for r in _fences.rows if r["loop_s"] >= 0.0]
        held = mc.experts_held * mc.expert_layers
        each = [touched_share([r], held) for r in since
                if r.get("iterations") and "moe_experts_touched" in r]
        harness.say(
            f"serve: the program's counters over {len(since)} fences "
            "since the window opened: nemotron_h_moe_held_touched_share "
            f"{touched_share(since, held) or 0.0:.2f} (a fence's least "
            f"{min(each, default=0.0):.2f}) of {held} held experts")
    return [{"H": got[i], "dtype": str(H.dtype),
             "picks": None if picks is None else picks[:, s]}
            for i, s in enumerate(slots)]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(the tokens a slot had taken in, `live_state`'s
    reading)]. `ssm_state_rel`: the widest distance of an element of
    the held state from the reference's direct sum over the same
    tokens (`reference.ssm_state`), as a share of that head's largest,
    over slots and heads. `state_dtype_differs`: 1 where the program
    holds the state in another type than the configuration's
    `ssm_state_dtype`. `router_picks_agree`: of the reference's picks
    for the last of those tokens, every expert layer's, the share the
    program picked too; the least over the slots; held from below.
    Under a reference control the reference in the lower precision
    stands in the program's place."""
    if not live:
        return []

    def read_from(cast):
        state = jax.jit(lambda flat, ids, n: reference.ssm_state(
            flat, ids, n, sizes, STATE_LAYER, cast))
        picks = jax.jit(lambda flat, ids, row: reference.router_picks(
            flat, ids, row, sizes, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            n = jnp.asarray(len(seq), jnp.int32)
            return (np.asarray(state(flat, jnp.asarray(ids), n)),
                    np.asarray(picks(flat, jnp.asarray(ids), n - 1)))
        return of

    want_of = read_from(None)
    lower = None if control_cast is None else read_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    far, agree = [], []
    for seq, got in live:
        want_H, want_picks = want_of(seq)
        held_H, held_picks = (got["H"], got["picks"]) if lower is None \
            else lower(seq)
        far.append(float((np.abs(held_H - want_H).max((1, 2)) /
                          np.abs(want_H).max((1, 2))).max()))
        if held_picks is not None:
            agree.append(float(np.mean([
                len(set(w) & set(h)) / len(w)
                for w, h in zip(want_picks, held_picks)])))
    configured = str(jnp.dtype(sizes["assumed"]["ssm_state_dtype"]))
    differs = float(any(got["dtype"] != configured for _, got in live))
    harness.say("reference: Mamba-2 layer", STATE_LAYER, "state matrix of",
                len(live), "live slots, element for element; off by",
                " ".join(f"{x:.5f}" for x in far), "; held as",
                live[0][1]["dtype"], "; of the reference's picks of their "
                "last rows in every expert layer the program picked",
                " ".join(f"{x:.4f}" for x in agree))
    checks = [{"name": "ssm_state_rel", "value": max(far),
               "limit": limits["ssm_state_rel"],
               "ok": max(far) <= limits["ssm_state_rel"]},
              {"name": "state_dtype_differs", "value": differs, "limit": 0.0,
               "ok": differs == 0.0}]
    if agree:
        checks.append({"name": "router_picks_agree", "value": min(agree),
                       "limit": limits["router_picks_agree"],
                       "ok": min(agree) >= limits["router_picks_agree"]})
    return checks
