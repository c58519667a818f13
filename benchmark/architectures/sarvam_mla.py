"""Architecture "sarvam_mla" (a configuration's `program.architecture`;
Sarvam-105B) for the kinds that build the program from that name
(`kinds/serve_open_arch.py`): the model config, the seeded weights
(`weights_sarvam_mla.py`) laid out both ways, and the plain reference
(`reference/sarvam_mla.py`).

The chip's share: the configuration's `num_experts` is the routed
experts HELD and goes to the model config as `experts_held`
(`first_expert` 0 unless the file says otherwise); the router's width
is `published.num_experts`.

Two comparisons beside the kind's three of the logits (`live_state` +
`state_checks`), both of what the window's own programs left:

  * `router_picks_agree`, as Trinity's builder has it: the experts the
    window's decode program picked in every expert layer for the row
    whose logits are compared (`engine.last_row_readings()`), against
    the reference's picks for the same row; held from below.
  * `latent_rows_rel` and `latent_rows_mean_rel`: the logits average
    over thousands of cached rows and cannot tell a row held in a
    lower precision, or written to another slot's page, from a sound
    one. So when the window closes the pool's rows of the first and of
    the last layer are read for the live slots through their page
    tables, every position, and held against the reference's
    [c~ ; k_rope] of the same tokens, a row's distance its widest
    value's as a share of the layer's largest. The FIRST layer's row
    depends on its own token and position alone, so its distance is
    rounding and nothing else: `latent_rows_rel` is the widest over
    rows and slots. The LAST layer's row has every layer before it
    behind it, and a pick that flips on bfloat16 rounding there moves
    single rows by a whole expert's share (the widest of a sound
    slot's 5,000 rows reads 0.14 to 0.17, its mean 0.012):
    `latent_rows_mean_rel` is the mean over a slot's rows, the largest
    over slots. `latent_dtype_differs` (limit 0) beside them: 1 where
    the pool is held in another type than the configuration's
    `program.param_dtype`.

The module also keeps the serving loop's `decode_batch` events for this
PR's program-counter metrics, as `afmoe.py` does and for its reason
(PERF.md section 7: `ctx["fence_rows"]` from `serve_open.drive` would
do without it; the sink's class and `touched_share` are that
module's): `fence_rows()` gives them to the readers under
`benchmark/metrics/`.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_sarvam_mla
# the sink of fence rows and the share of experts a launch touched are
# the expert layer's, whichever model holds it
from benchmark.architectures.afmoe import FenceRows, touched_share
from benchmark.reference import sarvam_mla as reference

NAME = "sarvam_mla"


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


def per_launch(rows, launches, *keys):
    """The mean a launch of each of `keys` over `rows`, a row's value
    its launches' sum (`launches` names the row's count of them); None
    where `rows` hold no such launch."""
    rows = [row for row in rows if all(k in row for k in keys)]
    n = sum(row[launches] for row in rows)
    return tuple(sum(row[k] for row in rows) / n for k in keys) if n \
        else None


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module). `overrides` lays `model` keys of a
    control over the model config. The selection bias is the drawn
    one run through the published load-balancing rule
    (`weights_sarvam_mla.balanced_bias`): this chip holds a share of
    the experts, and which of them are in favour must not be the
    seed's."""
    try:
        from deepspeed_tpu.models.sarvam_mla import SarvamMLAConfig
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture {NAME!r}: {e}")
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    settings = {f.name: sizes[f.name]
                for f in dataclasses.fields(SarvamMLAConfig)
                if f.name in sizes}
    settings.update(
        rope_scaling=tuple(sorted(sizes["rope_scaling"].items())),
        rope_theta=float(sizes["rope_theta"]),
        num_experts=weights_sarvam_mla.router_width(sizes),
        experts_held=sizes["num_experts"],
        first_expert=int(sizes.get("first_expert", 0)),
        initializer_range=sizes["assumed"]["initializer_range"],
        dtype=dtype, param_dtype=dtype)
    for k, v in (overrides or {}).items():
        settings[k] = jnp.dtype(v) if k.endswith("dtype") else v
    global _fences
    _fences = FenceRows()            # a run's own rows
    flat = weights_sarvam_mla.make_weights(sizes, seed, dtype)
    flat["h.expert_bias"] = weights_sarvam_mla.balanced_bias(
        flat, sizes, seed, reference)
    return (SarvamMLAConfig(**settings), flat,
            weights_sarvam_mla.to_program_tree(flat), reference)


def layers_read(n_layers):
    """The layers whose latent rows are compared: first and last."""
    return (0, n_layers - 1)


@functools.partial(jax.jit, static_argnames=("layers", "row"))
def _rows(pool, tables, layers, row):
    """[slots, len(layers), max_pages * page, row] of the pool, each
    slot's pages in its table's order."""
    got = pool[jnp.asarray(layers)][:, tables][..., :row]
    n_layers, slots = got.shape[:2]
    return got.reshape(n_layers, slots, -1, row).swapaxes(0, 1)


def live_state(engine, slots, width):
    """For each of `slots` (at most `width`: one compiled shape, so
    the kind's call before the window opens leaves nothing to compile
    at its close, and that call is where this module attaches its
    sink): the experts the window's decode program picked in every
    expert layer for the slot's row of its last launch, and the pool's
    rows of `layers_read` through the slot's page table.
    [{"picks": [expert layers, k] or None before any launch, "rows":
    [2, max_pages * page, row], "dtype": the pool's}]."""
    if _fences not in engine.monitor.sinks:
        engine.monitor.attach_sink(_fences)
    mc = engine.model_config
    (pool,) = engine.cache_arrays()[:1]
    at = np.zeros((width,), np.int32)
    at[:len(slots)] = slots
    rows = np.asarray(_rows(
        pool, jnp.asarray(np.array(engine.cache.tables[at])),
        layers=layers_read(mc.num_hidden_layers),
        row=mc.latent_row).astype(jnp.float32))
    picks = engine.last_row_readings().get("moe_picks")
    if picks is not None:
        picks = np.asarray(picks)
        picks = picks[picks[:, 0, 0] >= 0]      # a dense layer picks none
        since = [r for r in _fences.rows if r["loop_s"] >= 0.0]
        held = mc.experts_held * (mc.num_hidden_layers -
                                  mc.first_k_dense_replace)
        each = [touched_share([r], held) for r in since
                if r.get("iterations") and "moe_experts_touched" in r]
        harness.say(
            f"serve: the program's counters over {len(since)} fences "
            "since the window opened: moe_held_touched_share "
            f"{touched_share(since, held) or 0.0:.2f} (a fence's least "
            f"{min(each, default=0.0):.2f}) of {held} held experts; "
            "kv_latent_bytes_resident",
            since[-1].get("kv_latent_bytes_resident") if since else None)
    return [{"picks": None if picks is None else picks[:, s],
             "rows": rows[i], "dtype": str(pool.dtype)}
            for i, s in enumerate(slots)]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(the tokens a slot had taken in, `live_state`'s
    reading)]. `router_picks_agree`: of the reference's picks for the
    last of those tokens, every expert layer's, the share the program
    picked too; the least over the slots; held from below.
    `latent_rows_rel`: the widest distance of the pool's rows of those
    tokens in the first of `layers_read` from the reference's
    [c~ ; k_rope], as a share of the layer's largest, over slots;
    `latent_rows_mean_rel`: the same distance in the last of them, the
    mean over a slot's rows, the largest over slots.
    `latent_dtype_differs`: 1
    where the pool is held in another type than the configuration
    states. Under a reference control the reference in the lower
    precision stands in the program's place."""
    live = [(seq, got) for seq, got in live if got["picks"] is not None]
    if not live:
        return []
    layers = layers_read(sizes["num_hidden_layers"])

    def read_from(cast):
        f = jax.jit(lambda flat, ids, row: reference.picks_and_latent_rows(
            flat, ids, row, layers, sizes, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            picks, rows = f(flat, jnp.asarray(ids),
                            jnp.asarray(len(seq) - 1, jnp.int32))
            return np.asarray(picks), np.asarray(rows)[:, :len(seq)]
        return of

    want_of = read_from(None)
    lower = None if control_cast is None else read_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    agree, far, mean = [], [], []
    for seq, got in live:
        want_picks, want_rows = want_of(seq)
        held_picks, held_rows = (got["picks"], got["rows"][:, :len(seq)]) \
            if lower is None else lower(seq)
        agree.append(float(np.mean([
            len(set(w) & set(h)) / len(w)
            for w, h in zip(want_picks, held_picks)])))
        off = np.abs(held_rows - want_rows).max(2) / \
            np.abs(want_rows).max((1, 2))[:, None]      # [layers, T]
        far.append(float(off[0].max()))
        mean.append(float(off[-1].mean()))
        harness.say("reference: a slot's latent rows by layer, widest and "
                    "mean over its rows:", " ".join(
                        f"{m:.5f}/{a:.5f}" for m, a in zip(off.max(1),
                                                           off.mean(1))))
    configured = str(jnp.dtype(sizes["program"]["param_dtype"]))
    differs = float(any(got["dtype"] != configured for _, got in live))
    harness.say("reference: the picks of", len(live), "live slots' last "
                "rows in every expert layer; the program picked",
                " ".join(f"{x:.4f}" for x in agree), "of them; their "
                f"latent rows of layer {layers[0]} off by at most",
                " ".join(f"{x:.5f}" for x in far), "and of layer "
                f"{layers[-1]} by a mean of",
                " ".join(f"{x:.5f}" for x in mean), "; held as",
                live[0][1]["dtype"])
    return [{"name": "router_picks_agree", "value": min(agree),
             "limit": limits["router_picks_agree"],
             "ok": min(agree) >= limits["router_picks_agree"]},
            {"name": "latent_rows_rel", "value": max(far),
             "limit": limits["latent_rows_rel"],
             "ok": max(far) <= limits["latent_rows_rel"]},
            {"name": "latent_rows_mean_rel", "value": max(mean),
             "limit": limits["latent_rows_mean_rel"],
             "ok": max(mean) <= limits["latent_rows_mean_rel"]},
            {"name": "latent_dtype_differs", "value": differs, "limit": 0.0,
             "ok": differs == 0.0}]
