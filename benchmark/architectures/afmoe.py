"""Architecture "afmoe" (a configuration's `program.architecture`;
Arcee Trinity) for the kinds that build the program from that name
(`kinds/serve_open_arch.py`): the model config, the seeded weights
(`weights_afmoe.py`) laid out both ways, and the plain reference
(`reference/afmoe.py`).

A pick that flips on bfloat16 rounding moves a logit by a whole
expert's share, and the logits alone cannot say whether that is what
moved them. So the module also brings a comparison of the picks
themselves (`live_state` + `state_checks`): when the window closes,
for the slots then live, the experts that the window's own decode
program picked in every expert layer for the row whose logits are
compared (the block's `ROW_READINGS`, which that program gives out
beside its logits: `engine.last_row_readings()` after the kind's
`decode_once`), against the reference's picks for the same row:
`router_picks_agree`, the share of the reference's picks that the
program picked too, over slots and layers (sound at 1 minus the near
ties; it must not fall under its limit).

The module also keeps the serving loop's `decode_batch` events (one a
fence, with the program's counters) for this PR's program-counter
metrics: a sink of its own on the engine's monitor
(`Monitor.attach_sink`), attached when the kind first shows it the
engine, before the window. The kind hands its readers no such rows
and a reader cannot reach the engine, so the sink is this module's:
`fence_rows()` gives the rows to the readers under
`benchmark/metrics/` (PERF.md section 7: `ctx["fence_rows"]` from
`serve_open.drive` would do without it).
"""

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_afmoe
from benchmark.reference import afmoe as reference


class FenceRows:
    """A monitor sink that keeps the serving loop's fence rows."""
    name = "benchmark_fence_rows"

    def __init__(self):
        self.rows = []

    def emit(self, event):
        if event["kind"] == "decode_batch":
            self.rows.append(event)

    def flush(self):
        pass

    close = flush


_fences = FenceRows()        # of the run in this process, for the readers
# the interpreter's collections that took 50 ms or more, (generation,
# seconds), since the kind first showed this module an engine: a wait
# of seconds between two fences is the host's, and this is the part
# of the host that a process can see for itself
_pauses, _gc_began = [], [0.0]


def _on_gc(phase, info):
    if phase == "start":
        _gc_began[0] = time.perf_counter()
    elif time.perf_counter() - _gc_began[0] >= 0.05:
        _pauses.append((info["generation"],
                        time.perf_counter() - _gc_began[0]))


def fence_rows(ctx):
    """The `decode_batch` rows of the run in this process, the
    pre-roll's too (`loop_s` reads 0 when the window opens); [] for a
    cell of another architecture."""
    program = ctx.get("cell", {}).get("sizes", {}).get("program", {})
    if program.get("architecture") != "afmoe":
        return []
    return list(_fences.rows)


def window_rows(ctx):
    """The fence rows of the timed window: the first
    `fences_in_window` (the kind's own count) from clock 0 on."""
    rows = [row for row in fence_rows(ctx) if row["loop_s"] >= 0.0]
    return rows[:int(ctx.get("fences_in_window") or 0)]


def touched_share(rows, held):
    """Of `held` experts (experts x expert layers), the % that a
    decode launch's rows touched, mean over the launches of `rows`."""
    rows = [row for row in rows if "moe_experts_touched" in row]
    launches = sum(row["iterations"] for row in rows)
    if not launches:
        return None
    return 100.0 * sum(row["moe_experts_touched"] for row in rows) / \
        (launches * held)


def window_resident_share(rows):
    """Pages the window layers' pool holds over what whole histories
    would hold, in %, mean over the fences of `rows`."""
    shares = [row["kv_pages_window_in_use"] /
              (row["kv_pages_window_in_use"] +
               row["kv_pages_window_released"])
              for row in rows if row.get("kv_pages_window_in_use")]
    return 100.0 * sum(shares) / len(shares) if shares else None


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module)."""
    try:
        from deepspeed_tpu.models.trinity import TrinityConfig
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture 'afmoe': {e}")
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    settings = {f.name: sizes[f.name]
                for f in dataclasses.fields(TrinityConfig)
                if f.name in sizes and f.name != "layer_types"}
    settings.update(
        layer_types=tuple(sizes["layer_types"][i] for i in sizes.get(
            "kept_layers", range(sizes["num_hidden_layers"]))),
        initializer_range=sizes["assumed"]["initializer_range"],
        dtype=dtype, param_dtype=dtype)
    settings.update(overrides or {})
    global _fences
    _fences = FenceRows()            # a run's own rows
    del _pauses[:]
    flat = weights_afmoe.make_weights(sizes, seed, dtype)
    return (TrinityConfig(**settings), flat,
            weights_afmoe.to_program_tree(flat), reference)


def live_state(engine, slots, width):
    """The experts the window's decode program picked, in every
    expert layer, for the row of each of `slots` in its last launch
    (the kind's `decode_once`, whose logits it compares): [{"picks":
    [expert layers, k]}] a slot; None before any launch (the kind's
    call before the window, where this module attaches its sink)."""
    if _fences not in engine.monitor.sinks:
        engine.monitor.attach_sink(_fences)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    picks = engine.last_row_readings().get("moe_picks")
    if picks is None:
        return [{"picks": None} for _ in slots]
    picks = np.asarray(picks)
    picks = picks[picks[:, 0, 0] >= 0]          # a dense layer picks none
    mc = engine.model_config
    rows = [row for row in _fences.rows if row["loop_s"] >= 0.0]
    held = mc.num_experts * (mc.num_hidden_layers - mc.num_dense_layers)
    each = [touched_share([row], held) for row in rows
            if row.get("iterations") and "moe_experts_touched" in row]
    harness.say(
        f"serve: the program's counters over {len(rows)} fences since the "
        "window opened: moe_experts_touched_share "
        f"{touched_share(rows, held) or 0.0:.2f} (a fence's least "
        f"{min(each, default=0.0):.2f}), kv_window_resident_share "
        f"{window_resident_share(rows) or 0.0:.2f}; collections of the "
        "interpreter that took 50 ms or more (generation:s):",
        " ".join(f"{g}:{s:.2f}" for g, s in _pauses) or "none")
    return [{"picks": picks[:, s]} for s in slots]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(the tokens a slot had taken in, `live_state`'s
    reading)]. `router_picks_agree`: of the reference's picks for the
    last of those tokens, every expert layer's, the share the program
    picked too; the least over the slots. It is held from below: ok
    where it is at least the limit. Under a reference control the
    reference in the lower precision stands in the program's place."""
    live = [(seq, got) for seq, got in live if got["picks"] is not None]
    if not live:
        return []

    def picks_from(cast):
        f = jax.jit(lambda flat, ids, row: reference.router_picks(
            flat, ids, row, sizes, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            return np.asarray(f(flat, jnp.asarray(ids),
                                jnp.asarray(len(seq) - 1, jnp.int32)))
        return of

    want_of = picks_from(None)
    lower = None if control_cast is None else picks_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    agree = []
    for seq, got in live:
        want = want_of(seq)
        held = got["picks"] if lower is None else lower(seq)
        agree.append(float(np.mean([
            len(set(w) & set(h)) / len(w) for w, h in zip(want, held)])))
    harness.say("reference: the picks of", len(live), "live slots' last "
                "rows in every expert layer; the program picked",
                " ".join(f"{x:.4f}" for x in agree), "of them")
    return [{"name": "router_picks_agree", "value": min(agree),
             "limit": limits["router_picks_agree"],
             "ok": min(agree) >= limits["router_picks_agree"]}]
