"""Architecture "phi4flash" (a configuration's `program.architecture`;
Phi-4-mini-flash-reasoning) for the kinds that build the program from
that name (`kinds/serve_open_arch.py`): the model config, the seeded
weights (`weights_phi4flash.py`) laid out both ways, and the plain
reference (`reference/phi4flash.py`).

Two comparisons beside the kind's three of the logits (`live_state` +
`state_checks`), both of what the window's own programs left and the
logits cannot see:

  * `scan_state_rel`: layer 0's Mamba-1 state of the live slots,
    element for element, against the reference's direct sum over the
    slot's tokens (`reference.scan_state`: no recurrence), the widest
    distance as a share of the largest value at that state index. A
    read-out sums over a channel's 16 values and 5,120 channels, and a
    state kept in bfloat16 averages out of the logits (PR 26 found
    that for Brumby; `state_dtype_differs`, limit 0, says it outright);
  * `shared_rows_rel`: the shared pool's rows (the K and V that layer
    L/2 + 1 left for the cross-decoder: 17 layers of prefill in chunks
    and of decode wrote them, one row a token) read through the live
    slots' page tables, every position, against the reference's K and
    V of the same tokens: a row's distance its widest value's as a
    share of the largest of K (or V), the mean over a slot's rows and
    over K and V, the largest over slots. A row written to another
    slot's page, a chunk whose rows were skipped, or a pool held in a
    lower precision shows here before it shows in a logit.

The module keeps the serving loop's `decode_batch` events for this
PR's program-counter metrics, as `sarvam_mla.py` does and for its
reason: `fence_rows()` gives them to the readers under
`benchmark/metrics/`.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, weights_phi4flash
from benchmark.architectures.afmoe import FenceRows
from benchmark.reference import phi4flash as reference

NAME = "phi4flash"
STATE_KEY, POOL_KEYS = "scan_state", ("k_shared", "v_shared")

_fences = FenceRows()        # of the run in this process, for the readers


def fence_rows(ctx):
    """The `decode_batch` rows of the run in this process, the
    pre-roll's too (`loop_s` reads 0 when the window opens); [] for a
    cell of another architecture."""
    program = ctx.get("cell", {}).get("sizes", {}).get("program", {})
    if program.get("architecture") != NAME:
        return []
    return list(_fences.rows)


def build(sizes, seed, overrides=None):
    """(model config, flat weights, the program's tree of the very
    same arrays, reference module). `overrides` lays `model` keys of a
    control over the model config."""
    try:
        from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
    except ImportError as e:         # a program from before the model
        raise harness.Refused(
            f"the program cannot run architecture {NAME!r}: {e}")
    dtype = jnp.dtype(sizes["program"]["param_dtype"])
    names = {f.name for f in dataclasses.fields(Phi4FlashConfig)}
    assumed = sizes["assumed"]
    settings = {k: v for k, v in {**sizes, **assumed}.items()
                if k in names and not isinstance(v, (str, dict, list))}
    settings.update(state_dtype=jnp.dtype(assumed["state_dtype"]),
                    dtype=dtype, param_dtype=dtype)
    for k, v in (overrides or {}).items():
        settings[k] = jnp.dtype(v) if k.endswith("dtype") else v
    global _fences
    _fences = FenceRows()            # a run's own rows
    flat = weights_phi4flash.make_weights(sizes, seed, dtype)
    return (Phi4FlashConfig(**settings), flat,
            weights_phi4flash.to_program_tree(flat), reference)


@functools.partial(jax.jit, static_argnames=("row",))
def _rows(pool, tables, row):
    """[slots, max_pages * page, row] of the one-layer pool, each
    slot's pages in its table's order."""
    got = pool[0][tables][..., :row]
    return got.reshape(got.shape[0], -1, row)


def live_state(engine, slots, width):
    """For each of `slots` (at most `width`: one compiled shape, so
    the kind's call before the window opens leaves nothing to compile
    at its close, and that call is where this module attaches its
    sink): layer 0's scan state as it lies ([N, Di]) and the shared
    pool's K and V rows through the slot's page table.
    [{"S": [N, Di] float32, "dtype": the state's, "k", "v":
    [max_pages * page, Hk d] float32, "pool_dtype"}]."""
    if _fences not in engine.monitor.sinks:
        engine.monitor.attach_sink(_fences)
    mc = engine.model_config
    arrays = dict(zip(engine.serving.cache_keys, engine.cache_arrays()))
    at = np.zeros((width,), np.int32)
    at[:len(slots)] = slots
    S = arrays[STATE_KEY]
    held = np.asarray(S[0][jnp.asarray(at)].astype(jnp.float32))
    tables = jnp.asarray(np.array(engine.cache.shared.tables[at]))
    row = mc.n_kv_head * mc.head_dim
    k, v = (np.asarray(_rows(arrays[key], tables, row=row))
            for key in POOL_KEYS)
    return [{"S": held[i], "dtype": str(S.dtype),
             "k": k[i].astype(np.float32), "v": v[i].astype(np.float32),
             "pool_dtype": str(arrays[POOL_KEYS[0]].dtype)}
            for i in range(len(slots))]


def state_checks(flat, sizes, limits, live, max_seq, control_cast=None):
    """`live`: [(the tokens a slot had taken in, `live_state`'s
    reading)]; the module's docstring has the three checks. Under a
    reference control the reference in the lower precision stands in
    the program's place."""
    if not live:
        return []

    def read_from(cast):
        state = jax.jit(lambda flat, ids, n: reference.scan_state(
            flat, ids, n, sizes, cast))
        rows = jax.jit(lambda flat, ids: reference.shared_rows(
            flat, ids, sizes, cast))

        def of(seq):
            ids = np.zeros((max_seq,), np.int32)
            ids[:len(seq)] = seq
            ids = jnp.asarray(ids)
            k, v = rows(flat, ids)
            return (np.asarray(state(flat, ids, jnp.asarray(
                len(seq), jnp.int32))).T, np.asarray(k)[:len(seq)],
                np.asarray(v)[:len(seq)])
        return of

    want_of = read_from(None)
    lower = None if control_cast is None else read_from(
        reference.rounded_to(jnp.dtype(control_cast)))
    far, rows_far = [], []
    for seq, got in live:
        want_S, want_k, want_v = want_of(seq)
        held_S, held_k, held_v = (
            got["S"], got["k"][:len(seq)], got["v"][:len(seq)]) \
            if lower is None else lower(seq)
        far.append(float((np.abs(held_S - want_S).max(1) /
                          np.abs(want_S).max(1)).max()))
        off = [np.abs(held - want).max(1) / np.abs(want).max()
               for held, want in ((held_k, want_k), (held_v, want_v))]
        rows_far.append(float(np.mean(off)))
        harness.say("reference: a slot's shared rows, K and V, widest and "
                    "mean over its", len(seq), "rows:", " ".join(
                        f"{o.max():.5f}/{o.mean():.5f}" for o in off))
    assumed = sizes["assumed"]
    differs = float(any(
        got["dtype"] != str(jnp.dtype(assumed["state_dtype"])) or
        got["pool_dtype"] != str(jnp.dtype(sizes["program"]["param_dtype"]))
        for _, got in live))
    harness.say("reference: layer 0's scan state of", len(live),
                "live slots, element for element; off by",
                " ".join(f"{x:.5f}" for x in far), "; the shared pool's "
                "rows off by a mean of",
                " ".join(f"{x:.5f}" for x in rows_far), "; held as",
                live[0][1]["dtype"], "and", live[0][1]["pool_dtype"])
    return [{"name": "scan_state_rel", "value": max(far),
             "limit": limits["scan_state_rel"],
             "ok": max(far) <= limits["scan_state_rel"]},
            {"name": "shared_rows_rel", "value": max(rows_far),
             "limit": limits["shared_rows_rel"],
             "ok": max(rows_far) <= limits["shared_rows_rel"]},
            {"name": "state_dtype_differs", "value": differs, "limit": 0.0,
             "ok": differs == 0.0}]
