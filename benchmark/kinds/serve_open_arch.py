"""Traffic of kind "serve_open_arch": the open loop of `serve_open.py`
(its `drive`, `summarise`, `Recorder`, `pick_sample`,
`next_logits_of_live_slots` and `token_gaps`, by import) for a
configuration whose `program.architecture` names the model family. The
engine, the weights and the plain reference are built by the module of
that name under `benchmark/architectures/` (its `build`; a new family
is a new file there); everything about the window, the pre-roll, the
drain and the traced tail is `serve_open`'s, and `ctx["kind"]` stays
"serve_open" so that its readers serve these cells too.

`correct` is the GPT-2 serving cell's: `live_logits_rel` on the
window's own decode program at the slots then live (for a model of
recurrent state these logits come through prefill in chunks and then
hundreds of one-token updates of the state, against the reference's
one full forward with no state at all), and `served_gap_max/mean` on a
sample of the finished requests. The reference takes one sequence at a
time, padded to the engine's `max_seq_len`, and gives logits for the
rows that are compared only: [8192, 151936] float32 would be 5 GB.
An architecture that keeps state between tokens may bring a comparison
of that state too (`live_state` + `state_checks` in its module): the
logits average over a state's rows, and cannot tell a state held in a
lower precision from a sound one.

A control that names `model` keys (`control_program` in the traffic
file) lays them over the program's model config: a lower-precision
path of the program's own, read against the same limits (for Brumby a
bfloat16 state, which the comparison of the state fails and the three
of the logits do not: PERF.md section 2b).
"""

import gc
import importlib
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.harness import say
from benchmark.kinds.serve_open import (TRACE_ITERATIONS, drive,
                                        next_logits_of_live_slots,
                                        pick_sample, summarise)


def architecture(cell):
    """The module under `benchmark/architectures/` that the cell's
    configuration names."""
    name = cell["sizes"]["program"]["architecture"]
    module = "benchmark.architectures." + name
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise harness.Refused(
            f"{cell['name']}: no builder for architecture {name!r}: "
            f"benchmark/architectures/{name}.py is not there")


def build_engine(cell, seed, control=None):
    """(engine, flat weights, reference module). The engine serves the
    very arrays the reference later reads."""
    from deepspeed_tpu.inference import InferenceEngine
    control = control or {}
    block = harness.merged(cell["mix"]["inference"],
                           control.get("inference"))
    cfg, flat, tree, ref = architecture(cell).build(
        cell["sizes"], seed, control.get("model"))
    engine = InferenceEngine(cfg, tree, {"inference": block})
    return engine, flat, ref


def run(cell, seed, seconds, trace, control, t_start, compiles,
        check_only=False):
    sizes, mix = cell["sizes"], cell["mix"]
    engine, flat, ref = build_engine(cell, seed, control)
    block = engine.config
    occupancy = engine.cache.occupancy()
    state_bytes = engine.cache.pool_bytes \
        if engine.cache.kind == "recurrent" else None
    say(f"serve: {sizes['program']['architecture']}, {block.max_slots} "
        f"slots, prefill chunk {block.prefill_chunk}, sync_every "
        f"{block.sync_every}, rate {mix['arrivals']['rate_per_s']}/s, "
        f"pre-roll {mix['arrivals']['preroll_s']} s; cache "
        f"{engine.cache.kind}: {occupancy}"
        + ("" if state_bytes is None else
           f", {state_bytes / 1e9:.3f} GB of state resident"))
    tail_s = float(mix["drain_s"]) + TRACE_ITERATIONS if trace else 0.0
    requests = traffic.serve_requests(mix, sizes["vocab_size"], seconds, seed,
                                      tail_s)
    at_open, still_live, live_states = {}, [], []
    most = int(mix["check"].get("live_slots", 8))
    arch = architecture(cell)
    reads_state = hasattr(arch, "live_state")
    if reads_state:
        arch.live_state(engine, [0], most)       # compiled before the window

    def at_close(loop):
        """One more launch of the window's decode program on the slots
        then live, and (an architecture that keeps state) the state
        those slots are left with: it has taken in the tokens whose
        next logits that launch gave."""
        snap = engine.fetch_state()
        slots = [slot for slot, _ in sorted(loop.live.items())[:most]
                 if snap["active"][slot]]
        still_live.extend(next_logits_of_live_slots(engine, loop, most=most))
        if reads_state and slots:
            live_states.extend(arch.live_state(engine, slots, most))

    seen = drive(engine, requests, seconds, float(mix["drain_s"]),
                 name=cell["name"], trace=trace,
                 on_open=lambda: at_open.update(compiles.snapshot()),
                 on_close=at_close)
    setup_s = seen["opened_wall"] - t_start
    setup_compiles = dict(at_open)
    window_compiles = {k: compiles.counts[k] - at_open[k]
                       for k in compiles.counts}
    s = summarise(seen, seconds)
    say("serve: the generator ran 0.0 ms late (arrivals pre-submitted); "
        f"queued at open {s['queued_at_open']}, at close "
        f"{s['queued_at_close']}; {s['fences_in_window']} fences, "
        f"{s['tokens_delivered']} tokens delivered in the window; "
        f"slots in use {s['slots_occupied_mean']:.2f}; queue wait "
        f"{1e3 * float(np.mean(s['queue_wait_s'] or [0])):.0f} ms; ttft "
        "p50/p90/max " + "/".join(f"{1e3 * x:.0f}" for x in np.percentile(
            s["ttft_s"] or [0], [50, 90, 100])) + " ms")
    say("serve: host seconds inside the benchmark's spans, of the window's "
        f"{seconds:g}:", ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(
                s["host_seconds_by_span"].items())),
        "; longest waits from fence to fence",
        " ".join(f"{x:.2f}" for x in s["longest_fence_gaps_s"]), "s")
    peak = harness.memory_peak_bytes()

    finished = list(seen["loop"].results)
    short = [r for r in finished if len(r.out_tokens) != r.max_new_tokens]
    sample = [(np.asarray(r.tokens), np.asarray(r.out_tokens))
              for r in pick_sample(finished, int(mix["check"]["requests"]),
                                   seed)]
    max_seq, rows = engine.max_seq_len, block.max_new_tokens
    del engine, seen["loop"], seen["recorder"], finished
    gc.collect()
    cast = (control or {}).get("reference_cast")
    checks = compare_with_reference(
        ref, flat, sizes, mix["check"], sample, still_live, max_seq, rows,
        control_cast=cast)
    if reads_state:
        checks += arch.state_checks(
            flat, sizes, mix["check"]["limits"],
            [(seq, got) for (seq, _), got in zip(still_live, live_states)],
            max_seq, control_cast=cast)
    if not sample:
        checks.append({"name": "finished_requests_compared", "value": 0.0,
                       "limit": 1.0, "ok": False})
    checks.append({"name": "requests_short_of_max_new_tokens",
                   "value": float(len(short)), "limit": 0.0,
                   "ok": not short})
    return {
        "checks": checks, "attempted": s["attempted"],
        "failed": s["failed"] + len(short), "memory_peak_bytes": peak,
        "trace": seen["trace"],
        "end_to_end": {"itl_mean_ms": s["itl_mean_ms"],
                       "serve_tokens_per_s": s["serve_tokens_per_s"],
                       "setup_s": setup_s},
        "ctx": dict(s, kind="serve_open", chips=cell["chips"],
                    memory_peak_bytes=peak, setup_compiles=setup_compiles,
                    window_compiles=window_compiles,
                    state_resident_bytes=state_bytes),
    }


def compare_with_reference(ref, flat, sizes, check, sample, still_live,
                           max_seq, rows, control_cast=None):
    """As `serve_open.compare_with_reference`: the widest gap by which
    a served token's logit lies below the reference's best, over every
    served token of the sample; and, for the slots still live when the
    run ended, how far the decode program's logits lie from the
    reference's, as a share of the reference's largest. `rows` is the
    most tokens a request is served (the window of rows whose logits
    are formed at once)."""
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    limits = check["limits"]
    rows = min(rows, max_seq)

    def padded(seq):
        ids = np.zeros((max_seq,), np.int32)
        ids[:len(seq)] = seq[:max_seq]
        return jnp.asarray(ids)

    def rows_from(cast):
        """(flat, ids, first) -> [rows, V] logits of rows first.."""
        @jax.jit
        def f(flat, ids, first):
            x = ref.hidden(flat, ids, sizes, cast)
            x = jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)
            return ref.logits_of(flat, x, sizes)
        return lambda seq, first: f(flat, padded(seq),
                                    jnp.asarray(first, jnp.int32))

    logits_from = rows_from(None)

    @jax.jit
    def gaps(lg, nxt):
        took = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        return lg.max(-1) - took

    def window_of(prompt, n_out):
        """First row of a window of `rows` rows that holds the rows
        len(prompt) - 1 .. len(prompt) + n_out - 2, and their offset."""
        first = max(min(len(prompt) - 1, max_seq - rows), 0)
        return first, len(prompt) - 1 - first

    def last_row(fn, seq):
        """The logits after the last token of `seq`."""
        first, off = window_of(seq, 1)
        return np.asarray(fn(seq, first)[off])

    if control_cast is not None:
        lower = rows_from(ref.rounded_to(jnp.dtype(control_cast)))
        swapped = []
        for p, o in sample:
            first, off = window_of(p, len(o))
            lg = lower(np.concatenate([p, o]), first)
            swapped.append((p, np.asarray(lg.argmax(-1))[off:off + len(o)]))
        sample = swapped
        still_live = [(seq, last_row(lower, seq)) for seq, _ in still_live]

    worst, tokens, total = 0.0, 0, 0.0
    for prompt, out in sample:
        seq = np.concatenate([prompt, out])[:max_seq]
        first, off = window_of(prompt, len(out))
        nxt = np.zeros((rows,), np.int32)
        served = len(seq) - len(prompt)
        nxt[off:off + served] = seq[len(prompt):]
        g = np.asarray(gaps(logits_from(seq, first),
                            jnp.asarray(nxt)))[off:off + served]
        worst = max(worst, float(g.max()))
        total += float(g.sum())
        tokens += served
    far = []
    for seq, got in still_live:
        want = last_row(logits_from, seq)
        far.append(float(np.abs(got - want).max() / np.abs(want).max()))
    say(f"reference: {len(sample)} requests, {tokens} served tokens and "
        f"{len(far)} live slots' logits read in "
        f"{time.perf_counter() - t0:.1f} s; mean gap "
        f"{total / max(tokens, 1):.5f}; logits off by",
        " ".join(f"{x:.4f}" for x in far))
    live = [] if not far else [
        {"name": "live_logits_rel", "value": max(far),
         "limit": limits["live_logits_rel"],
         "ok": max(far) <= limits["live_logits_rel"]}]
    if not sample:
        return live
    return live + [
        {"name": "served_gap_max", "value": worst,
         "limit": limits["served_gap_max"],
         "ok": worst <= limits["served_gap_max"]},
        {"name": "served_gap_mean", "value": total / max(tokens, 1),
         "limit": limits["served_gap_mean"],
         "ok": total / max(tokens, 1) <= limits["served_gap_mean"]},
    ]
