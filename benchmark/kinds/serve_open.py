"""Traffic of kind "serve_open": `InferenceEngine` + `ServingLoop`
under an open loop. Arrival times are made before the window and
handed to the loop as `arrival_time`; every latency counts from them.

The loop's clock reads 0 when the window opens. The pre-roll before it
is set-up: the window opens on an engine whose slots are occupied as in
steady state. After the window the loop runs on, untimed, until every
request due in the window has its first token (or `drain_s` passed).
A traced run is an untraced one with a tail: the schedule goes on
after the window, and once the drain is over the profiler takes
`TRACE_ITERATIONS` iterations there. Starting and stopping it stalls
the loop for about a second, so it stays out of the window, and what
the client and the scheduler saw is read from the window in both.
`correct` then takes a seeded sample of the finished requests, the
longest among them, and reads in the plain reference's logits how far
each served token lies below the reference's best.
"""

import gc
import time

import numpy as np

from benchmark import harness, traffic, weights
from benchmark.harness import say
from benchmark.kinds.train import model_config

TRACE_ITERATIONS = 12


class Recorder:
    """What the client sees, noted at each fence from the benchmark's
    own wrapper round `engine.fetch_state`: per request the running
    count of tokens delivered, and how many slots were in use."""

    def __init__(self, loop):
        self.loop = loop
        self.deliveries = {}         # rid -> [(t, tokens so far)]
        self.fences = []             # (t, slots in use)
        self.calls = []              # (t, span, host seconds inside)

    def on_fence(self, snap):
        t = self.loop._now()
        self.fences.append((t, len(self.loop.live) +
                            len(self.loop.prefilling)))
        for slot, req in self.loop.live.items():
            self.deliveries.setdefault(req.rid, []).append(
                (t, int(snap["n_gen"][slot])))


WRAPPED = ("prefill_chunk", "activate_slot", "decode_block", "fetch_state")


def unannotate(engine):
    for name in WRAPPED:
        vars(engine).pop(name, None)


def annotate(engine, recorder):
    """Host spans from the benchmark's side of each call into the
    program (instance attributes over the engine's methods)."""
    import jax
    unannotate(engine)

    def wrap(name, span, after=None):
        inner = getattr(engine, name)

        def call(*a, **k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                out = inner(*a, **k)
            recorder.calls.append((recorder.loop._now(), span,
                                   time.perf_counter() - t0))
            if after is not None:
                after(out)
            return out
        setattr(engine, name, call)

    wrap("prefill_chunk", "bench/prefill")
    wrap("activate_slot", "bench/activate")
    wrap("decode_block", "bench/step")
    wrap("fetch_state", "bench/fence", after=recorder.on_fence)


def token_gaps(deliveries, t0, t1):
    """Gaps between the tokens delivered in [t0, t1), as the client
    sees them: tokens of one fence arrive together (gap 0 after the
    first of them), the first of a later fence after the time since
    the fence before. Returns (sum of gaps in s, count of gaps, tokens
    delivered, [(gap per token in s, tokens)] per delivery)."""
    total = gaps = delivered = 0
    per_delivery = []
    for series in deliveries.values():
        prev_n, prev_t = 0, None
        for t, n in series:
            new = n - prev_n
            if new <= 0:
                continue
            if t0 <= t < t1:
                delivered += new
                if prev_t is None:
                    gaps += new - 1
                else:
                    total += t - prev_t
                    gaps += new
                    per_delivery.append(((t - prev_t) / new, new))
            prev_n, prev_t = n, t
    return total, gaps, delivered, per_delivery


def weighted_percentile(pairs, q):
    if not pairs:
        return None
    pairs = sorted(pairs)
    cut = q / 100.0 * sum(w for _, w in pairs)
    acc = 0
    for v, w in pairs:
        acc += w
        if acc >= cut:
            return v
    return pairs[-1][0]


def build_engine(cell, seed, control=None):
    """(engine, flat weights, model config). The engine serves the very
    arrays the reference later reads. A control that names a
    lower-precision path of the program's own (`inference` keys laid
    over the mix's) switches it on here."""
    import jax
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM
    sizes, mix = cell["sizes"], cell["mix"]
    block = harness.merged(mix["inference"],
                           (control or {}).get("inference"))
    cfg = model_config(sizes, block["max_seq_len"])
    model = GPT2ForCausalLM(cfg)
    example = {"input_ids": np.zeros((1, block["max_seq_len"]), np.int32)}
    template = jax.eval_shape(lambda k: model.init(k, example),
                              jax.random.PRNGKey(0))
    flat = weights.make_weights(sizes, seed, cfg.param_dtype)
    engine = InferenceEngine(cfg, weights.to_program_tree(flat, template),
                             {"inference": block})
    return engine, flat, cfg


def drive(engine, requests, seconds, drain_s, name=None, trace=False,
          on_open=None, on_close=None):
    """Pre-roll, window, drain and (traced runs) the traced tail of one
    run on a fresh loop. Returns what was seen; the loop's clock reads
    0 at the window's start."""
    from deepspeed_tpu.inference import Request, ServingLoop
    loop = ServingLoop(engine)
    recorder = Recorder(loop)
    annotate(engine, recorder)
    reqs = [Request(rid=r["rid"], tokens=r["tokens"],
                    max_new_tokens=r["max_new_tokens"],
                    arrival_time=r["arrival_s"]) for r in requests]
    for r in reqs:
        loop.submit(r)
    due = [r for r in reqs if 0.0 <= r.arrival_time < seconds]
    preroll = -min([r.arrival_time for r in reqs] + [0.0])
    # the loop has no public way to be stepped against a clock of the
    # caller's; `serve_sequential` in the same module sets the same two
    loop._t0 = time.monotonic() + preroll
    loop._last_fence_t = loop._now()
    opened_wall = time.time() + preroll

    def step():
        if loop.step():
            return True
        time.sleep(0.0005)
        return False

    while True:
        now = loop._now()
        if on_open is not None and now >= 0.0:
            on_open()
            on_open = None
        if on_close is not None and now >= seconds and loop.live:
            on_close(loop)
            on_close = None
        if now >= seconds and (now >= seconds + drain_s or all(
                r.first_token_at is not None for r in due)):
            break
        step()
    traced = None
    if trace:
        with harness.TracedWindow(name) as traced:
            iterations = 0
            while iterations < TRACE_ITERATIONS and \
                    (loop.live or loop.prefilling or loop.queue):
                iterations += step()
    unannotate(engine)
    return {"loop": loop, "recorder": recorder, "due": due, "all": reqs,
            "opened_wall": opened_wall,
            "trace": traced.trace if traced is not None else None}


def summarise(seen, seconds):
    rec, due = seen["recorder"], seen["due"]
    served = [r for r in due if r.first_token_at is not None]
    ttft = [r.first_token_at - r.arrival_time for r in served]
    wait = [r.admitted_at - r.arrival_time for r in due
            if r.admitted_at is not None]
    gap_sum, gaps, delivered, per_delivery = token_gaps(
        rec.deliveries, 0.0, seconds)
    slots = [n for t, n in rec.fences if 0.0 <= t < seconds]
    between = np.diff([t for t, _ in rec.fences if 0.0 <= t < seconds])
    host = {}
    for t, span, took in rec.calls:
        if 0.0 <= t < seconds:
            host[span] = host.get(span, 0.0) + took
    queued = lambda at: sum(
        1 for r in seen["all"] if r.arrival_time <= at and
        (r.admitted_at is None or r.admitted_at > at))
    return {
        "attempted": len(due), "failed": len(due) - len(served),
        "ttft_s": ttft, "queue_wait_s": wait,
        "itl_mean_ms": 1e3 * gap_sum / max(gaps, 1),
        "itl_p95_ms": None if not per_delivery else
        1e3 * weighted_percentile(per_delivery, 95),
        "tokens_delivered": delivered,
        "serve_tokens_per_s": delivered / seconds,
        "ttft_mean_ms": 1e3 * float(np.mean(ttft)) if ttft else float("nan"),
        "slots_occupied_mean": float(np.mean(slots)) if slots else None,
        "fences_in_window": len(slots), "host_seconds_by_span": host,
        "longest_fence_gaps_s": sorted(between.tolist(), reverse=True)[:3],
        "queued_at_open": queued(0.0), "queued_at_close": queued(seconds),
    }


def run(cell, seed, seconds, trace, control, t_start, compiles,
        check_only=False):
    sizes, mix = cell["sizes"], cell["mix"]
    engine, flat, cfg = build_engine(cell, seed, control)
    block = engine.config
    say(f"serve: {sizes['program']['preset']}, {block.max_slots} slots, "
        f"prefill chunk {block.prefill_chunk}, sync_every "
        f"{block.sync_every}, rate {mix['arrivals']['rate_per_s']}/s, "
        f"pre-roll {mix['arrivals']['preroll_s']} s")
    # an iteration is under a second: the traced tail ends before the
    # schedule that feeds it does
    tail_s = float(mix["drain_s"]) + TRACE_ITERATIONS if trace else 0.0
    requests = traffic.serve_requests(mix, sizes["vocab_size"], seconds, seed,
                                      tail_s)
    at_open, still_live = {}, []
    # the pre-roll is set-up: what it compiles counts there. When the
    # window has closed, one more launch of its decode program on the
    # slots then live gives the logits that `correct` compares.
    seen = drive(engine, requests, seconds, float(mix["drain_s"]),
                 name=cell["name"], trace=trace,
                 on_open=lambda: at_open.update(compiles.snapshot()),
                 on_close=lambda loop: still_live.extend(
                     next_logits_of_live_slots(engine, loop)))
    setup_s = seen["opened_wall"] - t_start
    setup_compiles = dict(at_open)
    window_compiles = {k: compiles.counts[k] - at_open[k]
                       for k in compiles.counts}
    s = summarise(seen, seconds)
    # arrival times were handed over before the window opened, so no
    # generator ran during it and none could run late
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
    sample = pick_sample(finished, int(mix["check"]["requests"]), seed)
    sample = [(np.asarray(r.tokens), np.asarray(r.out_tokens))
              for r in sample]
    max_seq = engine.max_seq_len
    del engine, seen["loop"], seen["recorder"], finished
    gc.collect()
    checks = compare_with_reference(
        flat, sizes, mix["check"], sample, still_live, max_seq,
        control_cast=(control or {}).get("reference_cast"))
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
                    window_compiles=window_compiles),
    }


def next_logits_of_live_slots(engine, loop, most=8):
    """One more launch of the window's decode program on the state the
    window ended in, every live slot in it: [(prompt with the tokens
    served so far, the float32 logits of the token after them)]. The
    token it decodes is delivered at the next fence like any other."""
    snap = engine.fetch_state()
    for slot in loop.live:
        engine.ensure_decode_capacity(slot, int(snap["pos"][slot]), 1)
    engine.push_tables()
    t0 = time.perf_counter()
    logits = np.asarray(engine.decode_once(), np.float32)
    # the device was idle after the fence above: one launch, alone
    say(f"serve: one decode launch alone with {len(loop.live)} slots live: "
        f"{1e3 * (time.perf_counter() - t0):.1f} ms to its logits")
    out = []
    for slot, req in sorted(loop.live.items())[:most]:
        if snap["active"][slot]:
            so_far = snap["out_tokens"][slot][:int(snap["n_gen"][slot])]
            out.append((np.concatenate([req.tokens, so_far]).astype(
                np.int32), logits[slot]))
    return out


def pick_sample(finished, n, seed):
    """The longest finished request and n-1 others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -(len(r.tokens) +
                                             len(r.out_tokens)))
    rest = order[1:]
    rng = traffic.rng_for(seed, 31)
    picks = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [order[0]] + [rest[i] for i in picks]


def compare_with_reference(flat, sizes, check, sample, still_live, max_seq,
                           control_cast=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample; and, for
    the slots still live when the run ended, how far the decode
    program's logits lie from the reference's, as a share of the
    reference's largest."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as ref
    t0 = time.perf_counter()
    limits = check["limits"]

    @jax.jit
    def gaps(flat, ids):
        lg = ref.logits(flat, ids, sizes["n_head"])
        nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        took = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
        return lg.max(-1) - took

    @jax.jit
    def logits_at(flat, ids, last):
        return ref.logits(flat, ids, sizes["n_head"])[0, last]

    def padded(seq):
        ids = np.zeros((1, max_seq), np.int32)
        ids[0, :len(seq)] = seq[:max_seq]
        return jnp.asarray(ids)

    if control_cast is not None:
        cast = ref.rounded_to(jnp.dtype(control_cast))
        lower = jax.jit(lambda flat, ids: ref.logits(
            flat, ids, sizes["n_head"], cast)[0])
        sample = [(p, np.asarray(lower(flat, padded(np.concatenate(
            [p, o]))).argmax(-1))[len(p) - 1:len(p) + len(o) - 1])
            for p, o in sample]
        still_live = [(seq, np.asarray(lower(flat, padded(seq))[
            len(seq) - 1])) for seq, _ in still_live]

    worst, tokens, total = 0.0, 0, 0.0
    for prompt, out in sample:
        seq = np.concatenate([prompt, out])[:max_seq]
        g = np.asarray(gaps(flat, padded(seq)))[0]
        served = g[len(prompt) - 1:len(seq) - 1]
        worst = max(worst, float(served.max()))
        total += float(served.sum())
        tokens += len(served)
    far = []
    for seq, got in still_live:
        want = np.asarray(logits_at(flat, padded(seq), len(seq) - 1))
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
