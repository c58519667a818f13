"""Continuous batching over the sync-free dispatch loop (Orca-style
iteration-level scheduling on the PR-2 fence convention).

The unit of scheduling is one **serving iteration**:

  1. admission — queued requests whose arrival time has passed take
     free decode slots, IF the cache can cover their worst case (pages
     for a paged model: admitted requests never fail a page allocation
     mid-flight; a slot's fixed block for one of recurrent state);
  2. chunked prefill — every admitted-but-not-yet-live slot advances
     by ONE prompt chunk, so a long prompt shares the loop with the
     decode batch instead of stalling it; a slot whose prompt is fully
     cached flips live;
  3. decode block — `sync_every` single-token decode iterations for
     the whole slot batch, dispatched with zero host syncs;
  4. the fence — ONE `device_get` (engine.fetch_state) reads every
     slot's progress as the block BEFORE the one just dispatched left
     it; finished requests (EOS / max-tokens, decided device-side) are
     evicted, their pages freed, their results and latency stats
     recorded, and `request_finished` / `decode_batch` monitor events
     emitted.

The loop keeps the next block in flight: block k is in the device's
queue before block k-1's snapshot is fetched, so the device works
through the readback, the bookkeeping and the next step's admission
and dispatch. Every step fences once: the first after idle dispatches
two blocks and fences the first; a step with nothing live dispatches
nothing and fences what is unfetched (or, a step that only prefilled,
the live state); `run` ends with nothing unfetched. What the host
knows is therefore one block old. The engine makes that safe (pages
for the launches in flight, a reused slot read as activated and not as
its last request left it: `InferenceEngine.ensure_decode_capacity`,
`fetch_state`); the price is that admission reacts a block later, so
a new request's prefill chunks queue behind the block in flight. The
speculative loop fences the block it just dispatched: its fence trims
the page tables and sets the next block's draft depth.

Requests a slot never waits on each other: a request admitted at
iteration k starts decoding at iteration k+ceil(prompt/chunk) while
earlier requests keep decoding — that interleaving is the throughput
win over request-at-a-time serving (`serve_sequential`, below).

The loop times its own iteration: every host phase of `step` is one
span of `monitor/trace.py::SERVE_PHASES` on the engine's `StepTrace`
(the program's clock and the profiler's), stamped with the loop's id
and the iteration's number; the `decode_batch` fence row says where
the host's milliseconds since the last fence went (`host_ms`,
`host_iter_ms`, `host_longest`).
"""

import dataclasses
import time
from collections import deque
from typing import Any, Optional

import numpy as np

from deepspeed_tpu.monitor.trace import SERVE_PREFIX, new_loop_id


@dataclasses.dataclass
class Request:
    """One generation request. `tokens` is the int32 prompt;
    `arrival_time` is seconds after the loop's clock zero (0 = already
    waiting). Result fields are filled by the loop."""
    rid: Any
    tokens: np.ndarray
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    eos_token_id: Optional[int] = None
    arrival_time: float = 0.0
    # -- results ----------------------------------------------------
    out_tokens: Optional[np.ndarray] = None
    finish_reason: Optional[str] = None
    admitted_at: Optional[float] = None
    live_at: Optional[float] = None     # prompt fully cached, decoding
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None


class ServingLoop:
    """Drives one InferenceEngine; owns the request queue, the slot
    table, and the serving fence."""

    def __init__(self, engine):
        self._infer = engine
        self.queue = deque()
        self.live = {}        # slot -> Request (decoding)
        self.prefilling = {}  # slot -> [Request, next_prefill_pos]
        self.results = []
        self.token_latencies = []   # seconds per generated token
        self._t0 = None
        self._last_fence_t = None
        # what the spans of this loop's iterations share, the number
        # of the iteration under way, and whether the `idle` stretch
        # (ONE span from the first poll that finds nothing to do to
        # the next that does) is open
        self._id = new_loop_id()
        self._iteration = 0
        self._idle = False
        self._last_n_gen = np.zeros(
            (engine.config.max_slots,), np.int64)
        # host mirror of each live slot's position as of the last
        # fence (the engine counts the launches dispatched since that
        # snapshot was taken into the per-block capacity ensure)
        self._last_pos = np.zeros((engine.config.max_slots,), np.int64)
        # the decode blocks dispatched and not yet accounted, oldest
        # first: (host dispatch stamp, launches), the serving tracker's
        # per-fence decode window
        self._dispatched = deque()
        # the engine's prefill launches, the prompt tokens they covered
        # and the keys they walked as of the last fence's snapshot
        # (`engine._prefilled`, cumulative like the programs' counts
        # below: the fence rows take the difference)
        self._last_prefilled = 0
        # what the programs count (`engine.fetch_state`'s "counts":
        # the decode launches that drew a sample, the model's block's
        # counters): cumulative on the device, so the fence diffs them
        # against this mirror like the speculative counters below
        self._last_counts = {}
        # speculative-decoding fence mirrors: the device counters are
        # cumulative per slot (never reset mid-flight), so the fence
        # diffs them against these to get per-window numbers
        self._spec = bool(getattr(engine, "speculative_enabled", False))
        # the blocks in the device's queue behind the one a step
        # fences: one, but none where the fence decides the next
        # block's pages (it trims every live slot's table to what was
        # committed and sets the draft depth)
        self._keep_in_flight = 0 if self._spec else 1
        s = engine.config.max_slots
        self._last_drafted = np.zeros((s,), np.int64)
        self._last_accepted = np.zeros((s,), np.int64)
        self._last_verified = np.zeros((s,), np.int64)
        self._last_rollbacks = np.zeros((s,), np.int64)
        self._last_rounds = 0

    # -- submission -----------------------------------------------------
    def submit(self, req):
        try:
            self._check_submit(req)
        except ValueError:
            trk = self._infer.tracker
            if trk is not None:
                trk.on_rejected()
            raise
        self.queue.append(req)

    def _check_submit(self, req):
        req.tokens = np.asarray(req.tokens, np.int32).reshape(-1)
        if len(req.tokens) < 1:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        if req.eos_token_id is None:
            req.eos_token_id = self._infer.config.eos_token_id
        total = len(req.tokens) + req.max_new_tokens
        if total > self._infer.max_seq_len:
            raise ValueError(
                f"request {req.rid!r}: prompt ({len(req.tokens)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_seq_len {self._infer.max_seq_len}")
        if req.max_new_tokens > self._infer.config.max_new_tokens:
            raise ValueError(
                f"request {req.rid!r}: max_new_tokens "
                f"{req.max_new_tokens} exceeds the engine buffer width "
                f"inference.max_new_tokens="
                f"{self._infer.config.max_new_tokens}")
        never = self._infer.cache.never_fits(total)
        if never is not None:
            # a request that can NEVER fit the cache must be rejected
            # here: _admit would wait forever for an eviction that
            # cannot help, starving everything queued behind it
            raise ValueError(f"request {req.rid!r}: {never}")
        if req.top_k > self._infer.config.top_k_max:
            raise ValueError(
                f"request {req.rid!r}: top_k {req.top_k} exceeds the "
                "compiled sampling cap inference.top_k_max="
                f"{self._infer.config.top_k_max}")

    def serve(self, requests, clock_zero=None):
        """Submit `requests` and run until everything finished.
        Returns them in completion order (each with results filled)."""
        for r in requests:
            self.submit(r)
        self.run(clock_zero=clock_zero)
        return self.results

    # -- the loop -------------------------------------------------------
    def _now(self):
        return time.monotonic() - self._t0

    def run(self, clock_zero=None):
        self._t0 = clock_zero if clock_zero is not None \
            else time.monotonic()
        self._last_fence_t = self._now()
        while self.unfinished():
            try:
                progressed = self.step()
            except Exception as exc:
                # serving forensics: the crash guard the training loop
                # has had since PR 7 — the flight dump (with the live
                # request table in its sticky context, and the spans of
                # the iteration that failed) survives the process; the
                # exception still propagates
                self._infer.monitor.trace.end_iteration(None)
                self._infer.monitor.on_crash(exc)
                raise
            if not progressed:
                # idle: everything queued is in the future
                time.sleep(0.0005)

    def unfinished(self):
        """Whether a step has anything left to do, now or later: a
        request queued, prefilling or live, or a block dispatched and
        unfetched."""
        return bool(self.queue or self.live or self.prefilling or
                    self._infer.blocks_in_flight())

    def step(self):
        """One serving iteration (admit -> prefill chunk -> decode
        block -> fence of the block before it). Returns False when
        there was nothing to do but wait for arrivals."""
        now = self._now()
        trace = self._infer.monitor.trace
        if not (self.live or self.prefilling or
                self._infer.blocks_in_flight() or
                any(r.arrival_time <= now for r in self.queue)):
            if not self._idle:
                self._idle = True
                trace.start("serve/idle", loop=self._id)
            return False
        self._iteration += 1
        trace.begin_iteration(self._id, self._iteration)
        if self._idle:
            self._idle = False
            trace.stop("serve/idle")
        with trace.span("serve/admit"):
            self._admit(now)
        self._prefill_turn()
        if not (self.live or self.prefilling or
                self._infer.blocks_in_flight()):
            # a request is due and the cache cannot cover it yet
            trace.end_iteration(None)
            return False
        # one block beyond the one this step fences: one more in
        # steady state, two where none waits to be accounted (the
        # first step after idle)
        while self.live and len(self._dispatched) <= self._keep_in_flight:
            self._decode_block()
        self._fence()
        trace.end_iteration(self._last_fence_t)
        return True

    def _decode_block(self):
        """Pages for one more block of every live slot, and its
        dispatch behind whatever is in flight."""
        trace = self._infer.monitor.trace
        with trace.span("serve/decode.pages"):
            # a speculative round can commit up to (draft steps + 1)
            # tokens per slot, so the per-block capacity window widens
            # from sync_every iterations to sync_every rounds of that
            # worst case (reservation-backed either way)
            per_iter = (self._infer.spec_next_draft() + 1) \
                if self._spec else 1
            iters = self._infer.config.sync_every * per_iter
            for slot, req in self.live.items():
                self._infer.ensure_decode_capacity(
                    slot, int(self._last_pos[slot]), iters)
            self._infer.push_tables()
        with trace.span("serve/decode.dispatch"):
            self._dispatched.append(
                (time.perf_counter(), self._infer.config.sync_every))
            if self._spec:
                self._infer.spec_block(self._infer.config.sync_every)
            else:
                self._infer.decode_block(self._infer.config.sync_every)

    # -- phases ---------------------------------------------------------
    def _free_slots(self):
        busy = set(self.live) | set(self.prefilling)
        return [s for s in range(self._infer.config.max_slots)
                if s not in busy]

    def _admit(self, now):
        """FIFO admission over the ARRIVED requests: not-yet-arrived
        entries are skipped (submission order need not be arrival
        order), but a ready request the cache cannot cover yet blocks
        the ready ones behind it — head-of-line FIFO fairness, so a
        big request is not starved by smaller later ones."""
        free = self._free_slots()
        future = []
        trk = self._infer.tracker
        while free and self.queue:
            req = self.queue.popleft()
            if req.arrival_time > now:
                future.append(req)
                continue
            worst = len(req.tokens) + req.max_new_tokens
            if not self._infer.cache.can_admit(worst):
                # the cache is exhausted: wait for an eviction
                self.queue.appendleft(req)
                if trk is not None:
                    trk.on_admission_deferred()
                break
            slot = free.pop(0)
            self._infer.cache.admit(slot, worst, name=str(req.rid))
            req.admitted_at = now
            self.prefilling[slot] = [req, 0]
            reserved = self._infer.cache.reservation(worst)
            if trk is not None:
                trk.on_admitted(
                    slot, str(req.rid), len(req.tokens),
                    req.max_new_tokens,
                    queued_s=max(now - req.arrival_time, 0.0),
                    pages_reserved=reserved.get("kv_pages_reserved", 0))
            self._infer.monitor.event(
                "request_admitted",
                request_id=str(req.rid), slot=int(slot),
                prompt_tokens=int(len(req.tokens)),
                max_new_tokens=int(req.max_new_tokens),
                queue_depth=len(self.queue),
                queued_ms=round((now - req.arrival_time) * 1e3, 3),
                **reserved)
        # not-yet-arrived requests go back in their original order
        for req in reversed(future):
            self.queue.appendleft(req)

    def _prefill_turn(self):
        """ONE chunk per prefilling slot, then flip completed slots
        live — the chunk granularity is what interleaves long prompts
        with the decode batch."""
        chunk = self._infer.config.prefill_chunk
        trk = self._infer.tracker
        trace = self._infer.monitor.trace
        for slot in list(self.prefilling):
            req, start = self.prefilling[slot]
            t = len(req.tokens)
            n_prefill = t - 1
            if start < n_prefill:
                end = min(start + chunk, n_prefill)
                # prefill reads its table ROW from the host copy; the
                # device table upload happens once per iteration in
                # step() (push_tables dedupes by version anyway)
                with trace.span("serve/prefill.pages", slot=int(slot)):
                    self._infer.cache.ensure(slot, end, queries_from=start)
                with trace.span("serve/prefill.dispatch", slot=int(slot),
                                start=int(start), end=int(end)):
                    t0 = time.perf_counter()
                    self._infer.prefill_chunk(
                        slot, req.tokens[start:end], start)
                    if trk is not None:
                        trk.on_prefill_chunk(
                            slot, t0, time.perf_counter() - t0, start,
                            end)
                    self.prefilling[slot][1] = end
                start = end
            if start >= n_prefill:
                # decode writes the last prompt token's KV at t-1
                with trace.span("serve/prefill.pages", slot=int(slot)):
                    self._infer.cache.ensure(slot, max(t - 1, 1))
                self._infer.activate_slot(
                    slot, req.tokens[-1], t - 1, req.max_new_tokens,
                    req.temperature, req.top_k, req.eos_token_id)
                req.live_at = self._now()
                self.live[slot] = req
                self._last_pos[slot] = t - 1
                del self.prefilling[slot]
                if trk is not None:
                    trk.on_live(slot)

    def _fence(self):
        """The serving rendezvous: one device_get via
        engine.fetch_state, of the oldest block unfetched (or, with
        none, of the live state: a step that only prefilled), then
        eviction + events (host-only work — the tracker hooks are
        host dict/timestamp arithmetic; the sync-guard tests run with
        the tracker ENABLED)."""
        snap = self._infer.fetch_state()
        with self._infer.monitor.trace.span("serve/fence.bookkeeping"):
            self._account(snap)

    def _account(self, snap):
        """What the fence does with what it read: the slots' progress,
        evictions, the `decode_batch` row, the tracker's hooks."""
        # the blocks this snapshot covers: all the loop dispatched but
        # those still unfetched behind it (a caller's own `fetch_state`
        # between two steps took a snapshot the loop never saw: the
        # next step's fence then finds its own block the oldest
        # unfetched, reads it with nothing in flight, and accounts
        # both)
        decode_t0, iterations = None, 0
        while len(self._dispatched) > snap["blocks_in_flight"]:
            t0, launches = self._dispatched.popleft()
            decode_t0 = t0 if decode_t0 is None else decode_t0
            iterations += launches
        now = self._now()
        window_s = max(now - self._last_fence_t, 1e-9)
        trk = self._infer.tracker
        new_tokens = 0
        deltas = {}
        finished = []
        for slot, req in list(self.live.items()):
            gen = int(snap["n_gen"][slot])
            delta = gen - int(self._last_n_gen[slot])
            deltas[slot] = delta
            new_tokens += delta
            if delta > 0 and req.first_token_at is None:
                req.first_token_at = now
            self._last_pos[slot] = int(snap["pos"][slot])
            self._last_n_gen[slot] = gen
            if not snap["active"][slot]:
                finished.append((slot, req))
        if trk is not None:
            # TTFT + per-slot decode windows BEFORE evictions, so a
            # request that got its first token and finished inside the
            # same window still records both
            trk.on_fence_progress(decode_t0, iterations, deltas)
        for slot, req in finished:
            self._finish(slot, req, snap, now)
        rollback_pages = 0
        if self._spec:
            # rejected-suffix rollback, host side: trim each live
            # slot's page tables to its actual committed length (the
            # device kv_limit was rewound inside verify; no page data
            # moves) — the freed pages fund admissions this fence
            for slot in self.live:
                rollback_pages += self._infer.cache.rollback(
                    slot, int(snap["pos"][slot]) + 1)
            self._spec_fence(snap, window_s, iterations, rollback_pages)
        if new_tokens > 0:
            self.token_latencies.extend(
                [window_s / new_tokens] * new_tokens)
        self._last_fence_t = now
        mon = self._infer.monitor
        # how far the decode kernel engages: the pages it walks at the
        # next launch, or for recurrent state the slots it streamed
        # and advanced over this fence's launches, and the rows its
        # prefill launches took through a slot's state against the
        # prompt tokens among them, and the keys their attention walked
        # against their table rows'; host arithmetic on what the fence
        # already holds
        prefill_launches, prefill_tokens, *prefill_keys = \
            snap["prefilled"] - self._last_prefilled
        self._last_prefilled = snap["prefilled"]
        engaged = self._infer.cache.attended(
            snap["active"], snap["pos"], iterations, new_tokens,
            prefill_launches * self._infer.config.prefill_chunk,
            prefill_tokens, prefill_keys)
        mon.event(
            "decode_batch",
            # the loop's clock, on which requests arrive
            loop_s=round(now, 6),
            iterations=int(iterations),
            # the blocks the device still had in its queue when this
            # fence's device_get began: 1 where the loop kept the next
            # block in flight, 0 where it read the newest state
            blocks_in_flight=int(snap["blocks_in_flight"]),
            prefill_launches=int(prefill_launches),
            active_slots=len(self.live),
            prefilling_slots=len(self.prefilling),
            queue_depth=len(self.queue),
            window_ms=round(window_s * 1e3, 3),
            window_tokens=int(new_tokens),
            tokens_per_sec=round(new_tokens / window_s, 3),
            # pages in use and free, or for a model of recurrent state
            # the slots holding state and its bytes
            **self._infer.cache.occupancy(), **engaged,
            **self._counted(snap.get("counts")), **self._host_phases())
        if trk is not None:
            # SLO metrics AFTER evictions: this fence's finishes are in
            # the histograms/counters the event reports
            trk.on_fence_metrics(window_s, new_tokens,
                                 len(self.queue), len(self.live),
                                 len(self.prefilling), engaged)
        if mon.memory_enabled:
            mon._emit_memory_event(self._infer._host_steps)

    def _host_phases(self):
        """Where the host's time since the last fence went, by phase
        (self times; this fence's own bookkeeping, still open, lands
        on the next row): `host_ms` sums to the row's `window_ms`,
        `host_iter_ms` is the host's own part of it (all but the wait
        inside the `device_get` and for arrivals), `host_longest` the
        longest single span, which names the phase a stalled loop
        stood in."""
        spans = {name[len(SERVE_PREFIX):]: row for name, row in
                 self._infer.monitor.trace.drain().items()
                 if name.startswith(SERVE_PREFIX)}
        longest = max(spans, key=lambda p: spans[p]["max_ms"], default=None)
        return {
            "host_ms": {p: row["ms"] for p, row in spans.items()},
            "host_iter_ms": round(sum(
                row["ms"] for p, row in spans.items()
                if p not in ("fence.device_get", "idle")), 3),
            "host_longest": None if longest is None else
            [longest, spans[longest]["max_ms"]]}

    def _counted(self, counts):
        """What the programs counted over this fence's launches
        (`sample_draw_launches`, the model's block's counters): the
        decode program's under the counters' own names, prefill's
        under `prefill_<name>`. The device's sums are int32 and wrap;
        a fence's share of them does not."""
        out = {}
        for program, prefix in (("decode", ""), ("prefill", "prefill_")):
            for name, total in (counts or {}).get(program, {}).items():
                last = self._last_counts.get((program, name), 0)
                out[prefix + name] = (total - last) & 0xFFFFFFFF
                self._last_counts[program, name] = total
        return out

    def _spec_fence(self, snap, window_s, iterations, rollback_pages):
        """Per-fence speculative accounting: diff the cumulative
        device counters (read inside the ONE fetch_state device_get)
        against the host mirrors, emit the `speculative` event, and
        hand the tracker its drafted-vs-verified dispatch split."""
        sp = snap["speculative"]
        drafted = sp["drafted"].astype(np.int64)
        accepted = sp["accepted"].astype(np.int64)
        verified = sp["verified"].astype(np.int64)
        rollbacks = sp["rollbacks"].astype(np.int64)
        d = int((drafted - self._last_drafted).sum())
        a = int((accepted - self._last_accepted).sum())
        v = int((verified - self._last_verified).sum())
        rb = int((rollbacks - self._last_rollbacks).sum())
        rounds = int(sp["rounds"]) - self._last_rounds
        self._last_drafted = drafted
        self._last_accepted = accepted
        self._last_verified = verified
        self._last_rollbacks = rollbacks
        self._last_rounds = int(sp["rounds"])
        draft_s, verify_s = self._infer.spec_dispatch_split()
        trk = self._infer.tracker
        if trk is not None:
            trk.on_speculative(draft_s, verify_s, d, a, v, rb)
        if rounds <= 0 and d == 0:
            return
        self._infer.monitor.event(
            "speculative",
            rounds=int(rounds),
            drafted_tokens=d,
            accepted_tokens=a,
            acceptance_rate=round(a / d, 4) if d > 0 else None,
            # emitted tokens per flagship verify launch (each verified
            # slot-round commits its accepted drafts + one flagship
            # token) — THE speculative speedup number; vanilla decode
            # is identically 1.0
            tokens_per_verify=round((a + v) / v, 3) if v > 0 else None,
            rollback_events=rb,
            rollback_pages=int(rollback_pages),
            mean_k=round(float(np.mean(
                sp["k_slot"][snap["active"]])), 3)
            if snap["active"].any() else None,
            draft_dispatch_ms=round(draft_s * 1e3, 3),
            verify_dispatch_ms=round(verify_s * 1e3, 3))

    def _finish(self, slot, req, snap, now):
        gen = int(snap["n_gen"][slot])
        req.out_tokens = np.asarray(
            snap["out_tokens"][slot][:gen], np.int32)
        req.finish_reason = "eos" if snap["finished_eos"][slot] \
            else "max_tokens"
        req.finished_at = now
        del self.live[slot]
        self._last_n_gen[slot] = 0
        self._last_pos[slot] = 0
        trk = self._infer.tracker
        if trk is not None:
            # before cache.free: the tracker's final row keeps the
            # pages the request held when it finished
            trk.on_finished(slot, req.finish_reason)
        # the block in flight runs this slot as a no-op (the device's
        # `active` is False already) and reads none of its pages; they
        # go to work that is queued behind that block, and the device
        # runs its queue in order
        self._infer.cache.free(slot)
        self.results.append(req)
        wall_s = max(now - req.admitted_at, 1e-9)
        live_at = req.live_at if req.live_at is not None \
            else req.admitted_at
        decode_s = max(now - live_at, 1e-9)
        self._infer.monitor.event(
            "request_finished",
            request_id=str(req.rid), slot=int(slot),
            reason=req.finish_reason,
            prompt_tokens=int(len(req.tokens)),
            new_tokens=gen,
            queued_ms=round(
                (req.admitted_at - req.arrival_time) * 1e3, 3),
            # from ARRIVAL, as the client counts it: queued_ms is
            # the part of it spent before admission
            ttft_ms=None if req.first_token_at is None else round(
                (req.first_token_at - req.arrival_time) * 1e3, 3),
            prefill_ms=round(max(live_at - req.admitted_at, 0.0) * 1e3,
                             3),
            decode_ms=round(decode_s * 1e3, 3),
            token_ms=round(decode_s * 1e3 / max(gen, 1), 3),
            wall_ms=round(wall_s * 1e3, 3),
            tokens_per_sec=round(gen / wall_s, 3))


def serve_sequential(engine, requests, clock_zero=None):
    """Request-at-a-time baseline for the serving A/B: each request is
    served alone (admitted no earlier than its arrival time, run to
    completion before the next is looked at) on the SAME engine and
    cache. This is what continuous batching replaces."""
    loop = ServingLoop(engine)
    loop._t0 = clock_zero if clock_zero is not None \
        else time.monotonic()
    loop._last_fence_t = loop._now()
    for req in sorted(requests, key=lambda r: r.arrival_time):
        while loop._now() < req.arrival_time:
            time.sleep(0.0005)
        loop.submit(req)
        while loop.unfinished():
            loop.step()
    return loop
