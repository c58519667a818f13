"""Speculative decoding — draft-model propose, batched flagship
verify, lossless acceptance on the paged KV cache (ISSUE 18).

The vanilla engine emits exactly one token per flagship launch; this
module makes each launch emit up to k+1 **verified** tokens:

  1. **draft-decode** (k cheap steps): a small draft model — by
     default the flagship's first N layers with everything round them
     shared (`draft_model: "truncate:N"`, zero extra checkpoint; the
     model's own `first_layers`) — proposes the next k tokens
     autoregressively, writing its own K/V into a second paged pool
     that shares the flagship cache's page tables and allocator
     verbatim (one admission decision, one table upload; the draft
     pool is the `kv_cache_draft` ledger category);
  2. **verify** (ONE flagship launch): the widened decode program
     scores all k+1 positions per slot at once — the model's block
     with k+1 query rows a slot, through the same decode kernel as the
     one-row programs (`ops/transformer/paged_decode_attention.py`:
     each row masked at its own position) — and applies the
     acceptance rule **on device**, so a round adds zero host syncs
     and rounds chain back-to-back under the PR-2 dispatch discipline.

Losslessness (the output distribution is exactly vanilla decode's):

  * temperature 0 — greedy prefix-match: drafted token j is accepted
    while it equals argmax of the flagship logits given the committed
    prefix; the first mismatch position emits the flagship argmax
    instead. By induction every emitted token is the flagship's greedy
    choice, so the stream is BIT-IDENTICAL to vanilla decode (the
    decode kernel runs every query row through the same operations on
    the same page blocks whatever the number of rows, so a verify
    row's attention equals the single-token decode launch at that
    position bit for bit).
  * temperature > 0 — modified rejection sampling (Leviathan et al.):
    drafted token x ~ q is accepted with probability min(1, p(x)/q(x));
    the first rejection resamples from the residual
    norm(max(p - q, 0)), and a fully-accepted round draws one bonus
    token from p. Marginally each emitted token is distributed exactly
    as p — pinned statistically by tests/test_speculative.py.

Rollback is free by construction: stale K/V beyond a slot's `pos` is
past its live length, which the decode kernel neither reads (whole
pages) nor weighs (score-masked AND value-zeroed inside the last
page), so
rejecting a suffix just rewinds `pos` (device-side, in verify) and
trims the host page tables (`PagedKVCache.rollback` — LIFO, so
re-advancing pops the same physical pages back; no page is copied).

Adaptive k: each slot keeps an acceptance EMA on device; a fully
accepted round grows its k toward `speculative.k`, an EMA below
ADAPT_BACKOFF shrinks it toward `speculative.k_min`, and the host
reads max(live k) at the fence (inside the ONE fused device_get) to
dispatch fewer draft steps next block when the whole batch is being
rejected.

All three programs are built from the engine's adapters
(`engine.Serving`: the flagship's and the draft's `embed`, `layers`
and `head` over the paged kind's mixer); no model is named here.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import compile_fresh, process_logits

# fold_in lane separating the draft model's sampling stream from the
# flagship's (state["rng"] folded by step on one side, by
# DRAFT_RNG_LANE + draft_step on the other)
DRAFT_RNG_LANE = 1 << 20
# acceptance-EMA decay and the back-off threshold for adaptive k
ADAPT_EMA = 0.8
ADAPT_BACKOFF = 0.5


# ----------------------------------------------------------------------
# draft model derivation
# ----------------------------------------------------------------------
def derive_draft(model_config, params, draft_model):
    """Resolve `speculative.draft_model` to (draft_config,
    draft_params). "truncate:N" is the model's own `first_layers`: the
    stacked layer weights sliced to the first N, everything else the
    flagship's own buffers — the sliced leaves are the only new device
    bytes."""
    if not draft_model.startswith("truncate:"):
        raise ValueError(
            f"derive_draft cannot resolve draft_model={draft_model!r} "
            '(pass draft_params/draft_model_config for "external")')
    n = int(draft_model[len("truncate:"):])
    if n > model_config.n_layer:
        raise ValueError(
            f"speculative.draft_model={draft_model!r}: the flagship "
            f"has only {model_config.n_layer} layers")
    return model_config.serving_module.first_layers(model_config, params, n)


# ----------------------------------------------------------------------
# acceptance math (pure jnp; unit-tested in isolation)
# ----------------------------------------------------------------------
def leading_accept_count(flags):
    """Length of the leading all-True run along the last axis — the
    number of drafted tokens the acceptance rule keeps."""
    return jnp.cumprod(flags.astype(jnp.int32), axis=-1).sum(axis=-1)


def residual_distribution(p_probs, q_probs):
    """The modified-rejection-sampling correction distribution
    norm(max(p - q, 0)) [S, V]; degenerates to p where p == q (the
    only case the residual mass is zero — then the draft is never
    rejected anyway, so the fallback only guards float dust)."""
    res = jnp.maximum(p_probs - q_probs, 0.0)
    norm = res.sum(axis=-1, keepdims=True)
    return jnp.where(norm > 0.0, res / jnp.maximum(norm, 1e-30),
                     p_probs)


# ----------------------------------------------------------------------
# speculative device state
# ----------------------------------------------------------------------
def fresh_spec_state(engine):
    """Device-side round state: the draft KV pools (same page-table
    geometry as the flagship pools, draft layer count), the current
    round's proposals, and the per-slot counters the fence reads."""
    cfg, mc = engine.config, engine.model_config
    dmc = engine._draft_config
    s, k = cfg.max_slots, cfg.spec_k
    pool = engine.cache.pool_shape(dmc.n_layer)
    return {
        "dk_pool": jnp.zeros(pool, mc.dtype),
        "dv_pool": jnp.zeros(pool, mc.dtype),
        "dtoks": jnp.zeros((s, k), jnp.int32),
        "dlogits": jnp.zeros((s, k, mc.vocab_size), jnp.float32),
        "n_draft": jnp.zeros((), jnp.int32),
        "k_slot": jnp.full((s,), k, jnp.int32),
        "acc_ema": jnp.ones((s,), jnp.float32),
        "drafted_total": jnp.zeros((s,), jnp.int32),
        "accepted_total": jnp.zeros((s,), jnp.int32),
        "verified_total": jnp.zeros((s,), jnp.int32),
        "rollbacks": jnp.zeros((s,), jnp.int32),
        "rounds": jnp.zeros((), jnp.int32),
        "draft_step": jnp.zeros((), jnp.int32),
    }


# ----------------------------------------------------------------------
# the speculative AOT programs
# ----------------------------------------------------------------------
def build_draft_step(engine):
    """Compile the draft-decode program: ONE drafted token for every
    slot (call it n_draft times per round). Reads the flagship state
    (positions, tables, sampler params) without touching it; mutates
    only the spec state (donated)."""
    cfg, mc, draft = engine.config, engine.model_config, engine.draft_serving
    s, k = cfg.max_slots, cfg.spec_k
    top_k_cap = min(cfg.top_k_max, mc.vocab_size)

    def draft_fn(draft_params, state, spec):
        j = spec["n_draft"]
        active = state["active"]
        pos = state["pos"] + j
        # input token: the committed cur_token on step 0, last
        # proposal afterwards
        jprev = jnp.broadcast_to(jnp.clip(j - 1, 0, k - 1), (s, 1))
        prev = jnp.take_along_axis(spec["dtoks"], jprev, axis=1)[:, 0]
        cur = jnp.where(j == 0, state["cur_token"], prev)
        # never write K/V beyond the slot's generation budget: a round
        # emits at most (max_new - n_gen) tokens, so drafts past
        # budget-1 are dead weight AND would overrun the page table
        budget = state["max_new"] - state["n_gen"] - 1
        k_eff = jnp.minimum(spec["k_slot"], jnp.maximum(budget, 0))
        valid = active & (j < k_eff)
        posc = jnp.clip(pos, 0, mc.n_positions - 1)
        hidden = draft.embed(draft_params, cur, posc)[:, None, :]
        hidden, (dk, dv) = draft.layers(
            draft_params, hidden, (spec["dk_pool"], spec["dv_pool"]),
            pos[:, None], draft.kind.mixer(
                state["tables"], pos[:, None], valid[:, None], pos))
        logits = draft.head(draft_params, hidden)[:, 0]
        l32 = logits.astype(jnp.float32)
        greedy = jnp.argmax(l32, axis=-1).astype(jnp.int32)
        scaled = process_logits(l32, state["top_k"],
                                state["temperature"], top_k_cap)
        key = jax.random.fold_in(state["rng"],
                                 DRAFT_RNG_LANE + spec["draft_step"])
        keys = jax.vmap(jax.random.fold_in,
                        in_axes=(None, 0))(key, jnp.arange(s))
        drawn = jax.vmap(jax.random.categorical)(keys, scaled)
        tok = jnp.where(state["temperature"] > 0.0,
                        drawn.astype(jnp.int32), greedy)
        jc = jnp.clip(j, 0, k - 1)
        return dict(
            spec,
            dk_pool=dk, dv_pool=dv,
            dtoks=spec["dtoks"].at[:, jc].set(tok),
            dlogits=spec["dlogits"].at[:, jc].set(l32),
            n_draft=j + 1,
            draft_step=spec["draft_step"] + 1,
        )

    return compile_fresh(jax.jit(draft_fn, donate_argnums=(2,)).lower(
        engine._draft_params, engine._state, engine._spec_state))


def build_verify_step(engine):
    """Compile the verify program: the decode step widened to k+1
    positions per slot, plus the device-side acceptance rule, output
    commit, and kv_limit rollback. Consumes (donates) both the
    flagship state and the spec state."""
    cfg, mc, serving = engine.config, engine.model_config, engine.serving
    s, k, w = cfg.max_slots, cfg.spec_k, cfg.max_new_tokens
    top_k_cap = min(cfg.top_k_max, mc.vocab_size)
    adaptive = cfg.spec_adaptive
    k_min = cfg.spec_k_min

    def verify_fn(params, state, spec):
        active = state["active"]
        pos0 = state["pos"]
        n_gen = state["n_gen"]
        budget = state["max_new"] - n_gen
        # proposals this round: capped by the slot's adaptive k, the
        # draft steps actually dispatched, and the emission budget
        n_valid = jnp.minimum(jnp.minimum(spec["k_slot"],
                                          spec["n_draft"]),
                              jnp.maximum(budget - 1, 0))
        steps = jnp.arange(k + 1)
        tokens_in = jnp.concatenate(
            [state["cur_token"][:, None], spec["dtoks"]], axis=1)
        positions = pos0[:, None] + steps[None, :]
        write_ok = active[:, None] & (steps[None, :] <= n_valid[:, None])
        kv_limit = pos0 + n_valid
        posc = jnp.clip(positions, 0, mc.n_positions - 1)
        hidden = serving.embed(params, tokens_in, posc)
        hidden, (k_pool, v_pool) = serving.layers(
            params, hidden, (state["k_pool"], state["v_pool"]), positions,
            serving.kind.mixer(state["tables"], positions, write_ok,
                               kv_limit))
        logits = serving.head(params, hidden)
        l32 = logits.astype(jnp.float32)       # [s, k+1, V]

        d = spec["dtoks"]                      # [s, k]
        greedy = jnp.argmax(l32, axis=-1).astype(jnp.int32)
        valid = steps[None, :k] < n_valid[:, None]
        temp = state["temperature"]
        # -- acceptance rule ------------------------------------------
        match_greedy = d == greedy[:, :k]
        proc = jax.vmap(
            lambda lx: process_logits(lx, state["top_k"], temp,
                                      top_k_cap),
            in_axes=1, out_axes=1)
        p_probs = jax.nn.softmax(proc(l32), axis=-1)      # [s, k+1, V]
        q_probs = jax.nn.softmax(proc(spec["dlogits"]), axis=-1)
        p_d = jnp.take_along_axis(p_probs[:, :k], d[..., None],
                                  axis=-1)[..., 0]
        q_d = jnp.take_along_axis(q_probs, d[..., None],
                                  axis=-1)[..., 0]
        key = jax.random.fold_in(state["rng"], state["step"])
        u = jax.random.uniform(jax.random.fold_in(key, 1), (s, k))
        match_sample = u < (p_d / jnp.maximum(q_d, 1e-30))
        match = jnp.where((temp > 0.0)[:, None], match_sample,
                          match_greedy)
        a = leading_accept_count(valid & match)            # [s]
        # -- correction / bonus token at input position a -------------
        a3 = jnp.broadcast_to(a[:, None, None], (s, 1, mc.vocab_size))
        greedy_corr = jnp.take_along_axis(greedy, a[:, None],
                                          axis=1)[:, 0]
        pa = jnp.take_along_axis(p_probs, a3, axis=1)[:, 0]
        q_pad = jnp.concatenate(
            [q_probs, jnp.zeros((s, 1, mc.vocab_size), q_probs.dtype)],
            axis=1)
        qa = jnp.take_along_axis(q_pad, a3, axis=1)[:, 0]
        # a == n_valid means nothing was rejected: the extra token is
        # a BONUS draw from p itself, not a residual
        qa = jnp.where((a >= n_valid)[:, None], 0.0, qa)
        res = residual_distribution(pa, qa)
        rkeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.fold_in(key, 2), jnp.arange(s))
        drawn_corr = jax.vmap(jax.random.categorical)(
            rkeys, jnp.log(jnp.maximum(res, 1e-30))).astype(jnp.int32)
        corr = jnp.where(temp > 0.0, drawn_corr, greedy_corr)
        # -- commit: emitted tokens e_0..e_{m-1} ----------------------
        d_pad = jnp.concatenate(
            [d, jnp.zeros((s, 1), jnp.int32)], axis=1)
        e = jnp.where(steps[None, :] < a[:, None], d_pad,
                      corr[:, None])
        m0 = a + 1
        eos_hit = (e == state["eos"][:, None]) & \
            (steps[None, :] < m0[:, None])
        any_eos = eos_hit.any(axis=1)
        first_eos = jnp.argmax(eos_hit, axis=1)
        m1 = jnp.where(any_eos, first_eos + 1, m0)
        m = jnp.where(active, jnp.minimum(m1, budget), 0)
        eos_fin = active & any_eos & (first_eos + 1 <= m)
        n2 = n_gen + m
        hit_max = active & (n2 >= state["max_new"])
        wcols = jnp.arange(w)
        rel = wcols[None, :] - n_gen[:, None]
        in_win = (rel >= 0) & (rel < m[:, None])
        vals = jnp.take_along_axis(e, jnp.clip(rel, 0, k), axis=1)
        out = jnp.where(in_win, vals, state["out_tokens"])
        last = jnp.take_along_axis(
            e, jnp.clip(m - 1, 0, k)[:, None], axis=1)[:, 0]
        # -- adaptive k + fence counters ------------------------------
        frac = a.astype(jnp.float32) / \
            jnp.maximum(n_valid, 1).astype(jnp.float32)
        measured = active & (n_valid > 0)
        ema = jnp.where(measured,
                        ADAPT_EMA * spec["acc_ema"] +
                        (1.0 - ADAPT_EMA) * frac,
                        spec["acc_ema"])
        if adaptive:
            k_next = jnp.where(a >= n_valid, spec["k_slot"] + 1,
                               jnp.where(ema < ADAPT_BACKOFF,
                                         spec["k_slot"] - 1,
                                         spec["k_slot"]))
            k_next = jnp.clip(k_next, k_min, k)
            k_slot = jnp.where(measured, k_next, spec["k_slot"])
        else:
            k_slot = spec["k_slot"]
        rb = measured & (a < n_valid)
        new_state = dict(
            state,
            k_pool=k_pool, v_pool=v_pool,
            pos=pos0 + m,
            cur_token=jnp.where(m > 0, last, state["cur_token"]),
            active=active & ~(eos_fin | hit_max),
            finished_eos=state["finished_eos"] | eos_fin,
            n_gen=n2,
            out_tokens=out,
            step=state["step"] + 1,
        )
        new_spec = dict(
            spec,
            n_draft=jnp.zeros((), jnp.int32),
            k_slot=k_slot,
            acc_ema=ema,
            drafted_total=spec["drafted_total"] +
            jnp.where(active, n_valid, 0),
            accepted_total=spec["accepted_total"] +
            jnp.where(active, a, 0),
            verified_total=spec["verified_total"] +
            active.astype(jnp.int32),
            rollbacks=spec["rollbacks"] + rb.astype(jnp.int32),
            rounds=spec["rounds"] + 1,
        )
        return new_state, new_spec

    return compile_fresh(jax.jit(verify_fn, donate_argnums=(1, 2)).lower(
        engine._params, engine._state, engine._spec_state))


def build_draft_prefill_step(engine):
    """Compile the draft model's prefill twin: the same chunked prompt
    caching the flagship prefill does, into the draft pools (the draft
    attends over the full committed prefix, so its cache must cover
    the prompt too)."""
    draft, chunk = engine.draft_serving, engine.config.prefill_chunk

    def draft_prefill_fn(draft_params, dk_pool, dv_pool, page_row,
                         tokens, start, n_valid):
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        valid = jnp.arange(chunk) < n_valid
        hidden = draft.embed(draft_params, tokens, posv)[None]
        return draft.prefill_layers(draft_params, hidden, (dk_pool, dv_pool),
                                    page_row, posv, valid, start, n_valid)

    sp = engine._spec_state
    args = (engine._draft_params, sp["dk_pool"], sp["dv_pool"],
            jnp.asarray(engine.cache.tables[0]),
            jnp.zeros((chunk,), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    return compile_fresh(jax.jit(draft_prefill_fn, donate_argnums=(1, 2))
                         .lower(*args))
