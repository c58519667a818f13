"""Speculative decoding tests (ISSUE 18, inference/speculative.py).

Covers:
  * LOSSLESSNESS, the headline contract: at temperature 0 the
    speculative serving loop's per-request outputs are BIT-IDENTICAL
    to vanilla decode — with a perfect draft (100% acceptance), with a
    deliberately mismatched external draft (partial acceptance +
    rollbacks), and through EOS / max-tokens edge cases;
  * the modified-rejection-sampling acceptance math at temp > 0,
    statistically pinned in isolation (accept x~q with prob
    min(1, p/q), resample from norm(max(p-q, 0)) => the emitted
    distribution IS p), and its exactness corollary on device: a
    draft identical to the flagship is never rejected;
  * the HOTSYNC guard extended to the speculative loop: spec_block
    dispatches draft+verify rounds with ZERO host syncs, and the
    serving fence stays ONE fused device_get;
  * adaptive k: garbage drafts drive per-slot k to k_min and shrink
    the host's draft dispatch depth; perfect drafts keep k at the cap;
  * mixed-k continuous batching: slots at different accepted lengths
    with mid-round finishes still produce per-request streams
    identical to vanilla;
  * `speculative.enabled=false` (the default) leaves the engine
    byte-for-byte at vanilla behavior (no draft programs, no spec
    state keys, identical outputs);
  * the `speculative` monitor event schema and the tracker's
    drafted-vs-verified split (docs/monitoring.md EVTSCHEMA row).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import (InferenceConfigError, InferenceEngine,
                                     Request, ServingLoop)
from deepspeed_tpu.inference import speculative as spec_mod
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config


def _params(model):
    return model.init(jax.random.PRNGKey(0),
                      {"input_ids": np.zeros((1, 8), np.int32)})


def _inference_cfg(**speculative):
    block = {"max_slots": 4, "prefill_chunk": 16, "sync_every": 4,
             "max_new_tokens": 32,
             "kv_cache": {"num_pages": 120, "page_size": 4}}
    if speculative:
        block["speculative"] = dict({"enabled": True}, **speculative)
    return {"inference": block}


def _perturbed(params, scale, seed=99):
    """Flagship params with small noise on every block leaf: a draft
    that mostly agrees with the flagship but diverges often enough to
    exercise rejection + rollback."""
    r = np.random.RandomState(seed)
    blocks = jax.tree_util.tree_map(
        lambda x: x + scale * r.randn(*x.shape).astype(x.dtype),
        params["h"])
    return dict(params, h=blocks)


@pytest.fixture(scope="module")
def base():
    """One flagship + a vanilla engine and a truncate:1 speculative
    engine over the SAME params (the bit-identity pair)."""
    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    params = _params(model)
    vanilla = InferenceEngine(cfg, params, _inference_cfg())
    spec = InferenceEngine(cfg, params, _inference_cfg(
        draft_model="truncate:1", k=4, k_min=1, adaptive=True))
    return cfg, model, params, vanilla, spec


@pytest.fixture(scope="module")
def ext(base):
    """A speculative engine whose EXTERNAL draft is the flagship with
    perturbed block weights: high-but-partial acceptance, so rollback
    and the correction path run on every request."""
    cfg, model, params, vanilla, _ = base
    engine = InferenceEngine(
        cfg, params, _inference_cfg(draft_model="external", k=3),
        draft_params=_perturbed(params, 0.01),
        draft_model_config=cfg)
    return cfg, vanilla, engine


def _serve(engine, reqs):
    engine.reset()
    res = ServingLoop(engine).serve(reqs)
    return {q.rid: (q.out_tokens.tolist(), q.finish_reason)
            for q in res}


def _mixed_requests(cfg, seed, n=7, eos=None):
    r = np.random.RandomState(seed)
    return [Request(rid=i,
                    tokens=r.randint(0, cfg.vocab_size,
                                     size=int(r.randint(3, 30))
                                     ).astype(np.int32),
                    max_new_tokens=int(r.randint(3, 14)),
                    eos_token_id=eos)
            for i in range(n)]


# ----------------------------------------------------------------------
# config validation
# ----------------------------------------------------------------------
def test_speculative_config_validation():
    cfg = tiny_gpt2_config()
    params = _params(GPT2ForCausalLM(cfg))
    for bad in ({"draft_model": "half"}, {"draft_model": "truncate:0"},
                {"draft_model": "truncate:x"}, {"k": 0},
                {"k": 2, "k_min": 3}):
        with pytest.raises(InferenceConfigError,
                           match="inference\\.speculative\\."):
            InferenceEngine(cfg, params, _inference_cfg(**bad))
    # truncate deeper than the flagship
    with pytest.raises(ValueError, match="only"):
        InferenceEngine(cfg, params,
                        _inference_cfg(draft_model="truncate:9"))
    # external without the weights
    with pytest.raises(ValueError, match="external"):
        InferenceEngine(cfg, params,
                        _inference_cfg(draft_model="external"))


def test_derive_draft_shares_embeddings_and_slices_blocks():
    cfg = tiny_gpt2_config()
    params = _params(GPT2ForCausalLM(cfg))
    dcfg, dparams = spec_mod.derive_draft(cfg, params, "truncate:1")
    assert dcfg.n_layer == 1 and cfg.n_layer == 2
    # wte/wpe/ln_f are SHARED (same buffers, zero new bytes)
    assert dparams["wte"] is params["wte"]
    assert dparams["wpe"] is params["wpe"]
    assert dparams["ln_f"] is params["ln_f"]
    (_, stacked), = params["h"].items()
    (_, sliced), = dparams["h"].items()
    full = jax.tree_util.tree_leaves(stacked)
    cut = jax.tree_util.tree_leaves(sliced)
    for f, c in zip(full, cut):
        assert c.shape[0] == 1 and f.shape[0] == 2
        assert np.array_equal(np.asarray(f[:1]), np.asarray(c))


# ----------------------------------------------------------------------
# acceptance math, in isolation
# ----------------------------------------------------------------------
def test_leading_accept_count():
    flags = jnp.asarray([[1, 1, 0, 1], [0, 1, 1, 1],
                         [1, 1, 1, 1], [0, 0, 0, 0]], bool)
    assert spec_mod.leading_accept_count(flags).tolist() == [2, 0, 4, 0]


def test_residual_distribution_properties():
    r = np.random.RandomState(0)
    p = r.dirichlet(np.ones(16), size=3).astype(np.float32)
    q = r.dirichlet(np.ones(16), size=3).astype(np.float32)
    res = np.asarray(spec_mod.residual_distribution(
        jnp.asarray(p), jnp.asarray(q)))
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    # support: only where p > q
    assert (res[p <= q] == 0).all()
    ref = np.maximum(p - q, 0)
    ref /= ref.sum(-1, keepdims=True)
    np.testing.assert_allclose(res, ref, atol=1e-6)
    # zero residual mass (p == q) degenerates to p, not NaN
    same = np.asarray(spec_mod.residual_distribution(
        jnp.asarray(p), jnp.asarray(p)))
    np.testing.assert_allclose(same, p, atol=1e-6)


def test_process_logits_matches_topk_mask_and_temperature():
    r = np.random.RandomState(1)
    l32 = r.randn(2, 16).astype(np.float32)
    out = np.asarray(spec_mod.process_logits(
        jnp.asarray(l32), jnp.asarray([2, 0], np.int32),
        jnp.asarray([0.5, 2.0], np.float32), top_k_cap=16))
    # slot 0: only the top-2 survive, scaled by 1/0.5
    kth = np.sort(l32[0])[-2]
    ref0 = np.where(l32[0] < kth, -np.inf, l32[0]) / 0.5
    np.testing.assert_allclose(out[0], ref0, atol=1e-6)
    # slot 1: top_k=0 disables the mask
    np.testing.assert_allclose(out[1], l32[1] / 2.0, atol=1e-6)


def test_modified_rejection_sampling_targets_p_statistically():
    """The losslessness theorem, pinned numerically: drawing x ~ q,
    accepting when u < p(x)/q(x), and resampling from
    norm(max(p - q, 0)) on rejection emits EXACTLY p. Mirrors the
    verify program's formulas (same accept rule, same residual)."""
    r = np.random.RandomState(2)
    vocab, n = 8, 200_000
    p = r.dirichlet(np.ones(vocab) * 2).astype(np.float64)
    q = r.dirichlet(np.ones(vocab) * 2).astype(np.float64)
    x = r.choice(vocab, size=n, p=q)
    u = r.rand(n)
    accept = u < (p[x] / q[x])
    res = np.asarray(spec_mod.residual_distribution(
        jnp.asarray(p[None].astype(np.float32)),
        jnp.asarray(q[None].astype(np.float32))))[0].astype(np.float64)
    res /= res.sum()
    corr = r.choice(vocab, size=n, p=res)
    emitted = np.where(accept, x, corr)
    empirical = np.bincount(emitted, minlength=vocab) / n
    # 200k draws: ~3-sigma bound on each bucket is ~0.0034
    np.testing.assert_allclose(empirical, p, atol=0.006)
    # sanity: the acceptance path was actually partial
    assert 0.05 < accept.mean() < 0.999


# ----------------------------------------------------------------------
# temp-0 bit-identity (the headline contract)
# ----------------------------------------------------------------------
def test_temp0_bitexact_perfect_draft(base):
    """truncate:1 draft, 7 mixed continuous-batched requests queued
    through 4 slots: every output token stream and finish reason is
    identical to vanilla decode."""
    cfg, model, params, vanilla, spec = base
    reqs = _mixed_requests(cfg, seed=31)
    want = _serve(vanilla, _mixed_requests(cfg, seed=31))
    got = _serve(spec, reqs)
    assert got == want


def test_temp0_bitexact_partial_acceptance(ext):
    """Mismatched external draft: acceptance is PARTIAL (rollbacks
    happen), yet the output is still bit-identical — rejection +
    correction + rollback never leak into the emitted stream."""
    cfg, vanilla, spec = ext
    for seed in (41, 42, 43):
        want = _serve(vanilla, _mixed_requests(cfg, seed=seed, n=5))
        got = _serve(spec, _mixed_requests(cfg, seed=seed, n=5))
        assert got == want, seed
    snap = spec.fetch_state()["speculative"]
    drafted = int(snap["drafted"].sum())
    accepted = int(snap["accepted"].sum())
    assert drafted > 0
    assert 0 < accepted < drafted, "draft must be partially accepted"
    assert int(snap["rollbacks"].sum()) > 0, \
        "a mismatched draft must trigger rejected-suffix rollbacks"


def test_temp0_bitexact_eos_and_budget_edges(ext):
    """EOS hit mid-round (inside an accepted prefix AND via the
    correction token) and max_new exhaustion mid-round both truncate
    identically to vanilla."""
    cfg, vanilla, spec = ext
    r = np.random.RandomState(55)
    prompt = r.randint(0, cfg.vocab_size, size=9).astype(np.int32)
    probe = _serve(vanilla, [Request(rid="p", tokens=prompt.copy(),
                                     max_new_tokens=12)])
    out = probe["p"][0]
    assert len(out) == 12
    # pick EOS ids that cut the stream at different round offsets
    for eos in (out[0], out[2], out[5], out[11]):
        reqs = lambda: [Request(rid="e", tokens=prompt.copy(),
                                max_new_tokens=12, eos_token_id=eos)]
        want = _serve(vanilla, reqs())
        got = _serve(spec, reqs())
        assert got == want, eos
        assert want["e"][1] == "eos"
    # budget edge: max_new smaller than one full round
    for m in (1, 2, 3):
        reqs = lambda: [Request(rid="b", tokens=prompt.copy(),
                                max_new_tokens=m)]
        assert _serve(spec, reqs()) == _serve(vanilla, reqs()), m


# ----------------------------------------------------------------------
# temp > 0
# ----------------------------------------------------------------------
def test_temp_positive_identical_draft_never_rejected(base):
    """Exactness corollary of the accept rule on DEVICE: truncate:2 of
    a 2-layer flagship IS the flagship, so p == q and
    u < p/q == 1 always — every draft accepted, zero rollbacks, even
    at high temperature."""
    cfg, model, params, vanilla, _ = base
    engine = InferenceEngine(cfg, params, _inference_cfg(
        draft_model="truncate:2", k=3, adaptive=False))
    r = np.random.RandomState(61)
    res = ServingLoop(engine).serve(
        [Request(rid=i, tokens=r.randint(0, cfg.vocab_size, size=7 + i),
                 max_new_tokens=10, temperature=1.2, top_k=32)
         for i in range(3)])
    assert all(len(q.out_tokens) == 10 for q in res)
    assert all(0 <= t < cfg.vocab_size
               for q in res for t in q.out_tokens)
    snap = engine.fetch_state()["speculative"]
    assert int(snap["drafted"].sum()) > 0
    assert int(snap["accepted"].sum()) == int(snap["drafted"].sum())
    assert int(snap["rollbacks"].sum()) == 0


def test_temp_positive_mismatched_draft_smoke(ext):
    """End-to-end at temp > 0 with a mismatched draft: valid tokens,
    partial acceptance, deterministic under the same seed (the
    rejection coins and correction draws ride the engine RNG)."""
    cfg, vanilla, spec = ext
    r = np.random.RandomState(62)
    prompt = r.randint(0, cfg.vocab_size, size=11).astype(np.int32)

    def run():
        spec.reset()
        return ServingLoop(spec).serve(
            [Request(rid="t", tokens=prompt.copy(), max_new_tokens=10,
                     temperature=0.9, top_k=16)])[0].out_tokens.tolist()

    a = run()
    assert a == run(), "same seed must replay the same stream"
    assert len(a) == 10 and all(0 <= t < cfg.vocab_size for t in a)
    snap = spec.fetch_state()["speculative"]
    assert 0 < int(snap["accepted"].sum()) <= int(snap["drafted"].sum())


# ----------------------------------------------------------------------
# HOTSYNC: the speculative loop stays sync-free
# ----------------------------------------------------------------------
class _SyncCounters:
    """Same instrumentation as tests/test_inference.py: count the
    host-sync entry points."""

    def __init__(self, monkeypatch):
        self.device_get = 0
        self.effects_barrier = 0
        real_get, real_barrier = jax.device_get, jax.effects_barrier

        def counting_get(x):
            self.device_get += 1
            return real_get(x)

        def counting_barrier():
            self.effects_barrier += 1
            return real_barrier()

        monkeypatch.setattr(jax, "device_get", counting_get)
        monkeypatch.setattr(jax, "effects_barrier", counting_barrier)


def test_spec_block_zero_host_syncs(base, monkeypatch):
    """Draft chaining, device-side acceptance, adaptive-k updates —
    ALL of it without a single host<->device rendezvous between
    fences; the fence stays ONE fused device_get (now carrying the
    speculative counters too)."""
    cfg, model, params, vanilla, spec = base
    spec.reset()
    r = np.random.RandomState(71)
    for slot in range(3):
        prompt = r.randint(0, cfg.vocab_size,
                           size=6 + 3 * slot).astype(np.int32)
        spec.start_request(slot, prompt, max_new=24)
    spec.spec_block(2)      # warm the dispatch path
    counters = _SyncCounters(monkeypatch)
    for _ in range(3):
        spec.spec_block(2)
    assert counters.device_get == 0, \
        f"spec loop called jax.device_get {counters.device_get}x"
    assert counters.effects_barrier == 0
    snap = spec.fetch_state()
    assert counters.device_get == 1, \
        "the serving fence must stay exactly ONE device_get"
    assert snap["n_gen"][:3].min() > 0
    assert int(snap["speculative"]["drafted"].sum()) > 0
    spec.reset()


# ----------------------------------------------------------------------
# adaptive k
# ----------------------------------------------------------------------
def test_adaptive_k_backs_off_on_hopeless_draft(base):
    """A draft that NEVER matches the flagship (ln_f zeroed => its
    logits are identically 0, so it always proposes token 0) drives
    the per-slot k down to k_min and shrinks the host's draft dispatch
    depth, so the next block stops paying for dead draft steps."""
    cfg, model, params, vanilla, _ = base
    r = np.random.RandomState(81)
    prompt = r.randint(0, cfg.vocab_size, size=8).astype(np.int32)
    # precondition: the flagship's greedy stream never emits token 0,
    # so the constant-0 draft is rejected every single round
    vanilla.reset()
    ref = ServingLoop(vanilla).serve(
        [Request(rid="v", tokens=prompt.copy(), max_new_tokens=28)])[0]
    assert 0 not in ref.out_tokens.tolist()
    zero_head = dict(params, ln_f=jax.tree_util.tree_map(
        np.zeros_like, params["ln_f"]))
    engine = InferenceEngine(
        cfg, params, _inference_cfg(draft_model="external", k=4,
                                    k_min=1, adaptive=True),
        draft_params=zero_head, draft_model_config=cfg)
    engine.start_request(0, prompt, max_new=28)
    assert engine.spec_next_draft() == 4
    for _ in range(4):
        engine.spec_block(2)
        engine.fetch_state()
    snap = engine.fetch_state()
    assert int(snap["speculative"]["accepted"].sum()) == 0
    assert int(snap["speculative"]["k_slot"][0]) == 1
    assert engine.spec_next_draft() == 1
    engine.reset()
    # reset restores the optimistic depth
    assert engine.spec_next_draft() == 4


def test_adaptive_k_stays_at_cap_for_perfect_draft(base):
    cfg, model, params, vanilla, spec = base
    spec.reset()
    r = np.random.RandomState(82)
    spec.start_request(0, r.randint(0, cfg.vocab_size,
                                    size=8).astype(np.int32),
                       max_new=28)
    for _ in range(3):
        spec.spec_block(2)
        spec.fetch_state()
    snap = spec.fetch_state()
    assert int(snap["speculative"]["k_slot"][0]) == spec.config.spec_k
    assert spec.spec_next_draft() == spec.config.spec_k
    spec.reset()


# ----------------------------------------------------------------------
# mixed-k continuous batching (scheduler)
# ----------------------------------------------------------------------
def test_mixed_k_continuous_batching_mid_round_finish(ext):
    """Slots at different accepted lengths — a partial-acceptance
    draft guarantees heterogeneous per-slot commits — with tiny
    max_new requests finishing mid-round while others keep decoding,
    plus queueing past the slot count: the batch stays dense and
    every stream matches vanilla."""
    cfg, vanilla, spec = ext

    def reqs():
        r = np.random.RandomState(91)
        lens = [3, 17, 9, 24, 5, 12, 7, 20]
        news = [2, 13, 1, 9, 3, 11, 2, 6]    # 1- and 2-token finishers
        return [Request(rid=i,
                        tokens=r.randint(0, cfg.vocab_size,
                                         size=n).astype(np.int32),
                        max_new_tokens=m)
                for i, (n, m) in enumerate(zip(lens, news))]

    want = _serve(vanilla, reqs())
    got = _serve(spec, reqs())
    assert got == want
    assert sorted(len(v[0]) for v in got.values()) == \
        sorted([2, 13, 1, 9, 3, 11, 2, 6])


# ----------------------------------------------------------------------
# disabled by default: byte-for-byte vanilla
# ----------------------------------------------------------------------
def test_disabled_default_is_vanilla(base):
    cfg, model, params, vanilla, spec = base
    assert vanilla.speculative_enabled is False
    assert vanilla._draft_decode is None
    assert vanilla._verify is None
    assert vanilla._draft_prefill is None
    assert vanilla.cache.draft_n_layer == 0
    # explicit enabled=false is the same engine
    off = InferenceEngine(cfg, params, {"inference": dict(
        _inference_cfg()["inference"],
        speculative={"enabled": False, "k": 8})})
    assert off.speculative_enabled is False
    assert set(off._state.keys()) == set(vanilla._state.keys())
    snap = off.fetch_state()
    assert "speculative" not in snap
    want = _serve(vanilla, _mixed_requests(cfg, seed=101, n=4))
    got = _serve(off, _mixed_requests(cfg, seed=101, n=4))
    assert got == want


# ----------------------------------------------------------------------
# monitor event + tracker split
# ----------------------------------------------------------------------
def test_speculative_monitor_event_schema_and_tracker(tmp_path):
    cfg = tiny_gpt2_config()
    params = _params(GPT2ForCausalLM(cfg))
    engine = InferenceEngine(cfg, params, {
        "inference": {"max_slots": 2, "prefill_chunk": 8,
                      "sync_every": 4, "max_new_tokens": 16,
                      "kv_cache": {"num_pages": 48, "page_size": 4},
                      "speculative": {"enabled": True,
                                      "draft_model": "truncate:1"}},
        "monitor": {"enabled": True, "sinks": ["jsonl"],
                    "output_path": str(tmp_path)}})
    r = np.random.RandomState(111)
    ServingLoop(engine).serve(
        [Request(rid=f"r{i}", tokens=r.randint(0, cfg.vocab_size,
                                               size=6 + i),
                 max_new_tokens=8) for i in range(3)])
    trk = engine.tracker.snapshot()
    engine.monitor.close()
    events = []
    for root, _, files in os.walk(tmp_path):
        for f in files:
            if f.endswith(".jsonl"):
                with open(os.path.join(root, f)) as fh:
                    events += [json.loads(line) for line in fh]
    spec_events = [e for e in events if e["kind"] == "speculative"]
    assert spec_events, "serving fences must emit speculative events"
    keys = {"rounds", "drafted_tokens", "accepted_tokens",
            "acceptance_rate", "tokens_per_verify", "rollback_events",
            "rollback_pages", "mean_k", "draft_dispatch_ms",
            "verify_dispatch_ms"}
    for e in spec_events:
        assert keys <= set(e), keys - set(e)
    tot_drafted = sum(e["drafted_tokens"] for e in spec_events)
    tot_accepted = sum(e["accepted_tokens"] for e in spec_events)
    assert 0 < tot_accepted <= tot_drafted
    busy = [e for e in spec_events if e["acceptance_rate"] is not None]
    assert busy and all(0.0 <= e["acceptance_rate"] <= 1.0
                        for e in busy)
    assert all(e["tokens_per_verify"] >= 1.0 for e in busy
               if e["tokens_per_verify"] is not None)
    # the tracker carries the drafted-vs-verified dispatch split
    sp = trk["speculative"]
    assert sp["drafted_tokens"] == tot_drafted
    assert sp["accepted_tokens"] == tot_accepted
    assert sp["tokens_per_verify"] >= 1.0
    assert sp["draft_dispatch_s"] >= 0.0
    assert sp["verify_dispatch_s"] > 0.0


# ----------------------------------------------------------------------
# the draft and verify programs vs the per-layer-pool oracle (ISSUE 25)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("program", ["draft_prefill_fn", "draft_fn",
                                     "verify_fn"])
def test_spec_programs_bitexact_vs_per_layer_pool_oracle(base, program):
    """The three speculative programs share the layer scan that carries
    the pools (`scan_layers`): prompts of several chunks into two of
    four slots, one round of k draft steps and its verify, against the
    oracle of tests/paged_oracle.py (a Python loop over layers, one
    pool each). The draft's logits and both of its
    pools, and the flagship's pools after verify, are equal bit for
    bit, scratch page 0 included; verify keeps its logits to itself,
    so what it emitted is held against the oracle's greedy choice."""
    from tests.paged_oracle import (assert_pools_equal, oracle_forward,
                                    prefill_inputs)
    cfg, _, params, _, eng = base
    eng.reset()
    dcfg, dparams = eng._draft_config, eng._draft_params
    page, chunk = eng.cache.page_size, eng.config.prefill_chunk
    k, qb = eng.config.spec_k, eng.config.weight_quant_block
    draft_pools, pools = ("dk_pool", "dv_pool"), ("k_pool", "v_pool")
    r = np.random.RandomState(11)
    dk, dv = (np.asarray(eng._spec_state[n]) for n in draft_pools)
    for slot, length in ((1, 37), (3, 19)):
        prompt = r.randint(0, cfg.vocab_size, size=length)
        eng.start_request(slot, prompt, max_new=12)
        for start in range(0, length - 1, chunk):
            _, dk, dv = oracle_forward(
                dcfg, dparams, k_pool=dk, v_pool=dv, page_size=page,
                quant_block=qb, **prefill_inputs(
                    chunk, prompt[start:min(start + chunk, length - 1)],
                    start, eng.cache.tables[slot]))
    if program == "draft_prefill_fn":
        assert_pools_equal(eng._spec_state, draft_pools, (dk, dv), "")
        assert dk[:, 0].any() and dk[:, 1:].any()
        eng.reset()
        return
    st = jax.device_get({n: eng._state[n] for n in (
        "cur_token", "pos", "active", "tables", "n_gen", "max_new")})
    assert list(st["active"]) == [False, True, False, True]
    dk, dv = (np.asarray(eng._spec_state[n]) for n in draft_pools)
    sp = eng._spec_state
    for j in range(k):
        k_slot, dtoks = jax.device_get((sp["k_slot"], sp["dtoks"]))
        cur = st["cur_token"] if j == 0 else dtoks[:, j - 1]
        k_eff = np.minimum(k_slot, np.maximum(
            st["max_new"] - st["n_gen"] - 1, 0))
        sp = eng._draft_decode(dparams, eng._state, sp)
        if program != "draft_fn":
            continue
        pos = st["pos"] + j
        ref, dk, dv = oracle_forward(
            dcfg, dparams, cur[:, None], pos[:, None],
            (st["active"] & (j < k_eff))[:, None], pos, st["tables"],
            dk, dv, page, qb)
        got = np.asarray(sp["dlogits"][:, j])
        assert np.array_equal(got, ref[:, 0].astype(np.float32)), j
        assert_pools_equal(sp, draft_pools, (dk, dv), j)
    if program == "verify_fn":
        k_slot, dtoks, n_draft = jax.device_get(
            (sp["k_slot"], sp["dtoks"], sp["n_draft"]))
        k_ref, v_ref = (np.asarray(eng._state[n]) for n in pools)
        n_valid = np.minimum(np.minimum(k_slot, n_draft), np.maximum(
            st["max_new"] - st["n_gen"] - 1, 0))
        steps = np.arange(k + 1)
        new_state, sp = eng._verify(params, eng._state, sp)
        eng._state = new_state
        ref, k_ref, v_ref = oracle_forward(
            cfg, params,
            np.concatenate([st["cur_token"][:, None], dtoks], axis=1),
            st["pos"][:, None] + steps[None],
            st["active"][:, None] & (steps[None] <= n_valid[:, None]),
            st["pos"] + n_valid, st["tables"], k_ref, v_ref, page, qb)
        assert_pools_equal(new_state, pools, (k_ref, v_ref), "verify")
        greedy = ref.argmax(-1)
        out, n_gen = jax.device_get((new_state["out_tokens"],
                                     new_state["n_gen"]))
        for slot in (1, 3):
            accepted = 0
            while accepted < n_valid[slot] and \
                    dtoks[slot, accepted] == greedy[slot, accepted]:
                accepted += 1
            emitted = list(dtoks[slot, :accepted]) + \
                [greedy[slot, accepted]]
            assert n_gen[slot] == len(emitted)
            assert list(out[slot, :len(emitted)]) == emitted
    eng._spec_state = sp
    eng.reset()
