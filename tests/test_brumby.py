"""Brumby on the serving path (ISSUE 26): the retention operations
against the plain reference's all-pairs form, `InferenceEngine` and
`ServingLoop` through recurrent state against the reference's full
forward, and GPT-2's programs through the changed `scan_layers`.

Tolerances. float32 against float32 differs by rounding in another
order only: the chunked and the recurrent form sum the same products
as the all-pairs form, grouped differently, and the recurrent read-out
phi(q).S sums 136 (here; 8,256 at head width 128) products of mixed
sign where the reference squares one dot product, so its error is
about 1e-7 of |phi(q)||S|, not of the result: 2e-5 of the largest
output holds with room. bfloat16 compute rounds every activation to 8
bits (4e-3) through 3 layers; 4e-2 of the largest logit holds with
room, and the tight float32 case is what pins the mathematics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import brumby as ref
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.models import brumby
from deepspeed_tpu.ops.retention import (phi, retention_chunked,
                                         retention_step, state_dim)

T, HQ, HK, D = 37, 10, 2, 16          # five query heads on one state
EPS = 1e-6


@pytest.fixture(scope="module")
def qkv():
    """q and k of the norm that q/k-norm leaves them, gates in [0.9,
    0.9999]: state older than any chunk below matters."""
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * D ** 0.5
    k = unit(rng.normal(size=(T, HK, D)))
    # every query leans towards its own token's key, so that no
    # normaliser is tiny: the recurrent read-out's error is 1e-7 of
    # |phi(q)||S| and is divided by it
    q = unit(np.repeat(k, HQ // HK, axis=1) / D ** 0.5 +
             0.7 * rng.normal(size=(T, HQ, D)) / D ** 0.5)
    q, k = jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, HK, D)), jnp.float32)
    lg = jnp.asarray(np.log(rng.uniform(0.9, 0.9999, (T, HK))), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.retention_all_pairs(q, k, v, lg, 1.0 / D, EPS, rows=16)
    return q, k, v, lg, np.asarray(want)


def zero_state():
    return (jnp.zeros((1, HK, state_dim(D), D)),
            jnp.zeros((1, HK, state_dim(D))))


def test_phi_is_the_symmetric_square():
    rng = np.random.default_rng(1)
    u, w = (jnp.asarray(rng.normal(size=(3, D)), jnp.float32)
            for _ in range(2))
    got = (phi(u, 0.25) * phi(w, 0.25)).sum(-1)
    assert np.allclose(got, (0.25 * (u * w).sum(-1)) ** 2, rtol=1e-5)
    assert phi(u, 1.0).shape == (3, state_dim(D)) == (3, 136)


@pytest.mark.parametrize("chunk", [8, 16, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_equals_all_pairs(qkv, chunk):
    """Chunk sizes that divide the length, that do not, the length
    itself and one past it."""
    q, k, v, lg, want = qkv
    o, S, z = retention_chunked(q[None], k[None], v[None], lg[None],
                                *zero_state(), 1.0 / D, EPS, chunk)
    assert np.abs(np.asarray(o[0]) - want).max() < 2e-5 * np.abs(want).max()
    # the state after the sequence does not depend on how it was cut
    _, S1, z1 = retention_chunked(q[None], k[None], v[None], lg[None],
                                  *zero_state(), 1.0 / D, EPS, T)
    assert np.allclose(S, S1, atol=1e-5) and np.allclose(z, z1, atol=1e-5)


def test_step_iterated_equals_all_pairs_and_the_chunked_state(qkv):
    q, k, v, lg, want = qkv
    S, z = zero_state()
    outs = []
    for t in range(T):
        o, S, z = retention_step(q[t][None], k[t][None], v[t][None],
                                 lg[t][None], S, z, 1.0 / D, EPS)
        outs.append(np.asarray(o[0]))
    assert np.abs(np.stack(outs) - want).max() < 2e-5 * np.abs(want).max()
    _, S1, z1 = retention_chunked(q[None], k[None], v[None], lg[None],
                                  *zero_state(), 1.0 / D, EPS, 8)
    assert np.allclose(S, S1, atol=1e-5) and np.allclose(z, z1, atol=1e-5)


def test_padding_rows_fresh_rows_and_kept_rows(qkv):
    """A chunk's padding leaves the state as it was; a fresh row starts
    from zero whatever the slot held; a kept row is not touched."""
    q, k, v, lg, _ = qkv
    n = 21
    valid = (jnp.arange(T) < n)[None]
    _, S, z = retention_chunked(q[None], k[None], v[None], lg[None],
                                *zero_state(), 1.0 / D, EPS, 8, valid)
    _, S1, z1 = retention_chunked(q[None, :n], k[None, :n], v[None, :n],
                                  lg[None, :n], *zero_state(), 1.0 / D, EPS,
                                  8)
    assert np.allclose(S, S1, atol=1e-6) and np.allclose(z, z1, atol=1e-6)
    two = lambda x: jnp.stack([x, x])
    junk = (jnp.full((2, HK, state_dim(D), D), 7.0),
            jnp.full((2, HK, state_dim(D)), 7.0))
    o, S2, z2 = retention_step(
        two(q[0]), two(k[0]), two(v[0]), two(lg[0]), *junk, 1.0 / D, EPS,
        keep=jnp.asarray([False, True]), fresh=jnp.asarray([True, False]))
    o0, S0, z0 = retention_step(q[0][None], k[0][None], v[0][None],
                                lg[0][None], *zero_state(), 1.0 / D, EPS)
    assert np.array_equal(S2[0], S0[0]) and np.array_equal(o[0], o0[0])
    assert np.array_equal(S2[1], junk[0][1]) and \
        np.array_equal(z2[1], junk[1][1])


# ----------------------------------------------------------------------
# the engine and the loop
# ----------------------------------------------------------------------
SIZES = {"num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
         "rms_norm_eps": 1e-6, "rope_theta": 1e6,
         "assumed": {"retention": {"degree": 2, "eps": EPS}}}
BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
         "max_new_tokens": 12, "max_seq_len": 128}


def tiny(dtype):
    cfg = brumby.BrumbyConfig(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=3, num_attention_heads=10, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=128, retention_chunk=8,
        dtype=dtype, param_dtype=dtype)
    params = brumby.init_params(cfg, jax.random.PRNGKey(0))
    # gates from a few tokens of memory to hundreds, norms off 1
    rng = np.random.default_rng(2)
    layers = dict(params["layers"])
    layers["bg"] = jnp.asarray(rng.uniform(1.0, 7.0, (3, 2)), dtype)
    for name in ("norm_in", "norm_post", "q_norm", "k_norm"):
        layers[name] = jnp.asarray(
            1.0 + 0.1 * rng.normal(size=layers[name].shape), dtype)
    params = dict(params, layers=layers)
    flat = {k: v for k, v in params.items() if k != "layers"}
    flat.update({"h." + k: v for k, v in layers.items()})
    return cfg, params, flat


@pytest.fixture(scope="module")
def model32():
    return tiny(jnp.float32)


def reference_logits(flat, ids):
    return np.asarray(ref.logits(flat, jnp.asarray(ids, jnp.int32), SIZES))


@pytest.mark.parametrize("shape", [(5, 64), (3, 7, 64)], ids=["2d", "3d"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_head_projection_is_the_product(shape, dtype):
    """Pinning the output's layout changes no value: `head_projection`
    is `x @ w` bit for bit, called eagerly, under `jit` and in a scan's
    body over stacked weights (where the chip's compiler used to slice
    and transpose them)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=(4, 64, 48)), dtype)
    want = np.asarray(x @ w[2], np.float32)
    assert want.shape == shape[:-1] + (48,)
    for got in (brumby.head_projection(x, w[2]),
                jax.jit(brumby.head_projection)(x, w[2]),
                jax.lax.scan(lambda c, wl: (c, brumby.head_projection(x, wl)),
                             0, w)[1][2]):
        assert got.dtype == dtype
        assert np.array_equal(np.asarray(got, np.float32), want)


def test_models_forward_equals_the_reference(model32):
    cfg, params, flat = model32
    ids = np.random.default_rng(3).integers(0, 97, 50)
    got = np.asarray(brumby.forward(cfg, params, jnp.asarray(ids)[None]))[0]
    want = reference_logits(flat, ids)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_equals_the_reference(dtype, tol):
    """41 prompt tokens are two whole chunks of 16 and one of 8, then
    every decode step reads and advances the state."""
    cfg, params, flat = tiny(dtype)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 97, 53).astype(np.int32)
    want = reference_logits(flat, ids)
    engine.start_request(1, ids[:42], 12)
    for t in range(41, 52):
        got = np.asarray(engine.decode_once(), np.float32)[1]
        assert np.abs(got - want[t]).max() < tol * np.abs(want[t]).max(), t
        # teacher-forced: the next token is the sequence's, not the argmax
        engine._state["cur_token"] = \
            engine._state["cur_token"].at[1].set(int(ids[t + 1]))


def test_a_reused_slot_starts_from_zero_state(model32):
    cfg, params, flat = model32
    rng = np.random.default_rng(5)
    first, second = rng.integers(0, 97, 40), rng.integers(0, 97, 23)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    engine.start_request(0, first, 8)
    engine.decode_block(8)
    assert not engine.fetch_state()["active"][0]
    engine.cache.free(0)
    engine.start_request(0, second, 8)
    reused = np.asarray(engine.decode_once())[0]
    fresh_engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    fresh_engine.start_request(0, second, 8)
    assert np.array_equal(reused, np.asarray(fresh_engine.decode_once())[0])
    # a one-token prompt runs no prefill chunk: decode resets at pos 0
    engine.cache.free(0)
    engine.start_request(0, second[:1], 8)
    got = np.asarray(engine.decode_once())[0]
    want = reference_logits(flat, second[:1])[0]
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_requests_do_not_depend_on_their_neighbours(model32):
    """Five requests over three slots, joining and leaving: each one's
    tokens are those it gets when served alone, and the logits behind
    its first token are the reference's."""
    cfg, params, flat = model32
    rng = np.random.default_rng(6)
    lengths = [(30, 12), (5, 4), (47, 9), (17, 12), (1, 6)]
    make = lambda: [Request(rid=i, tokens=rng_tokens[i], max_new_tokens=m,
                            arrival_time=0.0)
                    for i, (_, m) in enumerate(lengths)]
    rng_tokens = [rng.integers(0, 97, n) for n, _ in lengths]
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    together = {r.rid: r.out_tokens for r in ServingLoop(engine).serve(make())}
    assert engine.cache.slots() == [] and \
        engine.cache.occupancy()["state_slots_free"] == 3
    for req in make():
        alone = InferenceEngine(cfg, params, {"inference": BLOCK})
        out, = ServingLoop(alone).serve([req])
        assert np.array_equal(out.out_tokens, together[req.rid]), req.rid
        want = reference_logits(flat, req.tokens)[-1]
        assert int(np.argmax(want)) == int(out.out_tokens[0]) or \
            np.sort(want)[-1] - np.sort(want)[-2] < 1e-4


def test_speculative_decoding_is_refused(model32):
    cfg, params, _ = model32
    with pytest.raises(ValueError, match="snapshots of state do not exist"):
        InferenceEngine(cfg, params, {"inference": dict(
            BLOCK, speculative={"enabled": True})})
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    engine.cache.admit(0, 20)
    with pytest.raises(NotImplementedError, match="snapshots of state"):
        engine.cache.rollback(0, 4)
    with pytest.raises(ValueError, match="no int8 path"):
        InferenceEngine(cfg, params, {"inference": dict(BLOCK,
                                                        weight_bits=8)})


def test_state_cache_admits_by_slot_and_keeps_the_ledger_whole(model32):
    cfg, params, _ = model32
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK, "monitor": {"enabled": False}})
    cache, ledger = engine.cache, engine.monitor.ledger
    per_slot = 3 * 2 * 36 * (8 + 1) * 4          # layers, heads, D, d + 1
    assert cache.kind == "recurrent" and cache.slot_state_bytes == per_slot
    assert cache.state_shapes() == ((3, 3, 2, 36, 8), (3, 3, 2, 36))
    assert engine._state["state_s"].shape == (3, 3, 2, 36, 8)
    assert cache.never_fits(129) and cache.never_fits(128) is None
    for slot in range(3):
        assert cache.can_admit(100)
        cache.admit(slot, 100, name=f"r{slot}")
        cache.ensure(slot, 100)                  # nothing grows
    assert not cache.can_admit(10)
    with pytest.raises(RuntimeError, match="exceeds the admission"):
        cache.ensure(0, 101)
    rows = ledger.category_breakdown("recurrent_state")
    assert sum(rows.values()) == cache.pool_bytes == 3 * per_slot
    assert rows["slots.unheld"] == 0
    cache.free(1)
    assert cache.occupancy() == {"state_slots_in_use": 2,
                                 "state_slots_free": 1,
                                 "state_bytes_resident": 2 * per_slot}
    rows = ledger.category_breakdown("recurrent_state")
    assert sum(rows.values()) == cache.pool_bytes and \
        rows["slots.unheld"] == per_slot


def test_fence_rows_report_the_state_where_pages_were(model32, tmp_path):
    cfg, params, _ = model32
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK,
        "monitor": {"enabled": True, "output_path": str(tmp_path),
                    "sinks": ["jsonl"]}})
    events = []
    real = engine.monitor.event
    engine.monitor.event = lambda name, **kw: (events.append((name, kw)),
                                               real(name, **kw))[1]
    rng = np.random.default_rng(7)
    ServingLoop(engine).serve([Request(rid=0, tokens=rng.integers(0, 97, 20),
                                       max_new_tokens=6)])
    by_name = {}
    for name, kw in events:
        by_name.setdefault(name, []).append(kw)
    assert by_name["request_admitted"][0]["state_bytes_reserved"] == \
        engine.cache.slot_state_bytes
    for name in ("decode_batch", "serving_slo"):
        row = by_name[name][0]
        assert row["state_slots_in_use"] == 1 and \
            row["state_bytes_resident"] == engine.cache.slot_state_bytes
        assert "kv_pages_in_use" not in row
    assert engine.tracker.snapshot()["state_slots_free"] == 3


def test_fence_rows_count_the_slots_streamed_and_advanced(model32, tmp_path):
    """Every decode launch streams every slot's state; the launches in
    which a slot was live advanced a request (one token each). Both on
    every `decode_batch` / `serving_slo` row, host arithmetic at the
    fence."""
    cfg, params, _ = model32
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK,
        "monitor": {"enabled": True, "output_path": str(tmp_path),
                    "sinks": ["jsonl"]}})
    rows = {"decode_batch": [], "serving_slo": []}
    real = engine.monitor.event
    engine.monitor.event = lambda name, **kw: (
        rows[name].append(kw) if name in rows else None, real(name, **kw))[1]
    rng = np.random.default_rng(9)
    served = ServingLoop(engine).serve([
        Request(rid=i, tokens=rng.integers(0, 97, n), max_new_tokens=m)
        for i, (n, m) in enumerate([(20, 6), (3, 5)])])
    assert len(rows["decode_batch"]) == len(rows["serving_slo"]) > 2
    for batch, slo in zip(rows["decode_batch"], rows["serving_slo"]):
        assert batch["state_slots_streamed"] == \
            batch["iterations"] * BLOCK["max_slots"]
        assert batch["state_slots_advanced"] == batch["window_tokens"] <= \
            batch["state_slots_streamed"]
        assert (slo["state_slots_streamed"], slo["state_slots_advanced"]) \
            == (batch["state_slots_streamed"], batch["state_slots_advanced"])
    assert sum(r["state_slots_advanced"] for r in rows["decode_batch"]) == \
        sum(len(r.out_tokens) for r in served) == 11
    # the prefill launches between two fences: their rows, padding
    # and all, and the prompt tokens among them (19 + 2 are prefilled)
    chunk = BLOCK["prefill_chunk"]
    prefilled = [(r["state_prefill_rows_streamed"], r["state_prefill_tokens"])
                 for r in rows["decode_batch"]]
    assert all(rows_ % chunk == 0 and 0 <= tokens <= rows_
               for rows_, tokens in prefilled)
    assert sum(tokens for _, tokens in prefilled) == 19 + 2
    assert sum(rows_ for rows_, _ in prefilled) == \
        (-(-19 // chunk) + 1) * chunk
    assert engine.cache.attended(None, None, 4, 7, 64, 40) == {
        "state_slots_streamed": 12, "state_slots_advanced": 7,
        "state_prefill_rows_streamed": 64, "state_prefill_tokens": 40}


def test_programs_carry_the_state_and_name_their_regions(model32):
    """Both state arrays are carry of the layer scan in both programs
    (nothing state-shaped is an xs or a ys), and every region of
    `SCOPES_RECURRENT` that a program has is in its name stacks."""
    from deepspeed_tpu.monitor import programs
    from tests.paged_oracle import pools_in_scans, traced_programs
    cfg, params, _ = model32
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    shapes = {engine._state["state_s"].shape, engine._state["state_z"].shape}
    for program in ("decode_fn", "prefill_fn"):
        carried, elsewhere = pools_in_scans(jaxprs[program], shapes)
        assert carried == 2 and not elsewhere, (program, elsewhere)
    want = {"jit_decode_fn": set(engine_mod.SCOPES_RECURRENT) -
            {"state_reset", "retention_chunk"},
            "jit_prefill_fn": {"embed", "layers", "attn_qkv", "state_reset",
                               "retention_chunk", "attn_out", "mlp"}}
    for program, regions in want.items():
        stacks = programs.op_scopes(program).values()
        named = {p for s in stacks for p in s.split("/")
                 if p in engine_mod.SCOPES_RECURRENT}
        assert named == regions, (program, named ^ regions)


# ----------------------------------------------------------------------
# GPT-2 through the changed scan_layers
# ----------------------------------------------------------------------
def test_gpt2_decode_through_scan_layers_equals_the_paged_oracle():
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from tests.paged_oracle import assert_pools_equal, oracle_forward
    cfg = gpt2_config("gpt2-tiny", n_layer=3, dropout=0.0, dtype=jnp.float32)
    ids = np.random.default_rng(8).integers(0, cfg.vocab_size, (1, 40))
    params = GPT2ForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": ids})
    engine = InferenceEngine(cfg, params, {"inference": {
        "max_slots": 2, "prefill_chunk": 16, "sync_every": 2,
        "max_new_tokens": 8, "max_seq_len": 64,
        "kv_cache": {"num_pages": 9, "page_size": 16}}})
    engine.start_request(1, ids[0], 8)
    st = {k: np.asarray(v) for k, v in engine._state.items()
          if k in ("k_pool", "v_pool", "tables", "pos", "cur_token",
                   "active")}
    got = np.asarray(engine.decode_once())
    want, k_ref, v_ref = oracle_forward(
        cfg, engine._params, st["cur_token"][:, None], st["pos"][:, None],
        st["active"][:, None], st["pos"], st["tables"], st["k_pool"],
        st["v_pool"], 16, 64)
    assert np.array_equal(got[1], want[1, 0])
    assert_pools_equal(engine._state, ("k_pool", "v_pool"), (k_ref, v_ref),
                       "decode")
