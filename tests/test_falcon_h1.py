"""Falcon-H1 on the serving path (ISSUE 31): the Mamba-2 operations
against the plain reference's recurrence, `InferenceEngine` and
`ServingLoop` through BOTH kinds of slot state (K/V pages with
grouped-query heads, and the state-space mixer's state) against the
model's own `forward` and against the plain reference's one full
forward, and the manager of both.

Tolerances. float32 against float32 differs by rounding in another
order only: the chunked form sums the recurrence's products grouped by
chunk. 2e-5 of the largest value holds with room (seen: 1e-6); the
acceptance criterion is 1e-4. bfloat16 compute rounds every activation
to 8 bits through 3 layers: 4e-2 of the largest logit, and the tight
float32 case is what pins the mathematics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_falcon_h1
from benchmark.reference import falcon_h1 as ref
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.models import falcon_h1
from deepspeed_tpu.ops.ssm import (causal_conv, split_xbc, ssd_chunked,
                                   ssm_step)

f32 = jnp.float32
T, NH, P, G, N = 37, 6, 8, 2, 16


@pytest.fixture(scope="module")
def tokens():
    """One sequence's inputs of the scan: steps from a hundredth to
    one, so that some heads remember the whole sequence and others a
    token."""
    rng = np.random.default_rng(0)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), f32)
    xs, B, C = arr(T, NH, P), arr(T, G, N), arr(T, G, N)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-2), 0.0, (T, NH))), f32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, NH), f32)
    D = arr(NH)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.ssm_recurrence(xs, dt, A, B, C, D))
    return xs, dt, A, B, C, D, want


def close(got, want, tol=2e-5):
    return np.abs(np.asarray(got) - want).max() < tol * np.abs(want).max()


@pytest.mark.parametrize("chunk", [8, 16, 37, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_equals_the_recurrence(tokens, chunk):
    """Chunk sizes that divide the length, that do not, the length
    itself and one past it; the state handed on does not depend on
    how the sequence was cut."""
    xs, dt, A, B, C, D, want = tokens
    zero = jnp.zeros((NH, P, N), f32)
    y, H = ssd_chunked(xs, dt, A, B, C, D, zero, chunk=chunk)
    assert close(y, want)
    _, H1 = ssd_chunked(xs, dt, A, B, C, D, zero, chunk=T)
    assert np.allclose(H, H1, atol=1e-5 * np.abs(H1).max())


def test_step_iterated_equals_the_recurrence_and_the_chunked_state(tokens):
    """`ssm_step` token by token on layer 1 of a three-layer array
    gives the recurrence's outputs and leaves the chunked form's
    state; the other layers are untouched."""
    xs, dt, A, B, C, D, want = tokens
    H = jnp.ones((3, 1, NH, P, N), f32)
    no, yes = jnp.zeros((1,), bool), jnp.ones((1,), bool)
    step = jax.jit(ssm_step)
    for t in range(T):
        y, H = step(xs[t][None], dt[t][None], A, B[t][None], C[t][None], D,
                    H, 1, no, yes if t == 0 else no)
        assert close(y[0], want[t]), t
    _, H1 = ssd_chunked(xs, dt, A, B, C, D, jnp.zeros((NH, P, N), f32),
                        chunk=8)
    assert np.allclose(H[1, 0], H1, atol=1e-5 * np.abs(H1).max())
    assert np.array_equal(H[0], np.ones_like(H[0])) and \
        np.array_equal(H[2], np.ones_like(H[2]))


def test_a_chunk_boundary_inside_a_launch_and_pad_rows(tokens):
    """Two launches of 24 rows over the 37 tokens (the second with 11
    pad rows of garbage behind its 13 tokens), chunks of 8 inside
    each: the state is carried across the boundary inside a launch
    and between launches, and pad rows leave it alone."""
    xs, dt, A, B, C, D, want = tokens
    rng = np.random.default_rng(1)
    H = jnp.zeros((NH, P, N), f32)
    got = []
    for start in (0, 24):
        n = min(24, T - start)
        pad = lambda a: jnp.concatenate([a[start:start + n], jnp.asarray(
            9.0 * rng.normal(size=(24 - n,) + a.shape[1:]), f32)])
        y, H = ssd_chunked(pad(xs), jnp.abs(pad(dt)), A, pad(B), pad(C), D,
                           H, valid=jnp.arange(24) < n, chunk=8)
        got.append(np.asarray(y[:n]))
    assert close(np.concatenate(got), want)
    _, H1 = ssd_chunked(xs, dt, A, B, C, D, jnp.zeros((NH, P, N), f32),
                        chunk=T)
    assert np.allclose(H, H1, atol=1e-5 * np.abs(H1).max())


def test_idle_slots_keep_their_state_and_fresh_slots_start_from_zero(tokens):
    xs, dt, A, B, C, D, _ = tokens
    rng = np.random.default_rng(2)
    H = jnp.asarray(rng.normal(size=(2, 3, NH, P, N)), f32)
    rows = lambda a: jnp.stack([a[0], a[1], a[2]])
    keep = jnp.asarray([False, True, False])
    fresh = jnp.asarray([False, False, True])
    y, H1 = ssm_step(rows(xs), rows(dt), A, rows(B), rows(C), D, H, 0, keep,
                     fresh)
    assert np.array_equal(H1[0, 1], H[0, 1]) and np.array_equal(H1[1], H[1])
    # slot 2 from zero: its state is the one token's outer product
    of_head = np.arange(NH) // (NH // G)
    one = np.asarray(dt[2])[:, None, None] * np.asarray(xs[2])[:, :, None] * \
        np.asarray(B[2])[of_head][:, None, :]
    assert np.allclose(H1[0, 2], one, atol=1e-6)
    # slot 0 from what it held
    a = np.exp(np.asarray(dt[0]) * np.asarray(A))[:, None, None]
    assert np.allclose(H1[0, 0], a * np.asarray(H[0, 0]) + (
        np.asarray(dt[0])[:, None, None] * np.asarray(xs[0])[:, :, None] *
        np.asarray(B[0])[of_head][:, None, :]), atol=1e-5)
    # a state kept in a lower type is read as float32 and written
    # back in its own
    _, low = ssm_step(rows(xs), rows(dt), A, rows(B), rows(C), D,
                      H.astype(jnp.bfloat16), 0, keep, fresh)
    assert low.dtype == jnp.bfloat16


def test_conv_rows_are_carried_over_a_boundary_and_past_pad_rows():
    rng = np.random.default_rng(3)
    c, k = 10, 4
    x = jnp.asarray(rng.normal(size=(29, c)), f32)
    w = jnp.asarray(rng.normal(size=(c, k)), f32)
    b = jnp.asarray(rng.normal(size=(c,)), f32)
    want = np.asarray(jax.nn.silu(ref.causal_conv(x, w, b)))
    whole, last = causal_conv(x, w, b, jnp.zeros((k - 1, c), f32))
    assert np.allclose(whole, want, atol=1e-6)
    assert np.array_equal(last, x[-3:])
    # three calls: 16 rows, 16 rows of which 9 are tokens, then the
    # last 4 one at a time as decode takes them
    rows = jnp.zeros((k - 1, c), f32)
    got = []
    first, rows = causal_conv(x[:16], w, b, rows, 16)
    padded = jnp.concatenate([x[16:25], 7.0 * jnp.ones((7, c), f32)])
    second, rows = causal_conv(padded, w, b, rows, 9)
    assert np.array_equal(rows, x[22:25])
    got += [first, second[:9]]
    for t in range(25, 29):
        y, rows = causal_conv(x[t][None], w, b, rows)
        got.append(y)
    assert np.allclose(np.concatenate(got), want, atol=1e-6)
    # stale rows (a boundary that did not carry) are seen
    stale, _ = causal_conv(x[16:25], w, b, jnp.zeros((k - 1, c), f32))
    assert np.abs(np.asarray(stale[:3]) - want[16:19]).max() > 1e-2


def test_split_reads_the_groups_off_the_width():
    x = jnp.arange(2 * (NH * P + 2 * G * N), dtype=f32).reshape(2, -1)
    xs, B, C = split_xbc(x, NH, P, N)
    assert (xs.shape, B.shape, C.shape) == ((2, NH, P), (2, G, N), (2, G, N))
    assert np.array_equal(jnp.concatenate(
        [xs.reshape(2, -1), B.reshape(2, -1), C.reshape(2, -1)], -1), x)


# ----------------------------------------------------------------------
# the model through InferenceEngine and ServingLoop
# ----------------------------------------------------------------------
SIZES = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 97, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000, "mamba_d_ssm": 64, "mamba_n_heads": 8,
    "mamba_d_head": 8, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "assumed": {"initializer_range": 0.02, "ssm_state_dtype": "float32"},
}
BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
         "max_new_tokens": 12, "max_seq_len": 128,
         "kv_cache": {"num_pages": 40, "page_size": 4}}


def tiny(dtype):
    """(config, the program's tree, the reference's flat dict) of the
    benchmark's seeded weights (the published multipliers divided out
    of the spreads, so that both branches reach the residual)."""
    keys = [k for k in SIZES if k not in (
        "assumed", "ssm_multipliers", "mlp_multipliers")]
    cfg = falcon_h1.FalconH1Config(
        **{k: SIZES[k] for k in keys},
        ssm_multipliers=tuple(SIZES["ssm_multipliers"]),
        mlp_multipliers=tuple(SIZES["mlp_multipliers"]),
        dtype=dtype, param_dtype=dtype)
    flat = weights_falcon_h1.make_weights(SIZES, 2**31 + 5, dtype)
    return cfg, weights_falcon_h1.to_program_tree(flat), flat


@pytest.fixture(scope="module")
def model32():
    return tiny(f32)


def reference_logits(flat, ids):
    return np.asarray(ref.logits(flat, jnp.asarray(ids, jnp.int32), SIZES))


def test_models_forward_equals_the_reference(model32):
    cfg, params, flat = model32
    ids = np.random.default_rng(3).integers(0, 97, 50)
    got = np.asarray(falcon_h1.forward(cfg, params,
                                       jnp.asarray(ids)[None]))[0]
    assert close(got, reference_logits(flat, ids))
    # the program's own initialisation has the tree's shapes
    own = falcon_h1.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, own) == \
        jax.tree_util.tree_map(lambda x: x.shape, params)


@pytest.mark.parametrize("dtype, tol", [(f32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_equals_the_reference(dtype, tol):
    """41 prompt tokens are two whole launches of 16 (two chunks of 8
    each) and one of 9 with pad rows behind it, then every decode step
    writes a K/V row, walks the pages and advances the state; the
    logits are the reference's one full forward's and the model's own
    `forward`'s, and layer 0's state the reference's direct sum."""
    cfg, params, flat = tiny(dtype)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 97, 53).astype(np.int32)
    want = reference_logits(flat, ids)
    own = np.asarray(falcon_h1.forward(cfg, params, jnp.asarray(ids)[None]),
                     np.float32)[0]
    assert close(own, want, tol)
    engine.start_request(1, ids[:42], 12)
    for t in range(41, 52):
        got = np.asarray(engine.decode_once(), np.float32)[1]
        assert close(got, want[t], tol), t
        # teacher-forced: the next token is the sequence's, not the argmax
        engine._state["cur_token"] = \
            engine._state["cur_token"].at[1].set(int(ids[t + 1]))
        held = np.asarray(engine._state["ssm_state"][0, 1], np.float32)
        state = np.asarray(ref.ssm_state(flat, jnp.asarray(ids), t + 1,
                                         SIZES, 0))
        assert (np.abs(held - state).max((1, 2)) <
                25 * tol * np.abs(state).max((1, 2))).all(), t
    if dtype == f32:
        assert (np.abs(held - state).max((1, 2)) <
                1e-4 * np.abs(state).max((1, 2))).all()


def test_a_reused_slot_starts_from_zero_state_and_fresh_rows(model32):
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
    # a one-token prompt runs no prefill chunk: decode resets state and
    # convolution rows at pos 0
    engine.cache.free(0)
    engine.start_request(0, second[:1], 8)
    got = np.asarray(engine.decode_once())[0]
    assert close(got, reference_logits(flat, second[:1])[0])


def test_requests_do_not_depend_on_their_neighbours(model32):
    """Five requests over three slots, joining and leaving: each one's
    tokens are those it gets when served alone, and the logits behind
    its first token are the reference's."""
    cfg, params, flat = model32
    rng = np.random.default_rng(6)
    lengths = [(30, 12), (5, 4), (47, 9), (17, 12), (1, 6)]
    rng_tokens = [rng.integers(0, 97, n) for n, _ in lengths]
    make = lambda: [Request(rid=i, tokens=rng_tokens[i], max_new_tokens=m,
                            arrival_time=0.0)
                    for i, (_, m) in enumerate(lengths)]
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    together = {r.rid: r.out_tokens for r in ServingLoop(engine).serve(make())}
    occupancy = engine.cache.occupancy()
    assert engine.cache.slots() == [] and \
        occupancy["state_slots_free"] == 3 and \
        occupancy["kv_pages_in_use"] == 0
    for req in make():
        alone = InferenceEngine(cfg, params, {"inference": BLOCK})
        out, = ServingLoop(alone).serve([req])
        assert np.array_equal(out.out_tokens, together[req.rid]), req.rid
        want = reference_logits(flat, req.tokens)[-1]
        assert int(np.argmax(want)) == int(out.out_tokens[0]) or \
            np.sort(want)[-1] - np.sort(want)[-2] < 1e-4


def test_speculation_and_int8_weights_are_refused_with_the_reason(model32):
    cfg, params, _ = model32
    with pytest.raises(ValueError, match="snapshots of state do not exist"):
        InferenceEngine(cfg, params, {"inference": dict(
            BLOCK, speculative={"enabled": True})})
    with pytest.raises(ValueError, match="no int8 path"):
        InferenceEngine(cfg, params, {"inference": dict(BLOCK,
                                                        weight_bits=8)})
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    engine.cache.admit(0, 20)
    with pytest.raises(NotImplementedError, match="snapshots of state"):
        engine.cache.rollback(0, 4)


def test_the_manager_of_both_keeps_both_ledgers_whole(model32):
    cfg, params, _ = model32
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK, "monitor": {"enabled": False}})
    cache, ledger = engine.cache, engine.monitor.ledger
    # 3 layers x ([3, 64 + 2 * 2 * 16] rows + [8, 8, 16] state) float32
    per_slot = 3 * (3 * 128 + 8 * 8 * 16) * 4
    assert cache.kind == "paged+state"
    assert cache.state.slot_state_bytes == per_slot
    assert cache.state_shapes() == ((3, 3, 3, 128), (3, 3, 8, 8, 16))
    # the pools hold the two key/value heads: 16 lanes padded to 128
    assert cache.pool_shape(3) == (3, 40, 4, 128)
    assert engine._state["ssm_state"].shape == (3, 3, 8, 8, 16) and \
        engine._state["k_pool"].shape == (3, 40, 4, 128)
    assert cache.pool_bytes == cache.pages.pool_bytes + 3 * per_slot
    assert cache.never_fits(129) and cache.never_fits(128) is None
    for slot in range(3):
        cache.admit(slot, 40, name=f"r{slot}")
        cache.ensure(slot, 30)
    assert sum(ledger.category_breakdown("recurrent_state").values()) == \
        3 * per_slot
    assert sum(ledger.category_breakdown("kv_cache").values()) == \
        cache.pages.pool_bytes
    # 3 x 10 pages reserved of 39: the pages would take a fourth
    # request, the state has no slot for it
    assert cache.pages.can_admit(30) and not cache.can_admit(30)
    cache.free(1)
    assert cache.occupancy() == {
        "kv_pages_in_use": 16, "kv_pages_free": 23,
        "state_slots_in_use": 2, "state_slots_free": 1,
        "state_bytes_resident": 2 * per_slot}
    # a slot of state is free, the pages are not: 2 x 10 reserved,
    # 16 assigned, 23 free, 4 still promised
    assert cache.state.can_admit(80) and not cache.can_admit(80)
    with pytest.raises(RuntimeError, match="cannot admit 80 tokens"):
        cache.admit(1, 80)
    assert cache.slots() == [0, 2]
    page_row, slot = cache.slot_operand(2)
    assert slot == 2 and np.array_equal(page_row, cache.tables[2])


def test_fence_rows_carry_both_managers_counters(model32, tmp_path):
    cfg, params, _ = model32
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK,
        "monitor": {"enabled": True, "output_path": str(tmp_path),
                    "sinks": ["jsonl"]}})
    rows = {"decode_batch": [], "serving_slo": [], "request_admitted": []}
    real = engine.monitor.event
    engine.monitor.event = lambda name, **kw: (
        rows[name].append(kw) if name in rows else None, real(name, **kw))[1]
    rng = np.random.default_rng(7)
    served = ServingLoop(engine).serve([
        Request(rid=i, tokens=rng.integers(0, 97, n), max_new_tokens=m)
        for i, (n, m) in enumerate([(20, 6), (3, 5)])])
    admitted = rows["request_admitted"][0]
    assert admitted["kv_pages_reserved"] == 7 and \
        admitted["state_bytes_reserved"] == engine.cache.state.slot_state_bytes
    assert len(rows["decode_batch"]) == len(rows["serving_slo"]) > 2
    for batch, slo in zip(rows["decode_batch"], rows["serving_slo"]):
        for row in (batch, slo):
            assert {"kv_pages_in_use", "kv_pages_free", "state_slots_in_use",
                    "state_slots_free", "state_bytes_resident",
                    "kv_pages_attended", "kv_pages_attended_share",
                    "state_slots_streamed", "state_slots_advanced"} <= \
                set(row)
        assert batch["state_slots_streamed"] == \
            batch["iterations"] * BLOCK["max_slots"]
        assert batch["state_slots_advanced"] == batch["window_tokens"]
    first = rows["decode_batch"][0]
    assert first["state_slots_in_use"] == 2 and first["kv_pages_in_use"] >= 5
    assert sum(r["state_slots_advanced"] for r in rows["decode_batch"]) == \
        sum(len(r.out_tokens) for r in served) == 11
    snapshot = engine.tracker.snapshot()
    assert snapshot["state_slots_free"] == 3 and snapshot["num_pages"] == 40


def test_programs_carry_both_caches_and_name_their_regions(model32):
    """Both pools and both state arrays are carry of the layer scan in
    both programs (nothing cache-shaped is an xs or a ys), and every
    region of `SCOPES_PAGED_STATE` that a program has is in its name
    stacks."""
    from deepspeed_tpu.monitor import programs
    from tests.paged_oracle import pools_in_scans, traced_programs
    cfg, params, _ = model32
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    shapes = {engine._state[k].shape for k in engine.serving.cache_keys}
    assert engine.serving.cache_keys == ("k_pool", "v_pool", "conv_state",
                                         "ssm_state") and len(shapes) == 3
    for program in ("decode_fn", "prefill_fn"):
        carried, elsewhere = pools_in_scans(jaxprs[program], shapes)
        assert carried == 4 and not elsewhere, (program, elsewhere)
    vocabulary = set(engine_mod.SCOPES_PAGED_STATE)
    want = {"jit_decode_fn": vocabulary - {"state_reset", "ssm_chunk",
                                           "kv_gather"},
            "jit_prefill_fn": {"embed", "layers", "attn_qkv", "kv_write",
                               "kv_gather", "attn", "state_reset",
                               "ssm_conv", "ssm_chunk", "attn_out", "mlp"}}
    for program, regions in want.items():
        stacks = programs.op_scopes(program).values()
        named = {p for s in stacks for p in s.split("/") if p in vocabulary}
        assert named == regions, (program, named ^ regions)
