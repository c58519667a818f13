"""Trinity on the serving path (ISSUE 35): the module's own `forward`,
and `InferenceEngine` / `ServingLoop` through BOTH page tables (the
full layer's whole history, the window layers' ring) and the dropless
expert layer, against the plain reference's one full forward
(`benchmark/reference/afmoe.py`: all-pairs attention under the band
mask, a loop over the experts); the expert layer told which experts it
holds; the grouped product's two forms.

The sizes are tiny and the contexts are not: a window of 12 tokens
over pages of 4 and contexts past 50, so that every request releases
pages and its ring (8 columns) wraps.

Tolerances. float32 against float32 differs by rounding in another
order only (the grouped product sums an expert's rows as the loop over
experts does, attention over pages as all-pairs does): 2e-5 of the
largest logit holds with room (seen: 2e-6). bfloat16 rounds every
activation to 8 bits through 5 layers, and a pick that flips on that
rounding moves a logit by a whole expert's share, a quarter of the
routed sum at 4 picks of 16: seen 0.016 to 0.115 over the steps, so
0.2; a key missed or a page read from the wrong column reads 0.5 and
more, and the float32 case is what pins the mathematics.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_afmoe
from benchmark.reference import afmoe as ref
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.models import trinity
from deepspeed_tpu.moe import serving as moe

f32 = jnp.float32
S, F = trinity.SLIDING, trinity.FULL
SIZES = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_dense_layers": 1, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 97,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "sliding_window": 12, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1, "route_scale": 2.826,
    "mup_enabled": True, "layer_types": [S, S, S, F] * 2,
    "kept_layers": [1, 4, 5, 6, 7],
    "published": {"num_hidden_layers": 8, "num_dense_layers": 2},
    "assumed": {"initializer_range": 0.02},
}
BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
         "max_new_tokens": 16, "max_seq_len": 128,
         "kv_cache": {"num_pages": 60, "page_size": 4}}
VOCAB = SIZES["vocab_size"]


def tiny(dtype):
    """(config, the program's tree, the reference's flat dict) of the
    benchmark's seeded weights."""
    cfg = trinity.TrinityConfig(
        **{k: v for k, v in SIZES.items() if k not in (
            "layer_types", "kept_layers", "published", "assumed")},
        layer_types=tuple(SIZES["layer_types"][i]
                          for i in SIZES["kept_layers"]),
        dtype=dtype, param_dtype=dtype)
    flat = weights_afmoe.make_weights(SIZES, 2**31 + 5, dtype)
    return cfg, weights_afmoe.to_program_tree(flat), flat


@pytest.fixture(scope="module")
def model32():
    return tiny(f32)


def reference_logits(flat, ids):
    return np.asarray(ref.logits(flat, jnp.asarray(ids, jnp.int32), SIZES))


def close(got, want, tol=2e-5):
    return np.abs(np.asarray(got) - want).max() < tol * np.abs(want).max()


def test_models_forward_equals_the_reference(model32):
    cfg, params, flat = model32
    assert cfg.layer_types == (S, S, S, S, F) and cfg.cache_kind == \
        "paged+window"
    ids = np.random.default_rng(3).integers(0, VOCAB, 50)
    got = np.asarray(trinity.forward(cfg, params, jnp.asarray(ids)[None]))[0]
    assert close(got, reference_logits(flat, ids))
    # the program's own initialisation has the tree's shapes
    own = trinity.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, own) == \
        jax.tree_util.tree_map(lambda x: x.shape, params)
    # the published pattern: every fourth layer full, two dense first
    whole = trinity.TrinityConfig()
    assert whole.layer_types[:8] == (S, S, S, F) * 2 and \
        whole.layer_types.count(F) == 8 and whole.num_dense_layers == 2


def test_the_selection_bias_selects_and_is_not_in_the_weight():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 8)), f32)
    w = jnp.asarray(rng.normal(size=(8, 6)), f32)
    bias = jnp.asarray([0, 0, 10.0, 0, 0, -10.0], f32)
    picks, weights, scores = moe.route(x, w, bias, 2, 2.826)
    assert (np.asarray(picks) == 2).any(1).all()
    assert not (np.asarray(picks) == 5).any()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(picks), 1)
    np.testing.assert_allclose(
        weights, 2.826 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.826, rtol=1e-6)


@pytest.mark.parametrize("dtype, tol", [(f32, 2e-5), (jnp.bfloat16, 0.2)],
                         ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_equals_the_reference(dtype, tol):
    """42 prompt tokens are two whole launches of 16 and one of 10
    with pad rows behind it: the second and third read a ring that has
    already released pages. Then every decode step writes a K/V row
    into both pools' layers, walks the window's pages from its first
    and the full layer's from page 0, and routes its rows; the logits
    are the reference's one full forward's."""
    cfg, params, flat = tiny(dtype)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    ring = engine.cache.window.ring
    assert ring == 8 and engine.cache.kind == "paged+window"
    ids = np.random.default_rng(4).integers(0, VOCAB, 57).astype(np.int32)
    want = reference_logits(flat, ids)
    engine.start_request(1, ids[:42], 16)
    assert engine.cache.window.released_pages() > 0
    for t in range(41, 56):
        got = np.asarray(engine.decode_once(), np.float32)[1]
        assert close(got, want[t], tol), t
        # teacher-forced: the next token is the sequence's, not the argmax
        engine._state["cur_token"] = \
            engine._state["cur_token"].at[1].set(int(ids[t + 1]))
    # the last position's page lies past the ring's columns: wrapped
    assert 56 // BLOCK["kv_cache"]["page_size"] >= ring


def test_decode_blocks_release_pages_and_wrap_the_ring(model32):
    """Through `ServingLoop`: contexts to 100 tokens, over eight times
    the window; each request's tokens are those it gets when served
    alone, its first token the reference's, and the fence rows carry
    both pools' counters and the expert layer's."""
    cfg, params, flat = model32
    rng = np.random.default_rng(6)
    lengths = [(30, 16), (5, 8), (84, 16), (17, 16), (1, 6), (60, 12)]
    tokens = [rng.integers(0, VOCAB, n) for n, _ in lengths]
    make = lambda: [Request(rid=i, tokens=tokens[i], max_new_tokens=m,
                            arrival_time=0.0)
                    for i, (_, m) in enumerate(lengths)]
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    # the loop's fence rows, through a sink of the caller's own on a
    # monitor that the config left off
    rows = []
    engine.monitor.attach_sink(types.SimpleNamespace(emit=rows.append))
    together = {r.rid: r.out_tokens
                for r in ServingLoop(engine).serve(make())}
    assert not engine.monitor.enabled and {r["kind"] for r in rows} == {
        "request_admitted", "decode_batch", "request_finished"}
    rows = [r for r in rows if r["kind"] == "decode_batch"]
    assert all(a["loop_s"] <= b["loop_s"] for a, b in zip(rows, rows[1:]))
    assert engine.cache.slots() == [] and engine.cache.occupancy() == {
        "kv_pages_full_in_use": 0, "kv_pages_window_in_use": 0,
        "kv_pages_window_released": 0, "kv_pages_free": 59}
    assert max(r["kv_pages_window_released"] for r in rows) >= 15
    assert all(r["kv_pages_window_in_use"] <= 3 * engine.cache.window.ring
               for r in rows)
    busiest = max(rows, key=lambda r: r["kv_pages_full_in_use"])
    assert busiest["kv_pages_window_in_use"] + \
        busiest["kv_pages_window_released"] == \
        busiest["kv_pages_full_in_use"]
    # every launch routes max_slots rows (a prefill launch its chunk)
    # through the four expert layers
    k, slots = SIZES["num_experts_per_tok"], BLOCK["max_slots"]
    for r in rows:
        assert r["moe_rows"] == r["iterations"] * 4 * slots * k
        assert r["prefill_moe_rows"] == \
            r["prefill_launches"] * 4 * BLOCK["prefill_chunk"] * k
        assert r["moe_rows_max_expert"] <= r["moe_rows"]
        assert 0 < r["moe_experts_touched"] <= r["iterations"] * 4 * 16 \
            or not r["iterations"]
    assert sum(r["prefill_launches"] for r in rows) == \
        sum(-(-(n - 1) // 16) for n, _ in lengths)
    alone = InferenceEngine(cfg, params, {"inference": BLOCK})
    for req in make():
        alone.reset()
        out, = ServingLoop(alone).serve([req])
        assert np.array_equal(out.out_tokens, together[req.rid]), req.rid
        want = reference_logits(flat, req.tokens)[-1]
        assert int(np.argmax(want)) == int(out.out_tokens[0]) or \
            np.sort(want)[-1] - np.sort(want)[-2] < 1e-4


def test_a_reused_slot_reads_nothing_of_its_last_request(model32):
    cfg, params, flat = model32
    rng = np.random.default_rng(5)
    first, second = rng.integers(0, VOCAB, 70), rng.integers(0, VOCAB, 23)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    engine.start_request(0, first, 8)
    engine.decode_block(8)
    assert not engine.fetch_state()["active"][0]
    engine.cache.free(0)
    engine.start_request(0, second, 8)
    got = np.asarray(engine.decode_once())[0]
    assert close(got, reference_logits(flat, second)[-1])


def test_the_decode_program_gives_out_the_picks_of_its_last_launch(model32):
    """`ROW_READINGS`: beside `decode_once`'s logits, the experts that
    the same launch's rows picked in every layer (a dense layer: -1),
    equal to the reference's for the row."""
    cfg, params, flat = model32
    prompt = np.random.default_rng(6).integers(0, VOCAB, 41)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    assert engine.last_row_readings() == {}
    engine.start_request(1, prompt, 8)
    logits = np.asarray(engine.decode_once())[1]
    assert close(logits, reference_logits(flat, prompt)[-1])
    picks = np.asarray(engine.last_row_readings()["moe_picks"])
    k, dense = SIZES["num_experts_per_tok"], SIZES["num_dense_layers"]
    assert picks.shape == (SIZES["num_hidden_layers"], BLOCK["max_slots"], k)
    assert (picks[:dense] == -1).all() and (picks[dense:] >= 0).all()
    want = np.asarray(ref.router_picks(
        flat, jnp.asarray(prompt, jnp.int32), len(prompt) - 1, SIZES))
    assert [set(row) for row in picks[dense:, 1]] == \
        [set(row) for row in want]
    # a block of launches leaves the last one's
    engine.decode_block(3)
    later = np.asarray(engine.last_row_readings()["moe_picks"])
    assert later.shape == picks.shape and (later[dense:] >= 0).all()


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------
def one_layer(flat, i=1):
    """(layer i's own leaves, every expert layer's routed experts)."""
    experts = {k: flat["h." + k] for k in trinity.EXPERT_LEAVES}
    lp = {k[2:]: v[i] for k, v in flat.items()
          if k[:2] == "h." and k[2:] not in experts}
    return lp, experts


def reference_layer(lp, experts, i, m):
    with jax.default_matmul_precision("highest"):
        _, weights = ref.route(m, lp["router"], lp["expert_bias"], SIZES)
        shared = ref._gated(m, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"], lambda y: y)
        return np.asarray(shared + ref.experts(
            m, dict(experts, layer=i), weights, None))


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(model32):
    """Section 4 of the model-configs guide: four shares of four
    experts each route over all sixteen and compute their own experts'
    part; the share that holds expert 0 adds the shared expert. The
    parts add up to what the uncut reference gives for the layer, and
    the counters to the whole layer's."""
    _, _, flat = model32
    lp, experts = one_layer(flat)
    m = jnp.asarray(np.random.default_rng(1).normal(size=(23, 64)), f32)
    want = reference_layer(lp, experts, 1, m)
    layer = functools.partial(moe.expert_layer, m, lp, layer=1, top_k=4,
                              route_scale=2.826)
    whole, counts, picks = layer(experts)
    assert close(whole, want)
    assert int(counts[1]) == 23 * 4 and 0 < int(counts[0]) <= 16
    parts, touched, rows = [], 0, 0
    for first in range(0, 16, 4):
        held = {k: v[:, first:first + 4] for k, v in experts.items()}
        part, c, routed = layer(held, first_expert=first)
        assert np.array_equal(routed, picks)   # every share routes alike
        parts.append(np.asarray(part))
        touched, rows = touched + int(c[0]), rows + int(c[1])
        assert int(c[2]) <= int(counts[2])
    assert close(sum(parts), want)
    assert (touched, rows) == (int(counts[0]), int(counts[1]))
    # a share's part is not the layer: what the others hold is missing
    assert not close(parts[1], want, 1e-2)
    # the shared expert is counted once: the other shares' parts at a
    # token that routed nothing to them are exactly zero
    assert np.array_equal(picks, moe.route(
        m, lp["router"], lp["expert_bias"], 4, 2.826)[0])
    picks = np.asarray(picks)
    idle = ~((picks >= 4) & (picks < 8)).any(1)
    assert idle.any() and not parts[1][idle].any()


@pytest.mark.parametrize("first, held", [(0, 16), (4, 8)])
def test_the_grouped_product_as_a_kernel_equals_the_ragged_product(
        first, held):
    """`megablox.gmm` in the Pallas interpreter, given every layer's
    experts as one run of groups, against `lax.ragged_dot` on the
    layer sliced out."""
    rng = np.random.default_rng(2)
    sizes = jnp.asarray(rng.multinomial(70, np.ones(16) / 16), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(70, 128)), f32)
    weights = jnp.asarray(rng.normal(size=(3, held, 128, 256)), f32)
    product = functools.partial(moe.grouped_product, rows, weights, 2, sizes,
                                first)
    want = product(use_gmm=False)
    got = product(use_gmm=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # rows of experts not held come out zero
    mine = np.repeat(np.arange(16), np.asarray(sizes))
    outside = (mine < first) | (mine >= first + held)
    assert not np.asarray(want)[outside].any()
    assert np.asarray(want)[~outside].any()


def test_the_grouped_product_lowers_for_the_chip_at_the_published_sizes():
    """`jax.export` for a TPU applies Pallas's TPU rules (block shapes)
    that the interpreter never checks: 96 slots x 8 picks over 128
    experts of [2048, 1024], two layers held."""
    rows = jax.ShapeDtypeStruct((768, 2048), jnp.bfloat16)
    weights = jax.ShapeDtypeStruct((2, 128, 2048, 1024), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((128,), jnp.int32)
    fn = jax.jit(lambda r, w, s: moe.grouped_product(r, w, 1, s,
                                                     use_gmm=True))
    exported = jax.export.export(fn, platforms=["tpu"])(rows, weights, sizes)
    assert "tpu_custom_call" in exported.mlir_module()


# ----------------------------------------------------------------------
# what the engine refuses, holds and names
# ----------------------------------------------------------------------
def test_speculation_and_int8_weights_are_refused_with_the_reason(model32):
    cfg, params, _ = model32
    with pytest.raises(ValueError, match="this model's slots\\s+hold two"):
        InferenceEngine(cfg, params, {"inference": dict(
            BLOCK, speculative={"enabled": True})})
    with pytest.raises(ValueError, match="no int8 path"):
        InferenceEngine(cfg, params, {"inference": dict(BLOCK,
                                                        weight_bits=8)})
    import dataclasses
    with pytest.raises(ValueError, match="window AND full layers"):
        InferenceEngine(dataclasses.replace(cfg, layer_types=(S,) * 5),
                        params, {"inference": BLOCK})


def test_programs_carry_both_pools_and_name_their_regions(model32):
    """Both pools' arrays are carry of the layer scans in both
    programs, and every region of `SCOPES_PAGED_MOE` that a program
    has is in its name stacks."""
    from deepspeed_tpu.monitor import programs
    from tests.paged_oracle import pools_in_scans, traced_programs
    cfg, params, _ = model32
    with traced_programs() as jaxprs:
        engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    assert engine.serving.cache_keys == (
        "k_pool", "v_pool", "k_window", "v_window", "model_counts")
    # the full layer's pool, and the window layers' four
    assert engine._state["k_pool"].shape == (1, 60, 4, 128) and \
        engine._state["k_window"].shape == (4, 3 * 8 + 1, 4, 128) and \
        engine._state["window_tables"].shape == (3, 8)
    shapes = {engine._state[k].shape for k in engine.serving.cache_keys[:4]}
    for program in ("decode_fn", "prefill_fn"):
        carried, elsewhere = pools_in_scans(jaxprs[program], shapes)
        # two scans (the dense layer, the expert layers) carry four each
        assert carried == 8 and not elsewhere, (program, elsewhere)
    vocabulary = set(engine_mod.SCOPES_PAGED_MOE)
    assert set(engine_mod.SCOPES_MOE) < vocabulary
    want = {"jit_decode_fn": vocabulary - {"kv_gather"},
            "jit_prefill_fn": vocabulary - {"head", "sample",
                                            "bookkeeping"}}
    for program, regions in want.items():
        stacks = programs.op_scopes(program).values()
        named = {p for s in stacks for p in s.split("/") if p in vocabulary}
        assert named == regions, (program, named ^ regions)
