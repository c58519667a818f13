"""Nemotron-H (`models/nemotron_h.py`) at a tiny size on the CPU,
float32: the pattern read as runs, the model's own `forward` and the
served path (prefill in chunks, then decode through state and pages,
slots released and admitted again between) against the plain reference
`benchmark/reference/nemotron_h.py` on the benchmark's seeded weights,
logits to 1e-5 and every pick equal, for the WHOLE published pattern
and for the cell's 16 letters; the seventh kind of cache, its two
halves counted apart; the two shares of an expert layer adding up to
the uncut reference's; the two forms of an expert in
`moe/serving.py::expert_layer`."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))

import tiny_nemotron_h  # noqa: E402
from benchmark import weights_nemotron_h as weights  # noqa: E402
from benchmark.architectures import nemotron_h as arch  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from deepspeed_tpu.inference import (InferenceEngine, Request,  # noqa: E402
                                     ServingLoop)
from deepspeed_tpu.inference import engine as engine_mod  # noqa: E402
from deepspeed_tpu.inference.layered_kind import PagedOrStateKind  # noqa: E402
from deepspeed_tpu.models import nemotron_h  # noqa: E402
from deepspeed_tpu.moe import serving as moe  # noqa: E402

PATTERN = nemotron_h.PUBLISHED_PATTERN
SEED = 2**31 + 45


def sizes_of(pattern, **over):
    return dict(tiny_nemotron_h.TINY_SIZES, num_hidden_layers=len(pattern),
                hybrid_override_pattern=pattern, **over)


def built(pattern, **over):
    """(sizes, model config, flat weights, the program's tree)."""
    sizes = sizes_of(pattern, **over)
    with jax.default_matmul_precision("highest"):
        cfg, flat, tree, _ = arch.build(sizes, SEED)
    return sizes, cfg, flat, tree


BLOCK = {"inference": {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
                       "max_new_tokens": 24, "max_seq_len": 96,
                       "kv_cache": {"num_pages": 40, "page_size": 8}}}


def test_the_pattern_is_read_as_runs():
    assert nemotron_h.runs(PATTERN) == [
        ("M", 1), ("EM", 2), ("*", 1), ("EM", 3), ("*", 1), ("EM", 3),
        ("*", 1), ("EM", 3), ("*", 1), ("EM", 3), ("*", 1), ("EM", 4),
        ("*", 1), ("EM", 4), ("E", 1)]
    assert nemotron_h.runs(PATTERN[:16]) == [
        ("M", 1), ("EM", 2), ("*", 1), ("EM", 3), ("*", 1), ("EM", 1),
        ("E", 1)]
    assert nemotron_h.runs(PATTERN) == reference.runs(PATTERN)
    # every prefix spells its own letters back, whatever it ends on
    for n in range(1, len(PATTERN) + 1):
        assert "".join(unit * steps for unit, steps in
                       nemotron_h.runs(PATTERN[:n])) == PATTERN[:n]
    assert nemotron_h.runs("MM**E") == [("M", 2), ("*", 2), ("E", 1)]


def test_the_config_derives_what_the_engine_reads_from_the_pattern():
    whole = nemotron_h.NemotronHConfig()
    assert (whole.state_layers, whole.expert_layers, whole.paged_layers) == \
        (23, 23, 6) and whole.n_layer == 52
    assert whole.d_ssm == 4096 and whole.conv_dim == 6144
    assert whole.state_slot_shapes == (
        ((3, 6144), np.dtype(jnp.bfloat16)),
        ((64, 64, 128), np.dtype("float32")))
    assert whole.experts_held == 128 and whole.expert_width_stored == 1856
    cut = nemotron_h.NemotronHConfig(
        num_hidden_layers=16, hybrid_override_pattern=PATTERN[:16],
        experts_held=64, vocab_size=65536, expert_width_stored=1920)
    assert (cut.state_layers, cut.expert_layers, cut.paged_layers) == \
        (7, 7, 2)
    assert cut.cache_kind == "paged|state" and \
        cut.serving_module is nemotron_h and \
        engine_mod.KINDS["paged|state"] is PagedOrStateKind
    with pytest.raises(ValueError, match="does not spell"):
        nemotron_h.NemotronHConfig(num_hidden_layers=15)
    with pytest.raises(ValueError, match="are not among"):
        nemotron_h.NemotronHConfig(experts_held=64, first_expert=65)
    with pytest.raises(ValueError, match="one group"):
        nemotron_h.NemotronHConfig(n_group=2)


@pytest.mark.parametrize("pattern", [PATTERN, PATTERN[:16]],
                         ids=["whole", "cut"])
def test_forward_agrees_with_the_plain_reference(pattern):
    sizes, cfg, flat, tree = built(pattern)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 37), 0, 512)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: nemotron_h.forward(cfg, p, i))(tree, ids)
        want = jax.jit(jax.vmap(lambda row: reference.logits(
            flat, row, sizes)))(ids)
    assert got.shape == (2, 37, 512)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max()) + 1e-6
    assert len(tree["runs"]) == len(nemotron_h.runs(pattern))


def served_against_reference(pattern, rounds):
    """Requests through `ServingLoop` on three slots, more requests
    than slots, so that slots are released and admitted again: at the
    end of each of `rounds` spans of steps, every live slot's next
    logits and picks against the reference's on the tokens the slot
    has taken in."""
    sizes, cfg, flat, tree = built(pattern)
    with jax.default_matmul_precision("highest"):
        engine = InferenceEngine(cfg, tree, BLOCK)
        loop = ServingLoop(engine)

        @jax.jit
        def reference_of(ids):
            """(logits [96, V], picks [expert layers, 96, k]) of a
            sequence padded to 96: no row sees a later one."""
            x, picked = reference._through(flat, ids, sizes, None)
            return reference.logits_of(flat, x, sizes), jnp.stack(picked)
        rng = np.random.default_rng(7)
        for i, (n, m) in enumerate([(37, 6), (9, 20), (50, 8), (21, 12),
                                    (1, 10), (33, 24)]):
            loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                                max_new_tokens=m))
        loop._t0, loop._last_fence_t = time.monotonic(), 0.0
        compared, reused = 0, set()
        for steps in rounds:
            for _ in range(steps):
                loop.step()
            while engine.blocks_in_flight():
                engine.fetch_state()
            snap = engine.fetch_state()             # the live state
            live = {slot: req for slot, req in loop.live.items()
                    if snap["active"][slot]}
            for slot in loop.live:
                engine.ensure_decode_capacity(slot, int(snap["pos"][slot]), 1)
            engine.push_tables()
            reused |= {req.rid for req in live.values() if req.rid >= 3}
            got = np.asarray(engine.decode_once())
            picks = np.asarray(engine.last_row_readings()["moe_picks"])
            picks = picks[picks[:, 0, 0] >= 0]
            assert picks.shape[0] == cfg.expert_layers
            for slot, req in live.items():
                seq = np.concatenate([
                    req.tokens, snap["out_tokens"][slot][:snap["n_gen"][slot]]
                ]).astype(np.int32)
                assert len(seq) == snap["pos"][slot] + 1
                padded = np.zeros((96,), np.int32)
                padded[:len(seq)] = seq
                want, want_picks = (np.asarray(a) for a in
                                    reference_of(jnp.asarray(padded)))
                want, want_picks = want[len(seq) - 1], \
                    want_picks[:, len(seq) - 1]
                assert np.abs(got[slot] - want).max() < \
                    1e-5 * np.abs(want).max() + 1e-6, (req.rid, len(seq))
                assert [sorted(p) for p in picks[:, slot]] == \
                    [sorted(p) for p in want_picks], req.rid
                compared += 1
            # `decode_once` moved the live slots on: the loop's next
            # fence reads them from the live state
        return compared, reused


def test_the_served_path_agrees_with_the_reference_on_the_cut():
    """Prefill in chunks of 16 (a prompt of 50: four launches; of 1:
    none, the state fresh at its first decode step), decode through 7
    layers of state and 2 of pages, slots reused by later requests
    whose state must start from zero."""
    compared, reused = served_against_reference(PATTERN[:16],
                                                (5, 4, 4, 5, 6, 6))
    assert compared >= 6 and reused


def test_the_served_path_agrees_with_the_reference_on_the_whole_pattern():
    compared, reused = served_against_reference(PATTERN, (5, 5, 6, 8))
    assert compared >= 3 and reused


def test_state_and_pages_are_counted_apart():
    sizes, cfg, flat, tree = built(PATTERN[:16])
    engine = InferenceEngine(cfg, tree, BLOCK)
    st = engine._state
    assert engine.serving.cache_keys == (
        "k_pool", "v_pool", "conv_state", "ssm_state", "model_counts")
    assert st["k_pool"].shape == (2, 40, 8, 128)
    assert st["conv_state"].shape == (7, 3, 3, 128) and \
        st["ssm_state"].shape == (7, 3, 8, 8, 16) and \
        st["ssm_state"].dtype == jnp.float32
    cache = engine.cache
    assert cache.pages.n_layer == 2 and cache.state.n_layer == 7
    # admission reserves pages AND a slot of state, behind one manager
    cache.admit(0, 30)
    assert cache.occupancy()["state_slots_in_use"] == 1 and \
        cache.reservation(30) == {
            "kv_pages_reserved": 4,
            "state_bytes_reserved": 7 * (3 * 128 * 4 + 8 * 8 * 16 * 4)}
    cache.free(0)
    assert engine.serving.counters == moe.COUNTERS and \
        engine.serving.row_readings == ("moe_picks",)


@pytest.mark.parametrize("block, message", [
    ({"speculative": {"enabled": True, "draft_model": "truncate:1"}},
     "snapshots of state do not exist yet"),
    ({"weight_bits": 8}, "no int8 path")])
def test_speculation_and_int8_are_refused_at_construction(block, message):
    sizes, cfg, flat, tree = built(PATTERN[:16])
    with pytest.raises(ValueError, match=message):
        InferenceEngine(cfg, tree, {"inference": dict(BLOCK["inference"],
                                                      **block)})


# ----------------------------------------------------------------------
# the expert layer: a share, and the two forms
# ----------------------------------------------------------------------
def expert_inputs(form, held=8, first=0, E=8, H=16, width=12, shared=20,
                  rows=11, stored=None):
    stored = stored or width
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 12))
    draw = lambda *shape: 0.3 * jax.random.normal(next(keys), shape)
    names = ("gate", "up") if form == moe.GATED_SILU else ("up",)
    lp = {"router": draw(H, E), "expert_bias": 0.05 * draw(E),
          "shared_down": draw(shared, H)}
    lp.update({"shared_" + n: draw(H, shared) for n in names})
    full = {"w_" + n: draw(2, E, H, width) for n in names}
    full["w_down"] = draw(2, E, width, H)
    pad = stored - width
    share = {k: jnp.pad(v[:, first:first + held], (
        ((0, 0),) * 2 + ((0, pad), (0, 0)) if k == "w_down"
        else ((0, 0),) * 3 + ((0, pad),))) for k, v in full.items()}
    return draw(rows, H), lp, full, share


def by_a_loop(x, lp, full, layer, k, scale, form):
    """y = Shared(x) + sum_j w_j Expert_j(x), every expert in turn."""
    picks, w, _ = moe.route(x, lp["router"], lp["expert_bias"], k, scale)
    hidden = lambda up, gate: jnp.square(jax.nn.relu(up)) \
        if form == moe.RELU2 else jax.nn.silu(gate) * up
    one = lambda e: hidden(
        x @ full["w_up"][layer, e],
        x @ full["w_gate"][layer, e] if "w_gate" in full else None) @ \
        full["w_down"][layer, e]
    shared = hidden(x @ lp["shared_up"],
                    x @ lp["shared_gate"] if "shared_gate" in lp else None) \
        @ lp["shared_down"]
    y = shared
    for e in range(lp["router"].shape[1]):
        y = y + ((picks == e) * w).sum(-1, keepdims=True) * one(e)
    return y, picks


@pytest.mark.parametrize("form", [moe.RELU2, moe.GATED_SILU])
def test_an_experts_form_is_the_callers(form):
    """The ungated relu^2 expert (two matrices, no `w_gate` anywhere)
    and Trinity's gated SiLU (three) through the one `expert_layer`,
    each against a loop over the experts."""
    with jax.default_matmul_precision("highest"):
        x, lp, full, _ = expert_inputs(form)
        assert ("w_gate" in full) == (form == moe.GATED_SILU)
        want, want_picks = by_a_loop(x, lp, full, 1, 3, 2.5, form)
        got, counts, picks = moe.expert_layer(
            x, lp, full, 1, 3, 2.5, use_gmm=False, form=form)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert np.array_equal(picks, want_picks)
    assert counts[1] == 11 * 3
    if form == moe.GATED_SILU:
        # the default IS the gated form: Trinity's and Sarvam's calls
        again = moe.expert_layer(x, lp, full, 1, 3, 2.5, use_gmm=False)[0]
        assert np.array_equal(again, got)
        assert np.allclose(moe.gated_mlp(
            x, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),
            (jax.nn.silu(x @ lp["shared_gate"]) * (x @ lp["shared_up"])) @
            lp["shared_down"], atol=1e-6)
    with pytest.raises(ValueError, match="an expert's form"):
        moe.expert_layer(x, lp, full, 1, 3, 2.5, use_gmm=False, form="gelu")


def test_the_two_shares_add_up_to_the_whole_layer():
    """Experts 0-3 and 4-7 of 8, each stored wider than published with
    zeros (exact: relu(0)^2 = 0), each share routing over all 8: their
    parts, the shared expert counted once (the share of expert 0 adds
    it), add up to the uncut layer, by the loop and by the plain
    reference's expert layer."""
    with jax.default_matmul_precision("highest"):
        x, lp, full, _ = expert_inputs(moe.RELU2)
        whole, _ = by_a_loop(x, lp, full, 0, 3, 2.5, moe.RELU2)
        shares = {first: expert_inputs(moe.RELU2, held=4, first=first,
                                       stored=16)[3] for first in (0, 4)}
        assert shares[4]["w_up"].shape == (2, 4, 16, 16)
        mine = lambda rows, first: moe.expert_layer(
            rows, lp, shares[first], 0, 3, 2.5, first_expert=first,
            use_gmm=False, form=moe.RELU2)
        parts = [mine(x, first) for first in (0, 4)]
        assert sum(int(counts[1]) for _, counts, _ in parts) == 11 * 3
        assert float(jnp.abs(parts[0][0] + parts[1][0] - whole).max()) < 1e-5
        # the reference's layer, given the whole and given a share
        sizes = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
                 "layer_norm_epsilon": 1e-5, "moe_intermediate_size": 12}
        ref_lp = dict(lp, norm=jnp.ones((16,)), layer=0)
        uncut = reference.experts(dict(ref_lp, **full), x, sizes)[0] - x
        normed = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-5)
        both = mine(normed, 0)[0] + mine(normed, 4)[0]
        assert float(jnp.abs(both - uncut).max()) < 1e-5
        half = reference.experts(dict(ref_lp, **shares[4]), x,
                                 dict(sizes, first_expert=4))[0] - x
        assert float(jnp.abs(half - mine(normed, 4)[0]).max()) < 1e-5


def test_rows_of_no_request_go_to_no_expert():
    with jax.default_matmul_precision("highest"):
        x, lp, full, _ = expert_inputs(moe.RELU2)
        live = jnp.arange(11) % 3 != 0
        y, counts, picks = moe.expert_layer(
            x, lp, full, 0, 3, 2.5, use_gmm=False, live=live, form=moe.RELU2)
        want, _ = by_a_loop(x, lp, full, 0, 3, 2.5, moe.RELU2)
    shared = jnp.square(jax.nn.relu(x @ lp["shared_up"])) @ lp["shared_down"]
    assert counts[1] == int(live.sum()) * 3
    assert np.all(np.asarray(picks)[~np.asarray(live)] == 8)
    assert float(jnp.abs(jnp.where(live[:, None], y - want,
                                   y - shared)).max()) < 1e-5


@pytest.mark.parametrize("shape, tiling", [
    ((2048, 1024), (128, 1024, 1024)),      # Trinity's up: as before
    ((1024, 2048), (128, 1024, 1024)),      # and down
    ((4096, 2048), (128, 1024, 1024)),      # Sarvam-105B's
    ((2688, 1920), (128, 896, 640)),        # this family's, as stored
    ((1920, 2688), (128, 640, 896)),
    ((2688, 1856), (128, 896, 1856)),       # 14.5 lane tiles: one tile
])
def test_the_grouped_products_tiling_follows_the_shapes(shape, tiling):
    assert moe.gmm_tiling(*shape) == tiling


def test_the_weights_tree_is_the_flat_dicts_arrays():
    sizes, cfg, flat, tree = built(PATTERN[:16])
    assert tree["experts"]["w_up"] is flat["x.w_up"] and \
        tree["runs"][1]["E"]["router"] is flat["r01.E.router"] and \
        tree["runs"][3]["M"]["w_in"] is flat["r03.M.w_in"] and \
        tree["runs"][2]["*"]["wq"] is flat["r02.*.wq"]
    assert flat["r03.E.router"].shape == (3, 64, 16) and \
        flat["x.w_up"].shape == (7, 8, 64, 32) and \
        flat["x.w_down"].shape == (7, 8, 32, 64)
    shapes = jax.tree_util.tree_map(
        lambda x: x.shape, nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes == jax.tree_util.tree_map(lambda x: x.shape, tree)
    assert weights.bias_names(sizes) == [
        "r01.E.expert_bias", "r03.E.expert_bias", "r05.E.expert_bias",
        "r06.E.expert_bias"]
