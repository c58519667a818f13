"""Sarvam-105B on the serving path (ISSUE 39): the module's own
`forward` (the EXPANDED form), and `InferenceEngine` / `ServingLoop`
through the latent page pool (the ABSORBED form: prefill in chunks
through `latent_attention`, decode through it or through the kernel)
and the dropless expert layer told that it holds a SHARE of the
experts, against the plain reference's one full forward
(`benchmark/reference/sarvam_mla.py`: expanded keys and values,
all-pairs attention, a loop over the held experts); the decode kernel
(interpreted) against its XLA oracle; YaRN's frequencies by hand; the
four shares of a layer adding up to the uncut layer; and the other
paged models' decode programs left as they were.

Tolerances. float32 against float32 differs by rounding in another
order only (absorbed against expanded moves the products with W_kvb
from the keys to the queries): 1e-5 of the largest logit holds with
room (seen: 3e-7).
"""

import dataclasses
import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_sarvam_mla
from benchmark.reference import sarvam_mla as ref
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.models import sarvam_mla
from deepspeed_tpu.moe import serving as moe_serving
from deepspeed_tpu.ops.transformer import latent_attention as latent

f32 = jnp.float32
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "deepseek_yarn"}
SIZES = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_head_dim": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "head_dim": 40, "vocab_size": 97, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5,
    "published": {"num_hidden_layers": 8, "num_experts": 16},
    "assumed": {"initializer_range": 0.02},
}
BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 2,
         "max_new_tokens": 16, "max_seq_len": 128,
         "kv_cache": {"num_pages": 60, "page_size": 4}}
VOCAB = SIZES["vocab_size"]
SEED = 2**31 + 5


def config(sizes, dtype=f32, **over):
    names = {f.name for f in dataclasses.fields(sarvam_mla.SarvamMLAConfig)}
    settings = {k: v for k, v in sizes.items() if k in names}
    settings.update(
        rope_scaling=tuple(sorted(sizes["rope_scaling"].items())),
        num_experts=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        first_expert=sizes.get("first_expert", 0), dtype=dtype,
        param_dtype=dtype)
    return sarvam_mla.SarvamMLAConfig(**dict(settings, **over))


def tiny(held=16, first=0, dtype=f32):
    """(sizes, config, the program's tree, the reference's flat dict)
    for the share of `held` experts from `first`: the benchmark's
    seeded weights of ALL 16 experts, the share's sliced out, so that
    every share reads the same router, attention and shared expert."""
    flat = weights_sarvam_mla.make_weights(SIZES, SEED, dtype)
    flat = {k: v[:, first:first + held] if k in (
        "h.w_gate", "h.w_up", "h.w_down") else v for k, v in flat.items()}
    sizes = dict(SIZES, num_experts=held, first_expert=first)
    return (sizes, config(sizes, dtype), weights_sarvam_mla.to_program_tree(
        flat), flat)


def reference_logits(flat, ids, sizes):
    return np.asarray(ref.logits(flat, jnp.asarray(ids, jnp.int32), sizes))


def close(got, want, tol=1e-5):
    return np.abs(np.asarray(got) - want).max() < tol * np.abs(want).max()


@pytest.mark.parametrize("held", [16, 4])
def test_models_forward_equals_the_reference(held):
    sizes, cfg, params, flat = tiny(held)
    assert cfg.cache_kind == "paged+latent" and cfg.latent_row == 40 and \
        cfg.experts_held == held and cfg.num_experts == 16
    ids = np.random.default_rng(3).integers(0, VOCAB, 50)
    got = np.asarray(sarvam_mla.forward(cfg, params,
                                        jnp.asarray(ids)[None]))[0]
    assert close(got, reference_logits(flat, ids, sizes))
    own = sarvam_mla.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, own) == \
        jax.tree_util.tree_map(lambda x: x.shape, params)
    # the published config: one row of 576 for 64 heads, scale with m^2
    whole = sarvam_mla.SarvamMLAConfig()
    assert whole.latent_row == whole.head_dim == 576 and \
        whole.experts_held == 128
    assert whole.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    with pytest.raises(ValueError, match="one cached row"):
        sarvam_mla.SarvamMLAConfig(head_dim=512)
    with pytest.raises(ValueError, match="not among"):
        sarvam_mla.SarvamMLAConfig(experts_held=32, first_expert=100)


def test_yarn_frequencies_by_hand():
    """The published numbers: 32 frequencies over the 64 rotary
    values; the correction dims from beta_fast 32 and beta_slow 1 over
    4,096 positions are floor(10.47) = 10 and ceil(22.51) = 23; below
    10 the plain frequency, from 23 on that / 40, a ramp of 13 steps
    between."""
    freq = sarvam_mla.frequencies(sarvam_mla.SarvamMLAConfig())
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) / (
        2 * math.log(10000)) == pytest.approx(10.47, abs=0.01)
    assert 64 * math.log(4096 / (2 * math.pi)) / (
        2 * math.log(10000)) == pytest.approx(22.51, abs=0.01)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-12)
    # j = 16: six thirteenths of the way
    assert freq[16] == pytest.approx(
        plain[16] * (7 / 13) + plain[16] / 40 * (6 / 13), rel=1e-12)
    assert (np.diff(freq) < 0).all()
    # the reference's own (float32) are the same numbers
    sizes = dict(SIZES, qk_rope_head_dim=64, rope_scaling=dict(
        YARN, original_max_position_embeddings=4096))
    np.testing.assert_allclose(ref.yarn_frequencies(sizes), freq, rtol=1e-5)
    assert ref.softmax_scale(dict(sizes, q_head_dim=192)) == pytest.approx(
        sarvam_mla.SarvamMLAConfig().softmax_scale)


def test_absorbed_equals_expanded():
    """One layer's attention half both ways on the same rows: the
    block's absorbed form through a dense mixer over latent rows, and
    `attend_expanded`."""
    _, cfg, params, _ = tiny()
    lp = jax.tree_util.tree_map(lambda x: x[1], {
        k: v for k, v in params["layers"].items()
        if k not in sarvam_mla.EXPERT_LEAVES})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 21, 64)), f32)
    positions = jnp.broadcast_to(jnp.arange(21), (2, 21))

    def dense_mixer(q, row, cache):
        s = jnp.einsum("bthw,bsw->bhts", q, row,
                       precision=jax.lax.Precision.HIGHEST)
        seen = jnp.arange(21)[:, None] >= jnp.arange(21)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
        return jnp.einsum("bhts,bsc->bthc", p, row[..., :cfg.kv_lora_rank],
                          precision=jax.lax.Precision.HIGHEST), cache, None

    with jax.default_matmul_precision("highest"):
        absorbed, _, _ = sarvam_mla.attend(cfg, lp, x, positions, dense_mixer,
                                        None)
        expanded = sarvam_mla.attend_expanded(cfg, lp, x, positions)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-6)
    assert np.abs(np.asarray(expanded - x)).max() > 1e-3


def latent_case(lens, page=8, heads=4, width=40, rank=32, seed=0):
    rng = np.random.default_rng(seed)
    b, max_pages = len(lens), -(-max(max(lens), 1) // page) + 1
    pool = jnp.asarray(rng.normal(size=(3, b * max_pages + 1, page, 128)),
                       f32).at[..., width:].set(0)
    tables = jnp.asarray(1 + rng.permutation(b * max_pages).reshape(
        b, max_pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, heads, width)), f32)
    return q, pool, tables, jnp.asarray(lens, jnp.int32), rank


def by_hand(q, pool, li, tables, lens, rank):
    """Softmax attention of each slot's one query row over its first
    lens[b] rows, gathered in numpy."""
    q, pool, tables = (np.asarray(x, np.float64) for x in (q, pool, tables))
    out = np.zeros(q.shape[:2] + (rank,))
    for b, n in enumerate(np.asarray(lens)):
        if not n:
            continue
        rows = pool[li, tables[b].astype(int)].reshape(-1, pool.shape[-1])
        rows = rows[:n, :q.shape[-1]]
        s = q[b] @ rows.T
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ rows[:, :rank]
    return out


@pytest.mark.parametrize("lens", [
    (5,), (8,), (9,), (16, 17, 1), (0, 41, 0, 24, 7)],
    ids=["inside", "at", "past", "pages", "idle-among-live"])
def test_decode_kernel_equals_its_oracle_and_the_sum_by_hand(lens):
    """Lengths that end inside a page, at its end and one past it; an
    idle slot reads nothing and returns zeros."""
    q, pool, tables, lens, rank = latent_case(lens)
    want = by_hand(q, pool, 2, tables, lens, rank)
    kernel = latent.latent_decode_attention(q, pool, jnp.asarray(2), tables,
                                            lens, rank, interpret=True)
    oracle = latent.latent_attention(
        q[:, None], pool, 2, tables, (lens - 1)[:, None], lens, rank)[:, 0]
    np.testing.assert_allclose(kernel, want, atol=5e-6)
    np.testing.assert_allclose(oracle, want, atol=5e-6)
    idle = np.asarray(lens) == 0
    assert (np.asarray(kernel)[idle] == 0).all() and \
        (np.asarray(oracle)[idle] == 0).all()


def test_rows_past_a_slots_length_contribute_nothing():
    """What lies past a slot's length (the tail of its last page,
    pages it does not hold) may be anything, not finite either."""
    q, pool, tables, lens, rank = latent_case((13, 6))
    want = latent.latent_decode_attention(q, pool, jnp.asarray(0), tables,
                                          lens, rank, interpret=True)
    dirty = np.array(pool)
    for b, n in enumerate((13, 6)):
        rows = dirty[0, np.asarray(tables[b])].reshape(-1, 128)
        rows[n:] = np.nan
        dirty[0, np.asarray(tables[b])] = rows.reshape(-1, 8, 128)
    dirty[0, 0] = np.inf
    for form in (
            lambda p: latent.latent_decode_attention(
                q, p, jnp.asarray(0), tables, lens, rank, interpret=True),
            lambda p: latent.latent_attention(
                q[:, None], p, 0, tables, (lens - 1)[:, None], lens,
                rank)[:, 0]):
        np.testing.assert_allclose(form(jnp.asarray(dirty)), want, atol=2e-6)


def test_a_chunks_rows_see_their_own_past_only():
    """A prefill chunk through `latent_attention`: row t of the chunk
    at position start + t sees keys [0, start + t], a block of four
    pages at a time as far as the chunk's last key (three blocks
    here)."""
    _, pool, tables, _, rank = latent_case((80,))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 6, 4, 40)), f32)
    pos = jnp.arange(66, 72)[None]
    got = latent.latent_attention(q, pool, 1, tables, pos,
                                  jnp.asarray([72]), rank)
    for t in range(6):
        want = by_hand(q[:, t], pool, 1, tables, [66 + t + 1], rank)
        np.testing.assert_allclose(got[:, t], want, atol=5e-6)


def chunk_case(lens, starts, t, dtype=f32, heads=64, width=40, rank=32,
               page=128, seed=0):
    """A chunk of `t` rows a slot from position starts[b], of which the
    rows below lens[b] are the request's, over pages of 128: the
    kernel's blocks are then the cell's, 512 keys, and 64 heads make a
    query tile 16 tokens."""
    rng = np.random.default_rng(seed)
    b, max_pages = len(lens), -(-max(max(lens), 1) // page) + 1
    pool = jnp.asarray(rng.normal(size=(2, b * max_pages + 2, page, 128)),
                       f32).at[..., width:].set(0).astype(dtype)
    tables = jnp.asarray(1 + rng.permutation(b * max_pages).reshape(
        b, max_pages), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, t, heads, width)), f32).astype(dtype)
    pos = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(t)[None]
    return q, pool, tables, pos, jnp.asarray(lens, jnp.int32), rank


def requests_rows(x, pos, lens):
    """x [B, T, ...] with the rows at or past their slot's length (a
    chunk's pad rows) zeroed."""
    live = np.asarray(pos) < np.asarray(lens)[:, None]
    return np.where(live[:, :, None, None], np.asarray(x, np.float32), 0)


@pytest.mark.parametrize("dtype", [f32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lens, starts, t", [
    ((24,), (0,), 24), ((205,), (200,), 5), ((560,), (520,), 40),
    ((1348,), (1300,), 48), ((1030,), (1000,), 48), ((3,), (0,), 40),
    ((0, 700, 0, 1100), (0, 660, 0, 1060), 40)],
    ids=["from-0", "mid-page-short-of-a-tile", "past-a-block-three-tiles",
         "past-blocks", "pad-rows-across-a-block", "pad-tiles",
         "idle-among-live"])
def test_prefill_kernel_equals_the_xla_form(lens, starts, t, dtype):
    """`latent_prefill_attention` (interpreted) against
    `latent_attention` on a chunk's rows: the same online softmax over
    the same blocks of 512 keys, so float32 agrees to the last bits of
    a sum taken in another order and bfloat16 to one rounding of the
    output. A chunk's pad rows and a slot of length 0 read zeros."""
    q, pool, tables, pos, lens, rank = chunk_case(lens, starts, t, dtype)
    got = latent.latent_prefill_attention(q, pool, jnp.asarray(1), tables,
                                          pos, lens, rank, interpret=True)
    want = latent.latent_attention(q, pool, 1, tables, pos, lens, rank)
    assert got.dtype == dtype and got.shape == want.shape
    want = requests_rows(want, pos, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want,
        atol=5e-6 if dtype == f32 else 2 ** -8 * np.abs(want).max())
    assert np.abs(want).max() > 0.1


def test_the_prefill_kernel_reads_nothing_past_the_length():
    """What lies past the slot's length (the tail of its last page,
    the table's other pages) and in pages the table does not name may
    be anything, not finite either."""
    q, pool, tables, pos, lens, rank = chunk_case((1030,), (1000,), 48)
    call = lambda p: latent.latent_prefill_attention(
        q, p, jnp.asarray(0), tables, pos, lens, rank, interpret=True)
    want = call(pool)
    dirty = np.full(pool.shape, np.nan, np.float32)
    named = np.asarray(tables[0])
    rows = np.array(pool[0, named]).reshape(-1, 128)
    rows[1030:] = np.inf
    dirty[0, named] = rows.reshape(-1, 128, 128)
    np.testing.assert_array_equal(call(jnp.asarray(dirty)), want)


@pytest.mark.parametrize("start, valid", [
    (0, 512), (2048, 512), (4096, 200), (1000, 512), (8192 - 512, 512)],
    ids=["first-chunk", "aligned", "last-chunk-with-pad-rows", "unaligned",
         "longest"])
def test_a_tiles_walk_on_host_numbers(start, valid):
    """`tile_walk` by hand in the cell's geometry (pages of 128, four a
    block, 16 tokens a tile of a chunk of 512): a tile copies the pages
    up to the one that holds its last token's position (or the slot's
    last key) and no page past it, walks whole only blocks that lie
    below its first token, and a tile of pad rows alone copies
    nothing."""
    page, npb, tq, chunk = 128, latent.block_pages(84, 128), \
        latent.tile_tokens(512, 64), 512
    assert (npb, tq) == (4, 16)
    length, walked = start + valid, 0
    for first in range(start, start + chunk, tq):
        low, high = first, first + tq - 1
        extent, n_pages, n_blocks, whole = (int(x) for x in latent.tile_walk(
            low, high, length, page, npb))
        if low >= length:
            assert (extent, n_pages, n_blocks) == (0, 0, 0)
            continue
        last = min(high, length - 1)
        assert extent == last + 1 and n_pages == last // page + 1
        assert n_blocks == last // (npb * page) + 1
        # whole blocks end at or below the tile's first position
        assert whole * npb * page <= low + 1 < (whole + 1) * npb * page + 1
        assert whole <= n_blocks
        walked += n_pages * page
    # against the launch's extent in whole blocks, which the fence rows
    # count for every tile (`walked_keys`): an aligned chunk's tiles
    # walk 320 of its own 512 keys on average
    full = latent.walked_keys(length - 1, page, 84) * (chunk // tq)
    assert walked < full
    if start % 512 == 0 and valid == chunk:
        assert walked == (start + 320) * (chunk // tq)


@pytest.mark.parametrize("held", [16, 4])
def test_prefill_in_chunks_then_decode_equals_the_reference(held):
    """42 prompt tokens are two whole launches of 16 and one of 10
    with pad rows behind it; then every decode step writes a latent
    row into every layer of the pool and attends over the slot's
    pages. The logits are the reference's one full forward's and every
    pick is its pick, for the whole layer and for a share of it."""
    sizes, cfg, params, flat = tiny(held)
    engine = InferenceEngine(cfg, params, {"inference": BLOCK})
    # served as handed in: every leaf is the caller's very array, none
    # copied or re-laid at load (PERF.md section 6, PR 40: a second
    # arrangement of W_q beside the given one does not fit the
    # Sarvam-105B cell)
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(engine._params),
        jax.tree_util.tree_leaves(params)))
    assert engine.cache.kind == "paged" and \
        engine.cache.pool_shape(3) == (3, 60, 4, 128)
    ids = np.random.default_rng(4).integers(0, VOCAB, 57).astype(np.int32)
    want = reference_logits(flat, ids, sizes)
    engine.start_request(1, ids[:42], 16)
    for t in range(41, 56):
        got = np.asarray(engine.decode_once(), np.float32)[1]
        assert close(got, want[t]), t
        picks = np.asarray(engine.last_row_readings()["moe_picks"])[:, 1]
        assert (picks[0] == -1).all()                # the dense layer
        want_picks = np.asarray(ref.router_picks(
            flat, jnp.asarray(ids[:t + 1]), t, sizes))
        assert (np.sort(picks[1:], -1) == np.sort(want_picks, -1)).all(), t
        engine._state["cur_token"] = \
            engine._state["cur_token"].at[1].set(int(ids[t + 1]))
    # the pool holds the reference's latent rows, through the table
    rows = np.asarray(engine.cache_arrays()[0])[
        :, engine.cache.tables[1]].reshape(3, -1, 128)[:, :56, :40]
    want_rows = np.asarray(ref.latent_rows(flat, jnp.asarray(ids[:56]),
                                           (0, 1, 2), sizes))
    np.testing.assert_allclose(rows, want_rows, atol=1e-5)
    # the lanes past the row stay zero
    assert not np.asarray(engine.cache_arrays()[0])[..., 40:].any()


def test_decode_through_the_kernel_equals_the_xla_form(monkeypatch):
    """The engine's decode with the kernel taken (interpreted: the
    probe answers "usable", the call interprets off a TPU) gives the
    logits of the XLA form."""
    _, cfg, params, _ = tiny(4)
    ids = np.random.default_rng(5).integers(0, VOCAB, 30).astype(np.int32)

    def logits(usable):
        monkeypatch.setattr(latent, "usable", lambda: usable)
        engine = InferenceEngine(cfg, params, {"inference": BLOCK})
        engine.start_request(0, ids[:23], 8)
        engine.start_request(2, ids[:9], 8)
        return np.stack([np.asarray(engine.decode_once())
                         for _ in range(5)])

    with_kernel, without = logits(True), logits(False)
    np.testing.assert_allclose(with_kernel[:, (0, 2)], without[:, (0, 2)],
                               atol=2e-6)


def test_prefill_through_the_kernel_equals_the_xla_form(monkeypatch):
    """The engine's chunked prefill AND decode with both kernels taken
    (interpreted) against both through the XLA form: 23 prompt tokens
    are a whole launch of 16 and one of 7 with pad rows behind them,
    into pages of 4; the prefill program holds the kernel where it
    held the gathered loop."""
    from paged_oracle import equations, traced_programs
    _, cfg, params, _ = tiny(4)
    ids = np.random.default_rng(6).integers(0, VOCAB, 60).astype(np.int32)

    def logits(usable):
        monkeypatch.setattr(latent, "usable", lambda: usable)
        with traced_programs() as jaxprs:
            engine = InferenceEngine(cfg, params, {"inference": BLOCK})
        calls = [str(e.params.get("name", e.params.get("name_and_src_info")))
                 for e in equations(jaxprs["prefill_fn"])
                 if e.primitive.name == "pallas_call"]
        engine.start_request(0, ids[:23], 8)
        engine.start_request(2, ids[:58], 8)
        return calls, np.stack([np.asarray(engine.decode_once())
                                for _ in range(3)])

    (calls, with_kernel), (none, without) = logits(True), logits(False)
    assert none == [] and calls and all(
        "latent_prefill_attention" in c for c in calls)
    np.testing.assert_allclose(with_kernel[:, (0, 2)], without[:, (0, 2)],
                               atol=2e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: at a small size, the parts of an
    expert layer's result that the four shares of 4 experts give, with
    the shared expert (which every chip computes alike) counted once,
    add up to what the uncut reference gives for the whole layer: in
    the program's expert layer and in the reference's own share."""
    sizes, cfg, params, flat = tiny(16)
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(1, 23, 64)), f32)
    top, blocks = ref.split(flat, sizes)
    lp_ref, dense = blocks[2]
    assert not dense
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.feed_forward(lp_ref, a[0], sizes, False)[0]
                           - a[0])
        shared = np.asarray(ref._gated(
            ref._rms(a[0], lp_ref["norm_mlp"].astype(f32), 1e-6),
            *(lp_ref[k].astype(f32) for k in ref.SHARED), lambda y: y))
    assert np.abs(whole - shared).max() > 0.1 * np.abs(whole).max()
    parts_program, parts_reference = [], []
    for first in (0, 4, 8, 12):
        s, c, p, f = tiny(4, first)
        lp = dict(jax.tree_util.tree_map(lambda x: x[1], {
            k: v for k, v in p["layers"].items()
            if k not in sarvam_mla.EXPERT_LEAVES}),
            expert_layer=jnp.asarray(1), experts={
                k: p["layers"][k] for k in sarvam_mla.EXPERT_LEAVES})
        with jax.default_matmul_precision("highest"):
            out, counts, _ = sarvam_mla.feed_forward(c, lp, a)
            parts_program.append(np.asarray(out - a)[0])
            parts_reference.append(np.asarray(ref.feed_forward(
                ref.split(f, s)[1][2][0], a[0], s, False)[0] - a[0]))
        # a share counts its own rows only
        assert 0 < int(counts[1]) < 23 * 4
    # the share of first_expert 0 added the shared expert: once
    for parts in (parts_program, parts_reference):
        np.testing.assert_allclose(sum(parts), whole, atol=2e-6)
        assert np.abs(parts[0] - parts[1]).max() > 0.1 * np.abs(shared).max()
    np.testing.assert_allclose(parts_program[0], parts_reference[0],
                               atol=2e-6)


@pytest.mark.parametrize("held", [4, 16])
def test_rows_of_no_request_go_to_no_expert(held):
    """`feed_forward` with `live`: the live rows' results are what
    they are without it to the bit, the others' are the shared expert's
    alone (or nothing's, on a share that does not add it), and the
    counters count the live rows only: 13 identical idle rows touch no
    expert and are in no expert's rows."""
    first = 0 if held == 16 else 4
    _, cfg, params, _ = tiny(held, first)
    lp = dict(jax.tree_util.tree_map(lambda x: x[1], {
        k: v for k, v in params["layers"].items()
        if k not in sarvam_mla.EXPERT_LEAVES}),
        expert_layer=jnp.asarray(1), experts={
            k: params["layers"][k] for k in sarvam_mla.EXPERT_LEAVES})
    rng = np.random.default_rng(3)
    rows = rng.normal(size=(20, 1, 64))
    # idle slots: all one row, and one that picks an expert held here
    _, _, picks = sarvam_mla.feed_forward(cfg, lp, jnp.asarray(rows, f32))
    held_here = (np.asarray(picks) >= first) & (np.asarray(picks) <
                                                 first + held)
    rows[7:] = rows[7 + int(np.argmax(held_here[7:].any(1)))]
    a = jnp.asarray(rows, f32)
    live = jnp.asarray(np.arange(20) < 7)[:, None]
    with jax.default_matmul_precision("highest"):
        every, counts_every, picks_every = sarvam_mla.feed_forward(cfg, lp, a)
        some, counts, picks = sarvam_mla.feed_forward(cfg, lp, a, live)
        alone, counts_alone, _ = sarvam_mla.feed_forward(cfg, lp, a[:7])
    np.testing.assert_array_equal(some[:7], every[:7])
    np.testing.assert_array_equal(np.asarray(counts), counts_alone)
    np.testing.assert_array_equal(np.asarray(picks)[:7],
                                  np.asarray(picks_every)[:7])
    assert (np.asarray(picks)[7:] == cfg.num_experts).all()
    assert int(counts_every[1]) >= int(counts[1]) + 13 * (held == 16) * 4
    # an idle row's result: a + the shared expert where this share adds it
    m = sarvam_mla.rms_norm(a[7:], lp["norm_mlp"], cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        shared = moe_serving.gated_mlp(m, lp["shared_gate"], lp["shared_up"],
                                       lp["shared_down"]) * (first == 0)
    np.testing.assert_allclose(some[7:] - a[7:], shared, atol=2e-6)
    assert np.abs(np.asarray(every[7:] - some[7:])).max() > 1e-4


def test_serving_loop_serves_the_references_tokens():
    """Requests of several launches through `ServingLoop` (admission,
    chunked prefill, blocks in flight, fences): every served token is
    the reference's argmax, the fence rows carry the paged kind's
    counters, the expert layer's and the pool's bytes, and speculation
    is refused."""
    sizes, cfg, params, flat = tiny(4)
    engine = InferenceEngine(cfg, params, {
        "inference": BLOCK, "monitor": {"enabled": True, "sinks": []}})
    rows = []

    class Sink:
        name = "rows"
        emit = staticmethod(lambda e: rows.append(e)
                            if e["kind"] == "decode_batch" else None)
        flush = close = staticmethod(lambda: None)

    engine.monitor.attach_sink(Sink)
    rng = np.random.default_rng(6)
    loop = ServingLoop(engine)
    requests = [Request(rid=i, tokens=rng.integers(0, VOCAB, n),
                        max_new_tokens=m)
                for i, (n, m) in enumerate([(40, 12), (19, 16), (33, 9),
                                            (5, 16)])]
    results = {r.rid: r for r in loop.serve(requests)}
    for req in requests:
        out = np.asarray(results[req.rid].out_tokens)
        seq = np.concatenate([req.tokens, out])
        lg = reference_logits(flat, seq[:-1], sizes)[len(req.tokens) - 1:]
        gap = lg.max(-1) - np.take_along_axis(lg, out[:, None], -1)[:, 0]
        assert gap.max() < 1e-5, req.rid
    busy = [r for r in rows if r["iterations"]]
    assert busy and all(
        r["kv_latent_bytes_resident"] == 60 * 3 * 4 * 128 * 4 ==
        engine.cache.pool_bytes for r in rows)
    for key in ("kv_pages_in_use", "kv_pages_attended", "prefill_launches",
                "moe_experts_touched", "moe_rows", "moe_rows_max_expert",
                "prefill_moe_rows"):
        assert all(key in r for r in busy), key
    # 2 expert layers x 4 held: never more a launch
    assert all(r["moe_experts_touched"] <= 8 * r["iterations"] for r in busy)
    # the ledger counts ONE pool under `kv_cache`
    assert engine.monitor.ledger.category_breakdown("kv_cache")[
        "pool.unallocated"] == engine.cache.pool_bytes
    with pytest.raises(ValueError, match="absorbed path under speculation"):
        InferenceEngine(cfg, params, {"inference": dict(
            BLOCK, speculative={"enabled": True, "draft_model":
                                "truncate:1", "k": 2})})


# ----------------------------------------------------------------------
# the other paged models' programs
# ----------------------------------------------------------------------
def program_digest(compiled):
    """A digest of an executable's optimized HLO with what names the
    source taken out (every `metadata={...}` and the tables of files,
    functions, locations and stack frames before the first
    computation), instructions numbered by first appearance."""
    lines = re.sub(r", metadata=\{[^}]*\}", "",
                   compiled.as_text()).splitlines()
    tables = lines.index("FileNames")
    first = next(i for i in range(tables, len(lines)) if re.match(
        r"(ENTRY )?%?[\w.\-]+ \(.*\{$", lines[i]))
    names = {}
    text = re.sub(r"%[\w.\-]+", lambda m: names.setdefault(
        m.group(0), f"%{len(names)}"), "\n".join(
            lines[:tables] + lines[first:]))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@functools.lru_cache(None)
def paged_engines():
    """{kind: engine} of the three models that keep K/V pages, at
    their tests' tiny sizes."""
    import test_falcon_h1
    import test_trinity
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    cfg = tiny_gpt2_config()
    gpt2 = GPT2ForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})
    engines = {"paged": InferenceEngine(cfg, gpt2, {"inference": BLOCK})}
    for kind, module in (("paged+state", test_falcon_h1),
                         ("paged+window", test_trinity)):
        mc, params, _ = module.tiny(f32)
        engines[kind] = InferenceEngine(mc, params,
                                        {"inference": module.BLOCK})
    return engines


def paged_decode_digests():
    return {kind: program_digest(engine._decode)
            for kind, engine in paged_engines().items()}


# recorded on PR 38's commit by this file's own function, under this
# jax: a record, not a standing test. PR 40 pinned the layout of
# Falcon-H1's and Trinity's W_q and W_k products (`head_projection`)
# and took their two digests out; GPT-2's block has no such product and
# its program is still that commit's. A PR that changes GPT-2's decode,
# or a new jax, deletes it
RECORDED_UNDER = "0.9.0"
PARENT_DIGESTS = {"paged": "1f6a55a425305981"}


@pytest.mark.skipif(jax.__version__ != RECORDED_UNDER,
                    reason="the digests were recorded under another jax")
def test_the_three_paged_models_decode_programs_are_what_they_were():
    """`PagedKVCache` took a parameter, the engine a fifth kind and
    (PR 40) four models' head projections a pinned layout: the decode
    program of GPT-2, which shares the engine and none of those
    blocks, is, op for op, what it was. (Falcon-H1's and Trinity's
    were held too until PR 40 changed their products.)"""
    digests = paged_decode_digests()
    assert {k: digests[k] for k in PARENT_DIGESTS} == PARENT_DIGESTS


@pytest.mark.parametrize("kind", ["paged", "paged+state", "paged+window"])
def test_the_paged_kinds_keep_two_pools_and_their_kernel(kind):
    """What the fifth kind must leave alone, without a record to keep:
    a paged model's manager counts TWO pools of its key/value heads,
    its state holds `k_pool` and `v_pool` and no latent pool, and its
    decode program attends through `paged_decode_attention` alone."""
    engine = paged_engines()[kind]
    cache = engine.cache
    for c in [getattr(cache, half) for half in ("pages", "full", "window")
              if hasattr(cache, half)] or [cache]:
        assert c.pool_bytes == 2 * int(np.prod(c.pool_shape(
            c.n_layer))) * c.dtype.itemsize
    assert {"k_pool", "v_pool"} <= set(engine._state) and \
        "latent_pool" not in engine._state
    # the ops' own names (the module's tables of files and functions
    # are the process's)
    ops = " ".join(re.findall(r'op_name="([^"]*)"',
                              engine._decode.as_text()))
    assert "paged_decode_attention" in ops and "latent" not in ops
