"""Phi-4-mini-flash-reasoning (`models/phi4flash.py`) and what it
forced: the Mamba-1 scan (`ops/ssm/mamba1.py`), the differential decode
kernel (`ops/transformer/diff_decode_attention.py`), the kind of cache
"state+window+shared" (`inference/hybrid_kind.py`,
`kv_cache.HybridKVCache`) and the engine's scan over periods with a
value in the carry that is not cache (`engine.layers_with_carry`). CPU,
tiny sizes, float32, seeded weights; the plain reference is the
benchmark's (`benchmark/reference/phi4flash.py`)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_phi4flash
from benchmark.reference import phi4flash as reference
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.hybrid_kind import StateWindowSharedKind
from deepspeed_tpu.models import phi4flash
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.ssm import mamba1
from deepspeed_tpu.ops.transformer import diff_decode_attention as dd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark/configs/phi-4-mini-flash.json")) as f:
    PUBLISHED = json.load(f)
TINY = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=4, sliding_window=12)
SIZES = dict(PUBLISHED, **TINY,
             assumed=dict(PUBLISHED["assumed"], mamba_dt_rank=4))
CFG = phi4flash.Phi4FlashConfig(
    **TINY, max_position_embeddings=256, mamba_dt_rank=4,
    dtype=jnp.float32, param_dtype=jnp.float32)
BLOCK = {"max_slots": 3, "prefill_chunk": 8, "sync_every": 2,
         "max_new_tokens": 16, "max_seq_len": 96,
         "kv_cache": {"num_pages": 60, "page_size": 4}}


@pytest.fixture(scope="module")
def weights():
    flat = weights_phi4flash.make_weights(SIZES, 5, jnp.float32)
    return flat, weights_phi4flash.to_program_tree(flat)


@pytest.fixture(scope="module")
def engine(weights):
    with jax.default_matmul_precision("highest"):
        return InferenceEngine(CFG, weights[1], {"inference": BLOCK})


def tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         TINY["vocab_size"]))


def test_forward_is_the_references(weights):
    flat, tree = weights
    ids = tokens(40)
    with jax.default_matmul_precision("highest"):
        got = phi4flash.forward(CFG, tree, jnp.asarray(ids)[None])[0]
    want = reference.logits(flat, jnp.asarray(ids), SIZES)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def served_logits(engine, slot, ids, n_prompt):
    """Prefill `ids[:n_prompt]` in chunks into `slot`, then decode the
    rest, each step fed the sequence's own next token; the logits
    after every token from the prompt's last on."""
    engine.start_request(slot, ids[:n_prompt], len(ids) - n_prompt + 1)
    out = []
    for nxt in list(ids[n_prompt:]) + [None]:
        out.append(np.asarray(engine.decode_once())[slot])
        if nxt is not None:
            engine._state["cur_token"] = \
                engine._state["cur_token"].at[slot].set(int(nxt))
    return np.stack(out)


def test_prefill_in_chunks_then_decode_is_the_references_forward(weights,
                                                                 engine):
    """A prompt longer than the window (12) and than two pages (4), in
    chunks of 8; then a shorter request in the SAME slot (its state,
    ring and pages start anew) while another slot is live beside it.
    (By hand a ring holds a block's steps and no more: 8 tokens.)"""
    flat, _ = weights
    with jax.default_matmul_precision("highest"):
        engine.reset()
        for n_total, n_prompt, slot, seed in ((48, 41, 1, 1), (26, 19, 1, 2),
                                              (27, 22, 2, 3)):
            ids = tokens(n_total, seed)
            got = served_logits(engine, slot, ids, n_prompt)
            want = np.asarray(reference.logits(flat, jnp.asarray(ids),
                                               SIZES))[n_prompt - 1:]
            assert np.abs(got - want).max() < 5e-5 * np.abs(want).max(), \
                (n_total, slot)
            if slot == 1:
                engine.cache.free(slot)


def test_a_slot_counts_all_three_parts(engine):
    cache = engine.cache
    engine.reset()
    assert cache.kind == "state+window+shared"
    assert cache.shared.n_layer == 1
    assert cache.window.n_layer == CFG.window_layers == 2
    assert cache.state.n_layer == CFG.state_layers == 3
    empty = cache.occupancy()
    cache.admit(0, 60)
    for start in range(0, 41, 8):                # a prompt's chunks
        cache.ensure(0, min(start + 8, 41), queries_from=start)
    held = cache.occupancy()
    assert held["state_slots_in_use"] == 1
    assert held["kv_pages_shared_in_use"] == 11          # ceil(41 / 4)
    # the ring keeps the pages of keys 40 - 11 = 29 .. 40 only
    assert held["kv_pages_window_in_use"] == 11 - 29 // 4
    assert held["kv_pages_window_released"] == 29 // 4
    assert cache.allocated_pages(0) == 11 + 4
    assert set(cache.reservation(60)) == {
        "kv_pages_reserved", "kv_pages_window_reserved",
        "state_bytes_reserved"}
    rows = cache.attended(np.array([True, False, False]),
                          np.array([40, 0, 0]), 2, 2, 16, 9)
    assert rows["kv_pages_shared_attended"] == 11 * CFG.shared_readers
    assert rows["kv_pages_window_attended"] == 4 * CFG.window_layers
    assert rows["prefill_layers_run"] == 2 * CFG.caching_layers
    with pytest.raises(NotImplementedError):
        cache.rollback(0, 10)
    cache.free(0)
    assert cache.occupancy() == empty
    # the shared pool bounds admission: one request may take it whole
    assert cache.never_fits(61 * 4) is not None
    assert cache.can_admit(59 * 4) is False or cache.shared.free_pages() >= 59


@pytest.mark.parametrize("over, message", [
    ({"speculative": {"enabled": True}}, "snapshots"),
    ({"weight_bits": 8}, "no int8 path")])
def test_what_state_cannot_do_is_refused_at_construction(weights, over,
                                                         message):
    with pytest.raises(ValueError, match=message):
        InferenceEngine(CFG, weights[1], {"inference": dict(BLOCK, **over)})


# ----------------------------------------------------------------------
# the prefill program stops where the cache is written
# ----------------------------------------------------------------------
def count_products(jaxpr, wanted, times=1):
    """How often a `dot_general` whose output's last dimension is
    `wanted` runs, scans' trip counts multiplied in."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and \
                eqn.outvars[0].aval.shape[-1] == wanted:
            n += times
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count_products(sub, wanted, inner)
    return n


def programs_products(cfg, block, wanted):
    """(prefill's, decode's) count of products of output width `wanted`,
    from the jaxprs of the engine's own layer scans on shapes alone."""
    conf = InferenceConfig({"inference": block})
    seq = block["max_seq_len"]
    family = engine_mod.Serving(cfg, conf, seq)
    cache = family.kind.make_cache(None)
    params = jax.eval_shape(lambda k: phi4flash.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    fresh = jax.eval_shape(lambda: family.kind.fresh(cache))
    arrays = tuple(fresh[k] for k in family.cache_keys)
    chunk, slots, H = block["prefill_chunk"], block["max_slots"], \
        cfg.hidden_size
    sds = jax.ShapeDtypeStruct

    def prefill(params, hidden, arrays, where, start, n_valid):
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        return family.prefill_layers(params, hidden, arrays, where, posv,
                                     jnp.arange(chunk) < n_valid, start,
                                     n_valid)

    def decode(params, hidden, arrays, tables, wtables, pos, active):
        return family.decode_layers(params, hidden, dict(
            zip(family.cache_keys, arrays), tables=tables,
            window_tables=wtables, pos=pos, active=active))

    i32 = lambda *shape: sds(shape, jnp.int32)
    ring = cache.window.ring
    pages = cache.shared.max_pages_per_slot
    pre = jax.make_jaxpr(prefill)(
        params, sds((1, chunk, H), cfg.dtype), arrays,
        (i32(pages), i32(ring), i32()), i32(), i32())
    dec = jax.make_jaxpr(decode)(
        params, sds((slots, 1, H), cfg.dtype), arrays, i32(slots, pages),
        i32(slots, ring), i32(slots), sds((slots,), bool))
    return (count_products(pre.jaxpr, wanted),
            count_products(dec.jaxpr, wanted))


@pytest.mark.parametrize("cfg, block, feed_forwards", [
    (CFG, BLOCK, (5, 8)),
    (phi4flash.Phi4FlashConfig(),
     {"max_slots": 64, "prefill_chunk": 512, "sync_every": 4,
      "max_new_tokens": 2048, "max_seq_len": 18432,
      "kv_cache": {"num_pages": 3500, "page_size": 128}}, (17, 32))],
    ids=["tiny", "published"])
def test_prefill_runs_the_layers_that_write_cache_and_no_other(
        cfg, block, feed_forwards):
    """17 of 32 feed-forwards a chunk at the published depth (the
    gate|up products counted in the program, not read off a flag), no
    memory unit and no query of a cross layer; decode runs all."""
    F, Di = cfg.intermediate_size, cfg.d_inner
    assert programs_products(cfg, block, 2 * F) == feed_forwards
    assert cfg.caching_layers == feed_forwards[0]
    # W_in's product (u | z) is the Mamba layers'; with W_g's (the
    # memory units') decode has one an even layer
    pre, dec = programs_products(cfg, block, 2 * Di)
    assert (pre, dec) == (cfg.state_layers, cfg.state_layers)
    pre, dec = programs_products(cfg, block, Di)
    # Di wide: W_dt's product in every Mamba layer and (the widths
    # coincide) W_qkv's in every layer that projects all three; decode
    # has the memory units' W_g and the middle layer's W_qkv besides,
    # of which prefill runs the K and V columns alone
    assert pre == cfg.state_layers + cfg.window_layers
    assert dec - pre == cfg.cross_periods + 1


# ----------------------------------------------------------------------
# the Mamba-1 scan
# ----------------------------------------------------------------------
def scan_inputs(t, d, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        c=jax.random.normal(k[0], (t, d)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (t, d)) - 2.0),
        A_t=-jnp.exp(jax.random.normal(k[2], (n, d))),
        B=jax.random.normal(k[3], (t, n)), C=jax.random.normal(k[4], (t, n)),
        D=1.0 + 0.1 * jax.random.normal(k[5], (d,)))


def direct_sum(c, dt, A_t, B, C, D, upto=None):
    """y [T, D] and the state after `upto` tokens, every pair of
    tokens written out: no recurrence."""
    t = c.shape[0]
    cum = jnp.cumsum(dt, axis=0)                               # [T, D]
    decay = jnp.exp((cum[:, None, None, :] - cum[None, :, None, :]) *
                    A_t[None, None])                           # [t, s, N, D]
    seen = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
    terms = decay * (dt * c)[None, :, None, :] * B[None, :, :, None]
    S = jnp.where(seen[:, :, None, None], terms, 0.0).sum(1)   # [T, N, D]
    y = (S * C[:, :, None]).sum(1) + D * c
    return y, S[t - 1 if upto is None else upto - 1]


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["xla", "kernel-interpreted"])
def test_selective_scan_chunk_by_chunk_is_the_direct_sum(interpret):
    """Chunks of 24 through 67 tokens with the last chunk's pad rows
    (garbage) left out of the state; the kernel pads 24 rows to its
    block of 128 and 40 channels to 512 itself."""
    t, d, n, chunk = 67, 40, 16, 24
    x = scan_inputs(t, d, n)
    want_y, want_S = direct_sum(**x)
    S = jnp.zeros((n, d))
    ys = []
    for at in range(0, t, chunk):
        rows = slice(at, at + chunk)
        pad = chunk - min(chunk, t - at)
        part = {k: jnp.pad(x[k][rows], ((0, pad), (0, 0)),
                           constant_values=7.0)
                for k in ("c", "dt", "B", "C")}
        y, S = mamba1.selective_scan_chunk(
            part["c"], part["dt"], x["A_t"], part["B"], part["C"], x["D"],
            S, valid=jnp.arange(chunk) < chunk - pad, interpret=interpret)
        ys.append(y[:chunk - pad])
    np.testing.assert_allclose(jnp.concatenate(ys), want_y, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-6)


def test_selective_step_token_by_token_is_the_direct_sum():
    """Three slots of one layer of a two-layer state array: one live
    from a prefilled state, one fresh (starts from zero whatever the
    slot held), one idle (keeps what it holds)."""
    t, d, n = 9, 24, 16
    x = scan_inputs(t, d, n, seed=3)
    want_y, want_S = direct_sum(**x)
    _, S5 = direct_sum(**x, upto=5)
    junk = jnp.full((n, d), 3.0)
    S = jnp.stack([jnp.zeros((3, n, d)), jnp.stack([S5, junk, junk])])
    keep = jnp.array([False, False, True])
    for i in range(5, t):
        tok = lambda k: jnp.stack([x[k][i], x[k][i - 5], x[k][i]])
        y, S = ssm.selective_step(
            tok("c"), tok("dt"), x["A_t"], tok("B"), tok("C"), x["D"], S,
            jnp.asarray(1), keep=keep, fresh=jnp.array([False, i == 5,
                                                         False]))
        np.testing.assert_allclose(y[0], want_y[i], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(y[1], want_y[i - 5], rtol=2e-5,
                                   atol=2e-5)
    np.testing.assert_allclose(S[1, 0], want_S, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(S[1, 1], direct_sum(**x, upto=4)[1],
                               rtol=2e-5, atol=2e-6)
    assert (S[1, 2] == junk).all() and (S[0] == 0).all()


def test_the_package_exports_both_scans_and_says_which_is_which():
    assert ssm.selective_scan_chunk is mamba1.selective_scan_chunk
    assert ssm.selective_step is mamba1.selective_step
    from deepspeed_tpu.ops.ssm import mamba2
    assert ssm.ssd_chunked is mamba2.ssd_chunked
    assert "ONE SCALAR decay a head" in mamba2.__doc__
    assert "(channel, state) pair" in mamba1.__doc__


# ----------------------------------------------------------------------
# differential attention over pages
# ----------------------------------------------------------------------
def four_products(q, k, v, seen):
    """a [H, 2 d] of one query row q [H, d] over keys k, v [Tk, Hk, d]
    (`seen` [Tk]): for every pair j its two softmaxes, each written
    out against the pair's two value heads side by side."""
    h, d = q.shape
    out = np.zeros((h, 2 * d), np.float32)
    for j in range(h // 2):
        p = j // 2
        vv = np.concatenate([v[:, 2 * p], v[:, 2 * p + 1]], axis=-1)
        for i in range(2):
            s = k[:, 2 * p + i] @ q[2 * j + i] / np.sqrt(d)
            s = np.where(seen, s, -np.inf)
            w = np.exp(s - s.max())
            out[2 * j + i] = (w / w.sum()) @ vv
    return out


@pytest.mark.parametrize("window", [None, 30], ids=["full", "window"])
def test_the_differential_kernel_is_the_four_products(window):
    """The kernel interpreted and the XLA form, one row a slot through
    shuffled page tables; a slot of length 0 gives zeros; a ring holds
    the window's pages only."""
    H, d, page, P, B = 8, 64, 16, 60, 3
    hk = H // 2
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    lens = np.array([37, 0, 150])
    cols = -(-150 // page)
    tables = np.asarray(jax.random.permutation(
        k[3], jnp.arange(1, P))[:B * cols].reshape(B, cols))
    keys = np.asarray(jax.random.normal(k[0], (B, cols * page, hk, d)))
    vals = np.asarray(jax.random.normal(k[1], (B, cols * page, hk, d)))
    q = jax.random.normal(k[2], (B, H * d))
    ring = None if window is None else (window - 2) // page + 2 + 1
    lanes = dd.padded_lanes(hk * d)
    pool_k = np.full((2, P, page, lanes), 9.0, np.float32)
    pool_v = np.full((2, P, page, lanes), 9.0, np.float32)
    used = tables if ring is None else tables[:, :ring]
    for b in range(B):
        first_page = 0 if ring is None else \
            max(lens[b] - window, 0) // page
        for p in range(first_page, -(-lens[b] // page)):
            phys = used[b, p % ring if ring else p]
            rows = slice(p * page, (p + 1) * page)
            pool_k[1, phys, :, :hk * d] = keys[b, rows].reshape(page, -1)
            pool_v[1, phys, :, :hk * d] = vals[b, rows].reshape(page, -1)
    q_pos = jnp.asarray(np.maximum(lens - 1, 0))
    first = None if window is None else jnp.maximum(q_pos - window + 1, 0)
    args = (q, jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(1),
            jnp.asarray(used), q_pos, jnp.asarray(lens), H)
    for interpret in (True, None):
        got = np.asarray(dd.diff_decode_attention(
            *args, first=first, ring=ring, interpret=interpret))
        assert got.shape == (B, H, 2 * d)
        assert (got[1] == 0).all()
        for b in (0, 2):
            at = np.arange(cols * page)
            seen = at < lens[b]
            if window is not None:
                seen &= at > lens[b] - 1 - window
            want = four_products(np.asarray(q[b]).reshape(H, d), keys[b],
                                 vals[b], seen)
            np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


def test_the_engine_reaches_the_kernel_interpreted(weights, monkeypatch):
    """Decode through both kernels in the Pallas interpreter (the
    engine's path on a TPU) gives the logits of the XLA forms."""
    flat, tree = weights
    ids = tokens(33, 7)
    with jax.default_matmul_precision("highest"):
        plain = InferenceEngine(CFG, tree, {"inference": BLOCK})
        want = served_logits(plain, 0, ids, 30)
        real = dd.diff_decode_attention
        monkeypatch.setattr(
            "deepspeed_tpu.inference.hybrid_kind.diff_decode_attention",
            lambda *a, **kw: real(*a, interpret=True, **kw))
        real_scan = mamba1.selective_scan_chunk
        monkeypatch.setattr(
            "deepspeed_tpu.inference.hybrid_kind.selective_scan_chunk",
            lambda *a, **kw: real_scan(*a, interpret=True, **kw))
        kernels = InferenceEngine(CFG, tree, {"inference": BLOCK})
        got = served_logits(kernels, 0, ids, 30)
    assert np.abs(got - want).max() < 5e-5 * np.abs(want).max()


def test_the_kind_is_registered_from_its_own_file():
    assert engine_mod.KINDS["state+window+shared"] is StateWindowSharedKind
    assert StateWindowSharedKind.__module__.endswith("hybrid_kind")
    assert CFG.cache_kind == "state+window+shared"
    # the engine names no model: the seam is `enter` / `leave` and
    # `stacks(caching=...)`
    import inspect
    assert "phi4flash" not in inspect.getsource(
        engine_mod.layers_with_carry).replace("models/phi4flash.py", "")
    stacks = phi4flash.stacks(CFG, jax.eval_shape(
        lambda k: phi4flash.init_params(CFG, k), jax.random.PRNGKey(0)))
    assert [whole["stack"] for _, whole in stacks] == [
        "self", "bridge", "cross"]
    caching = phi4flash.stacks(CFG, jax.eval_shape(
        lambda k: phi4flash.init_params(CFG, k), jax.random.PRNGKey(0)),
        caching=True)
    assert [whole["stack"] for _, whole in caching] == ["self",
                                                        "write_only"]
