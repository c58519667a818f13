"""The seam between a model and the serving engine (ISSUE 28): the
model supplies the block, the engine supplies the cache
(`inference/engine.py`'s docstring has the contract).

  * a third model, defined HERE (RMSNorm, rotary positions, no biases,
    an untied head; the paged kind), is served by `InferenceEngine` and
    `ServingLoop` with no line of `inference/` knowing it, and agrees
    with its own full forward;
  * the arrows point one way: nothing under `inference/` imports
    `models/`, nothing under `models/` imports `inference/`;
  * GPT-2's functional block, where it now lives, IS the training
    forward's math: with a dense causal mixer it gives
    `GPT2ForCausalLM.apply`'s logits on the unfused path.
"""

import ast
import dataclasses
import os
import sys
from typing import Any

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine, Request, ServingLoop
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.models.brumby import rms_norm, rope
from deepspeed_tpu.ops.transformer.flash_attention import dense_attention
from deepspeed_tpu.utils.scopes import (SCOPE_ATTN_OUT, SCOPE_ATTN_QKV,
                                        SCOPE_MLP)

f32 = jnp.float32


# ----------------------------------------------------------------------
# the third model: everything `InferenceEngine` asks of one
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RotaryConfig:
    vocab_size: int = 131
    n_positions: int = 96
    n_embd: int = 32
    n_layer: int = 3
    n_head: int = 4
    eps: float = 1e-6
    rope_theta: float = 1e4
    dtype: Any = f32

    cache_kind = "paged"
    serving_module = property(lambda self: sys.modules[__name__])
    head_dim = property(lambda self: self.n_embd // self.n_head)


QUANT_KERNEL_MODULES = ()


def init_params(cfg, seed):
    r = np.random.RandomState(seed)
    L, C, V = cfg.n_layer, cfg.n_embd, cfg.vocab_size
    draw = lambda *shape: jnp.asarray(0.2 * r.randn(*shape), cfg.dtype)
    return {"tok": draw(V, C), "out": draw(C, V),
            "norm_f": 1 + draw(C),
            "blocks": {"norm_1": 1 + draw(L, C), "wqkv": draw(L, C, 3 * C),
                       "wo": draw(L, C, C), "norm_2": 1 + draw(L, C),
                       "w_in": draw(L, C, 2 * C), "w_out": draw(L, 2 * C, C)}}


def layers(params):
    return params["blocks"]


def _rope(x, positions, cfg):
    """Rotary positions on x [B, T, C], head by head."""
    heads = x.reshape(x.shape[:2] + (cfg.n_head, cfg.head_dim))
    return rope(heads, positions, cfg.rope_theta).reshape(x.shape)


def embed(cfg, params, tokens, positions):
    return params["tok"][tokens]


def block(cfg, lp, hidden, positions, mixer, cache):
    with jax.named_scope(SCOPE_ATTN_QKV):
        q, k, v = jnp.split(
            rms_norm(hidden, lp["norm_1"], cfg.eps) @ lp["wqkv"], 3, axis=-1)
        q, k = _rope(q, positions, cfg), _rope(k, positions, cfg)
    attn, cache = mixer(q, k, v, cache)
    with jax.named_scope(SCOPE_ATTN_OUT):
        hidden = hidden + attn @ lp["wo"]
    with jax.named_scope(SCOPE_MLP):
        y = jax.nn.silu(rms_norm(hidden, lp["norm_2"], cfg.eps) @ lp["w_in"])
        hidden = hidden + y @ lp["w_out"]
    return hidden, cache


def head(cfg, params, hidden):
    return rms_norm(hidden, params["norm_f"], cfg.eps) @ params["out"]


def dense_causal_mixer(n_head):
    """Attention over the sequence itself, nothing kept: what a
    model's full forward hands its block."""
    def mixer(q, k, v, cache):
        b, t, c = q.shape
        heads = lambda x: x.reshape(b, t, n_head, c // n_head)
        out = dense_attention(heads(q), heads(k), heads(v), causal=True)
        return out.reshape(b, t, c), cache
    return mixer


def forward(model, cfg, params, ids):
    """[B, T] tokens -> [B, T, V] logits through `model`'s own embed,
    block and head, one layer after another."""
    b, t = ids.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    hidden = model.embed(cfg, params, ids, positions)
    stacked = model.layers(params)
    for li in range(cfg.n_layer):
        lp = jax.tree_util.tree_map(lambda x: x[li], stacked)
        hidden, _ = model.block(cfg, lp, hidden, positions,
                                dense_causal_mixer(cfg.n_head), None)
    return model.head(cfg, params, hidden)


# ----------------------------------------------------------------------
# (a) served with no line of inference/ knowing it
# ----------------------------------------------------------------------
BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 3,
         "max_new_tokens": 12,
         "kv_cache": {"num_pages": 40, "page_size": 8}}


@pytest.fixture(scope="module")
def third():
    cfg = RotaryConfig()
    params = init_params(cfg, 5)
    return cfg, params, InferenceEngine(cfg, params, {"inference": BLOCK})


def test_a_model_defined_here_decodes_to_its_own_forward(third):
    """Prompts of one and of three prefill chunks, then decode: the
    logits of every step against the model's full forward (causal, so
    one pass over the whole sequence holds every step's), to float32
    roundoff."""
    cfg, params, engine = third
    me = sys.modules[__name__]
    r = np.random.RandomState(3)
    for length in (9, 41):
        engine.reset()
        cur = list(r.randint(0, cfg.vocab_size, size=length))
        engine.start_request(1, cur, max_new=6)
        got = []
        for _ in range(6):
            got.append(np.asarray(engine.decode_once()[1]))
            cur.append(int(got[-1].argmax()))
        want = np.asarray(forward(me, cfg, params,
                                  jnp.asarray(cur)[None]))[0, length - 1:-1]
        np.testing.assert_allclose(np.stack(got), want, atol=2e-5, rtol=0,
                                   err_msg=str(length))
    engine.reset()


def test_a_model_defined_here_is_served_by_the_loop(third):
    """Five requests over three slots, chunked prefill interleaved with
    decode: every request's greedy tokens are its own forward's."""
    cfg, params, engine = third
    engine.reset()
    me = sys.modules[__name__]
    r = np.random.RandomState(4)
    reqs = [Request(rid=i, tokens=r.randint(
        0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=m)
        for i, (n, m) in enumerate([(5, 8), (33, 6), (18, 12), (2, 3),
                                    (47, 5)])]
    done = ServingLoop(engine).serve(reqs)
    assert sorted(q.rid for q in done) == [0, 1, 2, 3, 4]
    for q in done:
        assert len(q.out_tokens) == q.max_new_tokens
        seq = np.concatenate([q.tokens, q.out_tokens])
        want = np.asarray(forward(me, cfg, params, jnp.asarray(seq)[None]))[
            0, len(q.tokens) - 1:-1]
        for tok, row in zip(q.out_tokens, want):
            top = np.sort(row)
            assert int(tok) == int(row.argmax()) or \
                top[-1] - top[-2] < 1e-4, q.rid
    engine.reset()


def test_what_a_model_or_a_kind_cannot_do_is_refused(third):
    """No projection named for an int8 load: no int8 path. A kind no
    engine has: a KeyError at construction, not a fallback."""
    cfg, params, _ = third
    with pytest.raises(ValueError, match="no int8 path"):
        InferenceEngine(cfg, params, {"inference": dict(BLOCK,
                                                        weight_bits=8)})

    @dataclasses.dataclass(frozen=True)
    class Ring(RotaryConfig):
        cache_kind = "ring"
    with pytest.raises(KeyError, match="ring"):
        InferenceEngine(Ring(), params, {"inference": BLOCK})


# ----------------------------------------------------------------------
# (b) the arrows point one way
# ----------------------------------------------------------------------
def imported_modules(path, package):
    """Every module a file imports, lazy imports included, relative
    ones resolved against `package`."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.split(".")[:len(package.split(".")) -
                                        node.level + 1]
                base = ".".join(up + ([base] if base else []))
            yield base
            yield from (base + "." + a.name for a in node.names)


@pytest.mark.parametrize("importer, forbidden",
                         [("inference", "models"), ("models", "inference")])
def test_imports_between_models_and_inference_point_one_way(importer,
                                                            forbidden):
    root = os.path.dirname(deepspeed_tpu.__file__)
    banned = "deepspeed_tpu." + forbidden
    seen = 0
    for folder, _, files in os.walk(os.path.join(root, importer)):
        package = "deepspeed_tpu." + os.path.relpath(
            folder, root).replace(os.sep, ".")
        for name in files:
            if not name.endswith(".py"):
                continue
            seen += 1
            hits = [m for m in imported_modules(
                os.path.join(folder, name), package)
                if m == banned or m.startswith(banned + ".")]
            assert not hits, (os.path.join(folder, name), hits)
    assert seen >= 4


# ----------------------------------------------------------------------
# (c) GPT-2's serving block is the training forward's math
# ----------------------------------------------------------------------
@pytest.mark.parametrize("remat", [False, True])
def test_gpt2_functional_block_is_the_training_forward(remat):
    """float32, the unfused path: `embed`, `block` over the stacked
    leaves with a dense causal mixer, and `head` give
    `GPT2ForCausalLM.apply`'s logits to roundoff (the scan cell's name
    differs under remat; `layers` finds the stack either way)."""
    cfg = gpt2.tiny_gpt2_config(dropout=0.0, dtype=f32, fused_ops="off",
                                attention_impl="xla", remat=remat)
    model = gpt2.GPT2ForCausalLM(cfg)
    ids = np.random.RandomState(7).randint(0, cfg.vocab_size, size=(2, 24))
    params = model.init(jax.random.PRNGKey(1), {"input_ids": ids})
    want = np.asarray(model.apply(params, ids, True))
    got = np.asarray(forward(gpt2, cfg, params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=3e-6, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # and the draft a speculative engine derives is the first blocks
    # round the flagship's own buffers
    dcfg, dparams = gpt2.first_layers(cfg, params, 1)
    assert dcfg.n_layer == 1 and dparams["wte"] is params["wte"]
    assert jax.tree_util.tree_leaves(gpt2.layers(dparams))[0].shape[0] == 1


# ----------------------------------------------------------------------
# the loop keeps the next block in flight (ISSUE 38): every kind of
# cache under the one overlapped loop, pages of 8 under blocks of 4, so
# that a block crosses a page
# ----------------------------------------------------------------------
OVERLAP_BLOCK = {"max_slots": 3, "prefill_chunk": 16, "sync_every": 4,
                 "max_new_tokens": 24, "max_seq_len": 128,
                 "kv_cache": {"num_pages": 60, "page_size": 8}}
# (prompt tokens, new tokens): more requests than slots, prompts of one
# token and of four chunks, answers that end inside a block and on one
OVERLAP_LENGTHS = [(30, 16), (5, 7), (60, 24), (17, 12), (1, 6), (44, 9),
                   (9, 20)]


def _tiny_of(kind):
    """(model config, params) of the kind's tiny float32 preset."""
    if kind == "paged":
        cfg = gpt2.tiny_gpt2_config()
        model = gpt2.GPT2ForCausalLM(cfg)
        return cfg, model.init(jax.random.PRNGKey(0),
                               {"input_ids": np.zeros((1, 8), np.int32)})
    module = {"recurrent": "tests.test_brumby",
              "paged+state": "tests.test_falcon_h1",
              "paged+window": "tests.test_trinity"}[kind]
    import importlib
    cfg, params, _ = importlib.import_module(module).tiny(f32)
    return cfg, params


@pytest.fixture(scope="module", params=["paged", "recurrent", "paged+state",
                                        "paged+window"])
def overlapped(request):
    cfg, params = _tiny_of(request.param)
    assert cfg.cache_kind == request.param
    block = dict(OVERLAP_BLOCK)
    if request.param == "recurrent":
        del block["kv_cache"]
    return cfg, InferenceEngine(cfg, params, {"inference": block})


def _overlap_requests(cfg):
    r = np.random.RandomState(38)
    tokens = [r.randint(0, cfg.vocab_size, size=n).astype(np.int32)
              for n, _ in OVERLAP_LENGTHS]
    return lambda: [Request(rid=i, tokens=tokens[i].copy(),
                            max_new_tokens=m)
                    for i, (_, m) in enumerate(OVERLAP_LENGTHS)]


def test_the_overlapped_loop_serves_what_one_request_at_a_time_gets(
        overlapped):
    """Seven requests over three slots through the loop that keeps a
    block in flight: every request's tokens are those of
    `serve_sequential` (one request at a time, the same loop drained
    between two) and of the engine driven by hand, a launch at a time
    with no loop, no block and no snapshot."""
    from deepspeed_tpu.inference.scheduler import serve_sequential
    cfg, engine = overlapped
    make = _overlap_requests(cfg)
    engine.reset()
    loop = ServingLoop(engine)
    together = {q.rid: q.out_tokens.tolist() for q in loop.serve(make())}
    assert sorted(together) == list(range(len(OVERLAP_LENGTHS)))
    assert engine.blocks_in_flight() == 0 and engine.cache.slots() == []
    engine.reset()
    alone = {q.rid: q.out_tokens.tolist()
             for q in serve_sequential(engine, make()).results}
    assert engine.blocks_in_flight() == 0
    assert alone == together
    for q in make():
        engine.reset()
        engine.start_request(0, q.tokens, q.max_new_tokens)
        by_hand = [int(np.asarray(engine.decode_once())[0].argmax())
                   for _ in range(q.max_new_tokens)]
        assert by_hand == together[q.rid], q.rid
    engine.reset()


def _held(cache, slot, position):
    """Whether the slot holds a page for `position` in every table it
    has (a ring's column is the logical page's, modulo the ring)."""
    if hasattr(cache, "window"):
        page = position // cache.page_size
        return cache.full.tables[slot][page] != 0 and \
            cache.window.tables[slot][page % cache.window.ring] != 0
    pages = getattr(cache, "pages", cache)    # beside state: its pages
    return pages.tables[slot][position // pages.page_size] != 0


@pytest.mark.parametrize("foreign", [False, True],
                         ids=["loop-alone", "a-callers-launch-between"])
def test_two_blocks_ahead_of_the_known_position_lose_no_row(
        overlapped, foreign, monkeypatch):
    """When the loop asks for a block's pages it knows the positions
    of the block before the one in flight: the engine counts the
    launches dispatched since that snapshot was taken, so every row of
    every block lies on a page the slot holds, in the pools and in the
    ring, with a caller's own launch between two steps or without (a
    row past the pages asked for would go to the scratch page, and the
    served tokens need not show it)."""
    cfg, engine = overlapped
    if cfg.cache_kind == "recurrent":
        pytest.skip("state has no pages")
    make = _overlap_requests(cfg)
    engine.reset()
    want = {q.rid: q.out_tokens.tolist()
            for q in ServingLoop(engine).serve(make())}
    engine.reset()
    loop = ServingLoop(engine)
    for q in make():
        loop.submit(q)
    import time
    loop._t0 = time.monotonic()
    loop._last_fence_t = loop._now()
    real, checked = engine.decode_block, []
    page_size = OVERLAP_BLOCK["kv_cache"]["page_size"]

    def block(n):
        # the device's own positions, read beside the loop (not
        # through `fetch_state`, which would take the loop's snapshot)
        pos, active = jax.device_get([engine._state["pos"],
                                      engine._state["active"]])
        for slot in loop.live:
            if active[slot]:
                last = min(int(pos[slot]) + n,
                           engine.cache.reserved_tokens(slot)) - 1
                assert _held(engine.cache, slot, last), (slot, last)
                checked.append(last % page_size)
        real(n)

    monkeypatch.setattr(engine, "decode_block", block)
    launches = 0
    while loop.unfinished():
        loop.step()
        if foreign and loop.live and loop._iteration % 3 == 0:
            snap = engine.fetch_state()
            for slot in loop.live:
                engine.ensure_decode_capacity(slot, int(snap["pos"][slot]), 1)
            engine.push_tables()
            engine.decode_once()
            launches += 1
    assert not foreign or launches >= 3
    # blocks whose last row lies on another page than their first
    assert sum(row < engine.config.sync_every - 1 for row in checked) >= 5
    assert {q.rid: q.out_tokens.tolist() for q in loop.results} == want
    assert engine.blocks_in_flight() == 0 and engine.cache.slots() == []
    engine.reset()
