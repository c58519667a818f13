"""InferenceEngine — AOT prefill + single-token decode over the
model's cache, with device-side sampling and zero per-token host sync.

Exactly TWO programs are compiled per model (ahead of time, at engine
construction — no trace-on-first-request latency spike):

  * the **prefill** step: one prompt chunk ([1, prefill_chunk] tokens)
    through the stack, into the request's cache (chunked, so a long
    prompt interleaves with decode instead of stalling it);
  * the **decode** step: one token for EVERY request slot at once
    ([max_slots] lockstep) from each slot's cache, logits through the
    head, and greedy / temperature+top-k sampling device-side — the
    sampled token, the EOS/max-tokens finish flags, and the output
    ring all stay on device, so the host dispatches `sync_every`
    decode iterations back-to-back and reads NOTHING until the serving
    fence (the PR-2 async-dispatch convention applied to serving). It
    gives what the fence reads out a second time, in buffers that its
    next launch does not donate (`FENCE_KEYS`): the fence reads a
    block's snapshot while the next block runs (`fetch_state`).

(Beside them one small program of no layer and no weight: the nine
fields of a slot that `activate_slot` writes, in one dispatch.)

The model supplies the block, the engine supplies the cache (the seam;
docs/inference.md has it at length):

  * a MODEL MODULE (`models/gpt2.py`, `brumby.py`, `trinity.py`,
    `falcon_h1.py`, `sarvam_mla.py`, `phi4flash.py`, `nemotron_h.py`)
    holds the model's math as plain functions: `embed(mc, params,
    tokens, positions)`, ONE `block(mc, lp, hidden, positions, mixer,
    cache) -> (hidden, cache)`, `head(mc, params, hidden)`, `layers`
    (the stacked [n_layer, ...] weights `block` takes one layer of),
    `QUANT_KERNEL_MODULES` (the projections an int8 load may quantise;
    () refuses it) and, where `truncate:N` drafts are served,
    `first_layers(mc, params, n)`; a model whose layers do not all
    have weights of one shape gives `stacks(mc, params)`, the stacks
    `block` is scanned over one after another, each with the leaves
    that `block` takes WHOLE beside a layer's slice; one whose block
    counts something a launch (`COUNTERS`) returns the counts as a
    third value, and one whose block reads something off every row
    (`ROW_READINGS`; Trinity: the experts a row picked) returns those
    after the counts (`InferenceEngine.last_row_readings`). The block
    computes its own projections under SCOPE_ATTN_QKV / SCOPE_ATTN_OUT
    / SCOPE_MLP and calls `mixer` exactly once with what it projected
    (two branches side by side: both in that one call). A model whose
    layers keep DIFFERENT things calls `mixer(role, ...)` once for each
    thing a layer keeps or reads: through `stacks` where it also COUNTS
    (Nemotron-H), through `enter` / `leave` (`layers_with_carry` below)
    where a value rides beside the hidden state. No block knows
    of pages, tables, slots or state arrays. The MODEL CONFIG names the
    kind of cache the layers keep (`cache_kind`), carries the geometry
    that kind's manager needs and points at the module (`serving_module`).
    `models/` and this package import each other nowhere;
  * the ENGINE owns the kinds of cache (`PagedKind`, `RecurrentKind`,
    `PagedStateKind`: the paged kind and a state kind side by side in
    every layer, `PagedWindowKind`: pages in two geometries, a window
    or everything; `latent_kind.py` adds one pool of latent rows,
    `hybrid_kind.py` state, rings and one shared layer of pages,
    `layered_kind.py` pages OR state a layer, counted apart) and
    nothing of any model: per kind the manager
    (inference/kv_cache.py), the fresh device arrays and their keys in
    the engine's state, and the mixers;
  * ONE adapter (`Serving`) composes model x kind for the two programs
    here and the three of inference/speculative.py, through the one
    `scan_layers`.

Everything else (slot state, scheduler, sampling, bookkeeping, the
fence, the host's phases `SERVE_PHASES` in monitor/trace.py) is shared.
"""

import collections
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.kv_cache import (PagedKVCache,
                                              PagedStateCache,
                                              RecurrentStateCache,
                                              RingKVCache, WindowedKVCache,
                                              ring_columns)
from deepspeed_tpu.monitor import DeepSpeedMonitorConfig, Monitor
from deepspeed_tpu.monitor import memory as memory_mod
from deepspeed_tpu.monitor import programs
from deepspeed_tpu.ops.retention import (retention_chunked, retention_decode,
                                         retention_prefill)
from deepspeed_tpu.ops.ssm import (causal_conv, split_xbc, ssd_chunked,
                                   ssm_step)
from deepspeed_tpu.ops.transformer.paged_decode_attention import \
    paged_decode_attention
from deepspeed_tpu.ops.transformer.paged_prefill_attention import \
    paged_prefill_attention
from deepspeed_tpu.ops.transformer.quantized_matmul import (
    KERNEL_SCALE, quantize_kernel_int8_np)
from deepspeed_tpu.utils.logging import logger

# the regions of the serving programs: see utils/scopes.py
from deepspeed_tpu.utils.scopes import (  # noqa: F401
    SCOPE_ATTN, SCOPE_ATTN_OUT, SCOPE_ATTN_QKV, SCOPE_BOOKKEEPING,
    SCOPE_EMBED, SCOPE_HEAD, SCOPE_KV_GATHER, SCOPE_KV_WRITE, SCOPE_LAYERS,
    SCOPE_MLP, SCOPE_RETENTION_CHUNK, SCOPE_SAMPLE, SCOPE_SSM_CHUNK,
    SCOPE_SSM_CONV, SCOPE_STATE_RESET, SCOPE_STATE_UPDATE, SCOPES,
    SCOPES_IN_LAYER, SCOPES_IN_LAYER_RECURRENT, SCOPES_MOE,
    SCOPES_PAGED_MOE, SCOPES_PAGED_STATE, SCOPES_RECURRENT, SCOPES_SSM,
    SCOPES_STATE)


def compile_fresh(lowered):
    """Compile a lowered program; on XLA:CPU, with the persistent
    compilation cache bypassed. On XLA:CPU an executable deserialized
    from the cache is re-codegenned at load and its float reductions
    can land a few ulps away from a fresh compile of the SAME HLO. The
    serving programs carry cross-program bit-equality contracts on that
    backend (decode == training forward; speculative verify == decode)
    — those only hold when every program in the set comes from the
    same codegen path, so none of them may be resurrected from a cache
    written by another process. On an accelerator the contract is a
    tolerance, and a serving program compiles once per cache like any
    other."""
    if jax.default_backend() != "cpu" or \
            not jax.config.jax_enable_compilation_cache:
        return lowered.compile()
    from jax._src.compilation_cache import reset_cache
    # is_cache_used() memoizes its verdict process-wide at the first
    # compile, so flipping the flag alone is not enough: reset_cache()
    # drops the memo (and the in-memory LRU) so the disabled flag is
    # actually consulted, then again afterwards so later compiles
    # re-initialize the cache normally
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        reset_cache()


def _uploaded(host_array):
    """A device array of what `host_array` holds NOW: of a copy that
    nobody writes again."""
    return jnp.asarray(np.array(host_array))


def compile_registered(fn, args, donate_argnums):
    """`compile_fresh` of `jit(fn)` on `args`, with the executable left
    in the program registry under the name the profiler gives its
    launches (`jit_<fn>`). The registry reads the program's name
    stacks out of the executable, so for these programs they are part
    of the persistent cache's key: by default jax leaves them out, and
    a cache that an older build filled would hand back that build's
    names for the same HLO (seen on the chip: the parent's executables,
    none of the `SCOPE_*` in them)."""
    lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    key_had_names = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    t0 = time.perf_counter()
    try:
        compiled = compile_fresh(lowered)
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          key_had_names)
    programs.register("jit_" + fn.__name__, compiled,
                      time.perf_counter() - t0)
    return compiled


# query rows per slot up to which the paged mixer attends through the
# decode kernel: decode and draft decode carry 1, speculative verify
# k + 1. A prefill chunk carries more and attends in key blocks
# (`paged_prefill_attention`): one row against pages is a page walk
# bound by latency and bytes, a chunk of 128 rows against one slot is
# a matrix-unit problem.
DECODE_ROWS_MAX = 8

# what the serving fence reads of the decode state. The decode program
# gives them out a second time, in buffers of their own beside the
# state that its next launch donates: a block's snapshot
FENCE_KEYS = ("active", "finished_eos", "pos", "n_gen", "out_tokens",
              "sample_draws", "model_counts")
# the blocks that may be dispatched and unfetched at once: the one the
# device works on while the host reads the one before it
BLOCKS_KEPT = 2
# what `activate_slot` writes of a slot, in one dispatch
SLOT_KEYS = ("cur_token", "pos", "active", "finished_eos", "n_gen",
             "max_new", "temperature", "top_k", "eos")


def quantize_param_tree(params, block, modules):
    """The int8 load (`inference.weight_bits: 8`): a copy of a param
    tree with every projection kernel under a submodule named in
    `modules` (the model's `QUANT_KERNEL_MODULES`) quantised ONCE, by
    the shared primitive's layout (`ops/transformer/
    quantized_matmul.py`: symmetric int8, one fp32 scale per block of
    `block` rows of the contraction dim and output column) and a
    KERNEL_SCALE leaf beside it; dict structure otherwise unchanged. A
    model's dense application keys on KERNEL_SCALE's presence and
    dequantises in the matmul (`int8_matmul`). Everything else stays in
    the storage dtype."""
    def walk(tree):
        out = {}
        for name, sub in tree.items():
            if isinstance(sub, dict) and name in modules and "kernel" in sub:
                q, s = quantize_kernel_int8_np(sub["kernel"], block)
                out[name] = {**sub, "kernel": jnp.asarray(q),
                             KERNEL_SCALE: jnp.asarray(s)}
            else:
                out[name] = walk(sub) if isinstance(sub, dict) else sub
        return out

    return walk(params)


def scan_layers(stacked, hidden, cache, layer, first=0):
    """The layer stack of every serving program (decode, prefill, and
    for a paged model draft decode, verify, draft prefill): `lax.scan`
    over the stacked block weights and the layer index, with the
    hidden state AND the model's whole cache as the carry. `cache` is
    whatever pytree the model keeps between tokens (both K/V page
    pools; a retention model's state arrays) and `layer(lp, li,
    hidden, cache) -> (hidden, cache)` the model's block on layer `li`
    of it. Nothing cache-shaped is an `xs` or a `ys`: a pool that
    enters a scan as `xs` and leaves as `ys` is sliced, re-laid and
    stacked back layer by layer (70% of a decode step at 1.5B before
    PR 25). What `layer` returns after those two (a few numbers a
    row) IS the scan's `ys`: returned after the carry, stacked
    [n_layer, ...]."""
    n_layer = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def body(carry, xs):
        lp, li = xs
        hidden, cache, *read = layer(lp, li, *carry)
        return (hidden, cache), tuple(read)

    with jax.named_scope(SCOPE_LAYERS):
        carry, read = jax.lax.scan(
            body, (hidden, cache),
            (stacked, jnp.arange(first, first + n_layer)))
    return carry + read


# ----------------------------------------------------------------------
# the kinds of cache. Per kind: the manager, the fresh device
# arrays and their keys in the engine's state, and the mixers a model's
# block is handed (`mix(li, ...)`: on layer `li` of the WHOLE arrays,
# which ride in the layer scan's carry; no layer's part is ever sliced
# out, so the compiler updates the donated arrays in place)
# ----------------------------------------------------------------------
class PagedKind:
    """K/V page pools ([L, P, page, lanes], one token's K or V on the
    lanes, zeros from n_kv_head * head_dim up to the lane tile) behind
    per-slot page tables (`kv_cache.PagedKVCache`). A model with
    grouped-query heads names its key/value head count (`n_kv_head`
    on its config); the pools hold those heads only."""
    keys = ("k_pool", "v_pool")

    def __init__(self, model_config, config, max_seq_len):
        self.mc, self.cfg = model_config, config
        self.max_pages = -(-max_seq_len // config.kv_page_size)
        self.n_kv_head = getattr(model_config, "n_kv_head",
                                 model_config.n_head)

    def make_cache(self, ledger):
        mc, cfg = self.mc, self.cfg
        return PagedKVCache(
            n_layer=mc.n_layer, n_head=mc.n_head, head_dim=mc.head_dim,
            num_pages=cfg.kv_num_pages, page_size=cfg.kv_page_size,
            max_slots=cfg.max_slots, max_pages_per_slot=self.max_pages,
            dtype=np.dtype(mc.dtype), ledger=ledger,
            n_kv_head=self.n_kv_head)

    def fresh(self, cache):
        pool = cache.pool_shape(self.mc.n_layer)
        return {"k_pool": jnp.zeros(pool, self.mc.dtype),
                "v_pool": jnp.zeros(pool, self.mc.dtype),
                **self.tables(cache)}

    @staticmethod
    def tables(cache):
        """The manager's page tables as the engine's state holds
        them (uploaded again after every fence that changed them). A
        copy: the manager writes its tables in place while a block
        that holds the last upload is in flight, and on the CPU
        `jnp.asarray` may hand the program numpy's own memory."""
        return {"tables": _uploaded(cache.tables)}

    def mixer(self, tables, positions, valid, kv_limit):
        """The one mixer of all five paged programs, for rows at
        `positions` [B, T] of slots whose pages `tables` [B, max_pages]
        name. The rows' K/V are scattered into the pools at (li,
        physical page, offset); rows with valid=False (inactive decode
        slots, prefill pad rows) divert their writes to scratch page 0.

        How the rows attend is chosen by what the mixer can see, T at
        trace time. A few rows a slot (decode, draft decode, verify):
        one kernel walks each live slot's page table and reads the
        pages where they lie; a slot with no valid row is not live and
        gets zeros. A prefill chunk: the slot's pages are walked up to
        the chunk's last key, a block of pages at a time, the grouped
        query heads side by side against each key/value head
        (`paged_prefill_attention`); pages past it are never gathered.

        q is [B, T, n_head * d], k and v [B, T, n_kv_head * d]."""
        h, hk, d = self.mc.n_head, self.n_kv_head, self.mc.head_dim
        page_size = self.cfg.kv_page_size

        def mix(li, q, k, v, pools):
            k_pool, v_pool = pools
            b, t, _ = q.shape
            c = hk * d
            lanes = k_pool.shape[-1]
            # write-before-read: the chunk's own keys are part of its
            # causal window (a query attends to itself, like the
            # training mask)
            with jax.named_scope(SCOPE_KV_WRITE):
                pidx = positions // page_size
                off = positions % page_size
                phys = jnp.take_along_axis(tables, pidx, axis=1)
                phys = jnp.where(valid, phys, 0).reshape(-1)
                off = off.reshape(-1)
                row = lambda x: jnp.pad(x.reshape(b * t, c),
                                        ((0, 0), (0, lanes - c)))
                k_pool = k_pool.at[li, phys, off].set(row(k))
                v_pool = v_pool.at[li, phys, off].set(row(v))

            if t <= DECODE_ROWS_MAX:
                with jax.named_scope(SCOPE_ATTN):
                    live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)
                    attn = paged_decode_attention(
                        q, k_pool, v_pool, li, tables, positions, live_len,
                        h, hk)
            else:
                attn = paged_prefill_attention(
                    q, k_pool, v_pool, li, tables, positions, kv_limit, h, hk)
            return attn, (k_pool, v_pool)
        return mix

    def decode_mixer(self, state):
        pos = state["pos"]
        return self.mixer(state["tables"], pos[:, None],
                          state["active"][:, None], pos)

    def prefill_mixer(self, page_row, posv, valid, start, n_valid):
        return self.mixer(page_row[None], posv[None], valid[None],
                          (start + n_valid - 1)[None])


NO_SNAPSHOTS = (
    "inference.speculative.enabled: this model's slots hold recurrent "
    "state, and a rejected draft is undone by rewinding the cache: "
    "snapshots of state do not exist yet")


def make_state_cache(model_config, config, max_seq_len, ledger):
    """The manager of a model's recurrent state, in the per-slot
    shapes its config gives (`state_slot_shapes`)."""
    return RecurrentStateCache(
        n_layer=model_config.n_layer,
        slot_shapes=model_config.state_slot_shapes,
        max_slots=config.max_slots, max_tokens_per_slot=max_seq_len,
        ledger=ledger)


def fresh_state(keys, cache):
    return {k: jnp.zeros(shape, dtype) for k, shape, dtype in zip(
        keys, cache.state_shapes(), cache.state_dtypes())}


class RecurrentKind:
    """Recurrent state: per layer, slot and key/value head a matrix
    and its normaliser (the config's `state_slot_shapes`).
    Decode advances every slot's state by one token and reads it in
    the same region (`retention_decode` on layer `li` of the whole
    arrays: where Mosaic takes it one kernel call that passes over the
    state once, in place; else the XLA form); inactive slots keep
    theirs. Prefill advances one slot's state by a launch, from zero
    if the launch is the request's first (`retention_prefill` on layer
    `li` and that slot of the whole arrays: where Mosaic takes it one
    kernel call that keeps phi in VMEM and the state in place; else
    the XLA form `retention_chunked` on the slot sliced out)."""
    keys = ("state_s", "state_z")

    def __init__(self, model_config, config, max_seq_len):
        self.mc, self.cfg, self.max_seq_len = (model_config, config,
                                               max_seq_len)
        if config.spec_enabled:
            raise ValueError(NO_SNAPSHOTS)

    def make_cache(self, ledger):
        return make_state_cache(self.mc, self.cfg, self.max_seq_len, ledger)

    def fresh(self, cache):
        return fresh_state(self.keys, cache)

    def decode_mixer(self, state):
        mc = self.mc
        pos, idle = state["pos"], ~state["active"]

        def mix(li, q, k, v, lg, cache):
            S, z = cache
            with jax.named_scope(SCOPE_STATE_UPDATE):
                o, S, z = retention_decode(
                    q[:, 0], k[:, 0], v[:, 0], lg[:, 0], S, z, li,
                    mc.retention_scale, mc.retention_eps, keep=idle,
                    fresh=pos == 0)
                return o[:, None], (S, z)
        return mix

    def prefill_mixer(self, slot, posv, valid, start, n_valid):
        mc = self.mc

        def mix(li, q, k, v, lg, cache):
            S, z = cache
            with jax.named_scope(SCOPE_RETENTION_CHUNK):
                # (the start from zero of a request's first launch
                # lies under SCOPE_STATE_RESET inside)
                o, S, z = retention_prefill(
                    q, k, v, lg, S, z, li, slot, start, valid,
                    mc.retention_scale, mc.retention_eps,
                    mc.retention_chunk, chunked=retention_chunked)
            return o, (S, z)
        return mix


class PagedStateKind:
    """K/V pages AND recurrent state in every layer: a model whose
    block runs softmax attention and a Mamba-2 state-space mixer side
    by side (`models/falcon_h1.py`). The two halves lie on their own
    parts of the cache and neither is copied: the paged half IS
    `PagedKind` (its pools, its tables, its one mixer); the state half
    keeps, per layer and slot, the rows the causal convolution carries
    and the state matrix (the config's `state_slot_shapes`), advanced a
    chunk by `ssd_chunked` (prefill, from zero if the chunk is the
    request's first) or one token of every slot by `ssm_step` (decode;
    inactive slots keep theirs). The block hands both branches'
    projections over in one call, `mix(li, (q, k, v), (xbc, dt, A, D,
    conv_w, conv_b), cache)`, and gets both outputs back."""
    state_keys = ("conv_state", "ssm_state")
    keys = PagedKind.keys + state_keys

    def __init__(self, model_config, config, max_seq_len):
        if config.spec_enabled:
            raise ValueError(NO_SNAPSHOTS)
        self.mc, self.cfg, self.max_seq_len = (model_config, config,
                                               max_seq_len)
        self.paged = PagedKind(model_config, config, max_seq_len)

    def make_cache(self, ledger):
        return PagedStateCache(
            self.paged.make_cache(ledger),
            make_state_cache(self.mc, self.cfg, self.max_seq_len, ledger))

    def fresh(self, cache):
        return {**self.paged.fresh(cache),
                **fresh_state(self.state_keys, cache)}

    tables = staticmethod(PagedKind.tables)

    @staticmethod
    def both(paged_mix, state_mix):
        n = len(PagedKind.keys)

        def mix(li, attn_in, ssm_in, cache):
            o, pools = paged_mix(li, *attn_in, cache[:n])
            y, state = state_mix(li, *ssm_in, cache[n:])
            return (o, y), pools + state
        return mix

    def decode_mixer(self, state):
        idle, fresh = ~state["active"], state["pos"] == 0

        def mix(li, xbc, dt, A, D, conv_w, conv_b, cache):
            conv, H = cache
            with jax.named_scope(SCOPE_SSM_CONV):
                old = jax.lax.dynamic_index_in_dim(conv, li, 0,
                                                   keepdims=False)
                x, rows = causal_conv(
                    xbc, conv_w, conv_b,
                    jnp.where(fresh[:, None, None], 0, old))
                conv = jax.lax.dynamic_update_index_in_dim(
                    conv, jnp.where(idle[:, None, None], old, rows), li, 0)
            with jax.named_scope(SCOPE_STATE_UPDATE):
                xs, B, C = split_xbc(x[:, 0], *H.shape[2:])
                y, H = ssm_step(xs, dt[:, 0], A, B, C, D, H, li, keep=idle,
                                fresh=fresh)
            return y.reshape(y.shape[0], 1, -1), (conv, H)
        return self.both(self.paged.decode_mixer(state), mix)

    def prefill_mixer(self, where, posv, valid, start, n_valid):
        page_row, slot = where
        chunk = self.mc.mamba_chunk_size

        def mix(li, xbc, dt, A, D, conv_w, conv_b, cache):
            conv, H = cache
            with jax.named_scope(SCOPE_STATE_RESET):
                first = start == 0
                rows0 = jnp.where(first, 0, jax.lax.dynamic_slice(
                    conv, (li, slot, 0, 0), (1, 1) + conv.shape[2:])[0, 0])
                H0 = jnp.where(first, 0, jax.lax.dynamic_slice(
                    H, (li, slot, 0, 0, 0), (1, 1) + H.shape[2:])[0, 0])
            with jax.named_scope(SCOPE_SSM_CONV):
                x, rows1 = causal_conv(xbc[0], conv_w, conv_b, rows0,
                                       n_valid)
                conv = jax.lax.dynamic_update_slice(
                    conv, rows1[None, None], (li, slot, 0, 0))
            with jax.named_scope(SCOPE_SSM_CHUNK):
                xs, B, C = split_xbc(x, *H.shape[2:])
                y, H1 = ssd_chunked(xs, dt[0], A, B, C, D, H0, valid, chunk)
                H = jax.lax.dynamic_update_slice(
                    H, H1[None, None], (li, slot, 0, 0, 0))
            return y.reshape(1, y.shape[0], -1), (conv, H)
        return self.both(self.paged.prefill_mixer(page_row, posv, valid,
                                                  start, n_valid), mix)


class PagedWindowKind:
    """K/V pages in two geometries, for a model whose layers attend
    some over a sliding window and some over everything (the config's
    `layer_types` and `sliding_window`): two pools and two tables a
    slot (`kv_cache.WindowedKVCache`). The full layers' pool and table
    are `PagedKind`'s; a window layer's table is a ring (logical page
    p in column p % ring) that holds the window's pages only, and its
    queries see no key below position - window + 1.

    A layer's kind is an operand, looked up by the layer's index in
    the scan: the rows' K/V are scattered into both pools, the write
    to the pool that is not the layer's diverted to scratch page 0
    like an idle slot's, and `lax.cond` picks the pool that is read.
    A few rows a slot: `paged_decode_attention`, with the first
    visible key and the ring for a window layer. A prefill chunk:
    `paged_prefill_attention`, which walks a full layer's pages up to
    the chunk's last key and a window layer's from the page of the
    chunk's first visible key, through the ring."""
    keys = ("k_pool", "v_pool", "k_window", "v_window")

    def __init__(self, model_config, config, max_seq_len):
        if config.spec_enabled:
            raise ValueError(
                "inference.speculative.enabled: a draft's pool shares "
                "the flagship's one page table, and this model's slots "
                "hold two")
        self.mc, self.cfg = model_config, config
        self.max_pages = -(-max_seq_len // config.kv_page_size)
        self.n_kv_head = getattr(model_config, "n_kv_head",
                                 model_config.n_head)
        slides = np.asarray([t == "sliding_attention"
                             for t in model_config.layer_types])
        if slides.all() or not slides.any():
            raise ValueError(
                "cache_kind 'paged+window' is for window AND full layers "
                f"side by side; layer_types has {set(model_config.layer_types)}")
        self.slides = slides
        # a layer's index in the pool of its kind
        self.pool_index = np.where(slides, np.cumsum(slides),
                                   np.cumsum(~slides)) - 1
        self.window = int(model_config.sliding_window)

    def make_cache(self, ledger):
        mc, cfg = self.mc, self.cfg
        common = dict(n_head=mc.n_head, head_dim=mc.head_dim,
                      page_size=cfg.kv_page_size, max_slots=cfg.max_slots,
                      dtype=np.dtype(mc.dtype), ledger=ledger,
                      n_kv_head=self.n_kv_head)
        # the positions a slot's queries cover beyond the one the host
        # last knew: a prefill chunk, or the block in flight and the
        # one dispatched behind it (`ServingLoop.step`)
        span = max(cfg.prefill_chunk, 2 * cfg.sync_every)
        ring = ring_columns(self.window, cfg.kv_page_size, span)
        # every slot's ring, beside the scratch page: admission never
        # waits for a window page
        window = RingKVCache(
            self.window, span, n_layer=int(self.slides.sum()),
            num_pages=cfg.max_slots * ring + 1,
            category=memory_mod.CAT_KV_WINDOW, **common)
        full = PagedKVCache(
            n_layer=int((~self.slides).sum()), num_pages=cfg.kv_num_pages,
            max_pages_per_slot=self.max_pages, **common)
        return WindowedKVCache(full, window)

    def fresh(self, cache):
        full = cache.full.pool_shape(cache.full.n_layer)
        window = cache.window.pool_shape(cache.window.n_layer)
        dtype = self.mc.dtype
        return {"k_pool": jnp.zeros(full, dtype),
                "v_pool": jnp.zeros(full, dtype),
                "k_window": jnp.zeros(window, dtype),
                "v_window": jnp.zeros(window, dtype), **self.tables(cache)}

    @staticmethod
    def tables(cache):
        full, window = cache.tables
        return {"tables": _uploaded(full),
                "window_tables": _uploaded(window)}

    def mixer(self, tables, ring_tables, positions, valid, kv_limit):
        """As `PagedKind.mixer`, over both pools: rows at `positions`
        [B, T] of slots whose pages `tables` [B, max_pages] (the full
        layers') and `ring_tables` [B, ring] (the window layers')
        name."""
        h, hk, d = self.mc.n_head, self.n_kv_head, self.mc.head_dim
        page, window = self.cfg.kv_page_size, self.window
        ring = ring_tables.shape[1]
        slides_of = jnp.asarray(self.slides)
        index_of = jnp.asarray(self.pool_index, jnp.int32)

        def mix(li, q, k, v, pools):
            k_full, v_full, k_ring, v_ring = pools
            slides, at = slides_of[li], index_of[li]
            b, t, _ = q.shape
            c = hk * d
            lanes = k_full.shape[-1]
            pidx = positions // page
            with jax.named_scope(SCOPE_KV_WRITE):
                off = (positions % page).reshape(-1)
                row = lambda x: jnp.pad(x.reshape(b * t, c),
                                        ((0, 0), (0, lanes - c)))
                to_full = jnp.where(
                    valid & ~slides,
                    jnp.take_along_axis(tables, pidx, axis=1), 0).reshape(-1)
                to_ring = jnp.where(
                    valid & slides, jnp.take_along_axis(
                        ring_tables, pidx % ring, axis=1), 0).reshape(-1)
                fi, ri = jnp.where(slides, 0, at), jnp.where(slides, at, 0)
                k_full = k_full.at[fi, to_full, off].set(row(k))
                v_full = v_full.at[fi, to_full, off].set(row(v))
                k_ring = k_ring.at[ri, to_ring, off].set(row(k))
                v_ring = v_ring.at[ri, to_ring, off].set(row(v))
            first = jnp.maximum(positions - window + 1, 0)

            if t <= DECODE_ROWS_MAX:
                live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)
                kernel = functools.partial(
                    paged_decode_attention, q, li=at, q_pos=positions,
                    lens=live_len, n_head=h, n_kv_head=hk)
                with jax.named_scope(SCOPE_ATTN):
                    attn = jax.lax.cond(
                        slides,
                        lambda: kernel(k_pool=k_ring, v_pool=v_ring,
                                       tables=ring_tables, first=first,
                                       ring=ring),
                        lambda: kernel(k_pool=k_full, v_pool=v_full,
                                       tables=tables))
                return attn, (k_full, v_full, k_ring, v_ring)

            chunk = functools.partial(
                paged_prefill_attention, q, li=at, q_pos=positions,
                kv_limit=kv_limit, n_head=h, n_kv_head=hk)
            attn = jax.lax.cond(
                slides,
                lambda: chunk(k_pool=k_ring, v_pool=v_ring,
                              tables=ring_tables, first=first, ring=ring),
                lambda: chunk(k_pool=k_full, v_pool=v_full, tables=tables))
            return attn, (k_full, v_full, k_ring, v_ring)
        return mix

    def decode_mixer(self, state):
        pos = state["pos"]
        return self.mixer(state["tables"], state["window_tables"],
                          pos[:, None], state["active"][:, None], pos)

    def prefill_mixer(self, rows, posv, valid, start, n_valid):
        page_row, ring_row = rows
        return self.mixer(page_row[None], ring_row[None], posv[None],
                          valid[None], (start + n_valid - 1)[None])


KINDS = {"paged": PagedKind, "recurrent": RecurrentKind,
         "paged+state": PagedStateKind, "paged+window": PagedWindowKind}


class Serving:
    """One model over its kind of cache: what the serving programs
    call. `model_config.serving_module` is the model's module,
    `model_config.cache_kind` the kind (the module's docstring has
    what each supplies). A speculative engine holds a second one for
    the draft's config."""

    def __init__(self, model_config, config, max_seq_len):
        self.model = model_config.serving_module
        if config.weight_bits == 8:
            if not self.model.QUANT_KERNEL_MODULES:
                raise ValueError(
                    "inference.weight_bits: 8: this model names no "
                    "projection to quantise: it has no int8 path")
            model_config = dataclasses.replace(
                model_config, quant_block=config.weight_quant_block)
        self.mc = model_config
        self.kind = KINDS[model_config.cache_kind](model_config, config,
                                                   max_seq_len)
        # what the model's block counts a launch (`COUNTERS`): one row
        # for the decode program's launches and one for prefill's,
        # summed since the engine's reset, beside the kind's arrays
        self.counters = tuple(getattr(self.model, "COUNTERS", ()))
        # what it reads off every row, a layer (`ROW_READINGS`)
        self.row_readings = tuple(getattr(self.model, "ROW_READINGS", ()))
        self.cache_keys = self.kind.keys + \
            (("model_counts",) if self.counters else ())

    def quantized(self, params):
        return quantize_param_tree(params, self.mc.quant_block,
                                   self.model.QUANT_KERNEL_MODULES)

    def embed(self, params, tokens, positions):
        return self.model.embed(self.mc, params, tokens, positions)

    def fresh(self, cache):
        counts = {"model_counts": jnp.zeros((2, len(self.counters)),
                                            jnp.int32)} \
            if self.counters else {}
        return {**self.kind.fresh(cache), **counts}

    def layers(self, params, hidden, cache, positions, mixer, program=0,
               readings=False):
        """`scan_layers` of the model's block over `params`' stack(s), the
        kind's `mixer(li, ...)` on layer `li` of the whole `cache` arrays
        -> (hidden, cache[, readings: {name of `ROW_READINGS`: [layers,
        rows, ...]}]); row `program` of the block's counts takes the launch's."""
        if hasattr(self.model, "enter"):
            return layers_with_carry(self, params, hidden, cache, positions,
                                     mixer, caching=program == 1)
        n_arrays = len(cache) - bool(self.counters)

        def layer(lp, li, hidden, cache):
            hidden, arrays, *more = self.model.block(
                self.mc, lp, hidden, positions, functools.partial(mixer, li),
                cache[:n_arrays])
            if self.counters:
                arrays += (cache[-1].at[program].add(more.pop(0)),)
            return (hidden, arrays) + (tuple(more[0]) if readings else ())

        if not hasattr(self.model, "stacks"):
            hidden, cache, *read = scan_layers(
                self.model.layers(params), hidden, cache, layer)
        else:
            first, read = 0, []
            for scanned, whole in self.model.stacks(self.mc, params):
                hidden, cache, *each = scan_layers(
                    scanned, hidden, cache,
                    lambda lp, *a, whole=whole: layer({**lp, **whole}, *a),
                    first)
                first += jax.tree_util.tree_leaves(scanned)[0].shape[0]
                read.append(each)
            read = [jnp.concatenate(r) for r in zip(*read)]
        if not readings:
            return hidden, cache
        return hidden, cache, dict(zip(self.row_readings, read))

    def decode_layers(self, params, hidden, state, readings=False):
        hidden, cache, *read = self.layers(
            params, hidden, tuple(state[k] for k in self.cache_keys),
            state["pos"][:, None], self.kind.decode_mixer(state),
            readings=readings)
        return (hidden, dict(zip(self.cache_keys, cache))) + tuple(read)

    def prefill_layers(self, params, hidden, cache, where, posv, valid,
                       start, n_valid):
        """`where` finds the slot's part of `cache`: its page-table
        row, for recurrent state its index, for both the pair."""
        return self.layers(
            params, hidden, cache, posv[None],
            self.kind.prefill_mixer(where, posv, valid, start, n_valid),
            program=1)[1]

    def head(self, params, hidden):
        return self.model.head(self.mc, params, hidden)


def process_logits(l32, top_k, temperature, top_k_cap):
    """The sampler's per-slot top-k mask + temperature scale (l32
    [S, V] fp32; top_k/temperature [S]): what the decode program draws
    from, and what speculative decoding passes both p and q through
    for the acceptance ratio to target that same distribution."""
    vals, _ = jax.lax.top_k(l32, top_k_cap)
    idx = jnp.clip(top_k - 1, 0, top_k_cap - 1)
    kth = jnp.take_along_axis(vals, idx[:, None], axis=1)[:, 0]
    masked = jnp.where((top_k > 0)[:, None] & (l32 < kth[:, None]),
                       -jnp.inf, l32)
    return masked / jnp.maximum(temperature, 1e-6)[:, None]


def sample(logits, state, top_k_cap):
    """(next token [S], whether the draw ran) from a decode launch's
    logits [S, V]. The top-k over slots x vocabulary and the
    categorical draw run only in a launch where a live slot's
    temperature asks for them: a greedy batch takes the argmax alone.
    Keys: `fold_in(rng, step)`, then the slot's index. Each side
    widens the logits itself: one float32 copy shared by both would be
    an operand of the cond, written out in every launch."""
    with jax.named_scope(SCOPE_SAMPLE):
        greedy = jnp.argmax(logits.astype(jnp.float32),
                            axis=-1).astype(jnp.int32)
        temp = state["temperature"]
        asked = jnp.any(state["active"] & (temp > 0.0))

        def draw():
            scaled = process_logits(logits.astype(jnp.float32),
                                    state["top_k"], temp, top_k_cap)
            key = jax.random.fold_in(state["rng"], state["step"])
            keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                key, jnp.arange(logits.shape[0]))
            drawn = jax.vmap(jax.random.categorical)(keys, scaled)
            return jnp.where(temp > 0.0, drawn.astype(jnp.int32), greedy)

        return jax.lax.cond(asked, draw, lambda: greedy), asked


class InferenceEngine:
    """Serving engine for one model over the kind of cache its config
    names: K/V pages, recurrent state, or both in every layer (see the
    module's docstring).

    Construction compiles the two programs AOT against the configured
    shapes; `start_request`/`prefill_chunk`/`activate_slot` manage
    slots (fence-side host work), `decode_block` dispatches N sync-free
    decode iterations, and `fetch_state` is the ONE host<->device
    rendezvous (the serving fence — declared in the ds_lint registry
    and pinned by the dynamic guard test)."""

    def __init__(self, model_config, params, config=None, rank=0,
                 draft_params=None, draft_model_config=None):
        self.model_config = model_config
        cfg = InferenceConfig(config or {})
        self.config = cfg
        self.monitor = Monitor(self, DeepSpeedMonitorConfig(config or {}))
        self._host_steps = 0
        self.micro_steps = 0

        max_seq = model_config.n_positions
        if cfg.max_seq_len is not None:
            max_seq = min(max_seq, cfg.max_seq_len)
        self.max_seq_len = max_seq
        self.serving = Serving(model_config, cfg, max_seq)

        if cfg.weight_bits == 8:
            params = self.serving.quantized(params)
            logger.info(
                "inference: int8 weight-only quantization applied "
                f"(block {cfg.weight_quant_block} along the "
                "contraction dim)")
        self._params = params
        self.cache = self.serving.kind.make_cache(self.monitor.ledger)
        self.monitor.ledger.register_tree(
            memory_mod.CAT_PARAMS, "inference.params", params)

        # request-level serving observability (ISSUE 14): the tracker
        # follows the monitor.flight convention — on by default, but
        # only when a monitor block is enabled on the same config
        self.tracker = None
        if self.monitor.enabled and cfg.observability_enabled:
            from deepspeed_tpu.monitor.serving import ServingTracker
            self.tracker = ServingTracker(self.monitor, self.cache, cfg)
            self.monitor.attach_serving(self.tracker)

        self._state = self._fresh_state()
        self._decode = self._build_decode_step()
        self._prefill = self._build_prefill_step()
        self._activate = self._build_activate_step()
        self._last_logits = self._last_read = None
        self._forget_fences()

        # speculative decoding (ISSUE 18, inference/speculative.py):
        # gated on the config default-off, so the disabled engine's
        # compiled programs and state are byte-for-byte the above
        self.speculative_enabled = cfg.spec_enabled
        self._draft_decode = self._verify = self._draft_prefill = None
        if cfg.spec_enabled:
            from deepspeed_tpu.inference import speculative as spec_mod
            if cfg.spec_draft_model == "external":
                if draft_params is None or draft_model_config is None:
                    raise ValueError(
                        'inference.speculative.draft_model="external" '
                        "requires draft_params and draft_model_config")
                self._draft_config, self._draft_params = (
                    draft_model_config, draft_params)
            else:
                self._draft_config, self._draft_params = \
                    spec_mod.derive_draft(model_config, params,
                                          cfg.spec_draft_model)
            self.draft_serving = Serving(self._draft_config, cfg, max_seq)
            if cfg.spec_draft_model == "external" and cfg.weight_bits == 8:
                self._draft_params = self.draft_serving.quantized(
                    self._draft_params)
            if self._draft_config.n_head != model_config.n_head or \
                    self._draft_config.head_dim != model_config.head_dim:
                raise ValueError(
                    "speculative draft model must share the flagship's "
                    "head geometry (the draft KV pool reuses the "
                    "flagship page-table shapes)")
            self.cache.attach_draft(self._draft_config.n_layer)
            # only the draft's own layer stack is new device bytes: a
            # truncated draft shares everything else with the flagship
            self.monitor.ledger.register_tree(
                memory_mod.CAT_PARAMS, "inference.draft_params",
                self.draft_serving.model.layers(self._draft_params))
            self._spec_state = spec_mod.fresh_spec_state(self)
            self._draft_decode = spec_mod.build_draft_step(self)
            self._verify = spec_mod.build_verify_step(self)
            self._draft_prefill = spec_mod.build_draft_prefill_step(self)
            # host mirror of the draft dispatch depth: max(live k_slot)
            # as of the last fence (adaptive back-off without any extra
            # host<->device sync)
            self._spec_next_draft = cfg.spec_k
            self._spec_draft_dispatch_s = 0.0
            self._spec_verify_dispatch_s = 0.0
            logger.info(
                "inference: speculative decoding enabled "
                f"(draft={cfg.spec_draft_model}, "
                f"{self._draft_config.n_layer}/{model_config.n_layer} "
                f"layers, k={cfg.spec_k}, "
                f"adaptive={cfg.spec_adaptive})")

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def _fresh_state(self):
        cfg = self.config
        s, w = cfg.max_slots, cfg.max_new_tokens
        self._tables_version = self.cache.table_version
        return {
            **self.serving.fresh(self.cache),
            "pos": jnp.zeros((s,), jnp.int32),
            "cur_token": jnp.zeros((s,), jnp.int32),
            "active": jnp.zeros((s,), bool),
            "finished_eos": jnp.zeros((s,), bool),
            "n_gen": jnp.zeros((s,), jnp.int32),
            "out_tokens": jnp.zeros((s, w), jnp.int32),
            "max_new": jnp.full((s,), w, jnp.int32),
            "temperature": jnp.zeros((s,), jnp.float32),
            "top_k": jnp.zeros((s,), jnp.int32),
            "eos": jnp.full((s,), -1, jnp.int32),
            "rng": jax.random.PRNGKey(cfg.seed),
            "step": jnp.zeros((), jnp.int32),
            # decode launches in which a live slot asked for a draw
            "sample_draws": jnp.zeros((), jnp.int32),
        }

    def reset(self):
        """Drop all slots and cached pages (between two runs)."""
        for slot in self.cache.slots():
            self.cache.free(slot)
        # the old cache goes before the new one is made: a retention
        # model's state is a third of the chip
        self._state = None
        self._state = self._fresh_state()
        self._forget_fences()
        if self.speculative_enabled:
            from deepspeed_tpu.inference import speculative as spec_mod
            self._spec_state = spec_mod.fresh_spec_state(self)
            self._spec_next_draft = self.config.spec_k
            self._spec_draft_dispatch_s = 0.0
            self._spec_verify_dispatch_s = 0.0
        if self.tracker is not None:
            self.tracker.on_reset()

    # ------------------------------------------------------------------
    # the two AOT programs
    # ------------------------------------------------------------------
    def _build_decode_step(self):
        cfg, mc, serving = self.config, self.model_config, self.serving
        s = cfg.max_slots
        out_w = cfg.max_new_tokens
        top_k_cap = min(cfg.top_k_max, mc.vocab_size)

        def decode_fn(params, state):
            active = state["active"]
            pos = state["pos"]
            # a [S, 1] "sequence" at absolute positions `pos`
            with jax.named_scope(SCOPE_EMBED):
                hidden = serving.embed(params, state["cur_token"], pos)
                hidden = hidden[:, None, :]
            hidden, cache, *read = serving.decode_layers(
                params, hidden, state, readings=bool(serving.row_readings))
            with jax.named_scope(SCOPE_HEAD):
                logits = serving.head(params, hidden)[:, 0]
            next_tok, drew = sample(logits, state, top_k_cap)

            with jax.named_scope(SCOPE_BOOKKEEPING):
                n = state["n_gen"]
                idx = jnp.clip(n, 0, out_w - 1)
                rows = jnp.arange(s)
                prev = state["out_tokens"][rows, idx]
                out = state["out_tokens"].at[rows, idx].set(
                    jnp.where(active, next_tok, prev))
                n2 = n + active.astype(jnp.int32)
                hit_eos = active & (next_tok == state["eos"])
                hit_max = active & (n2 >= state["max_new"])
                new_state = dict(
                    state, **cache,
                    pos=pos + active.astype(jnp.int32),
                    cur_token=jnp.where(active, next_tok,
                                        state["cur_token"]),
                    active=active & ~(hit_eos | hit_max),
                    finished_eos=state["finished_eos"] | hit_eos,
                    n_gen=n2,
                    out_tokens=out,
                    step=state["step"] + 1,
                    sample_draws=state["sample_draws"] +
                    drew.astype(jnp.int32),
                )
            # what the fence reads, in buffers of their own: the next
            # launch donates the state's
            snapshot = {k: new_state[k] for k in FENCE_KEYS
                        if k in new_state}
            return new_state, logits, (read[0] if read else {}), snapshot

        return compile_registered(decode_fn, (self._params, self._state),
                                  donate_argnums=(1,))

    def _build_prefill_step(self):
        cfg, serving = self.config, self.serving
        chunk = cfg.prefill_chunk

        def prefill_fn(params, cache, where, tokens, start, n_valid):
            """`cache`: the model's cache arrays (`cache_arrays`);
            `where` finds the slot's part of them: its page-table row,
            for recurrent state its index, for both the pair."""
            posv = start + jnp.arange(chunk, dtype=jnp.int32)
            valid = jnp.arange(chunk) < n_valid
            with jax.named_scope(SCOPE_EMBED):
                hidden = serving.embed(params, tokens, posv)[None]
            return serving.prefill_layers(params, hidden, cache, where,
                                          posv, valid, start, n_valid)

        args = (self._params, self.cache_arrays(), self._where(0),
                jnp.zeros((chunk,), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        return compile_registered(prefill_fn, args, donate_argnums=(1,))

    def _build_activate_step(self):
        """The slot's fields of the state, written in ONE dispatch. As
        nine eager `.at[].set` they cost the host 1.7 to 2 ms each on
        the chip, and the seventh waited until the device's queue had
        drained (PERF.md section 6, PR 38): with a block in flight
        that stalled the loop at every activation."""
        def activate_fn(fields, slot, ints, temperature):
            cur_token, pos, max_new, top_k, eos = ints
            new = {"cur_token": cur_token, "pos": pos, "active": True,
                   "finished_eos": False, "n_gen": 0, "max_new": max_new,
                   "temperature": temperature, "top_k": top_k, "eos": eos}
            return {k: fields[k].at[slot].set(v) for k, v in new.items()}

        args = ({k: self._state[k] for k in SLOT_KEYS}, np.int32(0),
                np.zeros((5,), np.int32), np.float32(0))
        return jax.jit(activate_fn).lower(*args).compile()

    def _where(self, slot):
        """The cache manager's `slot_operand` as device operands
        (copies, as the kinds' `tables`: a row of a table is written
        again while the chunk that was handed it is in flight)."""
        return jax.tree_util.tree_map(_uploaded,
                                      self.cache.slot_operand(slot))

    def cache_arrays(self):
        """The model's cache arrays as the programs hold them, in the
        order of the kind's `keys` (the device arrays, not
        copies: both K/V page pools `PagedKVCache.pool_shape`, the
        arrays of `RecurrentStateCache.state_shapes`, or the pools and
        then the state)."""
        return tuple(self._state[k] for k in self.serving.cache_keys)

    # ------------------------------------------------------------------
    # fence-side slot management (host work, runs between blocks)
    # ------------------------------------------------------------------
    def push_tables(self):
        """Upload the page tables iff they changed since the last
        push — callers invoke this liberally at fences and pay one
        transfer per actual mutation batch."""
        if self._tables_version != self.cache.table_version:
            self._state.update(self.serving.kind.tables(self.cache))
            self._tables_version = self.cache.table_version

    def prefill_chunk(self, slot, tokens, start):
        """Cache `tokens` (<= prefill_chunk of them) for `slot` at
        positions [start, start+len). Pages must already be ensured."""
        n = len(tokens)
        buf = np.zeros((self.config.prefill_chunk,), np.int32)
        buf[:n] = tokens
        st = self._state
        st.update(zip(self.serving.cache_keys, self._prefill(
            self._params, self.cache_arrays(), self._where(slot),
            jnp.asarray(buf),
            jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32))))
        if self.speculative_enabled:
            # the draft attends over the whole committed prefix, so
            # its pool must cache the prompt too (same chunk, same
            # page-table row, draft layer count)
            sp = self._spec_state
            dk, dv = self._draft_prefill(
                self._draft_params, sp["dk_pool"], sp["dv_pool"],
                _uploaded(self.cache.tables[slot]), jnp.asarray(buf),
                jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32))
            sp["dk_pool"], sp["dv_pool"] = dk, dv
        self._host_steps += 1
        self._prefilled = self._prefilled + (
            1, n, *self.cache.prefill_keys(start, n))

    def activate_slot(self, slot, cur_token, pos, max_new, temperature,
                      top_k, eos):
        """Flip a fully-prefilled slot live for the decode batch: one
        dispatch of the slot's fields behind the slot's last prefill
        chunk (`activate.first_update`: where a wait for the device's
        queue would show), and a speculative engine's two eager
        updates after it (`activate.other_updates`)."""
        st, trace = self._state, self.monitor.trace
        # a snapshot taken before this shows the slot as its last
        # request left it: `fetch_state` lays this over it
        self._activated[int(slot)] = (self._launches, int(pos))
        with trace.span("serve/activate", slot=int(slot)):
            with trace.span("serve/activate.first_update"):
                st.update(self._activate(
                    {k: st[k] for k in SLOT_KEYS}, np.int32(slot),
                    np.asarray([cur_token, pos, max_new, top_k,
                                -1 if eos is None else eos], np.int32),
                    np.float32(temperature)))
            with trace.span("serve/activate.other_updates"):
                if self.speculative_enabled:
                    # new request, fresh speculation posture: optimistic
                    # k, clean acceptance EMA
                    sp = self._spec_state
                    sp["k_slot"] = sp["k_slot"].at[slot].set(
                        self.config.spec_k)
                    sp["acc_ema"] = sp["acc_ema"].at[slot].set(1.0)

    def start_request(self, slot, prompt, max_new, temperature=0.0,
                      top_k=0, eos=None):
        """Admit + fully prefill + activate one request in one call
        (a test's convenience; ServingLoop does the same piecewise,
        chunk-interleaved with decode)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        t = len(prompt)
        if t < 1:
            raise ValueError("empty prompt")
        if t + max_new > self.max_seq_len:
            raise ValueError(
                f"prompt ({t}) + max_new_tokens ({max_new}) exceeds "
                f"max_seq_len {self.max_seq_len}")
        if max_new > self.config.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {max_new} exceeds the device output "
                "ring width inference.max_new_tokens="
                f"{self.config.max_new_tokens}")
        if top_k > self.config.top_k_max:
            raise ValueError(
                f"top_k {top_k} exceeds the compiled sampling cap "
                f"inference.top_k_max={self.config.top_k_max}")
        self.cache.admit(slot, t + max_new)
        chunk = self.config.prefill_chunk
        n_prefill = t - 1
        for start in range(0, n_prefill, chunk):
            end = min(start + chunk, n_prefill)
            self.cache.ensure(slot, end, queries_from=start)
            self.prefill_chunk(slot, prompt[start:end], start)
        # direct (scheduler-less) use runs decode_block without a
        # fence-side capacity step, so assign the worst case up front
        # (a ring of pages cannot hold more than a block's steps: it
        # says so); ServingLoop allocates incrementally instead
        self.cache.ensure(slot, t + max_new, queries_from=t - 1)
        self.push_tables()
        self.activate_slot(slot, prompt[-1], t - 1, max_new,
                           temperature, top_k, eos)

    def ensure_decode_capacity(self, slot, known_pos, iters):
        """Assign pages covering `iters` more positions for a live
        slot before a decode block (reservation-backed: cannot fail).
        `known_pos` is the slot's position as the caller last read it.
        What counts is what `fetch_state` last handed out (a caller
        that is not the loop may have been handed a newer snapshot
        than the loop's) and the decode launches dispatched since that
        snapshot WAS TAKEN: the block in flight behind the one that
        was fenced, a caller's `decode_once` between two steps of the
        loop. They have moved the slot on and are counted in: a row
        past the pages asked for would be written to the scratch
        page."""
        worst = self.cache.reserved_tokens(slot)
        at, pos = self._activated.get(
            int(slot), (self._fenced_at, int(self._fenced_pos[slot])))
        known = max(int(known_pos), pos)
        ahead = self._launches - at + iters
        self.cache.ensure(slot, min(known + ahead, worst),
                          queries_from=known)

    # ------------------------------------------------------------------
    # the hot dispatch loop + the serving fence
    # ------------------------------------------------------------------
    def decode_block(self, n):
        """Dispatch n decode iterations back-to-back — no host sync,
        no device_get, nothing read until `fetch_state` (the dynamic
        guard test and ds_lint's HOTSYNC rule both pin this)."""
        st = self._state
        logits, read, snapshot = self._last_logits, self._last_read, None
        for _ in range(n):
            st, logits, read, snapshot = self._decode(self._params, st)
        self._state = st
        self._last_logits, self._last_read = logits, read
        self._host_steps += n
        self._launches += n
        self._block_dispatched(snapshot)

    def decode_once(self):
        """One decode iteration, returning the pre-sampling logits
        [max_slots, vocab] (parity tests read these)."""
        st, logits, read, _ = self._decode(self._params, self._state)
        self._state = st
        self._last_logits, self._last_read = logits, read
        self._host_steps += 1
        self._launches += 1
        return logits

    def last_row_readings(self):
        """What the model's block read off every row of the last
        decode launch ({name of its `ROW_READINGS`: device array
        [layers, max_slots, ...]}; {} for a model that reads nothing
        or before the first launch): beside `decode_once`'s logits,
        the same launch's."""
        return dict(self._last_read or {})

    def spec_block(self, rounds):
        """Dispatch `rounds` speculative rounds back-to-back — each
        round is `spec_next_draft()` draft-decode dispatches plus ONE
        flagship verify, acceptance decided device-side — with zero
        host syncs (the same HOTSYNC contract as decode_block; the
        guard tests run this loop under the sync counters). The
        per-phase perf_counter spans are DISPATCH time (execution is
        async and settles at the fence) — the drafted-vs-verified
        split the tracker reports."""
        st, sp = self._state, self._spec_state
        nd = self._spec_next_draft
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _j in range(nd):
                sp = self._draft_decode(self._draft_params, st, sp)
            t1 = time.perf_counter()
            st, sp = self._verify(self._params, st, sp)
            self._spec_draft_dispatch_s += t1 - t0
            self._spec_verify_dispatch_s += time.perf_counter() - t1
        self._state, self._spec_state = st, sp
        self._host_steps += rounds * (nd + 1)
        self._block_dispatched(None)

    def spec_next_draft(self):
        """Draft steps the next spec_block will dispatch per round
        (max live k_slot as of the last fence; the worst-case tokens
        per round for capacity planning is this + 1)."""
        return self._spec_next_draft

    def spec_dispatch_split(self):
        """Drain the accumulated (draft_s, verify_s) dispatch spans
        (host perf_counter, reset on read — one reader per fence)."""
        split = (self._spec_draft_dispatch_s,
                 self._spec_verify_dispatch_s)
        self._spec_draft_dispatch_s = 0.0
        self._spec_verify_dispatch_s = 0.0
        return split

    def _forget_fences(self):
        """Nothing dispatched, nothing unfetched, no slot known."""
        self._launches = 0       # decode launches dispatched
        # prefill launches, their prompt tokens, and what their
        # attention walked (`cache.prefill_keys`; a new array a launch:
        # the blocks below keep the one they were dispatched behind)
        self._prefilled = np.zeros(
            (2 + len(self.cache.prefill_keys(0, 1)),), np.int64)
        # the blocks dispatched and unfetched, oldest first: (the
        # block's snapshot, `_launches` and `_prefilled` when it was
        # taken); the oldest goes when a caller that never fetches
        # dispatches a third
        self._pending = collections.deque(maxlen=BLOCKS_KEPT)
        # what the host knows, for `ensure_decode_capacity`: the
        # positions in the snapshot that was last handed out and the
        # `_launches` at which it was taken, and the slots activated
        # since ({slot: (`_launches` then, its position)})
        self._fenced_pos = np.zeros((self.config.max_slots,), np.int64)
        self._fenced_at = 0
        self._activated = {}

    def _block_dispatched(self, snapshot):
        """A block's launches are in the device's queue: its snapshot
        (the decode program's, of the block's last launch) starts for
        the host and waits for `fetch_state`. The speculative programs
        leave none, and a block of theirs is read from the live state:
        its fence trims the page tables, so the loop dispatches
        nothing before it."""
        if self.speculative_enabled:
            snapshot = None
        for leaf in (snapshot or {}).values():
            leaf.copy_to_host_async()
        self._pending.append((snapshot, self._launches, self._prefilled))

    def blocks_in_flight(self):
        """The blocks dispatched (`decode_block`, `spec_block`) that no
        `fetch_state` has read yet."""
        return len(self._pending)

    def fetch_state(self):
        """THE serving fence: one fused device_get of the per-slot
        progress the scheduler needs (active flags, eos flags,
        positions, generated counts, output rings, and what the
        programs counted (the decode launches that drew a sample, the
        model's own counters) — or, when speculation is on, the round
        counters, still inside the SAME fused get).

        It reads the OLDEST block that is dispatched and unfetched, in
        the snapshot that block left: whatever was dispatched behind it
        stays in the device's queue and runs while the host reads and
        reacts (`blocks_in_flight` in the result says how many blocks
        that is). With nothing unfetched (no block since the last
        fetch, or launches by `decode_once` alone) it reads the live
        state, and waits for everything dispatched. A slot activated
        after the snapshot was taken reads as `activate_slot` wrote it,
        not as its last request left it."""
        st, trace = self._state, self.monitor.trace
        snapshot, taken_at, prefilled = self._pending.popleft() \
            if self._pending else (None, None, None)
        if snapshot is None:
            # the live state: no launch has donated it yet, and every
            # `activate_slot` is in it
            self._pending.clear()
            self._activated.clear()
            snapshot = {k: st[k] for k in FENCE_KEYS if k in st}
            taken_at, prefilled = self._launches, self._prefilled
        if self.speculative_enabled:
            sp = self._spec_state
            snapshot = dict(snapshot, speculative={
                "k_slot": sp["k_slot"], "drafted": sp["drafted_total"],
                "accepted": sp["accepted_total"],
                "verified": sp["verified_total"],
                "rollbacks": sp["rollbacks"], "rounds": sp["rounds"]})
        with trace.span("serve/fence.device_get"):
            got = jax.device_get(snapshot)
        with trace.span("serve/fence.bookkeeping"):
            snap = {k: got[k] for k in ("active", "finished_eos", "pos",
                                        "n_gen", "out_tokens")}
            snap["blocks_in_flight"] = len(self._pending)
            # the prefill launches dispatched before the snapshot was
            # taken, their prompt tokens and the keys they walked,
            # since the engine's reset (what the programs count of
            # prefill is as old)
            snap["prefilled"] = prefilled
            self._lay_activations_over(snap, taken_at)
            self._fenced_pos, self._fenced_at = snap["pos"], taken_at
            if not self.speculative_enabled:
                # what the programs counted since the engine's reset:
                # the decode launches that drew, and what the model's
                # block counts, the decode program's launches and
                # prefill's
                counts = {"decode": {"sample_draw_launches":
                                     int(got["sample_draws"])},
                          "prefill": {}}
                for program, row in zip(("decode", "prefill"),
                                        got.get("model_counts", ())):
                    counts[program].update(zip(self.serving.counters,
                                               row.tolist()))
                snap["counts"] = counts
                return snap
            spec = got["speculative"]
            if self.config.spec_adaptive:
                active, k_slot = snap["active"], spec["k_slot"]
                self._spec_next_draft = int(k_slot[active].max()) \
                    if active.any() else self.config.spec_k
            snap["speculative"] = dict(spec, rounds=int(spec["rounds"]))
            return snap

    def _lay_activations_over(self, snap, taken_at):
        """A slot activated after the snapshot was taken shows in it
        what its last request left (`active` False, that request's
        `n_gen` and `pos`): it reads as `activate_slot` wrote it. One
        activated before is in the snapshot, and forgotten here."""
        late = {}
        for slot, (at, pos) in list(self._activated.items()):
            # a snapshot is taken right behind its block's last launch:
            # an activation at the same count of launches came after
            if at >= taken_at:
                late[slot] = pos
            else:
                del self._activated[slot]
        if not late:
            return
        for key in ("active", "finished_eos", "pos", "n_gen", "out_tokens"):
            snap[key] = snap[key].copy()
        for slot, pos in late.items():
            snap["active"][slot], snap["finished_eos"][slot] = True, False
            snap["pos"][slot], snap["n_gen"][slot] = pos, 0
            snap["out_tokens"][slot] = 0


def layers_with_carry(serving, params, hidden, cache, positions, mixer,
                      caching):
    """`Serving.layers` for a model whose layers keep DIFFERENT things
    between tokens (`models/phi4flash.py`). Its `stacks(mc, params,
    caching)` are scanned one after another over PERIODS (a step of a
    scan is `block` on as many layers as the pattern repeats after),
    with the kind's `mixer(li, role, ...)` on period `li`; beside the
    hidden state the scans carry what the model's `enter(mc, hidden)`
    makes and its `leave(mc, carry)` drops when the launch ends: a
    value that later layers of the SAME launch read and no later token
    does. `caching`: the launch yields no logits (the prefill program),
    and the model gives only the stacks that write what later tokens
    read. Returns (hidden, cache)."""
    model, mc = serving.model, serving.mc
    carry, first = model.enter(mc, hidden), 0
    for scanned, whole in model.stacks(mc, params, caching=caching):
        carry, cache = scan_layers(
            scanned, carry, cache,
            lambda lp, li, carry, cache, whole=whole: model.block(
                mc, {**lp, **whole}, carry, positions,
                functools.partial(mixer, li), cache), first)
        first += jax.tree_util.tree_leaves(scanned)[0].shape[0]
    return model.leave(mc, carry), cache
