"""The sixth kind of cache, "state+window+shared": layers of DIFFERENT
kinds in one stack. A slot owns a Mamba-1 state in some layers, a ring
of K/V pages in others and pages of ONE layer that several layers
read; the rest keep nothing. It registers itself in `engine.KINDS`
as `latent_kind.py` does (`deepspeed_tpu.inference` imports it beside
the engine, whose own lines stay where the other models' cached
programs have them).

The model's block (`models/phi4flash.py`) is scanned over PERIODS of
two layers and calls its mixer once for each thing a layer keeps or
reads, by role; `li` is the period's index over all stacks:

    mix(li, "conv", u, conv_w, conv_b, cache)      the carried rows
    mix(li, "scan", c, dt, A_t, B, C, D, cache)    the Mamba-1 state
    mix(li, "window", q, k, v, cache)              write + attend, a ring
    mix(li, "full", q, k, v, cache)                write + attend, the pool
    mix(li, "write", k, v, cache)                  the pool, write alone
    mix(li, "shared", q, cache)                    attend to pages that
                                                   another layer wrote

over cache = (conv rows, state, k_window, v_window, k_shared,
v_shared), whole arrays in the layer scans' carry. Period li's Mamba
layer is layer li of the state arrays and its window layer layer li of
the rings (the model's stacks put those periods first); the shared
pool has one layer. A stack's kind is a Python word in the model, so
no `lax.cond` carries a state and a pool through a branch it does not
take.
"""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import (KINDS, NO_SNAPSHOTS, _uploaded,
                                            fresh_state)
from deepspeed_tpu.inference.kv_cache import (HybridKVCache, PagedKVCache,
                                              RecurrentStateCache,
                                              RingKVCache, ring_columns)
from deepspeed_tpu.monitor import memory as memory_mod
from deepspeed_tpu.ops.ssm import (causal_conv, selective_scan_chunk,
                                   selective_step)
from deepspeed_tpu.ops.transformer.diff_decode_attention import (
    diff_attention, diff_decode_attention, key_positions)
from deepspeed_tpu.utils.scopes import (  # noqa: F401
    SCOPE_ATTN, SCOPE_KV_GATHER, SCOPE_KV_WRITE, SCOPE_SHARED_KV,
    SCOPE_SSM_CHUNK, SCOPE_SSM_CONV, SCOPE_STATE_RESET, SCOPE_STATE_UPDATE,
    SCOPES_HYBRID)


# the decode kernel over the shared pool, as a device profile names it
# (over a ring it keeps its own name)
SHARED_KERNEL = "shared_kv_decode_attention"


class StateWindowSharedKind:
    """A Mamba-1 state (`RecurrentStateCache`: the convolution's rows
    and the scan's state, the config's `state_slot_shapes`, in
    `state_layers` layers), a ring of pages (`RingKVCache`, in
    `window_layers` layers) and ONE layer of pages that
    `shared_readers` layers read (`PagedKVCache`), behind
    `kv_cache.HybridKVCache`. Attention is differential
    (`ops/transformer/diff_decode_attention.py`): one row a slot walks
    the pages where they lie, both softmaxes of a pair from one read
    of a page; a prefill chunk attends to its ring gathered. The
    layers that read the shared pool run in no prefill launch (the
    model's `stacks(caching=True)` leaves them out), so only decode
    attends to it."""
    state_keys = ("conv_state", "scan_state")
    keys = state_keys + ("k_window", "v_window", "k_shared", "v_shared")

    def __init__(self, model_config, config, max_seq_len):
        if config.spec_enabled:
            raise ValueError(NO_SNAPSHOTS)
        self.mc, self.cfg, self.max_seq_len = (model_config, config,
                                               max_seq_len)
        self.window = int(model_config.sliding_window)

    def make_cache(self, ledger):
        mc, cfg = self.mc, self.cfg
        common = dict(n_head=mc.n_head, head_dim=mc.head_dim,
                      page_size=cfg.kv_page_size, max_slots=cfg.max_slots,
                      dtype=np.dtype(mc.dtype), ledger=ledger,
                      n_kv_head=mc.n_kv_head)
        # as `PagedWindowKind`: the positions a slot's queries cover
        # beyond the one the host last knew
        span = max(cfg.prefill_chunk, 2 * cfg.sync_every)
        ring = ring_columns(self.window, cfg.kv_page_size, span)
        state = RecurrentStateCache(
            n_layer=mc.state_layers, slot_shapes=mc.state_slot_shapes,
            max_slots=cfg.max_slots, max_tokens_per_slot=self.max_seq_len,
            ledger=ledger)
        rings = RingKVCache(
            self.window, span, n_layer=mc.window_layers,
            num_pages=cfg.max_slots * ring + 1,
            category=memory_mod.CAT_KV_WINDOW, **common)
        shared = PagedKVCache(
            n_layer=1, num_pages=cfg.kv_num_pages,
            max_pages_per_slot=-(-self.max_seq_len // cfg.kv_page_size),
            **common)
        return HybridKVCache(state, rings, shared, mc.shared_readers,
                             mc.caching_layers, cfg.prefill_chunk)

    def fresh(self, cache):
        dtype = self.mc.dtype
        ring = cache.window.pool_shape(cache.window.n_layer)
        shared = cache.shared.pool_shape(1)
        return {**fresh_state(self.state_keys, cache),
                "k_window": jnp.zeros(ring, dtype),
                "v_window": jnp.zeros(ring, dtype),
                "k_shared": jnp.zeros(shared, dtype),
                "v_shared": jnp.zeros(shared, dtype), **self.tables(cache)}

    @staticmethod
    def tables(cache):
        shared, ring = cache.tables
        return {"tables": _uploaded(shared),
                "window_tables": _uploaded(ring)}

    # -- the paged roles, alike in both programs ------------------------
    def paged(self, tables, ring_tables, positions, valid, kv_limit):
        """{role: mix} over the rings and the shared pool, for rows at
        `positions` [B, T] of slots whose pages `tables` [B, max_pages]
        and `ring_tables` [B, ring] name; rows with valid=False divert
        their writes to scratch page 0."""
        h, hk, d = self.mc.n_head, self.mc.n_kv_head, self.mc.head_dim
        page, window = self.cfg.kv_page_size, self.window
        ring = ring_tables.shape[1]
        b, t = positions.shape
        live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)
        first = jnp.maximum(positions - window + 1, 0)

        def written(k_pool, v_pool, at, phys, k, v):
            with jax.named_scope(SCOPE_KV_WRITE):
                lanes = k_pool.shape[-1]
                row = lambda x: jnp.pad(
                    x.reshape(b * t, hk * d),
                    ((0, 0), (0, lanes - hk * d))).astype(k_pool.dtype)
                phys = jnp.where(valid, phys, 0).reshape(-1)
                off = (positions % page).reshape(-1)
                return (k_pool.at[at, phys, off].set(row(k)),
                        v_pool.at[at, phys, off].set(row(v)))

        def walk(q, k_pool, v_pool, at, table, **more):
            """One row a slot through the kernel."""
            return diff_decode_attention(
                q[:, 0], k_pool, v_pool, at, table, positions[:, 0],
                live_len, h, **more)[:, None]

        def over_ring(li, q, k, v, cache):
            k_ring, v_ring = written(
                *cache[2:4], li, jnp.take_along_axis(
                    ring_tables, (positions // page) % ring, axis=1), k, v)
            if t == 1:
                with jax.named_scope(SCOPE_ATTN):
                    a = walk(q, k_ring, v_ring, li, ring_tables,
                             first=first[:, 0], ring=ring)
            else:
                with jax.named_scope(SCOPE_KV_GATHER):
                    rows = lambda pool: pool[li, ring_tables][
                        ..., :hk * d].reshape(b, -1, hk, d)
                    kc, vc = rows(k_ring), rows(v_ring)
                    k_pos = key_positions(kv_limit, ring, page, ring)
                with jax.named_scope(SCOPE_ATTN):
                    seen = (k_pos[:, None, :] <= positions[:, :, None]) & \
                        (k_pos[:, None, :] >= first[:, :, None])
                    ok = (k_pos <= kv_limit[:, None]) & \
                        (k_pos >= first[:, :1])
                    a = diff_attention(
                        q.reshape(b, t, h, d), kc,
                        jnp.where(ok[:, :, None, None], vc, 0), seen)
            return a, cache[:2] + (k_ring, v_ring) + cache[4:]

        def write(li, k, v, cache):
            return cache[:4] + written(
                *cache[4:], 0, jnp.take_along_axis(
                    tables, positions // page, axis=1), k, v)

        def shared(li, q, cache):
            if t != 1:
                raise NotImplementedError(
                    "a launch of several rows a slot attends to the shared "
                    "pool: the layers that read it run in decode alone")
            with jax.named_scope(SCOPE_ATTN), \
                    jax.named_scope(SCOPE_SHARED_KV):
                return walk(q, *cache[4:], 0, tables, name=SHARED_KERNEL)

        def full(li, q, k, v, cache):
            cache = write(li, k, v, cache)
            return shared(li, q, cache), cache

        return {"window": over_ring, "write": write, "shared": shared,
                "full": full}

    @staticmethod
    def by_role(roles):
        return lambda li, role, *args: roles[role](li, *args)

    def decode_mixer(self, state):
        pos, active = state["pos"], state["active"]
        idle, fresh = ~active, pos == 0

        def conv(li, u, conv_w, conv_b, cache):
            rows = cache[0]
            with jax.named_scope(SCOPE_SSM_CONV):
                old = jax.lax.dynamic_index_in_dim(rows, li, 0,
                                                   keepdims=False)
                c, new = causal_conv(
                    u, conv_w, conv_b,
                    jnp.where(fresh[:, None, None], 0, old))
                rows = jax.lax.dynamic_update_index_in_dim(
                    rows, jnp.where(idle[:, None, None], old, new), li, 0)
            return c, (rows,) + cache[1:]

        def scan(li, c, dt, A_t, B, C, D, cache):
            with jax.named_scope(SCOPE_STATE_UPDATE):
                y, S = selective_step(c[:, 0], dt[:, 0], A_t, B[:, 0],
                                      C[:, 0], D, cache[1], li, keep=idle,
                                      fresh=fresh)
            return y[:, None], cache[:1] + (S,) + cache[2:]

        return self.by_role({
            "conv": conv, "scan": scan,
            **self.paged(state["tables"], state["window_tables"],
                         pos[:, None], active[:, None], pos)})

    def prefill_mixer(self, where, posv, valid, start, n_valid):
        page_row, ring_row, slot = where

        def conv(li, u, conv_w, conv_b, cache):
            rows = cache[0]
            with jax.named_scope(SCOPE_STATE_RESET):
                rows0 = jnp.where(start == 0, 0, jax.lax.dynamic_slice(
                    rows, (li, slot, 0, 0), (1, 1) + rows.shape[2:])[0, 0])
            with jax.named_scope(SCOPE_SSM_CONV):
                c, rows1 = causal_conv(u[0], conv_w, conv_b, rows0, n_valid)
                rows = jax.lax.dynamic_update_slice(
                    rows, rows1[None, None], (li, slot, 0, 0))
            return c[None], (rows,) + cache[1:]

        def scan(li, c, dt, A_t, B, C, D, cache):
            S = cache[1]
            with jax.named_scope(SCOPE_STATE_RESET):
                S0 = jnp.where(start == 0, 0, jax.lax.dynamic_slice(
                    S, (li, slot, 0, 0), (1, 1) + S.shape[2:])[0, 0])
            with jax.named_scope(SCOPE_SSM_CHUNK):
                y, S1 = selective_scan_chunk(c[0], dt[0], A_t, B[0], C[0],
                                             D, S0, valid)
                S = jax.lax.dynamic_update_slice(S, S1[None, None],
                                                 (li, slot, 0, 0))
            return y[None], cache[:1] + (S,) + cache[2:]

        return self.by_role({
            "conv": conv, "scan": scan,
            **self.paged(page_row[None], ring_row[None], posv[None],
                         valid[None], (start + n_valid - 1)[None])})


KINDS["state+window+shared"] = StateWindowSharedKind
