"""The fifth kind of cache, "paged+latent": one pool of latent rows
behind the paged kind's tables. It registers itself in
`engine.KINDS`; `deepspeed_tpu.inference` imports it beside the
engine, whose own lines stay where the other models' cached programs
have them (the serving programs are cached with their name stacks in
the key, `compile_registered`)."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import KINDS, PagedKind
from deepspeed_tpu.inference.kv_cache import LatentKVCache
from deepspeed_tpu.ops.transformer import latent_attention as latent
from deepspeed_tpu.utils.scopes import (  # noqa: F401
    SCOPE_ATTN, SCOPE_KV_WRITE, SCOPES_LATENT_MOE)


class PagedLatentKind:
    """ONE page pool of latent rows ([L, P, page, lanes], a token's
    [c~ ; k_rope] of one layer on the lanes: the config's `latent_row`
    values, zeros up to the lane tile) behind `PagedKind`'s tables
    (`kv_cache.LatentKVCache`), for a model that attends by multi-head
    latent attention in its absorbed form (`models/sarvam_mla.py`):
    the block hands over every head's scaled query against that row,
    q [B, T, H, row], and the rows' one latent row each [B, T, row],
    and gets back the attended rows' first `kv_lora_rank` values a
    head, [B, T, H, rank], and which rows are a request's (`valid`:
    not an idle slot's, not a chunk's pad rows), which the block keeps
    out of its experts' products.

    Both programs attend through a kernel that walks the slot's table
    and reads the pages where they lie, the page block keys and values
    at once: one row a slot (decode) through `latent_decode_attention`,
    a prefill chunk through `latent_prefill_attention` (the 64 heads'
    rows of 16 tokens a query tile, scores and accumulator in VMEM;
    absorbed like decode: the products are 3.4 times the expanded
    form's and nothing is expanded; PERF.md has the chip's reading of
    both). Off a TPU both take the XLA form `latent_attention`
    (`latent.usable`: ISSUE 39 keeps the kernels' oracle as the CPU's
    path, where `PagedKind` interprets its kernel; tests run both
    kernels interpreted through the engine)."""
    keys = ("latent_pool",)

    def __init__(self, model_config, config, max_seq_len):
        if config.spec_enabled:
            raise ValueError(
                "inference.speculative.enabled: the absorbed path under "
                "speculation (a verify launch's k + 1 rows a slot through "
                "the decode kernel) does not exist yet")
        self.mc, self.cfg = model_config, config
        self.max_pages = -(-max_seq_len // config.kv_page_size)
        self.dtype = jnp.dtype(model_config.dtype)    # the pool's

    def make_cache(self, ledger):
        mc, cfg = self.mc, self.cfg
        return LatentKVCache(
            mc.latent_row, n_layer=mc.n_layer, num_pages=cfg.kv_num_pages,
            page_size=cfg.kv_page_size, max_slots=cfg.max_slots,
            max_pages_per_slot=self.max_pages, dtype=np.dtype(self.dtype),
            ledger=ledger)

    def fresh(self, cache):
        return {"latent_pool": jnp.zeros(cache.pool_shape(self.mc.n_layer),
                                         self.dtype),
                **self.tables(cache)}

    tables = staticmethod(PagedKind.tables)

    def mixer(self, tables, positions, valid, kv_limit):
        """The mixer of both programs, for rows at `positions` [B, T]
        of slots whose pages `tables` [B, max_pages] name: the rows'
        latent rows are scattered into the pool (rows with valid=False
        to scratch page 0), then the queries attend."""
        page_size, rank = self.cfg.kv_page_size, self.mc.kv_lora_rank

        def mix(li, q, row, pools):
            (pool,) = pools
            b, t, width = row.shape
            with jax.named_scope(SCOPE_KV_WRITE):
                phys = jnp.take_along_axis(tables, positions // page_size,
                                           axis=1)
                phys = jnp.where(valid, phys, 0).reshape(-1)
                off = (positions % page_size).reshape(-1)
                pool = pool.at[li, phys, off].set(jnp.pad(
                    row.reshape(b * t, width),
                    ((0, 0), (0, pool.shape[-1] - width))).astype(pool.dtype))
            live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)
            if not latent.usable():
                o = latent.latent_attention(q, pool, li, tables, positions,
                                            live_len, rank)
            else:
                with jax.named_scope(SCOPE_ATTN):
                    if t == 1:
                        o = latent.latent_decode_attention(
                            q[:, 0], pool, li, tables, live_len,
                            rank)[:, None]
                    else:
                        o = latent.latent_prefill_attention(
                            q, pool, li, tables, positions, live_len, rank)
            return o, (pool,), valid
        return mix

    decode_mixer = PagedKind.decode_mixer
    prefill_mixer = PagedKind.prefill_mixer


KINDS["paged+latent"] = PagedLatentKind
