"""The seventh kind of cache, "paged|state": a layer keeps K/V pages OR
a Mamba-2 state (or nothing), and the two are COUNTED APART: a slot
owns a state in `state_layers` layers and pages in `paged_layers`
others (`models/nemotron_h.py`: 23 and 6 of 52 layers; the rest are
expert layers and keep nothing). It registers itself in `engine.KINDS`
as `latent_kind.py` and `hybrid_kind.py` do (`deepspeed_tpu.inference`
imports it beside the engine, whose own lines stay where the other
models' cached programs have them).

Nothing of either half is new. The pages are `PagedKind`'s (its pools,
its tables, its one mixer) and the state is `PagedStateKind`'s state
half (the convolution's rows and the state matrix, its two state
mixers), behind the same `kv_cache.PagedStateCache`; what "paged+state"
ties together by ONE layer index is here untied: each half's arrays
and manager are sized by the number of layers that keep it, and the
model's block names a layer by its index among the layers of its own
kind (`at`), by role:

    mix(li, "state", at, xBC, dt, A, D, conv_w, conv_b, cache) -> (y, cache)
    mix(li, "pages", at, q, k, v, cache)                       -> (o, cache)
    mix(li, "live")    the rows that are a request's [B, T]

over cache = (k_pool, v_pool, conv rows, state), whole arrays in the
layer scans' carry (`li`, the step of the scan, finds nothing here). A
layer's kind is a Python word in the model, so no `lax.cond` carries a
state and a pool through a branch it does not take.
"""

import types

from deepspeed_tpu.inference.engine import (KINDS, PagedKind, PagedStateKind,
                                            make_state_cache)
from deepspeed_tpu.inference.kv_cache import PagedStateCache
from deepspeed_tpu.utils.scopes import SCOPES_LAYERED  # noqa: F401


def layers_of(model_config, n_layer):
    """What `PagedKind` and `make_state_cache` read off a model config,
    with `n_layer` the layers that keep THEIR half."""
    mc = model_config
    return types.SimpleNamespace(
        n_layer=n_layer, n_head=mc.n_head, n_kv_head=mc.n_kv_head,
        head_dim=mc.head_dim, dtype=mc.dtype,
        state_slot_shapes=mc.state_slot_shapes)


class PagedOrStateKind(PagedStateKind):
    """`PagedStateKind`'s two halves, each over its own count of
    layers (the config's `paged_layers` and `state_layers`), handed to
    the block by role. Admission reserves a request's pages and a slot
    of state (`PagedStateCache`); a slot's state is reset at its first
    chunk and kept while idle, as there."""

    def __init__(self, model_config, config, max_seq_len):
        super().__init__(model_config, config, max_seq_len)
        self.paged = PagedKind(
            layers_of(model_config, model_config.paged_layers), config,
            max_seq_len)

    def make_cache(self, ledger):
        return PagedStateCache(
            self.paged.make_cache(ledger),
            make_state_cache(layers_of(self.mc, self.mc.state_layers),
                             self.cfg, self.max_seq_len, ledger),
            kind="paged|state")

    # the two halves' mixers as they are: `by_role` hands them out
    both = staticmethod(lambda paged_mix, state_mix: (paged_mix, state_mix))

    @staticmethod
    def by_role(halves, live):
        paged_mix, state_mix = halves
        n = len(PagedKind.keys)

        def mix(li, role, at=None, *args):
            if role == "live":
                return live
            *args, cache = args
            if role == "pages":
                o, pools = paged_mix(at, *args, cache[:n])
                return o, pools + cache[n:]
            y, state = state_mix(at, *args, cache[n:])
            return y, cache[:n] + state
        return mix

    def decode_mixer(self, state):
        return self.by_role(super().decode_mixer(state),
                            state["active"][:, None])

    def prefill_mixer(self, where, posv, valid, start, n_valid):
        return self.by_role(
            super().prefill_mixer(where, posv, valid, start, n_valid),
            valid[None])


KINDS["paged|state"] = PagedOrStateKind
