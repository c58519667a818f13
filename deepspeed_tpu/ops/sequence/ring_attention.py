"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference snapshot predates sequence parallelism entirely (SURVEY
§2.3: its long-sequence story is block-sparse attention + activation
checkpointing); later DeepSpeed added Ulysses (all-to-all head/sequence
swap) and the community added ring attention. Both are first-class here
because they shape the long-context design:

  ring_attention    — Q stays put; KV blocks rotate around the `seq`
                      mesh axis via `ppermute` (ICI neighbor hops),
                      merging per-block softmax partials with the
                      online (m, l) recurrence. HBM per device is
                      O(T/S · d); total T is unbounded by chip memory.
  ulysses_attention — `all_to_all` swaps the sequence shard for a head
                      shard so every device runs *full-sequence*
                      attention on H/S heads (DeepSpeed-Ulysses
                      semantics), then swaps back. Cheaper collectives
                      for moderate T; requires heads % seq_par == 0.

Both run under `shard_map` over the `seq` axis and are transparent to
autodiff (the transpose of ppermute/all_to_all is the reverse
ppermute/all_to_all), so the backward pass is itself a ring/all-to-all
schedule — no hand-written backward communication.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

from deepspeed_tpu.ops import overlap as _overlap
from deepspeed_tpu.ops.transformer.flash_attention import (NEG_INF,
                                                           dense_attention)


def _ring_overlap_setup(k, v, axis_name, s_size, overlap_sched=None):
    """Resolve the `ring` overlap schedule and build the pre-rotated
    KV window (ops/overlap.py discipline).

    Returns (sched, win): `win` is None when the site is not
    overlapped (the caller keeps the baseline merge-then-permute
    scan); otherwise win[j] holds the block j hops back — the block
    step i+j consumes at step i — so each scan step issues ONE 1-hop
    `ppermute` of the window's deepest entry BEFORE the held block's
    merge consumes (`issue_distance` = window depth = permutes in
    flight; d-1 extra prologue rotations build the stagger). The merge
    order and block contents are identical to the baseline —
    scheduled-vs-unscheduled outputs are bit-exact (test-pinned)."""
    payload = 2 * int(np.prod(k.shape)) * np.dtype(k.dtype).itemsize
    sched = overlap_sched if overlap_sched is not None else \
        _overlap.schedule(_overlap.SITE_RING, payload_bytes=payload,
                          mesh={axis_name: s_size})
    if not sched["overlap"]:
        _overlap.record_inflight(_overlap.SITE_RING, axis_name, 0)
        return sched, None
    dist = min(max(int(sched["issue_distance"]), 1), s_size)
    win = [(k, v)]
    for j in range(1, dist):
        pj = [(i, (i + j) % s_size) for i in range(s_size)]
        win.append((jax.lax.ppermute(k, axis_name, pj),
                    jax.lax.ppermute(v, axis_name, pj)))
    # the send/recv window: `dist` (K, V) block pairs in flight
    _overlap.record_inflight(_overlap.SITE_RING, axis_name,
                             dist * payload)
    return sched, tuple(win)


def _block_attn_partial(q, k, v, sm_scale, mask=None):
    """Unmerged attention partial of one KV block: returns (numerator
    [B,Tq,H,D], m [B,H,Tq,1], l [B,H,Tq,1]) for online-softmax merging.

    XLA fallback path (scores materialize per ring step) — used when
    the local chunk doesn't meet the flash kernel's tiling contract;
    the primary path runs the Pallas flash kernel per ring step and
    merges normalized (out, lse) partials (`_ring_local_flash`)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)              # [B,H,Tq,1]
    # fully-masked rows: exp(NEG_INF - NEG_INF) would be 1; clamp m
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)              # [B,H,Tq,1]
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return num.astype(jnp.float32), m_safe, l


def _merge(acc, num, m_new, l_new):
    """Merge one block partial into the running (num, m, l)."""
    num_acc, m_acc, l_acc = acc
    m = jnp.maximum(m_acc, m_new)
    a1 = jnp.exp(m_acc - m)          # [B,H,Tq,1]
    a2 = jnp.exp(m_new - m)
    # broadcast [B,H,Tq,1] -> [B,Tq,H,1] for the numerator layout
    def bhq1_to_bqh1(x):
        return x.transpose(0, 2, 1, 3)
    num_out = num_acc * bhq1_to_bqh1(a1) + num * bhq1_to_bqh1(a2)
    l_out = l_acc * a1 + l_new * a2
    return num_out, m, l_out


def _ring_local_flash(q, k, v, axis_name, causal=True, sm_scale=None,
                      interpret=None, head_packing="auto",
                      overlap_sched=None):
    """Per-device ring body on the Pallas flash kernel: each ring step
    folds the held KV block into the running (out, lse) carry via
    `flash_attention_merge` — the softmax-partial merge
    (m = max(lse1, lse2); w_i = exp2(lse_i − m)) happens IN THE KERNEL
    EPILOGUE, so the per-step partial never round-trips HBM through an
    XLA elementwise merge chain (it previously cost ~5 extra passes
    over [B,Tl,H,D] fp32 per ring step).  Chunk-level causality picks
    the kernel variant per step: the diagonal chunk runs the causal
    kernel, strictly-lower chunks the non-causal one, upper chunks
    pass the carry through untouched (no kernel launch at all)."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention_merge
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, tl, h, d = q.shape

    o0 = jnp.zeros((b, tl, h, d), jnp.float32)
    lse0 = jnp.full((b, h, tl, 1), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % s_size) for i in range(s_size)]

    def merged(kb, vb, o, lse, step_causal):
        return flash_attention_merge(
            q, kb, vb, o, lse, causal=step_causal, sm_scale=sm_scale,
            interpret=interpret, head_packing=head_packing)

    def fold(kb, vb, o, lse, step_idx):
        src = (my_idx - step_idx) % s_size
        if causal:
            def diag(args):
                return merged(*args, True)

            def full(args):
                return merged(*args, False)

            def none(args):
                return args[2], args[3]

            branch = jnp.where(src == my_idx, 0,
                               jnp.where(src < my_idx, 1, 2))
            return jax.lax.switch(branch, [diag, full, none],
                                  (kb, vb, o, lse))
        return merged(kb, vb, o, lse, False)

    _sched, win = _ring_overlap_setup(k, v, axis_name, s_size,
                                      overlap_sched)
    if win is None:
        def step(carry, step_idx):
            o, lse, kb, vb = carry
            o, lse = fold(kb, vb, o, lse, step_idx)
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            return (o, lse, kb, vb), None

        (o, _, _, _), _ = jax.lax.scan(
            step, (o0, lse0, k, v), jnp.arange(s_size))
    else:
        def step(carry, step_idx):
            o, lse, blocks = carry
            kb, vb = blocks[0]
            nk = jax.lax.ppermute(blocks[-1][0], axis_name, perm)
            nv = jax.lax.ppermute(blocks[-1][1], axis_name, perm)
            # issue-early: chunk k+1's permute must be in flight
            # before chunk k's flash-merge consumes the held block
            kb, vb = _overlap.fence((kb, vb), (nk, nv))
            o, lse = fold(kb, vb, o, lse, step_idx)
            return (o, lse, blocks[1:] + ((nk, nv),)), None

        (o, _, _), _ = jax.lax.scan(
            step, (o0, lse0, win), jnp.arange(s_size))
    return o.astype(q.dtype)


def ring_attention_local(q, k, v, axis_name, causal=True, sm_scale=None,
                         overlap_sched=None):
    """Per-device body (inside shard_map): local Q [B,Tl,H,D] attends to
    the full sequence as KV blocks rotate around `axis_name`."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, tl, h, d = q.shape

    num0 = jnp.zeros((b, tl, h, d), jnp.float32)
    m0 = jnp.full((b, h, tl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tl, 1), jnp.float32)

    perm = [(i, (i + 1) % s_size) for i in range(s_size)]

    def fold(kb, vb, acc, step_idx):
        # kv block currently held originated at device (my_idx - step)
        src = (my_idx - step_idx) % s_size
        if causal:
            # chunk-causal: attend iff src < my_idx; diagonal chunk uses
            # the in-chunk triangular mask
            rows = jax.lax.broadcasted_iota(jnp.int32, (tl, tl), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (tl, tl), 1)
            tri = rows >= cols
            full = jnp.ones((tl, tl), bool)
            none = jnp.zeros((tl, tl), bool)
            mask2d = jnp.where(src == my_idx, tri,
                               jnp.where(src < my_idx, full, none))
            mask = mask2d[None, None, :, :]
        else:
            mask = None
        blk_num, blk_m, blk_l = _block_attn_partial(q, kb, vb, sm_scale,
                                                    mask)
        return _merge(acc, blk_num, blk_m, blk_l)

    _sched, win = _ring_overlap_setup(k, v, axis_name, s_size,
                                      overlap_sched)
    if win is None:
        def step(carry, step_idx):
            num, m, l, kb, vb = carry
            num, m, l = fold(kb, vb, (num, m, l), step_idx)
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            return (num, m, l, kb, vb), None

        (num, m, l, _, _), _ = jax.lax.scan(
            step, (num0, m0, l0, k, v), jnp.arange(s_size))
    else:
        def step(carry, step_idx):
            num, m, l, blocks = carry
            kb, vb = blocks[0]
            nk = jax.lax.ppermute(blocks[-1][0], axis_name, perm)
            nv = jax.lax.ppermute(blocks[-1][1], axis_name, perm)
            # issue-early: the next hop's send is in flight before the
            # held block's merge consumes
            kb, vb = _overlap.fence((kb, vb), (nk, nv))
            num, m, l = fold(kb, vb, (num, m, l), step_idx)
            return (num, m, l, blocks[1:] + ((nk, nv),)), None

        (num, m, l, _), _ = jax.lax.scan(
            step, (num0, m0, l0, win), jnp.arange(s_size))
    l = jnp.maximum(l, 1e-30)
    out = num / l.transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def _mesh_targets_tpu(mesh):
    """Whether the MESH's devices are TPUs. The auto-selection keys on
    this rather than jax.default_backend() so ahead-of-time lowering for
    a TPU target from a CPU host process still picks the flash body —
    default_backend() reports the HOST's backend at trace time, which
    silently chose the XLA fallback under cross-backend AOT."""
    try:
        return mesh.devices.flat[0].platform == "tpu"
    except Exception:  # ds-lint: allow[BROADEXC] AbstractMesh / device-less mesh variants have no .devices; fall back to the host backend
        return jax.default_backend() == "tpu"


def ring_attention(q, k, v, mesh: Mesh, axis_name="seq", causal=True,
                   sm_scale=None, use_flash=None, interpret=None,
                   head_packing="auto"):
    """Ring attention over [B, T, H, D] with T sharded on `axis_name`.

    use_flash=None auto-selects the per-step Pallas flash body when the
    mesh's devices are TPUs (keyed on the MESH target, not
    jax.default_backend()) and the LOCAL chunk meets the kernel's
    tiling contract (chunk length a multiple of 128, head dim a
    multiple of 64); otherwise the XLA online-softmax fallback runs.
    The flash body merges each step's (out, lse) partial in the kernel
    epilogue (`flash_attention_merge`) and packs d=64 head pairs into
    K=128 contractions per `head_packing` ("auto"|"packed"|"off").

    **Cross-backend AOT lowering (CPU host → TPU target): pass
    `use_flash=True` explicitly.** The auto-selection inspects the
    mesh's devices AT TRACE TIME; device-bearing meshes resolve the
    TPU target correctly even from a CPU host process, but abstract /
    device-less meshes (e.g. `jax.sharding.AbstractMesh` under
    `jax.export`-style lowering) fall back to the HOST backend and
    would silently pick the XLA body for a TPU executable.  interpret
    forwards to the kernel so CPU tests exercise the same code path.
    (Same selection and the same AOT caveat apply to
    `ulysses_attention`.)"""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention_usable

    s_size = mesh.shape[axis_name]
    b, t, h, d = q.shape
    if t % s_size:
        raise ValueError(
            f"sequence length {t} must be divisible by the '{axis_name}' "
            f"axis size {s_size} (pad the sequence; shard_map would "
            "otherwise fail with an opaque sharding error)")
    local_example = jax.ShapeDtypeStruct((b, t // s_size, h, d), q.dtype)
    if use_flash is None:
        use_flash = (_mesh_targets_tpu(mesh) or bool(interpret)) \
            and flash_attention_usable(local_example, True)
    if use_flash:
        body = functools.partial(_ring_local_flash, axis_name=axis_name,
                                 causal=causal, sm_scale=sm_scale,
                                 interpret=interpret,
                                 head_packing=head_packing)
    else:
        body = functools.partial(ring_attention_local, axis_name=axis_name,
                                 causal=causal, sm_scale=sm_scale)
    spec = PartitionSpec(None, axis_name, None, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention_local(q, k, v, axis_name, causal=True, sm_scale=None,
                            attn_fn=None):
    """Per-device body: all-to-all swaps the local sequence shard for a
    head shard, runs full-sequence attention on H/S heads, swaps back
    (DeepSpeed-Ulysses dataflow)."""
    s_size = jax.lax.psum(1, axis_name)
    b, tl, h, d = q.shape
    assert h % s_size == 0, \
        f"heads {h} must be divisible by seq-parallel degree {s_size}"

    def seq_to_head(x):
        # [B, Tl, H, D] -> [B, Tl*S, H/S, D]: trade head shards for the
        # full sequence (source devices concatenate in ring order, which
        # is global sequence order)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def head_to_seq(x):
        # [B, T, H/S, D] -> [B, Tl, H, D]: the inverse swap
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attn_fn is None:
        attn_fn = functools.partial(dense_attention, causal=causal,
                                    sm_scale=sm_scale)
    out = attn_fn(qg, kg, vg)                    # [B, T, H/S, D]
    return head_to_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name="seq", causal=True,
                      sm_scale=None, use_flash=None, head_packing="auto"):
    """Ulysses sequence-parallel attention over [B, T, H, D] with T
    sharded on `axis_name`.

    Cross-backend AOT lowering (CPU host → TPU target) must pass
    `use_flash=True` explicitly — see `ring_attention`'s note: the
    auto-selection keys on the mesh's devices at trace time and a
    device-less mesh falls back to the host backend."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention, flash_attention_usable)

    s_size = mesh.shape[axis_name]
    b, t, h, d = q.shape
    if t % s_size:
        raise ValueError(
            f"sequence length {t} must be divisible by the '{axis_name}' "
            f"axis size {s_size} (pad the sequence)")
    if h % s_size:
        raise ValueError(
            f"ulysses_attention needs heads {h} divisible by the "
            f"'{axis_name}' axis size {s_size} (the all-to-all trades "
            "a head shard for the sequence shard); use ring_attention "
            "for indivisible head counts")

    attn_fn = None
    if use_flash is None:
        # keyed on the mesh target, not default_backend() — see
        # _mesh_targets_tpu (cross-backend AOT lowering)
        use_flash = _mesh_targets_tpu(mesh)
    if use_flash:
        def attn_fn(qg, kg, vg):
            if flash_attention_usable(qg, True):
                return flash_attention(qg, kg, vg, causal=causal,
                                       sm_scale=sm_scale,
                                       head_packing=head_packing)
            return dense_attention(qg, kg, vg, causal=causal,
                                   sm_scale=sm_scale)

    spec = PartitionSpec(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          causal=causal, sm_scale=sm_scale,
                          attn_fn=attn_fn),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
