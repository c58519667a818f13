"""Flash-attention kernel numerics vs the dense XLA reference
(parity target: ref tests/unit/test_cuda_forward.py / test_cuda_backward.py
which sweep shapes and compare the fused kernel against a vendored torch
layer). Kernels run in Pallas interpreter mode on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.flash_attention import (
    dense_attention, flash_attention, flash_attention_qkv,
    flash_attention_qkv_usable, flash_attention_rematerializable,
    flash_attention_usable)
from deepspeed_tpu.models.gpt2 import causal_attention_xla


def qkv(b, t, h, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(b, t, h, d), dtype) for _ in range(3)]


def dense_reference(q, k, v, causal):
    if causal:
        return causal_attention_xla(q, k, v)
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,t,h,d", [
    (2, 256, 4, 64), (1, 384, 2, 128),
    (1, 128, 25, 64),   # GPT-2 1.5B's heads: C = 1,600 is 12.5 column
                        # tiles, the last one holds one head
    (2, 256, 3, 64),    # H·D = 192, no multiple of 128, two T blocks
])
def test_forward_matches_dense(b, t, h, d, causal):
    q, k, v = qkv(b, t, h, d)
    ref = dense_reference(q, k, v, causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,t,h,d,blocks,packing", [
    (1, 256, 2, 64, 128, "off"),
    (1, 256, 5, 64, 128, "packed"),   # odd heads, the two sweep kernels
    (1, 256, 5, 64, 128, "off"),
    (2, 128, 25, 64, None, "packed"),  # C = 1,600, the one-pass kernel
    (2, 128, 25, 64, None, "off"),
    (1, 256, 2, 128, 128, "off"),     # D = 128: a head a column tile
    (2, 128, 2, 128, None, "off"),
])
def test_grads_match_dense(b, t, h, d, blocks, packing, causal):
    q, k, v = qkv(b, t, h, d, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=blocks, block_k=blocks,
                                       head_packing=packing) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_reference(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


def test_uneven_blocks():
    """block_q != block_k and T not a multiple of the default block."""
    q, k, v = qkv(1, 512, 2, 64, seed=5)
    ref = dense_reference(q, k, v, True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_usability_gate():
    q = jnp.zeros((2, 256, 4, 64))
    assert flash_attention_usable(q, True)
    assert not flash_attention_usable(q, False)          # dropout active
    assert not flash_attention_usable(jnp.zeros((2, 100, 4, 64)), True)
    assert not flash_attention_usable(jnp.zeros((2, 256, 4, 48)), True)
    # 128 <= T < 1024 but T % 128 != 0: _fit_block would clamp the tile
    # to T itself, an unaligned lane dim Mosaic rejects on real TPU
    # (advisor r4) — the gate must refuse it
    assert not flash_attention_usable(jnp.zeros((2, 136, 4, 64)), True)
    assert flash_attention_usable(jnp.zeros((2, 640, 4, 64)), True)
    # a head is half a column tile (two of 64 side by side) or whole
    # tiles (a multiple of 128): 192 is neither
    assert flash_attention_usable(jnp.zeros((2, 256, 4, 128)), True)
    assert flash_attention_usable(jnp.zeros((2, 256, 4, 256)), True)
    assert not flash_attention_usable(jnp.zeros((2, 256, 4, 192)), True)


def test_jit_and_dtype_preserved():
    q, k, v = qkv(1, 256, 2, 64, dtype=jnp.bfloat16)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c))(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert out.shape == q.shape


def test_fused_single_tile_backward_parity():
    """Default blocks at T <= _DEFAULT_BLOCK route the backward through
    the fused one-pass kernel (nq == nk == 1) — pin its gradient parity
    against the dense reference (review r4: the path was untested)."""
    B, T, H, D = 2, 256, 4, 64
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)) * 0.3, jnp.bfloat16)
    for causal in (True, False):
        # no explicit blocks: min(_DEFAULT_BLOCK, T) == T == one tile
        gf = jax.grad(lambda q: flash_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum())(q)
        gd = jax.grad(lambda q: dense_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum())(q)
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gd, np.float32),
            atol=0.02, rtol=0.05)


def test_block_fit_fallback_lengths():
    """T divisible by 512 but not 1024 (1536, 2560) must still ride the
    kernel via the power-of-two block shrink, not fall back to dense or
    assert (review r4)."""
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_usable, _fit_block)
    assert _fit_block(1024, 1536) == 512
    assert _fit_block(1024, 2560) == 512
    assert _fit_block(1024, 384) == 384   # clamp: 384 divides itself
    B, H, D = 1, 2, 64
    for T in (1536, 2560):
        q = jnp.asarray(np.zeros((B, T, H, D)), jnp.bfloat16)
        assert flash_attention_usable(q, no_dropout=True), T
    out = flash_attention(
        jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, 1536, 2, 64)) * 0.3, jnp.bfloat16),
        jnp.asarray(np.zeros((1, 1536, 2, 64)), jnp.bfloat16),
        jnp.asarray(np.zeros((1, 1536, 2, 64)), jnp.bfloat16),
        causal=True)
    assert out.shape == (1, 1536, 2, 64)


# ----------------------------------------------------------------------
# (out, lse) form — the ring-attention partial (VERDICT r4 #4)
# ----------------------------------------------------------------------
def _lse_reference(q, k, v, causal):
    """Dense (out, log2-space lse) reference."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    lse_nat = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     jnp.exp(s - lse_nat).astype(v.dtype), v)
    return out, lse_nat * np.log2(np.e)        # kernel lse is log2-space


@pytest.mark.parametrize("causal", [True, False])
def test_with_lse_forward_matches_dense(causal):
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention_with_lse
    q, k, v = qkv(1, 256, 2, 64, seed=7)
    out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                        block_q=128, block_k=128)
    ref_out, ref_lse = _lse_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_with_lse_grads_flow_through_lse(causal):
    """The sharp edge: a loss consuming BOTH outputs must produce the
    same q/k/v grads as the dense reference — the lse cotangent enters
    the backward kernels as a delta shift (flash_attention.py _bwd)."""
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention_with_lse
    q, k, v = qkv(1, 256, 2, 64, seed=11)

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                            block_q=128, block_k=128)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        out, lse = _lse_reference(q, k, v, causal)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


# ----------------------------------------------------------------------
# the `c_attn` product whole: q, k, v read where the projection wrote them
# ----------------------------------------------------------------------
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("b,t,h,d,blocks,packing", [
    (2, 128, 4, 64, None, "packed"),   # one T tile: the one-pass backward
    (2, 128, 4, 64, None, "off"),
    (1, 256, 2, 64, 128, "packed"),    # the two sweep kernels
    (1, 256, 2, 64, 128, "off"),
    (2, 128, 2, 128, None, "off"),     # D = 128
])
def test_the_product_entry_is_the_split_entry_bit_for_bit(
        b, t, h, d, blocks, packing, remat):
    rng = np.random.RandomState(23)
    product = jnp.asarray(rng.randn(b, t, 3 * h * d), jnp.float32)
    assert flash_attention_qkv_usable(product, h, True)
    split_entry = flash_attention_rematerializable if remat \
        else flash_attention

    def whole(x):
        out = flash_attention_qkv(x, h, block_q=blocks, block_k=blocks,
                                  head_packing=packing,
                                  rematerializable=remat)
        return jnp.sum(jnp.sin(out)), out

    def split(x):
        q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(x, 3, axis=-1))
        out = split_entry(q, k, v, block_q=blocks, block_k=blocks,
                          head_packing=packing)
        return jnp.sum(jnp.sin(out)), out

    (_, out_w), grad_w = jax.value_and_grad(whole, has_aux=True)(product)
    (_, out_s), grad_s = jax.value_and_grad(split, has_aux=True)(product)
    assert out_w.shape == (b, t, h, d)
    np.testing.assert_array_equal(np.asarray(out_w), np.asarray(out_s))
    np.testing.assert_array_equal(np.asarray(grad_w), np.asarray(grad_s))


def test_the_product_entry_is_offered_where_the_shape_allows():
    # 4 heads of 64: two whole column tiles, k and v start on a tile
    assert flash_attention_qkv_usable(jnp.zeros((2, 256, 3 * 256)), 4, True)
    assert not flash_attention_qkv_usable(
        jnp.zeros((2, 256, 3 * 256)), 4, False)          # dropout active
    # 25 heads of 64: C = 1,600 is 12.5 tiles, k starts half a tile off
    assert not flash_attention_qkv_usable(
        jnp.zeros((2, 256, 3 * 1600)), 25, True)
    assert not flash_attention_qkv_usable(
        jnp.zeros((2, 100, 3 * 256)), 4, True)           # the kernel's own
    assert flash_attention_qkv_usable(jnp.zeros((2, 256, 3 * 256)), 2, True)
    with pytest.raises(ValueError, match="where it lies"):
        flash_attention_qkv(jnp.zeros((2, 256, 3 * 1600)), 25)


@pytest.mark.parametrize("h", [4, 2, 5, 25], ids=[
    "whole-tiles", "a-head-a-shard", "odd-heads-stay-whole",
    "25-heads-stay-whole"])
def test_heads_divide_over_the_mesh_columns(h):
    """A 2 x 2 mesh: the batch over its rows, the heads over its columns
    (`per_device`). With 2 heads each shard holds ONE, half a column
    tile: its launch runs the edge tile, not a fallback. With 5 or 25
    the columns divide H·D and not H: a shard of half of it would hold
    2.5 or 12.5 heads, so the columns divide nothing and every device
    of a row works all the heads. The product entry steps aside, since
    a shard of [q | k | v] is no [q | k | v]."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.runtime.mesh import build_mesh

    mesh = build_mesh({"pipe": 1, "data": 2, "model": 2},
                      devices=jax.devices()[:4])
    q, k, v = qkv(4, 128, h, 64, seed=29)

    def loss(q, k, v):
        out = flash_attention(q, k, v, head_packing="packed")
        return jnp.sum(jnp.sin(out))

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    want = grad(q, k, v)
    heads = NamedSharding(
        mesh, P("data", None, "model" if h % 2 == 0 else None, None))
    got = grad(*(jax.device_put(x, heads) for x in (q, k, v)))
    # the loss is a float32 sum of B·T·H·D terms, added in another order
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)
    assert got[1][0].sharding.is_equivalent_to(heads, 4)

    product = jax.device_put(
        jnp.zeros((4, 128, 3 * h * 64)),
        NamedSharding(mesh, P("data", None, "model")))
    text = str(jax.make_jaxpr(loss)(
        *(jax.device_put(x, heads) for x in (q, k, v))))
    assert "shard_map" in text and "flash_fwd_packed" in text
    seen = []
    jax.jit(lambda x: seen.append(
        flash_attention_qkv_usable(x, h, True)) or x)(product)
    assert seen == [False]
