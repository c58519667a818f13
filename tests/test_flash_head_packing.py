"""Head-packed vs unpacked flash-kernel parity (ISSUE 4 satellite).

Both kernels read the same 128-lane column tile of [B, T, H·D], two
d=64 heads side by side: the packed one works it whole, with
block-diagonal K/V so every score/output contraction runs at the MXU's
native K=128, the unpacked one its two halves in turn
(flash_attention.py module docstring). The zero lanes contribute exact
+0 to every fp32 partial sum, so packed and unpacked must agree to
fp32 roundoff — forward AND backward — across head counts (even, and
odd, whose last tile holds one head), seq lengths that are and are
not multiples of the default block, causal/bidirectional, and
bf16/fp32. Everything runs the real Pallas kernels in interpreter mode
on CPU (head_packing="packed" forces the packed body; "auto" stays
unpacked off-TPU by design)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.flash_attention import (
    _resolve_head_packing, flash_attention, flash_attention_merge,
    flash_attention_with_lse)


def qkv(b, t, h, d, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(b, t, h, d), dtype) for _ in range(3)]


def ab(x, dtype=np.float32):
    return np.asarray(x, dtype)


# fp32 accumulates identically in both kernels (the packed zero lanes
# add exact +0); bf16 pays one output-rounding step per kernel, so the
# two paths can land one ULP apart after the fp32->bf16 cast.
TOL = {jnp.float32: dict(atol=2e-6, rtol=2e-6),
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "bidir"])
@pytest.mark.parametrize("b,t,h", [
    (2, 128, 2),    # even H, single 128 tile
    (1, 256, 3),    # ODD H -> the last column tile holds one head
    (1, 384, 2),    # T=384: NOT a multiple of the 1024 default block
                    # (_fit_block shrinks to 128-wide tiles)
    (2, 128, 25),   # GPT-2 1.5B's heads: C = 1,600, 12.5 column tiles
])
def test_forward_parity(b, t, h, causal, dtype):
    q, k, v = qkv(b, t, h, 64, dtype)
    packed = flash_attention(q, k, v, causal=causal, interpret=True,
                             head_packing="packed")
    unpacked = flash_attention(q, k, v, causal=causal, interpret=True,
                               head_packing="off")
    assert packed.dtype == unpacked.dtype == dtype
    np.testing.assert_allclose(ab(packed), ab(unpacked), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "bidir"])
@pytest.mark.parametrize("b,t,h", [
    (2, 128, 2),    # single-tile -> fused one-pass backward kernel
    (1, 256, 3),    # odd H + multi-tile -> dkv+dq sweep kernels
    (2, 128, 25),   # C = 1,600 through the one-pass kernel
])
def test_backward_parity(b, t, h, causal, dtype):
    q, k, v = qkv(b, t, h, 64, dtype, seed=3)

    def loss(hp):
        def f(q, k, v):
            out = flash_attention(q, k, v, causal=causal, interpret=True,
                                  head_packing=hp)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for g_p, g_u in zip(loss("packed"), loss("off")):
        assert g_p.dtype == g_u.dtype == dtype
        np.testing.assert_allclose(ab(g_p), ab(g_u), **TOL[dtype])


@pytest.mark.parametrize("h", [3, 4])
def test_lse_parity(h):
    """The saved logsumexp rows (log2 space) drive both backward
    kernels and the ring merge — they must match too, including on the
    odd head's neighbors in the [B, T, H] block."""
    q, k, v = qkv(1, 256, h, 64, seed=5)
    out_p, lse_p = flash_attention_with_lse(
        q, k, v, causal=True, interpret=True, head_packing="packed")
    out_u, lse_u = flash_attention_with_lse(
        q, k, v, causal=True, interpret=True, head_packing="off")
    np.testing.assert_allclose(ab(out_p), ab(out_u), atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(ab(lse_p), ab(lse_u), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("h", [2, 3], ids=["even", "odd"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
def test_merge_parity(causal, h):
    """Ring-step epilogue merge: packed vs unpacked kernels folding the
    same prior (out, lse) partial must agree in the merged result AND
    in the gradients flowing to the prior partial (the ring backward
    differentiates through every step's carry)."""
    b, t = 1, 256
    q, k, v = qkv(b, t, h, 64, seed=7)
    k2, v2 = qkv(b, t, h, 64, seed=11)[:2]
    prev_out, prev_lse = flash_attention_with_lse(
        q, k2, v2, causal=False, interpret=True, head_packing="off")

    def merged(hp):
        def f(q, k, v, po, pl):
            o, l = flash_attention_merge(q, k, v, po, pl, causal=causal,
                                         interpret=True, head_packing=hp)
            return jnp.sum(o ** 2) + jnp.sum(l ** 2)
        out = flash_attention_merge(q, k, v, prev_out, prev_lse,
                                    causal=causal, interpret=True,
                                    head_packing=hp)
        grads = jax.grad(f, argnums=(0, 1, 2, 3, 4))(
            q, k, v, prev_out, prev_lse)
        return out, grads

    (o_p, l_p), g_p = merged("packed")
    (o_u, l_u), g_u = merged("off")
    np.testing.assert_allclose(ab(o_p), ab(o_u), atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(ab(l_p), ab(l_u), atol=2e-6, rtol=2e-6)
    for a, b_ in zip(g_p, g_u):
        np.testing.assert_allclose(ab(a), ab(b_), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("b,t,h", [(2, 128, 4), (2, 128, 5)],
                         ids=["even", "odd"])
def test_one_tile_packed_is_unpacked_bit_for_bit(b, t, h):
    """At one T tile (what the training cells run) the two kernels do
    not merely agree: the packed contraction's zero blocks add exact
    +0, so outputs, lse and all three gradients are the same bits."""
    q, k, v = qkv(b, t, h, 64, seed=19)

    def run(hp):
        def f(q, k, v):
            out, lse = flash_attention_with_lse(
                q, k, v, causal=True, interpret=True, head_packing=hp)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(lse)), (out, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    for a, b_ in zip(jax.tree_util.tree_leaves(run("packed")),
                     jax.tree_util.tree_leaves(run("off"))):
        np.testing.assert_array_equal(ab(a), ab(b_))


def test_packed_matches_dense_reference():
    """Not just self-consistency: the packed kernel against the plain
    XLA softmax(QK^T)V reference."""
    q, k, v = qkv(1, 256, 4, 64, seed=13)
    scale = 1.0 / np.sqrt(64)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((256, 256), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          head_packing="packed")
    np.testing.assert_allclose(ab(out), ab(ref), atol=2e-5, rtol=2e-5)


def test_resolution_rules():
    # d != 64 cannot pack: forcing is an error, auto falls back
    with pytest.raises(ValueError, match="head_dim 64"):
        _resolve_head_packing("packed", 128, False)
    assert not _resolve_head_packing("auto", 128, False)
    # interpreter path (CPU CI) stays unpacked under auto, packs on TPU
    assert not _resolve_head_packing("auto", 64, True)
    assert _resolve_head_packing("auto", 64, False)
    assert _resolve_head_packing("packed", 64, True)
    assert not _resolve_head_packing("off", 64, False)
    with pytest.raises(ValueError, match="head_packing"):
        _resolve_head_packing("sideways", 64, False)
    # d=128 (no packing possible) still runs fine under auto
    q, k, v = qkv(1, 128, 2, 128, seed=17)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          head_packing="auto")
    assert out.shape == (1, 128, 2, 128)


def test_the_chip_probe_compares_what_it_says_at_toy_size():
    """`tests/perf/flash_kernel_ab.py` is how packed against unpacked
    (and this launcher against a parent checkout's) is re-measured on
    the chip; here its comparisons run in the interpreter, timing
    nothing, with this tree standing in for the parent's checkout."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "flash_kernel_ab", os.path.join(repo, "tests/perf/flash_kernel_ab.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)

    shapes = {name: probe.TOY[name] for name in ("gpt2-350m", "gpt2-1.5b")}
    assert [s[2] * s[3] % 128 for s in shapes.values()] == [0, 64]
    rows = probe.compare(shapes, parent=probe.load_parent(repo),
                         interpret=True)
    assert set(rows["gpt2-350m"]) == {"packed", "off", "product",
                                      "parent_packed", "parent_off"}
    assert "product" not in rows["gpt2-1.5b"]       # 2.5 column tiles
    for variants in rows.values():
        for row in variants.values():
            assert row["finite"] and "fwd_ms" not in row
            assert max(row["grad_rel_err_vs_dense"]) < 1e-2
        assert variants["packed"]["grad_rel_err_vs_parent"] == [0.0] * 3
    assert rows["gpt2-350m"]["product"]["same_bits_as_packed"]
