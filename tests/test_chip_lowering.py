"""Every Pallas entry point lowers for a TPU, at the published-width
shapes `chip_smoke.py` runs on the chip.

`jax.export` with `platforms=["tpu"]` applies the Pallas-level TPU rules
(block shapes, memory spaces, the Mosaic dialect) on a machine with no
TPU; the interpreter the rest of the suite uses applies none of them.
This is the check that would have caught the int8 GEMM's and the MoE
dispatch's refused block shapes when they were written. What it cannot
see is the Mosaic compiler itself (VMEM limits, layouts): that is
`chip_smoke.py`'s kernel phase, on the chip.
"""

import importlib
import re

import jax
import pytest
from jax import export

import chip_smoke

_KERNEL_MODULES = (
    "deepspeed_tpu.ops.transformer.flash_attention",
    "deepspeed_tpu.ops.transformer.fused_ops",
    "deepspeed_tpu.ops.transformer.quantized_matmul",
    "deepspeed_tpu.ops.sparse_attention.block_sparse_attention",
    "deepspeed_tpu.moe.fused_dispatch",
)

# the Mosaic kernels each case must hold, forward and backward
_EXPECT = {
    "flash_packed": {"flash_fwd_packed", "flash_bwd_fused_packed"},
    "flash_unpacked": {"flash_fwd", "flash_bwd_fused"},
    "flash_d128": {"flash_fwd", "flash_bwd_fused"},
    "flash_multi_tile": {"flash_fwd_packed", "flash_bwd_dkv_packed",
                         "flash_bwd_dq_packed"},
    "fused_bias_residual_layernorm": {
        "fused_bias_residual_layernorm_fwd",
        "fused_bias_residual_layernorm_bwd"},
    "fused_bias_gelu": {"fused_bias_gelu_fwd", "fused_bias_gelu_bwd"},
    "int8_gemm": {"quantized_matmul"},
    "block_sparse_bslongformer": {"block_sparse_band_fwd",
                                  "block_sparse_bwd_dkv",
                                  "block_sparse_bwd_dq"},
    "block_sparse_fixed": {"block_sparse_band_fwd",
                           "block_sparse_bwd_dkv", "block_sparse_bwd_dq"},
    "block_sparse_bigbird": {"block_sparse_fwd", "block_sparse_bwd_dkv",
                             "block_sparse_bwd_dq"},
    "moe_dispatch_combine": {"moe_fused_dispatch", "moe_fused_combine"},
}


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels' own backend probes answer "TPU", so their default
    paths are the ones a chip selects."""
    for name in _KERNEL_MODULES:
        monkeypatch.setattr(importlib.import_module(name), "_on_tpu",
                            lambda: True)


def test_every_case_is_expected():
    assert {c.name for c in chip_smoke.kernel_cases()} == set(_EXPECT)


@pytest.mark.parametrize("name", sorted(_EXPECT))
def test_kernel_lowers_for_tpu(on_tpu, name):
    case, = [c for c in chip_smoke.kernel_cases() if c.name == name]
    exported = export.export(jax.jit(case.run),
                             platforms=["tpu"])(*case.shapes)
    text = exported.mlir_module()
    assert "tpu_custom_call" in text
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert kernels == _EXPECT[name]


@pytest.mark.parametrize("slots, heads, p, n, groups", [
    (96, 64, 64, 128, 8), (16, 32, 128, 256, 2)],
    ids=["nemotron-3-nano", "falcon-h1"])
def test_the_states_decode_step_lowers_for_tpu(monkeypatch, slots, heads, p,
                                               n, groups):
    """Decode's one-pass step of the Mamba-2 state at both cells'
    shapes (`ops/ssm/decode.py`; no case of `chip_smoke.py`: the
    serving cells run it): one Mosaic call, the state its operand and,
    aliased, its result."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.ssm import decode
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    sds = jax.ShapeDtypeStruct
    H = sds((2, slots, heads, p, n), jnp.float32)
    assert decode.usable(H)
    # the layout pinned on B and C is a custom call that an exported
    # module may not hold unless it is named (no promise of stability:
    # nothing is serialized here)
    exported = export.export(
        jax.jit(decode.ssm_decode), platforms=["tpu"], disabled_checks=[
            export.DisabledSafetyCheck.custom_call("LayoutConstraint")])(
        sds((slots, heads, p), jnp.bfloat16), sds((slots, heads), jnp.float32),
        sds((heads,), jnp.float32), sds((slots, groups, n), jnp.bfloat16),
        sds((slots, groups, n), jnp.bfloat16), sds((heads,), jnp.float32),
        H, sds((), jnp.int32), sds((slots,), bool), sds((slots,), bool))
    text = exported.mlir_module()
    assert re.findall(r'kernel_name = "([^"]+)"', text) == ["ssm_decode"]
    whole = "x".join(map(str, H.shape))
    call, = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert "output_operand_aliases" in call and f"tensor<{whole}xf32>" in call


@pytest.mark.parametrize("n_head", [2, 3], ids=[
    "a-head-a-column", "three-heads-whole-on-both-columns"])
def test_sharded_train_step_lowers_for_tpu(on_tpu, n_head):
    """GSPMD cannot partition a Mosaic call: a loss+grad whose operands
    live on a multi-device mesh lowers only because every launch runs
    per device (ops/per_device.py). The virtual CPU mesh never shows
    this — there the kernels are interpreted, ordinary XLA. (Three
    heads: the mesh's two columns divide H·D and not H, so the flash
    launches hold the heads whole.)"""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu.runtime.mesh import build_mesh

    mesh = build_mesh({"pipe": 1, "data": 4, "model": 2})
    model = GPT2ForCausalLM(gpt2_config(
        "gpt2-tiny", n_embd=64 * n_head, n_head=n_head, n_positions=128,
        dropout=0.0,
        remat=True, dtype=jnp.bfloat16))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 128), np.int32)}))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())), params)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (8, 128), jnp.int32, sharding=NamedSharding(mesh, P("data")))}
    grad = jax.jit(jax.grad(
        lambda p, b: model.loss_fn(p, b, deterministic=True)))
    text = export.export(grad, platforms=["tpu"])(params, batch) \
        .mlir_module()
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert {"flash_fwd_packed", "flash_bwd_fused_packed",
            "fused_bias_residual_layernorm_fwd",
            "fused_bias_residual_layernorm_bwd",
            "fused_bias_gelu_fwd", "fused_bias_gelu_bwd"} <= kernels
