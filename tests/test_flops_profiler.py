"""FLOPS profiler tests (parity target: ref tests/unit/test_flops_profiler.py
asserts flops/params within tolerance of analytic values)."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler import (FlopsProfiler,
                                                    get_model_profile)
from deepspeed_tpu.profiling.flops_profiler.profiler import num_params


def test_cost_analysis_matmul():
    prof = FlopsProfiler()
    n = 256
    x = jnp.ones((n, n), jnp.float32)
    prof.start_profile()
    cost = prof.profile_jitted(lambda a: a @ a, x)
    prof.stop_profile()
    # 2*n^3 flops for a matmul
    assert abs(cost["flops"] - 2 * n ** 3) / (2 * n ** 3) < 0.05


def test_get_model_profile_flax():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(64)(x)
            x = nn.relu(x)
            return nn.Dense(16)(x)

    flops, macs, params = get_model_profile(
        model=MLP(), args=(np.zeros((4, 32), np.float32),),
        print_profile=False, as_string=False)
    expect_params = 32 * 64 + 64 + 64 * 16 + 16
    assert params == expect_params
    # fwd flops >= the two matmuls
    assert flops >= 2 * 4 * 32 * 64 + 2 * 4 * 64 * 16


def test_calls_re_splits_condition_and_body():
    # a while line lists its callees unbraced and comma-separated; the
    # unbraced alternative must stop at the name (a greedy capture would
    # swallow ", body" into the condition's name and drop the body)
    from deepspeed_tpu.profiling.flops_profiler.profiler import (_CALLS_RE,
                                                                 _TRIP_RE)
    line = ('%while.1 = (f32[8,8]{1,0}, s32[]) while(%tuple.1), '
            'condition=%cond_comp.2, body=%body_comp.3, '
            'backend_config={"known_trip_count":{"n":"7"}}')
    names = []
    for m in _CALLS_RE.finditer(line):
        got = m.group(1) if m.group(1) is not None else m.group(2)
        names += [t.strip().lstrip("%") for t in got.split(",") if t.strip()]
    assert names == ["cond_comp.2", "body_comp.3"]
    t = _TRIP_RE.search(line)
    assert t and int(t.group(1)) == 7
    # braced form (branch_computations) still splits on commas
    braced = ('%cond.9 = f32[] conditional(%p.0), '
              'branch_computations={%br_a.1, %br_b.2}')
    bnames = []
    for m in _CALLS_RE.finditer(braced):
        got = m.group(1) if m.group(1) is not None else m.group(2)
        bnames += [t.strip().lstrip("%") for t in got.split(",") if t.strip()]
    assert bnames == ["br_a.1", "br_b.2"]


def test_per_fusion_costs_scan_trip_count_multiplier():
    # a scanned matmul lowers to a while loop whose body XLA annotates
    # with known_trip_count; the body's dot/fusion rows must be scaled
    # by the trip count, not counted once
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        per_fusion_costs
    steps, n = 6, 64

    def fn(x, w):
        def body(carry, _):
            return jnp.tanh(carry @ w), None
        out, _ = jax.lax.scan(body, x, None, length=steps)
        return out

    x = jnp.ones((n, n), jnp.float32)
    w = jnp.ones((n, n), jnp.float32)
    rows = per_fusion_costs(fn, x, w, peak_flops=1e12, hbm_gbps=100.0)
    assert rows, "expected at least one fusion/dot row"
    per_step = 2 * n ** 3
    flop_rows = [r for r in rows if r["flops"] > 0]
    assert flop_rows, "expected a row with visible dot flops"
    total_flops = sum(r["flops"] for r in flop_rows)
    # all `steps` iterations must be accounted for (the unfixed parser
    # dropped the while body entirely, leaving at most one step's flops)
    assert total_flops >= steps * per_step * 0.9, \
        f"scan body under-counted: {total_flops} < {steps}*{per_step}"
    assert any(r["calls"] >= steps for r in flop_rows)


def test_per_fusion_costs_dus_carry_not_inflated():
    # stacking ys in a scan lowers to a loop fusion whose ROOT
    # dynamic-update-slices the stacked buffer (aliased in place, one
    # slice touched per trip); charging the full buffer x trip_count
    # would let this near-free carry update out-rank the real matmuls
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        per_fusion_costs
    steps, n = 8, 64

    def fn(x, w):
        def body(c, _):
            c = jnp.tanh(c @ w)
            return c, c
        return jax.lax.scan(body, x, None, length=steps)

    x = jnp.ones((n, n), jnp.float32)
    w = jnp.ones((n, n), jnp.float32)
    rows = per_fusion_costs(fn, x, w, peak_flops=1e12, hbm_gbps=100.0)
    stack_bytes = steps * n * n * 4
    for r in rows:
        if r["flops"]:
            continue
        # flopless loop fusions (the ys-stacking DUS) must stay at
        # slice-traffic scale: well under a few x the stacked buffer,
        # nowhere near trip_count x full-buffer (= steps * stack_bytes)
        assert r["bytes"] <= 4 * stack_bytes, \
            f"DUS fusion bytes inflated: {r}"


def test_engine_profile_step_runs(capsys):
    from deepspeed_tpu.models.gpt2 import tiny_gpt2_config, GPT2ForCausalLM
    cfg = tiny_gpt2_config(n_layer=2, dropout=0.0)
    model = GPT2ForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 256, (8, 64)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "flops_profiler": {"enabled": True, "profile_step": 2}})
    for _ in range(3):
        engine.train_batch(batch={"input_ids": ids[None]})
    # the profiler logged at step 2 without crashing; params counted
    assert num_params(engine.state.params) > 0


def test_custom_call_kernel_labeling():
    """Pallas custom-calls must be attributable by kernel name in the
    per-fusion table, not an opaque "custom-call" (ISSUE 6 satellite).
    TPU lowering cannot run on CPU CI, so the labeling logic is pinned
    on representative HLO text through the same text-level path
    per_fusion_costs uses."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        _custom_call_label, per_fusion_costs_from_text)
    line = ('%custom-call.7 = f32[128,256]{1,0} custom-call('
            'f32[128,256]{1,0} %p0), '
            'custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(step)/fused_bias_residual_layernorm'
            '/pallas_call[name=fused_bias_residual_layernorm_fwd]" '
            'source_file="fused_ops.py" source_line=1}')
    assert _custom_call_label(line) == \
        "fused_bias_residual_layernorm_fwd"
    # no pallas metadata -> the call target is the label
    bare = ('%cc = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %a), '
            'custom_call_target="my_target"')
    assert _custom_call_label(bare) == "my_target"

    # end to end through the text parser: the row carries the kernel
    text = """HloModule m

ENTRY %main (p0: f32[128,256]) -> f32[128,256] {
  %p0 = f32[128,256]{1,0} parameter(0)
  ROOT %custom-call.7 = f32[128,256]{1,0} custom-call(f32[128,256]{1,0} %p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_bias_residual_layernorm/pallas_call[name=fused_bias_residual_layernorm_fwd]"}
}
"""
    rows = per_fusion_costs_from_text(text, peak_flops=1e12,
                                      hbm_gbps=100.0)
    cc = [r for r in rows if r["kind"] == "custom-call"]
    assert cc and cc[0]["kernel"] == "fused_bias_residual_layernorm_fwd"


def test_fused_chain_rows_attributable():
    """A jitted fused epilogue chain's rows carry the op's named scope
    in their op_name attribution on ANY backend (the named_scope the
    fused_ops wrappers open), so the roofline table names the fused
    chains instead of anonymous elementwise fusions."""
    from deepspeed_tpu.ops.transformer.fused_ops import (
        fused_bias_gelu, fused_bias_residual_layernorm)
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        per_fusion_costs

    def f(y, b, r, g, bet):
        out, s = fused_bias_residual_layernorm(y, b, r, g, bet,
                                               eps=1e-5, impl="xla")
        return fused_bias_gelu(out, bet, impl="xla").sum() + \
            (s ** 2).sum()

    h = 256
    args = [jnp.ones((64, h)), jnp.ones((h,)), jnp.ones((64, h)),
            jnp.ones((h,)), jnp.ones((h,))]
    rows = per_fusion_costs(jax.grad(f, argnums=(0, 1, 2, 3, 4)), *args,
                            peak_flops=1e12, hbm_gbps=100.0)
    assert rows
    ops = " ".join(r["op"] for r in rows)
    assert "fused_bias_residual_layernorm" in ops
