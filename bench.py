#!/usr/bin/env python
"""Benchmarks on real TPU hardware across the BASELINE.json config list.

Prints ONE JSON line. Headline: the FLAGSHIP config — GPT-2 1.5B
(BASELINE.json "GPT-2 1.5B ZeRO-Stage-2") training tokens/s/chip with
MFU reported top-level; `vs_baseline` = achieved_MFU / 0.45 (the
reference's north-star MFU, BASELINE.md). On a 16 GB v5e chip the 1.5B
state only fits via the bf16 master-less optimizer
(`bf16 {"master_weights": false}` — runtime/bf16_optimizer.py: fp32
Adam state would need 21.8 GB), which is the engine's intended flagship
configuration on this hardware.

`extra` carries the other BASELINE configs:
  * GPT-2 350M (continuity with BENCH_r01/r02 headlines)
  * BERT-large fused-layer seq128 (ref: 272 samples/s on 1x V100)
  * 16k/32k block-sparse vs dense flash (ref claims up to 6.3x)
  * a REAL ZeRO-Offload optimizer step (grads -> host CPU-Adam ->
    params), with the measured host/transfer split
  * ring-attention per-step flash partial vs the XLA fallback
  * GPT-2 13B ZeRO-3 memory plan (eval_shape arithmetic, no step;
    the executed 13B proof is artifacts/ARTIFACT_13B_r05.log)
  * 1F1B interpreter vs SPMD pipe ratio on the same model

Nothing this script prints is a record: it predates the chip tool and
is replaced by ROADMAP Speed item 1's benchmark. Measurement notes:
  * warmup >= 6 steps — the first executions after compile run slow
    (donated buffers settle into the step's output layouts), and
    timing them halves the reported number
  * the timed section runs 3-4 windows and keeps the best; the flagship
    interleaves a latency-cancelled matmul-peak probe between windows
  * sync via device_get of the step's loss
"""

import json
import os
import time

import jax
import numpy as np


# bf16 peak FLOP/s per chip by TPU generation (public spec sheets).
BASELINE_MFU = 0.45   # north-star target (BASELINE.md)

_PEAK_FLOPS = {
    "v5 lite": 197e12,   # v5e
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
    "v6e": 918e12,
}


# --peak-flops CLI override (satellite of ISSUE 7): lets CPU/virtual-
# mesh rehearsal runs report a meaningful MFU (and mirrors the
# monitor.peak_flops_override config key for in-loop telemetry).
_PEAK_FLOPS_OVERRIDE = None


def _peak_flops(device) -> float:
    if _PEAK_FLOPS_OVERRIDE is not None:
        return _PEAK_FLOPS_OVERRIDE
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    return 0.0  # unknown (e.g. CPU) -> MFU reported as 0


def _sync(x):
    float(jax.device_get(x))


def _probe_program(m=4096, iters=240):
    """Compiled dependence-chained matmul probe (the methodology that
    reads ~140 TF on this chip when healthy): returns a zero-argument
    callable measuring one probe window in FLOPS. Chained inside ONE
    jit, pre-warmed 6x (donated-buffer layouts settle over the first
    ~5 runs), and measured as the DIFFERENCE between a 2N-iteration
    and an N-iteration chain — the per-call dispatch/fetch overhead
    appears in both walls and cancels, so the quotient is pure device
    throughput."""
    import jax.numpy as jnp
    a = jnp.full((m, m), 0.001, jnp.bfloat16)

    def make(n):
        @jax.jit
        def chain(a):
            def body(i, c):
                return (a @ c) * jnp.bfloat16(0.001)
            return jax.lax.fori_loop(0, n, body, a)[0, 0]
        return chain

    short, long_ = make(iters), make(2 * iters)
    for _ in range(6):
        r = short(a)
    _sync(r.astype(jnp.float32))
    for _ in range(6):
        r = long_(a)
    _sync(r.astype(jnp.float32))
    flops_delta = 2.0 * m ** 3 * iters

    def run():
        t0 = time.perf_counter()
        _sync(short(a).astype(jnp.float32))
        t1 = time.perf_counter()
        _sync(long_(a).astype(jnp.float32))
        t2 = time.perf_counter()
        dt = max((t2 - t1) - (t1 - t0), 1e-6)
        return flops_delta / dt

    return run


def _run_engine(model, params_box, ds_config, make_batch, steps, warmup,
                windows=3, probe=False):
    """params_box: single-element list; popped so NO reference to the
    caller's param tree survives engine init (the engine copies it, and
    a dead 3.1 GB duplicate at 1.5B is the difference between fitting
    16 GB HBM and OOM). Callers must `del` their own binding too.

    probe=True interleaves a matmul-peak probe window around every step
    window (VERDICT r4 #6): probe and headline then come from the SAME
    throttle regime, so probe < achieved can no longer mean "the probe
    ran later in a bad window" — it means the step numbers themselves
    were taken on a degraded chip."""
    from deepspeed_tpu import initialize
    engine, _, _, _ = initialize(model=model,
                                 model_parameters=params_box.pop(),
                                 config=ds_config)
    for i in range(warmup):
        loss = engine.train_batch(batch=make_batch(i))
    _sync(loss)
    probe_run = None
    if probe:
        try:
            probe_run = _probe_program()
        except Exception:
            probe_run = None   # a dead probe must not kill the headline
    probe_samples = []
    # Each probe point takes _PROBE_REPS repetitions and the reported
    # probe is the MEDIAN over every repetition of every interleaved
    # point (BENCH_r04's `peak_probe_warning` flake: a single
    # contended probe window read 65 TF against 86 TF achieved —
    # "probe < achieved" — purely from one bad sample; the median
    # over N reps is robust to a minority of contended windows, and
    # main() only warns when the MEDIAN is below achieved).
    PROBE_REPS = 3

    def take_probe():
        if probe_run is None:
            return
        try:
            for _ in range(PROBE_REPS):
                probe_samples.append(probe_run())
        except Exception:
            pass

    # Pre-stage the window's batches on device (a real input pipeline
    # prefetches; through this host link an un-prefetched batch bills
    # ~4 ms of upload to every step). stage_batch is idempotent, so
    # train_batch passes the staged arrays through device-side.
    staged = [engine.stage_batch(make_batch(100 + i)) for i in range(steps)]
    best = float("inf")
    for w in range(windows):
        take_probe()
        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batch=staged[i])
        _sync(loss)
        best = min(best, time.perf_counter() - t0)
    take_probe()
    # median across all reps of all interleaved points: the
    # latency-difference trick jitters symmetrically (a max would
    # systematically over-read) and single contended windows are
    # outvoted (the BENCH_r04 peak_probe_warning fix)
    probe_med = float(np.median(probe_samples)) if probe_samples else 0.0
    return best, engine, probe_med


def _gpt2_throughput(model_name, batch, seq, steps, warmup, ds_config,
                     remat_policy=None, probe=False, windows=3,
                     **cfg_overrides):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config

    cfg = gpt2_config(model_name, n_positions=seq, dropout=0.0,
                      dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                      remat=True, remat_policy=remat_policy,
                      **cfg_overrides)
    model = GPT2ForCausalLM(cfg)
    params = jax.jit(lambda r: model.init(
        r, {"input_ids": np.zeros((batch, seq), np.int32)}))(
        jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    box = [params]
    del params

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    dt, _, probe_tf = _run_engine(model, box, ds_config, make_batch,
                                  steps, warmup, probe=probe,
                                  windows=windows)
    n_chips = len(jax.devices())
    tokens_per_sec_per_chip = batch * seq * steps / dt / n_chips
    # 6ND model flops (conservative convention; remat recompute and
    # attention-matmul flops not counted) — this is what the headline
    # mfu/vs_baseline use
    achieved = tokens_per_sec_per_chip * 6.0 * n_params
    peak = _peak_flops(jax.devices()[0])
    mfu = achieved / peak if peak else 0.0
    # Megatron-LM convention (the formula the north-star target's own
    # papers report MFU with) additionally counts the attention
    # matmuls: + 12·S·L·h useful flops per token
    attn_per_token = 12.0 * seq * cfg.n_layer * cfg.n_embd
    mfu_megatron = (achieved + tokens_per_sec_per_chip * attn_per_token) \
        / peak if peak else 0.0
    return tokens_per_sec_per_chip, mfu, achieved, mfu_megatron, probe_tf


def bench_gpt2_15b():
    """Flagship: GPT-2 1.5B, ZeRO-2 + bf16 master-less state (the only
    way 1.5B Adam state fits 16 GB HBM; BASELINE.json config 2).
    batch 10 is the largest microbatch that fits with the kernels
    `auto` selects on a v5e (11 fails to compile: 15.92 GB of 15.75;
    chip run, PR 21 — see examples/ds_config_gpt2_1.5b.json)."""
    # steps=16: the window-edge device fence drains the dispatch
    # queue; a longer window bills less of its wall to that fence
    # (real training has no such per-window fence)
    return _gpt2_throughput(
        "gpt2-1.5b", batch=10, seq=1024, steps=16, warmup=6, probe=True,
        windows=4,
        ds_config={
            "train_micro_batch_size_per_gpu": 10,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 1000,
            "bf16": {"enabled": True, "master_weights": False},
            "zero_optimization": {"stage": 2},
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
        })


def bench_gpt2_350m():
    """Continuity config (BENCH_r01/r02 headline): GPT-2 350M, classic
    bf16 + fp32 master, selective remat."""
    tps, mfu, _, _, _ = _gpt2_throughput(
        "gpt2-350m", batch=16, seq=1024, steps=10, warmup=6,
        remat_policy="dots_with_no_batch_dims_saveable",
        ds_config={
            "train_micro_batch_size_per_gpu": 16,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 1000,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 1e-4, "weight_decay": 0.01}},
        })
    out = {"tokens_per_sec_per_chip": round(tps, 1), "mfu": round(mfu, 4)}
    try:
        from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
        import jax.numpy as jnp
        cfg = gpt2_config("gpt2-350m", n_positions=1024, dropout=0.0,
                          dtype=jnp.bfloat16, remat=True,
                          remat_policy="dots_with_no_batch_dims_saveable")
        out["per_fusion_top3"] = _model_fusion_sinks(
            GPT2ForCausalLM(cfg),
            {"input_ids": np.zeros((16, 1024), np.int32)})
    except Exception as e:
        out["per_fusion_top3"] = f"unavailable: {type(e).__name__}: {e}"
    return out


def bench_gpt2_cpu_smoke():
    """CPU fallback so the bench always emits a line."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    cfg = tiny_gpt2_config(n_positions=64, dropout=0.0)
    model = GPT2ForCausalLM(cfg)
    batch, seq = 8, 64
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((batch, seq), np.int32)})
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    box = [params]
    del params
    dt, _, _ = _run_engine(model, box, {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
    }, make_batch, steps=2, warmup=1, windows=1)
    tps = batch * seq * 2 / dt / len(jax.devices())
    return tps, 0.0, 6.0 * n_params * tps


def bench_bert_large():
    """BERT-large pretraining step with the fused transformer layer,
    seq 128 (the reference's headline kernel benchmark: 272 samples/s /
    64 TFLOPS on 1x V100, bert-pretraining.md:387). Reported as
    TFLOPS/chip + MFU against THIS chip's peak (the honest yardstick),
    with the V100 ratio kept for reference."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.bert import BertForPreTrainingLM, bert_config

    batch, gas, seq, steps, warmup = 16, 16, 128, 3, 7
    cfg = bert_config("bert-large", max_position_embeddings=seq,
                      hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0, bf16=True)
    model = BertForPreTrainingLM(cfg)
    example = {"input_ids": np.zeros((batch, seq), np.int32)}
    params = model.init(jax.random.PRNGKey(0), example)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))

    def make_batch(i):
        r = np.random.default_rng(i)
        ids = r.integers(0, cfg.vocab_size,
                         (gas, batch, seq)).astype(np.int32)
        labels = np.where(r.random((gas, batch, seq)) < 0.15, ids, -100)
        return {"input_ids": ids,
                "masked_lm_labels": labels.astype(np.int32),
                "next_sentence_label": r.integers(
                    0, 2, (gas, batch)).astype(np.int32)}

    box = [params]
    del params
    dt, _, _ = _run_engine(model, box, {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "steps_per_print": 1000,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
    }, make_batch, steps, warmup)

    samples_per_sec = batch * gas * steps / dt / len(jax.devices())
    tflops = samples_per_sec * seq * 6.0 * n_params / 1e12
    peak = _peak_flops(jax.devices()[0])
    out = {"samples_per_sec_per_chip": round(samples_per_sec, 1),
           "tflops_per_chip": round(tflops, 1),
           "mfu": round(tflops * 1e12 / peak, 4) if peak else 0.0,
           "vs_v100_published": round(samples_per_sec / 272.0, 2)}
    try:
        # per-fusion time breakdown (HLO-cost-analysis roofline) of one
        # microbatch's fwd+bwd — the table that flagged the fp32 MLM
        # head as the top sink (fix: mlm_head_in_compute_dtype; A/B in
        # the bert_mlm_head_dtype leg)
        one = {k: v[0] for k, v in make_batch(0).items()}
        out["per_fusion_top3"] = _model_fusion_sinks(model, one)
    except Exception as e:
        out["per_fusion_top3"] = f"unavailable: {type(e).__name__}: {e}"
    return out


def bench_sparse_16k():
    """Block-sparse vs DENSE FLASH attention (our own Pallas kernel — a
    much stronger comparator than the reference's fp32 torch dense),
    fwd+bwd at 16k and 32k context (BASELINE config 5; reference claims
    up to 6.3x over its dense)."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention import (
        SparseSelfAttention, FixedSparsityConfig,
        BSLongformerSparsityConfig)
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention

    h, d = 16, 64
    rng = np.random.default_rng(0)
    out = {}

    def timed(fn, q):
        grad = jax.jit(lambda q: jax.grad(
            lambda q: fn(q).astype(jnp.float32).sum())(q).sum())
        for _ in range(6):   # first ~5 post-compile runs are slow
            r = grad(q)
        _sync(r)
        best = float("inf")
        for w in range(3):   # best-of-3
            t0 = time.perf_counter()
            for _ in range(5):
                r = grad(q)
            _sync(r)
            best = min(best, (time.perf_counter() - t0) / 5)
        return best

    # headline config: BSLongformer (1024-token sliding window + global
    # block) — the canonical long-context pattern; its band+global
    # structure rides the specialized forward (block_sparse_attention's
    # _band_fwd). The reference's default Fixed pattern now rides the
    # same fast forward (window-ALIGNED decomposition + sorted-tile
    # causal skip, round 4). Reading the ratio: Fixed's per-window
    # summary columns grow with position, so at 32k it ATTENDS ~4x the
    # blocks of longformer-w4g1 — a fixed/longformer time ratio below
    # 4 means per-block efficiency at or above the banded path, not a
    # deficiency (measured r4 interleaved: 1.03x @16k, 2.42x @32k,
    # from 1.64x/2.7x in r3).
    for b, t in ((1, 16384), (2, 32768)):
        q = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
        t_dense = timed(lambda q: flash_attention(q, q, q, causal=True), q)
        longf = SparseSelfAttention(
            BSLongformerSparsityConfig(num_heads=h, block=256,
                                       num_sliding_window_blocks=4),
            max_seq_length=t)
        t_lf = timed(lambda q: longf(q, q, q, causal=True), q)
        fixed = SparseSelfAttention(
            FixedSparsityConfig(num_heads=h, block=256,
                                num_local_blocks=4, num_global_blocks=1),
            max_seq_length=t)
        t_fx = timed(lambda q: fixed(q, q, q, causal=True), q)

        # Work-normalized comparison: Fixed's per-window summary
        # columns grow with position (sparsity_config.py:100-107), so
        # its attended-block count is a multiple of longformer's at
        # long T BY PATTERN DEFINITION — the raw time ratio conflates
        # pattern density with kernel efficiency. per_block_us is the
        # efficiency number: Fixed at or below longformer means the
        # Fixed path runs the shared band+global kernel at parity.
        def causal_pairs(cfg_obj):
            lay = np.asarray(cfg_obj.make_layout(t))[0]
            ii, jj = np.nonzero(lay)
            return int(np.count_nonzero(jj <= ii))

        p_lf = causal_pairs(longf.sparsity_config) * b
        p_fx = causal_pairs(fixed.sparsity_config) * b
        out[f"seq{t}"] = {
            "config": "bslongformer_w4_g1",
            "sparse_ms": round(t_lf * 1e3, 2),
            "dense_flash_ms": round(t_dense * 1e3, 2),
            "speedup_vs_dense_flash": round(t_dense / t_lf, 2),
            "fixed_pattern_ms": round(t_fx * 1e3, 2),
            "fixed_speedup_vs_dense_flash": round(t_dense / t_fx, 2),
            "fixed_blocks_vs_bsl": round(p_fx / p_lf, 2),
            "bsl_us_per_block": round(t_lf * 1e6 / p_lf, 2),
            "fixed_us_per_block": round(t_fx * 1e6 / p_fx, 2)}

    # reference-style comparator (materialized-scores dense attention,
    # what the 6.3x claim was measured against); it cannot even compile
    # past 8k here, which IS the '10x longer sequences' story.
    try:
        from deepspeed_tpu.ops.transformer.flash_attention import \
            dense_attention
        t = 8192
        q = jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.bfloat16)
        sparse = SparseSelfAttention(
            FixedSparsityConfig(num_heads=h, block=256,
                                num_local_blocks=4, num_global_blocks=1),
            max_seq_length=t)
        t_sparse = timed(lambda q: sparse(q, q, q, causal=True), q)
        t_naive = timed(lambda q: dense_attention(q, q, q, causal=True), q)
        out["seq8192_vs_naive_dense"] = {
            "sparse_ms": round(t_sparse * 1e3, 2),
            "naive_dense_ms": round(t_naive * 1e3, 2),
            "speedup": round(t_naive / t_sparse, 2)}
    except Exception as e:
        out["seq8192_vs_naive_dense"] = {
            "error": f"{type(e).__name__}: {e}"[:200]}
    return out


def bench_offload_real_step():
    """A REAL ZeRO-Offload optimizer step (BASELINE/ref claim: 13B on
    one device via host-offloaded Adam): GPT-2 125M, bf16 grads ->
    host, native CPU-Adam, bf16 params back. Reports the measured
    end-to-end optimizer-step wall time and the compute-side
    throughput, plus the split (device<->host transfer against the
    host optimizer step). gas amortizes the host step as in real
    use."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu import initialize

    batch, seq, gas = 8, 1024, 4
    cfg = gpt2_config("gpt2-125m", n_positions=seq, dropout=0.0,
                      dtype=jnp.bfloat16, param_dtype=jnp.float32,
                      remat=True)
    model = GPT2ForCausalLM(cfg)
    params = jax.jit(lambda r: model.init(
        r, {"input_ids": np.zeros((batch, seq), np.int32)}))(
        jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    engine, _, _, _ = initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": gas,
            "steps_per_print": 1000,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2, "cpu_offload": True},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        })
    del params

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (gas, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    # one warmup (compiles grads program + host step)
    engine.train_batch(batch=make_batch(0))
    t0 = time.perf_counter()
    loss = engine.train_batch(batch=make_batch(1))
    _sync(loss)
    step_s = time.perf_counter() - t0
    tokens = batch * seq * gas
    return {"model": "gpt2-125m", "params_m": round(n_params / 1e6, 1),
            "gas": gas,
            "measured_step_s": round(step_s, 2),
            "tokens_per_sec": round(tokens / step_s, 1),
            "tflops_per_chip": round(6.0 * n_params * tokens / step_s / 1e12,
                                     2),
            "note": "model size is kept small to bound bench time; "
                    "capability at scale is the ZeRO-3 memory plan + "
                    "offload test suite"}


def bench_offload_wire():
    """Compressed-wire ZeRO-Offload A/B (ISSUE 1): the SAME real
    optimizer step as `zero_offload_real_step`, run at each
    `offload_wire` setting. Reports measured bytes-on-wire per step
    (from the engine's wire_stats accounting) and the end-to-end step
    time, so the bytes→seconds translation on THIS link is explicit.
    Where the step is transfer-bound the int8 (~2x) and 1-bit (~16x)
    byte reductions land almost 1:1 in step time; on a CPU-only run
    the link is local RAM and the times collapse — the bytes numbers
    are the portable part."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu import initialize

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        batch, seq, gas, cfg_over = 8, 1024, 4, {}
    else:  # CPU smoke: tiny shapes (batch divisible by any test mesh),
        batch, seq, gas = 8, 128, 2
        cfg_over = dict(n_layer=2, n_embd=128, n_head=4)
    cfg = gpt2_config("gpt2-125m", n_positions=seq, dropout=0.0,
                      dtype=jnp.bfloat16, param_dtype=jnp.float32,
                      remat=True, **cfg_over)

    settings = [
        ("bf16_native", {}),
        ("int8", {"grad_bits": 8, "param_bits": 8}),
        ("1bit", {"grad_bits": 1, "param_bits": 8, "warmup_steps": 1}),
    ]
    out = {}
    for name, wire in settings:
        model = GPT2ForCausalLM(cfg)
        params = jax.jit(lambda r: model.init(
            r, {"input_ids": np.zeros((batch, seq), np.int32)}))(
            jax.random.PRNGKey(0))
        engine, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 1000,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2, "cpu_offload": True,
                                      "offload_wire": wire},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            })
        del params

        def make_batch(i):
            ids = np.random.default_rng(i).integers(
                0, cfg.vocab_size, (gas, batch, seq)).astype(np.int32)
            return {"input_ids": ids}

        # warmup past the wire's warmup window so the measured step uses
        # the compressed format
        for i in range(1 + wire.get("warmup_steps", 0)):
            loss = engine.train_batch(batch=make_batch(i))
        _sync(loss)
        best = float("inf")
        for w in range(2):
            t0 = time.perf_counter()
            loss = engine.train_batch(batch=make_batch(10 + w))
            _sync(loss)
            best = min(best, time.perf_counter() - t0)
        st = dict(engine.wire_stats)
        out[name] = {
            "measured_step_s": round(best, 3),
            "d2h_bytes": st["d2h_bytes"],
            "h2d_bytes": st["h2d_bytes"],
            "roundtrip_bytes": st["d2h_bytes"] + st["h2d_bytes"],
            "loss": round(float(jax.device_get(loss)), 3),
        }
        del engine

    base = out["bf16_native"]
    for name in ("int8", "1bit"):
        leg = out[name]
        leg["d2h_reduction_vs_bf16"] = round(
            base["d2h_bytes"] / leg["d2h_bytes"], 2)
        leg["roundtrip_reduction_vs_bf16"] = round(
            base["roundtrip_bytes"] / leg["roundtrip_bytes"], 2)
        leg["e2e_speedup_vs_bf16"] = round(
            base["measured_step_s"] / leg["measured_step_s"], 2)
    if not on_tpu:
        out["note"] = ("CPU run: no host link in the path, so step-time "
                       "speedups are ~1; bytes-on-wire ratios are the "
                       "hardware-independent result")
    return out


def bench_ring_attention():
    """Ring attention per-step body: Pallas flash (out, lse) partials
    (VERDICT r4 #4) vs the XLA online-softmax fallback, fwd+bwd. One
    chip = a 1-step ring, which is exactly the per-step body the swap
    changed; multi-step behavior (ppermute + merge) is numerics-pinned
    on the CPU mesh (tests/test_sequence_parallel.py). The fallback
    materializes [H, Tl, Tl] fp32 scores per step, so its leg runs at
    the largest shape that fits; the flash leg also runs 32k."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from deepspeed_tpu.ops.sequence import ring_attention

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("seq",))
    rng = np.random.default_rng(0)
    out = {}

    def timed(fn, q):
        grad = jax.jit(lambda q: jax.grad(
            lambda q: fn(q).astype(jnp.float32).sum())(q).sum())
        for _ in range(6):
            r = grad(q)
        _sync(r)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(3):
                r = grad(q)
            _sync(r)
            best = min(best, (time.perf_counter() - t0) / 3)
        return best

    # A/B at the largest fallback-feasible shape
    h, d, t = 4, 64, 8192
    q = jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.bfloat16)
    t_flash = timed(lambda q: ring_attention(
        q, q, q, mesh, causal=True, use_flash=True), q)
    t_xla = timed(lambda q: ring_attention(
        q, q, q, mesh, causal=True, use_flash=False), q)
    out["per_step_8k"] = {
        "flash_partial_ms": round(t_flash * 1e3, 2),
        "xla_partial_ms": round(t_xla * 1e3, 2),
        "flash_speedup": round(t_xla / t_flash, 2)}

    # long-T flash-path leg (the fallback cannot materialize 32k scores)
    h, t = 16, 32768
    q = jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.bfloat16)
    t32 = timed(lambda q: ring_attention(
        q, q, q, mesh, causal=True, use_flash=True), q)
    out["flash_32k"] = {"fwd_bwd_ms": round(t32 * 1e3, 2),
                        "tokens_per_sec": round(t / t32, 1)}
    return out


def _model_fusion_sinks(model, example_batch, top=3):
    """Top-N per-fusion time sinks of the model's jitted fwd+bwd at the
    bench shape (profiler HLO-cost-analysis roofline). Compile-only:
    params are abstract (eval_shape), nothing executes — the table says
    WHERE the step's time goes, the throughput numbers say how much."""
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        top_fusion_sinks)
    params = jax.eval_shape(lambda r: model.init(r, example_batch),
                            jax.random.PRNGKey(0))

    def loss(p):
        return model.loss_fn(p, example_batch, deterministic=True)

    peak = _peak_flops(jax.devices()[0])
    return top_fusion_sinks(jax.grad(loss), params, top=top,
                            peak_flops=peak if peak else None)


def bench_flash_head_packing():
    """Head-packing A/B: the packed flash kernel processes TWO d=64
    heads per grid step (block-diagonal K/V, [bq,128]x[128,2bk] score
    matmuls) so every contraction runs at the MXU's native K=128
    instead of half-starved K=64 (flash_attention.py docstring).
    Packed and unpacked kernels are timed fwd+bwd in INTERLEAVED
    best-of-N windows (same throttle regime), plus a forward parity
    check — the zero lanes contribute exact +0, so the two kernels
    agree to fp32 roundoff."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.flash_attention import \
        flash_attention

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # flagship-adjacent shape (gpt2-1.5b is h=25 d=64 t=1024; b*h
        # rounds to an even row count via the kernel's one-row pad)
        b, h, t, d, dtype, interpret, inner = \
            8, 16, 1024, 64, jnp.bfloat16, None, 8
    else:
        # CPU interpreter: same kernel logic; the packed grid has half
        # the row-blocks, which is the dominant term in interpret mode
        b, h, t, d, dtype, interpret, inner = \
            4, 8, 256, 64, jnp.float32, True, 2
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, t, h, d)), dtype)

    def make(hp):
        f = jax.jit(lambda q: jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, interpret=interpret, head_packing=hp)
            .astype(jnp.float32).sum())(q).sum())
        _sync(f(q))   # compile + warm
        return f

    f_packed, f_unpacked = make("packed"), make("off")

    def window(f):
        t0 = time.perf_counter()
        for _ in range(inner):
            r = f(q)
        _sync(r)
        return (time.perf_counter() - t0) / inner

    best = {"packed": float("inf"), "unpacked": float("inf")}
    for _ in range(4):                      # interleaved A/B windows
        best["packed"] = min(best["packed"], window(f_packed))
        best["unpacked"] = min(best["unpacked"], window(f_unpacked))

    o_p = flash_attention(q, q, q, causal=True, interpret=interpret,
                          head_packing="packed")
    o_u = flash_attention(q, q, q, causal=True, interpret=interpret,
                          head_packing="off")
    maxdiff = float(jnp.abs(o_p.astype(jnp.float32) -
                            o_u.astype(jnp.float32)).max())
    speedup = best["unpacked"] / best["packed"]
    return {"shape": f"b{b} h{h} t{t} d{d} {np.dtype(dtype).name}"
                     + (" interpret" if interpret else ""),
            "packed_fwd_bwd_ms": round(best["packed"] * 1e3, 2),
            "unpacked_fwd_bwd_ms": round(best["unpacked"] * 1e3, 2),
            "packed_speedup": round(speedup, 3),
            "packed_faster": bool(speedup >= 1.0),
            "fwd_max_abs_diff": maxdiff}


def bench_bert_mlm_head_dtype():
    """A/B of the BERT-large seq-128 top-sink fix: the MLM head
    (transform + [hidden, vocab] decoder) matmuls in the compute dtype
    vs the old fp32. The decoder is ~10% of the step's flops; in fp32
    it runs at a fraction of the MXU's bf16 rate and the per-fusion
    table ranked it the top sink of the seq-128 step (seq-128 BERT is
    MLP/head-dominated — attention is tiny at T=128). Interleaved
    best-of-N fwd+bwd windows; loss math is fp32 in both arms (the CE
    upcasts logits), so this is a matmul-precision A/B only.

    The A arm is the SHIPPED default ("auto": compute dtype on real
    TPU, fp32 on CPU — CPU XLA emulates bf16 dots slower than fp32),
    the B arm forces fp32: on TPU this measures the fix, on CPU it
    measures noise between two identical programs (the honest "the fix
    does not regress CPU" statement)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.bert import BertForPreTrainingLM, bert_config

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        name, batch, seq, inner = "bert-large", 16, 128, 4
    else:
        name, batch, seq, inner = "bert-base", 4, 128, 2
    r = np.random.default_rng(0)
    ids = r.integers(0, 1000, (batch, seq)).astype(np.int32)
    labels = np.where(r.random((batch, seq)) < 0.15, ids, -100) \
        .astype(np.int32)
    ex = {"input_ids": ids, "masked_lm_labels": labels,
          "next_sentence_label": r.integers(0, 2, (batch,))
          .astype(np.int32)}

    def make(head_in_compute_dtype):
        cfg = bert_config(name, max_position_embeddings=seq,
                          hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0, bf16=True,
                          mlm_head_in_compute_dtype=head_in_compute_dtype)
        model = BertForPreTrainingLM(cfg)
        params = jax.jit(lambda rr: model.init(rr, ex))(
            jax.random.PRNGKey(0))

        def loss(p):
            return model.loss_fn(p, ex, deterministic=True)

        g = jax.jit(lambda p: jax.tree_util.tree_reduce(
            lambda a, l: a + l.astype(jnp.float32).sum(),
            jax.grad(loss)(p), jnp.float32(0.0)))
        _sync(g(params))
        return g, params

    g_fix, p_fix = make("auto")
    g_f32, p_f32 = make(False)

    def window(g, p):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = g(p)
        _sync(out)
        return (time.perf_counter() - t0) / inner

    best = {"fix": float("inf"), "f32": float("inf")}
    for _ in range(4):
        best["fix"] = min(best["fix"], window(g_fix, p_fix))
        best["f32"] = min(best["f32"], window(g_f32, p_f32))
    speedup = best["f32"] / best["fix"]
    return {"model": name, "seq": seq, "batch": batch,
            "head_dtype_auto_resolves_to":
                "bf16" if on_tpu else "fp32",
            "fixed_head_ms": round(best["fix"] * 1e3, 2),
            "fp32_head_ms": round(best["f32"] * 1e3, 2),
            "fixed_speedup": round(speedup, 3),
            # 3% tolerance: on CPU the arms are identical programs
            # (auto -> fp32), so only timing noise separates them
            "regressed": bool(speedup < 0.97)}


def bench_pipe_interp_vs_spmd():
    """Same homogeneous model through the compiled 1F1B interpreter
    (the recommended substrate — see pipe/engine.py docstring) vs the
    GPipe SPMD scan. Pipeline parallelism needs pipe >= 2; with one
    real chip the comparison runs in a subprocess on an 8-device
    virtual CPU mesh. NOTE on reading the ratio: the virtual mesh
    SERIALIZES stages onto one core, so the scan's fill/drain bubble
    ((S-1)/m of extra stage-executions on garbage inputs) shows up as
    real compute time here, while on parallel hardware both paths pay
    the bubble as idle stages; the interp's win is therefore an upper
    bound, but its activation bound and per-stage param partitioning
    hold everywhere."""
    import subprocess
    import sys
    script = r"""
import os, json, time
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
import deepspeed_tpu
from deepspeed_tpu.runtime.mesh import build_mesh
from deepspeed_tpu.runtime.pipe.module import PipelineModule, LayerSpec
from deepspeed_tpu.models.gpt2 import GPT2Block, tiny_gpt2_config
from deepspeed_tpu.models.gpt2_pipe import PipelinedGPT2

L, S, GAS, MB, T = 8, 4, 8, 4, 128
cfg = tiny_gpt2_config(n_layer=L, n_embd=128, n_head=4, n_positions=T)
mesh = build_mesh({'pipe': S, 'data': 8 // S, 'model': 1})
ds = {'train_micro_batch_size_per_gpu': MB,
      'gradient_accumulation_steps': GAS, 'steps_per_print': 1000,
      'optimizer': {'type': 'Adam', 'params': {'lr': 1e-3}}}
rng0 = np.random.RandomState(0)
out = {}

def run(e, batches, warm=2, n=6):
    for i in range(warm):
        l = e.train_batch(batch=batches(i))
    float(jax.device_get(l))
    t0 = time.perf_counter()
    for i in range(n):
        l = e.train_batch(batch=batches(i))
    float(jax.device_get(l))
    return (time.perf_counter() - t0) / n * 1e3

# SPMD fast path: PipelinedGPT2 (transformer compute = L GPT2Blocks)
mp = PipelinedGPT2(cfg, num_stages=S, num_micro_batches=GAS)
ids = rng0.randint(0, cfg.vocab_size, (MB * GAS, T)).astype(np.int32)
pp = mp.init(jax.random.PRNGKey(0), {'input_ids': ids})
e1, _, _, _ = deepspeed_tpu.initialize(model=mp, model_parameters=pp,
                                       config=ds, mesh=mesh)
out['spmd_ms'] = round(run(e1, lambda i: {'input_ids': ids}), 1)

# compiled 1F1B interpreter: PipelineModule of the SAME GPT2Blocks
# (hidden-space in/out; embed/head excluded on both sides' delta)
mod = PipelineModule([LayerSpec(GPT2Block, cfg) for _ in range(L)],
                     num_stages=S,
                     loss_fn=lambda y, lab: jnp.mean(
                         (y - lab).astype(jnp.float32) ** 2))
x0 = rng0.randn(MB, T, 128).astype(np.float32)
prm = mod.init_params(jax.random.PRNGKey(0), jnp.asarray(x0))
e2, _, _, _ = deepspeed_tpu.initialize(model=mod, model_parameters=prm,
                                       config=ds, mesh=mesh)
xb = rng0.randn(MB * GAS, T, 128).astype(np.float32)
out['interp_ms'] = round(run(e2, lambda i: {'x': xb, 'y': xb * 0.5}), 1)
out['interp_used'] = e2._interp_fn is not None
out['interp_over_spmd'] = round(out['interp_ms'] / out['spmd_ms'], 2)
out['note'] = ('single-chip serialized measurement: every pipe shard '
               'executes on one device, so the scan substrate pays its '
               'fill/drain bubble (1+(S-1)/m) as REAL compute; on '
               'parallel hardware both paths pay it as idle stages — '
               'the ratio is expected to narrow there (analytic, '
               'unmeasurable in this environment)')
print('RESULT:' + json.dumps(out))
"""
    env = dict(__import__("os").environ)
    env.pop("JAX_PLATFORMS", None)
    try:
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=900)
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT:"):
                return json.loads(line[len("RESULT:"):])
        return {"error": (proc.stderr or proc.stdout)[-200:]}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def bench_13b_memory_plan():
    """GPT-2 13B ZeRO-3 memory feasibility (BASELINE config 4): exact
    per-device bytes of the sharded state groups under the ZeRO policy
    at a 128-chip data mesh, computed from abstract shapes (eval_shape —
    no 13B allocation happens). The execution path itself is validated
    by the driver's dryrun_multichip on tiny shapes; this records that
    the REAL config's optimizer state divides across the mesh."""
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy

    cfg = gpt2_config("gpt2-13b", n_positions=1024, dropout=0.0)
    model = GPT2ForCausalLM(cfg)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           {"input_ids": np.zeros((1, 1024), np.int32)}))

    class MeshShim:  # axis sizes are all the policy's pspec math needs
        shape = {"pipe": 1, "data": 128, "model": 1}

    policy = ZeroShardingPolicy(MeshShim(), stage=3)
    plan = policy.pad_plan(shapes)

    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(shapes))
    # bf16 params (stage-3 sharded) + fp32 master + 2 fp32 adam
    # moments — the per-component closed form the memory ledger
    # validates against (ZeroShardingPolicy.memory_plan; the
    # memory_ledger bench leg scores it vs a LIVE engine)
    comp = policy.memory_plan(shapes, compute_bytes=2, sr_mode=False,
                              gas=1)
    per_dev = comp["params"] + comp["master"] + comp["opt_state"]
    return {"params_b": round(n_params / 1e9, 2),
            "mesh": dict(MeshShim.shape),
            "padded_leaves": len(plan),
            "state_gb_per_device": round(per_dev / 2**30, 2),
            "unsharded_state_gb": round(n_params * 14 / 2**30, 1),
            # the plan is no longer analytic-only: tests/test_zero3_13b.py
            # EXECUTES the sharded init + per-device byte measurement at
            # the full 12.85B shape on the 8-device CPU mesh (plus real
            # sharded update steps at 6.4B/0.1B — the update program is
            # depth-repeated, structure-identical), gated DS_TPU_RUN_13B=1
            # because the full run needs ~110 GB host RAM
            "executed_validation": "tests/test_zero3_13b.py"}


def bench_memory_ledger():
    """Memory-ledger plan-vs-measured validation + overhead guard
    (ISSUE 8). Three parts:

    (a) 13B plan vs ledger arithmetic, abstract: the per-component
        `ZeroShardingPolicy.memory_plan` at the 128-chip bf16
        master-less config against the closed-form 6 B/param / dp —
        the two derivations must agree, or the feasibility number the
        ZeRO-3 roadmap leans on is wrong.
    (b) EXECUTED plan-vs-ledger-vs-measured on the live mesh: a scaled
        GPT-2 through the exact 13B code path (bf16 SR ZeRO-3, sharded
        init), per-component deltas between the plan formula, what the
        ledger registered, and real per-device shard bytes
        (addressable_shards — a measurement, not arithmetic).
    (c) overhead guard: paired order-alternating A/B windows (the
        numerics_overhead methodology), monitor ON both legs, memory
        ledger off vs on — reconciliation is fence-aligned host dict
        math and must stay inside the monitor's <3% contract."""
    import shutil
    import tempfile
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import (GPT2ForCausalLM,
                                           gpt2_config,
                                           tiny_gpt2_config)
    from deepspeed_tpu.monitor.memory import plan_vs_measured
    from deepspeed_tpu.runtime.mesh import build_mesh
    from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy
    from deepspeed_tpu import initialize

    out = {}

    # -- (a) 13B abstract: plan components vs the closed form ----------
    cfg13 = gpt2_config("gpt2-13b", n_positions=1024, dropout=0.0)
    shapes13 = jax.eval_shape(
        lambda: GPT2ForCausalLM(cfg13).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 1024), np.int32)}))

    class MeshShim:
        shape = {"pipe": 1, "data": 128, "model": 1}

    plan13 = ZeroShardingPolicy(MeshShim(), 3).memory_plan(
        shapes13, compute_bytes=2, sr_mode=True, gas=1)
    n13 = sum(int(np.prod(l.shape))
              for l in jax.tree_util.tree_leaves(shapes13))
    closed_form = 6.0 * n13 / MeshShim.shape["data"]
    planned13 = plan13["params"] + plan13["opt_state"]
    out["plan_13b"] = {
        "params_b": round(n13 / 1e9, 2),
        "components_gb": {k: round(v / 2**30, 3)
                          for k, v in plan13.items()},
        "state_gb_per_device": round(planned13 / 2**30, 3),
        "closed_form_gb_per_device": round(closed_form / 2**30, 3),
        # padding of non-divisible leaves makes the plan slightly
        # larger than 6N/dp, never smaller
        "vs_closed_form_pct": round(
            (planned13 - closed_form) / closed_form * 100.0, 3),
    }
    assert abs(out["plan_13b"]["vs_closed_form_pct"]) < 5.0, out

    # -- (b) executed: scaled 13B code path, plan vs ledger vs measured
    n_dev = len(jax.devices())
    mesh = build_mesh({"pipe": 1, "data": n_dev, "model": 1})
    cfg_s = gpt2_config("gpt2-125m", dropout=0.0, dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16, vocab_size=512,
                        n_positions=64, n_layer=2)
    model = GPT2ForCausalLM(cfg_s)
    params = model.init(
        jax.random.PRNGKey(0),
        {"input_ids": np.zeros((n_dev, 64), np.int32)})
    tmp = tempfile.mkdtemp(prefix="ds_memledger_bench_")
    try:
        engine, _, _, _ = initialize(
            model=model, model_parameters=params, mesh=mesh,
            config={
                "train_micro_batch_size_per_gpu": n_dev,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1000,
                "bf16": {"enabled": True, "master_weights": False},
                "zero_optimization": {"stage": 3},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                # fence every step so the 3-step run emits memory events
                "async_dispatch": {"enabled": True, "steps_per_sync": 1},
                "monitor": {"enabled": True, "sinks": ["jsonl"],
                            "output_path": tmp},
            })
        shapes = jax.eval_shape(lambda t: t, engine.state.params)
        plan = engine.zero_policy.memory_plan(
            shapes, compute_bytes=2, sr_mode=True, gas=1)
        engine.monitor.set_memory_plan(plan)
        for i in range(3):
            ids = np.random.default_rng(i).integers(
                0, cfg_s.vocab_size, (1, n_dev, 64)).astype(np.int32)
            loss = engine.train_batch(batch={"input_ids": ids})
        _sync(loss)
        snap = engine.monitor.snapshot()
        led = snap["memory_ledger"]
        cats = led["hbm"]["categories"]

        dev0 = jax.devices()[0]

        def dev_bytes(tree):
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                if isinstance(leaf, jax.Array):
                    for sh in leaf.addressable_shards:
                        if sh.device == dev0:
                            total += sh.data.nbytes
            return total

        measured = {"params": dev_bytes(engine.state.params),
                    "opt_state": dev_bytes(engine.state.opt_state)}
        out["executed"] = {
            "devices": n_dev,
            "plan_vs_ledger": plan_vs_measured(plan, cats),
            "plan_vs_measured": plan_vs_measured(plan, measured),
            "ledger_event_plan": led.get("plan") is not None,
        }
        for comp in ("params", "opt_state"):
            for scored in ("plan_vs_ledger", "plan_vs_measured"):
                d = out["executed"][scored][comp]["delta_pct"]
                assert d is not None and abs(d) < 15.0, \
                    (scored, comp, out["executed"][scored])
        mem_events = sum(
            1 for line in open(os.path.join(tmp, "events.jsonl"))
            if json.loads(line).get("kind") == "memory")
        out["executed"]["memory_events"] = mem_events
        assert mem_events > 0
        engine.monitor.close()
        del engine, params
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # -- (c) overhead guard: memory ledger off vs on -------------------
    batch, seq = 8, 64
    steps, warmup, windows = 12, 4, 8
    cfg_t = tiny_gpt2_config(n_positions=seq, dropout=0.0)
    tmp = tempfile.mkdtemp(prefix="ds_memledger_ab_")

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg_t.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    def build(mem_on):
        model = GPT2ForCausalLM(cfg_t)
        p = model.init(jax.random.PRNGKey(0),
                       {"input_ids": np.zeros((batch, seq), np.int32)})
        engine, _, _, _ = initialize(
            model=model, model_parameters=p,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 100000,
                "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                # fences every 3 steps: the reconciliation cost must
                # sit INSIDE the measured window, several times over
                "async_dispatch": {"enabled": True, "steps_per_sync": 3},
                "monitor": {"enabled": True, "sinks": ["jsonl"],
                            "output_path": tmp,
                            "job_name": "on" if mem_on else "off",
                            "memory": {"enabled": mem_on}},
            })
        del p
        assert engine.monitor.memory_enabled == mem_on
        for i in range(warmup):
            loss = engine.train_batch(batch=make_batch(i))
        _sync(loss)
        return engine

    def window(engine, base):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batch=make_batch(base + i))
        _sync(loss)
        return time.perf_counter() - t0

    try:
        engines = {"off": build(False), "on": build(True)}
        ratios = []
        for w in range(windows):
            order = ("off", "on") if w % 2 == 0 else ("on", "off")
            t = {}
            for name in order:
                t[name] = window(engines[name], 1000 + w * steps)
            ratios.append(t["on"] / t["off"])
        overhead = (float(np.median(ratios)) - 1.0) * 100.0
        out["overhead_pct"] = round(overhead, 2)
        out["windows_measured"] = len(ratios)
        out["regressed"] = bool(overhead >= 3.0)
        engines["on"].monitor.close()
        engines["off"].monitor.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_offload_overlap():
    """ZeRO-Offload chunk-pipeline overlap, measured on REAL transfers
    (VERDICT r3 #8): the production path (all chunk D2H copies started
    async up front, host CPU-Adam while later chunks are in flight,
    async H2D drain) vs a strict sequential
    fetch-then-compute-then-upload loop over the SAME buffers. The
    ratio isolates what the async pipeline buys at whatever link speed
    this environment has (a link much slower than the compute
    compresses the ratio toward 1)."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam

    n = 16 << 20            # 64 MB fp32 of grads on the wire (bf16: 32)
    chunk = 4 << 20
    master = np.zeros(n, np.float32)
    adam = DeepSpeedCPUAdam(n, lr=1e-4)
    flat = jnp.full((n,), 1e-3, jnp.bfloat16)
    _sync(flat[0].astype(jnp.float32))
    bounds = [(i, min(i + chunk, n)) for i in range(0, n, chunk)]

    def pipelined():
        # All D2H started async up front; H2D uploads run on a side
        # thread so the upload of chunk k overlaps the D2H drain +
        # CPU-Adam of chunk k+1 (true double-buffering — the transfer
        # bytes move in C with the GIL released).
        import concurrent.futures as cf
        adam.begin_step()
        chunks = [flat[lo:hi] for lo, hi in bounds]
        for c in chunks:
            c.copy_to_host_async()
        with cf.ThreadPoolExecutor(1) as up:
            futs = []
            for (lo, hi), c in zip(bounds, chunks):
                g = np.asarray(c).astype(np.float32, copy=False)
                adam.step_chunk(lo, hi, master[lo:hi], g, lr=1e-4)
                futs.append(up.submit(jnp.asarray, master[lo:hi].copy()))
            outs = [f.result() for f in futs]
        _sync(jnp.concatenate(outs)[0])

    def sequential():
        adam.begin_step()
        outs = []
        for lo, hi in bounds:
            g = np.asarray(flat[lo:hi]).astype(np.float32, copy=False)
            adam.step_chunk(lo, hi, master[lo:hi], g, lr=1e-4)
            out = jnp.asarray(master[lo:hi].copy())
            _sync(out[0])
            outs.append(out)

    def d2h_only():
        chunks = [flat[lo:hi] for lo, hi in bounds]
        for c in chunks:
            c.copy_to_host_async()
        for c in chunks:
            np.asarray(c).astype(np.float32, copy=False)

    def h2d_only():
        outs = [jnp.asarray(master[lo:hi].copy()) for lo, hi in bounds]
        _sync(jnp.concatenate(outs)[0])

    def compute_only(g_host):
        adam.begin_step()
        for lo, hi in bounds:
            adam.step_chunk(lo, hi, master[lo:hi], g_host[lo:hi], lr=1e-4)

    def duplex_probe():
        """Both directions in flight at once: all D2H async + H2D on a
        side thread, then drain. Wall ~= max(d2h, h2d) on a full-duplex
        link, ~= d2h + h2d when the link serializes transfers — THE
        measurement that decides what 'ideal overlap' can even be on
        this link."""
        import concurrent.futures as cf
        chunks = [flat[lo:hi] for lo, hi in bounds]
        for c in chunks:
            c.copy_to_host_async()
        with cf.ThreadPoolExecutor(1) as up:
            futs = [up.submit(jnp.asarray, master[lo:hi].copy())
                    for lo, hi in bounds]
            for c in chunks:
                np.asarray(c).astype(np.float32, copy=False)
            outs = [f.result() for f in futs]
        _sync(jnp.concatenate(outs)[0])

    g_host = np.asarray(flat).astype(np.float32, copy=False)
    pipelined()  # warmup all programs
    sequential()
    compute_only(g_host)
    d2h_only()
    h2d_only()
    duplex_probe()
    t_pipe = min(timeit_once(pipelined) for _ in range(3))
    t_seq = min(timeit_once(sequential) for _ in range(3))
    t_d2h = min(timeit_once(d2h_only) for _ in range(3))
    t_h2d = min(timeit_once(h2d_only) for _ in range(3))
    t_dup = min(timeit_once(duplex_probe) for _ in range(3))
    t_comp = min(timeit_once(lambda: compute_only(g_host))
                 for _ in range(3))
    # Two ideals (VERDICT r4 #8): `ideal_full_duplex` assumes D2H and
    # H2D ride independent channels (real TPU hosts: PCIe is
    # full-duplex); `ideal_this_link` uses the MEASURED duplex probe —
    # on a link that serializes transfers, t_dup ~= t_d2h + t_h2d and
    # no software pipeline can beat it. The ideal wall is
    # max(link-busy, compute) since the pipeline overlaps CPU-Adam
    # with transfers too. measured/ideal_this_link is the honest
    # pipelining-quality score; ideal_full_duplex is what the same
    # code achieves on real PCIe.
    legs = (t_d2h, t_comp, t_h2d)
    ideal_full = sum(legs) / max(max(legs), 1e-9)
    ideal_link = t_seq / max(t_dup, t_comp, 1e-9)
    return {"bytes_on_wire_mb": round(n * 2 / 2**20, 1),
            "chunks": len(bounds),
            "sequential_s": round(t_seq, 2),
            "pipelined_s": round(t_pipe, 2),
            "measured_overlap_speedup": round(t_seq / t_pipe, 2),
            "d2h_only_s": round(t_d2h, 2),
            "h2d_only_s": round(t_h2d, 2),
            "both_directions_concurrent_s": round(t_dup, 2),
            "link_duplex_factor": round((t_d2h + t_h2d) /
                                        max(t_dup, 1e-9), 2),
            "compute_only_s": round(t_comp, 2),
            "ideal_overlap_speedup": round(ideal_full, 2),
            "ideal_this_link_speedup": round(ideal_link, 2),
            "pipelining_quality": round(
                (t_seq / t_pipe) / max(ideal_link, 1e-9), 2)}


def bench_async_dispatch():
    """Async dispatch pipeline A/B (ISSUE 2) on the gpt2-cpu-smoke
    model: the SAME training loop run (a) fully synced — per-step host
    LR scheduler + scalar upload, per-step fp16 `device_get(overflow)`,
    batch collate on the critical path — vs (b) async — device-resident
    LR schedule compiled into the step, zero per-step host syncs,
    background PrefetchLoader staging. Reports steps/s and the measured
    host-blocked time per step (wall time the host spends inside
    train_batch before it can dispatch the next step). The win is the
    overlap of host-side Python/collate with device compute."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    from deepspeed_tpu import initialize

    # Small shapes on purpose: the A/B isolates PER-STEP HOST OVERHEAD
    # (input pipeline + scheduler python + lr upload + overflow
    # readback), so the device step must not dwarf it. On the CPU
    # backend of this container buffer DONATION serializes chained
    # dispatch (dispatch k+1 blocks until step k completes), so the
    # async win here is a LOWER bound for an accelerator. The input
    # pipeline does tokenizer-weight numpy work per
    # microbatch (measured and reported): the synced loop pays it on
    # the critical path, the async loop's PrefetchLoader overlaps it
    # with the in-flight step — numpy releases the GIL, so the worker
    # thread genuinely runs during device compute.
    batch, seq, gas = 8, 32, 1
    steps, warmup, windows = 30, 5, 5
    cfg = tiny_gpt2_config(n_positions=seq, dropout=0.0)

    def make_micro(i):
        # synthetic tokenizer: ~1 MB of "text" bytes hashed into vocab
        # ids (the per-batch host work a real loader does)
        rng = np.random.default_rng(i)
        raw = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
        toks = (raw.astype(np.int32) * 31 + 7) % cfg.vocab_size
        return {"input_ids": toks[:batch * seq].reshape(batch, seq)}

    def micro_stream():
        i = 0
        while True:
            yield make_micro(i)
            i += 1

    def build(async_enabled):
        model = GPT2ForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((batch, seq),
                                                   np.int32)})
        engine, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 100000,
                # modest initial scale: the point is the steady-state
                # hot path, not a scale-search prologue of skipped steps
                "fp16": {"enabled": True, "initial_scale_power": 8},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "scheduler": {"type": "WarmupLR",
                              "params": {"warmup_min_lr": 0.0,
                                         "warmup_max_lr": 1e-4,
                                         "warmup_num_steps": 1000}},
                "async_dispatch": {"enabled": async_enabled,
                                   "prefetch_depth": 2},
            })
        del params
        assert engine.async_dispatch_enabled() == async_enabled
        src = engine.prefetch(micro_stream()) if async_enabled \
            else micro_stream()
        for _ in range(warmup):
            loss = engine.train_batch(data_iter=src)
        _sync(loss)
        return engine, src

    def window(engine, src):
        host_blocked = 0.0
        t0 = time.perf_counter()
        for _ in range(steps):
            h0 = time.perf_counter()
            loss = engine.train_batch(data_iter=src)
            host_blocked += time.perf_counter() - h0
        _sync(loss)
        return time.perf_counter() - t0, host_blocked, loss

    # both engines built up front; windows INTERLEAVE so load drift on
    # a shared machine hits both legs equally
    legs = {False: build(False), True: build(True)}
    best = {False: (float("inf"), 0.0, None),
            True: (float("inf"), 0.0, None)}
    for _ in range(windows):
        for mode in (False, True):
            wall, host, loss = window(*legs[mode])
            if wall < best[mode][0]:
                best[mode] = (wall, host, loss)
    legs[True][1].close()

    def report(mode):
        wall, host, loss = best[mode]
        return {"steps_per_sec": round(steps / wall, 2),
                "host_blocked_ms_per_step": round(host * 1e3 / steps, 3),
                "step_ms": round(wall * 1e3 / steps, 3),
                "loss": round(float(jax.device_get(loss)), 3)}

    t0 = time.perf_counter()
    for i in range(20):
        make_micro(1000 + i)
    input_ms = (time.perf_counter() - t0) * 1e3 / 20

    out = {"model": "gpt2-tiny-smoke (fp16 + WarmupLR)",
           "input_pipeline_ms_per_batch": round(input_ms, 3),
           "sync": report(False), "async": report(True)}
    out["async_speedup"] = round(
        out["async"]["steps_per_sec"] / out["sync"]["steps_per_sec"], 3)
    out["async_faster"] = \
        out["async"]["steps_per_sec"] > out["sync"]["steps_per_sec"]
    out["host_unblocked_factor"] = round(
        out["sync"]["host_blocked_ms_per_step"] /
        max(out["async"]["host_blocked_ms_per_step"], 1e-9), 2)
    return out


def bench_async_checkpoint():
    """Zero-stall async checkpointing A/B (ISSUE 3): the SAME training
    loop with a save_checkpoint dropped into the middle of a timed
    window, run with checkpoint.async_save=false (legacy inline
    device_get + npz serialization on the train loop) vs =true (the
    loop pays only the device-side snapshot; a background writer
    serializes into `<tag>.tmp` and commits atomically). Reports
    steps/s over the save window, the isolated stall (save-window wall
    minus a no-save baseline window, best-of-N interleaved), the
    blocking time of the save_checkpoint call itself, and two
    bit-identical checks: an async-saved checkpoint vs a sync-saved
    one of the same state — with training continuing (donating
    buffers / mutating host masters in place) while the writer is
    still serializing — for (a) the bf16+master ZeRO-2 engine and
    (b) a ZeRO-Offload engine with the compressed int8 wire (masters,
    Adam moments, wire shadow/residual included)."""
    import shutil
    import tempfile
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    from deepspeed_tpu import initialize
    from deepspeed_tpu.runtime.checkpoint import checkpoint_dirs_bit_identical

    batch, seq = 8, 64
    steps, save_at, windows = 12, 6, 3
    # ~7M params -> ~130 MB of fp32 master+moments+module per save:
    # enough that inline serialization stalls the loop for many steps,
    # small enough for the CPU smoke run
    cfg = tiny_gpt2_config(n_layer=4, n_embd=384, n_head=8,
                           n_positions=seq)

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    def build(async_save, extra=None):
        model = GPT2ForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((batch, seq),
                                                   np.int32)})
        config = {
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 100000,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "checkpoint": {"async_save": async_save},
        }
        config.update(extra or {})
        engine, _, _, _ = initialize(model=model, model_parameters=params,
                                     config=config)
        del params
        for i in range(3):
            loss = engine.train_batch(batch=make_batch(i))
        _sync(loss)
        return engine

    def window(engine, save_dir=None, tag=None):
        save_call = 0.0
        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batch=make_batch(100 + i))
            if i == save_at and save_dir is not None:
                s0 = time.perf_counter()
                engine.save_checkpoint(save_dir, tag=tag)
                save_call = time.perf_counter() - s0
        _sync(loss)
        return time.perf_counter() - t0, save_call

    tmp = tempfile.mkdtemp(prefix="ds_async_ckpt_bench_")
    out = {}
    try:
        engines = {"sync": build(False), "async": build(True)}
        rec = {k: {"base": [], "save": [], "stall": [], "save_call": []}
               for k in engines}
        # interleaved windows: load drift hits both legs equally; the
        # stall is computed PAIRWISE (save window minus the adjacent
        # no-save window from the same load regime), then medianed —
        # robust against drift in a way best-of subtraction is not
        for w in range(windows):
            for name, engine in engines.items():
                b, _ = window(engine)
                s, call = window(engine, tmp, f"{name}_w{w}")
                # the commit itself happens off the timed window; the
                # barrier here also bounds disk usage across windows
                engine.wait_for_checkpoint()
                r = rec[name]
                r["base"].append(b)
                r["save"].append(s)
                r["stall"].append(s - b)
                r["save_call"].append(call)

        def leg(name):
            r = rec[name]
            stall = max(float(np.median(r["stall"])), 0.0)
            return {
                "steps_per_sec_baseline": round(
                    steps / min(r["base"]), 2),
                "steps_per_sec_with_save": round(
                    steps / min(r["save"]), 2),
                "train_loop_stall_ms": round(stall * 1e3, 1),
                "save_call_blocked_ms": round(
                    float(np.median(r["save_call"])) * 1e3, 1),
            }, stall

        out["sync"], stall_sync = leg("sync")
        out["async"], stall_async = leg("async")
        out["stall_reduction"] = round(
            stall_sync / max(stall_async, 1e-3), 1)
        out["save_call_speedup"] = round(
            float(np.median(rec["sync"]["save_call"])) /
            max(float(np.median(rec["async"]["save_call"])), 1e-4), 1)

        # bit-identical under concurrent training: sync and async save
        # of the SAME state, then keep stepping (buffer donation) while
        # the writer is still serializing
        e = engines["async"]
        e.save_checkpoint(tmp, tag="bit_sync", async_save=False,
                          save_latest=False)
        e.save_checkpoint(tmp, tag="bit_async")
        for i in range(2):
            loss = e.train_batch(batch=make_batch(500 + i))
        _sync(loss)
        e.wait_for_checkpoint()
        out["bit_identical"] = checkpoint_dirs_bit_identical(
            os.path.join(tmp, "bit_sync"), os.path.join(tmp, "bit_async"))

        # same check for ZeRO-Offload wire state (host masters + Adam
        # moments + int8 shadow/residual): train_batch mutates the host
        # master IN PLACE while the writer runs
        del engines
        wire_cfg = tiny_gpt2_config(n_positions=seq, dropout=0.0)
        model = GPT2ForCausalLM(wire_cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((batch, seq),
                                                   np.int32)})
        oe, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 100000,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2, "cpu_offload": True,
                                      "offload_wire": {"grad_bits": 8,
                                                       "param_bits": 8}},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            })
        del params
        for i in range(3):
            loss = oe.train_batch(batch=make_batch(i))
        _sync(loss)
        oe.save_checkpoint(tmp, tag="wire_sync", async_save=False,
                           save_latest=False)
        oe.save_checkpoint(tmp, tag="wire_async", async_save=True)
        for i in range(2):
            loss = oe.train_batch(batch=make_batch(600 + i))
        _sync(loss)
        oe.wait_for_checkpoint()
        out["offload_wire_bit_identical"] = checkpoint_dirs_bit_identical(
            os.path.join(tmp, "wire_sync"),
            os.path.join(tmp, "wire_async"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_fused_hot_loop():
    """Fused non-attention hot loop A/B (ISSUE 6): the SAME GPT-2 stack
    fwd+bwd with (a) the fused epilogue kernels + per-fusion remat
    (`fused_ops="on"`, `remat_policy="save_fused_epilogues"` — the
    shipped fast configuration) vs (b) unfused chains + full-block
    remat (the previous default).  Parity is pinned hard: identical
    fp32 loss and grads to 1e-5, bf16 loss to 1e-2 (the fused chain
    computes bias+residual+LN in fp32 — strictly MORE precise than the
    bf16-rounded unfused adds).  On CPU the fused ops lower to the
    fused-XLA fallback, so the measured win is the per-fusion remat's
    recompute avoidance (the backward skips re-running attention and
    the LN/GeLU chains); on TPU the Pallas kernels additionally collapse
    the launch count.  Also records `top_non_matmul_sinks` for both
    arms — the roofline regression guard: the fused arm's elementwise
    sinks carry the fused-op labels instead of anonymous LN/GeLU
    fusion chains."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        n_layer, n_embd, n_head, batch, seq, inner, windows = \
            12, 768, 12, 8, 1024, 4, 4
    else:
        n_layer, n_embd, n_head, batch, seq, inner, windows = \
            4, 256, 8, 8, 128, 2, 4
    ids = np.random.default_rng(0).integers(
        0, 50257, (batch, seq)).astype(np.int32)
    batch_d = {"input_ids": ids}

    def build(fused, policy, dtype=jnp.float32):
        cfg = gpt2_config("gpt2-125m", n_layer=n_layer, n_embd=n_embd,
                          n_head=n_head, n_positions=seq, dropout=0.0,
                          dtype=dtype, param_dtype=jnp.float32,
                          remat=True, remat_policy=policy,
                          fused_ops=fused)
        return GPT2ForCausalLM(cfg)

    m_fused = build("on", "save_fused_epilogues")
    m_plain = build("off", None)
    params = m_plain.init(jax.random.PRNGKey(0),
                          {"input_ids": np.zeros((batch, seq), np.int32)})

    def grad_fn(m):
        return jax.jit(lambda p: jax.grad(
            lambda p: m.loss_fn(p, batch_d, deterministic=True))(p))

    g_fused, g_plain = grad_fn(m_fused), grad_fn(m_plain)

    # parity: fwd loss + full grad tree, fused vs unfused on the SAME
    # params (fp32 — bit-level modulo reassociation)
    lf = float(m_fused.loss_fn(params, batch_d, deterministic=True))
    lu = float(m_plain.loss_fn(params, batch_d, deterministic=True))
    gf, gu = g_fused(params), g_plain(params)
    gmax = max(float(jnp.abs(l).max())
               for l in jax.tree_util.tree_leaves(gu))
    gdiff = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree_util.tree_leaves(gf),
                                jax.tree_util.tree_leaves(gu)))

    def window(fn):
        t0 = time.perf_counter()
        for _ in range(inner):
            r = fn(params)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / inner

    best = {"fused": float("inf"), "unfused": float("inf")}
    for _ in range(windows):               # interleaved A/B windows
        best["fused"] = min(best["fused"], window(g_fused))
        best["unfused"] = min(best["unfused"], window(g_plain))
    speedup = best["unfused"] / best["fused"]

    # bf16 parity (values only; the fused fp32 chain is the more
    # precise one, so this bounds the bf16-rounding disagreement)
    bf = build("on", "save_fused_epilogues", jnp.bfloat16)
    bu = build("off", None, jnp.bfloat16)
    lbf = float(bf.loss_fn(params, batch_d, deterministic=True))
    lbu = float(bu.loss_fn(params, batch_d, deterministic=True))

    out = {"shape": f"L{n_layer} E{n_embd} B{batch} T{seq} fp32"
                    + ("" if on_tpu else " (xla-fallback fused impl)"),
           "fused_fwd_bwd_ms": round(best["fused"] * 1e3, 1),
           "unfused_fwd_bwd_ms": round(best["unfused"] * 1e3, 1),
           "fused_speedup": round(speedup, 3),
           "fused_faster": bool(speedup >= 1.0),
           "loss_abs_diff_fp32": abs(lf - lu),
           "grad_max_abs_diff_fp32": gdiff,
           "grad_rel_diff_fp32": gdiff / max(gmax, 1e-20),
           "loss_abs_diff_bf16": abs(lbf - lbu),
           "parity_ok": bool(abs(lf - lu) <= 1e-5 and
                             gdiff / max(gmax, 1e-20) <= 1e-5 and
                             abs(lbf - lbu) <= 1e-2)}
    try:
        # roofline guard: top elementwise (flops==0) sinks per arm —
        # the fused arm's rows are attributable to the fused kernels
        from deepspeed_tpu.profiling.flops_profiler.profiler import \
            per_fusion_costs
        shapes = jax.eval_shape(lambda: params)

        def non_matmul_top(m, n=3):
            # ranked against the v5e roofline whatever device traces
            # this leg: the rows are an estimate from HLO, not a timing
            rows = per_fusion_costs(
                jax.grad(lambda p: m.loss_fn(p, batch_d,
                                             deterministic=True)),
                shapes, peak_flops=197e12, hbm_gbps=819.0)
            ew = [r for r in rows if r["kind"] != "dot" and
                  r["flops"] == 0]
            return [{"op": (r["op"] or r.get("kernel") or
                            r["name"])[-100:],
                     "est_us": r["est_us"], "bytes": r["bytes"],
                     "calls": r["calls"]} for r in ew[:n]]
        out["top_non_matmul_sinks"] = {
            "unfused": non_matmul_top(m_plain),
            "fused": non_matmul_top(m_fused)}
    except Exception as e:
        out["top_non_matmul_sinks"] = f"unavailable: {type(e).__name__}"
    return out


def bench_pipe_interleave():
    """Interleaved (virtual-stage) 1F1B A/B (ISSUE 6): the SAME
    PipelineModule of GPT-2 blocks through the compiled 1F1B executor
    at num_virtual_stages=1 vs 2, p=4 stages, m=8 microbatches on the
    8-device virtual CPU mesh (pipe=4 x data=2).  Loss parity is
    BIT-EXACT (same microbatch computations, same accumulation
    structure), best-of-N interleaved windows, and the clock tables'
    analytic bubble fractions ride along: v=2 executes ~2m·v
    chunk-ticks of 1/v work in fewer stage-time units
    ((p-1)/(v·m+p-1) bubble vs (p-1)/(m+p-1)).  The wall-clock ratio
    on the virtual mesh under-reads the analytic bound (per-tick
    dispatch overhead doubles while compute halves); on parallel
    hardware the bubble is pure idle time and the analytic number is
    the expectation."""
    import subprocess
    import sys
    from deepspeed_tpu.runtime.pipe.interp import build_clock_tables

    out = {}
    S, m, v = 4, 8, 2
    for vv in (1, v):
        t = build_clock_tables(m, S, num_virtual_stages=vv)
        busy = int((t["fwd_mb"] >= 0).sum() + (t["bwd_mb"] >= 0).sum())
        out[f"v{vv}_analytic"] = {
            "ticks": int(t["num_ticks"]),
            "wall_stage_units": round(t["num_ticks"] / vv, 1),
            "bubble_fraction": round(1 - busy / (t["num_ticks"] * S), 3)}
    out["analytic_speedup"] = round(
        out["v1_analytic"]["wall_stage_units"] /
        out[f"v{v}_analytic"]["wall_stage_units"], 3)

    script = r"""
import os, json, time
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
import deepspeed_tpu
from deepspeed_tpu.runtime.pipe.module import PipelineModule, LayerSpec
from deepspeed_tpu.models.gpt2 import GPT2Block, tiny_gpt2_config

L, S, GAS, MB, T, E = 8, 4, 8, 4, 128, 256
cfg = tiny_gpt2_config(n_layer=L, n_embd=E, n_head=8, n_positions=T)
rng0 = np.random.RandomState(0)
xb = rng0.randn(MB * GAS, T, E).astype(np.float32)
batch = {'x': xb, 'y': xb * 0.5}

def build(v):
    mod = PipelineModule([LayerSpec(GPT2Block, cfg) for _ in range(L)],
                         num_stages=S,
                         loss_fn=lambda y, lab: jnp.mean(
                             (y - lab).astype(jnp.float32) ** 2))
    prm = mod.init_params(jax.random.PRNGKey(0),
                          jnp.asarray(xb[:MB]))
    ds = {'train_micro_batch_size_per_gpu': MB,
          'gradient_accumulation_steps': GAS, 'steps_per_print': 1000,
          'optimizer': {'type': 'Adam', 'params': {'lr': 1e-3}},
          'mesh': {'pipe': S, 'data': 8 // S, 'model': 1},
          'pipeline': {'num_virtual_stages': v}}
    e, _, _, _ = deepspeed_tpu.initialize(model=mod, model_parameters=prm,
                                          config=ds)
    return e

def window(e, n=3):
    t0 = time.perf_counter()
    for i in range(n):
        l = e.train_batch(batch=batch)
    float(jax.device_get(l))
    return (time.perf_counter() - t0) / n * 1e3, float(jax.device_get(l))

out = {}
e1, e2 = build(1), build(2)
l1 = float(jax.device_get(e1.train_batch(batch=batch)))
l2 = float(jax.device_get(e2.train_batch(batch=batch)))
out['loss_parity_diff'] = abs(l1 - l2)
out['interp_used'] = e1._interp_fn is not None and e2._interp_fn is not None
best = {1: float('inf'), 2: float('inf')}
losses = {}
for w in range(3):                        # interleaved A/B windows
    for vsel, e in ((1, e1), (2, e2)):
        ms, ls = window(e)
        best[vsel] = min(best[vsel], ms)
        losses[vsel] = ls
out['loss_parity_diff_after_steps'] = abs(losses[1] - losses[2])
out['plain_1f1b_ms'] = round(best[1], 1)
out['interleaved_ms'] = round(best[2], 1)
out['interleave_speedup'] = round(best[1] / best[2], 3)
out['interleaved_faster'] = best[2] < best[1]
print('RESULT:' + json.dumps(out))
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    try:
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True,
                              timeout=900)
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT:"):
                out.update(json.loads(line[len("RESULT:"):]))
                out["note"] = (
                    "virtual-mesh measurement: per-tick dispatch "
                    "overhead doubles at v=2 while per-tick compute "
                    "halves, so the wall ratio under-reads the "
                    "analytic bubble win; parity is bit-exact")
                return out
        out["error"] = (proc.stderr or proc.stdout)[-300:]
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def bench_monitor_overhead():
    """Telemetry overhead A/B (ISSUE 5): the SAME async-dispatch train
    loop with monitor off vs monitor on (JSONL sink + device-side
    metric accumulators + fence drains every steps_per_sync). The
    monitor's contract is <3% step-time overhead: per-step cost is one
    extra jitted fold dispatch (a 6-float vector add, async like the
    step itself), per-fence cost is one device_get of that vector plus
    gauge sampling and a sink write. Windows INTERLEAVE (best-of-N per
    leg) so load drift on a shared machine hits both legs equally.
    Also returns `engine.monitor.snapshot()` — bench extras and
    training telemetry share one schema by construction."""
    import shutil
    import tempfile
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    from deepspeed_tpu import initialize

    batch, seq = 8, 64
    steps, warmup, windows, repetitions = 20, 5, 6, 3
    cfg = tiny_gpt2_config(n_positions=seq, dropout=0.0)
    tmp = tempfile.mkdtemp(prefix="ds_monitor_bench_")

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    def build(monitor_on):
        model = GPT2ForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((batch, seq),
                                                   np.int32)})
        engine, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 100000,
                "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                # fences every 5 steps so the drain cost is IN the
                # measured window, not dodged by a huge sync period
                "async_dispatch": {"enabled": True, "steps_per_sync": 5},
                "monitor": {"enabled": monitor_on,
                            "sinks": ["jsonl"],
                            "output_path": tmp,
                            "job_name": "on" if monitor_on else "off"},
            })
        del params
        assert engine.monitor.enabled == monitor_on
        for i in range(warmup):
            loss = engine.train_batch(batch=make_batch(i))
        _sync(loss)
        return engine

    def window(engine, base):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batch=make_batch(base + i))
        _sync(loss)
        return time.perf_counter() - t0

    out = {}
    try:
        engines = {"off": build(False), "on": build(True)}
        # PAIRED windows (back to back, order ALTERNATING per pair) and
        # a median of the per-pair ratios: load drift on a shared box
        # moves both legs of a pair together and the alternation
        # cancels any first-vs-second systematic, so the ratio stays
        # clean where best-of-N absolute times do not. Each pair is
        # additionally the MEDIAN of N=3 repetitions (the PR-13
        # peak-probe discipline): a single scheduler hiccup landing
        # inside one arm of one pair flaked this leg at PR-13 seed —
        # the per-window median absorbs it, and the leg's verdict
        # (`regressed`) only ever reads medians, never a raw window.
        times = {"off": [], "on": []}
        ratios = []
        for w in range(windows):
            reps = []
            for rep in range(repetitions):
                order = ("off", "on") if (w + rep) % 2 == 0 \
                    else ("on", "off")
                t = {}
                for name in order:
                    t[name] = window(
                        engines[name],
                        1000 + (w * repetitions + rep) * steps)
                times["off"].append(t["off"])
                times["on"].append(t["on"])
                reps.append(t["on"] / t["off"])
            ratios.append(float(np.median(reps)))

        best = {k: min(v) for k, v in times.items()}
        out = {
            "model": "gpt2-tiny-smoke (bf16, async dispatch, "
                     "fences every 5 steps)",
            "off": {"steps_per_sec": round(steps / best["off"], 2),
                    "step_ms": round(best["off"] * 1e3 / steps, 3)},
            "on": {"steps_per_sec": round(steps / best["on"], 2),
                   "step_ms": round(best["on"] * 1e3 / steps, 3)},
        }
        overhead = (float(np.median(ratios)) - 1.0) * 100.0
        out["overhead_pct"] = round(overhead, 2)
        out["window_repetitions"] = repetitions
        out["windows_measured"] = len(ratios)
        out["regressed"] = bool(overhead >= 3.0)
        snap = engines["on"].monitor.snapshot()
        # the proof the sink actually recorded the run: parse it back
        path = os.path.join(tmp, "on", "events.jsonl")
        n_events = sum(1 for line in open(path)
                       if json.loads(line).get("kind") == "metrics")
        out["jsonl_metric_events"] = n_events
        out["snapshot"] = {k: snap[k] for k in
                           ("loss", "lr", "samples_per_sec", "tokens",
                            "overflow_count")}
        engines["on"].monitor.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_numerics_overhead():
    """Numerics-health overhead A/B (ISSUE 7): the SAME monitor-enabled
    async-dispatch loop with monitor.numerics off vs on (per-group grad
    stats computed inside the jitted step + fence-drained health
    arrays). The accumulators share the monitor's <3% step-time
    contract: per-step cost is a few fused reductions inside the
    already-compiled program plus a list append; per-fence cost rides
    the SAME single device_get. Paired order-alternating windows,
    median-of-ratios (the monitor_overhead methodology)."""
    import shutil
    import tempfile
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, tiny_gpt2_config
    from deepspeed_tpu import initialize

    # bigger than the monitor_overhead smoke model: the numerics cost
    # is ~150 small fused reductions per step (one triple per grad
    # leaf), a FIXED dispatch cost — on a 17 ms tiny-model step it
    # reads as several percent of pure overhead-measurement noise,
    # while any realistic step amortizes it to <<1%. Sizing the model
    # up makes the leg measure the contract instead of the noise floor.
    batch, seq = 8, 128
    steps, warmup, windows = 8, 4, 10
    # shared-box jitter on a ~300 ms CPU step runs to ±3% per paired
    # window — the same order as the contract line. When the first
    # median lands within the noise band of 3%, the leg EXTENDS the
    # sample (one more batch of windows, overall median) instead of
    # flaking either way.
    extend_band = (1.5, 4.5)
    cfg = tiny_gpt2_config(n_positions=seq, n_layer=4, n_embd=256,
                           n_head=8, dropout=0.0)
    tmp = tempfile.mkdtemp(prefix="ds_numerics_bench_")

    def make_batch(i):
        ids = np.random.default_rng(i).integers(
            0, cfg.vocab_size, (1, batch, seq)).astype(np.int32)
        return {"input_ids": ids}

    def build(numerics_on):
        model = GPT2ForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((batch, seq),
                                                   np.int32)})
        engine, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={
                "train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 100000,
                "bf16": {"enabled": True},
                # the flagship-config baseline: clipping means the step
                # ALREADY reads the grads for a norm, so the numerics
                # reductions fuse with an existing pass instead of
                # adding the only one (a no-clip no-fp16 step skips
                # grad reductions entirely and would charge numerics
                # the whole first pass)
                "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "async_dispatch": {"enabled": True, "steps_per_sync": 5},
                "monitor": {"enabled": True,
                            "sinks": ["jsonl"],
                            "output_path": tmp,
                            "job_name": "on" if numerics_on else "off",
                            "numerics": {"enabled": numerics_on}},
            })
        del params
        assert engine._numerics_on == numerics_on
        for i in range(warmup):
            loss = engine.train_batch(batch=make_batch(i))
        _sync(loss)
        return engine

    def window(engine, base):
        t0 = time.perf_counter()
        for i in range(steps):
            loss = engine.train_batch(batch=make_batch(base + i))
        _sync(loss)
        return time.perf_counter() - t0

    out = {}
    try:
        engines = {"off": build(False), "on": build(True)}
        times = {"off": [], "on": []}
        ratios = []

        def run_windows(n, base):
            for w in range(n):
                order = ("off", "on") if w % 2 == 0 else ("on", "off")
                t = {}
                for name in order:
                    t[name] = window(engines[name],
                                     base + w * steps)
                times["off"].append(t["off"])
                times["on"].append(t["on"])
                ratios.append(t["on"] / t["off"])

        run_windows(windows, 1000)
        med = float(np.median(ratios))
        if extend_band[0] <= (med - 1.0) * 100.0 <= extend_band[1]:
            run_windows(windows, 5000)

        best = {k: min(v) for k, v in times.items()}
        out = {
            "model": "gpt2-tiny-smoke (bf16, async dispatch, monitor "
                     "on both legs, fences every 5 steps)",
            "off": {"steps_per_sec": round(steps / best["off"], 2),
                    "step_ms": round(best["off"] * 1e3 / steps, 3)},
            "on": {"steps_per_sec": round(steps / best["on"], 2),
                   "step_ms": round(best["on"] * 1e3 / steps, 3)},
        }
        overhead = (float(np.median(ratios)) - 1.0) * 100.0
        out["overhead_pct"] = round(overhead, 2)
        out["windows_measured"] = len(ratios)
        out["regressed"] = bool(overhead >= 3.0)
        # the health stream actually flowed: a numerics event per fence
        # with per-group grad stats
        snap = engines["on"].monitor.snapshot()
        num = snap["numerics"] or {}
        gn = num.get("grad_norm") or {}
        out["numerics_groups"] = len(gn)
        out["first_nonfinite"] = num.get("first_nonfinite")
        path = os.path.join(tmp, "on", "events.jsonl")
        out["jsonl_numerics_events"] = sum(
            1 for line in open(path)
            if json.loads(line).get("kind") == "numerics")
        assert out["numerics_groups"] > 0
        assert out["jsonl_numerics_events"] > 0
        engines["on"].monitor.close()
        engines["off"].monitor.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def timeit_once(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_zero3_overlap():
    """ZeRO-3 overlapped runtime A/B (ISSUE 9): the SAME GPT-2 stack
    trained at stage 3 with (a) the windowed gather/release schedule —
    layer k+1's all-gather issued while layer k computes, gathered
    buffers released after their fwd/bwd use, grads reduce-scattered
    per layer into the owning shard — vs (b) the naive baseline
    (stage3.release_after_use=false): the whole param stack gathered
    up front, held live through fwd+bwd, full stacked grad
    materialized before one bulk reduce-scatter.  Same total gather
    bytes either way; the win is the bounded live set (the naive arm's
    full-stack materialization + full-grad churn is real wall time on
    CPU, and idle all-gather latency on real chips).  Loss parity
    between the arms is asserted, and the memory ledger's zero3_gather
    entries are asserted against the schedule's bound: overlapped ==
    (prefetch_layers + 1) layers' worth, naive == the whole stack."""
    import jax.numpy as jnp
    from deepspeed_tpu import initialize
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        n_layer, n_embd, n_head, seq, steps, windows = 12, 768, 12, 256, 4, 4
    else:
        n_layer, n_embd, n_head, seq, steps, windows = 8, 384, 8, 64, 4, 4
    n_dev = len(jax.devices())
    prefetch = 1

    def build(stage3):
        cfg = gpt2_config("gpt2-125m", n_layer=n_layer, n_embd=n_embd,
                          n_head=n_head, vocab_size=512,
                          n_positions=seq, dropout=0.0,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          remat=True)
        model = GPT2ForCausalLM(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((n_dev, seq), np.int32)})
        engine, _, _, _ = initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": n_dev,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 100000,
                    "zero_optimization": {"stage": 3,
                                          "stage3": stage3},
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-4}}})
        assert engine.zero3_scheduler is not None, \
            "stage-3 engine did not weave the gather scheduler"
        return engine

    def batch(i):
        return {"input_ids": np.random.default_rng(i).integers(
            0, 512, (1, n_dev, seq)).astype(np.int32)}

    e_ov = build({"prefetch_layers": prefetch})
    e_nv = build({"release_after_use": False})

    staged, parity = {}, {}
    for name, e in (("overlap", e_ov), ("naive", e_nv)):
        for i in range(3):
            loss = e.train_batch(batch=batch(i))
        parity[name] = float(jax.device_get(loss))
        staged[name] = [e.stage_batch(batch(100 + i))
                        for i in range(steps)]

    def window(e, bs):
        t0 = time.perf_counter()
        for b in bs:
            loss = e.train_batch(batch=b)
        _sync(loss)
        return (time.perf_counter() - t0) / len(bs)

    best = {"overlap": float("inf"), "naive": float("inf")}
    for _ in range(windows):              # interleaved A/B windows
        best["overlap"] = min(best["overlap"],
                              window(e_ov, staged["overlap"]))
        best["naive"] = min(best["naive"],
                            window(e_nv, staged["naive"]))
    speedup = best["naive"] / best["overlap"]

    # ledger-asserted live gathered bytes: the tentpole's memory bound
    ov = e_ov.zero3_scheduler.stack_info["h"]
    nv = e_nv.zero3_scheduler.stack_info["h"]
    ov_cats = e_ov.monitor.ledger.totals()["hbm"]
    nv_cats = e_nv.monitor.ledger.totals()["hbm"]
    # Independent byte arithmetic straight from the raw param tree —
    # NOT the scheduler's own bookkeeping — so a ledger/accounting
    # regression cannot vouch for itself. (The release semantics — the
    # gathered buffers actually DYING after use — are structural in
    # the scan/remat form and only measurable against a real
    # allocator; on TPU the ledger reconcile scores them.)
    from deepspeed_tpu.models.gpt2 import stacked_block_params
    stacked = stacked_block_params(e_ov.state.params)
    stack_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(stacked))
    per_layer_indep = stack_bytes // n_layer
    extras_indep = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for k in ("wte", "wpe", "ln_f")
        for l in jax.tree_util.tree_leaves(e_ov.state.params[k]))
    window_ok = (
        # the stack's live window is exactly (prefetch + 1) layers,
        # the naive arm holds the whole stack, and the ledger's
        # zero3_gather entry equals the independently computed
        # gathered-window bytes (embeds + window x per-layer)
        ov["window_layers"] == prefetch + 1 and
        nv["window_layers"] == n_layer and
        ov_cats["zero3_gather"] ==
        per_layer_indep * (prefetch + 1) + extras_indep and
        nv_cats["zero3_gather"] == stack_bytes + extras_indep)
    assert window_ok, (ov, nv, ov_cats, nv_cats, per_layer_indep,
                       extras_indep)

    out = {"shape": f"L{n_layer} E{n_embd} B{n_dev} T{seq} fp32 "
                    f"dp={n_dev} prefetch={prefetch}",
           "overlap_step_ms": round(best["overlap"] * 1e3, 1),
           "naive_upfront_step_ms": round(best["naive"] * 1e3, 1),
           "overlap_speedup": round(speedup, 3),
           "overlap_faster": bool(speedup >= 1.0),
           "loss_abs_diff": abs(parity["overlap"] - parity["naive"]),
           "parity_ok": bool(abs(parity["overlap"] - parity["naive"])
                             <= 1e-5),
           "overlap_gathered_mb":
               round(ov_cats["zero3_gather"] / 2**20, 2),
           "naive_gathered_mb":
               round(nv_cats["zero3_gather"] / 2**20, 2),
           "window_layers": {"overlap": ov["window_layers"],
                             "naive": nv["window_layers"]},
           "per_layer_mb": round(ov["per_layer_bytes"] / 2**20, 2),
           "window_bound_ok": bool(window_ok),
           "schedule": e_ov.zero3_scheduler.describe()}
    return out


def bench_elastic_recovery():
    """Chaos bench (ISSUE 10): SIGKILL a sentinel "host" subprocess
    mid-run and measure the ElasticSupervisor's detection->resume wall
    time on the virtual mesh — teardown (drain/abandon writers), mesh
    re-formation on the survivors, ZeRO re-plan, engine rebuild, and
    the resharded restore from the last committed tag. Loss continuity
    is asserted BY the supervisor (a replayed step whose loss diverges
    from the recorded trajectory raises LossContinuityError and fails
    the leg), and re-checked here via the replayed-step count. With >=2
    devices the leg exercises the shrink+regrow path; on a single
    device it falls back to escalated-stall in-place recovery (same
    detection->resume metric, no world change)."""
    import tempfile

    import jax.numpy as jnp

    from deepspeed_tpu.elasticity.runtime import (ElasticSupervisor,
                                                  FaultInjector)

    n = len(jax.devices())
    hosts = 2 if n >= 2 and n % 2 == 0 else 1
    d_in, hid = 24, 12 * n

    def model_factory():
        rng = np.random.RandomState(0)
        params = {
            "w1": np.asarray(rng.randn(d_in, hid) * 0.1, np.float32),
            "b1": np.zeros(hid, np.float32),
            "w2": np.asarray(rng.randn(hid, 1) * 0.1, np.float32)}

        def loss_fn(p, batch, rngs=None, deterministic=False):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

        return loss_fn, params

    def batch_fn(step, spec):
        rng = np.random.RandomState(1000 + step)
        x = rng.randn(spec.total, d_in).astype(np.float32)
        y = (x[:, :1] * 0.5).astype(np.float32)
        return {"x": x.reshape(spec.gas, spec.rows, d_in),
                "y": y.reshape(spec.gas, spec.rows, 1)}

    tmp = tempfile.mkdtemp(prefix="elastic_bench_")
    cfg = {
        "steps_per_print": 100000,
        "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "elasticity": {
            "enabled": True, "max_train_batch_size": 6 * n,
            "micro_batch_sizes": [2], "version": 0.1,
            "runtime": {"enabled": True, "hosts": hosts,
                        "checkpoint_interval": 2,
                        "drain_timeout_sec": 10.0,
                        "escalate_after": 2}},
    }
    inj = FaultInjector()
    sup = ElasticSupervisor(cfg, model_factory, batch_fn,
                            save_dir=os.path.join(tmp, "ckpt"),
                            injector=inj)
    try:
        sup.run(3)    # checkpoints land at step 2 -> one replayed step
        world_before = sup.batch_spec.world
        if hosts >= 2:
            inj.spawn_host(0)
            inj.spawn_host(1)
            inj.sigkill_host(1)
            inj.wait_host_dead(1)   # let the kernel reap the sentinel
        else:
            inj.inject_stall()
            inj.inject_stall()
        t_kill = time.perf_counter()
        sup.run(8)
        resume_window_s = time.perf_counter() - t_kill
        rec = [e for e in sup.events if e["kind"] == "recovery"][0]
        grow = None
        if hosts >= 2:
            inj.return_capacity(1)
            sup.run(12)
            ups = [e for e in sup.events if e["kind"] == "scale_up"]
            grow = {"world_restored": sup.batch_spec.world,
                    "rebuild_ms": round(ups[0]["rebuild_sec"] * 1e3, 1)
                    if ups else None,
                    "at_checkpoint_boundary": bool(
                        ups and ups[0]["resumed_step"] % 2 == 0)}
        out = {
            "devices": n, "hosts": hosts,
            "cause": rec["cause"],
            "world_before": world_before,
            "world_after": rec["world_after"],
            "detect_to_resume_ms": round(
                rec["detect_to_resume_sec"] * 1e3, 1),
            "kill_to_caught_up_ms": round(resume_window_s * 1e3, 1),
            "resumed_from_tag": rec["resumed_from_tag"],
            "replayed_steps": rec["replayed_steps"],
            # the supervisor RAISES on divergence; reaching here with
            # replayed steps means the continuity assert really ran
            "loss_continuity_checked": rec["replayed_steps"] > 0,
            "loss_continuity_ok": True,
            "zero_plan_bytes_after": rec["zero_plan_bytes"],
            "recoveries": len(
                [e for e in sup.events if e["kind"] == "recovery"]),
            "grow": grow,
            "losses_finite": bool(all(
                np.isfinite(v) for v in sup.loss_history.values())),
        }
        return out
    finally:
        sup.close()


def bench_serving_throughput():
    """Serving A/B (ISSUE 12): iteration-level continuous batching vs
    request-at-a-time serving, same engine, same paged KV cache, same
    Poisson arrival stream. Also pins the two serving correctness
    contracts inline: decode-step logits BIT-exact vs the training
    forward (fp32, small-contraction regime — see docs/inference.md),
    the `kv_cache` ledger category equal to the pool bytes with
    per-request entries matching independent page arithmetic, and the
    int8 weight-only engine within tolerance of fp32."""
    from deepspeed_tpu.inference import (InferenceEngine, Request,
                                         ServingLoop, serve_sequential)
    from deepspeed_tpu.models.gpt2 import (GPT2ForCausalLM,
                                           tiny_gpt2_config)

    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    r = np.random.RandomState(0)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})
    inf_cfg = {"max_slots": 8, "prefill_chunk": 16, "sync_every": 8,
               "max_new_tokens": 32,
               "kv_cache": {"num_pages": 96, "page_size": 8}}
    eng = InferenceEngine(cfg, params, {"inference": dict(inf_cfg)})

    # -- decode-logits parity pin (fp32, total length <= 12 keeps both
    # programs in XLA-CPU's same-kernel regime -> literal bit equality)
    prompt = r.randint(0, cfg.vocab_size, size=7).astype(np.int32)
    eng.start_request(0, prompt, max_new=5)
    cur = list(prompt)
    parity_exact = True
    for _ in range(5):
        lg = np.asarray(eng.decode_once()[0])
        ref = np.asarray(model.apply(
            params, np.asarray(cur, np.int32)[None, :], True))[0, -1]
        parity_exact = parity_exact and np.array_equal(lg, ref)
        cur.append(int(lg.argmax()))
    assert parity_exact, \
        "decode logits diverged bitwise from the training forward"

    # -- kv_cache ledger vs independent page-pool arithmetic
    cats = eng.monitor.ledger.totals()["hbm"]
    ledger_exact = cats.get("kv_cache") == eng.cache.pool_bytes
    expect_req = -(-(len(prompt) + 5) // eng.cache.page_size) * \
        eng.cache.page_bytes
    ledger_exact = ledger_exact and eng.cache.slot_bytes(0) == expect_req
    assert ledger_exact, (cats.get("kv_cache"), eng.cache.pool_bytes,
                          eng.cache.slot_bytes(0), expect_req)
    eng.reset()

    # -- int8 weight-only A/B on the same prompt
    e8 = InferenceEngine(cfg, params, {"inference": dict(
        inf_cfg, weight_bits=8, weight_quant_block=32)})
    e8.start_request(0, prompt, max_new=5)
    eng.start_request(0, prompt, max_new=5)
    l8 = np.asarray(e8.decode_once()[0])
    l32 = np.asarray(eng.decode_once()[0])
    int8_maxdiff = float(np.abs(l8 - l32).max())
    int8_greedy_match = bool(l8.argmax() == l32.argmax())
    eng.reset()

    # -- the Poisson arrival stream (identical for both legs)
    n_req = 32
    gaps = r.exponential(scale=0.004, size=n_req)
    arrivals = np.cumsum(gaps)
    lens = r.randint(4, 29, size=n_req)
    news = r.randint(16, 33, size=n_req)
    prompts = [r.randint(0, cfg.vocab_size, size=int(l)).astype(np.int32)
               for l in lens]

    def make_requests():
        return [Request(rid=i, tokens=prompts[i].copy(),
                        max_new_tokens=int(news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(n_req)]

    def leg_metrics(loop):
        done = loop.results
        tokens = int(sum(len(q.out_tokens) for q in done))
        wall = max(q.finished_at for q in done)
        lats = sorted(loop.token_latencies)
        pick = lambda p: lats[min(int(p * len(lats)), len(lats) - 1)]  # noqa: E731
        return tokens, wall, {
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "requests": len(done),
            "p50_token_ms": round(pick(0.50) * 1e3, 3),
            "p99_token_ms": round(pick(0.99) * 1e3, 3),
        }

    # warmup both paths once (programs are AOT-compiled at engine
    # build; this settles donation/layouts)
    ServingLoop(eng).serve([Request(rid="w", tokens=prompts[0].copy(),
                                    max_new_tokens=4)])
    eng.reset()

    seq_loop = serve_sequential(eng, make_requests())
    seq_tokens, seq_wall, seq = leg_metrics(seq_loop)
    eng.reset()
    cont_loop = ServingLoop(eng)
    cont_loop.serve(make_requests())
    cont_tokens, cont_wall, cont = leg_metrics(cont_loop)

    assert cont_tokens == seq_tokens, (cont_tokens, seq_tokens)
    n_chips = max(len(jax.devices()), 1)
    speedup = (cont_tokens / cont_wall) / (seq_tokens / seq_wall)
    return {
        "model": "gpt2-tiny", "requests": n_req,
        "poisson_mean_interarrival_ms": 4.0,
        "max_slots": 8,
        "sequential": seq,
        "continuous": cont,
        "continuous_vs_sequential_speedup": round(speedup, 2),
        "tokens_per_sec_per_chip": round(
            cont_tokens / cont_wall / n_chips, 1),
        "devices": n_chips,
        "parity_bitexact_fp32": bool(parity_exact),
        "kv_ledger_exact": bool(ledger_exact),
        "int8_logits_maxdiff": int8_maxdiff,
        "int8_greedy_match": int8_greedy_match,
    }


def bench_serving_observability():
    """Serving-observability overhead + fidelity A/B (ISSUE 14): the
    PR-12 Poisson-arrival serving leg re-run with the request-lifecycle
    tracker ON vs OFF — monitor + jsonl sink + trace export enabled in
    BOTH legs, `inference.observability.enabled` toggled, so the ratio
    isolates the TRACKER (monitor_overhead already prices the monitor
    itself; the numerics_overhead discipline) — same engine config,
    same arrival stream. The tracker
    shares the monitor's <3% overhead contract: per-fence cost is host
    dict/timestamp arithmetic plus one JSONL write — `regressed` is
    the recorded contract flag, computed as a median of paired
    order-alternating throughput ratios with adaptive extension (the
    numerics_overhead discipline for environment-dependent ratios on
    a shared box). Hard-asserted instead (they are deterministic up to
    histogram bucket width): the tracker's reported p50/p99 TTFT and
    per-token latency must agree with the leg's OWN independently
    computed per-request latencies (from the Request result stamps the
    scheduler fills, a separate code path and clock chain) within one
    histogram bucket (the fixed log-spaced edges quantize at 2^(1/3)
    ≈ 1.26x; asserted at 1.45x for clock-jitter headroom), and the
    exported trace must carry the per-slot serving timeline + counter
    tracks with a working `ds_trace summary --serving` view."""
    import shutil
    import tempfile
    from deepspeed_tpu.inference import (InferenceEngine, Request,
                                         ServingLoop)
    from deepspeed_tpu.models.gpt2 import (GPT2ForCausalLM,
                                           tiny_gpt2_config)
    from deepspeed_tpu.monitor.trace_export import (load_trace,
                                                    summarize_trace)

    cfg = tiny_gpt2_config()
    model = GPT2ForCausalLM(cfg)
    r = np.random.RandomState(0)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})
    inf_cfg = {"max_slots": 8, "prefill_chunk": 16, "sync_every": 8,
               "max_new_tokens": 32,
               "kv_cache": {"num_pages": 96, "page_size": 8}}
    tmp = tempfile.mkdtemp(prefix="ds_serving_obs_bench_")

    def build(obs_on):
        # monitor ON in BOTH legs (the numerics_overhead discipline:
        # monitor_overhead already prices the monitor itself) — the
        # A/B isolates the TRACKER: inference.observability toggled
        config = {
            "inference": dict(
                inf_cfg, observability={"enabled": obs_on}),
            "monitor": {
                "enabled": True, "sinks": ["jsonl"],
                "output_path": tmp,
                "job_name": "on" if obs_on else "off",
                "trace": {"enabled": True}}}
        return InferenceEngine(cfg, params, config)

    # the PR-12 Poisson stream, identical across every run of each leg
    n_req = 32
    gaps = r.exponential(scale=0.004, size=n_req)
    arrivals = np.cumsum(gaps)
    lens = r.randint(4, 29, size=n_req)
    news = r.randint(16, 33, size=n_req)
    prompts = [r.randint(0, cfg.vocab_size,
                         size=int(l)).astype(np.int32) for l in lens]

    def make_requests():
        return [Request(rid=i, tokens=prompts[i].copy(),
                        max_new_tokens=int(news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(n_req)]

    def run_leg(eng, collect=None):
        eng.reset()
        loop = ServingLoop(eng)
        loop.serve(make_requests())
        tokens = int(sum(len(q.out_tokens) for q in loop.results))
        wall = max(q.finished_at for q in loop.results)
        if collect is not None:
            collect.extend(loop.results)
        return tokens / wall

    out = {}
    try:
        engines = {"off": build(False), "on": build(True)}
        assert engines["off"].tracker is None
        assert engines["on"].tracker is not None
        # warmup settles donation/layouts (one request per engine).
        # The ON warmup request lands in the tracker's cumulative
        # histograms but not in the independent sample below — one
        # 4-token request against the >=128 collected ones shifts a
        # percentile by well under one histogram bucket.
        for name in ("off", "on"):
            ServingLoop(engines[name]).serve(
                [Request(rid="w", tokens=prompts[0].copy(),
                         max_new_tokens=4)])
        on_requests = []
        ratios = []

        def run_pairs(n):
            for _ in range(n):
                # len(ratios) is the global pair counter, so the order
                # genuinely alternates across the adaptive extension
                order = ("off", "on") if len(ratios) % 2 == 0 \
                    else ("on", "off")
                tps = {}
                for name in order:
                    tps[name] = run_leg(
                        engines[name],
                        collect=on_requests if name == "on" else None)
                ratios.append(tps["off"] / tps["on"])

        run_pairs(4)
        med = float(np.median(ratios))
        if 1.5 <= (med - 1.0) * 100.0 <= 4.5:
            # median inside the noise band of the 3% line: extend the
            # sample instead of flaking either way
            run_pairs(4)
        overhead = (float(np.median(ratios)) - 1.0) * 100.0
        out = {
            "model": "gpt2-tiny", "requests": n_req,
            "poisson_mean_interarrival_ms": 4.0,
            "pairs_measured": len(ratios),
            "overhead_pct": round(overhead, 2),
            "regressed": bool(overhead >= 3.0),
        }

        # -- percentile fidelity: tracker histograms vs the leg's own
        # independently computed per-request latencies --------------
        trk = engines["on"].tracker
        # the warmup request is in the hists; fold its stamps in too
        # (its Request object was not collected — recompute from the
        # tracker-side totals is NOT independent, so instead serve the
        # comparison over collected runs only after priming both
        # sides equally: the single 4-token warmup request shifts a
        # >=128-sample distribution by well under one bucket)
        ttft_exact = sorted(
            (q.first_token_at - q.arrival_time) * 1e3
            for q in on_requests if q.first_token_at is not None)
        token_pairs = []
        for q in on_requests:
            n = max(len(q.out_tokens), 1)
            live = q.live_at if q.live_at is not None else q.admitted_at
            token_pairs.extend([(q.finished_at - live) * 1e3 / n] * n)
        token_exact = sorted(token_pairs)

        def pick(vals, p):
            return vals[min(int(p * len(vals)), len(vals) - 1)]

        def agree(reported, exact, band=1.45):
            if reported is None or exact <= 0:
                return False
            return 1.0 / band <= reported / exact <= band

        checks = {
            "ttft_p50": (trk.hist_ttft_ms.percentile(0.50),
                         pick(ttft_exact, 0.50)),
            "ttft_p99": (trk.hist_ttft_ms.percentile(0.99),
                         pick(ttft_exact, 0.99)),
            "token_p50": (trk.hist_token_ms.percentile(0.50),
                          pick(token_exact, 0.50)),
            "token_p99": (trk.hist_token_ms.percentile(0.99),
                          pick(token_exact, 0.99)),
        }
        for name, (rep, exact) in checks.items():
            out[f"{name}_ms"] = None if rep is None else round(rep, 3)
            out[f"{name}_exact_ms"] = round(exact, 3)
            out[f"{name}_agree"] = agree(rep, exact)
            assert out[f"{name}_agree"], \
                (name, rep, exact, "tracker percentile diverged from " \
                 "the independently computed request latencies")

        # -- the trace contract: per-slot tracks, counter tracks, and
        # the --serving summary view --------------------------------
        path = engines["on"].monitor.export_trace()
        doc = load_trace(path)
        track_names = {ev["args"]["name"]
                       for ev in doc["traceEvents"] if ev["ph"] == "M"}
        slot_tracks = sorted(n for n in track_names
                             if n.startswith("serve/slot"))
        counter_names = {ev["name"] for ev in doc["traceEvents"]
                         if ev["ph"] == "C"}
        summary = summarize_trace(doc).get("serving") or {}
        out["slot_tracks"] = len(slot_tracks)
        out["counter_tracks_ok"] = bool(
            {"queue_depth", "batch_occupancy", "kv_page_utilization",
             "tokens_per_sec"} <= counter_names)
        out["summary_requests"] = summary.get("requests", 0)
        out["summary_serving_ok"] = bool(
            summary.get("requests", 0) >= n_req and
            summary.get("ttft_ms", {}).get("p50") is not None and
            summary.get("token_ms", {}).get("p99") is not None)
        assert out["slot_tracks"] >= 1, "no per-slot serving track"
        assert out["counter_tracks_ok"], sorted(counter_names)
        assert out["summary_serving_ok"], summary
        # the SLO event stream flowed
        jsonl = os.path.join(tmp, "on", "events.jsonl")
        out["jsonl_serving_slo_events"] = sum(
            1 for line in open(jsonl)
            if json.loads(line).get("kind") == "serving_slo")
        assert out["jsonl_serving_slo_events"] > 0
        engines["on"].monitor.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_speculative_decode():
    """Speculative-decoding serving A/B (ISSUE 18): the Poisson-arrival
    continuous-batching harness run twice on the SAME engine config —
    vanilla decode vs draft-propose/flagship-verify — in paired
    order-alternating trials, all requests at temperature 0.

    The losslessness contract is HARD-asserted in-leg: every request's
    token stream from the speculative engine must be BIT-IDENTICAL to
    the vanilla engine's (greedy acceptance is exact prefix match, so
    at temp 0 speculation may only change wall time, never one token).

    The model is built so the draft is good but not perfect: an
    8-layer flagship whose blocks 1..7 have their residual projections
    (`c_proj` / `mlp_c_proj`) damped to 0.7x, making the truncate:1
    draft (block 0 + the shared embeddings/ln_f) agree with the
    flagship on most steps — acceptance lands ~0.99 with real
    rejected-suffix rollbacks, so the rollback path is exercised by
    the timed runs, not just the tests. Deterministic: no runtime RNG
    touches the draft, so acceptance numbers repeat exactly."""
    from deepspeed_tpu.inference import (InferenceEngine, Request,
                                         ServingLoop)
    from deepspeed_tpu.models.gpt2 import (GPT2ForCausalLM,
                                           tiny_gpt2_config)

    cfg = tiny_gpt2_config(n_layer=8, n_embd=128, n_positions=256)
    model = GPT2ForCausalLM(cfg)
    r = np.random.RandomState(0)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})
    # damp blocks 1..7 (stacked layer dim): flagship stays close to
    # its own first block = the draft, without being equal to it
    blocks = dict(params["h"]["GPT2Block_0"])
    for name in ("c_proj", "mlp_c_proj"):
        leaf = dict(blocks[name])
        for key in ("kernel", "bias"):
            arr = np.asarray(leaf[key]).copy()
            arr[1:] *= 0.7
            leaf[key] = arr
        blocks[name] = leaf
    params = dict(params)
    params["h"] = {"GPT2Block_0": blocks}

    inf_cfg = {"max_slots": 8, "prefill_chunk": 32, "sync_every": 4,
               "max_new_tokens": 128,
               "kv_cache": {"num_pages": 320, "page_size": 8}}
    spec_cfg = dict(inf_cfg, speculative={
        "enabled": True, "draft_model": "truncate:1",
        "k": 4, "k_min": 1, "adaptive": True})
    eng_van = InferenceEngine(cfg, params, {"inference": dict(inf_cfg)})
    eng_spec = InferenceEngine(cfg, params,
                               {"inference": dict(spec_cfg)})

    # decode-heavy Poisson stream: short prompts, long generations,
    # arrivals fast enough to keep all 8 slots saturated
    n_req = 24
    gaps = r.exponential(scale=0.004, size=n_req)
    arrivals = np.cumsum(gaps)
    lens = r.randint(4, 18, size=n_req)
    news = r.randint(64, 113, size=n_req)
    prompts = [r.randint(0, cfg.vocab_size, size=int(l)).astype(np.int32)
               for l in lens]

    def make_requests():
        return [Request(rid=i, tokens=prompts[i].copy(),
                        max_new_tokens=int(news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(n_req)]

    for eng in (eng_van, eng_spec):
        ServingLoop(eng).serve([Request(
            rid="w", tokens=prompts[0].copy(), max_new_tokens=4)])
        eng.reset()

    totals = {"van": [0, 0.0], "spec": [0, 0.0]}
    outs = {}
    spec_counters = None
    trials = 2
    for trial in range(trials):
        order = [("van", eng_van), ("spec", eng_spec)]
        if trial % 2:
            order.reverse()
        for tag, eng in order:
            loop = ServingLoop(eng)
            loop.serve(make_requests())
            wall = max(q.finished_at for q in loop.results)
            totals[tag][0] += sum(
                len(q.out_tokens) for q in loop.results)
            totals[tag][1] += wall
            outs[tag] = {q.rid: np.asarray(q.out_tokens)
                         for q in loop.results}
            if tag == "spec":
                sp = eng.fetch_state()["speculative"]
                spec_counters = (int(sp["drafted"].sum()),
                                 int(sp["accepted"].sum()),
                                 int(sp["verified"].sum()),
                                 int(sp["rollbacks"].sum()))
            eng.reset()
        # the losslessness contract, checked every trial
        assert all(np.array_equal(outs["van"][i], outs["spec"][i])
                   for i in range(n_req)), \
            "speculative decode diverged bitwise from vanilla at temp 0"

    d, a, v, rb = spec_counters
    van_tps = totals["van"][0] / totals["van"][1]
    spec_tps = totals["spec"][0] / totals["spec"][1]
    speedup = spec_tps / van_tps
    n_chips = max(len(jax.devices()), 1)
    return {
        "model": "gpt2-tiny-8l-128d (blocks 1..7 damped 0.7x)",
        "draft_model": "truncate:1", "k": 4, "adaptive": True,
        "requests": n_req, "trials": trials,
        "poisson_mean_interarrival_ms": 4.0,
        "temp0_bitexact": True,            # hard-asserted above
        "acceptance_rate": round(a / d, 4),
        "tokens_per_verify": round((a + v) / v, 3),
        "drafted_tokens": d, "accepted_tokens": a,
        "rollback_events": rb,
        "vanilla_tokens_per_sec": round(van_tps, 1),
        "speculative_tokens_per_sec": round(spec_tps, 1),
        "speculative_speedup": round(speedup, 2),
        "tokens_per_sec_per_chip": round(spec_tps / n_chips, 1),
        "target_1_5x_met": bool(speedup >= 1.5),
        "devices": n_chips,
    }


# Named bench legs (single source for both `--only` and the full-suite
# extras; each returns one JSON-able dict). Order matters: the full
# suite runs the TPU legs in this order, then the memory plan.
def bench_quantized_matmul():
    """Quantized-compute GEMM A/B (ISSUE 13): the int8 epilogue
    family — per-(K-block, N-column) weight scales + per-row
    activation scales, dequant fused into the GEMM epilogue
    (ops/transformer/quantized_matmul.py) — vs the plain bf16 GEMM at
    a flagship-shaped projection, PLUS a 10-step tiny-GPT-2 engine
    A/B with `quantized_compute` on vs off.  Parity is pinned
    in-leg (hard asserts): GEMM output within the int8 contract of
    the f32 reference, engine loss trajectory within bounds of the
    unquantized run.  On CPU the quantized leg runs the XLA fallback
    (identical quantization numerics; the measured win is the
    fallback's f32 GEMM route vs XLA-CPU's slow emulated-bf16 GEMM);
    on real TPU the Pallas kernel's int8 MXU contraction is the
    2x-peak path.  Timing is paired order-alternating
    median-of-ratios with adaptive extension (the numerics_overhead
    discipline): this shared box swings single GEMM calls ~1.5x at
    seconds scale, so `int8_speedup` is a recorded contract flag
    (int8_faster), not a hard assert — the parity bounds ARE hard
    asserts (they are deterministic)."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.quantized_matmul import (
        quantized_dense, DEFAULT_QUANT_BLOCK)

    on_tpu = jax.devices()[0].platform == "tpu"
    m, k, n = (8192, 1600, 6400) if on_tpu else (2048, 1024, 4096)
    block = DEFAULT_QUANT_BLOCK
    rng = np.random.default_rng(0)
    x32 = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w32 = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    xb, wb = x32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16)

    from deepspeed_tpu.ops.transformer.quantized_matmul import (
        quantize_kernel_int8, quantized_matmul)

    mm_bf16 = jax.jit(lambda x, w: x @ w)
    # the epilogue family's core GEMM: weights quantized ONCE (the
    # steady state — serving quantizes at load; training amortizes
    # the re-quantization over the step's microbatch GEMM uses),
    # activations quantized per call, dequant in the epilogue
    vdt = jnp.int8 if on_tpu else jnp.float32
    wq, sw = jax.jit(lambda w: quantize_kernel_int8(
        w, block, values_dtype=vdt))(wb)
    mm_q8 = jax.jit(lambda x, wq, sw: quantized_matmul(
        x, wq, sw, block=block, out_dtype=jnp.bfloat16))
    # the dynamic form: weights re-quantized INSIDE the call (what
    # quantized_dense pays per trace use when nothing amortizes)
    mm_q8_dyn = jax.jit(lambda x, w: quantized_dense(
        x, w, block=block, out_dtype=jnp.bfloat16))

    # parity FIRST (also warms the compiles): int8 contract vs the
    # f32 reference — per-row x scales + per-(block, col) w scales
    # bound the relative error at ~1% for gaussian operands
    ref = np.asarray(x32 @ w32)
    got = np.asarray(mm_q8(xb, wq, sw)).astype(np.float32)
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    assert rel <= 0.05, f"quantized GEMM parity broke: rel {rel}"
    got_dyn = np.asarray(mm_q8_dyn(xb, wb)).astype(np.float32)
    rel_dyn = float(np.abs(got_dyn - ref).max() / np.abs(ref).max())
    assert rel_dyn <= 0.05, \
        f"dynamic quantized GEMM parity broke: rel {rel_dyn}"
    _sync(mm_bf16(xb, wb)[0, 0].astype(jnp.float32))

    # paired order-alternating windows, median of per-pair ratios (the
    # numerics_overhead discipline): machine load on this shared box
    # swings both arms 1.5x at seconds scale, so a per-PAIR ratio
    # (both arms inside one ~100 ms window, order alternating to
    # cancel drift-within-pair) is the stable statistic
    inner = 2 if on_tpu else 3

    def window(fn, *args):
        t0 = time.perf_counter()
        for _ in range(inner):
            r = fn(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / inner

    window(mm_bf16, xb, wb)               # warm the timing paths
    window(mm_q8, xb, wq, sw)
    window(mm_q8_dyn, xb, wb)
    ratios, ratios_dyn, t_b, t_q = [], [], [], []

    def run_pairs(n):
        for i in range(n):
            if i % 2 == 0:
                tb, tq = window(mm_bf16, xb, wb), \
                    window(mm_q8, xb, wq, sw)
            else:
                tq, tb = window(mm_q8, xb, wq, sw), \
                    window(mm_bf16, xb, wb)
            td = window(mm_q8_dyn, xb, wb)
            ratios.append(tb / tq)
            ratios_dyn.append(tb / td)
            t_b.append(tb)
            t_q.append(tq)

    run_pairs(10)
    # adaptive extension (the numerics_overhead precedent): this
    # box's shared-CPU noise swings single GEMM calls ~1.5x AND the
    # host intermittently throttles to a state where every GEMM dtype
    # runs at the same (slow) rate — when the median lands in the
    # ambiguous band around the 1.15 contract line, extend the sample
    # instead of publishing a coin flip
    if 0.8 <= float(np.median(ratios)) <= 1.3:
        run_pairs(10)
    speedup = float(np.median(ratios))
    speedup_dyn = float(np.median(ratios_dyn))
    best = {"bf16": min(t_b), "q8": min(t_q)}
    # box-state diagnostic: in the healthy state XLA-CPU's f32 GEMM
    # runs ~4x the bf16 one (the margin the fallback rides); under
    # host throttle both flatten to the same rate and the recorded
    # ratio degrades toward 1.0 regardless of the family's merit
    mm_f32 = jax.jit(lambda x, w: x @ w)
    jax.block_until_ready(mm_f32(x32, w32))
    t0 = time.perf_counter()
    for _ in range(inner):
        r = mm_f32(x32, w32)
    jax.block_until_ready(r)
    f32_ms = (time.perf_counter() - t0) / inner * 1e3

    # engine A/B: same tiny GPT-2, same data, quantized_compute on
    # vs off — the training-hot-path weave the config block drives
    from deepspeed_tpu import initialize
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, \
        tiny_gpt2_config
    ids = np.random.default_rng(1).integers(
        0, 256, (10, 1, 4, 64)).astype(np.int32)

    def run(quant):
        cfg = tiny_gpt2_config(n_positions=64)
        model = GPT2ForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": ids[0, 0]})
        ds = {"train_micro_batch_size_per_gpu": 4,
              "gradient_accumulation_steps": 1,
              "steps_per_print": 1000,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}
        if quant:
            ds["quantized_compute"] = {"enabled": True, "mode": "on",
                                       "block": block}
        engine, _, _, _ = initialize(model=model,
                                     model_parameters=params,
                                     config=ds)
        losses = []
        for i in range(10):
            loss = engine.train_batch(batch={"input_ids": ids[i]})
            losses.append(float(jax.device_get(loss)))
        return losses

    l_base = run(False)
    l_quant = run(True)
    max_dev = max(abs(a - b) for a, b in zip(l_base, l_quant))
    # loss parity bound: int8 forward error perturbs the trajectory
    # but must track the fp32 run closely on this tiny model
    assert max_dev <= 0.2, \
        f"quantized engine trajectory diverged: {max_dev}"
    return {
        "shape": f"M{m} K{k} N{n} block{block}"
                 + ("" if on_tpu else " (xla-fallback int8 family)"),
        "bf16_gemm_ms": round(best["bf16"] * 1e3, 2),
        "quantized_gemm_ms": round(best["q8"] * 1e3, 2),
        "f32_gemm_ms": round(f32_ms, 2),
        "int8_speedup": round(speedup, 3),
        "int8_faster": bool(speedup >= 1.15),
        "windows_measured": len(ratios),
        "int8_dynamic_requant_speedup": round(speedup_dyn, 3),
        "gemm_rel_err_vs_f32": round(rel, 5),
        "gemm_rel_err_dynamic": round(rel_dyn, 5),
        "engine_loss_base_final": round(l_base[-1], 5),
        "engine_loss_quant_final": round(l_quant[-1], 5),
        "engine_loss_max_abs_dev": round(max_dev, 5),
        "parity_ok": True,     # the asserts above ARE the pin
    }


def bench_autotune_flash():
    """Pallas block-size autotuner on the flash forward kernel
    (ISSUE 13): search (block_q, block_k) candidates at a
    representative shape with the interleaved best-of-N timing
    discipline, persist the winning table (versioned JSON +
    kernel-source hash), prove the applied shapes are >= 1.0x vs the
    hand-picked defaults (never-slower is enforced by construction:
    the default is a candidate and the winner must beat it), then
    RELOAD the table in a fresh subprocess and assert the traced
    entry point transparently picks the winner up (the
    process-restart half of the contract)."""
    import subprocess
    import sys
    import tempfile
    import jax.numpy as jnp
    from deepspeed_tpu.ops import autotune
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention, _resolve_head_packing)

    on_tpu = jax.devices()[0].platform == "tpu"
    # t=1024 keeps the hand-picked default (1024/1024, unclamped) a
    # genuinely distinct candidate from the smaller tiles
    t, d, h = (1024, 64, 8) if on_tpu else (1024, 64, 1)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, t, h, d)),
                    jnp.bfloat16 if on_tpu else jnp.float32)
    # tune the SAME kernel variant real traces run here: d=64 under
    # head_packing "auto" packs on real TPU, stays unpacked in the
    # CPU interpreter — the lookup key must match or traces miss
    packed = _resolve_head_packing("auto", d, not on_tpu)
    kernel = "flash_fwd_packed" if packed else "flash_fwd"

    table = os.path.join(tempfile.mkdtemp(prefix="ds_autotune_"),
                         "autotune_table.json")
    autotune.reset()
    autotune.configure(table_path=table)
    try:
        def build(params):
            bq, bk = params["block_q"], params["block_k"]
            fn = jax.jit(lambda q: flash_attention(
                q, q, q, causal=True, block_q=bq, block_k=bk))
            return lambda: jax.block_until_ready(fn(q))

        default = {"block_q": 1024, "block_k": 1024}  # _DEFAULT_BLOCK
        candidates = [c for c in autotune.flash_block_candidates(t)
                      if c["block_q"] >= 256 and c["block_k"] >= 256]
        shape_class = autotune.flash_shape_class(t, d, True, packed)
        result = autotune.search(
            kernel, shape_class, q.dtype, candidates, default,
            build=build, warmup=1, reps=3)
        assert result["speedup_vs_default"] >= 1.0, result

        # process-restart reload: a fresh interpreter (inheriting
        # THIS backend — the entry was recorded under it) must load
        # the persisted table and steer the traced entry point to
        # the winner
        code = f"""
import os, json
import importlib
import jax, numpy as np
import jax.numpy as jnp
from deepspeed_tpu.ops import autotune
fa = importlib.import_module(
    "deepspeed_tpu.ops.transformer.flash_attention")
autotune.configure(table_path={table!r})
tuned = autotune.flash_blocks({t}, {d}, True, {packed!r},
                              np.dtype({str(q.dtype)!r}))
assert tuned is not None, "table did not reload across the restart"
q = jnp.zeros((1, {t}, 1, {d}),
              jnp.bfloat16 if {on_tpu!r} else jnp.float32)
args = fa._normalize_flash_args(q, q, q, True, None, None, None,
                                None)
print("RESULT:" + json.dumps(
    {{"tuned": list(tuned), "traced_blocks": [args[2], args[3]]}}))
"""
        env = dict(os.environ)
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=300)
        reload_info = None
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT:"):
                reload_info = json.loads(line[len("RESULT:"):])
        assert reload_info is not None, (
            "reload subprocess failed: "
            f"{(proc.stderr or proc.stdout)[-300:]}")
        assert reload_info["tuned"] == reload_info["traced_blocks"], \
            reload_info
        winner = result["params"]
        assert reload_info["traced_blocks"] == \
            [winner["block_q"], winner["block_k"]], \
            (reload_info, winner)
        return {
            "shape": f"t{t} d{d} h{h}"
                     + ("" if on_tpu else " (interpret-mode kernel)"),
            "kernel": kernel,
            "shape_class": shape_class,
            "default_blocks": [default["block_q"],
                               default["block_k"]],
            "winning_blocks": [winner["block_q"],
                               winner["block_k"]],
            "default_us": result["default_us"],
            "best_us": result["best_us"],
            "speedup_vs_default": result["speedup_vs_default"],
            "never_slower": bool(result["speedup_vs_default"] >= 1.0),
            "candidates_tried": result["candidates_tried"],
            "reloaded_across_restart": True,
            "table_path": table,
        }
    finally:
        # the leg's throwaway table must not steer later legs of a
        # full-suite run (reset restores factory state: lookups
        # enabled, default table path)
        autotune.reset()


def bench_moe_vs_dense():
    """Mixture-of-experts iso-step-FLOPs A/B (ISSUE 15): an 8-expert
    top-1 MoE GPT-2 (8x the MLP parameters of its dense twin, same
    per-token FLOPs — Switch routing sends each token through exactly
    one expert FFN of dense size) vs the dense twin on the virtual
    mesh, with an `expert` axis when the device count allows.  Hard
    asserts (deterministic contracts): grouped-GEMM MoE forward AND
    gradient parity vs the unpacked per-expert-loop reference <= 1e-5
    fp32 (gate math included — the reference reruns the same softmax
    top-k), dropless routing at cf >= 1.25 at production token counts
    (N/E >= 1k, where the 25% capacity margin dwarfs the multinomial
    count fluctuation; the small-batch engine run's init-noise drop
    fraction is bounded at 5%), and the iso-FLOPs step-time ratio
    <= 1.3x at 8 experts.  The packed-vs-unpacked grouped-GEMM
    microbench rides along as a recorded ratio (timing flags, not
    asserts — this box swings)."""
    import jax.numpy as jnp
    from deepspeed_tpu import initialize
    from deepspeed_tpu.moe import MoEConfig, MoEMLP, moe_mlp_reference
    from deepspeed_tpu.moe.experts import grouped_gemm
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config

    on_tpu = jax.devices()[0].platform == "tpu"
    n_dev = len(jax.devices())
    if on_tpu:
        n_layer, n_embd, n_head, seq, steps, windows = 8, 512, 8, 128, 4, 4
    else:
        # iso-FLOPs honesty needs the dispatch/combine einsums
        # (cf*k*N^2*H work, the GShard cost shape) amortized against
        # the MLP's 4*N*H^2 — i.e. tokens <~ H, production-like; at
        # tiny H the routing einsums dominate any MoE formulation
        n_layer, n_embd, n_head, seq, steps, windows = 2, 512, 8, 64, 3, 3
    experts, top_k, cf = 8, 1, 1.25
    expert_axis = 2 if n_dev % 2 == 0 and n_dev >= 2 else 1

    # ---- dropless at cf >= 1.25: a statistical property of the
    # capacity formula at production token counts (the per-expert
    # count's multinomial sd shrinks as sqrt(E/N) of the mean, so the
    # 25% capacity margin dwarfs it at N/E >= 1k). Asserted on the
    # router directly — the engine A/B below runs N/E = 64, where
    # init-noise overflow is expected and only BOUNDED.
    from deepspeed_tpu.moe.router import (router_capacity, top_k_gating,
                                          STAT_DROP)
    n_tok = 8192
    for k_chk in (1, 2):
        for seed in range(3):
            logits = jax.random.normal(jax.random.PRNGKey(seed),
                                       (n_tok, experts))
            cap = router_capacity(n_tok, experts, k_chk, cf)
            _, _, stats = jax.jit(
                lambda lg: top_k_gating(lg, k_chk, cap))(logits)
            drop = float(stats[STAT_DROP])
            assert drop == 0.0, (k_chk, seed, drop)

    # ---- parity: MoEMLP (packed grouped GEMMs + fused epilogues) vs
    # the unpacked per-expert-loop reference, forward AND grads ------
    # parity of the PACKED path explicitly (pack_experts="auto" would
    # unpack on CPU and the block-diagonal trick would go untested)
    moe_ref = MoEConfig(num_experts=experts, top_k=2,
                        capacity_factor=1.5,
                        pack_experts=True).validate()
    mlp = MoEMLP(moe=moe_ref, d_model=n_embd, d_ff=4 * n_embd)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, seq, n_embd),
                          jnp.float32)
    mp = mlp.init(jax.random.PRNGKey(1), x)["params"]

    def f_moe(p):
        y, _ = mlp.apply({"params": p}, x)
        return jnp.sum(y * y)

    def f_ref(p):
        y, _ = moe_mlp_reference(p, x, moe_ref)
        return jnp.sum(y * y)

    y_moe, _ = mlp.apply({"params": mp}, x)
    y_ref, _ = moe_mlp_reference(mp, x, moe_ref)
    fwd_delta = float(jnp.max(jnp.abs(y_moe - y_ref)) /
                      (jnp.max(jnp.abs(y_ref)) + 1e-6))
    g_moe = jax.grad(f_moe)(mp)
    g_ref = jax.grad(f_ref)(mp)
    # relative per leaf: gradient magnitudes scale with the summed
    # loss, so an absolute epsilon would tighten/loosen with shape
    grad_delta = max(
        float(jnp.max(jnp.abs(a - b)) /
              (jnp.max(jnp.abs(b)) + 1e-6)) for a, b in zip(
            jax.tree_util.tree_leaves(g_moe),
            jax.tree_util.tree_leaves(g_ref)))
    assert fwd_delta <= 1e-5 and grad_delta <= 1e-5, \
        (fwd_delta, grad_delta)

    # ---- packed vs unpacked grouped-GEMM microbench ----------------
    g, m, k, n = (experts, 512 if on_tpu else 128, n_embd, 4 * n_embd)
    xg = jax.random.normal(jax.random.PRNGKey(2), (g, m, k), jnp.float32)
    wg = jax.random.normal(jax.random.PRNGKey(3), (g, k, n), jnp.float32)
    mm_packed = jax.jit(lambda x, w: grouped_gemm(x, w, pack=True))
    mm_plain = jax.jit(lambda x, w: grouped_gemm(x, w, pack=False))
    gg_delta = float(jnp.max(jnp.abs(mm_packed(xg, wg) -
                                     mm_plain(xg, wg))))
    assert gg_delta <= 1e-4 * np.sqrt(k), gg_delta
    t_packed = t_plain = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        mm_packed(xg, wg).block_until_ready()
        t_packed = min(t_packed, time.perf_counter() - t0)
        t0 = time.perf_counter()
        mm_plain(xg, wg).block_until_ready()
        t_plain = min(t_plain, time.perf_counter() - t0)

    # ---- iso-step-FLOPs engine A/B ---------------------------------
    def build(moe_cfg, mesh_block, moe_block):
        cfg = gpt2_config("gpt2-125m", n_layer=n_layer, n_embd=n_embd,
                          n_head=n_head, vocab_size=512,
                          n_positions=seq, dropout=0.0,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          remat=True, moe=moe_cfg)
        model = GPT2ForCausalLM(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((n_dev, seq), np.int32)})
        ds = {"train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 1,
              "train_batch_size": n_dev,
              "steps_per_print": 100000,
              "monitor": {"enabled": True, "sinks": []},
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}}}
        if mesh_block:
            ds["mesh"] = mesh_block
        if moe_block:
            ds["moe"] = moe_block
        engine, _, _, _ = initialize(model=model,
                                     model_parameters=params, config=ds)
        return engine

    # the parity traces above recorded their own (meshless) dispatch
    # buffers into the process-global accounting; the engine's ledger
    # entry must reflect the ENGINE's traces only
    from deepspeed_tpu.moe.dispatch import reset_dispatch_accounting
    reset_dispatch_accounting()

    moe_cfg = MoEConfig(num_experts=experts, top_k=top_k,
                        capacity_factor=cf, every_n_layers=2).validate()
    mesh_block = {"data": -1, "expert": expert_axis} \
        if expert_axis > 1 else None
    e_moe = build(moe_cfg, mesh_block,
                  {"enabled": True, "num_experts": experts,
                   "top_k": top_k, "capacity_factor": cf,
                   "every_n_layers": 2})
    e_dense = build(None, None, None)
    n_moe = e_moe._count_model_params(e_moe.state.params)
    n_dense = e_dense._count_model_params(e_dense.state.params)

    def batch(i):
        return {"input_ids": np.random.default_rng(i).integers(
            0, 512, (1, n_dev, seq)).astype(np.int32)}

    staged = {}
    for name, e in (("moe", e_moe), ("dense", e_dense)):
        for i in range(3):
            loss = e.train_batch(batch=batch(i))
        assert np.isfinite(float(jax.device_get(loss))), name
        staged[name] = [e.stage_batch(batch(100 + i))
                        for i in range(steps)]

    def window(e, bs):
        t0 = time.perf_counter()
        for b in bs:
            loss = e.train_batch(batch=b)
        _sync(loss)
        return (time.perf_counter() - t0) / len(bs)

    best = {"moe": float("inf"), "dense": float("inf")}
    for _ in range(windows):              # interleaved A/B windows
        best["moe"] = min(best["moe"], window(e_moe, staged["moe"]))
        best["dense"] = min(best["dense"],
                            window(e_dense, staged["dense"]))
    ratio = best["moe"] / best["dense"]

    # the per-fence router event: dropless at cf >= 1.25 for this run,
    # loads summing to 1 (the replicate_stats contract)
    snap = e_moe.monitor.snapshot()
    router = snap["router"]
    assert router is not None and router["num_experts"] == experts
    # N/E = 64 here: init-noise overflow is EXPECTED (seed-dependent,
    # up to tens of percent before the aux loss balances the gate) —
    # recorded, while the production-count dropless contract is the
    # hard assert above
    assert 0.0 <= router["drop_fraction"] < 1.0, router
    assert abs(sum(router["expert_load"]) - 1.0) < 1e-3, router
    # the moe_dispatch ledger entry vs independent byte math from the
    # config (the PR-9 window-bound pattern)
    from deepspeed_tpu.moe.dispatch import dispatch_buffer_nbytes
    tokens = n_dev * seq
    capacity = router_capacity(tokens, experts, top_k, cf)
    indep = dispatch_buffer_nbytes(experts, capacity, n_embd,
                                   np.float32, e_moe.mesh) \
        * (n_layer // 2)
    led = e_moe.monitor.ledger.category_breakdown("moe_dispatch")
    assert led.get("moe.dispatch_buffers") == indep, (led, indep)

    assert ratio <= 1.3, (
        f"iso-FLOPs MoE step-time ratio {ratio:.3f} > 1.3x at "
        f"{experts} experts")
    # clean shutdown: an armed flight recorder would log its atexit
    # dump AFTER the driver's JSON line and corrupt the output contract
    e_moe.monitor.close()
    e_dense.monitor.close()
    return {
        "shape": f"L{n_layer} E{n_embd} B{n_dev} T{seq} fp32 "
                 f"experts={experts} top_k={top_k} cf={cf} "
                 f"expert_axis={expert_axis}",
        "moe_params_m": round(n_moe / 1e6, 3),
        "dense_params_m": round(n_dense / 1e6, 3),
        "param_multiplier": round(n_moe / n_dense, 2),
        "moe_step_ms": round(best["moe"] * 1e3, 1),
        "dense_step_ms": round(best["dense"] * 1e3, 1),
        "step_time_ratio": round(ratio, 3),
        "iso_flops_ok": bool(ratio <= 1.3),
        "fwd_parity_delta": fwd_delta,
        "grad_parity_delta": grad_delta,
        "parity_ok": bool(fwd_delta <= 1e-5 and grad_delta <= 1e-5),
        "grouped_gemm_packed_speedup": round(t_plain / t_packed, 3),
        "grouped_gemm_packed_faster": bool(t_plain >= t_packed),
        "router": router,
        "moe_dispatch_bytes": indep,
        "dropless_at_8k_tokens": True,   # hard-asserted above
        "engine_drop_fraction": router["drop_fraction"],
    }


def bench_comm_overlap():
    """Communication/compute overlap A/B (ISSUE 16): the SAME jitted
    step traced with the overlap discipline on vs off (ops/overlap.py
    — the config is read at trace time, so each arm is its own
    executable) at two sites on the 8-device virtual CPU mesh: a MoE
    forward+backward over a (data=4, expert=2) mesh (the dispatch
    all-to-all tied to the gate epilogue, the combine fenced under the
    residual) and a ring-attention forward+backward over a seq=8 mesh
    (the windowed ppermute chain, issue_distance rotations in
    flight).  Bit-exact loss parity between the arms is the hard
    assert — the barriers constrain the schedule, never the math.
    The speedup itself is recorded (`overlap_faster`), not asserted:
    the virtual mesh serializes the collectives onto one core, so
    latency hiding has nothing to hide here — the >=1.10x acceptance
    number is read off the recorded bench line on real chips (the
    zero3_overlap `overlap_faster` precedent)."""
    import subprocess
    import sys
    script = r"""
import os, json, time
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np, jax.numpy as jnp
from deepspeed_tpu.runtime.mesh import build_mesh
from deepspeed_tpu.moe import MoEConfig, MoEMLP
from deepspeed_tpu.ops import overlap
from deepspeed_tpu.ops.sequence import ring_attention

out = {}

def timed(fn, args, windows=4, iters=2):
    for _ in range(3):
        r = fn(*args)
    jax.block_until_ready(r)
    best = float('inf')
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        jax.block_until_ready(r)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best, r

# ---- site 1: MoE dispatch/combine pair over (data=4, expert=2) ----
mesh = build_mesh({'data': 4, 'expert': 2})
moe = MoEConfig(num_experts=8, top_k=2, capacity_factor=1.25,
                mesh=mesh).validate()
mlp = MoEMLP(moe=moe, d_model=256, d_ff=1024)
x = jnp.asarray(np.random.default_rng(0).standard_normal(
    (8, 128, 256)), jnp.float32)
params = mlp.init(jax.random.PRNGKey(0), x)['params']

def moe_loss(p, xb):
    y, stats = mlp.apply({'params': p}, xb)
    return jnp.sum(y * y) + stats[-1]

def trace_moe(enabled):
    overlap.configure(enabled=enabled)
    f = jax.jit(lambda p, xb: jax.grad(moe_loss)(p, xb))
    g = f(params, x)          # trace under the configured schedule
    jax.block_until_ready(g)
    return f

# overlapped arm traced LAST: record_inflight is keyed-overwrite, so
# the off-arm's zero registration must not be the surviving one
moe_arm = {False: trace_moe(False), True: trace_moe(True)}

# ---- site 2: ring attention over seq=8 -----------------------------
from jax.sharding import Mesh
smesh = Mesh(np.asarray(jax.devices()), ('seq',))
q = jnp.asarray(np.random.default_rng(1).standard_normal(
    (1, 2048, 4, 64)), jnp.float32)

def ring_loss(qkv):
    o = ring_attention(qkv, qkv, qkv, smesh, causal=True,
                       use_flash=False)
    return jnp.sum(o.astype(jnp.float32) ** 2)

def trace_ring(enabled):
    overlap.configure(enabled=enabled)
    f = jax.jit(jax.grad(ring_loss))
    g = f(q)
    jax.block_until_ready(g)
    return f

ring_arm = {False: trace_ring(False), True: trace_ring(True)}
overlap.configure(enabled=True)

for site, arm, args in (('moe', moe_arm, (params, x)),
                        ('ring', ring_arm, (q,))):
    best = {True: float('inf'), False: float('inf')}
    last = {}
    # paired order-alternating windows: each window times both arms,
    # flipping which goes first, so box drift cancels out of the ratio
    for w in range(4):
        order = (True, False) if w % 2 == 0 else (False, True)
        for on in order:
            t, r = timed(arm[on], args, windows=1, iters=2)
            best[on] = min(best[on], t)
            last[on] = r
    # bit-exact parity: the fences are identities on values
    deltas = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(last[True]),
        jax.tree_util.tree_leaves(last[False]))]
    assert max(deltas) == 0.0, (site, max(deltas))
    out[site] = {
        'overlap_ms': round(best[True] * 1e3, 2),
        'baseline_ms': round(best[False] * 1e3, 2),
        'speedup': round(best[False] / best[True], 3),
        'bit_exact': True,
    }

out['inflight_bytes'] = int(overlap.inflight_bytes())
assert out['inflight_bytes'] > 0   # both sites registered windows
out['overlap_faster'] = bool(any(
    out[s]['speedup'] >= 1.0 for s in ('moe', 'ring')))
print('RESULT:' + json.dumps(out))
"""
    env = dict(__import__("os").environ)
    env.pop("JAX_PLATFORMS", None)
    try:
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=900)
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT:"):
                return json.loads(line[len("RESULT:"):])
        return {"error": (proc.stderr or proc.stdout)[-400:]}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def bench_moe_dispatch_kernel():
    """Fused MoE dispatch/combine vs the one-hot einsum pair (ISSUE
    16): the same router decisions dispatched via the capacity-indexed
    gather + combined via the slot-indexed weighted scatter
    (moe/fused_dispatch.py) against the [N,E,C] one-hot einsum pair,
    forward+backward through the full gate (logits = x @ wg, so both
    VJP chains — dx and the gate-probability path into dwg — are
    compared).  Hard asserts: relative forward AND gradient parity
    <= 5e-7 fp32, and fused >= 1.15x over the einsum pair — the
    einsum's N*E*C*H one-hot MACs vs the gather's N*k*H rows is an
    asymptotic gap (E*C/k = 640x fewer MACs here), not a box-speed
    bet."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.fused_dispatch import (fused_combine,
                                                  fused_dispatch,
                                                  routing_slots)
    from deepspeed_tpu.moe.router import (router_capacity,
                                          top_k_gating,
                                          top_k_gating_indexed)

    n, h, experts, top_k, cf = 1024, 192, 8, 2, 1.25
    capacity = router_capacity(n, experts, top_k, cf)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    wg = jnp.asarray(0.1 * rng.standard_normal((h, experts)),
                     jnp.float32)
    # per-expert scale standing in for the expert FFNs: with identity
    # experts the renormalized gates sum the SAME row back (y == x
    # wherever both choices land), the loss goes flat in the gate
    # values, and the gate-gradient comparison would be pure rounding
    # noise over an exactly-zero gradient
    se = jnp.asarray(1.0 + 0.5 * rng.standard_normal((experts,)),
                     jnp.float32)

    def loss_einsum(x, wg):
        logits = x @ wg
        dispatch, combine, _ = top_k_gating(logits, top_k, capacity)
        xe = jnp.einsum("nec,nh->ech", dispatch, x)
        ye = xe * se[:, None, None]
        y = jnp.einsum("nec,ech->nh", combine, ye)
        return jnp.sum(y * y)

    def loss_fused(x, wg):
        logits = x @ wg
        routing, _ = top_k_gating_indexed(logits, top_k, capacity)
        src, dest = routing_slots(routing, experts, capacity)
        xe = fused_dispatch(x, src)
        ye = xe * jnp.repeat(se, capacity)[:, None]
        y = fused_combine(ye, dest, routing["keep"], routing["w"])
        return jnp.sum(y * y)

    f_einsum = jax.jit(jax.value_and_grad(loss_einsum, argnums=(0, 1)))
    f_fused = jax.jit(jax.value_and_grad(loss_fused, argnums=(0, 1)))

    # ---- parity: forward and both gradient chains, relative --------
    # The two formulations are the SAME math in a different op order,
    # so the honest comparison excludes fp32 summation-order noise
    # (~1e-6 relative at a 1024-token contraction): parity runs in
    # float64, where identical math agrees to ~1e-15 and any real VJP
    # defect (a wrong index, a lost keep mask) still shows up at O(1).
    jax.config.update("jax_enable_x64", True)
    try:
        x64, wg64 = (jnp.asarray(np.asarray(x), jnp.float64),
                     jnp.asarray(np.asarray(wg), jnp.float64))
        l_e, g_e = jax.value_and_grad(
            loss_einsum, argnums=(0, 1))(x64, wg64)
        l_f, g_f = jax.value_and_grad(
            loss_fused, argnums=(0, 1))(x64, wg64)
        fwd_delta = float(abs(l_f - l_e) / (abs(l_e) + 1e-6))
        grad_delta = max(
            float(jnp.max(jnp.abs(a - b)) /
                  (jnp.max(jnp.abs(b)) + 1e-6))
            for a, b in zip(g_f, g_e))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert fwd_delta <= 5e-7 and grad_delta <= 5e-7, \
        (fwd_delta, grad_delta)

    # ---- paired order-alternating A/B timing -----------------------
    best = {"einsum": float("inf"), "fused": float("inf")}
    for fn, xx, ww in ((f_einsum, x, wg), (f_fused, x, wg)):
        for _ in range(3):
            r = fn(xx, ww)
        jax.block_until_ready(r)
    for w in range(4):
        pairs = [("einsum", f_einsum), ("fused", f_fused)]
        if w % 2:
            pairs.reverse()
        for name, fn in pairs:
            t0 = time.perf_counter()
            for _ in range(3):
                r = fn(x, wg)
            jax.block_until_ready(r)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / 3)
    speedup = best["einsum"] / best["fused"]
    assert speedup >= 1.15, (
        f"fused dispatch {speedup:.3f}x over the einsum pair "
        "(contract: >= 1.15x)")
    return {
        "shape": f"N{n} H{h} E{experts} k{top_k} C{capacity} fp32",
        "einsum_fwd_bwd_ms": round(best["einsum"] * 1e3, 2),
        "fused_fwd_bwd_ms": round(best["fused"] * 1e3, 2),
        "fused_speedup": round(speedup, 3),
        "fwd_parity_delta": fwd_delta,
        "grad_parity_delta": grad_delta,
        "parity_ok": bool(fwd_delta <= 5e-7 and grad_delta <= 5e-7),
    }


BENCH_LEGS = {
    "comm_overlap": bench_comm_overlap,
    "moe_dispatch_kernel": bench_moe_dispatch_kernel,
    "async_checkpoint": bench_async_checkpoint,
    "async_dispatch": bench_async_dispatch,
    "monitor_overhead": bench_monitor_overhead,
    "numerics_overhead": bench_numerics_overhead,
    "gpt2_350m": bench_gpt2_350m,
    "bert_large_fused_seq128": bench_bert_large,
    "flash_head_packing": bench_flash_head_packing,
    "fused_hot_loop": bench_fused_hot_loop,
    "pipe_interleave": bench_pipe_interleave,
    "bert_mlm_head_dtype": bench_bert_mlm_head_dtype,
    "sparse_attention_16k": bench_sparse_16k,
    "ring_attention_per_step": bench_ring_attention,
    "zero_offload_real_step": bench_offload_real_step,
    "zero_offload_wire": bench_offload_wire,
    "offload_overlap_microbench": bench_offload_overlap,
    "pipe_interp_vs_spmd": bench_pipe_interp_vs_spmd,
    "gpt2_13b_zero3_memory_plan": bench_13b_memory_plan,
    "memory_ledger": bench_memory_ledger,
    "zero3_overlap": bench_zero3_overlap,
    "elastic_recovery": bench_elastic_recovery,
    "serving_throughput": bench_serving_throughput,
    "serving_observability": bench_serving_observability,
    "speculative_decode": bench_speculative_decode,
    "quantized_matmul": bench_quantized_matmul,
    "autotune_flash": bench_autotune_flash,
    "moe_vs_dense": bench_moe_vs_dense,
}


def main():
    import argparse
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    parser = argparse.ArgumentParser(
        description="deepspeed-tpu benchmark suite (one JSON line)")
    parser.add_argument(
        "--only", default=None, metavar="LEG",
        help="run a single bench leg instead of the full ~15-min suite "
             "and print {leg, result} as one JSON line "
             "(see --list for valid names)")
    parser.add_argument(
        "--list", action="store_true",
        help="print the valid bench leg names (one per line) and exit")
    parser.add_argument(
        "--peak-flops", type=float, default=None, metavar="FLOPS",
        help="override the per-chip peak FLOP/s used as the MFU "
             "denominator (e.g. 1.97e14). Makes MFU meaningful on "
             "CPU/virtual-mesh rehearsal runs; mirrors the "
             "monitor.peak_flops_override config key")
    args = parser.parse_args()
    if args.peak_flops is not None:
        global _PEAK_FLOPS_OVERRIDE
        _PEAK_FLOPS_OVERRIDE = float(args.peak_flops)
    if args.list:
        for name in sorted(BENCH_LEGS):
            print(name)
        return
    if args.only is not None:
        if args.only not in BENCH_LEGS:
            parser.error(
                f"unknown bench leg {args.only!r}; valid legs: "
                + ", ".join(sorted(BENCH_LEGS)))
        try:
            result = BENCH_LEGS[args.only]()
        except Exception as e:
            result = {"error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps({"leg": args.only, "result": result}))
        return

    on_tpu = jax.devices()[0].platform == "tpu"
    mfu_megatron = None
    probe_tf = None
    if on_tpu:
        model_name = "gpt2-1.5b"
        tps, mfu, achieved, mfu_megatron, probe_tf = bench_gpt2_15b()
    else:
        model_name = "gpt2-tiny-smoke"
        tps, mfu, achieved = bench_gpt2_cpu_smoke()

    extra = {"achieved_tflops_per_chip": round(achieved / 1e12, 1)}
    if on_tpu:
        extra["flagship_config"] = ("GPT-2 1.5B ZeRO-2, bf16 master-less "
                                    "(fp32 Adam state = 21.8 GB > 16 GB HBM)")
    if mfu_megatron is not None:
        # the headline mfu/vs_baseline stay on conservative 6ND; this
        # is the same step under the Megatron-LM flops formula (the
        # convention the north-star target's own papers report MFU
        # with: + attention-matmul flops, 72BSLh^2·(1 + S/6h + ...))
        extra["mfu_megatron_convention"] = round(mfu_megatron, 4)
        extra["vs_baseline_megatron_convention"] = round(
            mfu_megatron / 0.45, 4)
    if on_tpu and probe_tf:
        # The probe windows are INTERLEAVED with the flagship step
        # windows (_run_engine probe=True, VERDICT r4 #6): best-of-N
        # from the same throttle regime as the headline. The chip's
        # healthy dependent-chain peak is ~140 TF (~71% of the 197 TF
        # nominal); a probe far below that means the WHOLE bench run —
        # headline included — executed on a degraded chip, and the
        # true-hardware MFU is at least the nominal-peak figure.
        extra["matmul_peak_probe_tflops"] = round(probe_tf / 1e12, 1)
        healthy = 0.71 * _peak_flops(jax.devices()[0])
        if probe_tf < 0.6 * healthy:
            extra["chip_throttled_during_bench"] = True
            extra["peak_probe_note"] = (
                f"interleaved probe {probe_tf / 1e12:.0f} TF < 60% of "
                f"the chip's healthy {healthy / 1e12:.0f} TF chain "
                "peak: the step windows themselves ran throttled; "
                "mfu is a LOWER bound for healthy hardware")
        elif probe_tf < achieved:
            # the MEDIAN of N reps per interleaved point is below
            # achieved — not a single bad window (those are outvoted
            # now): say so rather than publish an impossible
            # >100% MFU-vs-measured-peak
            extra["peak_probe_note"] = (
                "median probe < achieved step TFLOPS despite "
                "interleaving and median-of-reps: sustained "
                "contention; nominal-peak MFU is the valid headline")
        elif _peak_flops(jax.devices()[0]) <= 0:
            pass   # unknown generation: no nominal to clamp against
        else:
            peak_nominal = _peak_flops(jax.devices()[0])
            if probe_tf > peak_nominal:
                # the difference method can exceed nominal when the
                # longer chain rides boosted sustained clocks; the
                # chip is healthy — clamp the ratio's denominator
                extra["peak_probe_note"] = (
                    "probe reads above nominal (sustained-clock "
                    "artifact of the N-vs-2N method); ratio uses "
                    "nominal")
            extra["mfu_vs_measured_peak"] = round(
                achieved / min(probe_tf, peak_nominal), 4)
    if on_tpu:
        extras = list(BENCH_LEGS.items())
    else:
        extras = [("gpt2_13b_zero3_memory_plan",
                   BENCH_LEGS["gpt2_13b_zero3_memory_plan"])]
    for name, fn in extras:
        try:
            extra[name] = fn()
        except Exception as e:  # a failed extra must not kill the line
            extra[name] = {"error": f"{type(e).__name__}: {e}"[:200]}

    # The per-leg extras dict grew enormous (every BENCH_r0* line was
    # truncated by log tails -> parsed: null): the FULL dict goes to an
    # artifacts file and the stdout metric line stays compact (headline
    # numbers + the extras path).
    extras_path = None
    try:
        ts = time.strftime("%Y%m%d_%H%M%S")
        art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "artifacts")
        os.makedirs(art_dir, exist_ok=True)
        extras_path = os.path.join(art_dir, f"bench_extras_{ts}.json")
        with open(extras_path, "w") as f:
            json.dump({"metric":
                       f"{model_name}_train_tokens_per_sec_per_chip",
                       "value": round(tps, 1), "mfu": round(mfu, 4),
                       "extra": extra}, f, indent=1)
    except Exception as e:   # an unwritable dir must not kill the line
        extras_path = f"unwritable: {type(e).__name__}"

    # keep only the small scalar headline extras inline; everything
    # else lives in the extras file
    inline_keys = ("achieved_tflops_per_chip", "flagship_config",
                   "mfu_megatron_convention",
                   "vs_baseline_megatron_convention",
                   "matmul_peak_probe_tflops", "mfu_vs_measured_peak",
                   "chip_throttled_during_bench", "peak_probe_note")
    print(json.dumps({
        "metric": f"{model_name}_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "mfu": round(mfu, 4),
        "vs_baseline": round(mfu / BASELINE_MFU, 4),
        "extras_path": extras_path,
        "extra": {k: extra[k] for k in inline_keys if k in extra},
    }))


if __name__ == "__main__":
    main()
