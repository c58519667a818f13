#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process, which holds the chip(s) it finds for its whole life and
starts no child. It names the device and refuses anything but a TPU,
then drives the main path once through the entry points a user calls,
at the full width of GPT-2 1.5B (48 x 1600, 25 heads of 64, vocab
50257, sequence 1024; weights random from a seed):

  train    `deepspeed_tpu.initialize()` with
           examples/ds_config_gpt2_1.5b.json, `engine.train_batch()` on
           one fixed batch. Every loss finite, last below first, the
           flash and fused LN/GeLU Mosaic kernels present in the
           compiled step, and the engine's own clock agreeing with a
           `block_until_ready`-bounded one.
  serve    `InferenceEngine` + `ServingLoop` on mixed-length greedy
           requests. Every request returns its `max_new_tokens`;
           prefill-then-decode logits agree with the training forward
           on the same prefix within LOGITS_TOL.
  kernels  every Pallas family compiled by Mosaic at one published-width
           shape and compared with its XLA reference, forward and
           backward, within the bound each case states.

A phase that fails raises; nothing is caught into a string. It times
nothing that is a result: the two rates the train phase prints are a
consistency check of the engine's fence. The last line of stdout is
one JSON object naming the device.

    python chip_smoke.py            # on the chip, through the chip tool
"""

import dataclasses
import gc
import json
import math
import os
import re
import sys
import time
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "examples"))

import deepspeed_tpu  # noqa: E402
import gpt2_train  # noqa: E402  (examples/gpt2_train.py)
from deepspeed_tpu.inference import (InferenceEngine, Request,  # noqa: E402
                                     ServingLoop)
from deepspeed_tpu.moe.fused_dispatch import (fused_combine,  # noqa: E402
                                              fused_dispatch,
                                              routing_slots)
from deepspeed_tpu.moe.router import (router_capacity,  # noqa: E402
                                      top_k_gating_indexed)
from deepspeed_tpu.ops.sparse_attention import (  # noqa: E402
    BigBirdSparsityConfig, BSLongformerSparsityConfig,
    FixedSparsityConfig, block_sparse_attention)
from deepspeed_tpu.ops.transformer.flash_attention import (  # noqa: E402
    dense_attention, flash_attention)
from deepspeed_tpu.ops.transformer.fused_ops import (  # noqa: E402
    fused_bias_gelu, fused_bias_residual_layernorm)
from deepspeed_tpu.ops.transformer.quantized_matmul import \
    quantized_dense  # noqa: E402
from deepspeed_tpu.utils.compile_cache import \
    enable_compile_cache  # noqa: E402

SEED = 1234
DS_CONFIG = os.path.join(HERE, "examples", "ds_config_gpt2_1.5b.json")
# Kernels the compiled train step must hold. The flash kernel absent
# means the interpreter or the XLA attention took over.
TRAIN_STEP_KERNELS = ("flash_fwd", "flash_bwd_fused",
                      "fused_bias_residual_layernorm_fwd",
                      "fused_bias_residual_layernorm_bwd",
                      "fused_bias_gelu_fwd", "fused_bias_gelu_bwd")
# Serving logits against the training forward, max-abs over the
# vocabulary, as a share of the reference's own max-abs logit: the two
# run the same math through differently shaped programs, so they differ
# by rounding in the compute dtype (a tolerance, not bit-equality).
LOGITS_TOL = {"float32": 1e-4, "bfloat16": 1e-1}
# Kernel against reference, max-abs over every output and gradient, as
# a share of the reference's max-abs in that tensor.
KERNEL_TOL = {"float32": 2e-5, "bfloat16": 2e-2}


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def check(ok, message):
    if not ok:
        raise SmokeFailure(message)


def say(*parts):
    print("[chip_smoke]", *parts, flush=True)


# ----------------------------------------------------------------------
# device, compile counters
# ----------------------------------------------------------------------
def describe_device():
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class CompileCounter:
    """Counts what jax's own monitoring reports: compile requests that
    consulted the persistent cache, how many it answered, and how many
    programs were compiled and written to it."""
    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
              "requests",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "compiled_and_written"}

    def __enter__(self):
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self):
        c = dict(self.counts)
        # below the cache's minimum compile time a program is compiled
        # every time and never written
        c["compiled"] = c["requests"] - c["cache_hits"]
        return c


def device_bytes():
    """Per-device bytes in use, from the allocator where the backend
    reports them (TPU), else summed over live arrays' shards."""
    stats = [d.memory_stats() for d in jax.devices()]
    if all(s and "bytes_in_use" in s for s in stats):
        return [int(s["bytes_in_use"]) for s in stats]
    per = {d: 0 for d in jax.devices()}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return [per[d] for d in jax.devices()]


def release():
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ----------------------------------------------------------------------
# train
# ----------------------------------------------------------------------
def mosaic_kernels(lowered):
    """Names of the Mosaic custom calls in a lowered program, with
    their counts (the `kernel_name` attribute Pallas puts on each
    `tpu_custom_call`)."""
    names = re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    return {n: names.count(n) for n in sorted(set(names))}


def train_phase(model_name="gpt2-1.5b", seq_len=1024, steps=12,
                ds_config=DS_CONFIG, expect_kernels=TRAIN_STEP_KERNELS):
    """`steps` engine.train_batch() calls on one fixed seeded batch.
    Returns what it observed (losses, kernels, per-device bytes, the
    device set of one optimizer-state leaf)."""
    if isinstance(ds_config, str):
        with open(ds_config) as f:
            ds_config = json.load(f)
    model, params = gpt2_train.build_model(model_name, seq_len, SEED)
    cfg = model.config
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    micro = engine.train_micro_batch_size_per_gpu()
    gas = engine.gradient_accumulation_steps()
    rows = micro * engine.dp_world_size
    say(f"train: {model_name} {cfg.n_layer} x {cfg.n_embd} x "
        f"{cfg.n_head} heads, seq {seq_len}, micro-batch {micro} x "
        f"{engine.dp_world_size} data-parallel, gas {gas}, "
        f"zero stage {engine.zero_optimization_stage()}, "
        f"mesh {dict(engine.mesh.shape)}")
    batch = {"input_ids": np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (gas, rows, seq_len)).astype(np.int32)}

    lowered = engine.lower_train_step(batch)
    kernels = mosaic_kernels(lowered)
    say("train: Mosaic kernels in the step:", json.dumps(kernels))
    for want in expect_kernels:
        check(any(name.startswith(want) for name in kernels),
              f"train step holds no Mosaic kernel named {want}*: "
              f"{sorted(kernels)}")
    if expect_kernels:
        n_calls = lowered.compile().as_text().count("tpu_custom_call")
        say(f"train: compiled step holds {n_calls} tpu_custom_call "
            "sites")
        check(n_calls > 0, "compiled train step holds no tpu_custom_call")

    # the engine's throughput window opens at the fence that ends its
    # warm-up and closes at the steps_per_print fence; the same window
    # is bounded here by block_until_ready on the step's output
    tput = engine.tput_timer
    open_step = tput.start_step
    close_step = engine.steps_per_print()
    check(open_step < close_step <= steps,
          f"steps={steps} does not cover the engine's throughput "
          f"window [{open_step}, {close_step}]")
    staged = engine.stage_batch(batch)
    losses, marks = [], {}
    for step in range(1, steps + 1):
        loss = engine.train_batch(batch=staged)
        losses.append(loss)
        if step in (open_step, close_step):
            jax.block_until_ready((engine.state, loss))
            marks[step] = time.perf_counter()
    losses = [float(x) for x in jax.device_get(losses)]
    say("train: losses", " ".join(f"{x:.4f}" for x in losses))
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses[0]} -> "
          f"{losses[-1]}")

    samples = (close_step - open_step) * gas * rows
    own = samples / (marks[close_step] - marks[open_step])
    theirs = tput.avg_samples_per_sec()
    say(f"train: fence check over steps {open_step}..{close_step}: "
        f"engine clock {theirs:.3f} samples/s, block_until_ready "
        f"clock {own:.3f} samples/s, ratio {theirs / own:.4f}")
    check(abs(theirs / own - 1.0) <= 0.05,
          "the engine's ThroughputTimer disagrees with a "
          f"block_until_ready-bounded clock: {theirs} vs {own}")

    per_device = device_bytes()
    leaf = max(jax.tree_util.tree_leaves(engine.state.opt_state),
               key=lambda x: getattr(x, "size", 0))
    shard_devices = sorted(s.device.id for s in leaf.addressable_shards)
    shard_shape = leaf.addressable_shards[0].data.shape
    say(f"train: per-device bytes in use {per_device}")
    say(f"train: largest optimizer-state leaf {leaf.shape} "
        f"{leaf.dtype}: shards of {shard_shape} on devices "
        f"{shard_devices}")
    if len(per_device) > 1:
        check(max(per_device) <= 1.10 * min(per_device),
              f"state is not divided evenly: {per_device}")
        check(math.prod(shard_shape) * len(shard_devices) ==
              leaf.size, "optimizer state is replicated, not sharded: "
              f"{leaf.shape} held as {shard_shape} on each device")
    return {"losses": losses, "kernels": kernels,
            "per_device_bytes": per_device,
            "opt_leaf_devices": shard_devices}


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def serve_phase(model_name="gpt2-1.5b", seq_len=1024,
                prompt_lens=(5, 37, 130, 300, 64),
                max_new=(8, 16, 12, 16, 4), prefill_chunk=128,
                parity_prompt=200, parity_steps=2, page_size=16):
    """Mixed-length greedy requests through InferenceEngine +
    ServingLoop, then prefill-then-decode logits against the training
    forward on the same prefix."""
    model, params = gpt2_train.build_model(model_name, seq_len, SEED)
    cfg = model.config
    slots = 4
    pages = slots * -(-seq_len // page_size) + 1
    engine = InferenceEngine(cfg, params, {"inference": {
        "max_slots": slots, "prefill_chunk": prefill_chunk,
        "sync_every": 4, "max_new_tokens": max(max_new),
        "max_seq_len": seq_len,
        "kv_cache": {"num_pages": pages, "page_size": page_size}}})
    say(f"serve: {model_name} {cfg.n_layer} x {cfg.n_embd}, "
        f"{np.dtype(cfg.dtype).name}, {slots} slots, prefill chunk "
        f"{prefill_chunk}, prompts {list(prompt_lens)}")
    check(any(n > prefill_chunk for n in prompt_lens),
          "no prompt is longer than one prefill chunk")
    rng = np.random.default_rng(SEED)
    requests = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, n),
                        max_new_tokens=m)
                for i, (n, m) in enumerate(zip(prompt_lens, max_new))]
    done = ServingLoop(engine).serve(requests)
    check(len(done) == len(requests),
          f"{len(done)} of {len(requests)} requests came back")
    for r in done:
        say(f"serve: request {r.rid}: prompt {len(r.tokens)}, "
            f"{len(r.out_tokens)} tokens out ({r.finish_reason})")
        check(len(r.out_tokens) == r.max_new_tokens,
              f"request {r.rid} returned {len(r.out_tokens)} tokens, "
              f"asked for {r.max_new_tokens}")

    # parity: the reference forward sees the prefix zero-padded to one
    # fixed length (a causal model's row L-1 does not see the padding),
    # so it compiles once
    engine.reset()
    pad_to = -(-(parity_prompt + parity_steps) // 128) * 128
    check(pad_to <= seq_len, "parity prefix does not fit the model")
    reference = jax.jit(lambda p, ids: model.apply(p, ids))
    prefix = list(rng.integers(0, cfg.vocab_size, parity_prompt))
    engine.start_request(0, np.asarray(prefix, np.int32),
                         max_new=parity_steps)
    tol = LOGITS_TOL[np.dtype(cfg.dtype).name]
    worst = 0.0
    for _ in range(parity_steps):
        got = np.asarray(engine.decode_once()[0], np.float32)
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(prefix)] = prefix
        want = np.asarray(reference(params, ids)[0, len(prefix) - 1],
                          np.float32)
        check(got.shape == want.shape == (cfg.vocab_size,),
              f"logits shapes {got.shape} vs {want.shape}")
        check(np.isfinite(got).all(), "non-finite serving logits")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        worst = max(worst, err)
        prefix.append(int(got.argmax()))
    say(f"serve: decode logits vs training forward on a "
        f"{parity_prompt}-token prefix: max-abs error {worst:.3e} of "
        f"the reference's max-abs logit (tolerance {tol:.1e})")
    check(worst <= tol,
          f"serving logits are {worst:.3e} from the training "
          f"forward, tolerance {tol:.1e}")
    return {"requests": len(done), "logits_err": worst}


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KernelSizes:
    """One published-width shape per Pallas family."""
    flash: Tuple[int, int, int, int] = (11, 1024, 25, 64)   # B T H d
    flash_d128: Tuple[int, int, int, int] = (2, 1024, 8, 128)
    # longer than one 1024 tile: the online-softmax carry and the
    # two-sweep backward that long-sequence and ring attention use
    flash_multi_tile: Tuple[int, int, int, int] = (1, 4096, 8, 64)
    rows: int = 11 * 1024            # fused LN / GeLU / int8 GEMM rows
    hidden: int = 1600
    sparse: Tuple[int, int, int, int, int] = (1, 16384, 16, 64, 256)
    moe: Tuple[int, int, int, int] = (4096, 2048, 64, 8)    # N H E k
    dtype: str = "bfloat16"


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """`run` (the Pallas path) and `ref` (XLA) map the same inputs to
    the same tuple of outputs and gradients."""
    name: str
    run: Callable
    ref: Callable
    shapes: Tuple[jax.ShapeDtypeStruct, ...]
    tol: float


def _weights(shape):
    """A fixed, non-constant cotangent for an output of `shape`.
    Positive, so the bias and LayerNorm-parameter gradients — sums of
    it over thousands of rows — do not cancel to a value smaller than
    the rounding of their terms."""
    n = math.prod(shape)
    return (0.75 + 0.25 * jnp.sin(
        jnp.arange(n, dtype=jnp.float32) * 0.37)).reshape(shape)


def _with_grads(fn):
    """fn(*args) -> outputs  ==>  (*args) -> outputs + d(sum of
    weighted outputs)/d(args)."""
    def both(*args):
        def loss(*a):
            outs = fn(*a)
            return sum((o.astype(jnp.float32) * _weights(o.shape)).sum()
                       for o in outs), outs
        grads, outs = jax.grad(loss, argnums=tuple(range(len(args))),
                               has_aux=True)(*args)
        return tuple(outs) + tuple(grads)
    return both


def _masked_dense_by_head(q, k, v, layout, block):
    """Causal block-masked attention, one head at a time so the [T, T]
    scores of a 16k sequence exist once. [1, T, H, D] in and out."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    t = q.shape[1]

    @jax.checkpoint
    def one_head(xs):
        qh, kh, vh, lay = xs
        mask = jnp.repeat(jnp.repeat(lay, block, 0), block, 1) & \
            jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask, (qh @ kh.T) * scale, -1e30)
        return jax.nn.softmax(s, axis=-1) @ vh

    heads = lambda x: x[0].transpose(1, 0, 2)      # [H, T, D]
    out = jax.lax.map(one_head, (heads(q), heads(k), heads(v),
                                 jnp.asarray(layout, bool)))
    return out.transpose(1, 0, 2)[None]


def kernel_cases(sizes=KernelSizes(), interpret=False):
    """Every Pallas entry point at `sizes`. interpret=False asks for
    the Mosaic kernels; True runs the same kernels in the Pallas
    interpreter (tests off the chip)."""
    dt = jnp.dtype(sizes.dtype)
    tol = KERNEL_TOL[dt.name]
    impl = "interpret" if interpret else "pallas"
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    S = jax.ShapeDtypeStruct
    cases = []

    def add(name, run, ref, shapes):
        cases.append(KernelCase(name, _with_grads(run), _with_grads(ref),
                                tuple(shapes), tol))

    for name, shape, packing in (
            ("flash_packed", sizes.flash, "packed"),
            ("flash_unpacked", sizes.flash, "off"),
            ("flash_d128", sizes.flash_d128, "off"),
            ("flash_multi_tile", sizes.flash_multi_tile, "packed")):
        b, t, h, d = shape
        add(name,
            lambda q, k, v, packing=packing: (flash_attention(
                q, k, v, causal=True, interpret=interpret,
                head_packing=packing),),
            lambda q, k, v: (dense_attention(*f32(q, k, v),
                                             causal=True),),
            [S((b, t, h, d), dt)] * 3)

    n, h = sizes.rows, sizes.hidden
    add("fused_bias_residual_layernorm",
        lambda y, b, r, g, beta: fused_bias_residual_layernorm(
            y, b, r, g, beta, impl=impl),
        lambda y, b, r, g, beta: fused_bias_residual_layernorm(
            y, b, r, g, beta, impl="xla"),
        [S((n, h), dt), S((h,), dt), S((n, h), dt), S((h,), dt),
         S((h,), dt)])
    add("fused_bias_gelu",
        lambda x, b: (fused_bias_gelu(x, b, approximate=True,
                                      impl=impl),),
        lambda x, b: (fused_bias_gelu(x, b, approximate=True,
                                      impl="xla"),),
        [S((n, 4 * h), dt), S((4 * h,), dt)])
    add("int8_gemm",
        lambda x, w: (quantized_dense(x, w, impl=impl),),
        lambda x, w: (quantized_dense(x, w, impl="xla"),),
        [S((n, h), dt), S((h, 4 * h), dt)])

    b, t, heads, d, block = sizes.sparse
    for name, config in (
            ("block_sparse_bslongformer", BSLongformerSparsityConfig(
                num_heads=heads, block=block,
                num_sliding_window_blocks=4)),
            ("block_sparse_fixed", FixedSparsityConfig(
                num_heads=heads, block=block, num_local_blocks=4,
                num_global_blocks=1)),
            # random blocks: no band to decompose, so the forward walks
            # the visible-block table like the backward
            ("block_sparse_bigbird", BigBirdSparsityConfig(
                num_heads=heads, block=block, num_random_blocks=1,
                num_sliding_window_blocks=3, num_global_blocks=1))):
        layout = np.asarray(config.make_layout(t))
        add(name,
            lambda q, k, v, layout=layout: (block_sparse_attention(
                q, k, v, layout, block, causal=True,
                interpret=interpret),),
            lambda q, k, v, layout=layout: (_masked_dense_by_head(
                *f32(q, k, v), layout, block),),
            [S((b, t, heads, d), dt)] * 3)

    tokens, width, experts, top_k = sizes.moe
    capacity = router_capacity(tokens, experts, top_k, 1.25)

    def moe(x, logits, use_pallas):
        routing, _ = top_k_gating_indexed(logits, top_k, capacity)
        src, dest = routing_slots(routing, experts, capacity)
        xe = fused_dispatch(x, src, use_pallas=use_pallas,
                            interpret=interpret)
        y = fused_combine(jnp.tanh(xe), dest, routing["keep"],
                          routing["w"], use_pallas=use_pallas,
                          interpret=interpret)
        return xe, y

    add("moe_dispatch_combine",
        lambda x, logits: moe(x, logits, True),
        lambda x, logits: moe(x, logits, False),
        [S((tokens, width), dt), S((tokens, experts), jnp.float32)])
    return cases


def _seeded(shapes, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return tuple(jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
                 for k, s in zip(keys, shapes))


def kernel_phase(sizes=KernelSizes(), interpret=False):
    """Compile and run every kernel case; returns {name: max-abs error
    as a share of the reference's max-abs}."""
    errors = {}
    for i, case in enumerate(kernel_cases(sizes, interpret)):
        args = _seeded(case.shapes, SEED + i)
        got = jax.jit(case.run)(*args)
        # the reference in full fp32 precision: on a TPU the default
        # matmul precision rounds fp32 operands to bf16
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.ref)(*args)
        check(len(got) == len(want), f"{case.name}: {len(got)} outputs "
              f"against {len(want)}")
        worst = 0.0
        for g, w in zip(got, want):
            check(g.shape == w.shape,
                  f"{case.name}: shape {g.shape} against {w.shape}")
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            check(np.isfinite(g).all(), f"{case.name}: non-finite output")
            worst = max(worst, float(np.abs(g - w).max() /
                                     max(np.abs(w).max(), 1e-30)))
        say(f"kernels: {case.name}: max-abs error {worst:.3e} of the "
            f"reference's max-abs (bound {case.tol:.1e})")
        check(worst <= case.tol,
              f"{case.name}: error {worst:.3e} over bound {case.tol:.1e}")
        errors[case.name] = worst
    return errors


# ----------------------------------------------------------------------
def main():
    device = describe_device()
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    if device["platform"] != "tpu":
        # jax carries on on the CPU with a warning when the TPU fails
        # to initialise, so this check is the script's own
        sys.exit("chip_smoke: needs a TPU; jax.devices()[0].platform is "
                 f"{device['platform']!r}")
    say("compile cache:", enable_compile_cache())
    t0 = time.perf_counter()
    with CompileCounter() as counter:
        for phase in (train_phase, serve_phase, kernel_phase):
            phase()
            release()
            say(f"{phase.__name__} passed; compilations so far "
                f"{json.dumps(counter.snapshot())}; "
                f"{time.perf_counter() - t0:.0f} s since start")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
