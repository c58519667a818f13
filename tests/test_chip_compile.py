"""The serving programs' layer scan compiled for a v5e that is
described, not attached (`jax.experimental.topologies`), at the shapes
of the benchmark's serving cell (GPT-2 1.5B, 1,025 pages of 16, 16
slots): what decides whether the page pools are copied, and whether
Mosaic takes the decode kernel's page copies, is the compiled program,
and this is the chip's compiler at no chip time (ISSUEs 25 and 27).
Nothing runs; a compile that passes is not a chip run.

Every test of the suite that describes a topology lives in THIS file,
and the description happens inside a fixture: one process at a time
may hold the TPU's library, and each test worker imports every file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.kv_cache import PagedKVCache
from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config

PAGES, PAGE, SLOTS, SEQ, CHUNK = 1025, 16, 16, 1024, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cell(one_chip):
    """(config, shapes of the weights and of one pool), placed on the
    described chip."""
    cfg = gpt2_config("gpt2-1.5b", n_positions=SEQ, dropout=0.0,
                      param_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda k: GPT2ForCausalLM(cfg).init(
            k, {"input_ids": np.zeros((1, SEQ), np.int32)}),
        jax.random.PRNGKey(0))
    cache = PagedKVCache(cfg.n_layer, cfg.n_head, cfg.head_dim, PAGES,
                         PAGE, SLOTS, SEQ // PAGE)
    pool = jax.ShapeDtypeStruct(cache.pool_shape(cfg.n_layer), cfg.dtype)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    return cfg, jax.tree_util.tree_map(place, params), place(pool), place


@pytest.mark.parametrize("rows, tokens",
                         [(SLOTS, 1), (1, CHUNK), (SLOTS, 4)],
                         ids=["decode", "prefill", "verify"])
def test_layer_scan_holds_no_copy_of_a_pool_on_a_v5e(cell, rows, tokens,
                                                     monkeypatch):
    cfg, params, pool, place = cell
    # the kernel's own backend probe answers "TPU": here it sees the
    # CPU and would hand the chip's compiler the interpreter's XLA
    from deepspeed_tpu.ops.transformer import paged_decode_attention
    monkeypatch.setattr(paged_decode_attention, "_on_tpu", lambda: True)

    serving = engine_mod.Serving(cfg, InferenceConfig({"inference": {
        "kv_cache": {"num_pages": PAGES, "page_size": PAGE}}}), SEQ)

    def layers(params, hidden, k_pool, v_pool, tables, positions, valid,
               kv_limit):
        hidden, (k_pool, v_pool) = serving.layers(
            params, hidden, (k_pool, v_pool), positions,
            serving.kind.mixer(tables, positions, valid, kv_limit))
        return hidden, k_pool, v_pool

    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    compiled = jax.jit(layers, donate_argnums=(2, 3)).lower(
        params, sds((rows, tokens, cfg.n_embd), cfg.dtype), pool, pool,
        sds((rows, SEQ // PAGE), jnp.int32),
        sds((rows, tokens), jnp.int32), sds((rows, tokens), bool),
        sds((rows,), jnp.int32)).compile()
    pool_bytes = int(np.prod(pool.shape)) * 2
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    whole = ",".join(map(str, pool.shape))
    layer = ",".join(map(str, pool.shape[1:]))
    moved = re.findall(
        rf"= \w+\[(?:{whole}|1,{layer}|{layer})\]\S* "
        r"(copy|dynamic-slice|dynamic-update-slice|transpose)\(", text)
    assert moved == []
    window_bytes = SLOTS * SEQ * pool.shape[-1] * 2
    if tokens == CHUNK:
        # prefill keeps the gathered window of its one slot and the
        # head-split copy of it
        assert memory.temp_size_in_bytes < pool_bytes // 4
    else:
        # a few rows a slot: the kernel reads the pages where they
        # lie. No gathered window (16 slots x 1,024 keys, in any
        # layout) is left in the program, and what the program holds
        # beside its arguments stays under one such window (52 MB)
        assert re.findall(r'custom_call_target="tpu_custom_call"', text)
        assert "paged_decode_attention" in text
        assert memory.temp_size_in_bytes < window_bytes
        assert re.findall(rf"\w+\[{SLOTS},(?:{SEQ}|{SEQ // PAGE},{PAGE}),"
                          rf"[^\]]*\]", text) == []


# ----------------------------------------------------------------------
# Brumby's layer scans at the shapes of `brumby-14b.serve-longdoc-steady`
# (8 layers of the published widths, 16 slots, chunk 512; ISSUE 26)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def brumby_scans(one_chip):
    """program -> (compiled layer scan, shape of S), compiled once a
    module with the decode kernel's own backend probe answering "TPU"
    (here it sees the CPU and `decode.usable` would hand the chip's
    compiler the XLA form)."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.ops.retention import decode
    cfg = brumby.BrumbyConfig(num_hidden_layers=8)
    block = InferenceConfig({"inference": {
        "max_slots": SLOTS, "prefill_chunk": 512, "max_seq_len": 8192}})
    family = engine_mod.Serving(cfg, block, 8192)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: brumby.init_params(cfg, k), jax.random.PRNGKey(0)))
    lead = (8, SLOTS, 8, cfg.state_dim)
    S, z = sds(lead + (128,), jnp.float32), sds(lead, jnp.float32)

    def decode_layers(params, hidden, S, z, pos, active):
        return family.decode_layers(params, hidden, {
            "state_s": S, "state_z": z, "pos": pos, "active": active})

    def prefill_layers(params, hidden, S, z, slot, start, n_valid):
        posv = start + jnp.arange(512, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, (S, z), slot, posv,
            jnp.arange(512) < n_valid, start, n_valid)

    programs = {
        "decode": (decode_layers, (
            params, sds((SLOTS, 1, 5120), cfg.dtype), S, z,
            sds((SLOTS,), jnp.int32), sds((SLOTS,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, 512, 5120), cfg.dtype), S, z) +
            (sds((), jnp.int32),) * 3)}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probe, decode._on_tpu = decode._on_tpu, lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2, 3)).lower(*args).compile()
            finally:
                decode._on_tpu = probe
        return compiled[program], S, z
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_state_is_updated_in_place_on_a_v5e(brumby_scans, program):
    """4.6 GB of recurrent state ride in the layer scan's carry: the
    donated arrays are the outputs, the temporaries stay far below one
    layer's state (575 MB), and the whole state is never copied.
    Decode passes over a layer's state ONCE: one Mosaic call a layer
    (the scan's body holds one), and no XLA fusion takes a layer's or
    the whole state as an operand (the XLA form had two that read it
    and one that wrote it). Prefill keeps phi in VMEM: one Mosaic call
    a layer, `retention_prefill`, and outside it no float32 array with
    a dimension of 8,704 but the state itself (S, and the normaliser z
    of the launch's slot); XLA's chunked form held phi(Q) of a
    pair-chunk, [128, 40, 8704], and 206 MB of temporaries."""
    compiled, S, z = brumby_scans(program)
    assert S.shape == (8, SLOTS, 8, 8704, 128)
    state_bytes = 4 * int(np.prod(S.shape) + np.prod(z.shape))
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < state_bytes // 16
    whole = ",".join(map(str, S.shape))
    moved = re.findall(rf"= f32\[{whole}\]\S* (copy|transpose)\(", text)
    assert moved == []
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 1 and f"retention_{program}" in text
    if program == "prefill":
        assert memory.temp_size_in_bytes < 50e6
        rows = S.shape[-2]
        own = {S.shape, z.shape, (1, 1) + z.shape[2:], z.shape[2:]}
        wide = {tuple(map(int, dims.split(",")))
                for dims in re.findall(r"f32\[([\d,]+)\]", text)
                if str(rows) in dims.split(",")}
        assert wide and wide <= own, wide - own
    if program == "decode":
        layer = ",".join(map(str, S.shape[1:]))
        reads_state = re.findall(
            rf"^%fused_computation\S* \(.*f32\[(?:{whole}|{layer})\]",
            text, re.M)
        assert reads_state == []


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_state_regions_survive_the_chips_compiler(brumby_scans, program):
    """What the benchmark's retention readers join on
    (`benchmark/state_scopes.py`: device time by the innermost region
    of an instruction's name stack, through the registry's
    `parse_op_scopes`), held where the chip's fusions decide it. In
    prefill every instruction that takes or gives a state-shaped array
    lies in `retention_chunk` or `state_reset`; in decode the Mosaic
    call lies in `state_update`; nothing state-shaped has no region.
    (PR 29's traced run on the chip found no device time under
    `retention_chunk` and was refused for the missing metric.)"""
    from benchmark import state_scopes
    from deepspeed_tpu.monitor import programs
    compiled, S, _ = brumby_scans(program)
    text = compiled.as_text()
    scopes = programs.parse_op_scopes(text)
    regions = {state_scopes.region_of(stack) for stack in scopes.values()}
    want = {"decode": {"state_update"},
            "prefill": {"retention_chunk", "state_reset"}}[program]
    assert want <= regions and not (regions & set(state_scopes.STATE)) - want
    # the slot's, the layer's or the whole state, in any instruction
    # that computes (the loop's plumbing carries no device time)
    dims = [S.shape, S.shape[1:], (1,) + S.shape[2:], S.shape[2:]]
    shaped = "|".join(",".join(map(str, d)) for d in dims)
    plumbing = ("parameter", "get-tuple-element", "tuple", "while",
                "bitcast")
    touching = {}
    for name, rest in re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*)$", text,
                                 re.M):
        head = rest.split(" metadata=")[0]
        kind = re.search(r"[}\])] ([\w-]+)\(", head)
        if re.search(rf"f32\[(?:{shaped})\]", head) and kind and \
                kind.group(1) not in plumbing:
            touching[name] = state_scopes.region_of(scopes.get(name))
    assert touching and set(touching.values()) <= want, touching
    call, = [n for n in touching if n.startswith(f"retention_{program}")]
    assert touching[call] == {"decode": "state_update",
                              "prefill": "retention_chunk"}[program]


def ssm_decode_regions(scopes, vocabulary):
    """The region of every Mosaic call `ssm_decode` (the state's
    one-pass decode step) in a compiled program's name stacks."""
    from benchmark import region_join
    return [region_join.region_of(stack, vocabulary)
            for name, stack in scopes.items()
            if name.startswith("ssm_decode")]


# ----------------------------------------------------------------------
# Falcon-H1's layer scans at the shapes of
# `falcon-h1-34b.serve-longctx-steady` (6 layers of the published
# widths, 16 slots, chunk 512, 1,537 pages of 128; ISSUE 31)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def falcon_scans(one_chip):
    """program -> (compiled layer scan, the four cache shapes), with
    the backend probes of the decode kernel and of the state's decode
    step answering "TPU" (here they see the CPU: the first would hand
    the chip's compiler the interpreter's XLA, the second `ssm_step`)."""
    from deepspeed_tpu.models import falcon_h1
    from deepspeed_tpu.ops.ssm import decode as ssm_decode
    from deepspeed_tpu.ops.transformer import paged_decode_attention
    cfg = falcon_h1.FalconH1Config(num_hidden_layers=6)
    block = InferenceConfig({"inference": {
        "max_slots": SLOTS, "prefill_chunk": 512, "max_seq_len": 16384,
        "kv_cache": {"num_pages": 1537, "page_size": 128}}})
    family = engine_mod.Serving(cfg, block, 16384)
    cache = family.kind.make_cache(None)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: falcon_h1.init_params(cfg, k), jax.random.PRNGKey(0)))
    pool = sds(cache.pool_shape(6), cfg.dtype)
    conv, H = (sds(shape, dtype) for shape, dtype in zip(
        cache.state_shapes(), cache.state_dtypes()))
    arrays = (pool, pool, conv, H)

    def decode_layers(params, hidden, k, v, conv, H, tables, pos, active):
        return family.decode_layers(params, hidden, {
            "k_pool": k, "v_pool": v, "conv_state": conv, "ssm_state": H,
            "tables": tables, "pos": pos, "active": active})

    def prefill_layers(params, hidden, k, v, conv, H, row, slot, start,
                       n_valid):
        posv = start + jnp.arange(512, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, (k, v, conv, H), (row, slot), posv,
            jnp.arange(512) < n_valid, start, n_valid)

    programs = {
        "decode": (decode_layers, (
            params, sds((SLOTS, 1, 5120), cfg.dtype)) + arrays + (
            sds((SLOTS, 128), jnp.int32), sds((SLOTS,), jnp.int32),
            sds((SLOTS,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, 512, 5120), cfg.dtype)) + arrays + (
            sds((128,), jnp.int32),) + (sds((), jnp.int32),) * 3)}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probes = (paged_decode_attention._on_tpu, ssm_decode._on_tpu)
            paged_decode_attention._on_tpu = ssm_decode._on_tpu = \
                lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2, 3, 4, 5)).lower(
                        *args).compile()
            finally:
                paged_decode_attention._on_tpu, ssm_decode._on_tpu = probes
        return compiled[program], arrays
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_both_caches_are_updated_in_place_on_a_v5e(falcon_scans, program):
    """2.42 GB of K/V pools (rows of 4 x 128 lanes: the key/value
    heads only) and 0.40 GB of state-space state ride in the layer
    scan's carry side by side: all four donated arrays are the
    outputs, no pool- or state-shaped array is copied, Mosaic takes
    the grouped-query decode kernel and the state's one-pass step
    `ssm_decode` (two calls in the scan's body, the second under
    `state_update`; no layer of the state is sliced out for it), and
    every region of the state half survives the chip's fusions."""
    from benchmark import region_join
    from deepspeed_tpu.monitor import programs
    compiled, arrays = falcon_scans(program)
    pool, _, conv, H = arrays
    assert pool.shape == (6, 1537, 128, 512) and \
        H.shape == (6, SLOTS, 32, 128, 256) and \
        conv.shape == (6, SLOTS, 3, 5120)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in arrays)
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= cache_bytes
    for a in (pool, H):
        whole = ",".join(map(str, a.shape))
        moved = re.findall(rf"= \w+\[{whole}\]\S* (copy|transpose)\(", text)
        assert moved == []
    scopes = programs.parse_op_scopes(text)
    regions = {region_join.region_of(stack, region_join.PAGED_STATE)
               for stack in scopes.values()}
    if program == "decode":
        # 0.48 MB with the state's step a Mosaic call (1.12 MB before)
        assert memory.temp_size_in_bytes < 1 << 20
        calls = re.findall(
            r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
        assert len(calls) == 2 and "paged_decode_attention" in text
        assert ssm_decode_regions(scopes, region_join.PAGED_STATE) == [
            "state_update"]
        layer = ",".join(map(str, H.shape[1:]))
        assert not re.findall(
            rf"= f32\[(?:1,)?{layer}\]\S* (copy|transpose|dynamic-slice)\(",
            text)
        assert {"kv_write", "attn", "ssm_conv", "state_update"} <= regions
        assert not regions & {"kv_gather", "ssm_chunk", "state_reset"}
    else:
        # a block of 1,024 keys at a time, not the 16,384-key row (its
        # probabilities alone were 0.34 GB before ISSUE 42; 6 MB now)
        assert memory.temp_size_in_bytes < 64 << 20
        assert {"kv_write", "kv_gather", "attn", "state_reset", "ssm_conv",
                "ssm_chunk"} <= regions
        assert "state_update" not in regions


# ----------------------------------------------------------------------
# Trinity's layer scans at the shapes of
# `trinity-mini.serve-reason-steady` (a dense layer and one period of
# four expert layers at the published widths, 96 slots, chunk 512,
# pages of 128; ISSUE 35)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def trinity_scans(one_chip):
    """program -> (compiled layer scans, the cache's arrays), with the
    backend probes of the decode kernel and of the grouped product
    answering "TPU"."""
    from deepspeed_tpu.models import trinity
    from deepspeed_tpu.moe import serving as moe
    from deepspeed_tpu.ops.transformer import paged_decode_attention
    slots, chunk, seq = 96, 512, 6144
    S, F = trinity.SLIDING, trinity.FULL
    cfg = trinity.TrinityConfig(num_hidden_layers=5, num_dense_layers=1,
                                layer_types=(S, S, S, S, F))
    block = InferenceConfig({"inference": {
        "max_slots": slots, "prefill_chunk": chunk, "sync_every": 4,
        "max_new_tokens": 2048, "max_seq_len": seq,
        "kv_cache": {"num_pages": 4097, "page_size": 128}}})
    family = engine_mod.Serving(cfg, block, seq)
    cache = family.kind.make_cache(None)
    ring = cache.window.ring
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: trinity.init_params(cfg, k), jax.random.PRNGKey(0)))
    full = sds(cache.full.pool_shape(1), cfg.dtype)
    window = sds(cache.window.pool_shape(4), cfg.dtype)
    arrays = (full, full, window, window, sds((2, 3), jnp.int32))
    keys = family.cache_keys

    def decode_layers(params, hidden, *rest):
        cache, (tables, rings, pos, active) = rest[:5], rest[5:]
        # as the decode program asks: the rows' picks beside the carry
        return family.decode_layers(params, hidden, dict(
            zip(keys, cache), tables=tables, window_tables=rings, pos=pos,
            active=active), readings=True)

    def prefill_layers(params, hidden, *rest):
        cache, (row, ring_row, start, n_valid) = rest[:5], rest[5:]
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, cache, (row, ring_row), posv,
            jnp.arange(chunk) < n_valid, start, n_valid)

    pages = seq // 128
    programs = {
        "decode": (decode_layers, (
            params, sds((slots, 1, 2048), cfg.dtype)) + arrays + (
            sds((slots, pages), jnp.int32), sds((slots, ring), jnp.int32),
            sds((slots,), jnp.int32), sds((slots,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, chunk, 2048), cfg.dtype)) + arrays + (
            sds((pages,), jnp.int32), sds((ring,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32)))}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probes = (paged_decode_attention._on_tpu, moe._on_tpu)
            paged_decode_attention._on_tpu = moe._on_tpu = lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2, 3, 4, 5, 6)).lower(
                        *args).compile()
            finally:
                paged_decode_attention._on_tpu, moe._on_tpu = probes
        return compiled[program], arrays
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_both_pools_pass_the_conditional_in_place_on_a_v5e(trinity_scans,
                                                           program):
    """1.07 GB of the full layer's pool and 2.11 GB of the window
    layers' rings ride in the carry of both layer scans and through
    the `lax.cond` on the layer's kind: all four donated arrays are
    the outputs, nothing pool-shaped is copied, Mosaic takes the decode
    kernel in both branches (with and without a first visible key) and
    the grouped product (three calls in the expert layers' body), no
    layer's experts are sliced out of the stack, and every region of
    the expert layer survives the chip's fusions."""
    from benchmark import moe_costs, region_join
    from deepspeed_tpu.monitor import programs
    compiled, arrays = trinity_scans(program)
    full, _, window, _, _ = arrays
    assert full.shape == (1, 4097, 128, 512) and \
        window.shape == (4, 96 * 21 + 1, 128, 512)
    cache_bytes = 2 * sum(int(np.prod(a.shape)) * 2 for a in (full, window))
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= cache_bytes
    # the pools, a layer of them, or a layer's experts [128, 2048, 1024]
    for shape in (full.shape, window.shape, window.shape[1:],
                  (128, 2048, 1024), (128, 1024, 2048)):
        whole = ",".join(map(str, shape))
        moved = re.findall(
            rf"= \w+\[(?:1,)?{whole}\]\S* "
            r"(copy|transpose|dynamic-slice)\(", text)
        assert moved == [], (shape, moved)
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    scopes = programs.parse_op_scopes(text)
    regions = {region_join.region_of(stack, moe_costs.PAGED_MOE)
               for stack in scopes.values()}
    assert set(moe_costs.MOE) <= regions and "kv_write" in regions
    if program == "decode":
        # gate, up, down; the decode kernel in each scan and branch
        assert len(calls) == 6 and "paged_decode_attention" in text
        # every layer's picks a row (the dense layer's: -1) leave with
        # the carry: `ROW_READINGS`, 15 KB
        assert "s32[5,96,8]" in text
        assert memory.temp_size_in_bytes < 64 << 20
        assert "kv_gather" not in regions
    else:
        assert len(calls) == 3
        # a block of 1,024 keys at a time, not the 6,144-key row
        # (20 MB, the expert layer's)
        assert memory.temp_size_in_bytes < 64 << 20
        assert "kv_gather" in regions


# ----------------------------------------------------------------------
# Sarvam-105B's layer scans at the serving cell's shapes (this chip's 32
# of 128 experts, 96 slots, one latent pool of 4,601 pages of 128;
# ISSUE 39)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def sarvam_scans(one_chip):
    """program -> (compiled layer scans, the latent pool), with the
    backend probes of the latent decode kernel and of the grouped
    product answering "TPU"."""
    from deepspeed_tpu.models import sarvam_mla
    from deepspeed_tpu.moe import serving as moe
    from deepspeed_tpu.ops.transformer import latent_attention
    slots, chunk, seq = 96, 512, 10752
    cfg = sarvam_mla.SarvamMLAConfig(num_hidden_layers=5, experts_held=32,
                                     vocab_size=65536)
    block = InferenceConfig({"inference": {
        "max_slots": slots, "prefill_chunk": chunk, "sync_every": 4,
        "max_new_tokens": 2560, "max_seq_len": seq,
        "kv_cache": {"num_pages": 4601, "page_size": 128}}})
    family = engine_mod.Serving(cfg, block, seq)
    cache = family.kind.make_cache(None)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: sarvam_mla.init_params(cfg, k), jax.random.PRNGKey(0)))
    pool = sds(cache.pool_shape(5), cfg.dtype)
    arrays = (pool, sds((2, 3), jnp.int32))
    keys = family.cache_keys

    def decode_layers(params, hidden, pool, counts, tables, pos, active):
        return family.decode_layers(params, hidden, dict(
            zip(keys, (pool, counts)), tables=tables, pos=pos,
            active=active), readings=True)

    def prefill_layers(params, hidden, pool, counts, row, start, n_valid):
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, (pool, counts), row, posv,
            jnp.arange(chunk) < n_valid, start, n_valid)

    pages = seq // 128
    programs = {
        "decode": (decode_layers, (
            params, sds((slots, 1, 4096), cfg.dtype)) + arrays + (
            sds((slots, pages), jnp.int32), sds((slots,), jnp.int32),
            sds((slots,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, chunk, 4096), cfg.dtype)) + arrays + (
            sds((pages,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32)))}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probes = (latent_attention._on_tpu, moe._on_tpu)
            latent_attention._on_tpu = moe._on_tpu = lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2, 3)).lower(*args).compile()
            finally:
                latent_attention._on_tpu, moe._on_tpu = probes
        return compiled[program], pool
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_latent_pool_rides_both_scans_in_place_on_a_v5e(sarvam_scans,
                                                            program):
    """3.77 GB of latent rows ride in the carry of both layer scans
    (the dense layer's and the expert layers'): the donated pool is
    the output, nothing pool-shaped is copied, Mosaic takes the
    program's latent kernel (once in each scan's body) and the grouped
    product over a SHARE of the experts (three calls in the expert
    layers' body), no layer's held experts are sliced out of the stack, and
    `mla_absorb` and every region of the expert layer survive the
    chip's fusions."""
    from benchmark import mla_costs, region_join
    from deepspeed_tpu.monitor import programs
    compiled, pool = sarvam_scans(program)
    assert pool.shape == (5, 4601, 128, 640)
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= int(np.prod(pool.shape)) * 2
    # the pool, a layer of it, or a layer's held experts [32, 4096, 2048]
    for shape in (pool.shape, pool.shape[1:], (32, 4096, 2048),
                  (32, 2048, 4096)):
        whole = ",".join(map(str, shape))
        moved = re.findall(
            rf"= \w+\[(?:1,)?{whole}\]\S* "
            r"(copy|transpose|dynamic-slice)\(", text)
        assert moved == [], (shape, moved)
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    scopes = programs.parse_op_scopes(text)
    regions = {region_join.region_of(stack, mla_costs.LATENT_MOE)
               for stack in scopes.values()}
    assert set(mla_costs.MOE + mla_costs.ABSORB) <= regions and \
        "kv_write" in regions
    if program == "decode":
        # gate, up, down; the decode kernel in each of the two scans
        assert len(calls) == 5 and "latent_decode_attention" in text
        assert "s32[5,96,8]" in text
        # 8.6 MB since W_q is read where it lies (ISSUE 40); 104 MB, one
        # layer's W_q transposed, before
        assert memory.temp_size_in_bytes < 16 << 20
        assert "kv_gather" not in regions
    else:
        # gate, up, down; the prefill kernel in each of the two scans
        assert len(calls) == 5
        assert "kv_gather" not in regions


def test_a_latent_chunk_keeps_its_scores_on_the_chip_on_a_v5e(sarvam_scans):
    """Mosaic takes `latent_prefill_attention` at the cell's shapes
    (the 640-lane page block sliced to 512 value lanes, the page
    copies, a contraction over the pool's 640 lanes), once in each
    scan's body; no page of the pool is gathered through the table and
    no float32 array has the chunk's 32,768 query rows (the XLA form's
    scores, probabilities' sums and accumulator [32768, 512], 67 MB
    each, which it wrote out every block): the program's temporaries
    are 48 MB where they were 137 (ISSUE 43)."""
    compiled, pool = sarvam_scans("prefill")
    text = compiled.as_text()
    kernels = re.findall(
        r'%latent_prefill_attention\S* = bf16\[1,32768,512\]\S* '
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 2
    page = ",".join(map(str, pool.shape[2:]))
    assert re.findall(rf"= \w+\[(?:\d+,)*{page}\]\S* gather\(", text) == []
    assert re.findall(r"f32\[(?:\d+,)*32768[,\]]", text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# ----------------------------------------------------------------------
# Phi-4-mini-flash-reasoning's layer scans and head at the serving
# cell's shapes (all 32 layers, 64 slots, rings of 9 pages, ONE layer
# of 3,500 shared pages of 128; ISSUE 41)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def phi4flash_scans(one_chip):
    """program -> (compiled layer scans [+ the head, in decode], the
    cache arrays), with the backend probes of the differential decode
    kernel and of the Mamba-1 scan answering "TPU"."""
    from deepspeed_tpu.models import phi4flash
    from deepspeed_tpu.ops.ssm import mamba1
    from deepspeed_tpu.ops.transformer import diff_decode_attention as dd
    slots, chunk, seq = 64, 512, 18432
    cfg = phi4flash.Phi4FlashConfig()
    block = InferenceConfig({"inference": {
        "max_slots": slots, "prefill_chunk": chunk, "sync_every": 4,
        "max_new_tokens": 2048, "max_seq_len": seq,
        "kv_cache": {"num_pages": 3500, "page_size": 128}}})
    family = engine_mod.Serving(cfg, block, seq)
    cache = family.kind.make_cache(None)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: phi4flash.init_params(cfg, k), jax.random.PRNGKey(0)))
    fresh = jax.eval_shape(lambda: family.kind.fresh(cache))
    keys = family.cache_keys
    arrays = tuple(place(fresh[k]) for k in keys)
    ring, pages = cache.window.ring, seq // 128

    def decode_layers(params, hidden, arrays, tables, ring_tables, pos,
                      active):
        hidden, state = family.decode_layers(params, hidden, dict(
            zip(keys, arrays), tables=tables, window_tables=ring_tables,
            pos=pos, active=active))
        return family.head(params, hidden)[:, 0], state

    def prefill_layers(params, hidden, arrays, row, ring_row, slot, start,
                       n_valid):
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, arrays, (row, ring_row, slot), posv,
            jnp.arange(chunk) < n_valid, start, n_valid)

    i32 = lambda *shape: sds(shape, jnp.int32)
    programs = {
        "decode": (decode_layers, (
            params, sds((slots, 1, 2560), cfg.dtype), arrays,
            i32(slots, pages), i32(slots, ring), i32(slots),
            sds((slots,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, chunk, 2560), cfg.dtype), arrays, i32(pages),
            i32(ring), i32(), i32(), i32()))}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probes = (dd._on_tpu, mamba1._on_tpu)
            dd._on_tpu = mamba1._on_tpu = lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2,)).lower(*args).compile()
            finally:
                dd._on_tpu, mamba1._on_tpu = probes
        return compiled[program], arrays
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_state_rings_and_the_shared_pool_ride_the_scans_in_place_on_a_v5e(
        phi4flash_scans, program):
    """0.21 GB of Mamba-1 state (held [16, 5120] a slot and layer:
    whole lane tiles), 3.03 GB of rings and 2.29 GB of ONE layer of
    shared pages ride in the carry of three layer scans of periods: all
    six donated arrays are the outputs and nothing cache-shaped is
    copied. Decode: Mosaic takes the differential decode kernel three
    times (a ring's in the self-decoder's body; the shared pool's in
    the middle period and in the cross-decoder's body: a shared page is
    read once a reading layer), the tied head reads the embedding
    where it lies (no temporary near its 1.02 GB), and `mem` costs no
    copy. Prefill: Mosaic takes the Mamba-1 scan (in the
    self-decoder's body and in the middle period), no [chunk, 5120,
    16] float32 array exists, and no kernel over the shared pool, no
    memory unit and no cross layer is in the program."""
    from benchmark import phi4flash_regions, region_join
    from deepspeed_tpu.inference.hybrid_kind import SHARED_KERNEL
    from deepspeed_tpu.monitor import programs
    compiled, arrays = phi4flash_scans(program)
    conv, state, k_ring, _, k_shared, _ = arrays
    assert state.shape == (9, 64, 16, 5120) and state.dtype == jnp.float32
    assert conv.shape == (9, 64, 3, 5120)
    assert k_ring.shape == (8, 64 * 9 + 1, 128, 1280)
    assert k_shared.shape == (1, 3500, 128, 1280)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in arrays)
    assert 5.5e9 < cache_bytes < 5.6e9
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= cache_bytes
    for a in (state, k_ring, k_shared):
        whole = ",".join(map(str, a.shape))
        assert re.findall(rf"= \w+\[{whole}\]\S* (copy|transpose)\(",
                          text) == []
    assert programs.parse_relaid(text) == 0
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    regions = {region_join.region_of(stack, phi4flash_regions.HYBRID)
               for stack in programs.parse_op_scopes(text).values()}
    for expanded in ("512,5120,16", "512,16,5120"):
        assert f"f32[{expanded}]" not in text
    if program == "decode":
        assert memory.temp_size_in_bytes < 64 << 20
        assert len(calls) == 3 and len(re.findall(
            rf"%{SHARED_KERNEL}[.\d]* = ", text)) == 2
        assert len(re.findall(r"%diff_decode_attention[.\d]* = ", text)) == 1
        assert {"kv_write", "attn", "shared_kv", "gmu", "ssm_conv",
                "state_update", "attn_qkv", "attn_out", "mlp"} <= regions
        assert not regions & {"kv_gather", "ssm_chunk", "state_reset"}
    else:
        # the float32 scores of 512 rows against a ring of 1,152 keys
        assert memory.temp_size_in_bytes < 256 << 20
        assert len(calls) == 2 and "mamba1_selective_scan" in text
        assert SHARED_KERNEL not in text
        assert {"kv_write", "kv_gather", "attn", "state_reset", "ssm_conv",
                "ssm_chunk"} <= regions
        assert not regions & {"state_update", "shared_kv", "gmu"}


# ----------------------------------------------------------------------
# Nemotron-3-Nano's layer scans and head at the serving cell's shapes
# (the first 16 layers of the pattern, this chip's 64 of 128 experts,
# 96 slots, 2 layers of 2,305 pages of 128 and 7 of state; ISSUE 45)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def nemotron_scans(one_chip):
    """program -> (compiled layer scans [+ the head, in decode], the
    cache arrays), with the backend probes of the paged decode kernel,
    of the grouped product and of the state's decode step answering
    "TPU"."""
    from deepspeed_tpu.models import nemotron_h
    from deepspeed_tpu.moe import serving as moe
    from deepspeed_tpu.ops.ssm import decode as ssm_decode
    from deepspeed_tpu.ops.transformer import paged_decode_attention as pda
    slots, chunk, seq = 96, 512, 3072
    cfg = nemotron_h.NemotronHConfig(
        num_hidden_layers=16,
        hybrid_override_pattern=nemotron_h.PUBLISHED_PATTERN[:16],
        experts_held=64, vocab_size=65536, expert_width_stored=1920)
    block = InferenceConfig({"inference": {
        "max_slots": slots, "prefill_chunk": chunk, "sync_every": 4,
        "max_new_tokens": 1024, "max_seq_len": seq,
        "kv_cache": {"num_pages": 2305, "page_size": 128}}})
    family = engine_mod.Serving(cfg, block, seq)
    cache = family.kind.make_cache(None)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sds = lambda shape, dtype: place(jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda k: nemotron_h.init_params(cfg, k), jax.random.PRNGKey(0)))
    fresh = jax.eval_shape(lambda: family.fresh(cache))
    keys = family.cache_keys
    arrays = tuple(place(fresh[k]) for k in keys)
    pages = seq // 128

    def decode_layers(params, hidden, arrays, tables, pos, active):
        hidden, state, read = family.decode_layers(params, hidden, dict(
            zip(keys, arrays), tables=tables, pos=pos, active=active),
            readings=True)
        return family.head(params, hidden)[:, 0], state, read

    def prefill_layers(params, hidden, arrays, row, slot, start, n_valid):
        posv = start + jnp.arange(chunk, dtype=jnp.int32)
        return family.prefill_layers(
            params, hidden, arrays, (row, slot), posv,
            jnp.arange(chunk) < n_valid, start, n_valid)

    i32 = lambda *shape: sds(shape, jnp.int32)
    programs = {
        "decode": (decode_layers, (
            params, sds((slots, 1, 2688), cfg.dtype), arrays,
            i32(slots, pages), i32(slots), sds((slots,), bool))),
        "prefill": (prefill_layers, (
            params, sds((1, chunk, 2688), cfg.dtype), arrays, i32(pages),
            i32(), i32(), i32()))}
    compiled = {}

    def get(program):
        if program not in compiled:
            layers, args = programs[program]
            probes = (pda._on_tpu, moe._on_tpu, ssm_decode._on_tpu)
            pda._on_tpu = moe._on_tpu = ssm_decode._on_tpu = lambda: True
            try:
                compiled[program] = jax.jit(
                    layers, donate_argnums=(2,)).lower(*args).compile()
            finally:
                pda._on_tpu, moe._on_tpu, ssm_decode._on_tpu = probes
        return compiled[program], arrays
    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_pages_and_state_counted_apart_ride_seven_scans_in_place_on_a_v5e(
        nemotron_scans, program):
    """2 layers of K/V pages (a row of 2 heads x 128 = 256 lanes) and 7
    of Mamba-2 state (1.41 GB float32) ride in the carry of the seven
    layer scans the pattern's first 16 letters make (M, 2 EM, *, 3 EM,
    *, EM, E): the donated arrays are the outputs and nothing
    cache-shaped is copied. Mosaic takes the grouped product at the
    tiling the shapes give (the experts stored at 1920 columns: at the
    published 1856 = 14.5 lane tiles the compiler re-laid the whole 4.5
    GB stack of W_up three times a launch), two calls an expert layer's
    body and NO third; no layer's held experts are sliced out of the stack; the
    state-space, paged and expert regions all survive the chip's
    fusions. Decode's state step is the Mosaic call `ssm_decode`, one
    in each of the four bodies that hold an `M` layer, under
    `state_update`, and no layer of the state is sliced out for it."""
    from benchmark import nemotron_h_costs, region_join
    from deepspeed_tpu.monitor import programs
    compiled, arrays = nemotron_scans(program)
    k_pool, _, conv, state, counts = arrays
    assert k_pool.shape == (2, 2305, 128, 256)
    assert conv.shape == (7, 96, 3, 6144)
    assert state.shape == (7, 96, 64, 64, 128) and state.dtype == jnp.float32
    assert counts.shape == (2, 3)
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in arrays)
    assert 2.0e9 < cache_bytes < 2.1e9
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    assert memory.alias_size_in_bytes >= cache_bytes
    for shape, moves in ((k_pool.shape, "copy|transpose|dynamic-slice"),
                         (state.shape, "copy|transpose|dynamic-slice"),
                         (state.shape[1:], "copy|transpose|dynamic-slice"),
                         ((7, 64, 2688, 1920), "copy|transpose"),
                         ((64, 2688, 1920), "copy|transpose|dynamic-slice"),
                         ((64, 1920, 2688), "copy|transpose|dynamic-slice")):
        whole = ",".join(map(str, shape))
        moved = re.findall(
            rf"= \w+\[(?:1,)?{whole}\]\S* ({moves})\(", text)
        assert moved == [], (shape, moved)
    calls = re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call"', text)
    scopes = programs.parse_op_scopes(text)
    regions = {region_join.region_of(stack, nemotron_h_costs.LAYERED)
               for stack in scopes.values()}
    assert set(nemotron_h_costs.MOE) <= regions
    assert {"kv_write", "attn", "ssm_conv", "attn_qkv", "attn_out",
            "mlp"} <= regions
    # up and down in each of the 4 bodies with an expert layer (2 EM,
    # 3 EM, EM, E), and the attention kernel of each of the two `*`;
    # in decode the state's step of each of the 4 bodies with an `M`
    # layer (M, 2 EM, 3 EM, EM)
    if program == "decode":
        assert len(calls) == 2 * 4 + 2 + 4
        assert ssm_decode_regions(scopes, nemotron_h_costs.LAYERED) == [
            "state_update"] * 4
        assert "state_update" in regions and "ssm_chunk" not in regions
        # 10.3 MB (9.9 with the XLA step): the convolution's carried
        # rows stay in the layout they arrive in. With the call's
        # row-major B and C left free to run back through the
        # convolution the compiler re-laid all 33 MB of them around
        # the scans, through an HBM temporary of that size (42.3 MB)
        assert memory.temp_size_in_bytes < 11 << 20
        rows = ",".join(map(str, conv.shape))
        assert not re.findall(rf"= bf16\[{rows}\]\S* copy\(", text)
    else:
        assert "ssm_chunk" in regions and "state_reset" in regions
        assert "state_update" not in regions
        assert memory.temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["brumby", "falcon", "trinity", "sarvam",
                                    "phi4flash", "nemotron"])
def test_no_head_projection_is_sliced_out_and_transposed_on_a_v5e(
        request, family, program):
    """W_q, W_k and W_v go through `head_projection`, which pins their
    product's layout: in no family's layer scans does the chip's
    compiler write a `copy` of a layer's slice of such a leaf
    (`layers/while/body/dynamic_slice`) or of the `params[...]` leaf
    itself (a stack of one layer: Sarvam's and Trinity's dense one),
    and none over 1 MB of any other but Sarvam's W_kvb [512, 16384],
    which is itself split into heads: 16.8 MB a stack, in VMEM. Left to
    itself the compiler moved 18.9 (Trinity) to 117.4 MB (Sarvam) a
    layer of every launch, in Sarvam's two scans twice (ISSUE 40)."""
    from deepspeed_tpu.monitor import programs
    compiled = request.getfixturevalue(family + "_scans")(program)[0]
    text = compiled.as_text()
    w_kvb = 512 * 16384 * 2
    # Nemotron's prefill: `ssd_chunked`'s own scan over the chunks is a
    # `while/body/dynamic_slice` too, and at 8 groups of 8 heads of 64
    # the compiler re-lays a chunk's xs, B, C and dt: activations (4 x
    # 262 KB and 33 KB), once in each of its 4 bodies with a Mamba-2
    # layer; no weight
    chunks = 4 * (4 * 128 * 8 * 128 * 2 + 128 * 8 * 8 * 4) \
        if (family, program) == ("nemotron", "prefill") else 0
    assert programs.parse_relaid(text) == \
        (2 * w_kvb if family == "sarvam" else chunks)
    if family == "sarvam":
        assert re.findall(r"= bf16\[1,4096,12288\]\S* copy\(", text) == []
    # what the count is made of is there to be counted: the stacks are
    # sliced under that name, and the weights are leaves of `params`
    stacks = set(programs.parse_op_scopes(text).values())
    assert any(s.endswith("layers/while/body/dynamic_slice") for s in stacks)
    assert any(s.startswith("params[") for s in stacks)


def test_the_bias_is_balanced_beside_the_weights_on_a_v5e(one_chip):
    """The benchmark balances Sarvam-105B's selection bias on the chip
    before the engine is built (`weights_sarvam_mla.balance_program`:
    8,192 rows through the plain reference): beside the 8.5 GB of
    weights it is handed, the program's temporaries stay under 3 GB,
    and its result is the bias alone."""
    import json
    from benchmark import weights_sarvam_mla as weights
    from benchmark.reference import sarvam_mla as reference
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "sarvam-105b.json")) as f:
        sizes = json.load(f)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    flat = {name: sds(shape, jnp.float32 if name in weights.FLOAT32_LEAVES
                      else jnp.bfloat16)
            for name, (shape, _, _) in weights.weight_shapes(sizes).items()}
    ids = sds((weights.BALANCE_SEQUENCES, weights.BALANCE_TOKENS), jnp.int32)
    memory = weights.balance_program(sizes, reference).lower(
        flat, ids).compile().memory_analysis()
    assert 8 << 30 > memory.argument_size_in_bytes > 7 << 30
    assert memory.temp_size_in_bytes < 3 << 30
    assert memory.output_size_in_bytes == 4 * 128 * 4


def test_nemotrons_bias_is_balanced_beside_the_weights_on_a_v5e(one_chip):
    """The same for Nemotron-3-Nano's cell, whose weights are 10.9 GB:
    `weights_nemotron_h.balance_program` takes the 4 x 2,048 rows
    through the plain reference a SEQUENCE at a time, and its
    temporaries stay under 1.5 GB (all four at once: 5.55 GB, past the
    chip beside the weights; ISSUE 45)."""
    import json
    from benchmark import weights_nemotron_h as weights
    from benchmark.reference import nemotron_h as reference
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "nemotron-3-nano-30b.json")) as f:
        sizes = json.load(f)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    flat = {name: sds(spec[1], jnp.float32 if weights.float32_leaf(name)
                      else jnp.bfloat16)
            for name, spec in weights.weight_shapes(sizes).items()}
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in flat.values())
    assert 10.8e9 < total < 10.9e9
    ids = sds((weights.BALANCE_SEQUENCES, weights.BALANCE_TOKENS), jnp.int32)
    memory = weights.balance_program(sizes, reference).lower(
        flat, ids).compile().memory_analysis()
    assert memory.temp_size_in_bytes < 1.5 * (1 << 30)
    assert memory.output_size_in_bytes < 16 << 10     # the biases alone


@pytest.mark.parametrize("slots, vocab", [(96, 200192), (16, 261120)])
def test_the_samplers_top_k_stays_in_the_conditional_on_a_v5e(
        one_chip, slots, vocab):
    """The sampler at the Trinity and Falcon-H1 cells' shapes: the
    chip's compiler keeps the `conditional`, the top-k over slots x
    vocabulary (a custom fusion) lies in its taken branch and not in
    the entry computation, and no float32 copy of the logits is made
    beside it (ISSUE 36)."""
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                      sharding=one_chip)
    state = {"temperature": place((slots,), jnp.float32),
             "top_k": place((slots,), jnp.int32),
             "active": place((slots,), jnp.bool_),
             "rng": place((2,), jnp.uint32), "step": place((), jnp.int32)}
    compiled = jax.jit(
        lambda logits, state: engine_mod.sample(logits, state, 64)).lower(
            place((slots, vocab), jnp.bfloat16), state).compile()
    text = compiled.as_text()
    # computations by name: a header at column 0 down to its "}"
    bodies = {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?^}", text, re.M | re.S)}
    entry = next(b for b in bodies.values() if b.startswith("ENTRY"))
    branches = re.search(r"conditional\(.*branch_computations=\{(.*?)\}",
                         entry).group(1).replace("%", "").split(", ")
    assert len(branches) == 2
    # who launches the top-k's fusion
    launches = [name for name, body in bodies.items() if re.search(
        r'fusion\(.*kind=kCustom.*op_name="[^"]*/top_k"', body)]
    assert launches == [branches[1]], (launches, branches)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        slots * vocab * 2


# ----------------------------------------------------------------------
# The two training cells' layer scans, forward and backward: no array is
# re-laid around a flash launch (ISSUE 47)
# ----------------------------------------------------------------------
TRAIN_CELLS = {
    # preset, rows a step, remat policy, parameter dtype, layers compiled
    "gpt2-350m.train-seq1024": (
        "gpt2-350m", 16, "dots_with_no_batch_dims_saveable", jnp.float32, 4),
    "gpt2-1.5b.train-zero2": ("gpt2-1.5b", 10, None, jnp.bfloat16, 2),
}
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*)$")
# what passes an array on as it is, or re-laid: followed from a launch
# to whatever copies feed it or take its results
_PASSES_ON = {"bitcast", "get-tuple-element", "copy", "transpose",
              "reshape"}


def flash_bodies(text):
    """[(the flash launches of one HLO computation, the re-layings tied
    to them)], for each computation that holds a launch. A re-laying is
    a `copy` or `transpose` instruction reached from a launch's operand
    or result through nothing but bitcasts, tuple elements and other
    re-layings, given as (name, shape, op_name's tail). Other copies of
    such a body (the loop's own copy of a LayerNorm result into its
    carry) are not a launch's."""
    out = []
    for body in re.split(r"\n\}\n", text):
        rows = {}
        for line in body.splitlines():
            m = _HLO_LINE.match(line)
            if m:
                name, shape, op, rest = m.groups()
                operands = re.findall(r"%([\w.\-]+)",
                                      rest.split(")", 1)[0])
                rows[name] = (shape, op, operands, line)
        launches = [n for n, (_, op, _, line) in rows.items()
                    if op == "custom-call" and
                    re.search(r"flash_(fwd|bwd)", line)]
        if not launches:
            continue
        users = {}
        for name, (_, _, operands, _) in rows.items():
            for o in operands:
                users.setdefault(o, []).append(name)
        tied, seen = [], set()

        def walk(name, step):
            for nxt in step(name):
                if nxt in rows and nxt not in seen and \
                        rows[nxt][1] in _PASSES_ON:
                    seen.add(nxt)
                    walk(nxt, step)

        for launch in launches:
            walk(launch, lambda n: rows[n][2])           # towards operands
            walk(launch, lambda n: users.get(n, []))     # towards users
        for name in seen:
            shape, op, _, line = rows[name]
            if op in ("copy", "transpose"):
                scope = re.search(r'op_name="([^"]+)"', line)
                tied.append((name, shape.split("{")[0],
                             scope.group(1)[-40:] if scope else ""))
        out.append((sorted(re.sub(r"[.\d]+$", "", n) for n in launches),
                    sorted(tied)))
    return out


@pytest.fixture(scope="module")
def train_scans(one_chip):
    """cell -> the HLO of value-and-grad of the cell's loss (a few
    layers of it, the scan's bodies being what is read), compiled once
    a module with the kernels' own backend probes answering "TPU": on
    the MODULES (`ops.transformer.flash_attention` the attribute is the
    function, and a patch on it would compile the interpreter's `while`
    loops)."""
    import importlib
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    cache = {}

    def build(cell):
        if cell in cache:
            return cache[cell]
        preset, rows, policy, param_dtype, layers = TRAIN_CELLS[cell]
        cfg = gpt2_config(preset, n_positions=SEQ, dropout=0.0, remat=True,
                          remat_policy=policy, param_dtype=param_dtype,
                          n_layer=layers)
        model = GPT2ForCausalLM(cfg)
        params = jax.tree_util.tree_map(place, jax.eval_shape(
            lambda k: model.init(
                k, {"input_ids": np.zeros((1, SEQ), np.int32)}),
            jax.random.PRNGKey(0)))
        ids = place(jax.ShapeDtypeStruct((rows, SEQ), jnp.int32))
        with pytest.MonkeyPatch.context() as patch:
            for name in ("flash_attention", "fused_ops"):
                patch.setattr(importlib.import_module(
                    "deepspeed_tpu.ops.transformer." + name),
                    "_on_tpu", lambda: True)
            text = jax.jit(jax.value_and_grad(
                lambda p, i: model.loss_fn(p, {"input_ids": i},
                                           deterministic=True))) \
                .lower(params, ids).compile().as_text()
        cache[cell] = (cfg, rows, text)
        return cache[cell]
    return build


@pytest.mark.parametrize("cell", sorted(TRAIN_CELLS))
def test_no_array_is_re_laid_around_a_flash_launch_on_a_v5e(train_scans,
                                                            cell):
    """The flash kernels read q, k and v where `c_attn` wrote them and
    write where `c_proj` reads: in the scan's forward and backward
    bodies the chip's compiler ties no `copy` or `transpose` of a
    [B, T, H·D]-sized array to a launch. Before ISSUE 47 this count
    read 7 and 13 a layer at 350M and 6 and 13 at 1.5B (`split`,
    `reshape`, `transpose` and the kernels' own results; the walk stops
    at the first fusion, and counted by hand with what lay behind those
    they were 28 and 25 arrays a layer: the ledger's 43 and 103 ms of
    `copy` a step). At 1.5B, where C = 1,600 is no whole number of
    lane tiles, q, k and v may be three slices of the product a launch
    (this compiler makes them three results of the bias add's fusion
    and copies nothing)."""
    cfg, rows, text = train_scans(cell)
    bodies = flash_bodies(text)
    assert [launches for launches, _ in bodies] == [
        ["flash_fwd_packed"],
        ["flash_bwd_fused_packed", "flash_fwd_packed"]], bodies
    tensor = rows * SEQ * cfg.n_embd
    whole = cfg.n_embd % 128 == 0
    for launches, tied in bodies:
        big = [t for t in tied
               if int(np.prod([int(d) for d in re.findall(
                   r"\d+", t[1].split("[", 1)[1])])) >= tensor]
        assert len(big) <= (0 if whole else 3 * len(launches)), big


@pytest.mark.parametrize("cell", sorted(TRAIN_CELLS))
def test_flash_reads_the_projection_where_it_lies_on_a_v5e(train_scans,
                                                           cell):
    """Where H·D is a whole number of lane tiles (350M: 1,024) every
    launch takes the `c_attn` product [B, T, 3·H·D] itself, three times
    over (three index maps on one operand); where it is not (1.5B) it
    takes three [B, T, H·D] arrays. Either way its result is
    [B, T, H·D] and its row statistics [B, T, H]."""
    cfg, rows, text = train_scans(cell)
    c = cfg.n_embd
    product = f"bf16[{rows},{SEQ},{3 * c}]"
    tensor = f"bf16[{rows},{SEQ},{c}]"
    stats = f"f32[{rows},{SEQ},{cfg.n_head}]"
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line
             and re.search(r"= \S+ flash_|%flash_(fwd|bwd)", line)]
    assert len(calls) == 3
    for line in calls:
        operands = re.findall(
            r"(\w+\[[\d,]+\])\{", re.search(
                r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
        assert operands[:3] == [product if c % 128 == 0 else tensor] * 3, \
            operands
        result = line.split(" = ", 1)[1].split(" custom-call(")[0]
        assert result.count(tensor) in (1, 3) and product not in result
        assert stats in result or stats in operands
