"""Decode's one-pass retention kernel (ISSUE 30) and prefill's kernel
that keeps phi in VMEM (ISSUE 32) in the Pallas interpreter against
the XLA forms they replace on the chip (`retention_step`,
`retention_chunked`: the oracles), and the padded layout of the
state's rows that both rest on: `phi`, `retention_step`,
`retention_chunked` and the kernels follow the state's row count, so
the same token sequence through the aligned and the compact layout
reads the same along any direction.

Tolerances: the kernels sum the products the XLA forms sum, with
the row's factor built as (a k_i)(a k_j), a^2 = sqrt(2) s, where the
oracle has (k_i k_j)(c s): a few units in the last place of float32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import brumby as arch
from benchmark.reference import brumby as ref
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models import brumby
from deepspeed_tpu.inference import Request, ServingLoop
from deepspeed_tpu.ops.retention import (phi, retention_chunked,
                                         retention_decode,
                                         retention_decode_kernel,
                                         retention_prefill,
                                         retention_prefill_kernel,
                                         retention_step, state_dim)
from deepspeed_tpu.ops.retention import decode as kernel_mod
from deepspeed_tpu.ops.retention.retention import RUN_ALIGN, _pairs

EPS = 1e-6
LAYERS, LI = 2, 1


def real_rows(d):
    return _pairs(d, RUN_ALIGN)[2] > 0


def operands(d, groups, slots, dtype, seed=0, hk=1):
    """One token of `slots` slots and a state whose padding rows are
    zero, as prefill and decode leave them."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * d ** 0.5
    f = lambda x: jnp.asarray(x, jnp.float32)
    q = f(unit(rng.normal(size=(slots, hk * groups, d))))
    k = f(unit(rng.normal(size=(slots, hk, d))))
    v = f(rng.normal(size=(slots, hk, d)))
    lg = f(np.log(rng.uniform(0.9, 0.9999, (slots, hk))))
    rows = state_dim(d, RUN_ALIGN)
    real = real_rows(d)
    S = jnp.asarray(rng.normal(size=(LAYERS, slots, hk, rows, d)) *
                    real[:, None], dtype)
    z = jnp.asarray(np.abs(rng.normal(size=(LAYERS, slots, hk, rows))) *
                    real, dtype)
    return q, k, v, lg, S, z


@pytest.mark.parametrize("state_type", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 5], ids=lambda g: f"G{g}")
@pytest.mark.parametrize("d", [8, 128], ids=lambda d: f"d{d}")
def test_kernel_equals_the_step(d, groups, state_type):
    """Output, S and z of a live, a fresh and a kept slot against
    `retention_step` on the layer sliced out; the other layer and the
    kept slot bit-equal, the padding rows exactly zero."""
    q, k, v, lg, S, z = operands(d, groups, 3, state_type)
    keep = jnp.asarray([False, True, False])
    fresh = jnp.asarray([False, False, True])
    o, S1, z1 = jax.jit(lambda *a: retention_decode_kernel(
        *a, jnp.asarray(LI), 1.0 / d, EPS, keep=keep, fresh=fresh))(
        q, k, v, lg, S, z)
    want_o, want_S, want_z = retention_step(
        q, k, v, lg, S[LI], z[LI], 1.0 / d, EPS, keep=keep, fresh=fresh)
    as32 = lambda x: np.asarray(x, np.float32)
    live = np.asarray([0, 2])
    tol = 1e-5 if state_type == jnp.float32 else 1e-2
    assert np.abs(as32(o)[live] - as32(want_o)[live]).max() < \
        1e-5 * np.abs(as32(want_o)).max()
    assert np.abs(as32(S1[LI]) - as32(want_S)).max() < tol
    assert np.abs(as32(z1[LI]) - as32(want_z)).max() < tol
    assert S1.dtype == S.dtype and z1.dtype == z.dtype
    # kept slot and the other layer: not a bit moved
    assert np.array_equal(as32(S1[LI, 1]), as32(S[LI, 1]))
    assert np.array_equal(as32(z1[LI, 1]), as32(z[LI, 1]))
    assert np.array_equal(as32(S1[0]), as32(S[0]))
    assert np.array_equal(as32(z1[0]), as32(z[0]))
    assert not as32(S1[LI])[:, :, ~real_rows(d)].any()
    assert not as32(z1[LI])[:, :, ~real_rows(d)].any()


def test_a_fresh_slot_starts_from_zero_whatever_it_held():
    """A freed slot is not cleared: junk in every row, padding too."""
    d = 8
    q, k, v, lg, S, z = operands(d, 5, 2, jnp.float32)
    junk_S, junk_z = jnp.full_like(S, 7.0), jnp.full_like(z, 7.0)
    fresh = jnp.asarray([True, False])
    o, S1, z1 = retention_decode_kernel(
        q, k, v, lg, junk_S, junk_z, jnp.asarray(LI), 1.0 / d, EPS,
        fresh=fresh)
    zero_o, zero_S, zero_z = retention_decode_kernel(
        q, k, v, lg, jnp.zeros_like(S), jnp.zeros_like(z), jnp.asarray(LI),
        1.0 / d, EPS)
    assert np.array_equal(S1[LI, 0], zero_S[LI, 0])
    assert np.array_equal(z1[LI, 0], zero_z[LI, 0])
    assert np.array_equal(o[0], zero_o[0])
    assert not np.asarray(S1[LI, 0])[:, ~real_rows(d)].any()


def test_kernel_iterated_equals_all_pairs_and_the_chunked_state():
    """40 tokens a step at a time through the kernel: the outputs are
    the all-pairs form's, the state the chunked form's."""
    d, hq, hk, T = 8, 10, 2, 40
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * d ** 0.5
    k = unit(rng.normal(size=(T, hk, d)))
    q = unit(np.repeat(k, hq // hk, axis=1) / d ** 0.5 +
             0.7 * rng.normal(size=(T, hq, d)) / d ** 0.5)
    q, k = jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, hk, d)), jnp.float32)
    lg = jnp.asarray(np.log(rng.uniform(0.9, 0.9999, (T, hk))), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.retention_all_pairs(q, k, v, lg, 1.0 / d, EPS,
                                                  rows=16))
    rows = state_dim(d, RUN_ALIGN)
    S, z = jnp.zeros((1, 1, hk, rows, d)), jnp.zeros((1, 1, hk, rows))
    step = jax.jit(lambda *a: retention_decode_kernel(
        *a, jnp.asarray(0), 1.0 / d, EPS))
    outs = []
    for t in range(T):
        o, S, z = step(q[t][None], k[t][None], v[t][None], lg[t][None], S, z)
        outs.append(np.asarray(o[0]))
    assert np.abs(np.stack(outs) - want).max() < 2e-5 * np.abs(want).max()
    _, S1, z1 = retention_chunked(q[None], k[None], v[None], lg[None],
                                  jnp.zeros_like(S[0]), jnp.zeros_like(z[0]),
                                  1.0 / d, EPS, 8)
    assert np.allclose(S[0], S1, atol=1e-5) and \
        np.allclose(z[0], z1, atol=1e-5)


# ----------------------------------------------------------------------
# prefill: a launch of one slot through the state where it lies
# ----------------------------------------------------------------------
SLOT, CHUNK, LAUNCH = 1, 8, 24


def launch_operands(d, groups, dtype, hk=2):
    """One launch of `LAUNCH` tokens for one slot of three, and whole
    state arrays that 16 earlier tokens of every layer and slot left
    (a state of random rows has normalisers near zero)."""
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * d ** 0.5
    f = lambda x: jnp.asarray(x, jnp.float32)

    def drawn(rows, tokens):
        return (f(unit(rng.normal(size=(rows, tokens, hk * groups, d)))),
                f(unit(rng.normal(size=(rows, tokens, hk, d)))),
                f(rng.normal(size=(rows, tokens, hk, d))),
                f(np.log(rng.uniform(0.9, 0.9999, (rows, tokens, hk)))))

    n_rows = state_dim(d, RUN_ALIGN)
    _, S, z = retention_chunked(
        *drawn(LAYERS * 3, 16), jnp.zeros((LAYERS * 3, hk, n_rows, d)),
        jnp.zeros((LAYERS * 3, hk, n_rows)), 1.0 / d, EPS, CHUNK)
    whole = lambda x: x.reshape((LAYERS, 3) + x.shape[1:]).astype(dtype)
    return drawn(1, LAUNCH) + (whole(S), whole(z))


@pytest.mark.parametrize("state_type", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", [1, 5], ids=lambda g: f"G{g}")
@pytest.mark.parametrize("d", [8, 128], ids=lambda d: f"d{d}")
def test_prefill_kernel_equals_the_chunked_form(d, groups, state_type):
    """Output, S and z of a launch against `retention_chunked` on the
    slot sliced out: a first launch (the block holding junk), a later
    one from a carried state, a ragged last one whose valid tokens end
    inside a pair-chunk, and one whose valid tokens end before a whole
    pair-chunk (skipped); every other layer and slot bit for bit, the
    padding rows exactly zero."""
    q, k, v, lg, S, z = launch_operands(d, groups, state_type, hk=1)
    real = real_rows(d)
    launch = jax.jit(lambda S, z, start, valid: retention_prefill_kernel(
        q, k, v, lg, S, z, jnp.asarray(LI), jnp.asarray(SLOT), start, valid,
        1.0 / d, EPS, CHUNK))
    as32 = lambda x: np.asarray(x, np.float32)
    tol = 2e-5 if state_type == jnp.float32 else 1e-2
    junk = (jnp.full_like(S, 7.0), jnp.full_like(z, 7.0))
    for name, (S0, z0), start, n_valid in [
            ("first", junk, 0, LAUNCH), ("later", (S, z), 48, LAUNCH),
            ("ragged inside", (S, z), 48, 2 * CHUNK + 3),
            ("ragged before", (S, z), 48, CHUNK)]:
        valid = jnp.arange(LAUNCH) < n_valid
        o, S1, z1 = launch(S0, z0, jnp.asarray(start), valid)
        from_zero = jnp.zeros((1,) + S.shape[2:], S.dtype), \
            jnp.zeros((1,) + z.shape[2:], z.dtype)
        want_o, want_S, want_z = retention_chunked(
            q, k, v, lg, *(from_zero if start == 0 else
                           (S0[LI, SLOT][None], z0[LI, SLOT][None])),
            1.0 / d, EPS, CHUNK, valid[None])
        want_o = as32(want_o)[:, :n_valid]
        assert np.abs(as32(o)[:, :n_valid] - want_o).max() < \
            tol * np.abs(want_o).max(), name
        assert np.isfinite(as32(o)).all(), name
        assert np.abs(as32(S1[LI, SLOT]) - as32(want_S[0])).max() < \
            tol * np.abs(as32(want_S)).max(), name
        assert np.abs(as32(z1[LI, SLOT]) - as32(want_z[0])).max() < \
            tol * np.abs(as32(want_z)).max(), name
        assert S1.dtype == S.dtype and z1.dtype == z.dtype
        # the other layer and the other slots: not a bit moved
        others = np.arange(3) != SLOT
        for got, had in ((S1, S0), (z1, z0)):
            assert np.array_equal(as32(got[0]), as32(had[0])), name
            assert np.array_equal(as32(got[LI])[others],
                                  as32(had[LI])[others]), name
        assert not as32(S1[LI, SLOT])[:, ~real].any(), name
        assert not as32(z1[LI, SLOT])[:, ~real].any(), name


def test_prefill_then_decode_through_the_kernels_equals_all_pairs():
    """27 tokens of a prompt in two launches of 16 rows (the second
    ragged) through prefill's kernel, then 13 tokens a step at a time
    through decode's: the outputs are the all-pairs form's."""
    d, hq, hk, T, prompt, rows16 = 8, 10, 2, 40, 27, 16
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * d ** 0.5
    k = unit(rng.normal(size=(T, hk, d)))
    q = unit(np.repeat(k, hq // hk, axis=1) / d ** 0.5 +
             0.7 * rng.normal(size=(T, hq, d)) / d ** 0.5)
    q, k = jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, hk, d)), jnp.float32)
    lg = jnp.asarray(np.log(rng.uniform(0.9, 0.9999, (T, hk))), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.retention_all_pairs(q, k, v, lg, 1.0 / d, EPS,
                                                  rows=16))
    rows = state_dim(d, RUN_ALIGN)
    S = jnp.full((1, 2, hk, rows, d), 7.0)               # junk: a freed slot
    z = jnp.full((1, 2, hk, rows), 7.0)
    li, slot = jnp.asarray(0), jnp.asarray(1)
    launch = jax.jit(lambda q, k, v, lg, S, z, start, valid:
                     retention_prefill_kernel(q, k, v, lg, S, z, li, slot,
                                              start, valid, 1.0 / d, EPS, 8))
    outs = []
    for start in range(0, prompt, rows16):
        at = slice(start, start + rows16)
        o, S, z = launch(q[None, at], k[None, at], v[None, at], lg[None, at],
                         S, z, jnp.asarray(start),
                         jnp.arange(start, start + rows16) < prompt)
        outs.extend(np.asarray(o[0, :prompt - start]))
    step = jax.jit(lambda *a: retention_decode_kernel(
        *a, li, 1.0 / d, EPS, keep=jnp.asarray([True, False])))
    pad = lambda x: jnp.stack([jnp.zeros_like(x), x])    # slot 0 idle
    for t in range(prompt, T):
        o, S, z = step(pad(q[t]), pad(k[t]), pad(v[t]), pad(lg[t]), S, z)
        outs.append(np.asarray(o[1]))
    assert np.abs(np.stack(outs) - want).max() < 2e-5 * np.abs(want).max()
    assert np.array_equal(S[0, 0], jnp.full_like(S[0, 0], 7.0))


def test_off_the_chip_the_prefill_dispatcher_runs_the_chunked_form():
    d = 8
    q, k, v, lg, S, z = launch_operands(d, 5, jnp.float32)
    valid = jnp.arange(LAUNCH) < 19
    for start in (0, 48):
        o, S1, z1 = retention_prefill(
            q, k, v, lg, S, z, jnp.asarray(LI), jnp.asarray(SLOT),
            jnp.asarray(start), valid, 1.0 / d, EPS, CHUNK)
        zero = lambda x: x if start else jnp.zeros_like(x)
        want_o, want_S, want_z = retention_chunked(
            q, k, v, lg, zero(S[LI, SLOT][None]), zero(z[LI, SLOT][None]),
            1.0 / d, EPS, CHUNK, valid[None])
        assert np.array_equal(o, want_o)
        assert np.array_equal(S1[LI, SLOT], want_S[0]) and \
            np.array_equal(z1[LI, SLOT], want_z[0])
        assert np.array_equal(S1[0], S[0]) and \
            np.array_equal(S1[LI, :SLOT], S[LI, :SLOT])


# ----------------------------------------------------------------------
# the layout: padded where a head fills the lanes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("d, align, rows, pairs", [
    (16, None, 136, 136), (16, RUN_ALIGN, 192, 136), (8, None, 36, 36),
    (8, RUN_ALIGN, 64, 36), (128, None, 8704, 8256), (128, 1, 8256, 8256)],
    ids=lambda x: str(x))
def test_phi_is_the_symmetric_square_in_either_layout(d, align, rows, pairs):
    assert state_dim(d, align) == rows
    rng = np.random.default_rng(1)
    u, w = (jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
            for _ in range(2))
    fu, fw = phi(u, 0.25, rows), phi(w, 0.25, rows)
    assert fu.shape == (3, rows)
    assert np.allclose((fu * fw).sum(-1), (0.25 * (u * w).sum(-1)) ** 2,
                       rtol=1e-5)
    assert (np.asarray(phi(jnp.ones((d,)), 1.0, rows)) != 0).sum() == pairs
    if align is None:
        assert phi(u, 0.25).shape == (3, rows)


def test_padding_stays_zero_and_the_probe_reads_the_compact_state():
    """A prompt through the chunked form and five decode steps through
    the kernel on the aligned state of a head that fills the lanes,
    against the same through `retention_step` on the compact state:
    the benchmark's probe (`architectures/brumby._read`, the program's
    own `phi` by default) reads the same numerators and normalisers,
    and no padding row ever holds anything."""
    d, groups, T, more = 128, 2, 20, 5
    rng = np.random.default_rng(3)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True) * d ** 0.5
    f = lambda x: jnp.asarray(x, jnp.float32)
    q = f(unit(rng.normal(size=(T + more, groups, d))))
    k = f(unit(rng.normal(size=(T + more, 1, d))))
    v = f(rng.normal(size=(T + more, 1, d)))
    lg = f(np.log(rng.uniform(0.9, 0.9999, (T + more, 1))))
    scale = 1.0 / d
    aligned, compact = state_dim(d), state_dim(d, 1)
    assert (aligned, compact) == (8704, 8256)
    real = real_rows(d)

    def prefilled(rows):
        return retention_chunked(
            q[None, :T], k[None, :T], v[None, :T], lg[None, :T],
            jnp.zeros((1, 1, rows, d)), jnp.zeros((1, 1, rows)), scale, EPS,
            8)

    o_a, S_a, z_a = prefilled(aligned)
    o_c, S_c, z_c = prefilled(compact)
    assert np.abs(np.asarray(o_a - o_c)).max() < 1e-5 * np.abs(o_c).max()
    assert not np.asarray(S_a)[:, :, ~real].any()
    S_a, z_a = S_a[None], z_a[None]                     # one layer
    step = jax.jit(lambda *a: retention_decode_kernel(
        *a, jnp.asarray(0), scale, EPS))
    for t in range(T, T + more):
        o_a, S_a, z_a = step(q[t][None], k[t][None], v[t][None],
                             lg[t][None], S_a, z_a)
        o_c, S_c, z_c = retention_step(q[t][None], k[t][None], v[t][None],
                                       lg[t][None], S_c, z_c, scale, EPS)
        assert np.abs(np.asarray(o_a - o_c)).max() < \
            1e-5 * np.abs(o_c).max(), t
    assert not np.asarray(S_a)[..., ~real, :].any()
    assert not np.asarray(z_a)[..., ~real].any()
    dirs = jnp.asarray(arch.probes(d))
    num, den = arch._read(S_a, z_a, jnp.zeros((1,), jnp.int32), dirs,
                          scale=scale)
    feat = phi(dirs, scale, compact)
    with jax.default_matmul_precision("highest"):
        want_num = jnp.einsum("pD,shDd->shpd", feat, S_c)
        want_den = jnp.einsum("pD,shD->shp", feat, z_c)
    assert np.abs(np.asarray(num - want_num)).max() < \
        1e-5 * np.abs(want_num).max()
    assert np.abs(np.asarray(den - want_den)).max() < \
        1e-5 * np.abs(want_den).max()


# ----------------------------------------------------------------------
# the engine takes the kernel where `usable` says so
# ----------------------------------------------------------------------
def test_the_engines_decode_through_the_kernel_equals_the_xla_form(
        monkeypatch):
    """A head that fills the lanes keeps the aligned state in the
    engine's cache. With `usable` answering as on the chip the decode
    program holds the kernel (here in the interpreter); its logits and
    state are the XLA form's."""
    cfg = brumby.BrumbyConfig(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, max_position_embeddings=64, retention_chunk=8,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = brumby.init_params(cfg, jax.random.PRNGKey(0))
    block = {"max_slots": 2, "prefill_chunk": 8, "sync_every": 2,
             "max_new_tokens": 4, "max_seq_len": 64}
    ids = np.random.default_rng(4).integers(0, 97, 13).astype(np.int32)

    def served(with_kernel):
        if with_kernel:
            monkeypatch.setattr(kernel_mod, "usable", lambda S: True)
        engine = InferenceEngine(cfg, params, {"inference": block})
        assert engine.cache_arrays()[0].shape == (2, 2, 1, 8704, 128)
        engine.start_request(1, ids, 4)
        logits = [np.asarray(engine.decode_once())[1] for _ in range(3)]
        S, z = engine.cache_arrays()
        return np.stack(logits), np.asarray(S), np.asarray(z), engine

    want, want_S, want_z, _ = served(False)
    got, got_S, got_z, engine = served(True)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert np.abs(got_S - want_S).max() < 1e-5 * np.abs(want_S).max()
    assert np.abs(got_z - want_z).max() < 1e-5 * np.abs(want_z).max()
    # slot 0 never held a request: the kernel copied it through
    assert not got_S[:, 0].any() and not got_z[:, 0].any()
    assert engine.cache.pool_bytes == got_S.nbytes + got_z.nbytes


def test_the_engines_prefill_through_the_kernel_equals_the_xla_form(
        monkeypatch, tmp_path):
    """A request prefilled in three launches (the last one ragged) and
    decoded through the serving loop, with `usable` answering as on
    the chip (both kernels, here in the interpreter): the XLA form's
    tokens and state. The fence rows carry the rows the prefill
    launches took through the state and the prompt tokens among them;
    their ratio is the prompt's valid share."""
    cfg = brumby.BrumbyConfig(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, max_position_embeddings=64, retention_chunk=4,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = brumby.init_params(cfg, jax.random.PRNGKey(0))
    block = {"max_slots": 2, "prefill_chunk": 8, "sync_every": 2,
             "max_new_tokens": 4, "max_seq_len": 64}
    ids = np.random.default_rng(5).integers(0, 97, 21).astype(np.int32)

    def served(with_kernel):
        if with_kernel:
            monkeypatch.setattr(kernel_mod, "usable", lambda S: True)
        engine = InferenceEngine(cfg, params, {
            "inference": block,
            "monitor": {"enabled": True, "sinks": ["jsonl"],
                        "output_path": str(tmp_path / str(with_kernel))}})
        rows = []
        real = engine.monitor.event
        engine.monitor.event = lambda name, **kw: (
            rows.append(kw) if name == "decode_batch" else None,
            real(name, **kw))[1]
        done, = ServingLoop(engine).serve(
            [Request(rid=0, tokens=ids, max_new_tokens=4)])
        S, z = engine.cache_arrays()
        return done.out_tokens, np.asarray(S), np.asarray(z), rows

    want, want_S, want_z, _ = served(False)
    got, got_S, got_z, rows = served(True)
    assert np.array_equal(got, want) and len(got) == 4
    assert np.abs(got_S - want_S).max() < 1e-5 * np.abs(want_S).max()
    assert np.abs(got_z - want_z).max() < 1e-5 * np.abs(want_z).max()
    # 20 prompt tokens are prefilled (the last one is decode's first):
    # launches of 8, 8 and 4 valid rows, one a fence
    streamed = [r["state_prefill_rows_streamed"] for r in rows]
    tokens = [r["state_prefill_tokens"] for r in rows]
    assert streamed[:3] == [8, 8, 8] and not any(streamed[3:])
    assert tokens[:3] == [8, 8, 4] and not any(tokens[3:])
    assert sum(tokens) / sum(streamed) == (len(ids) - 1) / 24


def test_off_the_chip_the_dispatcher_runs_the_step():
    d = 8
    q, k, v, lg, S, z = operands(d, 5, 2, jnp.float32)
    o, S1, z1 = retention_decode(q, k, v, lg, S, z, jnp.asarray(LI),
                                 1.0 / d, EPS)
    want_o, want_S, want_z = retention_step(q, k, v, lg, S[LI], z[LI],
                                            1.0 / d, EPS)
    assert np.array_equal(o, want_o) and np.array_equal(S1[LI], want_S) \
        and np.array_equal(z1[LI], want_z) and np.array_equal(S1[0], S[0])
