"""Mamba-1's selective scan (Gu and Dao, arXiv:2312.00752) as a serving
engine needs it: a state per CHANNEL that is a vector of N values,
advanced a chunk of tokens at a time (prefill, one slot) or one token
of every slot at a time (decode).

For channel d of D with the token's step dt_t[d] > 0, its input
c_t[d], the token's B_t, C_t [N] (shared by every channel) and the
channel's A[d] < 0 [N]:

    S_t[d] = exp(dt_t[d] A[d]) * S_{t-1}[d] + dt_t[d] c_t[d] B_t      [N]
    y_t[d] = S_t[d] . C_t + D[d] c_t[d]

The decay is a number for every (channel, state) pair: there is no
scalar a head to factor out of a chunk, so the chunk is no matrix
product (that is Mamba-2's, `mamba2.py`) and a token's update is
elementwise over D x N values. Expanded over a chunk of T tokens in
XLA, exp(dt A), dt c B and the states are [T, D, N] float32 each (168
MB at 512 x 5120 x 16); `selective_scan_chunk` on a TPU is instead one
Pallas kernel that keeps the state in VMEM, channels on the lanes, and
passes over dt, c, B and C once.

The state is held TRANSPOSED, [N, D] (N = 16 rows of D = 5120 lanes:
whole lane tiles; as [D, N] the chip would pad 16 to 128 lanes and the
state would take eight times its bytes), float32 unless the caller
keeps it in another type (read as float32, written back in its own).
dt, A, the decays and every sum into or out of the state are float32;
c, B, C and y are the caller's compute type.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _on_tpu

f32 = jnp.float32
LANE = 128
# tokens a grid step of the kernel takes, and channels (lanes) a step
_TOKENS = 128
_CHANNELS = 512


def usable():
    """Whether `selective_scan_chunk` takes the kernel (a TPU); the
    XLA form elsewhere (a test patches this to run the kernel
    interpreted)."""
    return _on_tpu()


def _scan_xla(dt, dtc, B, C, A_t, S0):
    """The recurrence as a `lax.scan` over the tokens: dt, dtc [T, D]
    float32 (dtc = dt * c), B, C [T, N] float32, A_t [N, D], S0 [N, D]
    float32 -> (S.C [T, D] float32, S_T)."""
    def step(S, tok):
        d, dc, b, c = tok
        S = jnp.exp(d[None, :] * A_t) * S + dc[None, :] * b[:, None]
        return S, (S * c[:, None]).sum(0)

    S1, y = jax.lax.scan(step, S0, (dt, dtc, B, C))
    return y, S1


def _kernel(dt_ref, dtc_ref, b_ref, c_ref, a_ref, s0_ref, y_ref, s1_ref,
            s_ref):
    """One block of `_TOKENS` tokens x `_CHANNELS` channels. The state
    of every channel tile waits in `s_ref` [tiles, N, channels] between
    the token blocks (the outer grid axis). B and C come with their N
    values on the sublanes and every lane alike ([tokens, N, 128]), so
    that a token's B is a [N, 128] tile as the state's lane tiles are."""
    tb, ct = pl.program_id(0), pl.program_id(1)
    tokens, channels = dt_ref.shape

    @pl.when(tb == 0)
    def _():
        s_ref[ct] = s0_ref[...].astype(f32)

    A = a_ref[...]

    def eight(i, S):
        at = pl.multiple_of(i * 8, 8)
        dt8 = dt_ref[pl.ds(at, 8), :]
        dc8 = dtc_ref[pl.ds(at, 8), :]
        rows = []
        for r in range(8):
            b = b_ref[at + r]                      # [N, 128]
            c = c_ref[at + r]
            d, dc = dt8[r:r + 1, :], dc8[r:r + 1, :]
            tiles, outs = [], []
            for j in range(channels // LANE):
                lanes = slice(j * LANE, (j + 1) * LANE)
                s = jnp.exp(d[:, lanes] * A[:, lanes]) * S[:, lanes] + \
                    dc[:, lanes] * b
                tiles.append(s)
                outs.append(jnp.sum(s * c, axis=0, keepdims=True))
            S = jnp.concatenate(tiles, axis=1)
            rows.append(jnp.concatenate(outs, axis=1))
        y_ref[pl.ds(at, 8), :] = jnp.concatenate(rows, axis=0)
        return S

    S = jax.lax.fori_loop(0, tokens // 8, eight, s_ref[ct])
    s_ref[ct] = S

    @pl.when(tb == pl.num_programs(0) - 1)
    def _():
        s1_ref[...] = S.astype(s1_ref.dtype)


def _scan_pallas(dt, dtc, B, C, A_t, S0, interpret=False):
    """As `_scan_xla`, T a multiple of `_TOKENS` and D of `_CHANNELS`
    (the caller pads)."""
    t, d = dt.shape
    n = A_t.shape[0]
    wide = lambda x: jnp.broadcast_to(x.astype(f32)[:, :, None], (t, n, LANE))
    grid = (t // _TOKENS, d // _CHANNELS)
    rows = pl.BlockSpec((_TOKENS, _CHANNELS), lambda tb, ct: (tb, ct))
    both = pl.BlockSpec((_TOKENS, n, LANE), lambda tb, ct: (tb, 0, 0))
    state = pl.BlockSpec((n, _CHANNELS), lambda tb, ct: (0, ct))
    return pl.pallas_call(
        _kernel,
        name="mamba1_selective_scan",
        grid=grid,
        in_specs=[rows, rows, both, both, state, state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((t, d), f32),
                   jax.ShapeDtypeStruct((n, d), f32)],
        scratch_shapes=[pltpu.VMEM((d // _CHANNELS, n, _CHANNELS), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(dt, dtc, wide(B), wide(C), A_t, S0)


def selective_scan_chunk(c, dt, A_t, B, C, D, S0, valid=None,
                         interpret=None):
    """A run of tokens of ONE sequence from the state S0.

    c [T, D] (the convolution's output, compute type); dt [T, D]
    float32 (after softplus); A_t [N, D] float32 (A transposed,
    negative); B, C [T, N]; D [D]; S0 [N, D], the state the run starts
    from; `valid` [T] bool (default: all): a row that is not valid
    (the pad behind a prefill chunk's tokens) takes a step of dt = 0,
    which leaves the state alone. Returns (y [T, D] in c's type, S1 in
    S0's type): S1 is the state after the last valid row. `interpret`:
    the kernel in the Pallas interpreter (tests); default: the kernel
    on a TPU (`usable`), the XLA form elsewhere."""
    t, d = c.shape
    dt = dt.astype(f32)
    if valid is not None:
        dt = jnp.where(valid[:, None], dt, 0.0)
    c32 = c.astype(f32)
    dtc, B, C, A_t, S = (dt * c32, B.astype(f32), C.astype(f32),
                         A_t.astype(f32), S0.astype(f32))
    if interpret is None and not usable():
        y, S1 = _scan_xla(dt, dtc, B, C, A_t, S)
    else:
        pt, pd = -t % _TOKENS, -d % _CHANNELS
        rows = lambda x, wide: jnp.pad(x, ((0, pt), (0, wide)))
        lanes = lambda x: jnp.pad(x, ((0, 0), (0, pd)))
        y, S1 = _scan_pallas(rows(dt, pd), rows(dtc, pd), rows(B, 0),
                             rows(C, 0), lanes(A_t), lanes(S),
                             interpret=bool(interpret))
        y, S1 = y[:t, :d], S1[:, :d]
    y = y + D.astype(f32) * c32
    return y.astype(c.dtype), S1.astype(S0.dtype)


def selective_step(c, dt, A_t, B, C, D, S, li, keep, fresh):
    """One token of every slot, on layer `li` of the WHOLE state array
    S [L, slots, N, D] (it rides in the layer scan's carry; the layer's
    part is updated in place): c [slots, D]; dt [slots, D] float32; B,
    C [slots, N]; A_t [N, D]; D [D]. A slot with `fresh` [slots] starts
    from zero state (a one-token prompt's first step), a slot with
    `keep` [slots] (not live) keeps the state it has. Returns (y
    [slots, D] in c's type, S)."""
    old = jax.lax.dynamic_index_in_dim(S, li, 0, keepdims=False)
    S0 = jnp.where(fresh[:, None, None], 0.0, old.astype(f32))
    dt, c32 = dt.astype(f32), c.astype(f32)
    S1 = jnp.exp(dt[:, None, :] * A_t.astype(f32)) * S0 + \
        (dt * c32)[:, None, :] * B.astype(f32)[:, :, None]
    y = (S1 * C.astype(f32)[:, :, None]).sum(1) + D.astype(f32) * c32
    S1 = jnp.where(keep[:, None, None], old, S1.astype(S.dtype))
    return y.astype(c.dtype), jax.lax.dynamic_update_index_in_dim(S, S1, li,
                                                                  0)
