"""What the serving programs are held against since the page pools
ride in the layer scan's carry (ISSUE 25): an oracle that never carries
anything, and a reader of the programs' jaxprs.

The oracle is the layer stack as it was written before: a Python loop
over the layers, each with its OWN page pool ([P, page, lanes], the
engine's row of a token), written with `.at[phys, off].set`.
Everything between the write and the block's output is the program's
own (`models/gpt2.py`'s `_ln_apply` and `_dense_apply`, and the
program's own attention entry on the layer's pool as a one-layer pool:
for a few query rows a slot `paged_decode_attention`, for a prefill
chunk `paged_prefill_attention`), so a difference is the carry's or
the scatter's. One jitted layer is called n_layer times: the same compiled
code for every layer, as in a scan's body."""

import contextlib
import dataclasses
import functools

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.engine import DECODE_ROWS_MAX
from deepspeed_tpu.ops.transformer.flash_attention import NEG_INF
from deepspeed_tpu.ops.transformer.paged_decode_attention import \
    paged_decode_attention
from deepspeed_tpu.ops.transformer.paged_prefill_attention import \
    paged_prefill_attention
from deepspeed_tpu.models.gpt2 import (_dense_apply, _ln_apply,
                                       stacked_block_params)


# ----------------------------------------------------------------------
# a prefill chunk's attention in its plain form: what the programs ran
# before ISSUE 42 (the slot's WHOLE table row gathered, keys and values
# repeated to the query head count, one dense masked softmax), kept as
# the reference `paged_prefill_attention` is held against
# ----------------------------------------------------------------------
def paged_attention(q, kc, vc, q_pos, kv_limit, first=None, k_pos=None):
    """Causal attention of q [B, Tq, H, D] against a gathered page
    window kc/vc [B, Tk, H, D], phrased like the training path's
    `dense_attention` (fp32 softmax, -1e30 where-masking): key
    positions are their indices, queries sit at absolute positions
    `q_pos` [B, Tq], and keys beyond `kv_limit` [B] are value-zeroed.
    A lower bound: a query sees no key below `first` [B, Tq], and keys
    below the earliest query's are value-zeroed. A window gathered
    through a ring of pages does not lie in the order of its
    positions: `k_pos` [B, Tk] then gives each key's (negative: no
    key)."""
    sm_scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32)
    scores = scores * sm_scale
    kpos = jnp.arange(kc.shape[1])[None, :] if k_pos is None else k_pos
    mask = kpos[:, None, None, :] <= q_pos[:, None, :, None]
    v_ok = kpos <= kv_limit[:, None]
    if first is not None:
        mask = mask & (kpos[:, None, None, :] >= first[:, None, :, None])
        v_ok = v_ok & (kpos >= first[:, :1])
    scores = jnp.where(mask, scores, jnp.float32(NEG_INF))
    probs = jax.nn.softmax(scores, axis=-1).astype(vc.dtype)
    vc = jnp.where(v_ok[:, :, None, None], vc, jnp.zeros((), vc.dtype))
    out = jnp.matmul(probs, vc.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


def dense_prefill_attention(q, k_pool, v_pool, li, tables, q_pos, kv_limit,
                            n_head, n_kv_head, first=None, ring=0):
    """`paged_prefill_attention`'s arguments through the plain form:
    the whole table row gathered, a ring's columns given the positions
    of the pages they hold (reckoned from the chunk's last page),
    grouped heads repeated."""
    b, t, _ = q.shape
    page = k_pool.shape[2]
    d = q.shape[-1] // n_head
    c = n_kv_head * d
    group = n_head // n_kv_head
    kc = k_pool[li, tables][..., :c].reshape(b, -1, n_kv_head, d)
    vc = v_pool[li, tables][..., :c].reshape(b, -1, n_kv_head, d)
    k_pos = None
    if ring:
        top = (kv_limit // page)[:, None]
        held = top - (top - jnp.arange(ring)[None, :]) % ring
        k_pos = (held[:, :, None] * page +
                 jnp.arange(page)[None, None, :]).reshape(b, -1)
    return paged_attention(
        q.reshape(b, t, n_head, d), jnp.repeat(kc, group, axis=2),
        jnp.repeat(vc, group, axis=2), q_pos, kv_limit, first=first,
        k_pos=k_pos).reshape(b, t, n_head * d)


@functools.partial(jax.jit, static_argnames=("cfg", "page_size",
                                              "quant_block"))
def _oracle_block(cfg, lp, hidden, kl, vl, tables, positions, valid,
                  kv_limit, page_size, quant_block):
    b, t, c = hidden.shape
    h, d = cfg.n_head, cfg.head_dim
    lanes = kl.shape[-1]
    cfg = dataclasses.replace(cfg, quant_block=quant_block)
    x = _ln_apply(cfg, lp["ln_1"], hidden).astype(cfg.dtype)
    qkv = _dense_apply(cfg, lp["c_attn"], x)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    pidx = positions // page_size
    off = (positions % page_size).reshape(-1)
    phys = jnp.take_along_axis(tables, pidx, axis=1)
    phys = jnp.where(valid, phys, 0).reshape(-1)
    row = lambda x: jnp.pad(x.reshape(b * t, c), ((0, 0), (0, lanes - c)))
    kl = kl.at[phys, off].set(row(k))
    vl = vl.at[phys, off].set(row(v))
    if t <= DECODE_ROWS_MAX:
        live_len = jnp.where(valid.any(axis=1), kv_limit + 1, 0)
        attn = paged_decode_attention(q, kl[None], vl[None], 0, tables,
                                      positions, live_len, h)
    else:
        attn = paged_prefill_attention(q, kl[None], vl[None], 0, tables,
                                       positions, kv_limit, h, h)
    attn = _dense_apply(cfg, lp["c_proj"], attn)
    hidden = hidden + attn
    y = _ln_apply(cfg, lp["ln_2"], hidden).astype(cfg.dtype)
    y = _dense_apply(cfg, lp["c_fc"], y)
    y = nn.gelu(y, approximate=True)
    y = _dense_apply(cfg, lp["mlp_c_proj"], y)
    return hidden + y, kl, vl


def oracle_forward(cfg, params, tokens, positions, valid, kv_limit,
                   tables, k_pool, v_pool, page_size, quant_block):
    """tokens/positions/valid [B, T], kv_limit [B], tables [B, pages];
    the pools as the engine holds them ([L, P, page, lanes], numpy).
    Returns (logits of ln_f + tied head [B, T, V], k_pool, v_pool),
    the pools again in the engine's shape."""
    dt = cfg.dtype
    wte, wpe = params["wte"], params["wpe"]
    posc = jnp.clip(positions, 0, cfg.n_positions - 1)
    hidden = wte[tokens].astype(dt) + wpe[posc].astype(dt)
    stacked = stacked_block_params(params)
    ks, vs = [], []
    for li in range(k_pool.shape[0]):
        lp = jax.tree_util.tree_map(lambda x: x[li], stacked)
        hidden, kl, vl = _oracle_block(
            cfg, lp, hidden, k_pool[li], v_pool[li], tables, positions,
            valid, kv_limit, page_size=page_size, quant_block=quant_block)
        ks.append(np.asarray(kl))
        vs.append(np.asarray(vl))
    final = _ln_apply(cfg, params["ln_f"], hidden)
    logits = jnp.einsum("btc,vc->btv", final.astype(dt), wte.astype(dt))
    return np.asarray(logits), np.stack(ks), np.stack(vs)


def assert_pools_equal(held, names, refs, what):
    """The pools `names` of the state dict `held`, bit for bit."""
    for name, ref in zip(names, refs):
        got = np.asarray(held[name])
        assert np.array_equal(got, ref), (what, name,
                                          np.abs(got - ref).max())


def prefill_inputs(chunk, tokens, start, page_row):
    """The prefill programs' view of one chunk of one request."""
    n = len(tokens)
    buf = np.zeros((chunk,), np.int32)
    buf[:n] = tokens
    return dict(
        tokens=buf[None],
        positions=(start + np.arange(chunk, dtype=np.int32))[None],
        valid=(np.arange(chunk) < n)[None],
        kv_limit=np.asarray([start + n - 1], np.int32),
        tables=np.asarray(page_row)[None])


# ----------------------------------------------------------------------
# the programs' jaxprs
# ----------------------------------------------------------------------
@contextlib.contextmanager
def traced_programs():
    """While this is open, every `jax.jit(fn).lower(*args)` of a serving
    program (`*_fn`) also leaves `jax.make_jaxpr(fn)(*args)` in the
    dict it yields, under the function's name."""
    jaxprs = {}
    real_jit = jax.jit

    class Recorded:
        def __init__(self, fn, jitted):
            self.fn, self.jitted = fn, jitted

        def lower(self, *args):
            jaxprs[self.fn.__name__] = jax.make_jaxpr(self.fn)(*args).jaxpr
            return self.jitted.lower(*args)

    def jit(fn, *a, **k):
        jitted = real_jit(fn, *a, **k)
        if getattr(fn, "__name__", "").endswith("_fn"):
            return Recorded(fn, jitted)
        return jitted

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "jit", jit)
        yield jaxprs


def equations(jaxpr, but=None):
    """Every equation of a jaxpr, inner jaxprs included; the equation
    `but` and what it holds left out."""
    for eqn in jaxpr.eqns:
        if eqn is but:
            continue
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def scans(jaxpr):
    """Every `scan` equation of a jaxpr, inner jaxprs included."""
    return (eqn for eqn in equations(jaxpr)
            if eqn.primitive.name == "scan")


def pools_in_scans(jaxpr, pool_shapes):
    """(carried, elsewhere): how many scan carries are a whole pool,
    and [(what, shape)] for every scan operand or result of a pool's
    shape (whole, or one layer's) that is NOT carry: a consumed `xs`,
    a stacked `ys`, a loop constant."""
    shapes = set(pool_shapes) | {s[1:] for s in pool_shapes}
    carried, elsewhere = 0, []
    for eqn in scans(jaxpr):
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        ins = [v.aval.shape for v in eqn.invars]
        outs = [v.aval.shape for v in eqn.outvars]
        carried += sum(s in pool_shapes
                       for s in ins[n_consts:n_consts + n_carry])
        for what, found in (("const", ins[:n_consts]),
                            ("xs", ins[n_consts + n_carry:]),
                            ("ys", outs[n_carry:])):
            elsewhere += [(what, s) for s in found if s in shapes]
    return carried, elsewhere
