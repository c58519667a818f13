"""The plain reference: GPT-2 as published (Radford et al. 2019; the
`gpt2*` `config.json` files), in `jax.numpy` and float32 at matmul
precision "highest". No kernels, no cache, no batching tricks, and no
import from the program.

Weights come as the flat dict of `benchmark/weights.py` (`h.*` leaves
stacked `[n_layer, ...]`, any dtype: they are read as float32). The
model is pre-LayerNorm, GeLU in its tanh form, causal attention scaled
by 1/sqrt(head width), output head tied to the token embedding; the
training loss is the mean next-token cross-entropy over every row's
first T-1 positions.

Two users: `logits` (serving: one full forward over a prompt with the
tokens served after it) and `TrainFollower` (training: the first steps
of AdamW, layer by layer so that a 1.5B model fits beside nothing else
on one 16 GB chip).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5          # layer_norm_epsilon of every gpt2* config.json
f32 = jnp.float32


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rounded_to(dtype):
    """Operands of the four projections rounded to `dtype` and read
    back as float32: the reference computed in a lower precision, which
    is what a control is. Gradients pass the rounding straight through
    (through the cast itself they would be rounded to `dtype` too, and
    at fp8 flush to zero)."""
    return lambda x: x + jax.lax.stop_gradient(
        x.astype(dtype).astype(f32) - x)


def layer(lp, h, n_head, cast=None):
    """One block. lp: this layer's slice, keys like "c_attn.kernel".
    `cast` rounds both operands of the four projections (controls)."""
    lp = {k: v.astype(f32) for k, v in lp.items()}
    if cast is None:
        return _layer(lp, h, n_head, lambda x: x)
    lp = {k: cast(v) if k.endswith(".kernel") else v for k, v in lp.items()}
    return _layer(lp, h, n_head, cast)


def _layer(lp, h, n_head, act):
    b, t, c = h.shape
    d = c // n_head
    x = act(_ln(h, lp["ln_1.scale"], lp["ln_1.bias"]))
    qkv = x @ lp["c_attn.kernel"] + lp["c_attn.bias"]
    q, k, v = (z.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
               for z in jnp.split(qkv, 3, axis=-1))
    s = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1) @ v
    a = act(a.transpose(0, 2, 1, 3).reshape(b, t, c))
    h = h + a @ lp["c_proj.kernel"] + lp["c_proj.bias"]
    y = act(_ln(h, lp["ln_2.scale"], lp["ln_2.bias"]))
    y = act(_gelu(y @ lp["c_fc.kernel"] + lp["c_fc.bias"]))
    return h + y @ lp["mlp_c_proj.kernel"] + lp["mlp_c_proj.bias"]


def embed(top, ids):
    t = ids.shape[1]
    return top["wte"].astype(f32)[ids] + top["wpe"].astype(f32)[:t][None]


def head_logits(top, h):
    x = _ln(h, top["ln_f.scale"].astype(f32), top["ln_f.bias"].astype(f32))
    return x @ top["wte"].astype(f32).T


def head_nll_sum(top, h, ids):
    """Sum of the next-token cross-entropy over rows' first T-1
    positions (the caller divides by the count of the whole batch)."""
    lg = head_logits(top, h)[:, :-1]
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(lg, axis=-1) - gold).sum()


def split(flat):
    """(top-level leaves, stacked block leaves with the "h." cut)."""
    top = {k: v for k, v in flat.items() if not k.startswith("h.")}
    blocks = {k[2:]: v for k, v in flat.items() if k.startswith("h.")}
    return top, blocks


def logits(flat, ids, n_head, cast=None):
    """[B, T] tokens -> [B, T, V] float32 logits, one layer at a time."""
    with jax.default_matmul_precision("highest"):
        top, blocks = split(flat)

        def body(h, lp):
            return layer(lp, h, n_head, cast), None

        h, _ = jax.lax.scan(body, embed(top, ids), blocks)
        return head_logits(top, h)


# ----------------------------------------------------------------------
# training: the first steps of AdamW, followed layer by layer
# ----------------------------------------------------------------------
def warmup_lr(step, min_lr, max_lr, warmup_steps):
    """DeepSpeed's WarmupLR (log warm-up, then constant) at the count
    of optimizer steps already taken."""
    gamma = min(1.0, math.log(step + 1) / math.log(max(2, warmup_steps)))
    return min_lr + (max_lr - min_lr) * gamma


class TrainFollower:
    """Gradients of the mean loss over a batch, layer by layer and in
    blocks of rows, then AdamW's first steps from them.

    On the device at once: the weights as given, one float32 gradient
    of the whole model, the block boundaries of one block of rows and
    one layer's temporaries. Earlier steps' gradients wait on the host.
    """

    def __init__(self, flat, n_head, rows_per_block, cast=None):
        self.top, self.blocks = split(flat)
        self.n_head = n_head
        self.n_layer = next(iter(self.blocks.values())).shape[0]
        self.rows = rows_per_block
        hp = jax.default_matmul_precision("highest")

        def jit(fn, **kw):
            def wrapped(*a):
                with hp:
                    return fn(*a)
            return jax.jit(wrapped, **kw)

        self._embed = jit(embed)
        self._layer = jit(lambda blocks, l, h: layer(
            self._slice(blocks, l), h, n_head, cast))

        def head(top, h, ids, count):
            (nll, (g_top, g_h)) = jax.value_and_grad(
                lambda tp, hh: head_nll_sum(tp, hh, ids) / count,
                argnums=(0, 1))(top, h)
            return nll, g_top, g_h
        self._head = jit(head)

        def layer_bwd(blocks, l, h_in, g_out, acc):
            lp = self._slice(blocks, l)
            _, vjp = jax.vjp(lambda p, x: layer(p, x, n_head, cast),
                             lp, h_in)
            g_lp, g_in = vjp(g_out)
            acc = {k: acc[k].at[l].add(g_lp[k].astype(f32)) for k in acc}
            return g_in, acc
        self._layer_bwd = jit(layer_bwd, donate_argnums=(4,))

        def embed_bwd(top, ids, g_h, acc_top):
            _, vjp = jax.vjp(lambda tp: embed(tp, ids), top)
            g, = vjp(g_h)
            return {k: acc_top[k] + g[k].astype(f32) for k in acc_top}
        self._embed_bwd = jit(embed_bwd, donate_argnums=(3,))

    @staticmethod
    def _slice(blocks, l):
        return {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
                for k, v in blocks.items()}

    def loss_and_grads(self, batch):
        """batch: int32 [rows, T] on the host. Returns (loss, flat
        float32 gradients of the mean loss, on the device)."""
        count = batch.shape[0] * (batch.shape[1] - 1)
        # jitted, so that the zeros lie where the weights lie
        zeros = jax.jit(lambda tree: {k: jnp.zeros(v.shape, f32)
                                      for k, v in tree.items()})
        acc, acc_top = zeros(self.blocks), zeros(self.top)
        loss = 0.0
        for r in range(0, batch.shape[0], self.rows):
            ids = jnp.asarray(batch[r:r + self.rows])
            hs = [self._embed(self.top, ids)]
            for l in range(self.n_layer):
                hs.append(self._layer(self.blocks, l, hs[-1]))
            nll, g_top, g_h = self._head(self.top, hs.pop(), ids,
                                         float(count))
            loss += float(nll)
            acc_top = {k: acc_top[k] + g_top[k] for k in acc_top}
            for l in reversed(range(self.n_layer)):
                g_h, acc = self._layer_bwd(self.blocks, l, hs.pop(),
                                           g_h, acc)
            acc_top = self._embed_bwd(self.top, ids, g_h, acc_top)
        grads = dict(acc_top)
        grads.update({"h." + k: v for k, v in acc.items()})
        return loss, grads


def global_norm(flat):
    return math.sqrt(sum(float(jnp.sum(jnp.square(v.astype(f32))))
                         for v in flat.values()))


def adamw_follow(flat, grads_per_step, lrs, b1, b2, eps, weight_decay,
                 clip=0.0):
    """AdamW (decoupled decay, bias-corrected) over `grads_per_step`
    (each a flat dict on the host or the device) from zero moments, leaf
    by leaf. Gradients are first clipped to the global norm `clip`
    (0 = off), as the optimizer gets them. Returns, per leaf:
    `grad_norm` (the first step's, after clipping), `dp_norm` (norm of
    the parameters' change) and `dp_along_mu` (the change along minus
    the last first moment, over that moment's norm)."""
    scales = []
    for g in grads_per_step:
        n = global_norm(g)
        scales.append(min(1.0, clip / (n + 1e-6)) if clip else 1.0)

    @jax.jit
    def leaf_stats(p0, gs, scales, lrs):
        p = p0.astype(f32)
        mu = jnp.zeros(p.shape, f32)
        nu = jnp.zeros(p.shape, f32)
        g_norm = None
        for i in range(len(gs)):
            g = gs[i].astype(f32) * scales[i]
            if i == 0:
                g_norm = jnp.sqrt(jnp.sum(g * g))
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * g * g
            upd = (mu / (1 - b1 ** (i + 1))) / (
                jnp.sqrt(nu / (1 - b2 ** (i + 1))) + eps)
            p = p - lrs[i] * (upd + weight_decay * p)
        dp = p - p0.astype(f32)
        mu_norm = jnp.sqrt(jnp.sum(mu * mu))
        return (g_norm, jnp.sqrt(jnp.sum(dp * dp)),
                -jnp.sum(dp * mu) / jnp.maximum(mu_norm, 1e-30))

    out = {"grad_norm": {}, "dp_norm": {}, "dp_along_mu": {}}
    for name, p0 in flat.items():
        gs = [jax.device_put(g[name], p0.sharding) for g in grads_per_step]
        g_norm, dp_norm, along = leaf_stats(
            p0, gs, jnp.asarray(scales, f32), jnp.asarray(lrs, f32))
        out["grad_norm"][name] = float(g_norm)
        out["dp_norm"][name] = float(dp_norm)
        out["dp_along_mu"][name] = float(along)
    return out


def to_host(flat):
    return {k: np.asarray(v) for k, v in flat.items()}
