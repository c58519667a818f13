#!/usr/bin/env python3
"""The flash kernels alone, on the chip: what the choices inside
`ops/transformer/flash_attention.py` cost, re-measurable from the tree.

For each shape (the two training cells' launches, one head a column
tile, and a T of four blocks, which runs the two sweep kernels), value
and gradient of one launch, for each variant:

  packed, off   `head_packing` on the same column tile (d = 64)
  product       `flash_attention_qkv` on the `c_attn` product whole
  parent_*      with --parent, the `flash_attention.py` of another
                checkout (a `git archive` of the parent commit) in the
                same process, beside this tree's

it prints the gradients' distance from `dense_attention`, whether packed
and unpacked (and product and split) are the same bits, and from one
profiler trace a variant the device time of the Mosaic calls themselves
(`fwd_ms`, `bwd_ms` a launch) and of the whole jitted program
(`program_ms`: the kernels and whatever XLA puts around them). Not a
benchmark cell and no record of the product's speed (PERF.md §2): the
yardstick for "is the kernel itself slower", which a cell cannot read.

    chiprun -- python3 tests/perf/flash_kernel_ab.py \\
        [--parent .archive_check/parent] [--launches 5]

Rows go to stdout and to chiprun_out/flash_kernel_ab.json. `--toy` runs
the comparisons at toy sizes in the interpreter and times nothing
(`tests/test_flash_head_packing.py` keeps that alive).
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
flash = importlib.import_module(
    "deepspeed_tpu.ops.transformer.flash_attention")

# name -> [B, T, H, D]
SHAPES = {"gpt2-350m": (16, 1024, 16, 64), "gpt2-1.5b": (10, 1024, 25, 64),
          "d128": (4, 1024, 8, 128), "sweeps": (2, 4096, 8, 64)}
TOY = {"gpt2-350m": (2, 128, 4, 64), "gpt2-1.5b": (2, 128, 5, 64),
       "d128": (2, 128, 2, 128), "sweeps": (2, 256, 2, 64)}


def _value_and_grad(attend):
    """attend(q, k, v) -> [B, T, H, D]; the cotangent g is an operand."""
    def loss(q, k, v, g):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) *
                       g.astype(jnp.float32))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _of_product(h, interpret):
    """The product entry behind the split entry's signature: the join
    and the split of the gradient stand for the caller's `c_attn`, and
    count in `program_ms` but not in `fwd_ms` / `bwd_ms`."""
    def attend(q, k, v):
        b, t = q.shape[:2]
        return flash.flash_attention_qkv(jnp.concatenate(
            [x.reshape(b, t, -1) for x in (q, k, v)], axis=-1), h,
            head_packing="packed", interpret=interpret)
    return attend


def variants(h, d, parent, interpret):
    out = {}
    for module, prefix in ((flash, ""), (parent, "parent_")):
        if module is None:
            continue
        for packing in ("packed", "off") if d == 64 else ("off",):
            out[prefix + packing] = _value_and_grad(
                lambda q, k, v, m=module, p=packing: m.flash_attention(
                    q, k, v, head_packing=p, interpret=interpret))
    if d == 64 and (h * d) % 128 == 0:
        out["product"] = _value_and_grad(_of_product(h, interpret))
    return out


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _dense_grads(q, k, v, g, rows=2):
    """dense_attention's gradients, a few batch rows at a time (its
    float32 scores are [rows, H, T, T])."""
    ref = _value_and_grad(
        lambda q, k, v: flash.dense_attention(q, k, v, causal=True))
    parts = [ref(*(x[i:i + rows] for x in (q, k, v, g)))[1]
             for i in range(0, q.shape[0], rows)]
    return [np.concatenate([np.asarray(p[n], np.float32) for p in parts])
            for n in range(3)]


def _device_ms(fn, args, launches):
    from benchmark import trace_reduce
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(launches):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    fwd, n_fwd = trace_reduce.matching_seconds(trace, r"flash_fwd")
    bwd, n_bwd = trace_reduce.matching_seconds(trace, r"flash_bwd")
    ops = trace_reduce.op_seconds(trace)
    return {"fwd_ms": 1e3 * fwd / max(n_fwd, 1),
            "bwd_ms": 1e3 * bwd / max(n_bwd, 1),
            "flash_calls_a_launch": (n_fwd + n_bwd) / launches,
            "program_ms": 1e3 * sum(ops.values()) / launches,
            "longest_ops_ms": {
                k: round(1e3 * s / launches, 4) for k, s in
                sorted(ops.items(), key=lambda kv: -kv[1])[:6]}}


def compare(shapes, parent=None, launches=0, interpret=False):
    """{shape: {variant: row}}; times where `launches` is not 0."""
    results = {}
    for name, (b, t, h, d) in shapes.items():
        keys = jax.random.split(jax.random.PRNGKey(7), 4)
        q, k, v, g = (0.5 * jax.random.normal(x, (b, t, h, d), jnp.bfloat16)
                      for x in keys)
        want = _dense_grads(q, k, v, g)
        rows, grads = {}, {}
        for variant, fn in variants(h, d, parent, interpret).items():
            grads[variant] = jax.block_until_ready(fn(q, k, v, g))[1]
            rows[variant] = {
                "finite": all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                              for x in grads[variant]),
                "grad_rel_err_vs_dense": [
                    _rel(x, w) for x, w in zip(grads[variant], want)]}
            if launches:
                rows[variant].update(_device_ms(fn, (q, k, v, g), launches))
        for variant, same_as in (("off", "packed"), ("product", "packed")):
            if variant in grads and same_as in grads:
                rows[variant]["same_bits_as_" + same_as] = all(
                    bool(jnp.array_equal(x, y))
                    for x, y in zip(grads[variant], grads[same_as]))
        if "parent_packed" in grads:
            rows["packed"]["grad_rel_err_vs_parent"] = [
                _rel(x, y) for x, y in zip(grads["packed"],
                                           grads["parent_packed"])]
        for variant, row in rows.items():
            print(name, [b, t, h, d], variant, json.dumps(row), flush=True)
        results[name] = rows
    return results


def load_parent(checkout):
    spec = importlib.util.spec_from_file_location(
        "flash_attention_parent", os.path.join(
            checkout, "deepspeed_tpu/ops/transformer/flash_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parent", help="a checkout of the parent commit")
    parser.add_argument("--launches", type=int, default=5)
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args()
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    parent = load_parent(args.parent) if args.parent else None
    if args.toy:
        compare(TOY, parent, interpret=True)
        return
    results = compare(SHAPES, parent, args.launches)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "flash_kernel_ab.json"),
              "w") as f:
        json.dump({"device": device.device_kind, "results": results}, f,
                  indent=1)
    print(json.dumps({"device": device.device_kind}))


if __name__ == "__main__":
    main()
