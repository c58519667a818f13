"""Traffic of kind "train": `deepspeed_tpu.initialize()` and
`engine.train_batch()` on a seeded token stream fed from the host.

Set-up builds one engine and drives it through its first three steps
by the same call and feed as the window; the same engine is then
timed. `correct` compares those first steps with the plain reference
(`benchmark/reference/gpt2.py`), which runs after the engine is freed.
"""

import collections
import gc
import math
import time

import numpy as np

from benchmark import harness, traffic, weights
from benchmark.harness import say

FIRST_STEPS = 3


def model_config(sizes, seq_len):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import gpt2_config
    prog = sizes["program"]
    cfg = gpt2_config(prog["preset"], n_positions=seq_len, dropout=0.0,
                      remat=True, remat_policy=prog["remat_policy"],
                      param_dtype=jnp.dtype(prog["param_dtype"]))
    for key in ("n_layer", "n_embd", "n_head", "vocab_size"):
        if getattr(cfg, key) != sizes[key]:
            raise ValueError(
                f"configuration file says {key}={sizes[key]}, the "
                f"program's preset {prog['preset']} {getattr(cfg, key)}")
    return cfg


def find_moments(opt_state):
    """The Adam state (first moments `mu`, second `nu`) inside the
    optimizer state, whichever transform wraps it."""
    import jax
    has = lambda x: hasattr(x, "mu") and hasattr(x, "nu")
    found = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=has)
             if has(x)]
    if len(found) != 1:
        raise ValueError(f"{len(found)} Adam states in the optimizer state")
    return found[0]


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    flat = weights.from_program_tree(tree)
    out = jax.jit(lambda f: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in f.items()})(flat)
    return {k: float(v) for k, v in out.items()}


def change_stats(now_tree, start_leaf, mu_tree, mesh):
    """Per leaf: the norm of the parameters' change and the change
    along minus the first moment, over that moment's norm.
    `start_leaf(name)` makes one leaf's starting value again, so that
    no second copy of the model lies beside the program's state."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    everywhere = NamedSharding(mesh, PartitionSpec())

    @jax.jit
    def one(p, p0, mu):
        # ZeRO pads a leaf that its shards do not divide: cut it back
        cut = tuple(slice(0, n) for n in p0.shape)
        dp = p[cut].astype(jnp.float32) - p0.astype(jnp.float32)
        mu = mu[cut].astype(jnp.float32)
        return (jnp.sqrt(jnp.sum(dp * dp)), -jnp.sum(dp * mu) /
                jnp.maximum(jnp.sqrt(jnp.sum(mu * mu)), 1e-30))

    now, mus = (weights.from_program_tree(t) for t in (now_tree, mu_tree))
    norm, along = {}, {}
    for k in now:
        a, b = one(now[k], jax.device_put(start_leaf(k), everywhere), mus[k])
        norm[k], along[k] = float(a), float(b)
    return norm, along


def worst_leaf_gap(got, want):
    """The gap between the program's number and the reference's, leaf
    by leaf, against the reference's number for that leaf or for the
    median leaf, whichever is larger; the worst leaf."""
    floor = float(np.median([abs(v) for v in want.values()]))
    gaps = {k: abs(got[k] - want[k]) / max(abs(want[k]), floor)
            for k in want}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def run(cell, seed, seconds, trace, control, t_start, compiles,
        check_only=False):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM

    sizes, mix = cell["sizes"], cell["mix"]
    seq = mix["seq_len"]
    ds_config = harness.merged(mix["ds_config"],
                               (control or {}).get("ds_config"))
    cfg = model_config(sizes, seq)
    model = GPT2ForCausalLM(cfg)
    example = {"input_ids": np.zeros((1, seq), np.int32)}
    template = jax.eval_shape(lambda k: model.init(k, example),
                              jax.random.PRNGKey(0))
    make = lambda only=None: weights.make_weights(
        sizes, seed, cfg.param_dtype, only)
    params = weights.to_program_tree(make(), template)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    chips = engine.dp_world_size
    gas = engine.gradient_accumulation_steps()
    rows = engine.train_micro_batch_size_per_gpu() * chips
    say(f"train: {sizes['program']['preset']} seq {seq}, {rows} rows a "
        f"step over {chips} chips, gas {gas}, zero stage "
        f"{engine.zero_optimization_stage()}")
    batches = traffic.train_batches(mix, sizes["vocab_size"], gas, rows,
                                    seq, seed)

    def step(batch):
        with jax.profiler.TraceAnnotation("bench/step"):
            return engine.train_batch(batch=batch)

    # ---- the first steps: set-up, and what `correct` compares --------
    follow = int(mix["check"]["steps"])
    first_batches, first_losses, got = [], [], {}
    b1 = float(ds_config["optimizer"]["params"]["betas"][0])
    for i in range(FIRST_STEPS):
        batch = next(batches)
        if i < follow:
            first_batches.append(batch["input_ids"].reshape(rows * gas, seq))
        first_losses.append(float(step(batch)))
        moments = find_moments(engine.state.opt_state)
        if i == 0:
            got["grad_norm"] = {k: v / (1.0 - b1) for k, v in
                                leaf_norms(moments.mu).items()}
        if i == follow - 1:
            state = engine.state
            held = state.master if state.master is not None \
                else state.params
            got["dp_norm"], got["dp_along_mu"] = change_stats(
                held, lambda name: make(only=(name,))[name], moments.mu,
                engine.mesh)
    del moments
    jax.block_until_ready(engine.state)
    say("train: first losses", " ".join(f"{x:.5f}" for x in first_losses))
    setup_compiles = compiles.snapshot()

    # ---- the window ---------------------------------------------------
    losses, done_at, n_steps = [], [], 0
    in_window = dict(compiles.counts)
    traced = None
    t_open = time.perf_counter()
    setup_s = time.time() - t_start
    inflight = collections.deque()
    while not check_only:
        if trace and n_steps == 2 and traced is None:
            for loss in inflight:
                loss.block_until_ready()
            with harness.TracedWindow(cell["name"]) as traced:
                for _ in range(3):
                    loss = step(next(batches))
                    n_steps += 1
                loss.block_until_ready()
            losses.extend(inflight)
            losses.append(loss)
            inflight.clear()
            done_at.clear()
        inflight.append(step(next(batches)))
        n_steps += 1
        if len(inflight) > 1:
            # one step queued behind the one that runs: the host stays
            # a step ahead and never further
            loss = inflight.popleft()
            loss.block_until_ready()
            done_at.append(time.perf_counter())
            losses.append(loss)
        if time.perf_counter() - t_open >= seconds:
            break
    for loss in inflight:
        loss.block_until_ready()
        done_at.append(time.perf_counter())
        losses.append(loss)
    t_close = time.perf_counter()
    window_compiles = {k: compiles.counts[k] - in_window[k]
                       for k in in_window}
    window_s = t_close - t_open
    losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(not math.isfinite(x) for x in losses + first_losses)
    tokens = n_steps * gas * rows * seq
    rate = tokens / window_s / chips if n_steps else 0.0
    if losses:
        say(f"train: {n_steps} steps in {window_s:.3f} s; loss "
            f"{losses[0]:.5f} -> {losses[-1]:.5f}; trajectory",
            " ".join(f"{x:.4f}" for x in losses))
    peak = harness.memory_peak_bytes()

    # ---- free the program, then the reference -------------------------
    del engine, inflight, model
    gc.collect()
    jax.clear_caches()
    gc.collect()
    checks = compare_with_reference(
        make(), sizes, ds_config, mix["check"], first_batches,
        first_losses, got,
        control_cast=(control or {}).get("reference_cast"))
    step_s = np.diff(done_at) if len(done_at) > 2 else np.array([])
    return {
        "checks": checks, "attempted": n_steps + FIRST_STEPS,
        "failed": failed, "memory_peak_bytes": peak, "trace":
        traced.trace if traced is not None else None,
        "end_to_end": {"train_tokens_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "ctx": {"kind": "train", "chips": chips,
                "tokens_per_s_per_chip": rate, "window_s": window_s,
                "step_seconds": step_s.tolist(),
                "rows_per_chip": rows // chips, "seq": seq,
                "memory_peak_bytes": peak,
                "setup_compiles": setup_compiles,
                "window_compiles": window_compiles},
    }


def follow(flat, sizes, ds_config, check, batches, cast=None):
    """The reference's first steps: (losses, per-leaf numbers of
    `adamw_follow`). With `cast`, computed in that lower precision."""
    import jax
    from benchmark.reference import gpt2 as ref
    opt = ds_config["optimizer"]["params"]
    sched = ds_config.get("scheduler", {}).get("params")
    follower = ref.TrainFollower(
        flat, sizes["n_head"], int(check["reference_rows_per_block"]), cast)
    losses, grads = [], []
    for i, batch in enumerate(batches):
        loss, g = follower.loss_and_grads(batch)
        losses.append(loss)
        # earlier steps' gradients wait on the host
        grads.append(g if i == len(batches) - 1 else ref.to_host(g))
        del g
    lrs = [ref.warmup_lr(i, sched["warmup_min_lr"], sched["warmup_max_lr"],
                         sched["warmup_num_steps"]) if sched
           else opt["lr"] for i in range(len(batches))]
    stats = ref.adamw_follow(
        flat, grads, lrs, opt["betas"][0], opt["betas"][1],
        opt.get("eps", 1e-8), opt.get("weight_decay", 0.0),
        clip=float(ds_config.get("gradient_clipping", 0.0)))
    del follower, grads, flat
    gc.collect()
    jax.clear_caches()
    return losses, stats


def compare_with_reference(flat, sizes, ds_config, check, batches, losses,
                           got, control_cast=None):
    """The program's first steps against the reference's. With
    `control_cast` the reference computed in that lower precision
    stands in the program's place."""
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as ref
    t0 = time.perf_counter()
    if control_cast is not None:
        losses, got = follow(flat, sizes, ds_config, check, batches,
                             ref.rounded_to(jnp.dtype(control_cast)))
    ref_losses, want = follow(flat, sizes, ds_config, check, batches)
    del flat
    say(f"reference: {len(batches)} steps followed in "
        f"{time.perf_counter() - t0:.1f} s; losses",
        " ".join(f"{x:.5f}" for x in ref_losses))
    limits = check["limits"]
    checks = []
    for i, (a, b) in enumerate(zip(losses, ref_losses)):
        checks.append({"name": f"loss_abs.step{i + 1}", "value": abs(a - b),
                       "limit": limits["loss_abs"]})
    for key in ("grad_norm", "dp_along_mu"):
        gap, leaf = worst_leaf_gap(got[key], want[key])
        say(f"{key}: worst leaf {leaf}: program {got[key][leaf]:.6g}, "
            f"reference {want[key][leaf]:.6g}")
        checks.append({"name": f"{key}_rel", "value": gap,
                       "limit": limits[f"{key}_rel"]})
    gap, leaf = worst_leaf_gap(got["dp_norm"], want["dp_norm"])
    say(f"dp_norm (not judged): worst leaf {leaf}: program "
        f"{got['dp_norm'][leaf]:.6g}, reference {want['dp_norm'][leaf]:.6g}, "
        f"gap {gap:.4g}")
    for c in checks:
        c["ok"] = bool(math.isfinite(c["value"]) and c["value"] <= c["limit"])
    return checks
