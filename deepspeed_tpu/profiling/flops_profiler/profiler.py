"""FLOPS profiler — XLA HLO cost analysis instead of op monkey-patching.

Counterpart of `deepspeed/profiling/flops_profiler/profiler.py:11` (814
LoC). The reference wraps every `torch.nn.functional` entry point with a
flop-counting closure and installs module hooks; under XLA the compiler
already knows the exact cost of the compiled program —
`jitted.lower(args).compile().cost_analysis()` returns flops / bytes
accessed / transcendentals for the whole fused step, and flax's
`nn.tabulate` supplies the per-module breakdown that the reference builds
from hooks. `get_model_profile` (ref `profiler.py:738`) is the standalone
entry point.
"""

import time

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


def _number_to_string(num, units=None, precision=2):
    if units is None:
        if num >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if num >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if num >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if num >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    return f"{num:.{precision}f} {units}"


def flops_to_string(flops, units=None, precision=2):
    return _number_to_string(flops, units, precision) + "FLOPS"


def params_to_string(params_num, units=None, precision=2):
    return _number_to_string(params_num, units, precision).rstrip() or "0"


def duration_to_string(duration, units=None, precision=2):
    if duration >= 1:
        return f"{duration:.{precision}f} s"
    if duration >= 1e-3:
        return f"{duration * 1e3:.{precision}f} ms"
    return f"{duration * 1e6:.{precision}f} us"


def num_params(params) -> int:
    return int(sum(np.prod(l.shape) for l in
                   jax.tree_util.tree_leaves(params)))


def cost_analysis_of(fn, *args, **kwargs):
    """HLO cost analysis of `fn(*args)`: dict with 'flops',
    'bytes accessed', 'transcendentals' (keys mirror XLA's names)."""
    jitted = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    cost = compiled.cost_analysis() or {}
    # some backends return a list of per-computation dicts
    if isinstance(cost, (list, tuple)):
        merged = {}
        for c in cost:
            for k, v in c.items():
                merged[k] = merged.get(k, 0.0) + v
        cost = merged
    return cost


def device_peak_specs(device=None):
    """(peak_bf16_flops, hbm_GBps) for the current/given device from
    the nominal spec table, or None for a device the table does not
    know — never a default: a utilisation against a made-up peak reads
    like a measurement."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    table = {"v4": (275e12, 1228.0), "v5 lite": (197e12, 819.0),
             "v5e": (197e12, 819.0), "v5p": (459e12, 2765.0),
             "v6": (918e12, 1640.0)}
    for k, specs in table.items():
        if k in kind:
            return specs
    return None


class FlopsProfiler:
    """Profiles one step of a jitted function (ref `profiler.py:11`).

    Usage (engine drives this at `profile_step`, ref `engine.py:803-832`):
        prof = FlopsProfiler(model)
        prof.start_profile()
        cost = prof.profile_jitted(step_fn, *args)   # or measure manually
        prof.stop_profile()
    """

    def __init__(self, model=None, config=None):
        self.model = model
        self.config = config
        self.started = False
        self.total_flops = 0.0
        self.total_bytes = 0.0
        self.total_params = 0
        self.total_duration = 0.0

    def start_profile(self, ignore_list=None):
        self.started = True
        self.total_flops = 0.0
        self.total_bytes = 0.0
        self.total_duration = 0.0

    def stop_profile(self):
        self.started = False

    def end_profile(self):
        self.stop_profile()

    def profile_jitted(self, fn, *args, measure_time=True, **kwargs):
        cost = cost_analysis_of(fn, *args, **kwargs)
        self.total_flops = float(cost.get("flops", 0.0))
        self.total_bytes = float(cost.get("bytes accessed", 0.0))
        if measure_time:
            jitted = fn if isinstance(fn, jax.stages.Wrapped) else \
                jax.jit(fn)
            out = jitted(*args, **kwargs)       # warm (cache hit)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = jitted(*args, **kwargs)
            jax.block_until_ready(out)
            self.total_duration = time.perf_counter() - t0
        return cost

    # -- accessors (ref profiler.py naming) -----------------------------
    def get_total_flops(self, as_string=False):
        return flops_to_string(self.total_flops) if as_string \
            else self.total_flops

    def get_total_params(self, as_string=False):
        return params_to_string(self.total_params) if as_string \
            else self.total_params

    def get_total_duration(self, as_string=False):
        return duration_to_string(self.total_duration) if as_string \
            else self.total_duration

    def print_model_profile(self, profile_step=1, module_depth=-1,
                            top_modules=3, detailed=True):
        tflops = self.total_flops / self.total_duration / 1e12 \
            if self.total_duration else 0.0
        logger.info(
            f"\n-------------------------- DeepSpeed Flops Profiler "
            f"--------------------------\n"
            f"Profile at step {profile_step}:\n"
            f"  params:            {params_to_string(self.total_params)}\n"
            f"  fwd+bwd+step flops:{flops_to_string(self.total_flops)}\n"
            f"  HBM bytes:         {_number_to_string(self.total_bytes)}B\n"
            f"  step latency:      "
            f"{duration_to_string(self.total_duration)}\n"
            f"  achieved:          {tflops:.2f} TFLOPS")

    def print_model_aggregated_profile(self, module_depth=-1,
                                       top_modules=3):
        self.print_model_profile(module_depth=module_depth,
                                 top_modules=top_modules)


def get_model_profile(model=None,
                      input_shape=None,
                      args=None,
                      kwargs=None,
                      print_profile=True,
                      detailed=True,
                      module_depth=-1,
                      top_modules=3,
                      warm_up=1,
                      as_string=True,
                      ignore_modules=None,
                      fn=None,
                      params=None):
    """Standalone profile (ref `profiler.py:738`): returns (flops,
    macs, params). Accepts either a callable `fn(*args)` (jittable) or a
    flax `model` + example `args`.

    With a flax model, the per-module table comes from `nn.tabulate`
    (the hook-built tree of the reference)."""
    kwargs = kwargs or {}
    table = None
    if fn is None:
        assert model is not None and (args is not None or
                                      input_shape is not None)
        if args is None:
            args = (np.zeros(input_shape, np.float32),)
        variables = model.init(jax.random.PRNGKey(0), *args, **kwargs)

        def fn(*a):
            return model.apply(variables, *a, **kwargs)
        params = variables
        try:
            import flax.linen as nn
            table = nn.tabulate(
                model, jax.random.PRNGKey(0),
                compute_flops=True, compute_vjp_flops=detailed,
                depth=None if module_depth == -1 else module_depth)(
                    *args, **kwargs)
        except Exception:
            logger.warning("nn.tabulate breakdown unavailable",
                           exc_info=True)
    assert args is not None

    prof = FlopsProfiler(model)
    prof.total_params = num_params(params) if params is not None else 0
    prof.start_profile()
    prof.profile_jitted(fn, *args)
    prof.stop_profile()

    if print_profile:
        prof.print_model_profile(module_depth=module_depth,
                                 top_modules=top_modules,
                                 detailed=detailed)
        if table is not None and detailed:
            logger.info("\n" + table)

    flops = prof.get_total_flops(as_string)
    macs = prof.total_flops / 2
    if as_string:
        macs = _number_to_string(macs) + "MACs"
    n = prof.get_total_params(as_string)
    return flops, macs, n
