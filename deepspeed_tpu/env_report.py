"""`ds_report` — environment and native-op compatibility report.

Counterpart of `deepspeed/env_report.py:23-105`: per-op
compatible/installed matrix (our ops are the C++ builders in op_builder/
plus the trace-time Pallas kernels), framework versions, and device
inventory. Run as `python -m deepspeed_tpu.env_report`."""

import os
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
SUCCESS = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
FAIL = f"{RED}[FAIL]{END}"
INFO = "[INFO]"

COLUMNS = ["op name", "installed", "compatible"]


def op_report():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from op_builder import ALL_OPS

    max_dots = 23
    print("-" * 64)
    print("DeepSpeed-TPU C++ op report")
    print("-" * 64)
    print("native ops compile with g++ on first use (JIT), cached by "
          "source hash")
    print("-" * 64)
    print("op name", "." * max_dots, "installed", "..", "compatible")
    print("-" * 64)
    for name, builder_cls in ALL_OPS.items():
        builder = builder_cls()
        installed = SUCCESS if builder.installed() else "[NO]"
        compatible = SUCCESS if builder.is_compatible() else FAIL
        dots = "." * (max_dots - len(name))
        print(name, dots, installed, "..", compatible)
    print("-" * 64)
    print("trace-time kernels (no prebuild needed):")
    print("  flash_attention ......... Pallas (TPU)")
    print("  block_sparse_attention .. Pallas masked-flash")
    print("  fused train step ........ XLA fusion of loss/grad/update")
    print("-" * 64)


def debug_report():
    import jax
    import jaxlib

    report = [("jax version", jax.__version__),
              ("jaxlib version", jaxlib.__version__)]
    try:
        import flax
        report.append(("flax version", flax.__version__))
    except ImportError:
        pass
    try:
        import optax
        report.append(("optax version", optax.__version__))
    except ImportError:
        pass
    try:
        devices = jax.devices()
        report.append(("platform", devices[0].platform))
        report.append(("backend", jax.default_backend()))
        report.append(("device count", len(devices)))
        report.append(("device kind", devices[0].device_kind))
        from deepspeed_tpu.utils.timer import device_memory_stats
        mem = device_memory_stats()
        if mem["device_count"]:
            gib = 1024 ** 3
            report.append((
                "device memory",
                f"{mem['in_use_bytes'] / gib:.2f} GiB in use, "
                f"{mem['peak_bytes'] / gib:.2f} GiB peak "
                f"({mem['device_count']} local devices)"))
        else:
            report.append(("device memory",
                           "allocator stats unavailable on this backend"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        report.append(("devices", f"unavailable: {e}"))
    import deepspeed_tpu
    report.append(("deepspeed_tpu version", deepspeed_tpu.__version__))
    report.append(("deepspeed_tpu install path",
                   os.path.dirname(deepspeed_tpu.__file__)))

    print("DeepSpeed-TPU general environment info:")
    for name, value in report:
        print(f"{name} {'.' * (28 - len(name))} {value}")


def feature_report():
    """Runtime feature availability: monitor sinks, native CPU-Adam,
    Pallas flash attention."""
    rows = []
    try:
        from deepspeed_tpu.monitor.sinks import VALID_SINKS
        rows.append(("monitor sinks",
                     f"{SUCCESS} {', '.join(VALID_SINKS)} "
                     "(dependency-free: no torch/tensorflow)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("monitor sinks", f"{FAIL} {e}"))
    try:
        from op_builder import CPUAdamBuilder
        native = CPUAdamBuilder().is_compatible()
        rows.append(("native CPU-Adam",
                     SUCCESS if native else
                     f"{WARNING} numpy fallback (no C++ toolchain)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("native CPU-Adam", f"{WARNING} {e}"))
    try:
        import jax
        from jax.experimental import pallas  # noqa: F401
        on_tpu = jax.devices()[0].platform == "tpu"
        rows.append(("Pallas flash attention",
                     SUCCESS if on_tpu else
                     f"{WARNING} no TPU attached (the Pallas "
                     "interpreter checks kernel logic in tests; it "
                     "is not a way to run the model)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("Pallas flash attention", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.ops.transformer.fused_ops import \
            fused_ops_available
        ok, mode = fused_ops_available()
        rows.append(("Pallas fused ops",
                     f"{SUCCESS} {mode} (bias+residual+LayerNorm, "
                     "bias+GeLU)" if ok else f"{FAIL} {mode}"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("Pallas fused ops", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.ops.transformer.quantized_matmul import \
            resolve_quantized_compute
        active = resolve_quantized_compute("auto")
        rows.append((
            "quantized compute",
            f"{SUCCESS} int8 GEMM epilogue family "
            f"({'Pallas MXU path' if active else 'XLA fallback'}; "
            "quantized_compute block; docs/quantized-compute.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("quantized compute", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.ops import autotune as _autotune
        rows.append((
            "kernel autotuner",
            f"{SUCCESS} block-size table at "
            f"{_autotune.table_path()} (autotune block; "
            "docs/quantized-compute.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("kernel autotuner", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.monitor.trace_export import TraceExporter  # noqa: F401
        rows.append(("trace export",
                     f"{SUCCESS} Perfetto/Chrome trace events "
                     "(monitor.trace + bin/ds_trace)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("trace export", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.monitor.flight import FlightRecorder  # noqa: F401
        rows.append(("flight recorder",
                     f"{SUCCESS} crash/stall dumps "
                     "(monitor.flight, flight_<ts>.json)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("flight recorder", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.monitor import numerics  # noqa: F401
        rows.append(("numerics health",
                     f"{SUCCESS} device-side per-layer accumulators "
                     "(monitor.numerics)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("numerics health", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.monitor.memory import MemoryLedger  # noqa: F401,E501
        rows.append(("memory ledger",
                     f"{SUCCESS} HBM/host byte attribution + OOM "
                     "forensics (monitor.memory, default on)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("memory ledger", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.runtime.zero.stage3 import \
            Zero3GatherScheduler  # noqa: F401
        rows.append((
            "ZeRO-3 overlap",
            f"{SUCCESS} layer-granular gather prefetch + "
            "reduce-scatter grads (zero_optimization.stage3; GPT-2/"
            "BERT stacks + sequential pipe chains)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("ZeRO-3 overlap", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.elasticity.runtime import \
            ElasticSupervisor  # noqa: F401
        rows.append((
            "elastic runtime",
            f"{SUCCESS} fault-injecting supervisor: mesh re-form + "
            "ZeRO re-plan + resharded resume (elasticity.runtime; "
            "docs/elasticity.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("elastic runtime", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.inference import InferenceEngine  # noqa: F401
        rows.append((
            "inference engine",
            f"{SUCCESS} AOT prefill+decode, paged KV cache, "
            "continuous batching, int8 weights (inference block; "
            "docs/inference.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("inference engine", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.monitor.serving import ServingTracker  # noqa: F401,E501
        rows.append((
            "serving observability",
            f"{SUCCESS} per-request lifecycle traces, SLO "
            "histograms, serving forensics (inference.observability; "
            "ds_trace summary --serving)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("serving observability", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.inference.speculative import build_verify_step  # noqa: F401,E501
        rows.append((
            "speculative decoding",
            f"{SUCCESS} draft propose + batched verify, lossless "
            "acceptance sampling, paged-KV rollback, adaptive k "
            "(inference.speculative; docs/inference.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("speculative decoding", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.moe import MoEMLP  # noqa: F401
        rows.append((
            "mixture of experts",
            f"{SUCCESS} expert-parallel top-k routing, all-to-all "
            "dispatch, grouped-GEMM FFNs composed with ZeRO-3 + "
            "elasticity (moe block + mesh expert axis; docs/moe.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("mixture of experts", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.ops import overlap as _overlap
        rows.append((
            "comm/compute overlap",
            f"{SUCCESS} async-collective scheduling at "
            f"{', '.join(_overlap.SITES)} (overlap block; "
            "docs/overlap.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("comm/compute overlap", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.moe.fused_dispatch import fused_dispatch  # noqa: F401,E501
        rows.append((
            "fused MoE dispatch",
            f"{SUCCESS} Pallas gather-scatter dispatch/combine "
            "kernels over capacity-indexed rows (moe.fused_dispatch; "
            "docs/overlap.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("fused MoE dispatch", f"{FAIL} {e}"))
    try:
        from deepspeed_tpu.analysis.rules import ALL_RULES
        from deepspeed_tpu.analysis import baseline as _bl
        bl_path = _bl.default_path(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        try:
            n_baselined = len(_bl.load(bl_path))
        except (ValueError, OSError):
            n_baselined = 0
        rows.append((
            "static analysis",
            f"{SUCCESS} ds_lint: {len(ALL_RULES)} rules "
            f"({', '.join(ALL_RULES)}), {n_baselined} baselined "
            "finding(s) (bin/ds_lint; docs/static-analysis.md)"))
    except Exception as e:  # ds-lint: allow[BROADEXC] environment probe: the failure text IS the report row
        rows.append(("static analysis", f"{FAIL} {e}"))

    print("-" * 64)
    print("runtime feature report")
    print("-" * 64)
    for name, value in rows:
        print(f"{name} {'.' * (28 - len(name))} {value}")
    print("-" * 64)


def main():
    op_report()
    feature_report()
    debug_report()


cli_main = main

if __name__ == "__main__":
    main()
