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

import re
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


# ----------------------------------------------------------------------
# per-fusion breakdown: where inside the compiled step the time goes
# ----------------------------------------------------------------------
# `compiled.cost_analysis()` is one aggregate number for the whole
# program; ranking the individual FUSIONS is what tells you which part
# of the step to fix. The optimized HLO text lists every fusion /
# custom-call (Pallas kernel) / bare dot with its operand and result
# shapes, so each one gets a roofline time estimate
# max(flops / peak_flops, bytes / hbm_bw) and the table below is the
# per-fusion time breakdown the bench publishes (top-3 sinks).

_SHAPE_RE = re.compile(r"(pred|[fbsu](?:f8\w*|\d+)|f8\w+)\[([\d,]*)\]")
_ELEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8": 1, "bf16": 2,
               "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
               "u32": 4, "f64": 8, "s64": 8, "u64": 8}
# VPU transcendentals: roughly an order of magnitude costlier than a
# mul/add lane op; counted so exp/erf-heavy elementwise fusions rank
# above same-byte-count copy fusions
_TRANSCENDENTAL_RE = re.compile(
    r"\b(exponential|exponential-minus-one|log|log-plus-one|tanh|erf|"
    r"rsqrt|sqrt|power|sine|cosine|atan2|logistic)\(")


def _shape_bytes(fragment):
    """Total bytes of every shape literal in an HLO text fragment."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(fragment):
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        key = dtype if dtype in _ELEM_BYTES else dtype[:2]
        total += elems * _ELEM_BYTES.get(key, 4)
    return total


def _first_shape_elems(fragment):
    m = _SHAPE_RE.search(fragment)
    if not m:
        return 0
    elems = 1
    if m.group(2):
        for d in m.group(2).split(","):
            elems *= int(d)
    return elems


def _dot_flops(line):
    """2 * prod(result dims) * prod(lhs contracting dims) for one
    `... = <shape> dot(<lhs>, <rhs>), lhs_contracting_dims={...}` line."""
    head, _, tail = line.partition(" dot(")
    out_elems = _first_shape_elems(head.split("=", 1)[-1])
    lhs = _SHAPE_RE.search(tail)
    if not lhs or not out_elems:
        return 0
    lhs_dims = [int(d) for d in lhs.group(2).split(",")] if lhs.group(2) \
        else []
    mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    contract = 1
    if mc and mc.group(1):
        for d in mc.group(1).split(","):
            contract *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
    return 2 * out_elems * contract


def _parse_hlo_computations(text):
    """HLO module text -> {comp_name: [instruction lines]}."""
    comps = {}
    cur = None
    for raw in text.splitlines():
        line = raw.strip()
        if cur is None:
            # the param list nests parens for tuple-typed params (every
            # while body: `(arg.1: (s32[], f32[64,64]))`) — a lazy group
            # that can grow past inner `)` is required or those
            # computations never parse and their rows are dropped
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*?\))?"
                         r"\s*->.*\{$", line)
            if m:
                cur = m.group(1)
                comps[cur] = []
        elif line == "}" or line.startswith("} "):
            cur = None
        elif line and not line.startswith("//"):
            comps[cur].append(line)
    return comps


def _comp_flops_transcendentals(lines):
    flops = 0
    trans = 0
    for line in lines:
        if " dot(" in line:
            flops += _dot_flops(line)
        m = _TRANSCENDENTAL_RE.search(line)
        if m:
            trans += _first_shape_elems(line.split("=", 1)[-1])
    return flops, trans


# a callee list is EITHER braced (branch_computations={%a, %b}) or a
# single unbraced name (calls=%f, body=%b, condition=%c) — an unbraced
# match must stop at the name so `condition=%c, body=%b` yields two
# matches instead of one capture that swallows the literal ", body"
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                       r"(?:\{([^}]*)\}|%?([\w.\-]+))")
_TRIP_RE = re.compile(r'known_trip_count[\\"{:\s]+n[\\"\s:]+(\d+)')
_PARAM_DEF_RE = re.compile(r"^(?:ROOT )?%([\w.\-]+) = [^ ]+ parameter\(\d+\)")


def _sliced_fusion_bytes(body):
    """Byte estimate for a fusion that slices its operands, or None when
    the call-site estimate (full operand + result shapes) is already
    right.  XLA's scan lowering emits loop fusions whose ROOT is a
    dynamic-update-slice of a carry parameter (aliased in place) and
    whose reads go through dynamic-slice — per call they touch ONE
    layer's slice, so charging the full stacked buffer on every trip
    inflates their bytes by ~trip_count× and a near-free carry update
    tops the sink table above every real matmul."""
    root = next((l for l in body if l.startswith("ROOT ")), "")
    root_dus = " dynamic-update-slice(" in root
    if not root_dus and not any(" dynamic-slice(" in l for l in body):
        return None
    param_full = {}
    for line in body:
        m = _PARAM_DEF_RE.match(line)
        if m:
            param_full[m.group(1)] = _shape_bytes(
                line.split("=", 1)[1].split(" parameter", 1)[0])
    if not param_full:
        return None
    sliced_reads = {}      # param -> slice bytes actually read
    whole_use = set()      # params touched any other way: full charge
    carries = set()        # DUS first operands: in-place, no read
    writes = 0
    for line in body:
        if _PARAM_DEF_RE.match(line):
            continue
        rhs = line.split("=", 1)[-1]
        opm = re.match(r"\s*\S+\s+([\w\-]+)\(", rhs)
        op = opm.group(1) if opm else ""
        # operand list = after the op's "(", before any metadata (whose
        # op_name strings contain parens of their own)
        tail = rhs.split("(", 1)[1] if "(" in rhs else rhs
        names = re.findall(r"%([\w.\-]+)", tail.split(", metadata=", 1)[0])
        for name in set(names) & set(param_full):
            if op == "dynamic-update-slice" and names and \
                    names[0] == name:
                carries.add(name)
                # index operands may reuse the carry name; any other
                # position is a real full read
                if names.count(name) > 1:
                    whole_use.add(name)
            elif op == "dynamic-slice" and names and names[0] == name:
                # read = the slice RESULT shape (first shape on the rhs)
                sliced_reads[name] = sliced_reads.get(name, 0) + \
                    _shape_bytes(rhs.split(" dynamic-slice(", 1)[0])
            else:
                whole_use.add(name)
        if op == "dynamic-update-slice":
            shapes = _SHAPE_RE.findall(
                tail.split(", metadata=", 1)[0])
            if len(shapes) >= 2:
                dtype, dims = shapes[1]
                elems = 1
                for d in (dims.split(",") if dims else []):
                    elems *= int(d)
                key = dtype if dtype in _ELEM_BYTES else dtype[:2]
                writes += elems * _ELEM_BYTES.get(key, 4)
    reads = 0
    for name, full in param_full.items():
        if name in whole_use:
            reads += full
        elif name in sliced_reads:
            reads += min(sliced_reads[name], full)
        elif name in carries:
            reads += 0
        else:
            reads += full
    if not root_dus:
        writes = _shape_bytes(root.split("=", 1)[-1].split("(", 1)[0])
    return reads + writes


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


# Pallas kernels appear in optimized HLO as custom-calls; the kernel
# identity lives in the op_name metadata (the jaxpr scope path, which
# includes any jax.named_scope the op wrapper opened and the
# pallas_call frame) and, failing that, the custom_call_target.
_PALLAS_NAME_RE = re.compile(r"pallas_call\[?[^\]\"]*?name=([\w./\-]+)")


def _custom_call_label(line):
    """Best-effort kernel label for a custom-call HLO line: the Pallas
    kernel name out of op_name metadata (`pallas_call[... name=...]`,
    or the innermost non-pallas scope segment — e.g. the
    jax.named_scope the fused-ops wrappers open), else the
    custom_call_target."""
    mo = re.search(r'op_name="([^"]+)"', line)
    if mo:
        op = mo.group(1)
        mk = _PALLAS_NAME_RE.search(op)
        if mk:
            return mk.group(1)
        if "pallas_call" in op:
            segs = [s for s in op.split("/")
                    if s and "pallas_call" not in s
                    and not s.startswith(("jit(", "jvp(", "transpose("))]
            if segs:
                return segs[-1]
    mt = re.search(r'custom_call_target="([^"]+)"', line)
    return mt.group(1) if mt else None


def per_fusion_costs(fn, *args, peak_flops=None, hbm_gbps=None, **kwargs):
    """Roofline time breakdown of `fn(*args)`'s optimized HLO, one row
    per top-level fusion / custom-call (Pallas kernel) / bare dot.

    Returns rows sorted by estimated time, each
    {name, op, kind, kernel, flops, bytes, transcendentals, calls,
    est_us, time_pct}: `op` is the semantic op_name metadata
    (model-layer path), `kernel` the resolved kernel label for
    custom-calls (Pallas kernel name / named_scope / call target — so
    the fused epilogue and flash kernels are attributable instead of
    an opaque "custom-call"), `calls` the executed multiplicity
    (propagated through call/while nesting; a while whose trip count
    the compiler did not record counts as 1 and the row says so via
    calls=1). est_us = max(flops/peak, bytes/bw [, transcendental
    time]) — an ESTIMATE for ranking sinks, not a measurement;
    custom-calls have no visible flops, so theirs is bytes-only (a
    lower bound).

    peak_flops/hbm_gbps default to the current device's nominal specs
    (v4/v5e/v5p/v6 table); on a device the table does not know (the
    CPU included) both must be passed."""
    jitted = fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn)
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return per_fusion_costs_from_text(text, peak_flops=peak_flops,
                                      hbm_gbps=hbm_gbps)


def per_fusion_costs_from_text(text, peak_flops=None, hbm_gbps=None):
    """`per_fusion_costs` off already-obtained optimized HLO module
    text (also the unit-testable seam for the parsing/labeling
    logic)."""
    if peak_flops is None or hbm_gbps is None:
        specs = device_peak_specs()
        if specs is None:
            raise ValueError(
                "no nominal peak is known for device_kind="
                f"{jax.devices()[0].device_kind!r}; pass peak_flops "
                "and hbm_gbps")
        peak_flops = peak_flops or specs[0]
        hbm_gbps = hbm_gbps or specs[1]
    comps = _parse_hlo_computations(text)

    # executed multiplicity per computation (entry = the one whose name
    # the module repeats in `ENTRY`; approximated as the computation
    # nobody calls)
    called_by_fusion = set()
    callees = {}
    for name, lines in comps.items():
        for line in lines:
            targets = []
            for m in _CALLS_RE.finditer(line):
                names = m.group(1) if m.group(1) is not None else m.group(2)
                targets += [t.strip().lstrip("%")
                            for t in names.split(",") if t.strip()]
            if not targets:
                continue
            mult = 1
            if " while(" in line:
                t = _TRIP_RE.search(line)
                mult = int(t.group(1)) if t else 1
            callees.setdefault(name, []).append((targets, mult))
            if " fusion(" in line:
                called_by_fusion.update(targets)
    all_called = {t for calls in callees.values()
                  for targets, _ in calls for t in targets}
    mults = {name: (1 if name not in all_called else 0)
             for name in comps}
    # propagate in a few passes (call graphs are shallow; cycles don't
    # occur in HLO)
    for _ in range(16):
        changed = False
        for name, calls in callees.items():
            for targets, mult in calls:
                for t in targets:
                    if t in mults and mults[name]:
                        new = mults[name] * mult
                        if new > mults[t]:
                            mults[t] = new
                            changed = True
        if not changed:
            break

    rows = []
    for name, lines in comps.items():
        if name in called_by_fusion or not mults.get(name):
            continue
        for line in lines:
            kind = None
            if " fusion(" in line:
                kind = "fusion"
            elif " custom-call(" in line:
                kind = "custom-call"
            elif " dot(" in line:
                kind = "dot"
            elif " convolution(" in line:
                kind = "convolution"
            if kind is None:
                continue
            iname = line.split("=", 1)[0].strip()
            if iname.startswith("ROOT "):
                iname = iname[5:]
            iname = iname.lstrip("%")
            args_part = line.split("(", 1)[-1].split("), ")[0]
            out_part = line.split("=", 1)[-1].split("(", 1)[0]
            nbytes = _shape_bytes(args_part) + _shape_bytes(out_part)
            flops, trans = 0, 0
            if kind == "fusion":
                mcall = re.search(r"calls=%?([\w.\-]+)", line)
                if mcall and mcall.group(1) in comps:
                    flops, trans = _comp_flops_transcendentals(
                        comps[mcall.group(1)])
                    sliced = _sliced_fusion_bytes(comps[mcall.group(1)])
                    if sliced is not None:
                        nbytes = sliced
            elif kind in ("dot", "convolution"):
                flops = _dot_flops(line) if kind == "dot" else 0
            mop = re.search(r'op_name="([^"]+)"', line)
            kernel = _custom_call_label(line) if kind == "custom-call" \
                else None
            calls = mults.get(name, 1)
            est_s = max(flops / peak_flops,
                        nbytes / (hbm_gbps * 1e9),
                        # ~16 transcendental results per lane-cycle at
                        # ~1 GHz-ish VPU throughput: crude, but ranks
                        # erf/exp chains above pure copies
                        trans / (peak_flops / 16.0)) * calls
            rows.append({
                "name": iname, "op": mop.group(1) if mop else "",
                "kind": kind, "kernel": kernel,
                "flops": int(flops * calls),
                "bytes": int(nbytes * calls),
                "transcendentals": int(trans * calls),
                "calls": calls, "est_us": est_s * 1e6})
    total = sum(r["est_us"] for r in rows) or 1.0
    for r in rows:
        r["time_pct"] = round(100.0 * r["est_us"] / total, 2)
        r["est_us"] = round(r["est_us"], 2)
    rows.sort(key=lambda r: -r["est_us"])
    return rows


def top_fusion_sinks(fn, *args, top=3, **kwargs):
    """Compact top-N per-fusion sink table (bench extras): list of
    {op, kind, est_us, time_pct, flops, bytes, calls} rows (+ `kernel`
    for custom-calls — the Pallas kernel label, which also becomes the
    `op` fallback so a Pallas row is never an opaque "custom-call")."""
    rows = per_fusion_costs(fn, *args, **kwargs)
    out = []
    for r in rows[:top]:
        row = {"op": (r["op"] or r.get("kernel") or r["name"])[-120:],
               "kind": r["kind"],
               "est_us": r["est_us"], "time_pct": r["time_pct"],
               "flops": r["flops"], "bytes": r["bytes"],
               "calls": r["calls"]}
        if r.get("kernel"):
            row["kernel"] = r["kernel"]
        out.append(row)
    return out


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
