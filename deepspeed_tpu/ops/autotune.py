"""Block-size autotuner for the repo's Pallas kernels.

Every Pallas kernel in the tree (flash attention, packed flash, the
fused epilogue family, the quantized GEMM) hand-picks its grid/block
shapes from one sweep on one chip generation (`_DEFAULT_BLOCK = 1024`
in flash_attention.py was swept on v5e at the flagship shape).  Those
constants are wrong the moment the backend, dtype, or shape class
changes — the autotuner replaces them with a measured, persisted
table:

  * `search(...)` enumerates grid/block candidates per
    (kernel, backend, dtype, shape-class), measures each with the
    same timing discipline every time (warmup, interleaved best-of-N
    windows so load drift hits every candidate equally), and keeps the
    winner ONLY if it beats the hand-picked default — the table is
    never-slower by construction.
  * The winning table persists as a versioned JSON next to the jax
    compile cache (`autotune.table_path` overrides).  Each entry
    records the SHA-256 of the defining kernel module's source; a
    kernel edit invalidates its entries on load (they fall back to
    defaults with one warning — no silent reuse of measurements taken
    on different kernel code).
  * `lookup(...)` is consulted transparently at trace time by the
    kernel entry points (flash `_normalize_flash_args`, the fused-ops
    row-block launchers, the quantized GEMM) whenever the caller did
    not pass explicit block sizes.  A corrupt or stale table degrades
    to the defaults with a single warning, never a crash.

Monitor events: `autotune_search` per completed search and
`autotune_hit` once per (kernel, shape-class) the first time a traced
entry point picks up a tuned shape (attach a monitor via
`configure(monitor=...)`; the engine does this when the monitor is
enabled).  Both are rows in the EVTSCHEMA table (docs/monitoring.md).

Lookups are pure host-side dict reads after one lazy table load — no
device sync ever happens on this path (the kernel entry points are
declared HOTSYNC hot entrypoints).
"""

import hashlib
import json
import os
import threading
import time

from deepspeed_tpu.utils.logging import logger

# v2: adds the collective-schedule family (overlap on/off, issue
# distance, dispatch granularity per site/mesh/payload class) and the
# fused MoE dispatch kernel family. v1 tables are ignored with one
# warning and repopulate on the next search.
TABLE_VERSION = 2
TABLE_BASENAME = f"autotune_table_v{TABLE_VERSION}.json"

# kernel family -> defining module (its source hash invalidates the
# family's entries). Import lazily: this module must stay importable
# without pulling every kernel module in.
KERNEL_MODULES = {
    "flash_fwd": "deepspeed_tpu.ops.transformer.flash_attention",
    "flash_fwd_packed": "deepspeed_tpu.ops.transformer.flash_attention",
    "fused_ln": "deepspeed_tpu.ops.transformer.fused_ops",
    "fused_gelu": "deepspeed_tpu.ops.transformer.fused_ops",
    "quantized_matmul":
        "deepspeed_tpu.ops.transformer.quantized_matmul",
    "moe_dispatch": "deepspeed_tpu.moe.fused_dispatch",
    # collective-schedule entries describe the overlap runtime's
    # behavior, so its module source is the invalidation key
    "collective_schedule": "deepspeed_tpu.ops.overlap",
}

_lock = threading.Lock()
_state = {
    "enabled": True,
    "path": None,          # explicit table path (configure/config key)
    "table": None,         # loaded entries dict
    "loaded_from": None,   # path the current table came from
    "monitor": None,
    "dirty_warned": set(),  # one warning per failure class
    "hit_emitted": set(),   # one autotune_hit event per key
}


def configure(enabled=None, table_path=None, monitor=None):
    """Engine/caller wiring: toggle lookups, point at a table file, and
    attach a monitor for `autotune_search`/`autotune_hit` events
    (monitor=False detaches — a later engine without telemetry must
    not leave events flowing to a closed monitor). Changing the path
    drops the in-memory table so the next lookup reloads."""
    with _lock:
        if enabled is not None:
            _state["enabled"] = bool(enabled)
        if table_path is not None:
            path = table_path or None
            if path != _state["path"]:
                _state["path"] = path
                _state["table"] = None
                _state["loaded_from"] = None
                _state["hit_emitted"] = set()
        if monitor is False:
            _state["monitor"] = None
        elif monitor is not None:
            _state["monitor"] = monitor


def reset(drop_monitor=True):
    """Test hook: forget the loaded table, warnings, and config."""
    with _lock:
        _state["enabled"] = True
        _state["path"] = None
        _state["table"] = None
        _state["loaded_from"] = None
        _state["dirty_warned"] = set()
        _state["hit_emitted"] = set()
        if drop_monitor:
            _state["monitor"] = None


def table_path():
    """Resolution order: configure()/autotune.table_path config key >
    DS_TPU_AUTOTUNE_TABLE env > beside the jax compile cache
    (utils/compile_cache.py)."""
    if _state["path"]:
        return _state["path"]
    env = os.environ.get("DS_TPU_AUTOTUNE_TABLE")
    if env:
        return env
    from deepspeed_tpu.utils.compile_cache import compile_cache_dir
    return os.path.join(compile_cache_dir(), TABLE_BASENAME)


def _backend():
    import jax
    return jax.default_backend()


def kernel_source_hash(kernel):
    """SHA-256 of the kernel family's defining module source — the
    cache-invalidation key. Unknown families hash their own name (so
    tests can register synthetic families)."""
    import importlib
    mod_name = KERNEL_MODULES.get(kernel)
    if mod_name is None:
        return hashlib.sha256(kernel.encode()).hexdigest()
    try:
        import inspect
        mod = importlib.import_module(mod_name)
        src = inspect.getsource(mod)
    except Exception:  # ds-lint: allow[BROADEXC] unreadable source (zipapp, stripped install): hash the module name — entries then never validate stale
        src = mod_name
    return hashlib.sha256(src.encode()).hexdigest()


def pow2_bucket(n):
    """Shape-class bucketing: next power of two >= n (floor 1), so one
    measured entry covers the whole bucket instead of every exact row
    count re-searching."""
    n = max(int(n), 1)
    b = 1
    while b < n:
        b *= 2
    return b


def _dtype_str(dtype):
    """Canonical dtype spelling for keys: np.dtype collapses jnp type
    objects, np dtypes and strings onto one name ("float32",
    "bfloat16", ...)."""
    import numpy as _np
    try:
        return str(_np.dtype(dtype))
    except TypeError:
        return str(dtype)


def entry_key(kernel, shape_class, dtype, backend=None):
    backend = backend or _backend()
    return f"{kernel}|{backend}|{_dtype_str(dtype)}|{shape_class}"


def _warn_once(tag, msg):
    if tag in _state["dirty_warned"]:
        return
    _state["dirty_warned"] = _state["dirty_warned"] | {tag}
    logger.warning(msg)


def _load_table_locked():
    """Load + validate the JSON table (call with _lock held). Any
    failure — unreadable file, bad JSON, wrong version, non-dict
    schema — degrades to an empty table with ONE warning."""
    if _state["table"] is not None:
        return _state["table"]
    path = table_path()
    entries = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict) or \
                    not isinstance(doc.get("entries"), dict):
                raise ValueError("not an autotune table document")
            if doc.get("version") != TABLE_VERSION:
                _warn_once(
                    "version",
                    f"autotune table {path} has version "
                    f"{doc.get('version')!r} != {TABLE_VERSION}; "
                    "ignoring it (kernels use default block sizes "
                    "until a new search repopulates it)")
            else:
                entries = doc["entries"]
        except Exception as e:  # ds-lint: allow[BROADEXC] corrupt table must degrade to defaults with one warning, never crash a training trace
            _warn_once(
                "corrupt",
                f"autotune table {path} is unreadable "
                f"({type(e).__name__}: {e}); kernels use default "
                "block sizes")
            entries = {}
    _state["table"] = entries
    _state["loaded_from"] = path
    return entries


def lookup(kernel, shape_class, dtype, backend=None):
    """Tuned params dict for (kernel, backend, dtype, shape_class), or
    None (no entry / autotune disabled / stale source hash). Consulted
    at trace time by the kernel entry points; one `autotune_hit` event
    per key when a monitor is attached."""
    if not _state["enabled"]:
        return None
    key = entry_key(kernel, shape_class, dtype, backend)
    with _lock:
        entries = _load_table_locked()
        entry = entries.get(key)
        if entry is None:
            return None
        if entry.get("source_hash") != kernel_source_hash(kernel):
            # the kernel changed since the measurement: measurements on
            # old kernel code must not silently steer the new one
            del entries[key]
            _warn_once(
                f"stale:{kernel}",
                f"autotune entries for kernel {kernel!r} were measured "
                "on different kernel source; using default block sizes "
                "until a new search runs")
            return None
        params = dict(entry.get("params") or {})
        first_hit = key not in _state["hit_emitted"]
        if first_hit:
            _state["hit_emitted"] = _state["hit_emitted"] | {key}
        mon = _state["monitor"]
    if first_hit and mon is not None:
        mon.event("autotune_hit", kernel=kernel,
                  shape_class=shape_class, dtype=_dtype_str(dtype),
                  backend=backend or _backend(), params=params)
    return params or None


def record(kernel, shape_class, dtype, params, best_us, default_us,
           candidates_tried, backend=None, persist=True):
    """Store a search result and (optionally) persist the table
    atomically (tmp + os.replace, no partial table ever visible)."""
    key = entry_key(kernel, shape_class, dtype, backend)
    entry = {
        "params": dict(params),
        "best_us": round(float(best_us), 3),
        "default_us": round(float(default_us), 3),
        "candidates_tried": int(candidates_tried),
        "source_hash": kernel_source_hash(kernel),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with _lock:
        entries = _load_table_locked()
        entries[key] = entry
        path = _state["loaded_from"] or table_path()
        doc = {"version": TABLE_VERSION, "entries": dict(entries)}
    if persist:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    return entry


def measure_callable(fn, warmup=2, reps=3, inner=1):
    """Bench-harness timing for one candidate: warm the compile +
    donated-buffer layouts, then best-of-`reps` windows of `inner`
    calls (jax.block_until_ready on the result). Returns seconds per
    call."""
    import jax
    r = None
    for _ in range(max(warmup, 1)):
        r = fn()
    jax.block_until_ready(r)
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        for _ in range(inner):
            r = fn()
        jax.block_until_ready(r)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def search(kernel, shape_class, dtype, candidates, default_params,
           measure=None, build=None, warmup=2, reps=3, backend=None,
           persist=True):
    """Enumerate `candidates` (list of params dicts; `default_params`
    is measured too and acts as the floor), measure each, keep the
    winner ONLY if it beats the default — so applying the table is
    never slower than the hand-picked shapes.

    Measurement comes either from `measure(params) -> seconds` or from
    `build(params) -> zero-arg jitted callable` timed by
    `measure_callable`. Candidate rounds INTERLEAVE (round-robin over
    candidates, best-of-`reps` per candidate) so machine-load drift
    lands on every candidate equally — an interleaved
    A/B discipline.

    Returns {params, best_us, default_us, speedup_vs_default,
    candidates_tried}."""
    if measure is None and build is None:
        raise ValueError("search() needs measure= or build=")
    all_params = [dict(default_params)] + \
        [dict(c) for c in candidates
         if dict(c) != dict(default_params)]
    if measure is not None:
        times = [measure(p) for p in all_params]
    else:
        fns = [build(p) for p in all_params]
        # warm every candidate first, then interleave the timed reps
        times = [float("inf")] * len(fns)
        for fn in fns:
            measure_callable(fn, warmup=warmup, reps=1, inner=1)
        import jax
        for _ in range(max(reps, 1)):
            for i, fn in enumerate(fns):
                t0 = time.perf_counter()
                r = fn()
                jax.block_until_ready(r)
                times[i] = min(times[i], time.perf_counter() - t0)
    default_s = times[0]
    best_i = min(range(len(all_params)), key=lambda i: times[i])
    best_params, best_s = all_params[best_i], times[best_i]
    if best_s > default_s:   # never-slower floor
        best_params, best_s = all_params[0], default_s
    entry = record(kernel, shape_class, dtype, best_params,
                   best_s * 1e6, default_s * 1e6, len(all_params),
                   backend=backend, persist=persist)
    result = {
        "params": best_params,
        "best_us": entry["best_us"],
        "default_us": entry["default_us"],
        "speedup_vs_default": round(default_s / max(best_s, 1e-12), 4),
        "candidates_tried": len(all_params),
    }
    mon = _state["monitor"]
    if mon is not None:
        mon.event("autotune_search", kernel=kernel,
                  shape_class=shape_class, dtype=_dtype_str(dtype),
                  backend=backend or _backend(),
                  params=best_params,
                  best_us=result["best_us"],
                  default_us=result["default_us"],
                  speedup_vs_default=result["speedup_vs_default"],
                  candidates_tried=result["candidates_tried"])
    return result


# ----------------------------------------------------------------------
# kernel-family helpers: shape classes + candidate enumeration. The
# kernel entry points call the *_params lookups at trace time; the
# operators (and tests) call the *_candidates enumerators to search.
# ----------------------------------------------------------------------
def flash_shape_class(t, d, causal, packed):
    return f"t{t}_d{d}_{'causal' if causal else 'bidir'}" + \
        ("_packed" if packed else "")


def flash_block_candidates(t):
    """(block_q, block_k) grid candidates: power-of-two tiles in
    [128, 1024] that divide t."""
    sizes = [b for b in (128, 256, 512, 1024) if b <= t and t % b == 0]
    return [{"block_q": bq, "block_k": bk}
            for bq in sizes for bk in sizes]


def flash_blocks(t, d, causal, packed, dtype):
    """Tuned (block_q, block_k) for a flash launch, or None."""
    kernel = "flash_fwd_packed" if packed else "flash_fwd"
    params = lookup(kernel, flash_shape_class(t, d, causal, packed),
                    dtype)
    if not params:
        return None
    bq, bk = params.get("block_q"), params.get("block_k")
    if not bq or not bk or t % int(bq) or t % int(bk):
        return None    # table entry from an incompatible shape class
    return int(bq), int(bk)


def row_kernel_shape_class(n, h_padded):
    return f"rows{pow2_bucket(n)}_h{h_padded}"


def row_block_candidates(n):
    """Row-block targets for the fused epilogue kernels (the
    `_row_block` launcher argument)."""
    return [{"row_block": rb} for rb in (64, 128, 256, 512, 1024)
            if rb <= max(n, 64)]


def row_block_target(kernel, n, h_padded, dtype):
    """Tuned row-block target for a fused epilogue launch, or None."""
    params = lookup(kernel, row_kernel_shape_class(n, h_padded), dtype)
    if not params:
        return None
    rb = params.get("row_block")
    return int(rb) if rb else None


def qmm_shape_class(m, k, n):
    return f"m{pow2_bucket(m)}_k{k}_n{n}"


def qmm_block_candidates(m, n):
    """(block_m, block_n) tile candidates for the quantized GEMM."""
    bms = [b for b in (128, 256, 512) if b <= max(m, 128)]
    bns = [b for b in (128, 256, 512) if b <= max(n, 128)]
    return [{"block_m": bm, "block_n": bn} for bm in bms for bn in bns]


def qmm_blocks(m, k, n, dtype):
    """Tuned (block_m, block_n) for the quantized GEMM, or None."""
    params = lookup("quantized_matmul", qmm_shape_class(m, k, n), dtype)
    if not params:
        return None
    bm, bn = params.get("block_m"), params.get("block_n")
    if not bm or not bn:
        return None
    return int(bm), int(bn)


# ----------------------------------------------------------------------
# collective-schedule family: per-(site, mesh-shape, payload-bytes)
# overlap variants, searched with the same never-slower discipline and
# persisted in the same versioned table as the block shapes. Consulted
# by ops/overlap.py `schedule()` when `overlap.sites == "auto"`.
# ----------------------------------------------------------------------
# entries are schedules, not kernels: this string fills the key's
# dtype slot (_dtype_str passes non-dtypes through verbatim)
COLLECTIVE_DTYPE = "schedule"

COLLECTIVE_DEFAULT = {"overlap": True, "issue_distance": 1,
                      "granularity": 1}


def mesh_shape_class(mesh):
    """Axis-signature string for a mesh ("p1.d8.e1.m1"); accepts a jax
    Mesh, a {name: size} dict, or None ("nomesh")."""
    if mesh is None:
        return "nomesh"
    try:
        items = list(mesh.shape.items())
    except AttributeError:
        items = list(dict(mesh).items())
    return ".".join(f"{str(n)[:1]}{int(s)}" for n, s in items) or "nomesh"


def collective_shape_class(site, mesh, payload_bytes):
    """Shape class for a collective site: mesh axis signature plus the
    pow2 KiB bucket of the per-shard payload."""
    kb = pow2_bucket(max(int(payload_bytes), 1024) // 1024)
    return f"{site}|{mesh_shape_class(mesh)}|kb{kb}"


def collective_candidates(site):
    """Schedule candidates per site. MoE varies dispatch granularity,
    ring varies how many permutes stay in flight, the ZeRO-3 leaf
    fence is a pure on/off decision."""
    if site == "moe_dispatch":
        return [{"overlap": o, "issue_distance": 1, "granularity": g}
                for o in (True, False) for g in (1, 2, 4)]
    if site == "ring":
        return [{"overlap": o, "issue_distance": d, "granularity": 1}
                for o in (True, False) for d in (1, 2)]
    return [{"overlap": o, "issue_distance": 1, "granularity": 1}
            for o in (True, False)]


def collective_schedule(site, mesh, payload_bytes):
    """Tuned schedule params for a collective site, or None."""
    return lookup("collective_schedule",
                  collective_shape_class(site, mesh, payload_bytes),
                  COLLECTIVE_DTYPE)


def search_collective_schedule(site, mesh, payload_bytes, measure,
                               backend=None, persist=True):
    """Search the schedule variants for one site with `measure(params)
    -> seconds`. The un-tuned behavior (overlap on, distance 1,
    granularity 1) is the default and the never-slower floor."""
    return search("collective_schedule",
                  collective_shape_class(site, mesh, payload_bytes),
                  COLLECTIVE_DTYPE, collective_candidates(site),
                  dict(COLLECTIVE_DEFAULT), measure=measure,
                  backend=backend, persist=persist)
