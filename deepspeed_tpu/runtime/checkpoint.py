"""Sharded checkpoint file I/O (no pickle, no full-state host gather).

Preserves the reference's on-disk layout (ref `engine.py:1255-1273`,
`engine.py:1522-1531`):

    <save_dir>/<tag>/mp_rank_00_model_states.npz (+ .json manifest)
    <save_dir>/<tag>/zero_pp_rank_{k}_mp_rank_00optim_states.npz (+ .json)
    <save_dir>/<tag>/zero_pp_rank_{k}_mp_rank_00model_states.npz (+ .json)
    <save_dir>/latest                      (pointer file)

Semantics, TPU-native:

* **Per-shard files, no gather.** Every device that owns a primary
  (replica_id == 0) shard of a sharded array contributes it to the
  bucket file of that device's dp ordinal — the single-controller
  equivalent of "every dp rank writes its own zero_pp_rank_N file with
  barriers" (ref `engine.py:1522-1531`).  Each process writes only its
  *addressable* shards, so a 13B multi-host save never materialises a
  full array on any host (the round-1 `_fetch_to_host` allgather is
  gone from the save path).
* **Streamed npz + JSON manifests instead of pickle** — loadable
  without arbitrary code execution, versioned (`format_version`).
* **Elastic by construction.** Leaves are reassembled per-leaf on load
  and re-placed under the *current* mesh sharding, so reloading onto a
  different mesh/world size just works — subsuming the reference's
  elastic-vs-rigid ZeRO-1 formats (`stage1.py:825-1024`).

Legacy (round-1) pickle checkpoints are still readable, with a warning.
"""

import contextlib
import json
import os
import pickle
import re
import shutil
import threading
import time
import zipfile

import jax
import numpy as np

FORMAT_VERSION = 2

MODEL_STATES_FMT = "mp_rank_{:02d}_model_states"
OPTIM_SHARD_FMT = "zero_pp_rank_{}_mp_rank_{:02d}optim_states"
MODEL_SHARD_FMT = "zero_pp_rank_{}_mp_rank_{:02d}model_states"
LATEST_FILE = "latest"

# Suffix of the in-progress staging directory an async (or crashed)
# save writes into before the atomic rename to `<tag>`. Readers must
# never treat one as a checkpoint.
STAGING_SUFFIX = ".tmp"

_SHARD_RE = re.compile(
    r"zero_pp_rank_(\d+)_mp_rank_(\d+)(optim|model)_states\.npz$")


# ----------------------------------------------------------------------
# error taxonomy (the elastic supervisor acts on the distinction)
# ----------------------------------------------------------------------
class CheckpointNotFoundError(FileNotFoundError):
    """No checkpoint exists under the requested tag at all — nothing
    was ever saved (or rotation removed it). Recovery action: start
    fresh, or pick a different tag."""


class CheckpointStagingOnlyError(FileNotFoundError):
    """The tag exists ONLY as a `<tag>.tmp` staging dir: a save was
    killed before its atomic commit. The staging dir must never be
    loaded. Recovery action: load an earlier committed tag (the
    `latest` pointer only ever names committed saves)."""


class CheckpointWaitTimeout(TimeoutError):
    """wait_for_checkpoint(timeout=...) expired with a writer still in
    flight. Carries the writer's last heartbeat age so the caller can
    tell a slow-but-alive writer from a wedged one before abandoning
    it (engine.abandon_checkpoint_writers). Note: abandonment unblocks
    in-process teardown/rebuild; writer threads stay non-daemon by
    design (the interpreter will not EXIT mid-write), so a truly
    wedged writer still blocks final process exit."""

    def __init__(self, msg, pending=0, heartbeat_age_sec=None):
        super().__init__(msg)
        self.pending = pending
        self.heartbeat_age_sec = heartbeat_age_sec


# Transient read failures worth retrying: a checkpoint dir mid-commit
# (two-rename window of commit_staging_dir), NFS attribute-cache
# flutter, or a reader racing rotation. Structural corruption
# (coverage mismatch, future format) is NOT retried.
_TRANSIENT_READ_ERRORS = (OSError, zipfile.BadZipFile)


def _retry_read(fn, retries, backoff_sec, describe):
    """Run fn() with bounded retries on transient read errors.
    CheckpointNotFoundError passes straight through — retrying cannot
    create a checkpoint that was never saved. CheckpointStagingOnlyError
    IS retried: a reader racing a same-tag RESAVE's two-rename commit
    window (old `<tag>` moved aside, new `<tag>.tmp` not yet renamed)
    sees exactly the staging-only signature for a few milliseconds;
    only after the retries exhaust is it the terminal interrupted-save
    verdict."""
    attempt = 0
    while True:
        try:
            return fn()
        except CheckpointNotFoundError:
            raise
        except _TRANSIENT_READ_ERRORS as e:
            attempt += 1
            if attempt > retries:
                raise
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                f"transient checkpoint read error ({describe}, attempt "
                f"{attempt}/{retries}): {e}; retrying in "
                f"{backoff_sec * attempt:.2f}s")
            time.sleep(backoff_sec * attempt)


# ----------------------------------------------------------------------
# npz-safe dtype encoding (np.savez silently degrades ml_dtypes arrays
# — bf16 etc. — to raw void records; store them as same-width uints and
# record the logical dtype in the manifest / shard meta)
# ----------------------------------------------------------------------
def _np_dtype(name):
    """np.dtype from a string, resolving ml_dtypes names ("bfloat16",
    "float8_e4m3fn", ...) that plain numpy does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _npz_encode(arr):
    """array -> (npz-native array, logical dtype string or None)."""
    arr = np.asarray(arr)
    try:
        np.dtype(arr.dtype.name)   # round-trippable by plain numpy?
        if arr.dtype.kind != "V":
            return arr, None
    except TypeError:
        pass
    uint = np.dtype(f"u{arr.dtype.itemsize}")
    return arr.view(uint), arr.dtype.name


def _npz_decode(arr, dtype_name):
    if dtype_name is None:
        return arr
    return arr.view(_np_dtype(dtype_name))


# ----------------------------------------------------------------------
# pytree <-> flat path/leaf maps
# ----------------------------------------------------------------------
def tree_to_entries(tree, prefix=""):
    """[(path_string, leaf)] with jax.tree_util paths (stable across
    save/load as long as the tree structure matches)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(prefix + jax.tree_util.keystr(path), leaf)
            for path, leaf in flat]


def entries_to_tree(template, flat, prefix=""):
    """Rebuild leaves of `template`'s structure from a {path: array}
    map (missing keys raise KeyError with the offending path)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, _ in paths:
        key = prefix + jax.tree_util.keystr(path)
        if key not in flat:
            raise KeyError(f"checkpoint is missing entry {key!r}")
        leaves.append(flat[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _is_array(x):
    return isinstance(x, (jax.Array, np.ndarray))


def _dp_ordinal(sharding, device):
    """Stable ordinal of `device` within the sharding's device set —
    the dp-rank analog that names the bucket file."""
    ids = sorted(d.id for d in sharding.device_set)
    return ids.index(device.id)


# ----------------------------------------------------------------------
# save
# ----------------------------------------------------------------------
def _ckpt_dir(save_dir, tag):
    return os.path.join(save_dir, str(tag))


def model_states_path(save_dir, tag, mp_rank=0):
    return os.path.join(_ckpt_dir(save_dir, tag),
                        MODEL_STATES_FMT.format(mp_rank) + ".npz")


def _split_shards(entries):
    """Split entries into (replicated, sharded).  `replicated` leaves
    are written once by process 0; `sharded` leaves contribute one
    piece per primary shard to per-ordinal bucket files."""
    replicated, sharded = [], []
    for key, leaf in entries:
        if isinstance(leaf, jax.Array) and hasattr(leaf, "sharding") and \
                not leaf.sharding.is_fully_replicated:
            sharded.append((key, leaf))
        else:
            replicated.append((key, leaf))
    return replicated, sharded


def _write_shard_buckets(ckpt_dir, fmt, sharded, mp_rank=0):
    """Write each primary shard of each sharded leaf into the bucket
    file of its owning device's dp ordinal.  Only addressable shards
    are touched — multi-host safe, no cross-host traffic."""
    buckets = {}       # ordinal -> {npz_name: np.ndarray}
    bucket_meta = {}   # ordinal -> [entry meta]
    for key, leaf in sharded:
        for shard in leaf.addressable_shards:
            if shard.replica_id != 0:
                continue
            ordinal = _dp_ordinal(leaf.sharding, shard.device)
            name = f"s{len(bucket_meta.get(ordinal, []))}"
            start = [0 if sl.start is None else int(sl.start)
                     for sl in shard.index]
            piece, enc = _npz_encode(np.asarray(shard.data))
            buckets.setdefault(ordinal, {})[name] = piece
            bucket_meta.setdefault(ordinal, []).append({
                "name": name, "key": key, "start": start,
                "global_shape": list(leaf.shape), "dtype": str(leaf.dtype),
                "npz_dtype": enc,
            })
    for ordinal, arrays in buckets.items():
        base = os.path.join(ckpt_dir, fmt.format(ordinal, mp_rank))
        np.savez(base + ".npz", **arrays)
        with open(base + ".json", "w") as f:
            json.dump({"format_version": FORMAT_VERSION,
                       "entries": bucket_meta[ordinal]}, f)


def _json_safe(obj):
    """Recursively convert checkpoint metadata to JSON-able values;
    numpy scalars/arrays become lists (small metadata only)."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (np.generic,)):
        return obj.item()
    if isinstance(obj, (np.ndarray, jax.Array)):
        return {"__ndarray__": np.asarray(obj).tolist(),
                "dtype": str(np.asarray(obj).dtype)}
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    from deepspeed_tpu.utils.logging import logger
    logger.warning(
        f"checkpoint metadata value of type {type(obj).__name__} is not "
        "JSON-serializable; storing its repr (round-trip lossy)")
    return {"__unserializable__": repr(obj)}


def _json_restore(obj):
    if isinstance(obj, dict):
        if "__ndarray__" in obj:
            return np.asarray(obj["__ndarray__"],
                              dtype=np.dtype(obj["dtype"]))
        return {k: _json_restore(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_json_restore(v) for v in obj]
    return obj


def save_checkpoint_files(save_dir, tag, model_sd, optim_sd, mp_rank=0,
                          ckpt_dir=None):
    """Write a sharded checkpoint.

    `model_sd` — dict with a "module" pytree of (possibly sharded) jax
    arrays plus JSON-able metadata entries.  `optim_sd` — dict with an
    "opt_state" pytree plus metadata; may be None.  All processes must
    call this (each writes its own shards); process 0 writes manifests.
    `ckpt_dir` overrides the destination directory (the async writer
    points it at the `<tag>.tmp` staging dir and renames on commit).
    """
    if ckpt_dir is None:
        ckpt_dir = _ckpt_dir(save_dir, tag)
    os.makedirs(ckpt_dir, exist_ok=True)

    module = model_sd.get("module", {})
    mod_entries = tree_to_entries(module, "module")
    mod_repl, mod_sharded = _split_shards(mod_entries)
    _write_shard_buckets(ckpt_dir, MODEL_SHARD_FMT, mod_sharded, mp_rank)

    opt_repl, opt_sharded = [], []
    opt_meta = {}
    if optim_sd is not None:
        opt_entries = []
        for k, v in optim_sd.items():
            if k == "opt_state":
                opt_entries += tree_to_entries(v, "optim")
            elif _is_array(v) or (isinstance(v, (tuple, list)) and
                                  any(_is_array(x) for x in
                                      jax.tree_util.tree_leaves(v))):
                opt_entries += tree_to_entries(v, f"aux/{k}")
            else:
                opt_meta[k] = v
        opt_repl, opt_sharded = _split_shards(opt_entries)
        _write_shard_buckets(ckpt_dir, OPTIM_SHARD_FMT, opt_sharded,
                             mp_rank)

    if jax.process_index() != 0:
        return

    meta = {k: v for k, v in model_sd.items() if k != "module"}
    main = {}
    npz_dtypes = {}
    for key, leaf in mod_repl + opt_repl:
        arr, enc = _npz_encode(np.asarray(jax.device_get(leaf)))
        main[key] = arr
        if enc is not None:
            npz_dtypes[key] = enc
    base = os.path.join(ckpt_dir, MODEL_STATES_FMT.format(mp_rank))
    np.savez(base + ".npz", **main)
    with open(base + ".json", "w") as f:
        json.dump({
            "format_version": FORMAT_VERSION,
            "meta": _json_safe(meta),
            "optim_meta": _json_safe(opt_meta),
            "npz_dtypes": npz_dtypes,
            "has_optim": optim_sd is not None,
        }, f)


# ----------------------------------------------------------------------
# load
# ----------------------------------------------------------------------
def _assemble(flat, shard_entries):
    """Reassemble sharded leaves on host, one leaf at a time (peak host
    memory = one global leaf, not the whole tree). Coverage is
    verified: the primary shards of a leaf tile it exactly, so any
    missing/unreadable bucket file shows up as covered != global and
    raises instead of silently zero-filling the hole."""
    by_key = {}
    for npz, entry in shard_entries:
        by_key.setdefault(entry["key"], []).append((npz, entry))
    for key, pieces in by_key.items():
        _, first = pieces[0]
        out = np.zeros(first["global_shape"],
                       dtype=_np_dtype(first["dtype"]))
        covered = 0
        for npz, entry in pieces:
            piece = _npz_decode(npz[entry["name"]],
                                entry.get("npz_dtype"))
            idx = tuple(slice(s, s + d) for s, d in
                        zip(entry["start"], piece.shape))
            out[idx] = piece
            covered += int(np.prod(piece.shape))
        total = int(np.prod(first["global_shape"]))
        if covered != total:
            raise ValueError(
                f"checkpoint shard coverage mismatch for {key!r}: "
                f"{covered} of {total} elements present — a "
                "zero_pp_rank shard file is missing or truncated")
        flat[key] = out
    return flat


def _load_legacy_pickle(load_dir, tag, mp_rank, dp_rank):
    from deepspeed_tpu.utils.logging import logger
    logger.warning(
        "loading legacy (round-1) pickle checkpoint; resave to upgrade "
        "to the sharded npz format")
    legacy_model = os.path.join(
        _ckpt_dir(load_dir, tag), f"mp_rank_{mp_rank:02d}_model_states.pt")
    with open(legacy_model, "rb") as f:
        model_sd = pickle.load(f)
    optim_sd = None
    legacy_opt = os.path.join(
        _ckpt_dir(load_dir, tag),
        f"zero_pp_rank_{dp_rank}_mp_rank_{mp_rank:02d}optim_states.pt")
    if os.path.exists(legacy_opt):
        with open(legacy_opt, "rb") as f:
            optim_sd = pickle.load(f)
    return model_sd, optim_sd, True


def load_checkpoint_flat(load_dir, tag, mp_rank=0, retries=0,
                         backoff_sec=0.05):
    """Read a sharded checkpoint into ({path: np.array}, meta,
    optim_meta, has_optim).  Paths are prefixed "module"/"optim"/"aux".

    `retries` bounds retry-with-backoff on TRANSIENT read errors
    (OSError/BadZipFile — a reader racing a commit's rename window or
    rotation). Missing checkpoints fail immediately with a distinct,
    actionable error: `CheckpointStagingOnlyError` when only the
    `<tag>.tmp` staging dir of an interrupted save exists,
    `CheckpointNotFoundError` when there is nothing at all."""
    return _retry_read(
        lambda: _load_checkpoint_flat_once(load_dir, tag, mp_rank),
        retries, backoff_sec, f"tag '{tag}' in {load_dir}")


def _load_checkpoint_flat_once(load_dir, tag, mp_rank=0):
    ckpt_dir = _ckpt_dir(load_dir, tag)
    base = os.path.join(ckpt_dir, MODEL_STATES_FMT.format(mp_rank))
    if not os.path.exists(base + ".json"):
        legacy = os.path.join(ckpt_dir,
                              f"mp_rank_{mp_rank:02d}_model_states.pt")
        if os.path.isdir(staging_dir(load_dir, tag)):
            # `<tag>.tmp` without the manifest: an interrupted save —
            # or, transiently, a same-tag resave mid-commit (the
            # two-rename window); _retry_read retries this verdict
            # before it becomes terminal
            raise CheckpointStagingOnlyError(
                f"checkpoint tag '{tag}' in {load_dir} only exists as "
                f"an incomplete staging dir ('{tag}{STAGING_SUFFIX}') "
                "left by an interrupted save; load an earlier tag (see "
                "the 'latest' pointer)")
        if not os.path.isdir(ckpt_dir):
            raise CheckpointNotFoundError(
                f"no checkpoint tag '{tag}' under {load_dir}: the tag "
                "directory does not exist (never saved, or removed by "
                "keep_last rotation)")
        # dir present, manifest absent: terminal, not a transient to
        # burn retries on. A legacy pickle dir gets an actionable
        # message (this flat loader never read the .pt format — the
        # pickle path lives in load_checkpoint_files).
        if os.path.exists(legacy):
            raise CheckpointNotFoundError(
                f"checkpoint dir {ckpt_dir} holds a legacy pickle "
                "checkpoint (mp_rank_*.pt) with no npz manifest; load "
                "it through load_checkpoint_files / "
                "engine.load_checkpoint")
        raise CheckpointNotFoundError(
            f"checkpoint dir {ckpt_dir} exists but has no manifest "
            f"{os.path.basename(base)}.json (mp_rank mismatch, or a "
            "corrupted/partially deleted checkpoint)")
    with open(base + ".json") as f:
        manifest = json.load(f)
    version = manifest.get("format_version", 1)
    if version > FORMAT_VERSION:
        raise ValueError(
            f"checkpoint {ckpt_dir} has format_version {version}, but "
            f"this build reads up to {FORMAT_VERSION} — upgrade "
            "deepspeed_tpu to load it")
    npz_dtypes = manifest.get("npz_dtypes", {})
    flat = {}
    with np.load(base + ".npz") as main:
        for key in main.files:
            flat[key] = _npz_decode(main[key], npz_dtypes.get(key))

    shard_entries = []
    opened = []
    try:
        for fname in sorted(os.listdir(ckpt_dir)):
            m = _SHARD_RE.match(fname)
            if not m or int(m.group(2)) != mp_rank:
                continue
            npz = np.load(os.path.join(ckpt_dir, fname))
            opened.append(npz)
            with open(os.path.join(
                    ckpt_dir, fname[:-len(".npz")] + ".json")) as f:
                bucket = json.load(f)
            for entry in bucket["entries"]:
                shard_entries.append((npz, entry))
        _assemble(flat, shard_entries)
    finally:
        for npz in opened:
            npz.close()
    return (flat, _json_restore(manifest.get("meta", {})),
            _json_restore(manifest.get("optim_meta", {})),
            manifest.get("has_optim", False))


def load_checkpoint_files(load_dir, tag, zero_enabled=True, mp_rank=0,
                          dp_rank=0, module_template=None,
                          opt_state_template=None, aux_templates=None,
                          retries=0):
    """Engine-facing loader.  Returns (model_sd, optim_sd) shaped like
    the save-side inputs: model_sd["module"] is a pytree when
    `module_template` is given (otherwise the flat {path: array} map
    under model_sd["module_flat"]); likewise optim_sd["opt_state"].
    `zero_enabled` gates whether optimizer state is assembled at all.
    `retries` bounds transient-read retries (see load_checkpoint_flat)."""
    legacy_marker = os.path.join(
        _ckpt_dir(load_dir, tag), f"mp_rank_{mp_rank:02d}_model_states.pt")
    npz_marker = model_states_path(load_dir, tag, mp_rank)
    if not os.path.exists(npz_marker) and os.path.exists(legacy_marker):
        model_sd, optim_sd, _ = _load_legacy_pickle(load_dir, tag, mp_rank,
                                                    dp_rank)
        return model_sd, optim_sd

    flat, meta, opt_meta, has_optim = load_checkpoint_flat(
        load_dir, tag, mp_rank, retries=retries)

    model_sd = dict(meta)
    if module_template is not None:
        model_sd["module"] = entries_to_tree(module_template, flat,
                                             "module")
    else:
        model_sd["module_flat"] = {
            k: v for k, v in flat.items() if k.startswith("module")}

    optim_sd = None
    if has_optim and zero_enabled:
        optim_sd = dict(opt_meta)
        if opt_state_template is not None:
            try:
                optim_sd["opt_state"] = entries_to_tree(
                    opt_state_template, flat, "optim")
            except KeyError:
                optim_sd["opt_state"] = None
        for name, template in (aux_templates or {}).items():
            try:
                optim_sd[name] = entries_to_tree(template, flat,
                                                 f"aux/{name}")
            except KeyError:
                pass
    return model_sd, optim_sd


# ----------------------------------------------------------------------
# durability: fsync helpers, staging-dir commit, latest tag, rotation
# ----------------------------------------------------------------------
def _fsync_path(path):
    """fsync a file (or directory) by descriptor; directory fsync is
    best-effort — not all filesystems support it."""
    flags = os.O_RDONLY
    if os.path.isdir(path) and hasattr(os, "O_DIRECTORY"):
        flags |= os.O_DIRECTORY
    try:
        fd = os.open(path, flags)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def staging_dir(save_dir, tag):
    """The `<tag>.tmp` directory an in-progress save writes into."""
    return _ckpt_dir(save_dir, tag) + STAGING_SUFFIX


def is_staging_name(name):
    return str(name).endswith(STAGING_SUFFIX)


def commit_staging_dir(save_dir, tag):
    """Durably publish `<tag>.tmp` as `<tag>`: fsync every file in the
    staging dir, atomically rename it over the final name, fsync the
    parent.  A crash at any point leaves either the old `<tag>` (or
    nothing) or the new one — never a half-written visible checkpoint."""
    src = staging_dir(save_dir, tag)
    dst = _ckpt_dir(save_dir, tag)
    for root, _, files in os.walk(src):
        for fname in files:
            _fsync_path(os.path.join(root, fname))
    _fsync_path(src)
    trash = None
    if os.path.exists(dst):
        # resave of an existing tag: move the old dir aside by RENAME
        # (microseconds) rather than rmtree'ing it in place (seconds
        # for a large checkpoint), so the window with no `<tag>`
        # visible is two renames wide. The trash name carries the
        # staging suffix so readers and rotation skip it, and a crash
        # inside the window leaves BOTH complete dirs (`<tag>.old.tmp`
        # and the fsynced `<tag>.tmp`) recoverable by hand.
        trash = dst + ".old" + STAGING_SUFFIX
        if os.path.exists(trash):
            shutil.rmtree(trash)
        os.replace(dst, trash)
    os.replace(src, dst)
    # stamp COMMIT time on the dir: rotation ranks by mtime, and a
    # slow writer finishing its file writes late must not make an
    # earlier-submitted checkpoint look newer than a later one
    os.utime(dst, None)
    _fsync_path(save_dir)
    if trash is not None:
        shutil.rmtree(trash, ignore_errors=True)


def checkpoint_dirs_bit_identical(d1, d2):
    """True when two checkpoint dirs are byte-identical: same file
    names, every npz entry equal in dtype and raw bytes, every json
    manifest equal.  Used by tests (tests/test_async_checkpoint.py) to
    prove async and sync saves of the same state match exactly."""
    f1, f2 = sorted(os.listdir(d1)), sorted(os.listdir(d2))
    if f1 != f2:
        return False
    for name in f1:
        p1, p2 = os.path.join(d1, name), os.path.join(d2, name)
        if name.endswith(".npz"):
            with np.load(p1) as a, np.load(p2) as b:
                if sorted(a.files) != sorted(b.files):
                    return False
                for k in a.files:
                    if a[k].dtype != b[k].dtype or \
                            a[k].tobytes() != b[k].tobytes():
                        return False
        elif name.endswith(".json"):
            with open(p1) as fa, open(p2) as fb:
                if json.load(fa) != json.load(fb):
                    return False
    return True


def is_checkpoint_dir(path):
    """True when `path` looks like a completed checkpoint directory
    (has a model-states file or per-layer files); staging dirs and
    unrelated directories are excluded."""
    if not os.path.isdir(path) or is_staging_name(path):
        return False
    try:
        names = os.listdir(path)
    except OSError:
        return False
    return any("model_states" in n or n.startswith("layer_")
               for n in names)


def rotate_checkpoints(save_dir, keep_last, protect=()):
    """Delete all but the newest `keep_last` checkpoint dirs under
    `save_dir` (by mtime).  `latest`'s target and `protect` tags are
    never deleted; `.tmp` staging dirs are never counted or touched.
    Returns the list of deleted tags."""
    if not keep_last or keep_last <= 0:
        return []
    keep = {str(t) for t in protect}
    latest = read_latest_tag(save_dir)
    if latest is not None:
        keep.add(latest)
    entries = []
    for name in os.listdir(save_dir):
        full = os.path.join(save_dir, name)
        if is_checkpoint_dir(full):
            try:
                entries.append((os.path.getmtime(full), name))
            except OSError:
                continue   # vanished concurrently (shared save_dir)
    entries.sort(reverse=True)
    deleted = []
    for _, name in entries[keep_last:]:
        if name in keep:
            continue
        shutil.rmtree(os.path.join(save_dir, name), ignore_errors=True)
        deleted.append(name)
    return deleted


class AsyncCheckpointWriter:
    """Background checkpoint writer: one non-daemon thread per save job
    (the interpreter cannot exit with a write half-done), a bounded
    in-flight window for backpressure, and error propagation into the
    training loop at the next submit/wait.

    queue_depth: saves allowed in flight before backpressure engages.
    queue_policy: "block" — a submit over the depth waits for the
    oldest job; "drop" — the new save is discarded with a warning
    (the snapshot is released, nothing is written).

    Jobs may SERIALIZE concurrently (queue_depth >= 2) but COMMIT in
    submission order via the gate submit() hands to each job — so
    `latest` and keep_last rotation can never regress to an older save
    whose writer happened to finish last.
    """

    def __init__(self, queue_depth=1, queue_policy="block"):
        assert queue_depth >= 1, queue_depth
        assert queue_policy in ("block", "drop"), queue_policy
        self._depth = queue_depth
        self._policy = queue_policy
        # set when the engine detaches this writer (wedged-writer
        # recovery): jobs still commit their tag dirs atomically, but
        # must no longer move `latest` or rotate — a stale writer
        # unwedging AFTER a successor engine committed newer tags
        # would otherwise regress the pointer to an older save
        self.abandoned = threading.Event()
        self._jobs = []          # [(thread, tag)]
        self._lock = threading.Lock()
        self._error = None
        self._seq_next = 0       # submission-order ticket
        self._commit_turn = 0    # ticket currently allowed to commit
        self._done_seqs = set()  # finished out-of-order, turn not theirs yet
        self._commit_cv = threading.Condition()

    def _reap(self):
        with self._lock:
            self._jobs = [(t, tag) for t, tag in self._jobs
                          if t.is_alive()]
            return list(self._jobs)

    def queue_depth(self):
        """Saves currently in flight (the monitor's checkpoint
        queue-depth gauge)."""
        return len(self._reap())

    def tag_in_flight(self, tag):
        """True while a live job of THIS writer holds `tag` (and so
        owns its `<tag>.tmp` staging dir). Successor writers consult
        this on abandoned predecessors before touching the same tag —
        two writers sharing one staging dir would corrupt the
        commit."""
        tag = str(tag)
        return any(jt == tag for _, jt in self._reap())

    def _raise_pending(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError(
                "background checkpoint write failed") from err

    def _warn_drop(self, tag):
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            f"async checkpoint '{tag}' dropped: "
            f"{self._depth} save(s) already in flight "
            "(checkpoint.queue_policy=drop)")

    def admit(self, tag):
        """Cheap pre-snapshot check: False when queue_policy="drop"
        would discard a submit right now, letting the caller skip
        building the snapshot entirely (for offload engines that is a
        full host copy of masters and moments).  Under "block" always
        True — submit() provides the backpressure."""
        if self._policy != "drop":
            return True
        jobs = self._reap()
        tag = str(tag)
        # a same-tag job in flight would force submit() to block on it
        # (shared staging dir) — under "drop" that save drops instead
        if len(jobs) < self._depth and \
                not any(jt == tag for _, jt in jobs):
            return True
        self._warn_drop(tag)
        return False

    def _mark_done(self, seq):
        """Job `seq` no longer needs its commit turn (it committed or
        died).  Advance the turn across contiguously-finished seqs
        ONLY — jumping past a still-running earlier job would strand
        its writer at the gate forever."""
        with self._commit_cv:
            if seq < self._commit_turn:
                return           # turn already consumed (gate path ran)
            self._done_seqs.add(seq)
            while self._commit_turn in self._done_seqs:
                self._done_seqs.discard(self._commit_turn)
                self._commit_turn += 1
            self._commit_cv.notify_all()

    def submit(self, fn, tag, on_done=None):
        """Run fn(commit_gate) on a writer thread; `commit_gate` is a
        context manager the job must hold around its commit section
        (rename + `latest` + rotation) — gates open in submission
        order.  Returns True when the job was accepted, False when
        queue_policy="drop" rejected it.  `on_done` (optional, must
        not raise meaningfully) runs on the writer thread after the
        job finishes — success OR failure — e.g. releasing the
        snapshot's memory-ledger entries: the double-buffers are gone
        once the writer is, however the write ended."""
        self._raise_pending()
        tag = str(tag)
        # two writers on one tag would share a `<tag>.tmp` staging dir
        # (the second rmtrees it out from under the first): serialize
        # same-tag jobs regardless of queue depth
        while True:
            same = [t for t, jt in self._reap() if jt == tag]
            if not same:
                break
            if self._policy == "drop":
                # blocking on the shared staging dir would violate
                # drop's never-stall contract
                self._warn_drop(tag)
                return False
            same[0].join()
        while True:
            jobs = self._reap()
            if len(jobs) < self._depth:
                break
            if self._policy == "drop":
                self._warn_drop(tag)
                return False
            # join via the snapshot — another thread's concurrent
            # _reap() may swap self._jobs out from under an index
            jobs[0][0].join()
        seq = self._seq_next
        self._seq_next += 1

        @contextlib.contextmanager
        def commit_gate():
            with self._commit_cv:
                while self._commit_turn != seq:
                    self._commit_cv.wait()
            try:
                yield
            finally:
                self._mark_done(seq)

        def run():
            try:
                fn(commit_gate)
            except BaseException as e:  # noqa: BLE001 — must not die silent
                from deepspeed_tpu.utils.logging import logger
                import traceback
                logger.error("async checkpoint write failed:\n"
                             + traceback.format_exc())
                with self._lock:
                    if self._error is None:
                        self._error = e
            finally:
                # a job that died before (or without) taking its gate
                # must still release its turn or later jobs deadlock
                self._mark_done(seq)
                if on_done is not None:
                    try:
                        on_done()
                    except Exception:
                        # the hook releases ledger entries etc.; its
                        # failure must not kill the writer thread but
                        # must leave evidence
                        from deepspeed_tpu.utils.logging import logger
                        import traceback
                        logger.warning(
                            "checkpoint on_done hook failed:\n"
                            + traceback.format_exc())

        t = threading.Thread(target=run, daemon=False,
                             name=f"ckpt-writer-{tag}")
        with self._lock:
            self._jobs.append((t, tag))
        t.start()
        return True

    def wait(self, timeout=None):
        """Barrier: block until every in-flight save has committed;
        re-raise the first writer error, if any.  With a `timeout`
        (seconds, across ALL in-flight jobs) returns True when drained
        and False when the deadline expired with a writer still alive
        — pending errors are re-raised either way, so a wedged writer
        cannot mask an earlier failed one."""
        deadline = None if timeout is None else \
            time.monotonic() + float(timeout)
        while True:
            with self._lock:
                jobs = list(self._jobs)
            if not jobs:
                break
            for t, _ in jobs:
                if deadline is None:
                    t.join()
                else:
                    t.join(max(0.0, deadline - time.monotonic()))
                    if t.is_alive():
                        self._raise_pending()
                        return False
            self._reap()
        self._raise_pending()
        return True

    def pending(self):
        return len(self._reap())


# ----------------------------------------------------------------------
# latest tag + tag validation
# ----------------------------------------------------------------------
def write_latest_tag(save_dir, tag):
    """Crash-atomic `latest` pointer: write a tmp file, fsync, then
    os.replace — a reader (or a restart after a kill) sees either the
    previous tag or the new one, never a torn write."""
    os.makedirs(save_dir, exist_ok=True)
    path = os.path.join(save_dir, LATEST_FILE)
    # unique tmp name: concurrent writer threads (queue_depth >= 2)
    # must not truncate each other's tmp file between write and rename
    tmp = (f"{path}.{os.getpid()}.{threading.get_ident()}"
           f"{STAGING_SUFFIX}")
    with open(tmp, "w") as f:
        f.write(str(tag))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_path(save_dir)


def read_latest_tag(load_dir, retries=0, backoff_sec=0.05):
    """Read the `latest` pointer (None when absent). `retries` bounds
    retry-with-backoff on transient OSErrors (a reader racing the
    pointer's atomic replace on a laggy network filesystem)."""
    def once():
        path = os.path.join(load_dir, LATEST_FILE)
        if not os.path.exists(path):
            return None
        with open(path, "r") as f:
            return f.read().strip()

    tag = _retry_read(once, retries, backoff_sec,
                      f"latest pointer in {load_dir}")
    if tag is None:
        return None
    if not tag or is_staging_name(tag):
        # a staging name can only reach `latest` by hand-editing; treat
        # it as absent rather than load a possibly half-written dir
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            f"{os.path.join(load_dir, LATEST_FILE)} points at staging "
            f"entry {tag!r}; ignoring it")
        return None
    return tag


def validate_checkpoint_tag(tag, fail_on_mismatch=False):
    """Cross-process tag consistency vote (ref `engine.py:1448-1463`:
    sha1 min/max allreduce).  Returns True when all processes agree."""
    import hashlib
    digest = np.frombuffer(hashlib.sha1(str(tag).encode()).digest(),
                           dtype=np.uint8).astype(np.int32)
    if jax.process_count() == 1:
        return True
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(digest)
    valid = bool((gathered == gathered[0]).all())
    msg = (f"checkpoint tag '{tag}' is not consistent across all "
           "processes; rank-unique tags break restores at different "
           "world sizes")
    if fail_on_mismatch:
        if not valid:
            raise ValueError(msg)
    elif not valid:
        from deepspeed_tpu.utils.logging import logger
        logger.warning(msg)
    return valid
