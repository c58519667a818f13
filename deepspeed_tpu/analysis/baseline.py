"""ds_lint baseline: allowlist pre-existing findings.

The baseline file is a JSON document mapping finding fingerprints to
their human-readable record — rule, location, message — so the tree
lints clean from day one while every NEW finding still fails CI (the
trick of a ratchet: what stands is recorded, what is new fails).

Fingerprints hash (rule, relative path, enclosing qualname,
normalized source line text) — NOT line numbers — so edits elsewhere
in a file don't expire its baselined findings, while touching the
offending line itself does (you edited it; fix it properly).

Workflow:
  ds_lint deepspeed_tpu/                      # uses the repo baseline
  ds_lint deepspeed_tpu/ --update-baseline    # rewrite after triage
Expired entries (baselined findings that no longer occur) are
reported so the allowlist shrinks over time instead of rotting.
"""

import json
import os

BASELINE_VERSION = 1
DEFAULT_BASENAME = ".ds_lint_baseline.json"


def default_path(repo_root):
    return os.path.join(repo_root, DEFAULT_BASENAME)


def load(path):
    if not path or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"baseline {path}: unsupported version {doc.get('version')!r}")
    return dict(doc.get("findings", {}))


def save(path, entries):
    doc = {
        "version": BASELINE_VERSION,
        "tool": "ds_lint",
        "findings": dict(sorted(entries.items())),
    }
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def fingerprint(finding, index, repo_root):
    mod = index.by_path.get(os.path.abspath(finding.path))
    line_text = ""
    if mod is not None:
        from deepspeed_tpu.analysis import core
        line_text = core.source_line(mod, finding.line)
    return finding.fingerprint(repo_root, line_text)


def fingerprints(findings, index, repo_root):
    """One fingerprint per finding, aligned with the input order.

    Identical source lines in the same function (two `except
    Exception: pass` handlers, say) hash identically — so repeated
    occurrences get an ordinal suffix (`<hash>#2`, `#3`, …) in line
    order. A SECOND identical violation added after the first was
    baselined therefore surfaces as a NEW finding instead of being
    silently auto-baselined."""
    order = sorted(range(len(findings)),
                   key=lambda i: (findings[i].path, findings[i].line))
    seen, out = {}, [None] * len(findings)
    for i in order:
        fp = fingerprint(findings[i], index, repo_root)
        n = seen.get(fp, 0) + 1
        seen[fp] = n
        out[i] = fp if n == 1 else f"{fp}#{n}"
    return out


def apply(findings, entries, index, repo_root):
    """Split findings into (new, baselined) and compute expired
    baseline fingerprints. `findings` must be the WHOLE-package set —
    applying a scope-filtered subset would mark out-of-scope entries
    expired."""
    new, baselined, live = [], [], set()
    for f, fp in zip(findings, fingerprints(findings, index,
                                            repo_root)):
        if fp in entries:
            baselined.append(f)
            live.add(fp)
        else:
            new.append(f)
    expired = {fp: rec for fp, rec in entries.items()
               if fp not in live}
    return new, baselined, expired


def build_entries(findings, index, repo_root):
    out = {}
    for f, fp in zip(findings, fingerprints(findings, index,
                                            repo_root)):
        out[fp] = {
            "rule": f.rule,
            "location": f.location(repo_root),
            "qualname": f.qualname,
            "message": f.message,
        }
    return out
