"""The documents name files that exist (ISSUE 44): every backticked
path in a document that starts with one of the repo's directories is a
file or a directory of this checkout, and every backticked bare
`*.py` / `*.md` / `*.json` is the name of a file somewhere in it (a
page of a subsystem writes `engine.py` for that subsystem's). One case
a document, so that a page that drifts fails under its own name.

Left out on purpose, because they are history and name what stood when
they were written: `CHANGES.md`, `ROADMAP.md` (its "Recent"),
`PERF.md` (its section 6), `SURVEY.md`, `PAPER.md`, `PAPERS.md`,
`SNIPPETS.md` and `ISSUE.md`."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIRECTORIES = ("deepspeed_tpu/", "tests/", "benchmark/", "docs/", "bin/",
               "examples/")
DOCUMENTS = sorted(
    os.path.relpath(path, REPO) for pattern in (
        "README.md", "docs/*.md", "docs/tutorials/*.md",
        ".claude/skills/verify/SKILL.md", "benchmark/README.md")
    for path in glob.glob(os.path.join(REPO, pattern)))
# a path, then what a document may hang on it: `file.py:12`,
# `file.py:12-40`, `file.py::name`, `file.py -k name`
_PATH = re.compile(r"`([A-Za-z0-9_./\-]+)(?:[: ][^`]*)?`")
# What the documents name that is NOT this repo's, and why. A name goes
# here only for one of these two reasons.
# (a) the REFERENCE's files, which MIGRATION.md and the tutorials cite
#     beside their counterparts here (its Jekyll tree `docs/_*`, its
#     launcher, its tests and ZeRO stages):
THE_REFERENCES = {
    "bin/deepspeed", "tests/perf/adam_test.py", "stage1.py", "stage2.py",
    "p2p.py", "run_func_test.py", "test_pld.py", "test_cuda_backward.py",
    "test_cuda_forward.py", "bert-finetuning.md"}
THE_REFERENCES_TREES = ("docs/_",)
# (b) files a run or a user makes: the autotuner's table beside the
#     compile cache, the config of the getting-started page
MADE_AT_RUN_TIME = {"autotune_table_v2.json", "ds_config.json", "ds.json"}


@functools.lru_cache(maxsize=None)
def _file_names():
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") or
                   d == ".claude"]
        names.update(files)
    return names


def named_paths(text):
    """The backticked paths of `text` that this test judges, each
    without its line, its test name or its arguments: (rooted in one
    of DIRECTORIES, bare file names)."""
    rooted, bare = set(), set()
    for match in _PATH.finditer(text):
        path = match.group(1).rstrip(".")
        if path in THE_REFERENCES or path in MADE_AT_RUN_TIME or \
                path.startswith(THE_REFERENCES_TREES):
            continue
        if path.startswith(DIRECTORIES):
            rooted.add(path.rstrip("/"))
        elif "/" not in path and path.endswith((".py", ".md", ".json")):
            bare.add(path)
    return rooted, bare


def test_the_pattern_reads_what_documents_write():
    text = ("see `deepspeed_tpu/inference/engine.py:110`, "
            "`tests/test_moe.py::test_x`, `tests/test_chip_compile.py "
            "-k sliced_out`, `docs/`, `README.md`, `BENCHMARK.json`, "
            "`benchmark/run.py --workload <cell>`, `events.jsonl`, "
            "`flight_<ts>.json`, `tests/test_*.py`, `engine.py:110`, "
            "`models/gpt2.py`, `docs/_tutorials/zero.md`, `stage2.py`")
    assert named_paths(text) == (
        {"deepspeed_tpu/inference/engine.py", "tests/test_moe.py",
         "tests/test_chip_compile.py", "docs", "benchmark/run.py"},
        {"README.md", "BENCHMARK.json", "engine.py"})


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        rooted, bare = named_paths(f.read())
    missing = sorted(p for p in rooted
                     if not os.path.exists(os.path.join(REPO, p)))
    missing += sorted(bare - _file_names())
    assert not missing, f"{document} names what is not there: {missing}"
