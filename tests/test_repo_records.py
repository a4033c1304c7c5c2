"""The repo's documents name only what exists, and no drill report is a
record. No JAX: these read text and the tree.

Since PR 29 a performance number has one source (``benchmark/run.py`` on
the chip, ``PERF_LEDGER.jsonl``, ``PERF.md``). The pre-chip benches, their
ledger and their result files left, and the documents that sent a reader
to them were rewritten; these tests keep it so."""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md", "docs/README.md", ".claude/skills/verify/SKILL.md",
     "scripts/check.sh"]
    + sorted(os.path.relpath(p, ROOT)
             for p in glob.glob(os.path.join(ROOT, "docs", "tutorials", "*.md"))))

# Named on purpose though not in the tree. Short, and every entry says why.
# (What git ignores by design, .jax_cache, chiprun_out/, .bench_trace/, is
# under none of the prefixes below, so the rule never asks for it.)
NOT_IN_THE_TREE = {
    # files a checkpoint or a version registry writes beside its data
    "SPECS.json", "MANIFEST.json", "VERSIONS.json",
    # the reference's own files (under /root/reference/), cited by name
    "docs/_tutorials/", "docs/_posts/2020-09-08", "compressed_ar.py",
    "pipelined_optimizer_swapper.py", "partition_parameters.py",
    # a user's script, as an example
    "train.py",
}

_NOT_BEFORE = r"(?<![\w./<>{}$~*-])"
# scripts/..., deeperspeed_tpu/..., benchmark/..., tests/..., configs/...,
# traces/..., docs/...: a path from the root of the repo
_PREFIXED = re.compile(
    _NOT_BEFORE + r"(?:scripts|deeperspeed_tpu|benchmark|tests|configs|"
    r"traces|docs)/[\w./*\-]*")
# a bare file name: a root record (CAPITALS.json|jsonl|md) or a *.py
_BARE = re.compile(
    _NOT_BEFORE + r"[A-Za-z_][\w*\-]*(?:\.[\w*\-]+)*\.(?:py|jsonl|json|md)\b"
    r"(?![\w/])")
_ROOT_RECORD = re.compile(r"[A-Z][A-Z0-9]*(?:_[A-Za-z0-9*]+)*")
_LINK = re.compile(r"\]\(([^)\s#]+)(?:#[^)]*)?\)")


# what git ignores and a working tree may still hold (scratch copies of a
# parent commit live under _chip/)
_IGNORED_DIRS = {".git", "__pycache__", "_chip", "chiprun_out", ".jax_cache",
                 ".bench_trace", ".pytest_cache", ".hypothesis"}


def _tree_files():
    for where, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _IGNORED_DIRS]
        for name in files:
            yield os.path.relpath(os.path.join(where, name), ROOT)


@functools.lru_cache(maxsize=None)
def _tree_names():
    return frozenset(os.path.basename(p) for p in _tree_files())


def _strip(token):
    token = token.rstrip(".,;:")
    return re.sub(r":\d+(?:[-–]\d+)?$", "", token)


def _there(path):
    full = os.path.join(ROOT, path)
    return bool(glob.glob(full)) if "*" in path else os.path.exists(full)


def dead_paths(doc, text, names):
    """Every token of ``text`` that reads as a path of this repo and names
    nothing in it."""
    dead = set()
    for m in _PREFIXED.finditer(text):
        token = _strip(m.group(0))
        if not _there(token):
            dead.add(token)
    for m in _BARE.finditer(text):
        token = _strip(m.group(0))
        if token.endswith(".py"):
            # "`engine.py`" may mean runtime/engine.py: any file of that name
            found = token in names
        elif _ROOT_RECORD.fullmatch(token.split(".")[0]):
            found = _there(token) or token in names
        else:
            continue            # trace.json, ds_config.json: the user's files
        if not found:
            dead.add(token)
    for m in _LINK.finditer(text):
        target = m.group(1)
        if re.match(r"[a-z]+:", target):
            continue            # a URL
        if not os.path.exists(os.path.normpath(
                os.path.join(ROOT, os.path.dirname(doc), target))):
            dead.add(target)
    return dead - NOT_IN_THE_TREE


def test_the_path_rule_catches_what_left_and_passes_what_stays():
    names = _tree_names()
    text = ("run `python gone.py`, then scripts/gone_bench.py:272 and "
            "`GONE_LEDGER.jsonl`, `BENCH_*.json`; see [x](docs/nope.md), "
            "`deeperspeed_tpu/monitor/gone.py`. Kept: `BENCHMARK.json`, "
            "scripts/check.sh:114, `PERF.md`, benchmark/run.py, `trace.json`, "
            "/root/reference/tests/unit/test_x.py, [r](README.md).")
    assert dead_paths("README.md", text, names) == {
        "gone.py", "scripts/gone_bench.py", "GONE_LEDGER.jsonl",
        "BENCH_*.json", "docs/nope.md", "deeperspeed_tpu/monitor/gone.py"}


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc)) as fh:
        text = fh.read()
    assert dead_paths(doc, text, _tree_names()) == set()


def test_no_result_file_is_a_record():
    """A drill's report is a pass/fail with counts, not a record: none is
    at the root, git ignores the default names, and README lists none."""
    assert [os.path.basename(p)
            for p in glob.glob(os.path.join(ROOT, "BENCH_*.json"))] == []
    assert os.path.exists(os.path.join(ROOT, "BENCHMARK.json"))
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        ignored = {line.strip() for line in fh}
    assert {"BENCH_*.json", "ONEBIT_WIRE.json"} <= ignored
    with open(os.path.join(ROOT, "README.md")) as fh:
        assert re.findall(r"BENCH_[\w*]+\.jsonl?", fh.read()) == []


def test_the_documents_were_found():
    """README, docs/README, the verify skill, check.sh and the 24 tutorials
    of PR 29; a glob that finds nothing would pass everything."""
    assert len(DOCUMENTS) >= 28, DOCUMENTS


def test_nothing_reads_the_apparatus_that_left():
    """No program, script or document but the history (CHANGES.md, PERF.md,
    ROADMAP.md, this PR's ISSUE.md) still speaks of the old ledger or the
    old benches."""
    # classes, not escapes: this file must not match its own pattern
    gone = re.compile(r"monitor[./]ledger|BENCH[_]LEDGER|"
                      r"(^|[^_a-z])bench[.]py|serving_bench[.]py", re.M)
    history = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md"}
    speaking = set()
    for path in _tree_files():
        if path.endswith((".py", ".sh", ".md")) and path not in history:
            with open(os.path.join(ROOT, path), errors="replace") as fh:
                if gone.search(fh.read()):
                    speaking.add(path)
    assert speaking == set()
