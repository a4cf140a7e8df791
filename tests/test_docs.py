"""The documents send a reader only to files that exist.

Every repo path a document gives in backticks — starting with one of the
tree's directories and ending in a file extension — must be in the tree
(a ``*`` makes it a glob that must match something).  Bare names such as
``meta.json`` or ``run.jsonl`` are files a run writes, not paths, and are
not checked.  ``ROADMAP.md``, ``CHANGES.md`` and ``PERF.md`` are histories
and name what went on purpose: they are not cases.  When a case fails,
mend the document."""
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "artifacts/README.md"] + sorted(
    os.path.relpath(p, REPO_ROOT)
    for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md")))

_CITED = re.compile(
    r"`((?:r2d2_tpu|tools|tests|docs|artifacts|benchmark)/[\w./*-]*"
    r"\.(?:py|md|jsonl|json|c))`")


def cited_paths(text: str) -> list:
    return sorted(set(_CITED.findall(text)))


def test_the_rule_reads_paths_and_globs_not_bare_names():
    text = ("see `tools/soak.py`, `artifacts/r04/CURVES_*_r04.json` and "
            "`meta.json`; `docs/notes` and `r2d2_tpu/` are no files")
    assert cited_paths(text) == ["artifacts/r04/CURVES_*_r04.json",
                                 "tools/soak.py"]


@pytest.mark.parametrize("doc", DOCS)
def test_doc_cites_only_files_that_exist(doc):
    with open(os.path.join(REPO_ROOT, doc), encoding="utf-8") as f:
        cited = cited_paths(f.read())
    missing = [p for p in cited
               if not (glob.glob(os.path.join(REPO_ROOT, p)) if "*" in p
                       else os.path.isfile(os.path.join(REPO_ROOT, p)))]
    assert not missing, f"{doc} cites files that are not in the tree"
