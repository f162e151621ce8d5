"""The documents name only what exists: every backticked path ending in
`.py`, `.md` or `.json` resolves under the repo root, under
`deeplearning4j_tpu/` or beside the document, and no document names a
`python <file>` command whose file is missing. One case per document."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, ROOT) for p in
                 glob.glob(os.path.join(ROOT, "docs", "*.md"))))

# a backticked span that is one path: no blanks, no placeholder (<cell>),
# brace or glob; a `:line`, `:line-line` or `::test` suffix is dropped
_PATH = re.compile(r"`([\w./-]+\.(?:py|md|json))(?::[\d,-]+|::[\w:.\[\]-]+)?`")
# files the program writes at run time (inside a checkpoint zip, beside a
# profile capture), which the documents name by design
WRITTEN_AT_RUN_TIME = {"trainingState.json", "meta.json"}
_COMMAND = re.compile(r"\bpython3?\s+(?:-[^m\s]\S*\s+)*([\w./-]+\.py)\b")


def _resolves(path, doc_dir):
    return any(os.path.exists(os.path.join(base, path)) for base in
               (ROOT, os.path.join(ROOT, "deeplearning4j_tpu"), doc_dir))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    doc_dir = os.path.dirname(os.path.join(ROOT, doc))
    missing = sorted({p for p in _PATH.findall(text)
                      if p not in WRITTEN_AT_RUN_TIME
                      and not p.startswith("/")   # outside the repo
                      and not _resolves(p, doc_dir)})
    commands = sorted({p for p in _COMMAND.findall(text)
                       if not os.path.exists(os.path.join(ROOT, p))})
    assert not missing and not commands, (
        f"{doc} names paths that do not exist: {missing}; "
        f"`python <file>` commands whose file is missing: {commands}")
