"""The documents a user reads name only files that exist.

README, PARITY, OBSERVABILITY, CHAOS, CONCURRENCY and MIGRATION send their
reader to files by name. A back-quoted name that ends in ``.py``, ``.json``,
``.md`` or ``.jsonl`` and sits at the root of the repository or under
``ray_tpu/``, ``tests/``, ``benchmarks/`` or ``examples/`` must be a file of
this tree, unless its sentence says ``git show`` (a pointer into history).
Fenced blocks are examples and are not read.
``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are logs that cite what was and
stay out.
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCUMENTS = ("README.md", "PARITY.md", "OBSERVABILITY.md", "CHAOS.md", "CONCURRENCY.md", "MIGRATION.md")
TREES = ("ray_tpu/", "tests/", "benchmarks/", "examples/")
QUOTED = re.compile(r"`([^`\n]+)`")
NAME = re.compile(r"^([\w./\-]+\.(?:py|jsonl|json|md))(?::[\d,\-]+|::[\w\[\]\-.:]+)?$")
SENTENCE_END = re.compile(r"(?<=[.!?])\s+(?=[A-Z`*(\[])")
FENCED = re.compile(r"^```.*?^```", re.M | re.S)


def _sentences(text):
    for paragraph in re.split(r"\n\s*\n", FENCED.sub("", text)):
        yield from SENTENCE_END.split(" ".join(paragraph.split()))


def _named_files(sentence):
    for quoted in QUOTED.findall(sentence):
        for token in quoted.split():
            m = NAME.match(token)
            if m and ("/" not in m.group(1) or m.group(1).startswith(TREES)):
                yield m.group(1)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    missing = [
        f"{name}  <-  {sentence[:160]}"
        for sentence in _sentences((REPO / document).read_text())
        if "git show" not in sentence
        for name in _named_files(sentence)
        if not (REPO / name).is_file()
    ]
    assert not missing, f"{document} names files that are not in the tree:\n" + "\n".join(missing)
