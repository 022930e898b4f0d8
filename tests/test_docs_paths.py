"""The documents a new reader opens first name files that exist.

A backticked path under one of the repository's top-level directories
that ends in ``.py``, ``.sh`` or ``.json`` (a ``:line`` or ``::test``
suffix and a ``*`` wildcard allowed) must name a file of this checkout:
a document that describes a tool that is gone sends every reader after
it.
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TOP_DIRS = sorted(
    p.name for p in REPO.iterdir()
    if p.is_dir() and not p.name.startswith((".", "_"))
)
_PATH = re.compile(
    r"(?<![\w./-])((?:%s)/[\w.*/-]+\.(?:py|sh|json))(?![\w/-])"
    % "|".join(map(re.escape, TOP_DIRS))
)


def paths_named(text):
    """Every such path inside a backticked span of ``text``."""
    return sorted({
        path
        for span in re.findall(r"`([^`\n]+)`", text)
        for path in _PATH.findall(span)
    })


def test_paths_named_reads_suffixes_and_skips_package_relative_names():
    text = (
        "`python tools/ci_smoke.sh lint`, `tests/proc/test_x.py::test_y`, "
        "`mpi4jax_tpu/ops/_core.py:549`, `ops/_core.py`, `tools/*_smoke.py` "
        "and tools/not_in_backticks.py"
    )
    assert paths_named(text) == [
        "mpi4jax_tpu/ops/_core.py", "tests/proc/test_x.py",
        "tools/*_smoke.py", "tools/ci_smoke.sh",
    ]


@pytest.mark.parametrize("doc", [
    "README.md", "docs/shallow-water.md", "docs/performance.md",
    "docs/observability.md", "docs/serving.md",
])
def test_every_path_a_document_names_exists(doc):
    named = paths_named((REPO / doc).read_text())
    assert named, f"{doc} names no file: the pattern has gone blind"
    missing = [p for p in named if not any(REPO.glob(p))]
    assert missing == []
