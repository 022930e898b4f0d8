"""The documents a new reader opens first name files that exist.

A backticked path under one of the repository's top-level directories
that ends in ``.py``, ``.sh`` or ``.json`` (a ``:line`` or ``::test``
suffix and a ``*`` wildcard allowed) must name a file of this checkout:
a document that describes a tool that is gone sends every reader after
it.  And an environment variable of the library's (``MPI4JAX_TPU_*``,
``T4J_*``) that a document names must be one the sources read: a switch
that is gone, still documented, is set by a reader and does nothing.
"""

import functools
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


_VARIABLE = re.compile(r"\b((?:MPI4JAX_TPU|T4J)_(?:[A-Z0-9_]*[A-Z0-9])?)(_?\*)?")


def variables_named(text):
    """The library's environment variables in ``text``, as ``(name,
    is_prefix)``: ``T4J_BACKOFF_*`` names the prefix ``T4J_BACKOFF``."""
    return sorted({(name, bool(star)) for name, star in _VARIABLE.findall(text)
                   if star or not name.endswith("_")})


@functools.cache
def variables_read():
    """Every such name in the sources that can read one: the package's
    ``.py``, ``.cc`` and ``.h``, and ``setup.py`` (the install-time
    prebuild's switch)."""
    sources = [REPO / "setup.py", *(
        p for ext in ("py", "cc", "h")
        for p in (REPO / "mpi4jax_tpu").rglob(f"*.{ext}"))]
    return frozenset(
        name for p in sources
        for name, _ in variables_named(p.read_text(errors="replace")))


def test_variables_named_reads_names_and_prefixes():
    text = ("`T4J_RETRY_MAX=0`, `T4J_BACKOFF_*`/`T4J_RETRY_*`, every `T4J_*` "
            "and MPI4JAX_TPU_NO_FENCE, not MY_T4J_THING")
    assert variables_named(text) == [
        ("MPI4JAX_TPU_NO_FENCE", False), ("T4J_", True), ("T4J_BACKOFF", True),
        ("T4J_RETRY", True), ("T4J_RETRY_MAX", False)]


@pytest.mark.parametrize("doc", [
    "docs/api.md", "docs/performance.md", "docs/observability.md",
    "docs/failure-semantics.md", "docs/serving.md", "docs/async.md",
    "PARITY.md",
])
def test_every_variable_a_document_names_is_one_the_sources_read(doc):
    named = variables_named((REPO / doc).read_text())
    assert named, f"{doc} names no variable: the pattern has gone blind"
    read = variables_read()

    def is_read(name, prefix):
        return any(r.startswith(name) for r in read) if prefix else name in read

    assert [name + "*" * prefix for name, prefix in named
            if not is_read(name, prefix)] == []
