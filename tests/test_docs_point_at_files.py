"""The documents point at files that exist.

One case per document a newcomer reads first (``README.md``, ``PARITY.md``
and every ``docs/*.md``). ``ROADMAP.md``, ``PERF.md`` and ``CHANGES.md``
tell history, and ``chipbench/README.md`` belongs to the benchmark: they
are not cases. A back-ticked token that looks like a path in this
repository must resolve against the repository root, then the package
directory (the README writes ``kvcache/router.py``), then ``docs/``.
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
ROOTS = (REPO, REPO / "llm_d_kv_cache_manager_tpu", REPO / "docs")

DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")
)

FILE_SUFFIXES = (".py", ".md", ".json", ".yaml", ".txt")

#: The one allow-list: what a document may name that is not in the tree.
NOT_OURS = {
    # the reference project's own paths (llm-d-kv-cache-manager, Go;
    # ``SURVEY.md`` and ``BASELINE.md`` map them). Its ``*.go`` files are
    # skipped by suffix.
    "pkg/utils", "examples/kv_events/offline", "examples/kv_cache_index",
    "examples/kv_cache_aware_scorer",
    # made at run time, never committed
    ".jax_compile_cache/",
    # not paths: a unit, an alternation, a model id
    "bytes/page", "used/total/fill", "JetLM/SDAR-30B-A3B-Chat",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_LINE_SUFFIX = re.compile(r"(:\d+(-\d+)?)+$")
_PATHLIKE = re.compile(r"^[\w.\-/]+$")  # no spaces, globs, URLs, <placeholders>


def path_tokens(text):
    """Back-ticked tokens of ``text`` that look like paths of this repo."""
    text = re.sub(r"```.*?```", "", text, flags=re.S)  # fenced blocks: commands
    found = []
    for span in _SPAN.findall(text):
        token = _LINE_SUFFIX.sub("", span.strip().split("::")[0])  # file::test
        if not _PATHLIKE.match(token) or token.startswith("/"):  # `/stats`
            continue
        if not ("/" in token or token.endswith(FILE_SUFFIXES)):
            continue
        if token.endswith(".go") or token.rsplit("/", 1)[-1].isdigit():
            continue  # the reference's source; a ratio such as `scale/2`
        if token not in NOT_OURS:
            found.append(token)
    return found


def exists(token):
    """``token`` is a file or directory under one of ``ROOTS``, or names an
    attribute of a module there (``ops/sampling.spec_sample``)."""
    module, _, attribute = token.rpartition(".")
    definition = re.compile(rf"^\s*(def|class) {re.escape(attribute)}\b", re.M)
    for root in ROOTS:
        if (root / token).exists():
            return True
        source = root / f"{module}.py"
        if "/" in module and source.is_file() and definition.search(source.read_text()):
            return True
    return False


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_points_at_files_that_exist(document):
    tokens = path_tokens((REPO / document).read_text(encoding="utf-8"))
    assert tokens, f"{document}: no path found; has the pattern rotted?"
    missing = sorted({t for t in tokens if not exists(t)})
    assert not missing, f"{document} names files that are not in the tree: {missing}"

