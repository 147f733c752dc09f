"""The documents name files that exist: every repo-relative ``*.py``,
``*.json`` or ``*.md`` path that ``README.md``, ``docs/*.md`` and
``benchmarks/README.md`` put in backticks is in the tree. A PR that
deletes a tool has to delete the sentence that sells it."""

import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "benchmarks/README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"[\w.<>*{}\[\]/-]+\.(?:py|json|md)\b(?!l)")
# Not claims about the tree: patterns and placeholders
# (`configs/<config>.json`, `trace_p<P>_a<A>.json`), and the two trace
# files a telemetry run writes into its own directory.
_PLACEHOLDER = re.compile(r"[*<>{}\[\]]")
_WRITTEN_BY_A_RUN = {"trace.json", "trace_merged.json"}


def _tree_basenames() -> set[str]:
    """File names of the tree, the directories .gitignore lists left out
    (a copy of the parent commit in one of them proves nothing)."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {ln.strip().rstrip("/") for ln in f if ln.strip().endswith("/")}
    names: set[str] = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in ignored]
        names.update(files)
    return names


def _exists(path: str, doc: str, basenames: set[str]) -> bool:
    """A bare file name may lie anywhere in the tree; a path resolves
    against the root, the package (`serving/engine.py`) or the document's
    own directory (`harness/peaks.py` in benchmarks/README.md)."""
    if "/" not in path:
        return path in basenames
    bases = ("", "distributeddeeplearning_tpu", os.path.dirname(doc))
    return any(os.path.exists(os.path.join(REPO, b, path)) for b in bases)


def test_backticked_paths_exist():
    basenames = _tree_basenames()
    missing = []
    for doc in DOCS:
        with open(os.path.join(REPO, doc)) as f:
            for n, line in enumerate(f, 1):
                for span in _SPAN.findall(line):
                    for path in _PATH.findall(span):
                        if (_PLACEHOLDER.search(path)
                                or path in _WRITTEN_BY_A_RUN):
                            continue
                        if not _exists(path, doc, basenames):
                            missing.append(f"{doc}:{n}: {path}")
    assert not missing, "\n".join(missing)
