"""The documents name only files that exist.

A path in backticks in ``README.md``, ``PARITY.md`` or ``docs/*.md`` is a
promise to the reader; a PR that deletes a file and leaves its mention
fails here.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_SPAN = re.compile(r"`([^`\n]+)`")
_TREES = ("horovod_tpu/", "tools/", "tests/", "examples/", "benchmark/",
          "docs/")
_ROOT_PY = re.compile(r"^[A-Za-z_]\w*\.py$")
# What stands for a name the reader fills in, or for several.
_PLACEHOLDER = re.compile(r"[<>*…{}$]|\.\.\.")


def _candidates(span):
    """The path-like words of one backticked span (a span may be a whole
    command line), without line numbers, anchors or trailing punctuation."""
    for word in span.split():
        word = word.split("::")[0].split("#")[0]
        word = re.sub(r":\d+([-–,]\d+)*$", "", word).rstrip(".,;:)").lstrip("(")
        if word.startswith("./"):
            word = word[2:]
        if _PLACEHOLDER.search(word):
            continue
        if word.startswith(_TREES) or _ROOT_PY.match(word):
            yield word


def named_paths(text):
    return sorted({w for span in _SPAN.findall(text)
                   for w in _candidates(span)})


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_docs_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        paths = named_paths(f.read())
    missing = [p for p in paths
               if not os.path.exists(os.path.join(REPO, p))]
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_named_paths_reads_commands_and_skips_placeholders():
    text = ("run `python3 benchmark/run.py --workload <cell>` then read "
            "`tools/hvdtop.py`, `horovod_tpu/jax/train.py:17-21`, "
            "`tests/test_cache.py::test_x`, `docs/metrics.md#names`, "
            "`benchmark/_out/<cell>/`, `tools/*.py`, `gone.py`, `a.b.py`, "
            "`optax.adamw`")
    assert named_paths(text) == [
        "benchmark/run.py", "docs/metrics.md", "gone.py",
        "horovod_tpu/jax/train.py", "tests/test_cache.py",
        "tools/hvdtop.py"]
