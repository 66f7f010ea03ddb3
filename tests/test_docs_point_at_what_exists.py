"""The documents a reader is sent to name files that exist: every
back-ticked path ending in ``.py``, ``.json``, ``.md``, ``.jdf`` or
``.cpp`` and every ``python <file>`` command of the user-facing documents
resolves in this tree.  (``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md``
are history and may name what went.)"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ("README.md", "docs/OPERATIONS.md", "docs/TRACING.md",
             "docs/USERGUIDE.md", "PARITY.md", ".github/workflows/main.yml")

#: where a document's relative paths start: the repo, the package (the
#: documents write ``dsl/ptg.py`` for ``parsec_tpu/dsl/ptg.py``), the
#: documents' own directory, the engine's sources
BASES = ("", "parsec_tpu", "docs", "native/src", "tests")

#: files of the READER's, which the documents tell them to write or
#: which a run leaves behind
THEIRS = {"my_app.py", "my_mesh.py", "merged.json", "all.json"}

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+\.(?:py|json|md|jdf|cpp)$")
_COMMAND = re.compile(r"\bpython3?\s+((?!-)[\w./-]+\.py)\b")

_SKIP_DIRS = {".git", ".chipwork", "chiprun_out", "__pycache__",
              ".parsec_tpu_cache", ".bench_trace", ".pytest_cache"}


def _basenames():
    names = set()
    for _dirpath, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        names.update(files)
    return names


def _exists(path, basenames):
    if path in THEIRS or path.startswith("/") \
            or (path.startswith(".") and "/" not in path):
        return True  # (absolute, or a suffix such as ``.meta.json``)
    if "/" not in path:  # a file spoken of by its name alone
        return path in basenames
    return any(os.path.exists(os.path.join(ROOT, base, path))
               for base in BASES)


def _cited(text):
    """The paths a document's text names: back-ticked ones (a trailing
    ``:line`` or ``:line-line`` aside) and ``python <file>`` commands."""
    out = set(_COMMAND.findall(text))
    for token in _TICKED.findall(text):
        token = re.sub(r"(?::[\d,:-]+)+$", "", token.strip())
        if _PATH.match(token):
            out.add(token)
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_points_at_what_exists(document):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        cited = _cited(f.read())
    basenames = _basenames()
    missing = sorted(p for p in cited if not _exists(p, basenames))
    assert not missing, (
        f"{document} names files that are not in the tree: {missing}")


def test_a_citation_of_a_file_that_went_is_caught(tmp_path):
    text = ("see `no_such_bench.py` and `docs/OLD.md:12`; run\n"
            "    python3 tools/gone.py --fast\n"
            "but `parsec_tpu/native`, `<cell>.json` and `python -m x.y` "
            "are no paths, and `README.md:3` and `dsl/ptg.py:96-99` exist")
    cited = _cited(text)
    assert cited == {"no_such_bench.py", "docs/OLD.md", "tools/gone.py",
                     "README.md", "dsl/ptg.py"}
    names = _basenames()
    assert sorted(p for p in cited if not _exists(p, names)) == [
        "docs/OLD.md", "no_such_bench.py", "tools/gone.py"]
