"""MCA-parameter doc-drift lint: registered params <-> OPERATIONS.md.

Every tunable the runtime registers (``mca_param.register``) is an
operator-facing contract: it appears in ``parsec-tools mca-params``, is
env-overridable as ``PARSEC_MCA_<framework>_<name>``, and operators
read ``docs/OPERATIONS.md`` to learn it exists.  The two drift apart
silently — a param lands without a doc row, or a doc row survives the
param's removal and operators tune a knob that no longer exists.

This lint closes the loop in BOTH directions, statically (a regex scan
over the source tree for ``register("<framework>", "<name>", ...)``
call sites — no imports, so params registered by rarely-loaded modules
are still seen):

* DOC001 — a registered param of an operator framework is not
  mentioned in OPERATIONS.md;
* DOC002 — OPERATIONS.md documents a param (a ``framework_name`` row
  in an ``| MCA param |`` table) that no source registers.

A param counts as documented when OPERATIONS.md backticks either its
full ``framework_name`` or its bare ``name`` (the compile-cache
section's ``PARSEC_MCA_runtime_<name>`` + bare-name idiom).
``tools check`` runs this beside the graph linter and the ABI lint.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

#: frameworks whose params are operator-facing contracts; params
#: registered under other frameworks (e.g. test-local ones) are exempt
FRAMEWORKS = ("runtime", "sched", "serve", "comm", "coll", "profiling")

#: a ``register(`` call whose first two arguments are string literals,
#: framework then name — module alias, method, and keyword forms
_REGISTER_RE = re.compile(
    r"""\bregister\(\s*
        ['"](?P<fw>[a-z_]+)['"]\s*,\s*
        ['"](?P<name>[a-z0-9_]+)['"]""",
    re.VERBOSE | re.DOTALL)

#: a documented table row: | `runtime_fusion` | default | meaning |
_DOC_ROW_RE = re.compile(r"^\|\s*`(?P<fw>[a-z]+)_(?P<name>[a-z0-9_]+)`\s*\|",
                         re.MULTILINE)

#: any backticked token (bare-name prose mentions)
_TICKED_RE = re.compile(r"`([A-Za-z0-9_.]+)`")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def registered_params(src_root: str = None,
                      frameworks: Optional[Sequence[str]] = FRAMEWORKS
                      ) -> Dict[Tuple[str, str], List[str]]:
    """Scan ``parsec_tpu/**/*.py`` for register() call sites; returns
    ``(framework, name) -> ["relative/source.py:line", ...]``, every
    site in walk order, of the ``frameworks`` asked for (``None``: all
    of them, the census of ``tests/analysis/test_doc_lint.py``)."""
    if src_root is None:
        src_root = os.path.join(_repo_root(), "parsec_tpu")
    out: Dict[Tuple[str, str], List[str]] = {}
    for dirpath, dirs, files in os.walk(src_root):
        dirs.sort()
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, "r", encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            rel = os.path.relpath(path, src_root)
            for m in _REGISTER_RE.finditer(text):
                key = (m.group("fw"), m.group("name"))
                if frameworks is None or key[0] in frameworks:
                    line = text.count("\n", 0, m.start()) + 1
                    out.setdefault(key, []).append(f"{rel}:{line}")
    return out


def documented_params(ops_path: str = None
                      ) -> Tuple[Dict[Tuple[str, str], int], Set[str]]:
    """Parse OPERATIONS.md; returns (table rows keyed (fw, name) ->
    line number, set of every backticked token for prose mentions)."""
    if ops_path is None:
        ops_path = os.path.join(_repo_root(), "docs", "OPERATIONS.md")
    with open(ops_path, "r", encoding="utf-8") as f:
        text = f.read()
    rows: Dict[Tuple[str, str], int] = {}
    for m in _DOC_ROW_RE.finditer(text):
        key = (m.group("fw"), m.group("name"))
        rows.setdefault(key, text.count("\n", 0, m.start()) + 1)
    ticked = set(_TICKED_RE.findall(text))
    return rows, ticked


def doc_findings(src_root: str = None, ops_path: str = None
                 ) -> List[Finding]:
    regs = registered_params(src_root)
    rows, ticked = documented_params(ops_path)
    out: List[Finding] = []
    for (fw, name), sites in sorted(regs.items()):
        full = f"{fw}_{name}"
        if full not in ticked and name not in ticked:
            out.append(Finding(
                "DOC001", f"MCA param {full} (registered in {sites[0]}) is "
                "not documented in docs/OPERATIONS.md",
                dep=full))
    row_fw_ok = {(fw, name) for fw, name in regs}
    # a doc row `fw_rest` may split ambiguously (fw_a, b_c): accept it
    # when ANY registered param's full name equals the row's token
    full_names = {f"{fw}_{name}" for fw, name in regs}
    for (fw, name), line in sorted(rows.items(), key=lambda kv: kv[1]):
        if fw not in FRAMEWORKS:
            continue  # metric tables etc. share the | `...` | shape
        full = f"{fw}_{name}"
        if full not in full_names and (fw, name) not in row_fw_ok:
            out.append(Finding(
                "DOC002", f"docs/OPERATIONS.md line {line} documents MCA "
                f"param {full} but no source registers it (removed knob, "
                "or a typo in the row)",
                dep=full))
    return out
