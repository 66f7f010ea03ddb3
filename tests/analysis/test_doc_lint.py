"""MCA-param doc-drift lint (analysis/doc_lint.py): the shipped tree
is drift-free both directions, and synthetic drift — an undocumented
registration, a documented ghost knob — fires DOC001/DOC002; and the
census of the registry: one site a name, no name with one value."""

import os
import re

import pytest

from parsec_tpu.analysis import doc_lint


def test_shipped_tree_is_drift_free():
    assert doc_lint.doc_findings() == []


def test_registered_params_sees_the_real_registry():
    regs = doc_lint.registered_params()
    # anchor on long-standing knobs from distinct frameworks
    assert ("runtime", "comm_eager_limit") in regs
    assert any(fw == "profiling" for fw, _ in regs)


def _tree(tmp_path, source, doc):
    src = tmp_path / "src"
    src.mkdir()
    (src / "knobs.py").write_text(source)
    ops = tmp_path / "OPERATIONS.md"
    ops.write_text(doc)
    return str(src), str(ops)


_DOC_OK = """\
| param | default | meaning |
|---|---|---|
| `runtime_alpha` | 1 | documented knob |
"""


def test_undocumented_registration_fires_doc001(tmp_path):
    src, ops = _tree(
        tmp_path,
        'mca_param.register("runtime", "alpha", 1)\n'
        'mca_param.register("runtime", "ghost", 0, help="undocumented")\n',
        _DOC_OK)
    findings = doc_lint.doc_findings(src, ops)
    assert [f.code for f in findings] == ["DOC001"]
    assert "runtime_ghost" in findings[0].message


def test_bare_name_prose_mention_counts_as_documented(tmp_path):
    """A knob explained in prose as `beta` (not a table row) passes —
    the lint demands documentation, not a specific layout."""
    src, ops = _tree(
        tmp_path,
        'mca_param.register("runtime", "beta", 2)\n',
        "set `beta` to taste\n")
    assert doc_lint.doc_findings(src, ops) == []


def test_documented_ghost_knob_fires_doc002(tmp_path):
    src, ops = _tree(
        tmp_path,
        'mca_param.register("runtime", "alpha", 1)\n',
        _DOC_OK + "| `runtime_removed_knob` | 9 | no longer exists |\n")
    findings = doc_lint.doc_findings(src, ops)
    assert [f.code for f in findings] == ["DOC002"]
    assert "runtime_removed_knob" in findings[0].message


def test_non_mca_tables_are_ignored(tmp_path):
    """Metric/finding tables share the | `token` | row shape; only
    rows whose prefix is a real MCA framework can fire DOC002."""
    src, ops = _tree(
        tmp_path,
        'mca_param.register("runtime", "alpha", 1)\n',
        _DOC_OK + "| `obs_queue_p99` | gauge | a metric, not a knob |\n")
    assert doc_lint.doc_findings(src, ops) == []


# -- the census: one site a name, and a second value or a reason ----------

#: names no test, benchmark file or example sets, with why each stays a
#: parameter: what a deployment or an operator decides, not the code
DEPLOYMENT = {
    "debug_verbose": "an operator's log level",
    "debug_color": "an operator's terminal",
    "debug_history_size": "sizes the operator's debug history ring",
    "profiling_fr_events": "sizes the operator's flight recorder",
    "device_tpu_device_index": "which chip of the host a rank drives",
    "runtime_bind_threads": "core pinning is the host's layout",
    "runtime_arena_max_used": "a host's cap on outstanding comm buffers",
    "runtime_comm_send_timeout": "a network's latency",
    "runtime_comm_close_timeout": "a network's latency",
    "runtime_coll_err_grace": "a network's latency",
    "runtime_comm_max_frame": "refuses oversized frames from the wire",
    "runtime_watchdog_window": "an operator's patience before a hang "
                               "is diagnosed",
    "runtime_compile_bcast": "off on a mesh of unlike hosts, whose "
                             "executables do not load on each other",
    "runtime_native_conformance": "an operator's diagnostic mode "
                                  "(certifies a pump run's event stream)",
}

_ALL = doc_lint.registered_params(frameworks=None)
_NAMING_ROOTS = ("tests", "benchmark", "examples")


def _naming_text():
    """Everything under tests/, benchmark/ and examples/ that can name a
    parameter, this file aside (it names the reasons, not a value)."""
    root = doc_lint._repo_root()
    chunks = []
    for top in _NAMING_ROOTS:
        for dirpath, _dirs, files in os.walk(os.path.join(root, top)):
            for fn in files:
                path = os.path.join(dirpath, fn)
                if not fn.endswith((".py", ".json", ".jdf", ".md", ".sh")) \
                        or os.path.samefile(path, __file__):
                    continue
                with open(path, "r", encoding="utf-8", errors="ignore") as f:
                    chunks.append(f.read())
    return "\n".join(chunks)


@pytest.mark.parametrize("framework", sorted({fw for fw, _ in _ALL}))
def test_census_one_site_and_a_second_value_or_a_reason(framework):
    """Every name of the framework is registered at exactly ONE site (a
    default cannot differ between two files), and is named by a test, a
    file of the benchmark or an example (somebody needs a second value)
    or stands in ``DEPLOYMENT`` with its reason.  A name that is neither
    is a constant: see ``CHANGES.md`` PR 42 for the eighteen that
    went."""
    text = _naming_text()
    twice, unnamed = {}, []
    for (fw, name), sites in sorted(_ALL.items()):
        if fw != framework:
            continue
        if len(sites) != 1:
            twice[f"{fw}_{name}"] = sites
        full = f"{fw}_{name}"
        pair = re.compile(r"""['"]%s['"]\s*,\s*['"]%s['"]""" % (fw, name))
        if full not in DEPLOYMENT and full not in text \
                and not pair.search(text):
            unnamed.append(full)
    assert not twice, f"registered at more than one site: {twice}"
    assert not unnamed, (
        f"{unnamed}: no test, benchmark file or example names them and "
        "DEPLOYMENT gives no reason: a constant, not a parameter")


def test_census_reasons_are_of_names_that_exist():
    """A reason outlives its name no more than a doc row does."""
    full = {f"{fw}_{name}" for fw, name in _ALL}
    assert set(DEPLOYMENT) <= full, sorted(set(DEPLOYMENT) - full)
    assert len(_ALL) <= 48
