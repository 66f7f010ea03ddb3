"""engine-verify (analysis/engine_verify.py): the lifecycle model
checker is silent on the healthy engine model and every seeded fault
fires its ENG code; the conformance automaton certifies real drained
streams and rejects doctored ones; the ABI lint passes the shipped
spec/so pair and catches seeded drift; clang-tidy absence is an
explicit ENG021 skip, never a silent pass."""

import os
import shutil

import pytest

from parsec_tpu.analysis import engine_verify as ev
from parsec_tpu.native import abi

# ---------------------------------------------------------------------------
# model checker: healthy = silent, exhaustively
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [2, 3])
def test_healthy_model_is_silent(workers):
    findings, stats = ev.model_findings(workers=workers)
    assert findings == []
    # every seed DAG actually explored (no truncation, terminals seen)
    for dag in ev.SEED_DAGS:
        st = stats[dag.name]
        assert st.states > 0 and st.terminals > 0, dag.name
        assert not st.truncated, dag.name


def test_state_budget_truncation_is_flagged():
    """An exhausted exploration budget must be visible, not a pass."""
    dag = ev.SEED_DAGS[2]  # diamond4: > 3 reachable states
    m = ev.EngineModel(dag, policy="prio")
    c = ev.ModelChecker(m, workers=2, max_states=3)
    c.run()
    assert c.stats.truncated


# the mutation matrix of the module docstring: every lifecycle
# invariant is demonstrably live — each seeded fault fires its code
_MUTATION_CODE = {
    "lost_retire": "ENG010",
    "double_retire": "ENG010",
    "early_quiesce": "ENG011",
    "double_publish": "ENG012",
    "drop_event": "ENG012",
    "retire_before_deps": "ENG012",
    "wdrr_lose_bin": "ENG013",
}


def test_mutation_table_matches_module():
    assert set(_MUTATION_CODE) == set(ev.MUTATIONS)


@pytest.mark.parametrize("mutation", sorted(_MUTATION_CODE))
def test_seeded_mutation_fires_its_code(mutation):
    findings, _ = ev.model_findings(mutate=mutation)
    codes = {f.code for f in findings}
    assert _MUTATION_CODE[mutation] in codes, (mutation, codes)


# ---------------------------------------------------------------------------
# conformance replay
# ---------------------------------------------------------------------------

_CHAIN2 = ev.SeedDag("chain2", 2, ((0, 1),))

# the engine's emission order for a 2-task chain: root publishes at
# commit; done(0) emits the successor's DEP_DEC (ready) and PUBLISH
# before task 0's own RETIRE; done(1) retires the sink.
_GOOD_STREAM = (
    (ev.EVT_PUBLISH, 0, 0),
    (ev.EVT_DEP_DEC, 1, 1),
    (ev.EVT_PUBLISH, 1, 0),
    (ev.EVT_RETIRE, 0, 1),
    (ev.EVT_RETIRE, 1, 1),
)


def test_conformance_accepts_faithful_stream():
    assert ev.conformance_findings(_CHAIN2, _GOOD_STREAM) == []


@pytest.mark.parametrize("doctor, what", [
    (lambda s: s[:-1], "dropped final retire"),
    (lambda s: s + (s[-1],), "duplicated retire"),
    (lambda s: s[1:], "publish lost"),
    (lambda s: (s[0], s[2]) + s[1:], "publish before ready dep-dec"),
    (lambda s: s, "engine says quiesced=False"),
])
def test_conformance_rejects_doctored_stream(doctor, what):
    events = doctor(_GOOD_STREAM)
    quiesced = what != "engine says quiesced=False"
    findings = ev.conformance_findings(_CHAIN2, events, quiesced=quiesced)
    assert findings, what
    assert all(f.code == "ENG014" for f in findings), what


def test_native_conformance_certifies_real_pump_runs():
    from parsec_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    findings, stats = ev.native_conformance(nt=3, seeds=(0, 1))
    assert findings == []
    assert stats["runs"] == 2 and stats["events"] > 0


# ---------------------------------------------------------------------------
# ABI contract lint
# ---------------------------------------------------------------------------


def test_shipped_abi_is_clean():
    from parsec_tpu import native

    lib = native.lib_path() if os.path.exists(native.lib_path()) else None
    assert abi.abi_findings(lib, native._SRC_DIR) == []


def test_abi_catches_signature_drift(tmp_path, monkeypatch):
    """A drifted source prototype (extra parameter) fires ENG003, and a
    brand-new undeclared export fires ENG002 — both without touching
    the real tree."""
    from parsec_tpu import native

    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    graph = src / "graph.cpp"
    body = graph.read_text()
    assert "void pz_graph_seal(void* gp)" in body
    body = body.replace("void pz_graph_seal(void* gp)",
                        "void pz_graph_seal(void* gp, int32_t hard)")
    body += ('\nextern "C" {\n'
             'void pz_graph_rogue(void* gp) { (void)gp; }\n'
             '}\n')
    graph.write_text(body)
    findings = abi.abi_findings(None, str(src))
    codes = {f.code for f in findings}
    assert "ENG003" in codes and "ENG002" in codes
    drift = [f for f in findings if f.code == "ENG003"]
    assert any("pz_graph_seal" in f.message for f in drift)


def test_abi_catches_dropped_definition(tmp_path):
    """Deleting a spec'd entry point from the source fires ENG004."""
    from parsec_tpu import native

    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    graph = src / "graph.cpp"
    body = graph.read_text().replace("pz_graph_seal", "pz_graph_sea1")
    graph.write_text(body)
    codes = {f.code for f in abi.abi_findings(None, str(src))}
    assert "ENG004" in codes


def test_required_symbols_derive_from_spec():
    """REQUIRED_SYMBOLS is a view of the spec, not a second list that
    can drift from it."""
    assert set(abi.required_symbols()) <= set(abi.SPEC)


# ---------------------------------------------------------------------------
# clang-tidy leg
# ---------------------------------------------------------------------------


def test_tidy_absence_is_explicit_skip(monkeypatch):
    monkeypatch.setattr(shutil, "which", lambda name: None)
    findings = ev.tidy_findings()
    assert [f.code for f in findings] == ["ENG021"]


def test_tidy_failure_to_run_is_explicit_skip(tmp_path):
    """A binary that cannot execute reports ENG021, never a pass."""
    bogus = tmp_path / "clang-tidy"
    bogus.write_text("")  # exists but not executable
    findings = ev.tidy_findings(binary=str(bogus))
    assert findings and all(f.code == "ENG021" for f in findings)


# ---------------------------------------------------------------------------
# aggregate entry point
# ---------------------------------------------------------------------------


def test_verify_engine_runs_requested_legs_only():
    findings, stats = ev.verify_engine(legs=("abi", "model"))
    assert set(stats) == {"abi", "model"}
    assert [f for f in findings if f.code != "ENG021"] == []


# ---------------------------------------------------------------------------
# CLI: tools engine-verify / tools check
# ---------------------------------------------------------------------------


def test_tools_engine_verify_abi_model_exits_zero(capsys):
    from parsec_tpu.profiling import tools

    rc = tools.main(["engine-verify", "--abi", "--model"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 error(s)" in out
    for dag in ev.SEED_DAGS:  # per-DAG exploration stats are printed
        assert f"model {dag.name}:" in out


def test_tools_engine_verify_tidy_skip_is_not_fatal(capsys, monkeypatch):
    from parsec_tpu.profiling import tools

    monkeypatch.setattr(ev.shutil, "which", lambda name: None)
    rc = tools.main(["engine-verify", "--tidy"])
    out = capsys.readouterr().out
    assert rc == 0                 # skipped, visibly, but not a failure
    assert "ENG021" in out and "1 skipped" in out


def test_tools_engine_verify_strict_ignores_skips(capsys, monkeypatch):
    """--strict promotes warnings, never the explicit ENG021 skip."""
    from parsec_tpu.profiling import tools

    monkeypatch.setattr(ev.shutil, "which", lambda name: None)
    assert tools.main(["engine-verify", "--tidy", "--strict"]) == 0


def test_tools_check_aggregate_gate(capsys, monkeypatch):
    from parsec_tpu.profiling import tools

    monkeypatch.setattr(ev.shutil, "which", lambda name: None)
    rc = tools.main(["check"])
    out = capsys.readouterr().out
    assert rc == 0
    # the summary table covers every section
    for section in ("graph-lint", "abi", "model", "doc-drift", "tidy"):
        assert section in out
    assert "check: 5 section(s), 0 error(s)" in out
