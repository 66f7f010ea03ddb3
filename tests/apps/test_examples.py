"""Smoke-run the tutorial examples (reference examples/Ex00..Ex07 +
dtd examples are built and run by CI; here each example is executed
in-process and must self-check)."""

import os
import runpy
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "..", "examples")

ALL = [
    "ex00_startstop.py",
    "ex01_helloworld.py",
    "ex02_chain.py",
    "ex03_chain_multirank.py",
    "ex04_chaindata.py",
    "ex05_broadcast.py",
    "ex06_raw.py",
    "ex07_raw_ctl.py",
    "ex08_tpu_graph.py",
    "ex09_jdf_graph.py",
    "ex10_sequence_parallel.py",
    "ex11_pallas_native.py",
    "ex12_qr_lu.py",
    "ex13_segmented_native_dist.py",
    "ex14_round4_features.py",
    "ex15_spd_inverse.py",
    os.path.join("dtd", "dtd_helloworld.py"),
    os.path.join("dtd", "dtd_hello_arg.py"),
    os.path.join("dtd", "dtd_untied.py"),
    os.path.join("dtd", "dtd_potrf.py"),
]


@pytest.mark.parametrize("script", ALL, ids=[os.path.basename(s) for s in ALL])
def test_example_runs(script, capsys):
    path = os.path.abspath(os.path.join(EXAMPLES, script))
    runpy.run_path(path, run_name="__main__")
    out = capsys.readouterr().out
    assert ":" in out  # every example prints a self-check summary line
