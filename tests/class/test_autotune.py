"""nb/wave autotuner (parsec_tpu.tuning): store round trips, winner
selection, ``nb="auto"`` resolution in the segmented drivers, and the
``tools autotune`` CLI."""

import json
import os

import numpy as np
import pytest

from parsec_tpu import tuning


@pytest.fixture
def store(tmp_path):
    return tuning.TuningStore(str(tmp_path / "autotune"))


def test_autotune_picks_fastest_and_persists(store):
    times = {16: 0.5, 32: 0.1, 64: 0.3}
    calls = []

    def runner(nb):
        calls.append(nb)
        return times[nb]

    doc = tuning.autotune("demo", 128, "float32", param="nb",
                          candidates=[16, 32, 64], runner=runner,
                          reps=2, store=store)
    assert doc["best"] == 32
    # one warmup + reps timed calls per candidate
    assert calls.count(16) == 3 and calls.count(32) == 3
    key = tuning.tune_key("demo", 128, "float32",
                          tuning._device_kind(), "nb")
    assert store.load(key)["best"] == 32
    assert tuning.resolve_nb("demo", 128, "float32", store=store) == 32


def test_autotune_survives_failing_candidate(store):
    def runner(nb):
        if nb == 64:
            raise MemoryError("tile too big")
        return 1.0 / nb

    doc = tuning.autotune("demo", 128, "float32", param="nb",
                          candidates=[16, 64], runner=runner,
                          reps=1, store=store)
    assert doc["best"] == 16
    assert "64" in doc["failures"]


def test_autotune_all_failed_raises(store):
    def runner(nb):
        raise RuntimeError("no")

    with pytest.raises(RuntimeError, match="every candidate failed"):
        tuning.autotune("demo", 64, "float32", param="nb",
                        candidates=[16], runner=runner, store=store)


def test_resolve_nb_divisor_guard(store):
    def runner(nb):
        return 0.1

    tuning.autotune("demo", 100, "float32", param="nb",
                    candidates=[48], runner=runner, reps=1, store=store)
    # 48 does not divide 100: the default stands
    assert tuning.resolve_nb("demo", 100, "float32", store=store,
                             default=32, divides=100) == 32
    assert tuning.resolve_nb("demo", 100, "float32", store=store,
                             default=32) == 48


def test_auto_nb_passthrough_and_default_clipping():
    # explicit values pass through untouched
    assert tuning.auto_nb(256, "demo", 512) == 256
    # auto with nothing tuned: the default clips to a divisor of N
    assert tuning.auto_nb("auto", "never_tuned_op", 96,
                          default=512, divides=96) in (32, 16, 8, 4, 2, 1)


def test_corrupt_tuning_entry_reads_as_absent(store):
    key = tuning.tune_key("demo", 64, "float32", "cpu", "nb")
    os.makedirs(store.dir, exist_ok=True)
    with open(os.path.join(store.dir, f"{key}.json"), "w") as f:
        f.write("{ not json")
    assert store.load(key) is None


def test_segmented_cholesky_nb_auto_uses_tuned_winner(monkeypatch,
                                                      tmp_path):
    """ops.* pick the tuned nb by default: seed a winner for
    (dpotrf_seg, N, f32, this device generation), construct with
    nb="auto", and the driver must adopt it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from parsec_tpu import Context
    from parsec_tpu.ops.segmented_chol import SegmentedCholesky

    n = 128
    st = tuning.default_store()
    kind = tuning._device_kind()
    st.save(tuning.tune_key("dpotrf_seg", n, "float32", kind, "nb"),
            {"best": 32, "param": "nb"})
    ctx = Context(nb_cores=1)
    try:
        sc = SegmentedCholesky(ctx, n)  # nb defaults to "auto"
        assert sc.nb == 32
        sc2 = SegmentedCholesky(ctx, n, nb=64)  # explicit wins
        assert sc2.nb == 64
        # untuned size: the clipped default stands (512 -> divisor of n)
        sc3 = SegmentedCholesky(ctx, 96)
        assert 96 % sc3.nb == 0
    finally:
        ctx.fini()


def test_tools_autotune_cli_real_dpotrf(monkeypatch, tmp_path, capsys):
    """End-to-end: the CLI times real (tiny) dynamic dpotrf runs per nb
    candidate and persists a winner nb='auto' resolves."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from parsec_tpu.profiling.tools import main as tools_main

    rc = tools_main(["autotune", "--op", "dpotrf", "--n", "64",
                     "--nb", "16,32", "--reps", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "best nb=" in out
    best = tuning.resolve_nb("dpotrf", 64, "float32")
    assert best in (16, 32)
