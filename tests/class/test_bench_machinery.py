"""The bench's evidence machinery is load-bearing (round-3 VERDICT #1:
the driver artifact IS the number of record) — pin its helpers.

Covers: incremental field merge under leg failure, the single retry with
interrupt passthrough, fixed-cost subtraction guards, and the budget
shedding thresholds.  (The always-print finally in ``main`` is exercised
end-to-end by the driver-method runs, not here.)"""

import pytest

import bench


def test_minus_cost_guard():
    # subtract only when the run dwarfs the cost
    assert bench._minus_cost(1.0, 0.1) == pytest.approx(0.9)
    # below the 2x threshold: no subtraction (noise would go negative)
    assert bench._minus_cost(0.15, 0.1) == pytest.approx(0.15)
    assert bench._minus_cost(0.0, 0.1) == 0.0


def test_leg_retries_once_then_records_error(monkeypatch):
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)  # skip backoff
    fields = {}
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient")
        fields["x"] = 1

    assert bench._leg(fields, "demo", flaky) is True
    assert fields["x"] == 1 and len(calls) == 2
    assert "demo_error" not in fields

    fields2 = {}

    def broken():
        fields2["partial"] = 7  # merged BEFORE the failure
        raise ValueError("persistent")

    assert bench._leg(fields2, "bad", broken) is False
    # the error is recorded AND the partial field survives
    assert fields2["partial"] == 7
    assert fields2["bad_error"].startswith("ValueError")


def test_leg_interrupt_passes_through():
    def interrupted():
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        bench._leg({}, "ki", interrupted)


def test_over_budget_threshold(monkeypatch):
    monkeypatch.setattr(bench, "_BUDGET", 100.0)
    monkeypatch.setattr(bench.time, "perf_counter",
                        lambda: bench._T_START + 90.0)
    assert bench._over_budget(0.85, "x") is True
    assert bench._over_budget(0.95, "x") is False


def test_bench_refuses_to_measure_without_a_chip(monkeypatch):
    """No silent CPU run: off the chip the bench starts only when the CI
    smoke asks for the CPU backend by name."""
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert "BENCH_PLATFORM=cpu" in str(exc.value)


def _main_with_legs(monkeypatch, capsys, legs):
    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    monkeypatch.delenv("BENCH_JSON_OUT", raising=False)
    monkeypatch.setattr(
        bench, "_rest_of_main",
        lambda *a: a[-1].update(legs))  # fields is the last argument
    try:
        bench.main()
        rc = 0
    except SystemExit as e:
        rc = e.code
    import json

    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_bench_names_its_device_and_passes_when_every_leg_did(
        monkeypatch, capsys):
    rc, out = _main_with_legs(monkeypatch, capsys, {"graph_gflops": 1.0})
    assert rc == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["kind"] and out["device"]["count"] >= 1


def test_bench_exits_nonzero_when_a_leg_recorded_an_error(
        monkeypatch, capsys):
    """The JSON line still carries everything measured — and the exit
    code says a leg failed."""
    rc, out = _main_with_legs(
        monkeypatch, capsys,
        {"graph_gflops": 1.0, "dynamic_error": "RuntimeError: boom"})
    assert rc == 1
    assert out["graph_gflops"] == 1.0 and "dynamic_error" in out
