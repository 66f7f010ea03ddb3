"""The reduction from a profiler trace to busy seconds, idle share and
named idle gaps: on synthetic events, and on a small trace recorded on a
TPU v5e (``recorded/tiny_pump.xplane.pb``: two solves of the pump driver
at N=1024, nb=256)."""

import os

import pytest

from benchmark.trace import reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "tiny_pump.xplane.pb")


def test_merge_is_the_union():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == \
        [(0, 4), (5, 12)]
    assert tr.length(tr.merge([(0, 10), (2, 3), (4, 20)])) == 20


def test_clip_keeps_what_is_inside_the_windows():
    assert tr.clip([(0, 10), (15, 30)], [(5, 20), (25, 40)]) == \
        [(5, 10), (15, 20), (25, 30)]


def test_gaps_are_the_idle_stretches_of_each_window():
    busy = tr.merge([(2, 4), (6, 7)])
    assert tr.gaps(busy, [(0, 10)]) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], [(0, 10)]) == [(0, 10)]
    assert tr.gaps(busy, [(0, 3), (6, 9)]) == [(0, 2), (7, 9)]


def _events():
    host = [("bench:solve", 0, 1000), ("bench:attach", 0, 100),
            ("bench:run", 100, 900), ("bench:flush", 900, 1000),
            ("bench:check", 1000, 1500),         # outside the window
            ("bench:solve", 2000, 3000), ("bench:run", 2000, 3000),
            ("DeferredTpuAllocator::Allocate", 150, 390),
            ("python", 100, 900), ("D2H Dispatch", 905, 990)]
    device = {0: [("fusion", 100, 150), ("fusion", 400, 500),
                  ("copy", 450, 600),             # overlaps a fusion
                  ("slice", 880, 900),
                  ("fusion", 1200, 1400),         # between the solves
                  ("custom-call", 2500, 2600)],
              1: [("fusion", 0, 1000), ("fusion", 2000, 3000)]}
    return tr.Events(device=device, host=host)


def test_summary_busy_idle_and_ops():
    s = tr.summarize(_events(), chips=2)
    assert s.solves == 2 and s.window_s == pytest.approx(2000e-9)
    # chip 0: 50 + (400..600 = 200) + 20 + 100 inside the windows
    assert s.busy_by_chip[0] == pytest.approx(370e-9)
    assert s.busy_by_chip[1] == pytest.approx(2000e-9)
    assert s.busy_s == pytest.approx(1185e-9)          # mean of the chips
    assert s.idle_pct_worst == pytest.approx(100 * (1 - 370 / 2000))
    ops = dict(s.device_ops)
    assert ops["fusion"] == pytest.approx((50 + 100 + 2000) * 1e-9)
    assert ops["copy"] == pytest.approx(150e-9)
    assert "custom-call" in ops


def test_gaps_are_named_by_phase_and_host_event():
    s = tr.summarize(_events(), chips=1)
    gaps = dict((n, d) for n, d in s.idle_gaps)
    # the longest gap of chip 0: 2000..2500 inside the second solve
    assert s.idle_gaps[0][1] == pytest.approx(500e-9)
    assert s.idle_gaps[0][0] == "run:none"
    # 150..400: the allocator covers most of it, inside bench:run
    assert gaps["run:DeferredTpuAllocator::Allocate"] == \
        pytest.approx(250e-9)
    assert gaps["flush:D2H_Dispatch"] == pytest.approx(100e-9)
    assert s.breakdown().keys() == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in s.breakdown().values())


def test_a_trace_without_device_operations_is_refused():
    ev = _events()
    ev.device = {0: [("fusion", 1200, 1400)]}  # only between the solves
    with pytest.raises(RuntimeError, match="no operation ran"):
        tr.summarize(ev, chips=1)
    with pytest.raises(RuntimeError, match="uses 4 chips"):
        tr.summarize(_events(), chips=4)


def test_a_trace_without_the_window_span_is_refused():
    ev = _events()
    ev.host = [e for e in ev.host if e[0] != "bench:solve"]
    with pytest.raises(RuntimeError, match="bench:solve"):
        tr.summarize(ev, chips=1)


def test_recorded_tpu_trace_reduces():
    ev = tr.load_events(RECORDED)
    assert sorted(ev.device) == [0] and ev.device[0]
    assert any(n == "bench:solve" for n, _, _ in ev.host)
    s = tr.summarize(ev, chips=1)
    assert s.solves == 2
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_pct_worst < 100
    assert s.device_ops and s.device_ops[0][1] > 0
    assert len(s.idle_gaps) == 10
    assert all(":" in n and " " not in n for n, _ in s.idle_gaps)
    # gaps and busy time tile the window
    ops = tr.merge(tr.clip(
        [(b, e) for _, b, e in ev.device[0]],
        tr.merge([(b, e) for n, b, e in ev.host if n == "bench:solve"])))
    assert tr.length(ops) / 1e9 == pytest.approx(s.busy_s)
