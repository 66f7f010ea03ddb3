"""The out-of-core cell: its rehearsal on the CPU through the whole
harness (by size only: at the rehearsal's size the matrix fits, eviction
is ``tests/runtime/test_out_of_core.py``'s), its control and a skipped
update failing the check, its reference against a float64 Cholesky of the
same hashed matrix with a stale tile and a bf16-class factor planted, and
its four readers on a synthetic run and on a program without the span."""

import json
import os

import numpy as np
import pytest

from benchmark import harness
from benchmark.trace import evict, spans
from parsec_tpu import native

from bench_testlib import ROOT, tiny_cell, tiny_spec

CELL = "ooc_pump_n90112"
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(**kw):
    return harness.run_cell(ROOT, tiny_cell(CELL), 2147483999, 0.5, False,
                            platform="cpu", paths=tiny_spec()["paths"], **kw)


@needs_native
def test_the_rehearsal_runs_the_cell_and_reports_its_three_metrics(capsys):
    r = run()
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tile_solve_s", "tile_home_s", "setup_s"}
    # the deployment's host memory is logged beside the machine's
    out = capsys.readouterr().out
    assert "[bench] host memory at prepare: peak RSS" in out
    assert "of MemTotal" in out


@needs_native
def test_the_control_fails_the_check(capsys):
    r = run(control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"] >= 3
    out = capsys.readouterr().out
    assert "FAILED solve" in out and "violations []" in out


@needs_native
def test_an_update_skipped_in_the_timed_path_fails_the_check(monkeypatch):
    from parsec_tpu.ops import tiles

    monkeypatch.setattr(tiles, "gemm_update_tpu",
                        lambda A, B1, B2, **_: A + 0.0)
    r = run()
    assert r["correct"] is False and r["failed"] > 0


def test_the_cell_rides_the_pump_driver_behind_a_refusal():
    cell = harness.load_cell(ROOT, CELL)
    from benchmark.drivers import pump

    assert cell.traffic["driver"] == "pump_ooc"
    drv = cell.driver.open(cell.config, cell.traffic, {}, [], "cpu")
    assert isinstance(drv, pump.Pump) and type(drv).solve is pump.Pump.solve
    assert cell.traffic["discard_solves"] == 0
    assert cell.traffic["traced_solves"] == 1
    n, nb = cell.config["n"], cell.config["nb"]
    assert (n, nb, n // nb) == (90112, 2048, 44)
    assert cell.config["assumed"] == ["nb"]


def test_the_driver_holds_the_out_of_core_counters_at_zero():
    """A copy home that left a cached host value, room that could not be
    made, a tile charged to nobody: each is a fallback to the check."""
    import types

    cell = harness.load_cell(ROOT, CELL)
    drv = cell.driver.open(cell.config, cell.traffic, {}, [], "cpu")
    assert drv.counters()["fallbacks"] == 0
    for k in ("wb_alias_fallbacks", "reserve_gave_up", "unaccounted_tiles"):
        drv.dev = types.SimpleNamespace(stats={k: 2}, _zone=object())
        assert drv.counters()["fallbacks"] == 2
    drv.dev = None


def test_the_driver_refuses_a_program_without_the_capability(monkeypatch):
    from parsec_tpu.device import residency

    assert residency.OUT_OF_CORE is True
    monkeypatch.delattr(residency, "OUT_OF_CORE")
    with pytest.raises(harness.BenchError, match="OUT_OF_CORE"):
        harness.load_cell(ROOT, CELL)


def _problem(n=256, nb=32, seed=11):
    import jax

    cell = tiny_cell(CELL)
    cell.config.update(n=n, nb=nb)
    p = cell.reference.make_problem(seed, cell.config, cell.traffic,
                                    jax.devices()[:1])
    cell.reference.prepare(p)
    return cell, p


def _dense(p):
    n, nb = p["n"], p["nb"]
    a = np.zeros((n, n))
    for (i, j), t in p["tiles"].items():
        a[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = t
        a[j * nb:(j + 1) * nb, i * nb:(i + 1) * nb] = t.T
    return a


def _tiles_of(L, p):
    nb = p["nb"]
    return {(i, j): L[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            .astype(np.float32) for (i, j) in p["tiles"]}


def test_the_tiles_are_built_from_the_hash_when_they_are_read():
    cell, p = _problem()
    tiles = p["tiles"]
    assert len(tiles) == 36 and list(tiles)[:3] == [(0, 0), (1, 0), (1, 1)]
    a = _dense(p)
    rows = np.arange(p["n"])
    want = cell.reference.closed_form(rows, p["n"], 0.75, p["seed"])
    off = ~np.eye(p["n"], dtype=bool)
    np.testing.assert_array_equal(a[off], want[off])
    # (the bump is added in f32 on the device, in f64 in the closed form)
    np.testing.assert_allclose(np.diag(a), np.diag(want), rtol=1e-7)
    np.testing.assert_array_equal(tiles[(3, 1)], a[96:128, 32:64])
    assert not tiles[(3, 1)].flags.writeable  # the driver copies it
    assert (2, 5) not in tiles
    with pytest.raises(KeyError):
        tiles[(2, 5)]
    # every tile row is sampled, the last row among the samples
    per = cell.config["samples_per_tile_row"]
    assert len(p["rows"]) == per * p["nt"] and p["rows"][-1] == p["n"] - 1
    assert {r // p["nb"] for r in p["rows"]} == set(range(p["nt"]))


def test_a_tile_built_for_one_reader_is_handed_over_not_copied():
    """``fresh_matrix`` copies every tile it reads; one that ``items()``
    built has no other holder, so its ``copy()`` is the tile itself.  On
    the CPU backend the host value is a view of the device's memory and
    stays read-only for the reader to copy."""
    from benchmark.drivers import _common
    from parsec_tpu.datadist import TiledMatrix

    cell, p = _problem()
    ref = cell.reference
    host = np.arange(12, dtype=np.float32).reshape(3, 4)
    host.flags.writeable = False        # as a device array's host value is
    mine = ref._own(host)
    handed = mine.copy()
    assert type(handed) is np.ndarray and handed.flags.writeable
    assert np.shares_memory(handed, host)
    view = np.frombuffer(bytes(16), np.float32)   # memory not its own
    assert ref._own(view) is view and not view.flags.writeable
    # through the driver's own call either way, tile for tile the hash
    A = _common.fresh_matrix(TiledMatrix, p)
    for key in [(0, 0), (3, 1), (7, 7)]:
        got = A.data_of(*key).get_copy(0).payload
        assert got.flags.writeable and type(got) is np.ndarray
        np.testing.assert_array_equal(got, p["tiles"][key])


def test_the_reference_fails_a_stale_tile_and_a_bf16_class_factor():
    import jax.numpy as jnp

    cell, p = _problem()
    limits = cell.config["limits"]
    L = np.linalg.cholesky(_dense(p))
    good = cell.reference.compare(p, _tiles_of(L, p))
    assert set(good) == set(limits) == {"diagonal_error", "offdiag_error"}
    assert harness.within_limits(good, limits)
    assert good["diagonal_error"] < 1e-6 and good["offdiag_error"] < 1e-5
    # ONE tile at an intermediate version: A(5, 3) with step 0's update
    # alone, as an eviction's write-back that nothing superseded leaves it
    nb = p["nb"]
    stale = _tiles_of(L, p)
    a = _dense(p)
    stale[(5, 3)] = (a[5 * nb:6 * nb, 3 * nb:4 * nb]
                     - L[5 * nb:6 * nb, :nb] @ L[3 * nb:4 * nb, :nb].T
                     ).astype(np.float32)
    assert not harness.within_limits(cell.reference.compare(p, stale),
                                     limits)
    # a factor of bf16 class (the control's precision)
    rounded = np.asarray(jnp.asarray(L, jnp.bfloat16).astype(jnp.float32))
    assert not harness.within_limits(
        cell.reference.compare(p, _tiles_of(rounded, p)), limits)
    # a tile that is missing, or of another shape
    short = _tiles_of(L, p)
    del short[(4, 2)]
    assert cell.reference.compare(p, short)["diagonal_error"] == float("inf")


def _run(counters, trace=None):
    cell = tiny_cell(CELL)
    cell.config.update(n=90112, nb=2048)
    return harness.Run(cell=cell, readings=[], counters=counters, solves=2,
                       compiles={}, memory={},
                       peaks={"bf16_flops_per_s": 197e12}, trace=trace)


TILE = 2048 * 2048 * 4


def test_h2d_per_tile_reads_bytes_in_over_the_lower_matrix():
    read = tiny_cell(CELL).readers["h2d_per_tile"].read
    assert read(_run({"bytes_in": 2 * 990 * TILE})) == 1.0
    assert read(_run({"bytes_in": 3 * 990 * TILE})) == 1.5
    assert read(_run({})) is None


def test_evictions_per_tile_reads_evictions_over_the_lower_tiles():
    read = tiny_cell(CELL).readers["evictions_per_tile"].read
    assert read(_run({"evictions": 2 * 495})) == 0.5
    assert read(_run({"evictions": 0})) == 0.0
    assert read(_run({})) is None


def _trace_with_evictions():
    ms = 1_000_000
    sp = [spans.Span("dev:stage_args", 10 * ms, 40 * ms, 1, {}),
          spans.Span("dev:evict", 12 * ms, 30 * ms, 1,
                     {"victims": 8, "dirty": 6, "bytes_home": 6 * TILE,
                      "wait_us": 15000, "need": 8 * TILE}),
          spans.Span("dev:evict", 120 * ms, 126 * ms, 2,
                     {"victims": 4, "dirty": 0, "bytes_home": 0,
                      "wait_us": 0, "need": 4 * TILE}),
          # outside the solves: not counted
          spans.Span("dev:evict", 300 * ms, 350 * ms, 1,
                     {"victims": 9, "dirty": 9, "bytes_home": 9 * TILE,
                      "wait_us": 40000})]
    return spans.Trace(sp, [(0, 100 * ms), (110 * ms, 200 * ms)], {})


def test_the_span_readers_on_a_synthetic_run(monkeypatch):
    e = evict.summarize(_trace_with_evictions())
    assert (e.solves, e.spans, e.victims, e.dirty) == (2, 2, 12, 6)
    assert e.bytes_home == 6 * TILE and e.wait_us == 15000
    assert e.total_ns == 24_000_000
    r = tiny_cell(CELL).readers
    monkeypatch.setattr(evict, "of_run", lambda run: e)
    run = _run({}, trace=object())
    assert r["evict_home_mb_per_solve"].read(run) == 6 * 16 / 2
    assert r["evict_wait_s"].read(run) == pytest.approx(0.012)


def test_the_span_readers_find_nothing_in_a_program_without_the_span():
    recorded = spans.load(f"{ROOT}/tests/benchmark_harness/recorded/"
                          "tiny_pump_spans.xplane.pb")
    assert recorded.windows and recorded.spans
    assert evict.summarize(recorded) is None
    r = tiny_cell(CELL).readers
    # an untraced run, and a traced one whose trace is not there
    assert r["evict_home_mb_per_solve"].read(_run({})) is None
    assert r["evict_wait_s"].read(_run({})) is None


def test_the_new_entries_of_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [w["name"] for w in spec["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    mine = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "h2d_per_tile", "evictions_per_tile", "evict_home_mb_per_solve",
        "evict_wait_s"]
    assert all(m["layer"] == "device" and m["moves"] == "tile_solve_s"
               for m in mine)
    for m in spec["end_to_end"]:
        if m["name"] in ("tile_solve_s", "tile_home_s"):
            assert m["workloads"][-1] == CELL
