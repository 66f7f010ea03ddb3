"""Rehearsals by size only: every driver through the whole harness on the
CPU backend with tiny traffic; the result line's keys; the control of
each configuration (its lower precision) failing the check; and a timed
path broken underneath, which ``correct`` has to catch.

No number from here is a device number: ``platform="cpu"`` skips nothing
but the look for a chip.
"""

import json

import jax
import numpy as np
import pytest

from benchmark import control, harness
from parsec_tpu import native

from bench_testlib import ROOT, tiny_cell, tiny_spec

CELLS = ["tile_pump_n8192", "panel_n32768", "tile_ctx_n8192",
         "tile_2x2_n16384"]
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")


def run(workload, *, seed=2147483999, seconds=0.5, cell=None, **kw):
    cell = cell or tiny_cell(workload)
    return harness.run_cell(ROOT, cell, seed, seconds, False,
                            platform="cpu", paths=tiny_spec()["paths"],
                            **kw)


@needs_native
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_result_line_has_exactly_the_contract_keys(workload):
    r = run(workload)
    assert tuple(r) == harness.RESULT_KEYS
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 1
    cell = tiny_cell(workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)


@needs_native
def test_attempted_is_the_number_of_readings_in_the_median(capsys):
    r = run("tile_pump_n8192", seconds=1.0)
    detail = json.loads(next(
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("[bench] detail ")).split("detail ", 1)[1])
    assert r["attempted"] == detail["median_of"]
    # one solve of the window is discarded by the traffic's own field
    # (and on a loaded machine the last one may end after the window)
    assert detail["window_solves"] - detail["median_of"] in (1, 2)
    assert detail["compared"]["factor_error"] < detail["limits"][
        "factor_error"]


def test_a_cell_refuses_the_wrong_platform():
    with pytest.raises(harness.BenchError, match="runs on 'tpu' only"):
        harness.run_cell(ROOT, tiny_cell("panel_n32768"), 1, 0.1, False)


@needs_native
def test_different_seeds_give_different_problems_same_seed_the_same():
    cell = tiny_cell("panel_n32768")
    dev = jax.devices()[:1]
    a, b, c = (cell.reference.make_problem(s, cell.config, cell.traffic,
                                           dev) for s in (5, 5, 2 ** 31 + 6))
    A, B, C = (np.asarray(p["make"]()) for p in (a, b, c))
    np.testing.assert_array_equal(A, B)
    assert a["rows"] == b["rows"] and len(a["rows"]) == len(c["rows"])
    import random
    rows = [cell.reference.sample_rows(random.Random(s), 4096, 512, 256)
            for s in (5, 6)]
    assert rows[0] != rows[1] and all(r[-1] == 4095 for r in rows)
    for r in rows:  # as many for every seed, 32 of every tile row
        assert np.bincount(np.asarray(r) // 512).tolist() == [32] * 8
    assert np.abs(A - C).mean() > 0.2  # another matrix, entry by entry
    # the device's matrix is the numpy closed form, dense and symmetric,
    # and positive definite with room to spare
    for M, p in ((A, a), (C, c)):
        np.testing.assert_array_equal(M, M.T)
        np.testing.assert_array_equal(
            M[np.ix_(p["rows"], p["rows"])], p["want"].astype(np.float32))
        assert (np.abs(M) > 1e-3).mean() > 0.99
        eig = np.linalg.eigvalsh(M.astype(np.float64))
        assert eig[0] > 0.1 * np.sqrt(len(M)) and eig[-1] / eig[0] < 12
    cell = tiny_cell("tile_pump_n8192")
    big = cell.reference.make_problem(2 ** 31 + 12345, cell.config,
                                      cell.traffic, dev)
    assert np.isfinite(big["tiles"][(0, 0)]).all()


# ---------------------------------------------------------------------------
# the control: each configuration's lower precision has to FAIL the check
# ---------------------------------------------------------------------------

@needs_native
@pytest.mark.parametrize("workload", CELLS)
def test_the_lower_precision_control_fails_the_check(workload):
    cell = tiny_cell(workload)
    limits = cell.config["limits"]
    seeds = [11, 12, 13]
    sound = control.read_numbers(cell, jax.devices(), seeds, 1,
                                 control=False, platform="cpu")
    lower = control.read_numbers(cell, jax.devices(), seeds, 1,
                                 control=True, platform="cpu")
    for seed in map(str, seeds):
        assert harness.within_limits(sound[seed], limits), sound
        assert not harness.within_limits(lower[seed], limits), lower
    # the lower precision fails one of the numbers, by a wide margin
    assert any(min(v[name] for v in lower.values())
               > 3 * max(v[name] for v in sound.values())
               for name in limits)


@needs_native
def test_a_control_run_through_the_harness_is_not_correct():
    r = run("panel_n32768", control=True)
    assert r["correct"] is False and r["failed"] == r["attempted"]


# ---------------------------------------------------------------------------
# the timed path broken underneath
# ---------------------------------------------------------------------------

@needs_native
def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """One tile of the factor comes home with one entry off by 1e-3."""
    from benchmark.drivers import _common

    real = _common.gather_home

    def altered(A, keys):
        tiles = real(A, keys)
        k = sorted(tiles)[-1]
        tiles[k] = tiles[k].copy()
        tiles[k][3, 1] += 1e-3
        return tiles

    monkeypatch.setattr(_common, "gather_home", altered)
    r = run("tile_pump_n8192")
    assert r["correct"] is False and r["failed"] >= r["attempted"] >= 1


@needs_native
def test_a_solve_that_returns_its_input_unchanged_is_not_correct(
        monkeypatch):
    """The segmented path hands back the matrix it was given."""
    from parsec_tpu.ops import segmented_chol

    monkeypatch.setattr(segmented_chol.SegmentedCholesky, "run",
                        lambda self, A, **kw: A)
    r = run("panel_n32768")
    # every solve fails twice over: the factor is wrong and no task ran
    assert r["correct"] is False and r["failed"] >= r["attempted"] >= 1


@needs_native
def test_a_trailing_update_cut_to_a_band_is_not_correct(monkeypatch):
    """The panel path updates only the blocks next to the diagonal: every
    trailing-update block two tiles or more off it is dropped."""
    import jax.numpy as jnp
    from parsec_tpu.ops import segmented_chol

    def banded_body(n, nb, bf16, strip, kt):
        blocks = np.arange(n) // nb
        near = jnp.asarray(np.abs(blocks[:, None] - blocks[None, :]) <= 1)

        def step(M, k):
            k0, k1 = k * nb, (k + 1) * nb
            L = jnp.linalg.cholesky(M[k0:k1, k0:k1])
            M = M.at[k0:k1, k0:k1].set(jnp.tril(L))
            if k1 == n:
                return M
            P = jnp.linalg.solve(L, M[k1:, k0:k1].T).T
            M = M.at[k1:, k0:k1].set(P)
            return M.at[k1:, k1:].add(-(P @ P.T) * near[k1:, k1:])

        def panel(M, k):
            for kk in range(int(k), n // nb if int(k) >= kt else int(k) + 1):
                M = step(M, kk)
            return M

        panel._static_values = True
        panel._donate_args = (0,)
        panel._jit_key = ("segchol_panel_banded", n, nb, str(bf16), kt)
        return panel

    monkeypatch.setattr(segmented_chol, "_make_panel_body", banded_body)
    r = run("panel_n32768")
    assert r["correct"] is False and r["failed"] >= r["attempted"] >= 1


def test_a_trailing_update_cut_to_a_band_misses_offdiag_error_alone():
    """What the sampled check sees of that fault, without the program:
    the factor of the band-limited updates fails ``offdiag_error`` by far
    and the sound factor passes both numbers."""
    cell = tiny_cell("panel_n32768")
    n, nb = cell.config["n"], cell.config["nb"]
    p = cell.reference.make_problem(77, cell.config, cell.traffic,
                                    jax.devices()[:1])
    A = np.asarray(p["make"]()).astype(np.float64)
    sound = np.linalg.cholesky(A)
    blocks = np.arange(n) // nb
    near = np.abs(blocks[:, None] - blocks[None, :]) <= 1
    M = A.copy()
    for k0 in range(0, n, nb):
        k1 = k0 + nb
        L = np.linalg.cholesky(M[k0:k1, k0:k1])
        M[k0:k1, k0:k1] = np.tril(L)
        P = np.linalg.solve(L, M[k1:, k0:k1].T).T
        M[k1:, k0:k1] = P
        M[k1:, k1:] -= (P @ P.T) * near[k1:, k1:]
    limits = cell.config["limits"]
    good = cell.reference.compare(p, jax.numpy.asarray(sound, np.float32))
    bad = cell.reference.compare(p, jax.numpy.asarray(M, np.float32))
    assert harness.within_limits(good, limits), good
    assert bad["offdiag_error"] > 5 * limits["offdiag_error"], bad


@needs_native
def test_a_task_left_unexecuted_is_a_failed_solve(monkeypatch):
    from benchmark import ops_count

    monkeypatch.setattr(ops_count, "dpotrf_ntasks", lambda nt: 21)
    r = run("tile_ctx_n8192")
    assert r["correct"] is False and r["failed"] >= 1


@needs_native
def test_a_compile_inside_the_window_fails_a_fixed_program_set(monkeypatch):
    cell = tiny_cell("panel_n32768")
    real = cell.reference.compare
    calls = []

    def compare(problem, L):
        calls.append(1)
        # two warm-ups and one discarded solve come first; from then on
        # every solve is in the window: a shape never seen, each time
        if len(calls) >= 4:
            jax.jit(lambda x: x * 2 + len(calls))(
                np.zeros(17 + len(calls), np.float32)).block_until_ready()
        return real(problem, L)

    monkeypatch.setattr(cell.reference, "compare", compare)
    r = run("panel_n32768", cell=cell)
    assert r["failed"] == 0 and r["correct"] is False
