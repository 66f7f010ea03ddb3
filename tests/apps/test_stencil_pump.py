"""The iterative 5-point stencil through the pump
(``NativeExecutor(native_device=True)``) at small size on the CPU backend:
its values against the benchmark's window reference AND the dense
``reference_stencil``, the ``Context`` path beside it, the counts a solve
is held to (the grid in once and home once, generations born and freed on
the device, nothing dirty evicted under a budget of three grids, an attach
plan found again), and the window reference itself: its domain-of-
dependence values against a dense float64 sweep, and three planted faults
and the bf16 control each missing a limit."""

import json
import os

import numpy as np
import pytest

from benchmark.reference import sstencil_windows as ref
from parsec_tpu import Context, native
from parsec_tpu.dsl import attach_plan
from parsec_tpu.ops import stencil
from parsec_tpu.ops.stencil import (reference_stencil, stencil_grid,
                                    stencil_taskpool)
from parsec_tpu.profiling import pins

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NB = 32
SEED = 2147483999
GRIDS = [(1, 1), (1, 4), (3, 3), (4, 4)]
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="needs the native core")

with open(os.path.join(
        ROOT, "benchmark/configs/sstencil_2d5pt_nb4096_1chip.json")) as f:
    LIMITS = json.load(f)["limits"]


def hashed_grid(mt, nt, seed=SEED):
    return ref.patch(0, mt * NB, 0, nt * NB, seed).astype(np.float32)


def tiles_of(grid, mt, nt):
    return {(i, j): grid[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB]
            for i in range(mt) for j in range(nt)}


def problem_of(mt, nt, iters, seed=SEED):
    """What ``sstencil_windows.make_problem`` + ``prepare`` build, for a
    grid that need not be square."""
    m, n = mt * NB, nt * NB
    wins = ref.windows_of(m, n, NB, NB)
    return {"seed": seed, "nb": NB, "iters": iters,
            "tiles": tiles_of(hashed_grid(mt, nt, seed), mt, nt),
            "windows": wins,
            "want": [ref.window_values(w, iters, m, n, seed) for w in wins]}


def inside(numbers):
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def pump(A, iters, dev=None, B=None, **bodies):
    """One solve through the pump; the executor's and the device's
    counters of it."""
    from parsec_tpu.dsl.native_exec import NativeExecutor

    tp = stencil_taskpool(A, iters, B=B, use_tpu=True, use_cpu=False,
                          **bodies)
    ex = NativeExecutor(tp, native_device=True, device=dev)
    before = dict(ex.device.stats)
    ran = ex.run()
    ex.close()
    moved = {k: v - before.get(k, 0) for k, v in ex.device.stats.items()
             if isinstance(v, (int, float))}
    return ran, ex, moved


def programs_of(moved):
    """Device programs of a solve: wave programs and tasks alone."""
    return moved["wave_submits"] + moved["executed_tasks"] \
        - moved["wave_tasks"]


@needs_native
@pytest.mark.parametrize("iters", [1, 2, 5])
@pytest.mark.parametrize("mt,nt", GRIDS)
def test_pump_matches_the_windows_and_the_dense_reference(mt, nt, iters):
    grid = hashed_grid(mt, nt)
    A = stencil_grid(grid, mt, nt)
    # one sweep over several tiles cannot be in place
    B = stencil_grid(np.zeros_like(grid), mt, nt, name="B") \
        if iters == 1 and mt * nt > 1 else A
    ran, ex, _ = pump(A, iters, B=B)
    assert ran == iters * mt * nt
    assert ex.stats["pumped_tasks"] == ran and ex.stats["pop_batches"]
    assert not ex.stats["trampoline_entries"]
    assert not ex.stats["completion_callbacks"]
    got = B.to_array()
    numbers = ref.compare(problem_of(mt, nt, iters), tiles_of(got, mt, nt))
    assert inside(numbers), numbers
    np.testing.assert_allclose(
        got, reference_stencil(grid.astype(np.float64), iters),
        rtol=0, atol=2e-6)
    if B is not A:  # generation 0 is read, never written
        np.testing.assert_array_equal(A.to_array(), grid)


@needs_native
@pytest.mark.parametrize("mt,nt", [(1, 4), (3, 3)])
def test_the_context_path_gives_the_same_grid(mt, nt):
    grid = hashed_grid(mt, nt)
    A = stencil_grid(grid, mt, nt)
    pump(A, 5)
    C = stencil_grid(grid, mt, nt)
    with Context(nb_cores=2) as ctx:
        tp = stencil_taskpool(C, 5, use_tpu=True, use_cpu=False)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
    np.testing.assert_array_equal(C.to_array(), A.to_array())


def test_one_sweep_in_place_is_refused():
    A = stencil_grid(hashed_grid(2, 2), 2, 2)
    with pytest.raises(ValueError, match="one sweep in place"):
        stencil_taskpool(A, 1)
    stencil_taskpool(stencil_grid(hashed_grid(1, 1), 1, 1), 1)


@needs_native
@pytest.mark.parametrize("in_place", [True, False])
@pytest.mark.parametrize("budget_grids", [3, 2.25])
def test_a_solve_moves_the_grid_in_once_and_home_once(in_place,
                                                      budget_grids):
    """The counts of a solve, on a device whose budget is three grids
    (generation 0 and two live generations: nothing has to leave but
    for what the device has not let go of yet, which stays charged:
    where the pump leads it, tiles of generation 0 go, for nothing) or
    less (generation 0 has to make room, and it alone).  Under either a
    chunk has room for two or three tiles (``Residency.chunk_limit``, a
    sixteenth of the budget): a task of sweep 0, which reads the host's
    tiles, goes out alone; from sweep 1 on the operands were born on the
    device and cost the chunk nothing, the tile written does: two tasks
    a program."""
    mt = nt = 4
    iters = 6
    grid = hashed_grid(mt, nt)
    attach_plan.clear()
    dev = None
    for solve in range(2):
        A = stencil_grid(grid, mt, nt)
        B = A if in_place else stencil_grid(np.zeros_like(grid), mt, nt,
                                            name="B")
        if dev is not None:
            dev.hbm_budget = int(budget_grids * grid.nbytes)
        ran, ex, moved = pump(A, iters, dev=dev, B=B)
        dev = ex.device
        assert ran == iters * mt * nt == moved["executed_tasks"]
        assert moved["bytes_in"] == grid.nbytes
        assert moved["bytes_out"] == grid.nbytes
        # every generation but the last is born on the device and dies
        # there with its last reader
        assert moved["scratch_tiles_born"] == (iters - 1) * mt * nt
        assert moved["scratch_tiles_freed"] == moved["scratch_tiles_born"]
        assert moved["scratch_bytes_in"] == moved["scratch_bytes_out"] == 0
        assert moved["evict_dirty"] == moved["evict_bytes_home"] == 0
        assert moved["reserve_gave_up"] == moved["unaccounted_tiles"] == 0
        # a wave of a generation hands a tile to several of its tasks
        assert 0 < moved["tile_args_repeated"] < moved["tile_args_passed"]
        if solve:
            # sweep 0: sixteen programs of one task; then a sweep is the
            # four interior tasks two by two, four edges of two, four
            # corners alone
            assert programs_of(moved) == 16 + (iters - 1) * 10
            assert moved["chunks_cut_by_bytes"] == 7 + (iters - 1)
            # five operands an interior task, four an edge task (a
            # corner goes out alone: nobody cuts it)
            assert moved["chunk_bytes_born_here"] \
                == (iters - 1) * (4 * 5 + 8 * 4) * NB * NB * 4
        else:
            assert moved["chunks_cut_by_bytes"] == 0
        assert ex.stats["attach_plan_uncacheable"] == 0
        assert ex.stats["attach_plan_hits"] == solve
        np.testing.assert_allclose(
            B.to_array(), reference_stencil(grid.astype(np.float64), iters),
            rtol=0, atol=2e-6)
    # under the smaller budget generation 0 made room, clean
    # (of generation 0 alone, and never twice: ``evict_dirty`` is 0 and
    # ``bytes_in`` one grid a solve, above)
    assert moved["evict_clean"] <= mt * nt
    assert moved["evict_clean"] > 0 or budget_grids == 3


@needs_native
def test_the_cells_counts_at_tiny_tiles():
    """``stencil_pump_n32768`` replayed: 8 x 8 tiles, 100 sweeps, the
    budget at the cell's share of the grid (14.37 of 4.29 GB), on a
    device that has solved before.  The pump's order does not depend on
    time, so these are the chip's counts: until PR 45 3,404 programs
    (two tasks a program: the five resident operands counted as new
    memory; the ledger's ``tasks_per_program`` 1.8801 and
    ``repeated_args_pct`` 11.98 to the digit), now eight a program where
    a sweep has them."""
    mt = nt = 8
    iters = 100
    grid = hashed_grid(mt, nt)
    attach_plan.clear()
    _ran, ex, _moved = pump(stencil_grid(hashed_grid(1, 1), 1, 1), 2)
    dev = ex.device
    dev.hbm_budget = int(3.35 * grid.nbytes)
    A = stencil_grid(grid, mt, nt)
    waves = []

    def note(es, p):
        waves.append((p["n"], p["cut"]))
    pins.subscribe("dev:wave_end", note)
    try:
        ran, ex, moved = pump(A, iters, dev=dev)
    finally:
        pins.unsubscribe("dev:wave_end", note)
    assert ran == iters * mt * nt == moved["executed_tasks"]
    # sweep 0, whose operands are the host's tiles, in programs of two
    # (and of one where the first batches are split for the transfer);
    # then 17 programs a sweep: the 36 interior tasks 8 + 8 + 8 + 8 + 4,
    # four edges of 6 as 4 + 2, four corners alone
    assert programs_of(moved) == 1723  # 3.71 tasks a program
    assert sorted(set(waves)) == [(1, "tasks"), (2, "bytes"), (2, "tasks"),
                                  (4, "tasks"), (8, "bytes"), (8, "tasks")]
    assert waves.count((8, "bytes")) + waves.count((8, "tasks")) \
        == 4 * (iters - 1) - 1
    assert moved["chunks_cut_by_bytes"] == 315
    assert moved["tile_args_passed"] == 28800
    assert moved["tile_args_repeated"] == 10372
    # and everything else a solve is held to, as before
    assert moved["bytes_in"] == moved["bytes_out"] == grid.nbytes
    assert moved["scratch_tiles_born"] == (iters - 1) * mt * nt \
        == moved["scratch_tiles_freed"]
    assert moved["scratch_bytes_in"] == moved["scratch_bytes_out"] == 0
    assert moved["evict_dirty"] == moved["evict_bytes_home"] == 0
    assert moved["reserve_gave_up"] == moved["unaccounted_tiles"] == 0
    np.testing.assert_allclose(
        A.to_array(), reference_stencil(grid.astype(np.float64), iters),
        rtol=0, atol=2e-6)


@pytest.mark.parametrize("mt,nt", GRIDS)
def test_the_windows_equal_the_dense_float64_sweep(mt, nt):
    iters = 5
    p = problem_of(mt, nt, iters)
    dense = ref.sweep(ref.patch(0, mt * NB, 0, nt * NB, SEED), iters)
    assert len(p["windows"]) == (mt + 1) * (nt + 1) + mt * nt
    for (r0, r1, c0, c1), want in zip(p["windows"], p["want"]):
        np.testing.assert_allclose(want, dense[r0:r1, c0:c1], rtol=1e-13,
                                   atol=0)
    numbers = ref.compare(p, tiles_of(dense, mt, nt))
    assert numbers["window_error"] < 1e-13
    assert numbers["edge_error"] < 1e-13
    # and the hash is the same on either side
    np.testing.assert_array_equal(
        ref.sweep(ref.patch(0, mt * NB, 0, nt * NB, SEED), 0),
        hashed_grid(mt, nt).astype(np.float64))


def tiled_sweeps(grid, mt, nt, iters, up_of=lambda i, j: (i - 1, j)):
    """The tile algorithm in float64 numpy; ``up_of`` says which tile's
    last row feeds a tile from above."""
    g = tiles_of(grid.astype(np.float64), mt, nt)
    for _ in range(iters):
        g = {(i, j): stencil._apply_5pt(
            g[(i, j)], g.get(up_of(i, j)) if i > 0 else None,
            g.get((i + 1, j)), g.get((i, j - 1)), g.get((i, j + 1)))
            for (i, j) in g}
    return g


def periodic_sweeps(grid, iters):
    g = grid.astype(np.float64)
    for _ in range(iters):
        g = 0.25 * (np.roll(g, 1, 0) + np.roll(g, -1, 0)
                    + np.roll(g, 1, 1) + np.roll(g, -1, 1))
    return g


@pytest.mark.parametrize("fault", ["stale_tile", "up_from_the_wrong_tile",
                                   "periodic_boundary"])
def test_a_planted_fault_misses_a_limit(fault):
    mt = nt = 3
    iters = 5
    grid = hashed_grid(mt, nt)
    p = problem_of(mt, nt, iters)
    sound = tiled_sweeps(grid, mt, nt, iters)
    assert inside(ref.compare(p, sound))
    if fault == "stale_tile":
        got = dict(sound)
        got[(1, 2)] = tiled_sweeps(grid, mt, nt, iters - 1)[(1, 2)]
    elif fault == "up_from_the_wrong_tile":
        got = tiled_sweeps(grid, mt, nt, iters,
                           up_of=lambda i, j: (i - 1, (j + 1) % nt))
    else:
        got = tiles_of(periodic_sweeps(grid, iters), mt, nt)
    numbers = ref.compare(p, got)
    assert not inside(numbers), numbers
    assert max(numbers.values()) > 100 * max(LIMITS.values())


def test_a_missing_or_misshapen_tile_is_not_correct():
    p = problem_of(2, 2, 2)
    sound = tiled_sweeps(hashed_grid(2, 2), 2, 2, 2)
    assert inside(ref.compare(p, sound))
    short = {k: v for k, v in sound.items() if k != (1, 1)}
    assert ref.compare(p, short)["window_error"] == float("inf")
    sound[(0, 0)] = sound[(0, 0)][:-1]
    assert ref.compare(p, sound)["edge_error"] == float("inf")


@needs_native
def test_the_bf16_control_misses_the_limits():
    mt = nt = 3
    iters = 5
    A = stencil_grid(hashed_grid(mt, nt), mt, nt)
    pump(A, iters, bf16_updates=True)
    numbers = ref.compare(problem_of(mt, nt, iters),
                          tiles_of(A.to_array(), mt, nt))
    assert min(numbers[k] / LIMITS[k] for k in LIMITS) > 10, numbers


def test_the_pallas_body_refuses_a_tile_it_cannot_hold():
    import jax

    big = jax.ShapeDtypeStruct((1024, 1024), np.float32)
    with pytest.raises(ValueError, match="holds a whole tile in VMEM"):
        jax.eval_shape(lambda o: stencil.stencil_pallas(
            o, None, None, None, None, None), big)
