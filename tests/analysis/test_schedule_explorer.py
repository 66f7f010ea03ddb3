"""Deterministic schedule explorer: seeded perturbations of pop order /
completion timing / frame delivery, with bit-identical results and a
clean hb-check per seed — the tier-1 "analysis" leg runs the explorer on
two small registry graphs over 2 virtual ranks."""

import numpy as np
import pytest

from parsec_tpu.analysis.schedules import (
    ExplorationError,
    ExplorerFabric,
    explore,
    tile_digest,
)
from parsec_tpu.utils import mca_param


# ---------------------------------------------------------------------------
# the rnd scheduler's replay hook (MCA sched_rnd_seed)
# ---------------------------------------------------------------------------

class _T:
    def __init__(self, k):
        self.k = k
        self.priority = 0


def _pop_order(seed_set: bool, seed: int = 0):
    from parsec_tpu.core.sched.rnd import SchedRND

    if seed_set:
        mca_param.params.set("sched", "rnd_seed", seed)
    try:
        s = SchedRND()
        s.install(context=None)
        s.schedule(None, [_T(k) for k in range(32)])
        out = []
        while True:
            t = s.select(None)
            if t is None:
                return [x.k for x in out], s.seed
            out.append(t)
    finally:
        mca_param.params.unset("sched", "rnd_seed")


def test_rnd_seed_replays_one_schedule():
    a, seed_a = _pop_order(True, 1234)
    b, seed_b = _pop_order(True, 1234)
    c, _ = _pop_order(True, 99)
    assert seed_a == seed_b == 1234
    assert a == b              # same seed -> same schedule
    assert a != c              # different seed -> different schedule


def test_rnd_default_stays_unseeded():
    _, seed = _pop_order(False)
    assert seed is None


# ---------------------------------------------------------------------------
# explorer on the two small registry graphs (2 virtual ranks) — tier-1
# ---------------------------------------------------------------------------

N, NB = 32, 8
_rng = np.random.default_rng(7)
_M = _rng.standard_normal((N, N))
SPD = _M @ _M.T + N * np.eye(N)


def _build_dpotrf(rank, ctx):
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.ops.cholesky import cholesky_ptg

    A = TwoDimBlockCyclic(N, N, NB, NB, p=2, q=1, myrank=rank, name="A")
    A.from_array(SPD)
    return cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A), A


def test_explorer_dpotrf_2ranks_identical_and_raceless():
    res = explore(_build_dpotrf, nranks=2, seeds=range(4), timeout=90)
    assert res.identical
    assert res.race_findings() == []
    # and the result is RIGHT, not merely identical: stitch rank tiles
    ref = np.linalg.cholesky(SPD)
    d0 = res.digests[res.seeds[0]]
    out = np.zeros((N, N))
    for rank, tiles in enumerate(d0):
        for (i, j), payload in tiles.items():
            shape, dtype, raw = payload
            out[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB] = \
                np.frombuffer(raw, dtype=dtype).reshape(shape)
    np.testing.assert_allclose(np.tril(out), ref, rtol=1e-8, atol=1e-8)


GRID = np.random.default_rng(3).standard_normal((16, 16))
T_ITERS = 2


def _build_stencil(rank, ctx):
    from parsec_tpu.ops.stencil import stencil_grid, stencil_taskpool

    # row distribution (rank = i % 2): UP/DOWN halos cross the ranks
    # every iteration
    A = stencil_grid(GRID, 2, 2, p=2, myrank=rank)
    return stencil_taskpool(A, T_ITERS, use_cpu=True), A


def _stencil_snapshot(users):
    # digest each rank's OWN tiles (a remote tile is generation 0 still:
    # every rank holds the whole input)
    out = []
    for rank, A in enumerate(users):
        tiles = {}
        for i in range(A.mt):
            for j in range(A.nt):
                if A.rank_of(i, j) != rank:
                    continue
                c = A.data_of(i, j).newest_copy()
                arr = np.asarray(c.payload)
                tiles[(i, j)] = (arr.shape, str(arr.dtype), arr.tobytes())
        out.append(tiles)
    return out


def test_explorer_stencil_2ranks_identical_and_raceless():
    from parsec_tpu.ops.stencil import reference_stencil

    res = explore(_build_stencil, nranks=2, seeds=range(4), timeout=90,
                  snapshot=_stencil_snapshot)
    assert res.identical
    assert res.race_findings() == []
    ref = reference_stencil(GRID, T_ITERS)
    d0 = res.digests[res.seeds[0]]
    th = GRID.shape[0] // 2
    for rank, tiles in enumerate(d0):
        for (i, j), (shape, dtype, raw) in tiles.items():
            got = np.frombuffer(raw, dtype=dtype).reshape(shape)
            np.testing.assert_allclose(
                got, ref[i * th:(i + 1) * th, j * th:(j + 1) * th],
                rtol=1e-12)


def test_explorer_detects_schedule_dependent_results():
    """A pool whose visible result depends on execution order must make
    the explorer fail loudly with the diverging seed."""
    import threading

    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import PTG, INOUT

    def build(rank, ctx):
        order = []
        lock = threading.Lock()
        dc = LocalCollection("D", shape=(1,), init=lambda k: np.zeros(1))
        ptg = PTG("orderdep")
        a = ptg.task_class("a", k="0 .. 7")
        a.affinity("D(k)")
        a.flow("X", INOUT, "<- D(k)", "-> D(k)")

        def body(X, k):
            with lock:
                order.append(k)

        a.body(cpu=body)
        tp = ptg.taskpool(D=dc)
        return tp, order

    with pytest.raises(ExplorationError, match="DIVERGE"):
        explore(build, nranks=1, nb_cores=1, seeds=range(4), timeout=60,
                snapshot=lambda users: tuple(users[0]))


def test_perturbed_inbox_preserves_every_frame():
    import random

    from parsec_tpu.analysis.schedules import _PerturbedInbox

    box = _PerturbedInbox(random.Random(0), delay_prob=0.8, max_delay=4)
    for i in range(50):
        box.put(i)
    got = []
    import queue as _q

    spins = 0
    while len(got) < 50:
        try:
            got.append(box.get_nowait())
        except _q.Empty:
            spins += 1
            assert spins < 10_000, "deferral must be bounded (liveness)"
    assert sorted(got) == list(range(50))
    assert got != list(range(50))  # and genuinely reordered
    assert box.qsize() == 0


@pytest.mark.slow
def test_explorer_200_seeds_dpotrf_and_stencil():
    """The acceptance-scale sweep: 200 seeds each on dpotrf + stencil,
    zero findings, bit-identical results across every seed."""
    res = explore(_build_dpotrf, nranks=2, seeds=range(200), timeout=90)
    assert res.identical and res.race_findings() == []
    res = explore(_build_stencil, nranks=2, seeds=range(200), timeout=90,
                  snapshot=_stencil_snapshot)
    assert res.identical and res.race_findings() == []
