"""Concurrent-taskpool correctness floor (satellite of the serving
plane): 2-8 heterogeneous taskpools (dpotrf + stencil + LU + chains)
executing SIMULTANEOUSLY on one context — single-rank and inproc
2-rank multirank — must produce bit-identical results vs solo runs,
with clean per-pool termination detection.  (The loopback-TCP leg lives
in tests/runtime/test_tcp.py::test_tcp_multipool via the
``multipool`` tcp_driver scenario.)"""

import threading

import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.analysis.schedules import tile_digest
from parsec_tpu.comm import InprocFabric
from parsec_tpu.datadist import TiledMatrix, TwoDimBlockCyclic
from parsec_tpu.ops.cholesky import cholesky_ptg
from parsec_tpu.ops.lu import lu_ptg
from parsec_tpu.ops.stencil import stencil_grid, stencil_taskpool

N, NB = 64, 16

_rng = np.random.default_rng(42)
_M = _rng.standard_normal((N, N))
SPD = _M @ _M.T + N * np.eye(N)
# diagonally dominant: stable no-pivot LU
LUIN = _rng.standard_normal((N, N)) + N * np.eye(N)
GRID = _rng.standard_normal((32, 48))
ST_ITERS = 4


def _build_pool(kind: str, rank: int = 0, nranks: int = 1):
    """One (taskpool, digestable-user) pair per workload kind."""
    if kind.startswith("dpotrf"):
        if nranks > 1:
            A = TwoDimBlockCyclic(N, N, NB, NB, p=nranks, q=1,
                                  myrank=rank, name=f"A{kind}")
        else:
            A = TiledMatrix(N, N, NB, NB, name=f"A{kind}")
        A.from_array(SPD)
        return cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A), A
    if kind.startswith("lu"):
        if nranks > 1:
            A = TwoDimBlockCyclic(N, N, NB, NB, p=1, q=nranks,
                                  myrank=rank, name=f"B{kind}")
        else:
            A = TiledMatrix(N, N, NB, NB, name=f"B{kind}")
        A.from_array(LUIN)
        return lu_ptg(use_tpu=False).taskpool(NT=A.mt, A=A), A
    if kind.startswith("stencil"):
        # rows over the ranks: rank = i % nranks
        bufs = stencil_grid(GRID, 4, 3, p=nranks, myrank=rank)
        return stencil_taskpool(bufs, ST_ITERS), bufs
    raise ValueError(kind)


def _digest(kind, user):
    if kind.startswith("stencil"):
        # this rank's tiles of the grid after the sweeps, bit-exact
        out = {}
        for i in range(user.mt):
            for j in range(user.nt):
                if user.rank_of(i, j) != user.myrank:
                    continue
                c = user.data_of(i, j).newest_copy()
                arr = np.asarray(c.payload)
                out[(i, j)] = (arr.shape, str(arr.dtype), arr.tobytes())
        return out
    return tile_digest(user)


def _solo_digests(kinds, nranks=1):
    """Reference digests: each workload run ALONE (one pool per fresh
    context / mesh)."""
    out = {}
    for kind in kinds:
        if nranks == 1:
            ctx = Context(nb_cores=2)
            try:
                tp, user = _build_pool(kind)
                ctx.add_taskpool(tp)
                assert tp.wait(timeout=120), f"solo {kind} hung"
                out[kind] = _digest(kind, user)
            finally:
                ctx.fini()
        else:
            fabric = InprocFabric(nranks)
            ces = fabric.endpoints()
            ctxs = [Context(nb_cores=2, rank=r, nranks=nranks,
                            comm=ces[r]) for r in range(nranks)]
            users = [None] * nranks
            oks = [False] * nranks

            def worker(r):
                tp, users[r] = _build_pool(kind, r, nranks)
                ctxs[r].add_taskpool(tp)
                oks[r] = tp.wait(timeout=180)

            ts = [threading.Thread(target=worker, args=(r,))
                  for r in range(nranks)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=240)
            try:
                assert all(oks), f"solo {kind} multirank hung: {oks}"
                out[kind] = [_digest(kind, u) for u in users]
            finally:
                for c in ctxs:
                    c.fini()
    return out


def _assert_clean_termdet(tp):
    """Per-pool termdet closed its books: no outstanding tasks or
    runtime actions linger on the monitor."""
    nb = getattr(tp.tdm, "_nb_tasks", None)
    if isinstance(nb, int):
        assert nb <= 0, (tp.name, nb)
    ra = getattr(tp.tdm, "_runtime_actions", None)
    if isinstance(ra, int):
        assert ra == 0, (tp.name, ra)
    assert tp.is_done() and not tp.failed


@pytest.mark.parametrize("kinds", [
    ["dpotrf0", "stencil0"],
    ["dpotrf0", "stencil0", "lu0"],
    ["dpotrf0", "stencil0", "lu0", "dpotrf1",
     "stencil1", "lu1", "dpotrf2", "lu2"],
], ids=["2pools", "3pools", "8pools"])
def test_concurrent_heterogeneous_pools_single_rank(kinds):
    """dpotrf + stencil + LU running AT THE SAME TIME on one context:
    bit-identical to their solo runs, every pool's termdet clean."""
    solo = _solo_digests(sorted(set(kinds)))
    ctx = Context(nb_cores=4)
    try:
        pools = [(kind, *_build_pool(kind)) for kind in kinds]
        for _, tp, _u in pools:
            ctx.add_taskpool(tp)
        ctx.start()
        for kind, tp, _u in pools:
            assert tp.wait(timeout=180), f"{kind} hung concurrently"
        for kind, tp, user in pools:
            _assert_clean_termdet(tp)
            got = _digest(kind, user)
            assert got == solo[kind], \
                f"{kind}: concurrent result differs from solo run"
    finally:
        ctx.fini()


def test_concurrent_heterogeneous_pools_2rank_inproc():
    """The same floor across a 2-rank inproc mesh: each rank's context
    carries dpotrf + LU + stencil concurrently; every distributed
    dependency interleaves with the other pools' traffic on one comm
    engine.  Results must match the solo multirank runs bit-exactly."""
    kinds = ["dpotrf0", "lu0", "stencil0"]
    nranks = 2
    solo = _solo_digests(kinds, nranks=nranks)
    fabric = InprocFabric(nranks)
    ces = fabric.endpoints()
    ctxs = [Context(nb_cores=2, rank=r, nranks=nranks, comm=ces[r])
            for r in range(nranks)]
    users = [None] * nranks
    oks = [False] * nranks

    def worker(r):
        built = [(kind, *_build_pool(kind, r, nranks)) for kind in kinds]
        users[r] = {kind: user for kind, _tp, user in built}
        for _, tp, _u in built:
            ctxs[r].add_taskpool(tp)
        ok = True
        for kind, tp, _u in built:
            ok = tp.wait(timeout=240) and ok
            _assert_clean_termdet(tp)
        oks[r] = ok

    ts = [threading.Thread(target=worker, args=(r,))
          for r in range(nranks)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    try:
        assert all(not t.is_alive() for t in ts), "concurrent mesh hung"
        assert all(oks), oks
        for i, kind in enumerate(kinds):
            for r in range(nranks):
                assert _digest(kind, users[r][kind]) == solo[kind][r], \
                    f"{kind} rank {r}: concurrent differs from solo"
    finally:
        for c in ctxs:
            c.fini()
