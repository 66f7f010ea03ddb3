"""``ops/inverse.py``: the two new DAGs (``trtri_ptg``, ``lauum_ptg``)
against numpy and against their loop nests, and ``poinv`` (three pools
composed) through a context, the numpy executor and the pump, where the
matrix goes onto the device once and comes home once."""

import numpy as np
import pytest

from parsec_tpu import native
from parsec_tpu.core.compound import CompoundTaskpool, compose
from parsec_tpu.core.context import Context
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.graph import capture
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.ops import cholesky_ptg, lauum_ptg, poinv, trtri_ptg
from parsec_tpu.ops.inverse import LAUUM_CLASSES, TRTRI_CLASSES

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native core unavailable")

NB = 8
#: float32 tiles against a float64 reference, matrices of condition ~10
TOL = 5e-5


def spd(n, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return m @ m.T / n + np.eye(n)


def tiled(a, nb=NB):
    A = TiledMatrix(a.shape[0], a.shape[0], nb, nb, name="A",
                    dtype=np.float32, uplo="lower")
    return A.from_array(a.astype(np.float32))


def lower_err(A, ref):
    return float(np.abs(np.tril(A.to_array()) - np.tril(ref)).max())


def run_ctx(make, A):
    with Context(nb_cores=4) as ctx:
        tp = make(A, use_tpu=False, use_cpu=True)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)


def run_numpy(make, A):
    ex = NativeExecutor(make(A, use_tpu=False, use_cpu=True))
    try:
        return ex.run()
    finally:
        ex.close()


def run_pump(make, A, device=None):
    ex = NativeExecutor(make(A, use_tpu=True, use_cpu=False),
                        native_device=True, device=device)
    try:
        n = ex.run()
        assert ex.stats["trampoline_entries"] == 0
        return n
    finally:
        ex.close()


PATHS = {"context": run_ctx, "numpy": run_numpy, "pump": run_pump}


def member(ptg):
    def make(A, **kw):
        return ptg(**kw).taskpool(NT=A.mt, A=A)
    return make


def ntasks(NT):
    return NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("NT", [1, 2, 5])
def test_trtri_is_the_inverse_of_the_factor(NT, path):
    L = np.linalg.cholesky(spd(NT * NB, seed=NT))
    A = tiled(L)
    PATHS[path](member(trtri_ptg), A)
    assert lower_err(A, np.linalg.inv(L)) < TOL


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("NT", [1, 2, 5])
def test_lauum_is_wt_w(NT, path):
    W = np.linalg.cholesky(spd(NT * NB, seed=10 + NT))
    A = tiled(W)
    PATHS[path](member(lauum_ptg), A)
    assert lower_err(A, W.T @ W) < TOL


def poinv_of(A, **kw):
    return poinv(A, **kw)


@pytest.mark.parametrize("NT", [1, 2, 5])
def test_poinv_is_the_inverse_through_all_three_paths(NT):
    S = spd(NT * NB, seed=20 + NT)
    ref = np.linalg.inv(S)
    got = {}
    for path, run in PATHS.items():
        A = tiled(S)
        n = run(poinv_of, A)
        if path != "context":
            assert n == 3 * ntasks(NT)
        assert lower_err(A, ref) < TOL, path
        got[path] = np.tril(A.to_array())
    # the two in-place numpy paths run the same bodies on the same
    # values; the device bodies are XLA's, equal to rounding
    np.testing.assert_array_equal(got["context"], got["numpy"])
    np.testing.assert_allclose(got["pump"], got["context"], atol=TOL)


def test_compose_takes_any_number_of_pools():
    A = tiled(spd(2 * NB))
    pools = [member(p)(A) for p in (cholesky_ptg, trtri_ptg, lauum_ptg)]
    comp = compose(*pools)
    assert isinstance(comp, CompoundTaskpool) and comp.members == pools
    assert compose(compose(pools[0], pools[1]), pools[2]).members == pools


# -- the DAGs against the loop nests ----------------------------------------

class _Nest:
    """Tasks and edges of an in-place tile loop nest: a task depends on
    the last writer of every tile it reads or writes, and an overwriter
    on every reader of the version it overwrites."""

    def __init__(self):
        self.tasks, self.edges = set(), set()
        self._last = {}      # tile -> the task that wrote its version
        self._readers = {}   # tile -> the tasks that read that version

    def task(self, name, *locs, reads=(), writes):
        t = (name, locs)
        self.tasks.add(t)
        for tile in reads:
            if tile in self._last:
                self.edges.add((self._last[tile], t))
            self._readers.setdefault(tile, []).append(t)
        if writes in self._last:
            self.edges.add((self._last[writes], t))
        self.edges.update((r, t) for r in self._readers.pop(writes, [])
                          if r != t)
        self._last[writes] = t


def trtri_nest(NT):
    """``pztrtri`` (lower), as ``ops/inverse.py``'s docstring has it."""
    g = _Nest()
    for k in range(NT):
        for m in range(k + 1, NT):
            g.task("trtri_trsm_r", k, m, reads=[(k, k)], writes=(m, k))
        for m in range(k + 1, NT):
            for n in range(k):
                g.task("trtri_gemm", k, m, n, reads=[(m, k), (k, n)],
                       writes=(m, n))
        for n in range(k):
            g.task("trtri_trsm_l", k, n, reads=[(k, k)], writes=(k, n))
        g.task("trtri_diag", k, writes=(k, k))
    return g.tasks, g.edges


def lauum_nest(NT):
    """``pzlauum`` (lower)."""
    g = _Nest()
    for k in range(NT):
        for n in range(k):
            g.task("lauum_syrk", k, n, reads=[(k, n)], writes=(n, n))
            for m in range(n + 1, k):
                g.task("lauum_gemm", k, m, n, reads=[(k, m), (k, n)],
                       writes=(m, n))
        for n in range(k):
            g.task("lauum_trmm", k, n, reads=[(k, k)], writes=(k, n))
        g.task("lauum_diag", k, writes=(k, k))
    return g.tasks, g.edges


@pytest.mark.parametrize("NT", [1, 2, 5, 7])
@pytest.mark.parametrize("ptg,nest,classes", [
    (trtri_ptg, trtri_nest, TRTRI_CLASSES),
    (lauum_ptg, lauum_nest, LAUUM_CLASSES)], ids=["trtri", "lauum"])
def test_the_dag_is_the_loop_nests(ptg, nest, classes, NT):
    A = tiled(np.eye(NT * NB))
    tp = ptg(use_tpu=False).taskpool(NT=NT, A=A)
    g = capture(tp, ranks=[0])
    tasks, edges = nest(NT)
    assert set(g.nodes) == tasks and len(tasks) == ntasks(NT)
    assert {c for c, _ in g.nodes} <= set(classes)
    got = {(tid, succ) for tid, node in g.nodes.items()
           for (_f, succ, _sf) in node.out_edges}
    assert got == edges
    assert ptg(use_tpu=False).verify({"NT": NT, "A": A}) == []


# -- the compound on the pump path -------------------------------------------

@pytest.fixture
def dev():
    d = NativeExecutor._make_device()
    yield d
    d.detach()


def lower_bytes(A):
    return sum(int(np.prod(A.tile_shape(*k))) * 4 for k in A.tiles())


def test_the_matrix_goes_in_once_and_comes_home_once(dev):
    attach_plan.clear()
    NT = 5
    S = spd(NT * NB, seed=3)
    for solve in range(2):
        A = tiled(S)
        before = dict(dev.stats)
        ex = NativeExecutor(poinv(A, use_cpu=False), native_device=True,
                            device=dev)
        assert ex.run() == 3 * ntasks(NT)
        ex.close()
        st = ex.stats
        moved = {k: dev.stats[k] - before[k] for k in
                 ("bytes_in", "bytes_out", "evictions", "donation_refused")}
        assert moved == {"bytes_in": lower_bytes(A),
                         "bytes_out": lower_bytes(A),
                         "evictions": 0, "donation_refused": 0}
        assert st["members_run"] == 3
        assert st["member_home_bytes"] == st["member_restaged_tiles"] == 0
        # every tile, at both boundaries
        assert st["member_kept_tiles"] == 2 * NT * (NT + 1) // 2
        assert st["member_kept_bytes"] == 2 * lower_bytes(A)
        assert st["trampoline_entries"] == st["completion_callbacks"] == 0
        assert (st["attach_plan_misses"], st["attach_plan_hits"]) == \
            ((3, 0) if solve == 0 else (0, 3))
        assert lower_err(A, np.linalg.inv(S)) < TOL


def test_nothing_goes_home_before_the_last_member(dev):
    """The factor and ``trtri``'s version never leave the device: at the
    end of ``run()`` the bytes home are at most the lower matrix (what
    ``lauum`` sent as its versions became final)."""
    NT = 4
    A = tiled(spd(NT * NB, seed=4))
    ex = NativeExecutor(poinv(A, use_cpu=False), native_device=True,
                        device=dev)
    homes = [[t._tpu_home for t in m._pump_index.values()]
             for m in ex._members]
    assert all(h == () or h is None or not h for h in homes[0] + homes[1])
    assert sum(len(h) for h in homes[2]) == NT * (NT + 1) // 2
    ex.run()
    ex.close()
    assert dev.stats["bytes_out"] == lower_bytes(A)


def test_a_pool_run_alone_after_a_compound_keeps_its_home_set(dev):
    """The Cholesky pool's stored plan served a compound's member 1 (no
    tile home); the same shape run ALONE binds the same plan and still
    brings its factor home."""
    attach_plan.clear()
    NT = 4
    S = spd(NT * NB, seed=5)
    A = tiled(S)
    ex = NativeExecutor(poinv(A, use_cpu=False), native_device=True,
                        device=dev)
    ex.run()
    ex.close()
    B = tiled(S)
    alone = NativeExecutor(
        cholesky_ptg(use_cpu=False).taskpool(NT=NT, A=B),
        native_device=True, device=dev)
    assert alone.stats["attach_plan_hits"] == 1
    assert sum(len(t._tpu_home) for t in alone._pump_index.values()) == \
        NT * (NT + 1) // 2
    alone.run()
    alone.close()
    assert lower_err(B, np.linalg.cholesky(S)) < TOL
    assert alone.stats["members_run"] == 0


def test_a_member_that_raises_leaves_the_device_usable(dev):
    NT = 3
    S = spd(NT * NB, seed=6)
    bad = S.copy()
    bad[NB:, NB:] = np.nan   # the factor's trailing tiles: member 2 reads them
    A = tiled(bad)

    def boom(T, **_):
        raise RuntimeError("planted")

    comp = poinv(A, use_cpu=False)
    comp.members[1].ptg.classes["trtri_diag"].bodies["tpu"] = boom
    ex = NativeExecutor(comp, native_device=True, device=dev)
    with pytest.raises(RuntimeError):
        ex.run()
    assert ex.stats["members_run"] == 1
    try:
        ex.close()
    except Exception:
        pass
    assert not dev._res.owed and not dev._res.handed
    B = tiled(S)
    assert run_pump(poinv_of, B, device=dev) == 3 * ntasks(NT)
    assert lower_err(B, np.linalg.inv(S)) < TOL


def test_a_compound_of_something_else_is_refused():
    from parsec_tpu.core.taskpool import Taskpool

    A = tiled(spd(NB))
    comp = compose(member(cholesky_ptg)(A), Taskpool(name="plain"))
    with pytest.raises(TypeError, match="PTG"):
        NativeExecutor(comp)


def test_member_spans_in_order(dev):
    """``pump:member`` around each member; ``pump:member_gap`` its first
    child from the second on, ended before its first
    ``dev:submit_batch``."""
    from parsec_tpu.profiling import pins

    seen = []
    subs = []
    for site in ("pump:member_begin", "pump:member_end",
                 "pump:member_gap_begin", "pump:member_gap_end",
                 "dev:submit_batch_begin"):
        def cb(es, payload, _site=site):
            if _site.startswith("dev:"):
                if seen and seen[-1][0] == _site:
                    return
                seen.append((_site, None))
            else:
                seen.append((_site, dict(payload)))
        pins.subscribe(site, cb)
        subs.append((site, cb))
    try:
        NT = 3
        A = tiled(spd(NT * NB, seed=7))
        ex = NativeExecutor(poinv(A, use_cpu=False), native_device=True,
                            device=dev)
        ex.run()
        ex.close()
    finally:
        for site, cb in subs:
            pins.unsubscribe(site, cb)
    names = [s for s, _ in seen]
    later = ["pump:member_begin", "pump:member_gap_begin",
             "pump:member_gap_end", "dev:submit_batch_begin",
             "pump:member_end"]
    assert names == ["pump:member_begin", "dev:submit_batch_begin",
                     "pump:member_end"] + later * 2
    members = [p for s, p in seen if s == "pump:member_begin"]
    assert [p["member"] for p in members] == [0, 1, 2]
    assert all(p["tasks"] == ntasks(NT) for p in members)
    gaps = [p for s, p in seen if s == "pump:member_gap_end"]
    assert [p["kept_tiles"] for p in gaps] == [6, 6]
    assert [p["kept_bytes"] for p in gaps] == [lower_bytes(A)] * 2
