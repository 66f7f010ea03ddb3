"""The tile Cholesky as a DTD program (``ops.cholesky.cholesky_dtd``:
DPLASMA's ``testing_dpotrf_dtd.c``): against float64 numpy, against the
PTG form of the same mathematics (bitwise with CPU bodies), the graph
the insertions infer against the PTG's captured graph, the tiles' way
home (at the flush, once; at ``close()`` for a tile never flushed), and
the insertion window."""

import numpy as np
import pytest

from parsec_tpu import Context, DEV_TPU
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl import DTDTaskpool
from parsec_tpu.dsl.dtd_native import NativeDTD
from parsec_tpu.dsl.graph import capture
from parsec_tpu.ops import cholesky_dtd, cholesky_ptg
from parsec_tpu.utils import mca_param


def spd(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.random((n, n), dtype=np.float32) - np.float32(0.5)
    return ((a + a.T) / 2
            + np.float32(0.75 * np.sqrt(n)) * np.eye(n, dtype=np.float32))


def matrix(M, nb):
    n = M.shape[0]
    return TiledMatrix(n, n, nb, nb, name="A",
                       dtype=np.float32).from_array(M.copy())


def ntasks(nt):
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


def tpu_dev(ctx):
    return next(d for d in ctx.devices if d.device_type == DEV_TPU)


def dtd_factor(ctx, M, nb, window=None, **kw):
    """The user's calling sequence; returns the lower factor and the
    pool's counters."""
    A = matrix(M, nb)
    tp = DTDTaskpool(ctx)
    if window:
        tp.window, tp.threshold = window, window // 2
    inserted = cholesky_dtd(tp, A, **kw)
    assert inserted == ntasks(A.mt)
    assert tp.wait(timeout=300)
    tp.flush_all(A)
    tp.close()
    return np.tril(A.to_array()), tp.counters()


def tiles_close(L, ref, nb, tol):
    """Tile by tile, relative to the factor's largest entry."""
    nt = L.shape[0] // nb
    scale = np.abs(ref).max()
    for i in range(nt):
        for j in range(i + 1):
            got = L[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            want = ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            assert np.abs(got - want).max() / scale < tol, (i, j)


@pytest.fixture
def ctx():
    c = Context(nb_cores=4)
    yield c
    c.fini()


# -- (a) against float64 numpy, tile by tile --------------------------------

@pytest.mark.parametrize("n,nb,window", [(256, 32, 16), (512, 64, 32),
                                         (1024, 128, 64)])
@pytest.mark.parametrize("bodies", ["cpu", "device"])
def test_the_dtd_factor_is_the_float64_cholesky(ctx, n, nb, window, bodies):
    M = spd(n, seed=n)
    kw = dict(use_tpu=False, use_cpu=True) if bodies == "cpu" \
        else dict(use_tpu=True, use_cpu=False)
    L, counters = dtd_factor(ctx, M, nb, window, **kw)
    tiles_close(L, np.linalg.cholesky(M.astype(np.float64)), nb, 3e-6)
    assert counters["dtd_inserted"] == ntasks(n // nb)
    assert counters["dtd_renames"] == 0
    assert counters["dtd_window_stalls"] >= 1  # the window filled
    assert counters["dtd_flushed_tiles"] == (n // nb) * (n // nb + 1) // 2


@pytest.mark.parametrize("bodies", ["cpu", "device"])
def test_the_trtri_form_inserts_its_scratch_tiles(ctx, bodies):
    M = spd(256, seed=3)
    kw = dict(use_tpu=False, use_cpu=True) if bodies == "cpu" \
        else dict(use_tpu=True, use_cpu=False)
    A = matrix(M, 32)
    tp = DTDTaskpool(ctx)
    assert cholesky_dtd(tp, A, use_trtri=True, **kw) == ntasks(8) + 7
    assert tp.wait(timeout=300)
    tp.flush_all(A)
    tp.close()
    tiles_close(np.tril(A.to_array()),
                np.linalg.cholesky(M.astype(np.float64)), 32, 3e-6)
    with pytest.raises(ValueError, match="scratch tiles"):
        cholesky_dtd(DTDTaskpool(ctx), A, use_trtri=True,
                     tile=lambda m, n: None)


def test_the_native_dtd_takes_the_same_insertion_program():
    M = spd(256, seed=5)
    A = matrix(M, 32)
    with NativeDTD(nthreads=4) as tp:
        n = cholesky_dtd(
            tp, A, use_tpu=False, use_cpu=True,
            tile=lambda i, j: A.data_of(i, j).get_copy(0).payload)
    assert n == tp.inserted == ntasks(8)
    tiles_close(np.tril(A.to_array()),
                np.linalg.cholesky(M.astype(np.float64)), 32, 3e-6)


# -- (b) against the PTG form ------------------------------------------------

def ptg_factor(ctx, M, nb, **kw):
    A = matrix(M, nb)
    tp = cholesky_ptg(**kw).taskpool(NT=A.mt, A=A)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=300)
    tpu_dev(ctx).flush()
    return np.tril(A.to_array())


@pytest.mark.parametrize("n,nb", [(256, 32), (512, 64)])
def test_dtd_and_ptg_are_bitwise_one_with_cpu_bodies(ctx, n, nb):
    """Same bodies, same order of updates a tile: the two DSLs differ in
    how the graph is known, not in what is computed."""
    M = spd(n, seed=11)
    kw = dict(use_tpu=False, use_cpu=True)
    L_dtd, _ = dtd_factor(ctx, M, nb, 32, **kw)
    assert np.array_equal(L_dtd, ptg_factor(ctx, M, nb, **kw))
    # and both DTDs
    A = matrix(M, nb)
    with NativeDTD(nthreads=4) as tp:
        cholesky_dtd(tp, A, tile=lambda i, j:
                     A.data_of(i, j).get_copy(0).payload, **kw)
    assert np.array_equal(L_dtd, np.tril(A.to_array()))


def test_dtd_and_ptg_agree_through_the_device_module(ctx):
    """A wave's composition follows the schedule, so not bitwise: both
    inside the tile configurations' ``factor_error`` class."""
    M = spd(512, seed=13)
    ref = np.linalg.cholesky(M.astype(np.float64))
    kw = dict(use_tpu=True, use_cpu=False)
    L_dtd, _ = dtd_factor(ctx, M, 64, 32, **kw)
    L_ptg = ptg_factor(ctx, M, 64, **kw)
    scale = np.abs(ref).max()
    assert np.abs(L_dtd - ref).max() / scale < 3e-6
    assert np.abs(L_ptg - ref).max() / scale < 3e-6
    assert np.abs(L_dtd - L_ptg).max() / scale < 3e-6


# -- (c) the graph the insertions infer --------------------------------------

def task_id(task, where):
    """``(class, locals)`` of an inserted dpotrf task, as the PTG names
    it, from the tiles it was given (``where``: data_id -> (m, n))."""
    tiles = [where[s[1].data_id] for s in task.body_args if s[0] == "data"]
    cls = task.task_class.name
    if cls == "potrf":
        return cls, (tiles[0][0],)
    if cls == "trsm":      # (k, k) IN, (m, k) INOUT
        return cls, (tiles[0][0], tiles[1][0])
    if cls == "syrk":      # (m, m) INOUT, (m, k) IN
        return cls, (tiles[1][1], tiles[0][0])
    (m, n), (_, k) = tiles[0], tiles[1]   # gemm: (m, n), (m, k), (n, k)
    return cls, (k, m, n)


@pytest.mark.parametrize("nt", [4, 8])
def test_the_inferred_graph_is_the_captured_ptg_graph(monkeypatch, nt):
    """Nothing executes while the user inserts (one stream, which only
    ``wait`` drives), so every dependency found is an edge added."""
    nb = 16
    M = spd(nt * nb, seed=nt)
    A = matrix(M, nb)
    where = {A.data_of(i, j).data_id: (i, j)
             for i in range(nt) for j in range(i + 1)}
    edges = []
    add = DTDTaskpool._add_edge

    def recording(pred, succ, state):
        added = add(pred, succ, state)
        if added:
            edges.append((pred, succ))
        return added

    monkeypatch.setattr(DTDTaskpool, "_add_edge", staticmethod(recording))
    ctx = Context(nb_cores=1)
    try:
        tp = DTDTaskpool(ctx)
        tasks = []
        insert = tp.insert_task
        tp.insert_task = lambda *a, **kw: tasks.append(insert(*a, **kw))
        cholesky_dtd(tp, A, use_tpu=False, use_cpu=True)
        inferred = {(task_id(p, where), task_id(s, where)) for p, s in edges}
        assert len(inferred) == len(edges) == tp.counters()["dtd_edges"]
        g = capture(cholesky_ptg(use_tpu=False, use_cpu=True)
                    .taskpool(NT=nt, A=matrix(M, nb)))
        captured = {(tid, succ) for tid, node in g.nodes.items()
                    for (_f, succ, _sf) in node.out_edges}
        assert inferred == captured
        assert {task_id(t, where): t.priority for t in tasks} == \
            {tid: node.priority for tid, node in g.nodes.items()}
        assert tp.wait(timeout=120)
        tp.flush_all(A)
        tp.close()
    finally:
        ctx.fini()
    tiles_close(np.tril(A.to_array()),
                np.linalg.cholesky(M.astype(np.float64)), nb, 3e-6)


# -- (d) a tile's way home ----------------------------------------------------

def test_a_tile_goes_home_at_its_flush_once_and_not_before(ctx):
    dev = tpu_dev(ctx)
    M = spd(256, seed=17)
    A = matrix(M, 32)
    lower = 36 * 32 * 32 * 4
    out0 = dev.stats["bytes_out"]
    tp = DTDTaskpool(ctx)
    cholesky_dtd(tp, A)
    assert tp.wait(timeout=300)
    dev.flush()   # whatever the committer was given has landed
    assert dev.stats["bytes_out"] == out0     # nothing: no copy home yet
    assert all(A.data_of(i, j).newest_copy().device_index == dev.data_index
               for i in range(8) for j in range(i + 1))
    tp.flush_all(A)
    assert dev.stats["bytes_out"] - out0 == lower   # d2h_per_result 1.00
    assert tp.counters()["dtd_flushed_tiles"] == 36
    assert not tp._tiles   # no tile state (and no payload) is kept
    tp.close()
    tiles_close(np.tril(A.to_array()),
                np.linalg.cholesky(M.astype(np.float64)), 32, 3e-6)
    # a second flush has nothing to bring
    tp2 = DTDTaskpool(ctx)
    tp2.flush_all(A)
    tp2.close()
    assert dev.stats["bytes_out"] - out0 == lower


def test_a_tile_never_flushed_comes_home_at_close(ctx):
    dev = tpu_dev(ctx)
    M = spd(256, seed=19)
    A = matrix(M, 32)
    out0 = dev.stats["bytes_out"]
    tp = DTDTaskpool(ctx)
    cholesky_dtd(tp, A)
    assert tp.wait(timeout=300)
    tp.data_flush(A.data_of(7, 7))      # one tile the user asked for
    assert dev.stats["bytes_out"] - out0 == 32 * 32 * 4
    tp.close()                          # the other 35: handed over here
    assert ctx.wait(timeout=60)
    dev.flush()
    assert dev.stats["bytes_out"] - out0 == 36 * 32 * 32 * 4
    host = np.zeros_like(M)
    for i in range(8):
        for j in range(i + 1):
            hc = A.data_of(i, j).get_copy(0)
            assert hc.version == A.data_of(i, j).newest_copy().version
            host[i * 32:(i + 1) * 32, j * 32:(j + 1) * 32] = hc.payload
    tiles_close(np.tril(host), np.linalg.cholesky(M.astype(np.float64)),
                32, 3e-6)


# -- (e) the window -----------------------------------------------------------

@pytest.mark.parametrize("nt,window,threshold", [(8, 32, 16), (8, 64, 32),
                                                 (6, 16, 8)])
def test_the_window_stalls_as_often_as_the_sizes_imply(nt, window, threshold):
    """Execution held back: with one stream, tasks run only when the
    inserter helps at a full window (or waits)."""
    M = spd(nt * 16, seed=23)
    A = matrix(M, 16)
    total = ntasks(nt)
    ctx = Context(nb_cores=1)
    try:
        tp = DTDTaskpool(ctx)
        tp.window, tp.threshold = window, threshold
        peak = []
        throttle = tp._throttle_window

        def watched():
            peak.append(tp._inserted - tp._retired)
            throttle()
            assert tp._inserted - tp._retired <= window

        tp._throttle_window = watched
        cholesky_dtd(tp, A, use_tpu=False, use_cpu=True)
        c = tp.counters()
        assert max(peak) == window            # never more in flight
        assert c["dtd_window_stalls"] == \
            (total - window) // (window - threshold) + 1
        # what left the window, the inserter executed itself
        assert c["dtd_helped"] == total - (tp._inserted - tp._retired)
        assert c["dtd_window_stall_s"] > 0
        assert 0 < c["dtd_insert_done_s"]
        assert tp.wait(timeout=120)
        tp.flush_all(A)
        tp.close()
    finally:
        ctx.fini()
    tiles_close(np.tril(A.to_array()),
                np.linalg.cholesky(M.astype(np.float64)), 16, 3e-6)


def test_no_stall_at_a_window_larger_than_the_dag(ctx):
    assert mca_param.get("dtd", "window_size") == 2048
    assert mca_param.get("dtd", "threshold_size") == 1024
    _, counters = dtd_factor(ctx, spd(256, seed=29), 32,
                             use_tpu=False, use_cpu=True)
    assert counters["dtd_window_stalls"] == 0
    assert counters["dtd_window_stall_s"] == 0.0
    assert counters["dtd_helped"] == 0
