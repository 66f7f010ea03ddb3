"""Tile QR through the pump, and the scratch tiles of its ``NEW`` flows.

``qr_ptg`` runs through ``NativeExecutor(native_device=True)`` against
the benchmark's plain reference (an unblocked Householder QR in float32)
and against ``numpy.linalg.qr``; pump mode is held; the dense Q blocks
are born on the device, never cross the host and are freed with their
last consumer; waves of both tile shapes batch; the same PTG through
``Context`` gives the same R.  A body that READS its ``NEW`` input gets
zeros, on the device, on the host and in a mixed graph.
"""

import numpy as np
import pytest

from benchmark.reference.sgeqrf_gram import householder_r
from parsec_tpu import Context, native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.dsl.ptg import PTG
from parsec_tpu.ops.qr import qr_ptg
from parsec_tpu.profiling import pins

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")
NB = 32
INOUT, IN = AccessMode.INOUT, AccessMode.IN


@pytest.fixture(autouse=True)
def _clean_pins():
    pins.clear()
    yield
    pins.clear()


def _matrix(nt, seed):
    n = nt * NB
    a = np.random.default_rng(seed).uniform(-0.5, 0.5, (n, n)) \
        .astype(np.float32)
    return a, TiledMatrix(n, n, NB, NB, name="A",
                          dtype=np.float32).from_array(a)


def _taskpool(A, **kw):
    return qr_ptg(use_tpu=True, use_cpu=False, **kw).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32,
        QSHAPE2=(np.float32, (2 * NB, 2 * NB)))


def _pump(A, tp=None):
    """One solve through the pump: (executor stats, device stats)."""
    ex = NativeExecutor(tp or _taskpool(A), native_device=True)
    dev = ex.device
    ran = ex.run()
    ex.close()
    assert ran == len(ex.graph.nodes)
    return ex.stats, dev.stats


def _canonical(r):
    r = np.asarray(r, np.float64)
    return r * np.where(np.diagonal(r) < 0, -1.0, 1.0)[:, None]


def _close(got, want, tol):
    assert np.max(np.abs(_canonical(got) - _canonical(want))) \
        <= tol * np.max(np.abs(want))


@pytest.mark.parametrize("nt", [2, 4, 6])
def test_pump_qr_gives_r_of_both_references(nt):
    a, A = _matrix(nt, seed=nt)
    s, d = _pump(A)
    R = A.to_array()
    assert np.max(np.abs(np.tril(R, -1))) == 0.0
    _close(R, householder_r(a), 2e-5)
    _close(R, np.linalg.qr(a.astype(np.float64), mode="r"), 2e-5)
    # pump mode held: the interpreter was entered for no task
    ntasks = nt + nt * (nt - 1) + (nt - 1) * nt * (2 * nt - 1) // 6
    assert s["pumped_tasks"] == d["executed_tasks"] == ntasks
    assert s["trampoline_entries"] == s["completion_callbacks"] == 0
    assert d["wave_fallbacks"] == d["submit_retries"] == 0
    # the Q blocks: one a geqrt, one a tsqrt; born and freed on the device
    assert d["scratch_tiles_born"] == nt + nt * (nt - 1) // 2
    assert d["scratch_tiles_freed"] == d["scratch_tiles_born"]
    assert d["tile_args_dropped"] == d["scratch_tiles_born"]
    assert d["scratch_bytes_in"] == d["scratch_bytes_out"] == 0
    # what crossed the host is the matrix, once each way
    assert d["bytes_in"] == d["bytes_out"] == a.nbytes


def test_waves_of_both_tile_shapes_batch_and_say_what_they_dropped():
    waves = []
    pins.subscribe("dev:wave_end", lambda es, p: waves.append(p))
    _a, A = _matrix(6, seed=11)
    _pump(A)
    by_cls = {}
    for w in waves:
        by_cls.setdefault(w["cls"], []).append(w)
    assert {"tsqrt", "tsmqr", "unmqr"} <= set(by_cls)
    for w in by_cls["tsqrt"]:       # (R, B, Q): Q unborn, three outputs
        assert w["tdrop"] == w["n"] and w["outs"] == 3 * w["n"]
    for w in by_cls["tsmqr"]:       # Q is read: nothing dropped
        assert w["tdrop"] == 0 and w["outs"] == 2 * w["n"]
    # (a wave goes out in power-of-two chunks: the last may be one task)
    assert max(w["n"] for w in by_cls["tsqrt"]) >= 2
    assert max(w["n"] for w in by_cls["tsmqr"]) >= 4
    assert sum(w["n"] for w in by_cls["tsmqr"]) > \
        sum(w["n"] for w in by_cls["tsqrt"]) > 0


def test_the_same_ptg_through_context_gives_the_same_r():
    a, A = _matrix(4, seed=5)
    _pump(A)
    _a, B = _matrix(4, seed=5)
    with Context(nb_cores=2) as ctx:
        tp = _taskpool(B)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
        dev = next(d for d in ctx.devices if d.mca_name == "tpu")
        born, freed = (dev.stats["scratch_tiles_born"],
                       dev.stats["scratch_tiles_freed"])
        moved = dev.stats["scratch_bytes_in"] + dev.stats["scratch_bytes_out"]
    assert born == freed == 4 + 6 and moved == 0
    _close(B.to_array(), A.to_array(), 1e-6)
    _close(B.to_array(), householder_r(a), 2e-5)


def test_the_control_path_is_bf16_class():
    a, A = _matrix(4, seed=9)
    _pump(A, _taskpool(A, bf16_updates=True))
    R, want = A.to_array(), np.linalg.qr(a.astype(np.float64), mode="r")
    err = np.max(np.abs(_canonical(R) - _canonical(want))) \
        / np.max(np.abs(want))
    assert 1e-4 < err < 5e-2


# -- a body that READS its NEW input ---------------------------------------

def _reading_ptg(use_on_cpu):
    """fill(k): T += sum(S) + 1, S += 2 with S ``<- NEW``; use(k):
    T += S[0, 0].  Zeros in S give A + 3 on the diagonal tiles."""
    ptg = PTG("reads_new")
    fill = ptg.task_class("fill", k="0 .. NT-1")
    fill.affinity("A(k, k)")
    fill.flow("T", INOUT, "<- A(k, k)", "-> T use(k)")
    fill.flow("S", INOUT, "<- NEW", "-> S use(k)")
    fill.body(tpu=lambda T, S, **_: (T + S.sum() + 1.0, S + 2.0))
    use = ptg.task_class("use", k="0 .. NT-1")
    use.affinity("A(k, k)")
    use.flow("T", INOUT, "<- T fill(k)", "-> A(k, k)")
    use.flow("S", IN, "<- S fill(k)")
    if use_on_cpu:
        def use_cpu(T, S, **_):
            T += S[0, 0]
        use.body(cpu=use_cpu)
    else:
        use.body(tpu=lambda T, S, **_: T + S[0, 0])
    return ptg


@pytest.mark.parametrize("use_on_cpu", [False, True],
                         ids=["device", "mixed"])
def test_a_body_that_reads_its_new_input_gets_zeros(use_on_cpu):
    a, A = _matrix(3, seed=2)
    tp = _reading_ptg(use_on_cpu).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(NB, NB), TILE_DTYPE=np.float32)
    _s, d = _pump(A, tp)
    want = a.copy()
    for k in range(3):
        want[k * NB:(k + 1) * NB, k * NB:(k + 1) * NB] += 3.0
    np.testing.assert_allclose(A.to_array(), want, rtol=0, atol=1e-6)
    # read or not, an unborn tile is no argument of the program
    assert d["tile_args_dropped"] == d["scratch_tiles_born"] == 3
    # a CPU consumer never releases: the tile stays until its Data dies
    assert d["scratch_tiles_freed"] == (0 if use_on_cpu else 3)
    assert d["scratch_bytes_in"] == 0


def test_a_cpu_body_gets_zeros_for_its_new_input_through_context():
    ptg = PTG("reads_new_cpu")
    fill = ptg.task_class("fill", k="0 .. NT-1")
    fill.affinity("A(k, k)")
    fill.flow("T", INOUT, "<- A(k, k)", "-> A(k, k)")
    fill.flow("S", INOUT, "<- NEW")

    def fill_cpu(T, S, **_):
        T += S.sum() + 1.0
        S += 2.0
    fill.body(cpu=fill_cpu)
    a, A = _matrix(2, seed=3)
    with Context(nb_cores=2) as ctx:
        tp = ptg.taskpool(NT=A.mt, A=A, TILE_SHAPE=(NB, NB),
                          TILE_DTYPE=np.float32)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=60)
    want = a.copy()
    for k in range(2):
        want[k * NB:(k + 1) * NB, k * NB:(k + 1) * NB] += 1.0
    np.testing.assert_allclose(A.to_array(), want, rtol=0, atol=1e-6)


def test_an_evicted_scratch_tile_is_spilled_and_comes_back():
    """Under memory pressure a scratch tile that still has users goes to
    the host like any dirty tile: the only way scratch bytes move."""
    a, A = _matrix(4, seed=4)
    ex = NativeExecutor(_taskpool(A), native_device=True)
    dev = ex.device
    dev.hbm_budget = 12 * NB * NB * 4      # twelve tiles of room
    ex.run()
    ex.close()
    assert dev.stats["evictions"] > 0
    _close(A.to_array(), np.linalg.qr(a.astype(np.float64), mode="r"),
           2e-5)
    # (a spilled tile is staged again for every later consumer it missed)
    assert 0 < dev.stats["scratch_bytes_out"] <= dev.stats["scratch_bytes_in"]


@pytest.mark.parametrize("dag", ["geqrf", "dpotrf"])
def test_only_the_version_the_dag_sends_home_goes_to_the_committer(
        dag, monkeypatch):
    """The executor knows from the captured graph which output is the
    last of its tile: every tile is enqueued once, however often the DAG
    rewrites it (tsmqr's C2 declares ``-> A(m, n)`` at every step) —
    but the tiles a kill leaves as zeros: their body says so (``_zeros``)
    and zeros are landed at home without a copy from the device."""
    from parsec_tpu.device.staging import WritebackCommitter
    from parsec_tpu.ops import cholesky_ptg

    seen = []
    enqueue_all = WritebackCommitter.enqueue_all  # every hand-off's way in
    monkeypatch.setattr(
        WritebackCommitter, "enqueue_all",
        lambda self, datas, *a, **kw: (seen.extend(d.key for d in datas),
                                       enqueue_all(self, datas, *a, **kw))[1])
    a, A = _matrix(4, seed=6)
    blank = 0
    if dag == "geqrf":
        tp, tiles, blank = _taskpool(A), 16, 6  # (the tiles under R)
    else:
        spd = (a @ a.T + 4 * NB * np.eye(4 * NB)).astype(np.float32)
        A.from_array(spd)
        tp, tiles = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(
            NT=A.mt, A=A), 10
    _s, d = _pump(A, tp)
    assert len(seen) == len(set(seen)) == tiles - blank
    assert d["wb_zeros_landed"] == blank
    assert all(m <= n for (m, n) in seen) or not blank
    assert d["bytes_out"] == tiles * NB * NB * 4
    if blank:
        assert not np.tril(A.to_array(), -1).any()
