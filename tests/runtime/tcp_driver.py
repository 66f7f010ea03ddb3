"""Per-rank driver for the TCP backend tests (run as a subprocess per rank
by test_tcp.py; scenario name in argv[1]).  Prints one JSON line of
per-rank results on success; any assertion failure exits nonzero.
"""

import faulthandler
import json
import os
import signal
import sys
import time

import numpy as np

# kill -USR1 <pid> dumps all thread stacks to /tmp/tcpdrv_<pid>.stacks
_fh = open(f"/tmp/tcpdrv_{os.getpid()}.stacks", "w")
faulthandler.register(signal.SIGUSR1, file=_fh)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU device module only: these scenarios exercise the wire, and ranks
# started by comm.launch are CPU-device ranks (one process owns a chip)
os.environ.setdefault("PARSEC_MCA_device_enabled", "cpu")

from parsec_tpu import Context  # noqa: E402
from parsec_tpu.comm import endpoint_from_env  # noqa: E402
from parsec_tpu.comm.engine import TAG_USER_BASE  # noqa: E402
from parsec_tpu.data import LocalCollection  # noqa: E402
from parsec_tpu.dsl.ptg import PTG, IN, INOUT  # noqa: E402


def scenario_smoke(ce):
    """AM echo, aggregation, one-sided get, barrier — pure CE layer."""
    got = []
    ce.register_am(TAG_USER_BASE, lambda src, p: got.append((src, p)))
    ce.barrier()
    # every rank sends 3 AMs to every other rank (exercises per-peer batching)
    for dst in range(ce.nranks):
        if dst != ce.rank:
            for i in range(3):
                ce.send_am(TAG_USER_BASE, dst, {"from": ce.rank, "i": i})
    deadline = time.time() + 30
    while len(got) < 3 * (ce.nranks - 1):
        time.sleep(0.005)
        assert time.time() < deadline, f"only {len(got)} AMs arrived"
    assert sorted(p["i"] for _, p in got) == sorted(list(range(3)) * (ce.nranks - 1))

    # one-sided get of a large registered buffer
    payload = np.arange(65536, dtype=np.float64) + ce.rank
    ce.mem_register(("blk", ce.rank), payload)
    ce.barrier()
    pulled = []
    src = (ce.rank + 1) % ce.nranks
    ce.get(src, ("blk", src), lambda buf: pulled.append(buf))
    deadline = time.time() + 30
    while not pulled:
        time.sleep(0.005)
        assert time.time() < deadline, "get never completed"
    np.testing.assert_allclose(pulled[0], np.arange(65536, dtype=np.float64) + src)
    ce.barrier()
    return {"ams": len(got), "get_bytes": int(ce.stats["get_bytes"])}


def scenario_ptg_chain(ce):
    """Cross-rank PTG chain: every dependency crosses the real wire."""
    n = 12
    seen = []
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    dc = LocalCollection("D", shape=(n,), nodes=ce.nranks, myrank=ce.rank,
                         init=lambda k: np.zeros(4))
    dc.rank_of = lambda *key: dc.data_key(*key) % ce.nranks

    ptg = PTG("chain")
    step = ptg.task_class("step", k="0 .. N-1")
    step.affinity("D(k)")
    step.flow("X", INOUT,
              "<- (k == 0) ? D(0) : X step(k-1)",
              "-> (k < N-1) ? X step(k+1) : D(k)")

    def body(X, k):
        seen.append(k)
        X += 1.0

    step.body(cpu=body)
    tp = ptg.taskpool(N=n, D=dc)
    ctx.add_taskpool(tp)
    ok = tp.wait(timeout=90)
    assert ok, "taskpool did not quiesce"
    assert seen == list(range(ce.rank, n, ce.nranks)), seen
    # final value: D(n-1) on its owner holds n increments
    if dc.rank_of(n - 1) == ce.rank:
        final = dc.data_of(n - 1).newest_copy().payload
        np.testing.assert_allclose(final, np.full(4, float(n)))
    ce.barrier()
    ctx.fini()
    return {"seen": seen}


def scenario_ptg_bigpayload(ce):
    """Broadcast with a payload above the short limit → GET path on wire."""
    from parsec_tpu.utils import mca_param

    mca_param.set_param("runtime", "comm_short_limit", 64)
    got = []
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    dc = LocalCollection("D", shape=(8,), nodes=ce.nranks, myrank=ce.rank,
                         init=lambda k: np.arange(1024.0))
    dc.rank_of = lambda *key: dc.data_key(*key) % ce.nranks

    ptg = PTG("big")
    src = ptg.task_class("src")
    src.affinity("D(0)")
    src.flow("X", INOUT, "<- D(0)", "-> X sink(0 .. NR-1)")
    src.body(cpu=lambda X: X.__imul__(3.0))
    sink = ptg.task_class("sink", r="0 .. NR-1")
    sink.affinity("D(r)")
    sink.flow("X", IN, "<- X src()")
    sink.body(cpu=lambda X, r: got.append(X.copy()))
    tp = ptg.taskpool(NR=ce.nranks, D=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=90)
    # the sink on THIS rank saw the producer's value
    mine = [g for g in got]
    assert len(mine) == 1, f"expected 1 local sink, got {len(mine)}"
    np.testing.assert_allclose(mine[0], np.arange(1024.0) * 3.0)
    stats = dict(rank=ce.rank,
                 get_issued=int(ctx.comm.remote_dep.stats["get_issued"]))
    if ce.rank != 0:
        assert stats["get_issued"] >= 1, "big payload should use GET path"
    ce.barrier()
    ctx.fini()
    return stats


def scenario_dtd_gemm(ce):
    """Distributed DTD tiled GEMM over real processes: shadow-task
    protocol, epoch transfers, and cross-rank flush on the wire. Ragged
    N=80/NB=32 yields 8192-, 4096- and 2048-byte tiles around the 4096-byte
    short limit, so both the inline and GET paths carry real traffic."""
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import AFFINITY, DTDTaskpool, IN, INOUT
    from parsec_tpu.utils import mca_param

    mca_param.set_param("runtime", "comm_short_limit", 4096)
    N, NB = 80, 32
    p = 2 if ce.nranks % 2 == 0 else 1
    q = ce.nranks // p
    rng = np.random.default_rng(11)
    A0 = rng.standard_normal((N, N))
    B0 = rng.standard_normal((N, N))
    C_ref = A0 @ B0

    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    mk = lambda nm: TwoDimBlockCyclic(N, N, NB, NB, p=p, q=q,
                                      nodes=ce.nranks, myrank=ce.rank, name=nm)
    A, B, C = mk("tA"), mk("tB"), mk("tC")
    A.from_array(A0)
    B.from_array(B0)

    dtd = DTDTaskpool(ctx, name="tcp_gemm")

    def gemm(a, b, c):
        c += a @ b

    nt = A.nt
    for i in range(nt):
        for j in range(nt):
            for k in range(nt):
                dtd.insert_task(gemm,
                                (A.data_of(i, k), IN),
                                (B.data_of(k, j), IN),
                                (C.data_of(i, j), INOUT | AFFINITY))
    dtd.flush_all()
    dtd.close()
    # every local tile of C must match the reference product
    for (i, j) in C.local_tiles():
        h, w = C.tile_shape(i, j)
        got = np.asarray(C.data_of(i, j).newest_copy().payload)[:h, :w]
        ref = C_ref[i * NB:i * NB + h, j * NB:j * NB + w]
        np.testing.assert_allclose(got, ref, atol=1e-9)
    stats = {"dtd_sent": int(ce.remote_dep.stats["dtd_sent"]),
             "dtd_recv": int(ce.remote_dep.stats["dtd_recv"]),
             "dtd_inline": int(ce.remote_dep.stats["dtd_inline_sent"]),
             "dtd_get": int(ce.remote_dep.stats["dtd_get_advertised"])}
    ce.barrier()
    ctx.fini()
    return stats


def scenario_dist_dpotrf(ce):
    """Distributed dpotrf over real TCP processes — the multi-rank
    RUNTIME perf row (round-2 VERDICT item 3).  Config via env:
    PERF_N, PERF_NB, PERF_P (grid rows; cols = nranks//P)."""
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.ops import cholesky_ptg

    N = int(os.environ.get("PERF_N", "512"))
    nb = int(os.environ.get("PERF_NB", "32"))
    p = int(os.environ.get("PERF_P", "1"))
    q = max(1, ce.nranks // p)
    rng = np.random.default_rng(3)
    M = rng.standard_normal((N, N))
    SPD = M @ M.T + N * np.eye(N)
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    A = TwoDimBlockCyclic(N, N, nb, nb, p=p, q=q, myrank=ce.rank, name="A")
    A.from_array(SPD)
    tp = cholesky_ptg(use_tpu=False, use_cpu=True).taskpool(NT=A.mt, A=A)
    ce.barrier()  # synchronized start: elapsed is comparable across ranks
    t0 = time.perf_counter()
    ctx.add_taskpool(tp)
    ok = tp.wait(timeout=600)
    dt = time.perf_counter() - t0
    assert ok, "dpotrf did not quiesce"
    ce.barrier()
    # spot-check: my local diagonal tiles match the reference factor
    L = np.linalg.cholesky(SPD)
    for (i, j) in A.local_tiles():
        if i == j:
            c = A.data_of(i, j).newest_copy()
            np.testing.assert_allclose(
                np.tril(np.asarray(c.payload)),
                L[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                rtol=1e-6, atol=1e-8)
    ctx.fini()
    nt = N // nb
    return {"elapsed": dt, "ntasks": nt * (nt + 1) * (nt + 2) // 6,
            "acts": int(ce.remote_dep.stats.get("activations_sent", 0))}


def scenario_dist_segchol(ce):
    """Distributed PANEL-SEGMENTED cholesky over real TCP processes
    (round-4: the north-star formulation across ranks) — panel columns
    1D block-cyclic, the factored column broadcast down the activation
    trees, per-owner trailing updates; every local column verified
    against numpy."""
    from parsec_tpu.ops.segmented_chol_dist import dist_segmented_cholesky_ptg

    n, nb = int(os.environ.get("SEG_N", "256")), int(os.environ.get("SEG_NB", "32"))
    rng = np.random.default_rng(7)
    m = rng.standard_normal((n, n)).astype(np.float32)
    SPD = m @ m.T + n * np.eye(n, dtype=np.float32)
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    dc = LocalCollection(
        "C", shape=(n, nb), dtype=np.float32, nodes=ce.nranks,
        myrank=ce.rank,
        init=lambda j: np.ascontiguousarray(SPD[:, j * nb:(j + 1) * nb]))
    dc.rank_of = lambda j: j % ce.nranks
    NT = n // nb
    tp = dist_segmented_cholesky_ptg(n, nb).taskpool(
        NT=NT, C=dc, TILE_SHAPE=(n, nb), TILE_DTYPE=np.float32)
    ce.barrier()
    t0 = time.perf_counter()
    ctx.add_taskpool(tp)
    ok = tp.wait(timeout=300)
    dt = time.perf_counter() - t0
    ce.barrier()
    assert ok, "dist segchol did not quiesce"
    ref = np.linalg.cholesky(SPD.astype(np.float64))
    err = 0.0
    for j in range(NT):
        if j % ce.nranks != ce.rank:
            continue
        col = np.asarray(dc.data_of(j).newest_copy().payload,
                         dtype=np.float64)
        # the panel body zeroes rows above the diagonal block, so the
        # stored column IS tril-form — compare directly
        reftri = np.tril(ref)[:, j * nb:(j + 1) * nb]
        err = max(err, float(np.abs(col - reftri).max()))
    ctx.fini()
    return {"elapsed": dt, "err": err / float(np.abs(ref).max()),
            "acts": int(ce.remote_dep.stats.get("activations_sent", 0))}


def scenario_dtt_pingpong(ce):
    """dtt_bug_replicator-class datatype regression over the REAL TCP
    activation path (reference
    ``/root/reference/tests/runtime/dtt_bug_replicator.jdf`` +
    ``dtt_bug_replicator_ex.c:66-78``: the same flow ping-pongs between
    two ranks under DIFFERENT wire datatypes — whole-tile contiguous one
    way, a transposed/strided vector type the other).  Here each hop's
    producer REBINDS its flow payload to an adversarial layout — PING
    emits A as an F-order transposed view and B contiguous; PONG emits A
    as a stride-2 embedded view and B as an F-order view — so one flow
    carries MIXED shapes/strides across hops, through both the inline
    and the GET wire paths (NB chosen per mode around the short limit).
    Exact pins: activation counts, per-rank payload byte sums (from the
    CommProfiler trace, check-comms discipline), datatype-packed sends,
    and the final values after 2*NT-1 increments."""
    from parsec_tpu.profiling import CommProfiler, Trace
    from parsec_tpu.utils import mca_param

    NB = int(os.environ.get("DTT_NB", "48"))
    NT = 6
    mca_param.set_param("runtime", "comm_short_limit", 4096)
    tile_bytes = NB * NB * 8  # 18432 (GET path) or 2048 (inline) per hop
    prof = CommProfiler(Trace()).install()
    rng = np.random.default_rng(33)
    A0 = rng.standard_normal((NB, NB))
    B0 = rng.standard_normal((NB, NB))
    inits = {0: A0, 1: B0, 2: np.zeros((NB, NB))}
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    try:
        dc = LocalCollection("D", shape=(NB, NB), nodes=ce.nranks,
                             myrank=ce.rank,
                             init=lambda k: inits[k].copy())
        dc.rank_of = lambda *key: 0 if dc.data_key(*key) < 2 else 1

        ptg = PTG("dtt_pingpong")
        ping = ptg.task_class("ping", k="0 .. NT-1")
        ping.affinity("D(0)")
        ping.flow("A", INOUT,
                  "<- (k == 0) ? D(0) : A pong(k-1)",
                  "-> (k < NT-1) ? A pong(k) : D(0)")
        ping.flow("B", INOUT,
                  "<- (k == 0) ? D(1) : B pong(k-1)",
                  "-> (k < NT-1) ? B pong(k) : D(1)")

        def ping_body(A, B, k):
            # A leaves as a row-embedded strided view (Vector blocks=NB,
            # blocklen=NB, stride=2*NB over a bigger base — the LAPACK
            # panel shape, wire-packed via the datatype layer); B leaves
            # contiguous — the DTT1 whole-tile direction
            bigr = np.zeros((2 * NB, NB))
            bigr[::2] = A + 1.0
            A_out = bigr[::2]
            assert not A_out.flags.c_contiguous
            return A_out, B + 1.0

        ping.body(cpu=ping_body)

        pong = ptg.task_class("pong", k="0 .. NT-2")
        pong.affinity("D(2)")
        pong.flow("A", INOUT, "<- A ping(k)", "-> A ping(k+1)")
        pong.flow("B", INOUT, "<- B ping(k)", "-> B ping(k+1)")

        def pong_body(A, B, k):
            # A leaves as a column stride-2 embedded view (non-unit inner
            # stride: the vector-of-single-elements DTT2 analog, gathered
            # by the wire's fallback path); B as an F-CONTIGUOUS array
            # (ships as-is — order preservation is part of the pin)
            big = np.zeros((NB, 2 * NB))
            big[:, ::2] = A + 1.0
            A_out = big[:, ::2]
            assert not A_out.flags.c_contiguous
            B_out = np.asfortranarray(B + 1.0)
            assert B_out.flags.f_contiguous and not B_out.flags.c_contiguous
            return A_out, B_out

        pong.body(cpu=pong_body)
        tp = ptg.taskpool(NT=NT, D=dc)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=90), "dtt pingpong did not quiesce"
        ce.barrier()

        # every hop's increment survived every layout change: the final
        # home tiles hold exactly A0/B0 + (2*NT - 1)
        if ce.rank == 0:
            for key, base in ((0, A0), (1, B0)):
                out = np.asarray(dc.data_of(key).newest_copy().payload)
                np.testing.assert_allclose(out, base + (2 * NT - 1),
                                           rtol=0, atol=1e-12)

        df = prof.trace.to_dataframe()
        act = df[df["name"] == "MPI_ACTIVATE"]
        pld = df[df["name"] == "MPI_DATA_PLD"]
        # exact pins, receiver side: every inbound activation carries
        # both flows' payloads of exactly NB*NB*8 bytes each (nbytes
        # counts DATA, not the strided extent — a layout leak would
        # break the sum)
        n_in = NT - 1
        assert len(pld) == 2 * n_in, (len(pld), n_in)
        assert int(pld["bytes"].sum()) == 2 * n_in * tile_bytes
        assert len(act) == n_in, len(act)
        sent = int(ctx.comm.remote_dep.stats["activations_sent"])
        assert sent == n_in, sent
        # the adversarial layouts really crossed the datatype packer
        packed = int(ce.stats.get("dt_packed", 0))
        assert packed >= n_in, packed
        return {"pld_bytes": int(pld["bytes"].sum()),
                "pld_kinds": sorted(set(pld["kind"])),
                "dt_packed": packed}
    finally:
        ctx.fini()
        prof.uninstall()


def main():
    scenario = sys.argv[1]
    ce = endpoint_from_env()
    fn = globals()[f"scenario_{scenario}"]
    out = fn(ce)
    ce.close()
    print(json.dumps({"rank": ce.rank, "ok": True, **(out or {})}))



def scenario_ptg_qr(ce):
    """Distributed tiled QR over real TCP processes: NEW-flow Q transfers
    and cross-rank final write-backs ('writeback' activation messages)
    on the wire."""
    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.ops.qr import qr_ptg

    N, nb, p, q = 64, 16, 2, ce.nranks // 2
    rng = np.random.default_rng(21)
    A0 = rng.standard_normal((N, N))
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    try:
        A = TwoDimBlockCyclic(N, N, nb, nb, p=p, q=q, myrank=ce.rank, name="A")
        A.from_array(A0)
        tp = qr_ptg(use_tpu=False).taskpool(
            NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=np.float64,
            QSHAPE2=(np.float64, (2 * nb, 2 * nb)))
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120), "qr taskpool did not quiesce"
        ce.barrier()  # all ranks done before reading tiles
        # each rank checks its local tiles against numpy's R (sign-fixed)
        Rnp = np.linalg.qr(A0, mode="r")
        s_n = np.sign(np.diag(Rnp))
        bad = 0
        for (i, j) in A.local_tiles():
            c = A.data_of(i, j).newest_copy()
            tile = np.asarray(c.payload)
            ref = Rnp[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            if i > j:
                ok = np.abs(tile).max() < 1e-9
            else:
                # row signs follow the diagonal convention of OUR factor;
                # compare via R^T R restriction: cheap local check is the
                # absolute-value match after sign canonicalisation
                s_rows = s_n[i * nb:(i + 1) * nb]
                ok = np.allclose(np.abs(tile), np.abs(ref), rtol=1e-7, atol=1e-7)
            bad += 0 if ok else 1
        assert bad == 0, f"rank {ce.rank}: {bad} bad tiles"
        return {"tiles": len(list(A.local_tiles()))}
    finally:
        ctx.fini()



def scenario_multipool(ce):
    """Concurrent heterogeneous taskpools on ONE context per rank over
    the REAL wire (the serving-plane correctness floor): a distributed
    dpotrf, a no-pivot LU and a cross-rank chain execute SIMULTANEOUSLY,
    their activations interleaving on one TCP engine.  Every local tile
    must be BIT-IDENTICAL to a solo single-process run of the same
    factorization, and each pool's termdet must close its books."""
    from parsec_tpu.datadist import TiledMatrix, TwoDimBlockCyclic
    from parsec_tpu.ops.cholesky import cholesky_ptg
    from parsec_tpu.ops.lu import lu_ptg

    N, nb = 64, 16
    rng = np.random.default_rng(42)
    M = rng.standard_normal((N, N))
    SPD = M @ M.T + N * np.eye(N)
    LUIN = rng.standard_normal((N, N)) + N * np.eye(N)

    # solo references, computed in THIS process on plain single-rank
    # contexts (bit-identical is the contract: per-tile ops see the
    # same operand bits in the same per-task order either way)
    refs = {}
    for key, data, build in (("chol", SPD, cholesky_ptg),
                             ("lu", LUIN, lu_ptg)):
        sctx = Context(nb_cores=2)
        try:
            A = TiledMatrix(N, N, nb, nb, name=f"solo_{key}")
            A.from_array(data)
            stp = build(use_tpu=False).taskpool(NT=A.mt, A=A)
            sctx.add_taskpool(stp)
            assert stp.wait(timeout=120), f"solo {key} hung"
            refs[key] = A.to_array()
        finally:
            sctx.fini()

    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    try:
        A = TwoDimBlockCyclic(N, N, nb, nb, p=ce.nranks, q=1,
                              myrank=ce.rank, name="mpA")
        A.from_array(SPD)
        B = TwoDimBlockCyclic(N, N, nb, nb, p=1, q=ce.nranks,
                              myrank=ce.rank, name="mpB")
        B.from_array(LUIN)
        dc = LocalCollection("mpD", shape=(1,), nodes=ce.nranks,
                             myrank=ce.rank, init=lambda k: np.zeros(2))
        dc.rank_of = lambda *key: dc.data_key(*key) % ce.nranks
        nchain = 10
        ptg = PTG("mpchain")
        step = ptg.task_class("step", k="0 .. N-1")
        step.affinity("D(k)")
        step.flow("X", INOUT,
                  "<- (k == 0) ? D(0) : X step(k-1)",
                  "-> (k < N-1) ? X step(k+1) : D(k)")
        step.body(cpu=lambda X, k: X.__iadd__(1.0))

        pools = [
            ("chol", cholesky_ptg(use_tpu=False).taskpool(NT=A.mt, A=A)),
            ("lu", lu_ptg(use_tpu=False).taskpool(NT=B.mt, A=B)),
            ("chain", ptg.taskpool(N=nchain, D=dc)),
        ]
        ce.barrier()
        for _, tp in pools:
            ctx.add_taskpool(tp)
        bad = 0
        for key, tp in pools:
            assert tp.wait(timeout=240), f"{key} hung concurrently"
            # clean termdet per pool
            nbt = getattr(tp.tdm, "_nb_tasks", None)
            assert not isinstance(nbt, int) or nbt <= 0, (key, nbt)
            assert not tp.failed
        ce.barrier()  # all ranks quiesced before reading tiles
        for key, coll, ref in (("chol", A, refs["chol"]),
                               ("lu", B, refs["lu"])):
            for (i, j) in coll.local_tiles():
                got = np.asarray(
                    coll.data_of(i, j).newest_copy().payload)
                want = ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
                if not np.array_equal(got, want):
                    bad += 1
        assert bad == 0, f"rank {ce.rank}: {bad} tiles differ from solo"
        if dc.rank_of(nchain - 1) == ce.rank:
            final = dc.data_of(nchain - 1).newest_copy().payload
            np.testing.assert_array_equal(final, np.full(2, float(nchain)))
        return {"tiles_checked": len(list(A.local_tiles()))
                + len(list(B.local_tiles()))}
    finally:
        ctx.fini()


def scenario_barrier_close(ce):
    """Regression: barrier releases queued just before close() must be
    flushed. Late ranks enter the barrier while rank 0 is already past
    it and about to close — without flush-on-close they hang/fail."""
    if ce.rank >= ce.nranks // 2:
        time.sleep(1.0)  # stagger: late ranks arrive after early ones
    ce.barrier()
    # early ranks fall straight through to close() in main()
    return {}


def scenario_send_then_close(ce):
    """The close handshake's stronger guarantee: an AM sent IMMEDIATELY
    before close() must still reach a peer that isn't even reading yet.
    Rank 0 fires one AM at every peer and closes in the same breath; the
    peers sleep first, then must observe the payload — close() may not
    return until every queued frame is irrevocably deliverable (peer FIN
    received), so nothing rides on scheduling luck."""
    got = []
    ce.register_am(TAG_USER_BASE, lambda src, p: got.append((src, p)))
    ce.barrier()
    if ce.rank == 0:
        for dst in range(1, ce.nranks):
            ce.send_am(TAG_USER_BASE, dst, {"fin_race": dst})
        return {"got": 0}  # falls straight through to close() in main()
    time.sleep(1.5)  # close() on rank 0 long since initiated
    deadline = time.time() + 30
    while not got:
        time.sleep(0.005)
        assert time.time() < deadline, "last-breath AM never arrived"
    assert got[0][1] == {"fin_race": ce.rank}
    return {"got": len(got)}




def scenario_perf(ce):
    """RTT + bandwidth through the real AM path (reference
    tests/apps/pingpong rtt.jdf / bandwidth.jdf): rank 0 <-> rank 1,
    small-payload round trips, then large one-way transfers with a
    final ack.  Rank 1 echoes from inside the AM callback (comm-thread
    turnaround, no scheduler in the loop)."""
    TRIPS, REPS = 200, 30
    got = []
    if ce.rank == 1:
        def echo(src, p):
            if "seq" in p:
                ce.send_am(TAG_USER_BASE, 0, {"ack": p["seq"]})
            elif p.get("last"):
                ce.send_am(TAG_USER_BASE, 0, {"done": True})
        ce.register_am(TAG_USER_BASE, echo)
    else:
        ce.register_am(TAG_USER_BASE, lambda src, p: got.append(p))
    ce.barrier()
    out = {}
    if ce.rank == 0:
        t0 = time.perf_counter()
        for i in range(TRIPS):
            ce.send_am(TAG_USER_BASE, 1, {"seq": i})
            while len(got) <= i:
                time.sleep(0)
        rtt_us = (time.perf_counter() - t0) / TRIPS * 1e6
        got.clear()
        arr = np.arange(1 << 20, dtype=np.float64)  # 8 MiB
        t0 = time.perf_counter()
        for i in range(REPS):
            ce.send_am(TAG_USER_BASE, 1, {"blk": arr, "last": i == REPS - 1})
        while not got:
            time.sleep(0)
        dt = time.perf_counter() - t0
        out = {"rtt_us": round(rtt_us, 1),
               "mb_s": round(REPS * arr.nbytes / dt / 1e6, 1)}
    ce.barrier()
    return out




def scenario_bcast(ce):
    """1 -> R broadcast of an above-short-limit payload over the real
    wire, topology from PARSEC_MCA_runtime_bcast_topo: pins that
    aggregation + forward sets behave identically over TCP (async GETs,
    forwarding from inside GET callbacks) as over the test fabric."""
    got = []
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    dc = LocalCollection("D", shape=(65536,), nodes=ce.nranks, myrank=ce.rank,
                         init=lambda k: np.full(65536, 7.0))
    dc.rank_of = lambda *key: dc.data_key(*key) % ce.nranks

    ptg = PTG("bcast")
    src = ptg.task_class("src")
    src.affinity("D(0)")
    src.flow("X", INOUT, "<- D(0)", "-> X sink(0 .. NR-1)")
    src.body(cpu=lambda X: X.__iadd__(35.0))
    sink = ptg.task_class("sink", r="0 .. NR-1")
    sink.affinity("D(r)")
    sink.flow("X", IN, "<- X src()")
    sink.body(cpu=lambda X, r: got.append(float(X[0])))
    tp = ptg.taskpool(NR=ce.nranks, D=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=90)
    assert got == [42.0], got
    ce.barrier()
    st = ce.remote_dep.stats
    out = {"sent": int(st["activations_sent"]),
           "recv": int(st["activations_recv"]),
           "fwd": int(st["forwarded"]),
           "get_adv": int(st["get_advertised"]),
           "mem_left": len(ce._mem)}
    ctx.fini()
    return out


def scenario_jobtrace(ce):
    """Job-level trace propagation over the REAL wire (PR-15 acceptance
    leg): one serve job on a 2-rank loopback-TCP mesh — a small
    (eager) and a big (rendezvous) cross-rank chain plus one allreduce
    task per rank — traced per rank, dumped to TRACE_DIR.  The parent
    test merges the dumps and pins that every span of the job's tasks
    on BOTH ranks carries the job's trace id (compute, eager AND rdv
    wire events, collective spans), that the merged timeline has
    exactly one track group for the job, and that critpath --job
    attributes queue/admit/run/drain."""
    from parsec_tpu.profiling.binary import RankTraceSet
    from parsec_tpu.profiling.merge import clock_handshake
    from parsec_tpu.serve import RuntimeService
    from parsec_tpu.utils import mca_param

    mca_param.set_param("runtime", "comm_eager_limit", 2048)
    out_dir = os.environ["TRACE_DIR"]
    traces = RankTraceSet(nranks=1, base_rank=ce.rank).install()
    ctx = Context(nb_cores=2, rank=ce.rank, nranks=ce.nranks, comm=ce)
    traces.set_clock_offset(ce.rank, clock_handshake(ce))

    n = 8
    ds = LocalCollection("DS", shape=(n,), nodes=ce.nranks,
                         myrank=ce.rank, init=lambda k: np.zeros(8))
    ds.rank_of = lambda *key: ds.data_key(*key) % ce.nranks
    db = LocalCollection("DB", shape=(n,), nodes=ce.nranks,
                         myrank=ce.rank, init=lambda k: np.zeros(4096))
    db.rank_of = lambda *key: db.data_key(*key) % ce.nranks
    dr = LocalCollection("DR", shape=(ce.nranks,), nodes=ce.nranks,
                         myrank=ce.rank,
                         init=lambda k: np.full(16, float(ce.rank + 1)))
    dr.rank_of = lambda *key: dr.data_key(*key)

    ptg = PTG("jt_tcp_job")
    small = ptg.task_class("jt_small", k="0 .. N-1")
    small.affinity("DS(k)")
    small.flow("X", INOUT, "<- (k == 0) ? DS(0) : X jt_small(k-1)",
               "-> (k < N-1) ? X jt_small(k+1) : DS(k)")
    small.body(cpu=lambda X, k: X.__iadd__(1.0))
    big = ptg.task_class("jt_big", k="0 .. N-1")
    big.affinity("DB(k)")
    big.flow("X", INOUT, "<- (k == 0) ? DB(0) : X jt_big(k-1)",
             "-> (k < N-1) ? X jt_big(k+1) : DB(k)")
    big.body(cpu=lambda X, k: X.__iadd__(1.0))
    ar = ptg.task_class("jt_ar", r=f"0 .. {ce.nranks - 1}")
    ar.affinity("DR(r)")
    ar.flow("X", INOUT, "<- DR(r)", "-> DR(r)")

    def ar_body(X, r):
        h = ctx.comm.coll.allreduce(np.ascontiguousarray(X),
                                    cid=("jt_tcp", 1))
        assert h.wait(timeout=60), h.state()
        X[...] = np.asarray(h.result()).reshape(X.shape)

    ar.body(cpu=ar_body)

    svc = RuntimeService(context=ctx, fairness=False)
    ce.barrier()
    h = svc.submit("acme", ptg.taskpool(N=n, DS=ds, DB=db, DR=dr))
    assert h.wait(timeout=120), h.status()
    trace_id = h.trace_id
    ce.barrier()
    assert svc.close(timeout=60)
    ctx.fini()
    paths = traces.dump(out_dir)
    traces.uninstall()
    traces.close()
    return {"trace_id": f"{trace_id:016x}", "paths": paths}


def scenario_coll(ce):
    """Runtime collectives over the REAL wire (TCP + inproc parity pin):
    ring allreduce of a chunk-training payload, reduce-scatter,
    allgather, binomial bcast — numerics self-checked per rank, endpoint
    bookkeeping (staging registrations reclaimed, nothing in flight)
    pinned like the inproc suite."""
    N = ce.nranks
    _ = ce.coll  # register the ctl op on every rank before any advert
    ce.barrier()

    # ring allreduce, payload >> rdv chunk so segments pipeline
    n = 65536  # 512 KiB f64
    h = ce.coll_allreduce(np.arange(n, dtype=np.float64) * (ce.rank + 1))
    assert h.wait(timeout=90)
    ref = np.arange(n, dtype=np.float64) * sum(range(1, N + 1))
    np.testing.assert_array_equal(h.result(), ref)

    # reduce-scatter: this rank's partition of the sum
    h = ce.coll_reduce_scatter(np.arange(64, dtype=np.float64)
                               + 100.0 * ce.rank)
    assert h.wait(timeout=90)
    full = sum(np.arange(64, dtype=np.float64) + 100.0 * r
               for r in range(N))
    b0, b1 = ce.rank * 64 // N, (ce.rank + 1) * 64 // N
    np.testing.assert_array_equal(h.result(), full[b0:b1])

    # allgather
    h = ce.coll_allgather(np.full(8, float(ce.rank)))
    assert h.wait(timeout=90)
    np.testing.assert_array_equal(
        h.result(), np.repeat(np.arange(float(N)), 8))

    # binomial bcast from rank 1
    arr = (np.arange(256.0) if ce.rank == 1 else np.zeros(256))
    h = ce.coll_bcast(arr, root=1)
    assert h.wait(timeout=90)
    np.testing.assert_array_equal(h.result(), np.arange(256.0))

    ce.barrier()
    s = ce.coll.summary()
    assert s["ops_done"] == s["ops_started"] == 4, s
    assert s["segments_inflight"] == 0, s
    assert not ce._mem, list(ce._mem)  # every staging reg reclaimed
    return {"ops": s["ops_done"], "bytes": s["bytes"],
            "segs": s["segments"]}


if __name__ == "__main__":
    main()
