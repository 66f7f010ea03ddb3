"""Asynchronous staging pipeline — dynamic-runtime and unit coverage.

The round-19 pipeline (``parsec_tpu/device/staging.py``) defers dirty
write-backs to a background committer and batches host<->device
transfers.  These tests pin the correctness contracts the design rests
on:

* the :class:`WritebackCommitter` unit surface against a stub device —
  per-tile dedup, the drain watermark, the flush barrier, and the STICKY
  failure discipline (a dead committer fails enqueuers and ``flush``,
  it never hangs them);
* ``detach()`` after async write-backs commits every dirty tile home
  EXACTLY once — tiles the committer already landed are version-guard
  dropped by the sync flush (no double commit, no stale rollback);
* custom ``stage_in``/``stage_out`` hooks compose with the deferred
  path: a packed device copy is never flushed home (the home-layout
  host copy already carries the version) and numerics stay exact;
* a committer death surfaces as a POOL failure through the epilog
  enqueue, not a hang;
* LRU eviction writes its victims home itself, a batch at a time, and
  data survives budget pressure;
* the dynamic runtime's tile digests are bit-identical with the
  pipeline on vs off.
"""

import collections
import threading
import time

import numpy as np
import pytest

from parsec_tpu import Context, DEV_TPU
from parsec_tpu.data import data_create
from parsec_tpu.device.staging import HostWriter, WritebackCommitter
from parsec_tpu.dsl import DTDTaskpool, INOUT
from parsec_tpu.utils import mca_param


def _set(framework, name, value):
    mca_param.params.set(framework, name, value)


def _unset(framework, name):
    mca_param.params.unset(framework, name)


@pytest.fixture
def ctx():
    c = Context(nb_cores=2)
    yield c
    c.fini()


def tpu_dev(ctx):
    for d in ctx.devices:
        if d.mca_name == "tpu":
            return d
    pytest.skip("no jax device available")


# ---------------------------------------------------------------------------
# WritebackCommitter unit surface (stub device)
# ---------------------------------------------------------------------------

class _StubDev(HostWriter):
    """The exact surface the committer drives — the device's write-back
    halves (``device/staging.py``) — with the D2H counted and failable
    and the commits recorded."""

    def __init__(self):
        super().__init__(1, collections.Counter(), name="stub")
        self.commits = []  # (data_id, version) in commit order
        self.fail: BaseException = None
        self.d2h_calls = 0

    def d2h_batch(self, payloads):
        self.d2h_calls += 1
        if self.fail is not None:
            raise self.fail
        return [np.asarray(p) for p in payloads]

    def commit(self, data, version, host):
        landed = super().commit(data, version, host)
        if landed:
            self.commits.append((data.data_id, version))
        return landed


def _dirty(key, value, version=2, n=16):
    """A Data whose device copy (index 1) is ``version`` ahead of the
    host copy — exactly what an epilog leaves behind."""
    d = data_create(key, payload=np.zeros(n))
    c = d.attach_copy(1, np.full(n, float(value)))
    c.version = version
    return d


def test_committer_dedup_commits_newest_version_once():
    dev = _StubDev()
    com = WritebackCommitter(dev)
    try:
        d = _dirty("a", 1.0, version=2)
        t1 = com.enqueue(d)
        # re-dirty while pending: the dedup keeps ONE entry, the
        # snapshot at drain time sees the newest version
        with d.lock:
            d.get_copy(1).payload = np.full(16, 9.0)
            d.get_copy(1).version = 3
        t2 = com.enqueue(d)
        assert t2 > t1
        assert com.stats["enqueued"] == 2
        assert com.pending() == 1
        com.flush()
        assert dev.commits == [(d.data_id, 3)]
        np.testing.assert_allclose(np.asarray(d.get_copy(0).payload), 9.0)
        assert com.stats["committed"] == 1
    finally:
        com.close(flush=False)


def test_committer_watermark_defers_below_window():
    """Small dirty bytes sit pending (no eager D2H flood); the flush
    barrier drains them."""
    dev = _StubDev()
    com = WritebackCommitter(dev)  # default window: 32 MB
    try:
        ds = [_dirty(i, float(i)) for i in range(4)]
        for d in ds:
            com.enqueue(d)
        time.sleep(0.4)  # > the committer's poll interval
        assert com.pending() == 4  # watermark not crossed: nothing drained
        assert dev.d2h_calls == 0
        com.flush()
        assert com.pending() == 0
        assert com.stats["committed"] == 4
        assert com.drained() == 4
    finally:
        com.close(flush=False)


def test_committer_flush_brings_one_tile_home():
    """One tile, far below the watermark: ``flush`` (the barrier every
    caller in the runtime takes) returns with it home and nothing held."""
    dev = _StubDev()
    com = WritebackCommitter(dev)
    try:
        d = _dirty("v", 5.0)
        com.enqueue(d)
        assert com.holding([d.data_id]) == {d.data_id}
        com.flush(timeout=30.0)
        assert com.holding([d.data_id]) == set()
        np.testing.assert_allclose(np.asarray(d.get_copy(0).payload), 5.0)
    finally:
        com.close(flush=False)


def test_committer_stale_entry_dropped_not_committed():
    """Host already at (or past) the device version: the version guard
    drops the entry — a deferred commit can never roll a tile back."""
    dev = _StubDev()
    com = WritebackCommitter(dev)
    try:
        d = _dirty("s", 7.0, version=2)
        d.get_copy(0).version = 5  # host moved past the device copy
        com.enqueue(d)
        com.flush()
        assert dev.commits == []
        assert com.stats["dropped_stale"] == 1
    finally:
        com.close(flush=False)


def test_committer_failure_is_sticky_and_loud():
    """A D2H failure kills the committer; the stored error re-raises on
    the next enqueue AND on flush — callers fail, they don't hang."""
    dev = _StubDev()
    dev.fail = RuntimeError("injected D2H loss")
    com = WritebackCommitter(dev)
    try:
        com.enqueue(_dirty("f0", 1.0))
        com.kick()
        deadline = time.monotonic() + 30
        while com.error is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert com.error is not None
        assert not com.healthy
        with pytest.raises(RuntimeError, match="committer failed"):
            com.enqueue(_dirty("f1", 2.0))
        with pytest.raises(RuntimeError, match="committer failed"):
            com.flush()
    finally:
        com.close(flush=False)


# ---------------------------------------------------------------------------
# detach after async write-back: exactly once per dirty tile
# ---------------------------------------------------------------------------

def test_detach_after_async_writeback_commits_exactly_once():
    """Tiles the committer already landed mid-run must NOT be committed
    again by detach's sync flush: bytes_out counts every dirty tile's
    payload exactly once, and values are the final versions."""
    NT, N = 4, 512  # 512x512 f64 = 2 MB/tile > the 1 MB watermark
    _set("runtime", "wb_window_mb", 1)
    ctx = Context(nb_cores=2)
    try:
        dev = tpu_dev(ctx)
        tiles = [data_create(i, payload=np.zeros((N, N))) for i in range(NT)]
        tp = DTDTaskpool(ctx)
        for i, t in enumerate(tiles):
            tp.insert_task({DEV_TPU: lambda x, i=i: x + float(i + 1)},
                           (t, INOUT))
        assert tp.wait(timeout=120)
        com = dev._wb_committer()
        assert com is not None, "stage_depth default engages the committer"
        # an inserted task's tile goes home at its flush: two of the four
        # through the committer now, the others at detach
        assert com.stats["committed"] == 0 and dev.stats["bytes_out"] == 0
        tp.data_flush(tiles[0])
        tp.data_flush(tiles[1])
        assert com.stats["committed"] == 2
    finally:
        ctx.fini()  # detach: flush barrier + sync batch for the rest
        _unset("runtime", "wb_window_mb")
    tile_bytes = N * N * 8
    # exactly once per dirty tile: async commits + detach commits == NT
    assert dev.stats["bytes_out"] == NT * tile_bytes
    for i, t in enumerate(tiles):
        hc = t.get_copy(0)
        np.testing.assert_allclose(np.asarray(hc.payload), float(i + 1))
        assert hc.version == t.newest_copy().version  # no stale rollback


# ---------------------------------------------------------------------------
# custom stage hooks x deferred write-back
# ---------------------------------------------------------------------------

def test_custom_stage_hooks_compose_with_deferred_writeback():
    """A packed custom-staged device copy must never be flushed home by
    the committer (it is NOT home layout); the pre-flushed host copy
    carries the version and the scatter hook's output lands exact."""
    import jax.numpy as jnp

    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import INOUT as P_INOUT, PTG

    _set("runtime", "wb_window_mb", 1)
    ctx = Context(nb_cores=2)
    try:
        dev = tpu_dev(ctx)
        N, NT = 512, 3  # full tiles are 2 MB: enqueues cross the watermark
        base = np.arange(float(N * N)).reshape(N, N)
        dc = LocalCollection("A", shape=(N, N), init=lambda k: base.copy())

        def pack(data, device):
            return jnp.asarray(
                np.asarray(data.newest_copy().payload)[:, ::2])

        def scatter(arr, data, device):
            full = jnp.asarray(np.asarray(data.get_copy(0).payload))
            return full.at[:, ::2].set(arr)

        ptg = PTG("stagewb")
        t = ptg.task_class("t", k=f"0 .. {NT - 1}")
        t.affinity("A(k)")
        t.flow("X", P_INOUT, "<- A(k)", "-> A(k)")
        t.stage("X", stage_in=pack, stage_out=scatter)
        t.body(tpu=lambda X, k: X * 10.0)
        tp = ptg.taskpool(A=dc)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
        com = dev._wb_committer()
        assert com is not None
        com.flush()
        # the epilog ran stage_out (scatter) BEFORE enqueueing, so the
        # deferred commits are home-layout — one per task output
        assert com.stats["committed"] == NT
        expect = base.copy()
        expect[:, ::2] *= 10.0
        from parsec_tpu.dsl.dtd import stage_to_cpu

        for k in range(NT):
            np.testing.assert_allclose(stage_to_cpu(dc.data_of(k)), expect)
    finally:
        ctx.fini()
        _unset("runtime", "wb_window_mb")


def test_packed_read_copy_never_flushed_home(ctx):
    """A READ flow's pack hook leaves a PACKED device copy (staged_by
    marker set, no epilog to unpack it): the committer must drop it —
    flushing a packed representation home would corrupt the tile."""
    import jax.numpy as jnp

    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import IN, PTG

    dev = tpu_dev(ctx)
    com = dev._wb_committer()
    assert com is not None
    N = 8
    base = np.arange(float(N * N)).reshape(N, N)
    dc = LocalCollection("A", shape=(N, N), init=lambda k: base.copy())

    def pack(data, device):
        return jnp.asarray(np.asarray(data.newest_copy().payload)[:, ::2])

    ptg = PTG("pkro")
    t = ptg.task_class("t", k="0 .. 0")
    t.affinity("A(0)")
    t.flow("X", IN, "<- A(0)")
    t.stage("X", stage_in=pack)
    t.body(tpu=lambda X, k: ())
    tp = ptg.taskpool(A=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    d = dc.data_of(0)
    assert d.get_copy(dev.data_index) is not None  # packed copy resident
    before = np.asarray(d.get_copy(0).payload).copy()
    com.enqueue(d)
    com.flush()
    assert com.stats["dropped_stale"] >= 1
    np.testing.assert_array_equal(np.asarray(d.get_copy(0).payload), before)


def test_committer_death_fails_pool_not_hang():
    """An injected D2H failure inside the committer thread surfaces at
    the flush that needed it (the barrier re-raises the sticky error)
    — the run terminates, it does not wedge."""
    _set("runtime", "wb_window_mb", 1)
    ctx = Context(nb_cores=2)
    try:
        dev = tpu_dev(ctx)
        com = dev._wb_committer()
        assert com is not None
        orig = dev._wb.d2h_batch
        state = {"boomed": False}

        def boom(payloads):
            if not state["boomed"]:
                state["boomed"] = True
                raise RuntimeError("injected D2H failure")
            return orig(payloads)

        dev._wb.d2h_batch = boom
        d = data_create("chain", payload=np.zeros((512, 512)))  # 2 MB
        tp = DTDTaskpool(ctx)
        for _ in range(10):
            tp.insert_task({DEV_TPU: lambda x: x + 1.0}, (d, INOUT))
        # nothing of an inserted task's is the committer's before its
        # flush: the pool drains, and the failure surfaces, loudly, at
        # the flush barrier
        assert tp.wait(timeout=120)
        with pytest.raises(RuntimeError, match="committer"):
            tp.data_flush(d)
        assert state["boomed"]
        assert not com.healthy
        # teardown below must not trip over the dead committer: drop it
        # (detach then takes the synchronous batch path) and restore D2H
        dev._wb.d2h_batch = orig
        com.close(flush=False)
        dev._committer = None
    finally:
        ctx.fini()
        _unset("runtime", "wb_window_mb")


# ---------------------------------------------------------------------------
# eviction writes its victims home itself, in batches
# ---------------------------------------------------------------------------

def test_eviction_writes_its_victims_home_a_batch_at_a_time(ctx):
    """Under budget pressure the victims whose only valid copy is on the
    device go home as ONE batch on the thread that needs the room (PR
    30: not a committer round trip a tile under the residency lock) —
    every tile's data survives eviction, and what the committer still
    holds of them drops as stale."""
    dev = tpu_dev(ctx)
    com = dev._wb_committer()
    assert com is not None, "stage_depth default engages the committer"
    dev.hbm_budget = 4 * 1024 * 8  # room for ~4 tiles of 1024 f64
    tiles = [data_create(i, payload=np.full((1024,), float(i)))
             for i in range(12)]
    tp = DTDTaskpool(ctx)
    for t in tiles:
        tp.insert_task({DEV_TPU: lambda x: x + 0.0}, (t, INOUT))
    assert tp.wait(timeout=120)
    s = dev.stats
    assert s["evictions"] > 0 and s["evict_batches"] > 0
    assert s["evict_dirty"] > 0
    assert s["evict_bytes_home"] == s["evict_dirty"] * 1024 * 8
    assert s["evict_clean"] + s["evict_dirty"] == s["evictions"]
    dev.flush()
    assert com.healthy
    from parsec_tpu.dsl.dtd import stage_to_cpu

    for i, t in enumerate(tiles):
        np.testing.assert_allclose(stage_to_cpu(t), float(i))


# ---------------------------------------------------------------------------
# pipeline on/off: bit-identical dynamic-runtime digests
# ---------------------------------------------------------------------------

def _dynamic_dpotrf_digest(depth):
    from parsec_tpu.analysis.schedules import tile_digest
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    rng = np.random.default_rng(17)
    n, nb = 96, 24
    M = rng.standard_normal((n, n))
    S = M @ M.T + n * np.eye(n)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    _set("runtime", "stage_depth", depth)
    ctx = Context(nb_cores=2)
    try:
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120)
    finally:
        ctx.fini()
        _unset("runtime", "stage_depth")
    return tile_digest(A), S, A


def test_dynamic_digests_identical_pipeline_on_vs_off():
    """The acceptance bar: same schedule class, stage_depth 1 (all
    transfers synchronous) vs 2 (prefetch + deferred write-back) land
    bit-identical tiles.  Wave batching off: wave composition is
    schedule-dependent and vmapped kernels need not match singles."""
    _set("device", "tpu_wave_batch", 0)
    try:
        off, S, _ = _dynamic_dpotrf_digest(1)
        on, _, A = _dynamic_dpotrf_digest(2)
    finally:
        _unset("device", "tpu_wave_batch")
    assert on == off, "staging pipeline changed numerics"
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# donation vs deferred write-back (found on the chip, PR 21)
# ---------------------------------------------------------------------------

def test_donating_chain_is_not_handed_to_the_committer(ctx):
    """The segmented factorizations thread ONE whole-matrix INOUT flow
    through donating programs: each step's successor consumes the very
    buffer the epilog just committed.  Eagerly writing every
    intermediate version home stalled the chain behind a 4 GiB get per
    step on the chip (or read a deleted array and failed the pool), so
    a donating program's outputs stay dirty-resident instead."""
    from parsec_tpu.ops.segmented_chol import SegmentedCholesky

    dev = tpu_dev(ctx)
    assert dev.stage_depth > 1  # the pipeline is on by default
    n, nb = 128, 32
    rng = np.random.default_rng(5)
    M = rng.standard_normal((n, n)).astype(np.float32)
    spd = M @ M.T + n * np.eye(n, dtype=np.float32)
    sc = SegmentedCholesky(ctx, n, nb, strip=64, tail=0)
    assert sc.nt_tasks == 4  # a real chain, not one fused task
    L = sc(spd)
    np.testing.assert_allclose(L @ L.T, spd, rtol=2e-4, atol=2e-3)
    com = dev._committer
    assert com is None or com.stats["enqueued"] == 0
    assert dev.stats["bytes_out"] == 0  # no intermediate version went home


def test_committer_drops_a_version_consumed_by_a_donating_task(ctx):
    """A snapshot whose device array a donating task consumed before the
    get is a superseded version: dropped as stale, never a dead
    committer."""
    import jax.numpy as jnp

    dev = tpu_dev(ctx)
    live, gone = jnp.ones(8), jnp.ones(8)
    gone.delete()
    hosts = dev._wb.d2h_batch([live, gone])
    assert hosts[1] is None
    np.testing.assert_allclose(hosts[0], 1.0)

    com = WritebackCommitter(dev._wb)
    try:
        d = data_create("donated", payload=np.zeros(8))
        c = d.attach_copy(dev.data_index, gone)
        c.version = 2
        com.enqueue(d)
        com.flush()
        assert com.healthy
        assert com.stats["dropped_stale"] == 1 and com.stats["committed"] == 0
    finally:
        com.close()


# ---------------------------------------------------------------------------
# a copy home is started before anybody waits for it (PR 29)
# ---------------------------------------------------------------------------

class _Tile:
    """A device payload double that records, in one shared log, when its
    copy home is started and when it is collected; its host value is
    read-only, as a ``jax.Array``'s is."""

    def __init__(self, log, name, value, n=16, nbytes=None):
        self.log, self.name = log, name
        self._host = np.full(n, float(value))
        self._host.flags.writeable = False
        #: (what the committer's watermark counts: a tile may claim more)
        self.nbytes = self._host.nbytes if nbytes is None else nbytes

    def copy_to_host_async(self):
        self.log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("collect", self.name))
        return self._host


def _device_dirty(log, key, value, version=2, nbytes=None):
    d = data_create(key, payload=np.zeros(16))
    c = d.attach_copy(1, _Tile(log, key, value, nbytes=nbytes))
    c.version = version
    return d


class _GatedWriter(HostWriter):
    """A writer whose drain waits at a gate: what the hand-over did is
    read before the committer thread adds to the log."""

    def __init__(self):
        super().__init__(1, collections.Counter(), name="gated")
        self.gate = threading.Event()

    def writeback_batch(self, *args, **kw):
        assert self.gate.wait(timeout=30)
        return super().writeback_batch(*args, **kw)


def test_every_copy_of_a_drain_is_started_before_the_first_is_collected():
    log = []
    w = HostWriter(1, collections.Counter())
    datas = [_device_dirty(log, k, i + 1.0) for i, k in enumerate("abcde")]
    assert w.writeback_batch(datas) == (5, 5)
    assert log[:5] == [("start", k) for k in "abcde"]
    # ONE wait: the last started is the first collected, the rest follow
    assert log[5:] == [("collect", k) for k in "eabcd"]
    for i, d in enumerate(datas):
        host = d.get_copy(0)
        assert host.version == 2 and host.payload.flags.writeable
        np.testing.assert_allclose(host.payload, i + 1.0)
        host.payload += 1.0  # a CPU body may write it: it is no view
        np.testing.assert_allclose(np.asarray(d.get_copy(1).payload), i + 1.0)
    assert w.stats["wb_started_early"] == w.stats["wb_early_hits"] == 0
    # the synchronous write-back of one tile: the same collect and landing
    log.clear()
    lone = _device_dirty(log, "z", 7.0)
    w.writeback(lone)
    assert log == [("collect", "z")]
    assert lone.get_copy(0).payload.flags.writeable


@pytest.mark.parametrize("last", [True, False], ids=["last", "unknown"])
def test_a_last_version_starts_its_copy_at_hand_over_and_no_other(last):
    """What the task carries decides (``_tpu_home``, through
    ``TpuDevice._send_home``): a version known to be the tile's last is
    started as it is handed over; of one that may be superseded nothing
    is started before its drain — and both are collected the same way."""
    log = []
    w = _GatedWriter()
    com = WritebackCommitter(w)
    try:
        datas = [_device_dirty(log, k, 3.0) for k in "ab"]
        com.enqueue_all(datas, last=last)
        handed = list(log)  # (the gate is shut: the drain added nothing)
        w.gate.set()
        com.flush()
        if last:
            assert handed == [("start", "a"), ("start", "b")]
        else:
            assert handed == []
        assert log[len(handed):] == [("start", "a"), ("start", "b"),
                                     ("collect", "b"), ("collect", "a")]
        assert w.stats["wb_started_early"] == w.stats["wb_early_hits"] \
            == (2 if last else 0)
        assert com.stats["committed"] == 2
        for d in datas:
            np.testing.assert_allclose(d.get_copy(0).payload, 3.0)
    finally:
        w.gate.set()
        com.close(flush=False)


def test_a_watermark_drain_of_versions_that_may_be_superseded_keeps_its_pace():
    """Nobody waits for these tiles and a later task may rewrite them:
    the drain starts nothing ahead (a round trip a tile, as it was),
    because on this path the committer's rate is what bounds the bytes
    that go home.  A kick, a flush or a last version lifts that."""
    log = []
    w = _GatedWriter()
    _set("runtime", "wb_window_mb", 1)
    try:
        com = WritebackCommitter(w)
    finally:
        _unset("runtime", "wb_window_mb")
    try:
        datas = [_device_dirty(log, k, 4.0, nbytes=1 << 20) for k in "ab"]
        com.enqueue_all(datas)  # 2 MiB pending: over the watermark
        deadline = time.monotonic() + 30
        while com.pending_bytes() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert com.pending_bytes() == 0 and com.pending() == 2  # grabbed
        w.gate.set()
        com.flush()
        assert log == [("collect", "b"), ("collect", "a")]
        assert com.stats["committed"] == 2
        # ... and a tile below the watermark, behind a kick, is started
        # before it is collected
        log.clear()
        w.gate.clear()
        com.enqueue(_device_dirty(log, "c", 5.0))
        com.kick()
        w.gate.set()
        com.flush()
        assert log == [("start", "c"), ("collect", "c")]
    finally:
        w.gate.set()
        com.close(flush=False)


def test_a_tile_rebound_after_hand_over_lands_its_newest_version_once():
    """The early copy is simply not the one collected: the drain's
    snapshot takes the version that stands, and ``wb_early_hits`` says
    the start was for another."""
    log = []
    w = _GatedWriter()
    com = WritebackCommitter(w)
    try:
        d = _device_dirty(log, "v2", 2.0, version=2)
        com.enqueue_all([d], last=True)
        with d.lock:  # a later task rebinds the device copy
            c = d.get_copy(1)
            c.payload, c.version = _Tile(log, "v3", 3.0), 3
        w.gate.set()
        com.flush()
        assert log == [("start", "v2"), ("start", "v3"), ("collect", "v3")]
        assert w.stats["wb_started_early"] == 1
        assert w.stats["wb_early_hits"] == 0
        assert com.stats["committed"] == 1 and w.stats["bytes_out"] == 128
        host = d.get_copy(0)
        assert host.version == 3
        np.testing.assert_allclose(host.payload, 3.0)
        # handed over again as a last version: the newest start stands
        with d.lock:
            c.payload, c.version = _Tile(log, "v4", 4.0), 4
        w.gate.clear()
        com.enqueue_all([d], last=False)
        com.enqueue_all([d], last=True)
        w.gate.set()
        com.flush()
        assert w.stats["wb_started_early"] == 2
        assert w.stats["wb_early_hits"] == 1
        assert d.get_copy(0).version == 4
    finally:
        w.gate.set()
        com.close(flush=False)


def test_a_version_consumed_after_its_copy_was_started_is_dropped(ctx):
    """A donating task took the buffer between the start and the
    collect: that version is gone, its consumer's output supersedes it —
    dropped as stale, never a dead committer."""
    import jax.numpy as jnp

    dev = tpu_dev(ctx)
    w = _GatedWriter()
    w.index = dev.data_index
    com = WritebackCommitter(w)
    try:
        taken, kept = jnp.ones(8) * 2.0, jnp.ones(8) * 5.0
        datas = []
        for key, arr in (("taken", taken), ("kept", kept)):
            d = data_create(key, payload=np.zeros(8))
            d.attach_copy(dev.data_index, arr).version = 2
            datas.append(d)
        com.enqueue_all(datas, last=True)
        assert w.stats["wb_started_early"] == 2
        taken.delete()
        w.gate.set()
        com.flush()
        assert com.healthy
        assert com.stats["dropped_stale"] == 1 and com.stats["committed"] == 1
        assert datas[0].get_copy(0).version == 0
        np.testing.assert_allclose(datas[1].get_copy(0).payload, 5.0)
        assert datas[1].get_copy(0).payload.flags.writeable
        # ... and one consumed BEFORE the hand-over starts nothing
        gone = data_create("gone", payload=np.zeros(8))
        gone.attach_copy(dev.data_index, taken).version = 2
        com.enqueue_all([gone], last=True)
        com.flush()
        assert w.stats["wb_started_early"] == 2 and com.healthy
    finally:
        w.gate.set()
        com.close(flush=False)


@pytest.mark.parametrize("path", ["pump", "context"])
def test_the_task_says_where_the_copy_starts(path):
    """The pump's tasks carry ``_tpu_home`` (the DAG's last versions):
    every tile that goes home was started at hand-over and collected at
    that version.  The ``Context`` path's PTG tasks say the same of the
    same DAG, from their own output dependencies (a version no successor
    overwrites: ``PTGTaskpool._home_rule``): potrf's and trsm's tiles
    start at hand-over, syrk's and gemm's stay on the device, no task
    leaves the question open, and the lower matrix goes home once."""
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    n, nb = 96, 24
    rng = np.random.default_rng(11)
    M = rng.standard_normal((n, n))
    S = M @ M.T + n * np.eye(n)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float64).from_array(S)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    lower = A.mt * (A.mt + 1) // 2
    if path == "pump":
        from parsec_tpu.dsl.native_exec import NativeExecutor

        ex = NativeExecutor(tp, native_device=True)
        dev = ex.device
        assert ex.run() == 20
        ex.close()
    else:
        c = Context(nb_cores=2)
        try:
            dev = tpu_dev(c)
            c.add_taskpool(tp)
            assert c.wait(timeout=120)
        finally:
            c.fini()
    assert dev.stats["wb_started_early"] == lower
    assert dev.stats["wb_early_hits"] == lower
    assert dev.stats["commits_home_unknown"] == 0
    assert dev.stats["bytes_out"] == lower * nb * nb * 8
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, S, rtol=1e-10, atol=1e-10)
    for (i, j) in ((0, 0), (A.mt - 1, 0), (A.mt - 1, A.mt - 1)):
        assert A.data_of(i, j).get_copy(0).payload.flags.writeable


# ---------------------------------------------------------------------------
# a hand-over of last versions does not wait for the committer's capacity
# (PR 35)
# ---------------------------------------------------------------------------

def _held_committer(log):
    """A committer inside ``_commit`` (its writer's gate is shut) with
    200 MiB queued behind it: over its capacity of 128 for whatever
    comes next."""
    w = _GatedWriter()
    com = WritebackCommitter(w)
    com.enqueue(_device_dirty(log, "first", 1.0, nbytes=64 << 20))
    deadline = time.monotonic() + 30
    while com.pending_bytes() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert com.pending_bytes() == 0 and com.pending() == 1  # grabbed
    # (a hand-over never waits for room it fills itself)
    com.enqueue_all([_device_dirty(log, k, 2.0, nbytes=100 << 20)
                     for k in ("q1", "q2")])
    assert com.pending_bytes() == 200 << 20
    assert com.stats["capacity_waits"] == 0
    return w, com


@pytest.mark.parametrize("how", ["last", "unknown", "last_not_started"])
def test_only_a_started_last_version_skips_the_capacity_wait(how):
    """The pump's hand-over (``last``: the copies are started, each tile
    is queued once, an entry pins the tile's own accounted buffer)
    returns while the committer is held; a version that may be
    superseded (the ``Context`` path: the committer's rate bounds what
    goes home) and one whose copy could not be started still wait."""
    log = []
    w, com = _held_committer(log)
    try:
        if how == "last_not_started":
            # a host array: nothing to start (``_start_copy``)
            d = _dirty("plain", 3.0)
            d.get_copy(1).payload = np.full(16 << 20, 3.0, np.float32)
        else:
            d = _device_dirty(log, "new", 3.0, nbytes=100 << 20)
        handed = threading.Thread(
            target=com.enqueue_all, args=([d],),
            kwargs={"last": how != "unknown"}, daemon=True)
        handed.start()
        if how == "last":
            handed.join(timeout=30)
            assert not handed.is_alive()
            assert com.stats["capacity_waits"] == 0
            assert ("start", "new") in log
            assert com.pending_bytes() == 300 << 20
        else:
            deadline = time.monotonic() + 30
            while not com.stats["capacity_waits"] \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            assert com.stats["capacity_waits"] > 0 and handed.is_alive()
            assert com.pending_bytes() == 200 << 20
        w.gate.set()
        handed.join(timeout=30)
        assert not handed.is_alive()
        com.flush()
        assert com.stats["committed"] == 4 and d.get_copy(0).version == 2
    finally:
        w.gate.set()
        com.close(flush=False)
