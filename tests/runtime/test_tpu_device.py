"""TPU device-module tests (reference tests/runtime/cuda shape:
nvlink/stress/stage/get_best_device_check).

Under pytest these run against the JAX CPU backend — same machinery
(stage-in, jit dispatch, LRU residency, manager state machine), virtual
device. On real TPU hardware nothing changes but the platform.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from parsec_tpu import Context, DEV_CPU, DEV_TPU
from parsec_tpu.dsl import DTDTaskpool, IN, INOUT
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.data import data_create, Coherency


@pytest.fixture
def ctx():
    c = Context(nb_cores=2)
    yield c
    c.fini()


def tpu_dev(ctx):
    for d in ctx.devices:
        if d.device_type == DEV_TPU:
            return d
    pytest.skip("no jax device available")


def test_tpu_device_attached(ctx):
    dev = tpu_dev(ctx)
    assert dev.hbm_budget > 0


def test_device_body_executes_on_device(ctx):
    dev = tpu_dev(ctx)
    d = data_create("x", payload=np.full((8, 8), 3.0))
    tp = DTDTaskpool(ctx)

    def body(x):
        return x * 2.0  # functional device body

    tp.insert_task({DEV_TPU: body}, (d, INOUT))
    assert tp.wait(timeout=60)
    # result lives on the device, host copy is stale until staged
    c = d.get_copy(dev.data_index)
    assert c is not None and c.version == d.newest_copy().version
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), 6.0)
    assert dev.stats["executed_tasks"] == 1
    assert dev.stats["bytes_in"] == 8 * 8 * 8


def test_device_chain_stays_resident(ctx):
    """RAW chain on device: only ONE stage-in should happen — intermediate
    versions never bounce through the host."""
    dev = tpu_dev(ctx)
    d = data_create("x", payload=np.ones((16,)))
    tp = DTDTaskpool(ctx)

    def inc(x):
        return x + 1.0

    for _ in range(10):
        tp.insert_task({DEV_TPU: inc}, (d, INOUT))
    assert tp.wait(timeout=60)
    assert dev.stats["bytes_in"] == 16 * 8  # exactly one H2D
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), 11.0)


def test_mixed_cpu_tpu_chores(ctx):
    """A task class with CPU and TPU incarnations: the ETA policy may pick
    either; results must be identical."""
    d = data_create("x", payload=np.arange(8.0))
    tp = DTDTaskpool(ctx)

    def cpu_body(x):
        x *= 3.0

    def tpu_body(x):
        return x * 3.0

    tp.insert_task({DEV_CPU: cpu_body, DEV_TPU: tpu_body}, (d, INOUT))
    assert tp.wait(timeout=60)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), np.arange(8.0) * 3.0)


def test_gemm_on_device_matches_numpy(ctx):
    rng = np.random.default_rng(7)
    M = 64
    nb = 32
    Ad = rng.standard_normal((M, M))
    Bd = rng.standard_normal((M, M))
    A = TiledMatrix(M, M, nb, nb, name="A").from_array(Ad)
    B = TiledMatrix(M, M, nb, nb, name="B").from_array(Bd)
    C = TiledMatrix(M, M, nb, nb, name="C")
    tp = DTDTaskpool(ctx)

    def gemm(a, b, c):
        return c + jnp.dot(a, b)

    for i in range(A.mt):
        for j in range(B.nt):
            for k in range(A.nt):
                tp.insert_task(
                    {DEV_TPU: gemm},
                    (A.data_of(i, k), IN),
                    (B.data_of(k, j), IN),
                    (C.data_of(i, j), INOUT),
                    name="gemm",
                )
    assert tp.wait(timeout=120)
    # pull results home
    for key in C.tiles():
        from parsec_tpu.dsl.dtd import stage_to_cpu

        stage_to_cpu(C.data_of(*key))
    np.testing.assert_allclose(C.to_array(), Ad @ Bd, rtol=1e-10)


def test_lru_eviction_under_budget_pressure(ctx):
    dev = tpu_dev(ctx)
    dev.hbm_budget = 4 * 1024 * 8  # room for ~4 tiles of 1024 f64
    tiles = [data_create(i, payload=np.full((1024,), float(i))) for i in range(12)]
    tp = DTDTaskpool(ctx)

    def touch(x):
        return x + 0.0

    for t in tiles:
        tp.insert_task({DEV_TPU: touch}, (t, INOUT))
    assert tp.wait(timeout=60)
    assert dev.stats["evictions"] > 0
    assert dev.hbm_used <= dev.hbm_budget * 2  # bounded residency
    # every tile's data survived eviction (write-back preserved versions)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    for i, t in enumerate(tiles):
        np.testing.assert_allclose(stage_to_cpu(t), float(i))


def test_out_only_flow_skips_stage_in(ctx):
    """Write-only tiles must not pay an H2D transfer (regression)."""
    from parsec_tpu.dsl import OUT

    dev = tpu_dev(ctx)
    d = data_create("x", payload=np.full(64, -1.0))
    tp = DTDTaskpool(ctx)
    tp.insert_task({DEV_TPU: lambda x: x + 3.0}, (d, OUT))
    assert tp.wait(timeout=60)
    assert dev.stats["bytes_in"] == 0  # no stage-in for OUT-only
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), 3.0)  # zeros placeholder + 3


def test_restage_does_not_leak_hbm_accounting(ctx):
    """Alternating CPU/TPU writes re-stage the same tile repeatedly; the
    replaced device copy's bytes must be reclaimed (regression)."""
    dev = tpu_dev(ctx)
    d = data_create("x", payload=np.zeros(128))
    tp = DTDTaskpool(ctx)

    def cpu_w(x):
        x += 1.0

    def tpu_w(x):
        return x + 1.0

    for _ in range(6):
        tp.insert_task(cpu_w, (d, INOUT))
        tp.insert_task({DEV_TPU: tpu_w}, (d, INOUT))
    assert tp.wait(timeout=60)
    assert dev.hbm_used <= 2 * 128 * 8  # one tile resident, not six


def test_cpu_body_after_device_body_can_mutate(ctx):
    """D2H of a jax.Array is read-only; staged host copies must be made
    writable so CPU in-place bodies keep working (regression)."""
    d = data_create("x", payload=np.zeros(8))
    tp = DTDTaskpool(ctx)

    def cpu_add(x):
        x += 1.0

    def tpu_mul(x):
        return x * 2.0

    for _ in range(3):
        tp.insert_task(cpu_add, (d, INOUT))
        tp.insert_task({DEV_TPU: tpu_mul}, (d, INOUT))
    assert tp.wait(timeout=60)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), 14.0)


def test_detach_flushes_dirty_tiles():
    ctx = Context(nb_cores=2)
    try:
        dev = tpu_dev(ctx)
        d = data_create("x", payload=np.zeros(4))
        tp = DTDTaskpool(ctx)
        tp.insert_task({DEV_TPU: lambda x: x + 5.0}, (d, INOUT))
        assert tp.wait(timeout=60)
    finally:
        ctx.fini()
    host = d.get_copy(0)
    assert host is not None
    np.testing.assert_allclose(np.asarray(host.payload), 5.0)
    assert host.version == d.newest_copy().version


def test_data_advise_prefetch_and_warmup(ctx):
    """Reference device.h data_advise: PREFETCH stages ahead of use (the
    task then sees zero stage-in bytes), WARMUP re-touches the LRU."""
    from parsec_tpu.device.device import ADVICE_PREFETCH, ADVICE_WARMUP

    dev = tpu_dev(ctx)
    d = data_create("adv", payload=np.full((16, 16), 2.0))
    dev.data_advise(d, ADVICE_PREFETCH)
    staged = dev.stats["bytes_in"]
    assert staged == 16 * 16 * 8  # prefetch did the H2D
    tp = DTDTaskpool(ctx)
    tp.insert_task({DEV_TPU: lambda x: x + 1.0}, (d, INOUT))
    assert tp.wait(timeout=60)
    assert dev.stats["bytes_in"] == staged  # no second transfer
    dev.data_advise(d, ADVICE_WARMUP)  # resident: must not raise


def test_data_advise_preferred_device(ctx):
    """PREFERRED_DEVICE pins selection even when the ETA would pick the
    other device."""
    from parsec_tpu.device.device import ADVICE_PREFERRED_DEVICE

    dev = tpu_dev(ctx)
    d = data_create("pref", payload=np.ones(4))
    dev.data_advise(d, ADVICE_PREFERRED_DEVICE)
    assert d.preferred_device == dev.index
    ran_on = []
    tp = DTDTaskpool(ctx)
    # both incarnations available: preference must force the TPU one
    tp.insert_task({DEV_CPU: lambda x: ran_on.append("cpu"),
                    DEV_TPU: lambda x: (ran_on.append("tpu"), x + 0.0)[1]},
                   (d, INOUT))
    assert tp.wait(timeout=60)
    assert ran_on == ["tpu"]


@pytest.mark.parametrize("eager", [1, 0])
def test_eager_mixed_chore_ordering(eager):
    """Round-1 VERDICT item 10a: under ``tpu_eager_complete`` a CPU
    successor that MUTATES a tile is released at device-task dispatch —
    while the device computation that reads the tile may still be in
    flight.  Correct ordering falls out of the functional device design:
    the device body read immutable input arrays (XLA semantics — there
    is no tile memory a host write could race), the CPU successor's
    stage_to_cpu blocks on the producing computation's OUTPUT array, and
    its mutation lands in a fresh host buffer that becomes the next
    version.  Reference polls real completion events instead
    (device_gpu.c:1879-1999) because its bodies mutate device memory in
    place.  Pinned under BOTH completion modes."""
    from parsec_tpu.utils import mca_param

    mca_param.set_param("device", "tpu_eager_complete", eager)
    try:
        ctx = Context(nb_cores=2)
        try:
            dev = tpu_dev(ctx)
            d = data_create("t", payload=np.full((64, 64), 1.0, np.float32))
            tp = DTDTaskpool(ctx)

            def heavy_device(x):
                # a long dependency chain keeps the computation in flight
                # while the CPU successor is (eagerly) released
                for _ in range(60):
                    x = x @ jnp.eye(64, dtype=x.dtype) + 1.0
                return x  # 1 + 60 = 61 everywhere

            def cpu_mutate(x):
                x += 1.0  # in-place on the staged host copy -> 62

            def device_scale(x):
                return x * 2.0  # -> 124

            tp.insert_task({DEV_TPU: heavy_device}, (d, INOUT))
            tp.insert_task({DEV_CPU: cpu_mutate}, (d, INOUT))
            tp.insert_task({DEV_TPU: device_scale}, (d, INOUT))
            assert tp.wait(timeout=120)
            from parsec_tpu.dsl.dtd import stage_to_cpu

            np.testing.assert_allclose(stage_to_cpu(d), 124.0)
            assert dev.stats["executed_tasks"] == 2
        finally:
            ctx.fini()
    finally:
        mca_param.params.unset("device", "tpu_eager_complete")


def test_wave_batching_dispatch():
    """Round-5 (VERDICT #6): a ready wave of same-class device tasks is
    submitted as one jitted multi-body program (power-of-2 chunks) — the
    device stats record wave submissions and the numerics are identical
    to per-task dispatch."""
    import jax.numpy as jnp

    from parsec_tpu import Context
    from parsec_tpu.data import data_create
    from parsec_tpu.dsl import DTDTaskpool, IN, INOUT

    import time

    rng = np.random.default_rng(21)
    K = 24
    tiles = [data_create(("t", i), payload=rng.standard_normal((64, 64)))
             for i in range(K)]
    outs = [data_create(("o", i), payload=np.zeros((64, 64)))
            for i in range(K)]
    ctx = Context(nb_cores=2)
    try:
        dev = next(d for d in ctx.devices if d.mca_name == "tpu")
        # hold the manager role: every worker submitting enqueues to
        # _pending and leaves with ASYNC — the deterministic backlog a
        # busy manager sees in production
        with dev._lock:
            dev._manager_active = True
        tp = DTDTaskpool(ctx)

        def body(x, o):
            return jnp.matmul(x, x) + 1.0

        body._jit_key = ("wave_test_body",)
        for i in range(K):
            tp.insert_task({dev.device_type: body},
                           (tiles[i], IN), (outs[i], INOUT))
        # release the role; wait() starts the workers — one becomes
        # manager while the other feeds the backlog (its first wave
        # compile gives the pile-up every busy manager sees)
        with dev._lock:
            dev._manager_active = False
        assert tp.wait(timeout=60)
        for i in range(K):
            got = np.asarray(outs[i].newest_copy().payload)
            want = (np.asarray(tiles[i].newest_copy().payload) @
                    np.asarray(tiles[i].newest_copy().payload)) + 1.0
            np.testing.assert_allclose(got, want, rtol=1e-5)
        # waves really formed (>= 2 tasks per program at least once)
        assert dev.stats.get("wave_tasks", 0) >= 2, dict(dev.stats)
        assert dev.stats.get("wave_submits", 0) >= 1
        assert (dev.stats["wave_tasks"]
                > dev.stats["wave_submits"]), dict(dev.stats)
    finally:
        ctx.fini()


def test_wave_batching_disabled_by_param():
    """tpu_wave_batch=0 restores strict per-task dispatch."""
    import jax.numpy as jnp

    from parsec_tpu import Context
    from parsec_tpu.data import data_create
    from parsec_tpu.dsl import DTDTaskpool, IN, INOUT
    from parsec_tpu.utils import mca_param

    mca_param.set_param("device", "tpu_wave_batch", 0)
    try:
        rng = np.random.default_rng(22)
        tiles = [data_create(("t2", i), payload=rng.standard_normal((32, 32)))
                 for i in range(8)]
        ctx = Context(nb_cores=1)
        try:
            dev = next(d for d in ctx.devices if d.mca_name == "tpu")
            tp = DTDTaskpool(ctx)

            def body(x):
                return x + 1.0

            body._jit_key = ("wave_test_body2",)
            for t in tiles:
                tp.insert_task({dev.device_type: body}, (t, INOUT))
            assert tp.wait(timeout=60)
            assert dev.stats.get("wave_tasks", 0) == 0, dict(dev.stats)
        finally:
            ctx.fini()
    finally:
        mca_param.params.unset("device", "tpu_wave_batch")


def test_wave_staging_is_per_chunk(ctx):
    """_submit_wave stages each pow2 chunk's
    inputs immediately before THAT chunk's dispatch — never the whole
    wave up front — so peak HBM holds one chunk's inputs, not the
    wave's.  Observed through the chunk's staging walk + the native-path
    EXEC pins: a 6-task wave (chunks 4+2) must interleave stage(4) →
    dispatch(4) → stage(2) → dispatch(2)."""
    from parsec_tpu.core.task import Chore, TaskClass
    from parsec_tpu.dsl.native_exec import _NativeDeviceTask
    from parsec_tpu.profiling import pins
    from types import SimpleNamespace

    dev = tpu_dev(ctx)
    events = []

    orig_stage = dev._stage_chunk

    def recording_stage(grp, *rest):
        # the chunk's ONE residency pass: one event a task it stages
        events.extend(("stage", id(task)) for task in grp)
        return orig_stage(grp, *rest)

    dev._stage_chunk = recording_stage

    def on_exec(es, task):
        events.append(("dispatch", task.prof.get("wave")))

    pins.subscribe(pins.EXEC_BEGIN, on_exec)

    pool = SimpleNamespace(failed=False, task_done=lambda t=None: None,
                           context=None)
    tclass = TaskClass("wavetest")
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = lambda x: x + 1.0
    tasks = []
    for i in range(6):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [("data", data_create(
            ("wv", i), payload=np.ones((8, 8), np.float32)), INOUT)]
        t.on_complete = lambda task: None
        tasks.append(t)
    try:
        dev._submit_wave(tasks, None)
    finally:
        dev._stage_chunk = orig_stage
        pins.unsubscribe(pins.EXEC_BEGIN, on_exec)

    kinds = [k for (k, _v) in events]
    # 6 = 4 + 2: four stages, four dispatches, two stages, two dispatches
    assert kinds == (["stage"] * 4 + ["dispatch"] * 4
                     + ["stage"] * 2 + ["dispatch"] * 2), kinds
    assert [v for (k, v) in events if k == "dispatch"] == [4, 4, 4, 4, 2, 2]


# ---------------------------------------------------------------------------
# the wave commit: one residency pass and one epilog a chunk
# ---------------------------------------------------------------------------

def _commit_tasks(layout, n, home):
    """``n`` independent device tasks of one class over 8x8 tiles, by
    ``layout``; returns ``(stages, tiles)``: the task lists in the order
    they become ready, and every tile by a name of its own.

    * ``potrf1`` — dpotrf-like: ``x`` (IN), ``o`` (INOUT): one output;
    * ``qr2`` — geqrt-like: ``a`` (INOUT) and a scratch ``q`` nobody has
      written (INOUT, ``<- NEW``): two outputs, one of them born here;
    * ``qr3`` — the ``qr2`` stage, then tsmqr-like consumers: ``c1``,
      ``c2`` (INOUT), the born scratch ``q`` (IN, its last user) and an
      unborn scratch ``w`` (INOUT): three outputs.

    ``home``: the pump's knowledge of which outputs go home (every
    non-scratch one here); False leaves ``_tpu_home`` None, as the
    Context path does."""
    from parsec_tpu.core.lifecycle import AccessMode
    from parsec_tpu.core.task import Chore, TaskClass
    from parsec_tpu.device import scratch
    from parsec_tpu.dsl.native_exec import _NativeDeviceTask
    from types import SimpleNamespace

    pool = SimpleNamespace(failed=False, task_done=lambda t=None: None,
                           context=None)
    tiles = {}

    def tile(name, i, fill):
        d = tiles[(name, i)] = data_create(
            (layout, name, i), payload=np.full((8, 8), fill, np.float32))
        return d

    def new(name, i, users):
        d = tiles[(name, i)] = scratch.new((layout, name, i), (8, 8),
                                           np.float32)
        scratch.add_users(d, users)
        return d

    def stage(cls, body, rows):
        tclass = TaskClass(cls)
        chore = Chore(DEV_TPU, hook=lambda es, t: None)
        chore.body_fn = body
        out = []
        for i, flows in enumerate(rows):
            t = _NativeDeviceTask(pool, tclass, (i,), 0)
            t.selected_chore = chore
            t.body_args = [("data", d, mode) for (d, mode) in flows]
            t.body_args.append(("value", i, AccessMode.VALUE))
            if home:
                t._tpu_home = tuple(
                    pos for pos, (d, mode) in enumerate(flows)
                    if d.scratch is None and mode & AccessMode.OUT)
            t.on_complete = lambda task: None
            out.append(t)
        return out

    if layout in ("potrf1", "hooked", "donate"):
        def body(x, o, i):
            return o + 2.0 * x[:o.shape[0]]

        if layout == "hooked":
            # the body sees the top half of ``o``, packed; the home
            # layout lives in the host copy
            body._stage_in = {1: lambda data, device: jnp.asarray(
                np.asarray(data.newest_copy().payload)[:4])}
            body._stage_out = {1: lambda arr, data, device: jnp.asarray(
                np.asarray(data.get_copy(0).payload)).at[:4].set(arr)}
        elif layout == "donate":
            body._donate_args = (1,)
        return [stage(layout, body,
                      [[(tile("x", i, i + 1.0), IN),
                        (tile("o", i, 0.5), INOUT)] for i in range(n)])], \
            tiles
    first = stage("qr2", lambda a, q, i: (a * 2.0, q + a + 1.0),
                  [[(tile("a", i, i + 1.0), INOUT),
                    (new("q", i, 1 if layout == "qr2" else 2), INOUT)]
                   for i in range(n)])
    if layout == "qr2":
        return [first], tiles
    second = stage("qr3", lambda c1, c2, q, w, i: (c1 + q, c2 - q, w + q),
                   [[(tile("c1", i, 1.0), INOUT), (tile("c2", i, 2.0), INOUT),
                     (tiles[("q", i)], IN), (new("w", i, 1), INOUT)]
                    for i in range(n)])
    return [first, second], tiles


def _run_commit_tasks(layout, n, complete, waves):
    """The tasks of ``_commit_tasks`` through a fresh device, as waves or
    one by one; returns everything the two ways must agree on."""
    from parsec_tpu.core import scheduling

    c = Context(nb_cores=1)
    try:
        dev = tpu_dev(c)
        last = {}
        commit = dev._commit_output

        def recording_commit(data, arr, *rest):
            last[data.data_id] = arr
            return commit(data, arr, *rest)

        dev._commit_output = recording_commit
        stages, tiles = _commit_tasks(layout, n, home=not complete)
        for tasks in stages:
            for t in tasks:
                t.selected_device = dev   # where a completion is counted
            if waves:
                dev._submit_units(dev._units_of(tasks), None, complete)
            else:
                for t in tasks:
                    dev._submit_one(t, None, complete)
            assert all(t._tpu_completed for t in tasks)
            if not complete:
                scheduling.retire_native(tasks, dev)
        com = dev._committer
        dev.flush()   # the committer runs beside us: settle it first
        names = {d.data_id: k for k, d in tiles.items()}
        seen = {"resident": {}, "tile": {}}
        for k, d in tiles.items():
            mine = d.get_copy(dev.data_index)
            if mine is not None and mine.payload is not None:
                # the device copy IS what the program returned for it
                assert mine.payload is last.get(d.data_id, mine.payload)
                seen["resident"][k] = np.asarray(mine.payload).tolist()
            seen["tile"][k] = (
                d.owner_device == dev.data_index,
                sorted((i == dev.data_index, cp.coherency, cp.version,
                        cp.payload is None) for i, cp in d.copies.items()))
        seen.update(
            hbm_used=dev.hbm_used,
            clean=sorted(names[i] for i in dev._res.clean),
            dirty=sorted(names[i] for i in dev._res.dirty),
            # (a device that sent nothing home never armed a committer)
            enqueued=com.stats["enqueued"] if com else 0,
            committed=com.stats["committed"] if com else 0,
            host={k: None if d.scratch is not None
                  else np.asarray(d.get_copy(0).payload).tolist()
                  for k, d in tiles.items()},
            stats={k: dev.stats.get(k, 0) for k in (
                "custom_stage_in", "custom_stage_out",
                "executed_tasks", "bytes_in", "bytes_out",
                "scratch_tiles_born", "scratch_tiles_freed",
                "scratch_bytes_in", "scratch_bytes_out", "evictions",
                "wave_fallbacks", "submit_retries")})
        counts = {k: dev.stats.get(k, 0) for k in (
            "wave_commits", "wave_submits", "wave_tasks", "task_commits")}
        return seen, counts
    finally:
        c.fini()


def _what_the_per_task_epilog_left(layout, n):
    """What the per-task ``_epilog`` left behind these tasks (PR 27's
    tree, recorded before it went): the reference the one commit is held
    to, by tile name — its value, who owns it, ``(on the device,
    coherency, version, payload is None)`` of each copy, whether it is
    resident and in which LRU, what the host holds."""
    S, O, I = Coherency.SHARED, Coherency.OWNED, Coherency.INVALID
    full = lambda v: np.full((8, 8), v, np.float32).tolist()
    # written here, sent home once: the device owns version 1, the host
    # holds it too
    home = (True, [(False, S, 1, False), (True, O, 1, False)])
    read = (False, [(False, S, 0, False), (True, S, 0, False)])
    kept = (True, [(False, I, 0, False), (True, O, 1, False)])  # not sent
    gone = (False, [])   # scratch: born, used, dropped with its last user
    rows = {   # name: (state, value on the device, value on the host)
        "potrf1": {"x": (read, lambda i: i + 1.0, lambda i: i + 1.0),
                   "o": (home, lambda i: 2.5 + 2 * i, lambda i: 2.5 + 2 * i)},
        "donate": {"x": (read, lambda i: i + 1.0, lambda i: i + 1.0),
                   "o": (kept, lambda i: 2.5 + 2 * i, lambda i: 0.5)},
        "qr2": {"a": (home, lambda i: 2 * i + 2.0, lambda i: 2 * i + 2.0),
                "q": (gone, None, None)},
    }
    rows["qr3"] = dict(rows["qr2"], w=(gone, None, None),
                       c1=(home, lambda i: i + 3.0, lambda i: i + 3.0),
                       c2=(home, lambda i: -1.0 * i, lambda i: -1.0 * i))
    if layout == "hooked":   # the top half went through the body
        half = lambda i: np.vstack([np.full((4, 8), 2.5 + 2 * i, np.float32),
                                    np.full((4, 8), 0.5, np.float32)]).tolist()
        table = {"x": (read, lambda i: full(i + 1.0), lambda i: full(i + 1.0)),
                 "o": (home, half, half)}
    else:
        table = {name: (st, dv and (lambda i, f=dv: full(f(i))),
                        hv and (lambda i, f=hv: full(f(i))))
                 for name, (st, dv, hv) in rows[layout].items()}
    names = [(name, i) for name in table for i in range(n)]
    resident = [k for k in names if table[k[0]][1] is not None]
    written = [k for k in resident if table[k[0]][0] is not read]
    sent = [k for k in written if table[k[0]][0] is home]
    nscratch = sum(st is gone for st, _d, _h in table.values()) * n
    nstages = 2 if layout == "qr3" else 1
    # a packed stage-in moves the top half of ``o``
    staged_in = 256 * len(resident) - 128 * n * (layout == "hooked")
    return dict(
        resident={k: table[k[0]][1](k[1]) for k in resident},
        tile={k: table[k[0]][0] for k in names},
        hbm_used=256 * len(resident),
        clean=sorted(k for k in resident if k not in written),
        dirty=sorted(written),
        enqueued=len(sent), committed=len(sent),
        host={k: table[k[0]][2] and table[k[0]][2](k[1]) for k in names},
        stats=dict(custom_stage_in=n * (layout == "hooked"),
                   custom_stage_out=n * (layout == "hooked"),
                   executed_tasks=nstages * n, bytes_in=staged_in,
                   bytes_out=256 * len(sent), scratch_tiles_born=nscratch,
                   scratch_tiles_freed=nscratch, scratch_bytes_in=0,
                   scratch_bytes_out=0, evictions=0, wave_fallbacks=0,
                   submit_retries=0))


@pytest.mark.parametrize("complete", [False, True], ids=["pump", "context"])
@pytest.mark.parametrize("n", [2, 4, 64, 7])
@pytest.mark.parametrize("layout", ["potrf1", "qr2", "qr3"])
def test_wave_commit_leaves_what_the_per_task_epilog_leaves(layout, n,
                                                            complete):
    """A chunk of n against n chunks of one, through the one commit
    (``_commit_chunk``): what differs between them — the outputs' sizes
    asked once a chunk, one hold of the residency lock, one call to the
    committer — is what this guards.  After the same tasks every tile's
    version, owner, coherency and payload, the residency accounting and
    the LRUs' membership, the scratch counters, the bytes that went home
    and the committer's dedup read the same, and both read what the
    per-task ``_epilog`` left while there was one; the two commit
    counters add up to the tasks executed."""
    nstages = 2 if layout == "qr3" else 1
    wave, counts = _run_commit_tasks(layout, n, complete, waves=True)
    alone, alone_counts = _run_commit_tasks(layout, n, complete, waves=False)
    assert wave == alone == _what_the_per_task_epilog_left(layout, n)
    ntasks = nstages * n
    # a chunk a power of two: 7 = 4 + 2 + 1
    chunks = nstages * bin(n).count("1")
    assert counts == {"wave_commits": chunks, "wave_submits": chunks,
                      "wave_tasks": ntasks, "task_commits": 0}
    assert alone_counts == {"wave_commits": 0, "wave_submits": 0,
                            "wave_tasks": 0, "task_commits": ntasks}


@pytest.mark.parametrize("complete", [False, True], ids=["pump", "context"])
@pytest.mark.parametrize("layout", ["hooked", "donate"])
def test_a_task_that_goes_out_alone_leaves_what_the_per_task_epilog_left(
        layout, complete):
    """The two layouts only the lone path has, through the one walk and
    the one commit: a ``stage_in`` / ``stage_out`` hook pair (the body
    computes on the packed half, the home layout is committed and sent
    home, both hooks counted) and a donating body (its outputs stay
    dirty on the device: nothing is enqueued to the committer, which is
    never armed)."""
    alone, counts = _run_commit_tasks(layout, 3, complete, waves=True)
    assert alone == _what_the_per_task_epilog_left(layout, 3)
    assert counts == {"wave_commits": 0, "wave_submits": 0,
                      "wave_tasks": 0, "task_commits": 3}


@pytest.mark.parametrize("where", ["staging", "trace"])
def test_a_wave_that_fails_before_dispatch_touches_no_task(ctx, where):
    """An error in a chunk's staging or in its trace raises before ANY of
    its tasks has an effect; the per-task fallback then runs each task
    exactly once: one fallback counted, no version bumped twice."""
    dev = tpu_dev(ctx)
    (tasks,), tiles = _commit_tasks("potrf1", 4, home=True)
    state = {"failed": 0}

    def once(fn):
        def failing(*a, **k):
            if not state["failed"]:
                state["failed"] = 1
                raise RuntimeError(f"injected {where} failure")
            return fn(*a, **k)
        return failing

    if where == "staging":
        dev._h2d.batch = once(dev._h2d.batch)
    else:
        chore = tasks[0].selected_chore
        chore.body_fn = once(chore.body_fn)
    dev._submit_units(dev._units_of(tasks), None, False)
    assert state["failed"] == 1
    assert dev.stats["wave_fallbacks"] == 1
    assert dev.stats["wave_commits"] == 0 and dev.stats["task_commits"] == 4
    assert dev.stats["submit_retries"] == 0
    for (name, i), d in tiles.items():
        c = d.get_copy(dev.data_index)
        assert c.version == (1 if name == "o" else 0), (name, i)
        if name == "o":
            np.testing.assert_allclose(np.asarray(c.payload),
                                       0.5 + 2.0 * (i + 1.0))


def test_an_error_inside_the_wave_commit_fails_the_pool_loudly():
    """Once a chunk's commit has begun nothing is retried: a committer
    that died (its error is sticky) fails the pool at the chunk's
    hand-off, ``wait()`` returns False, and no task of the chunk runs a
    second time."""
    c = Context(nb_cores=2)
    try:
        dev = tpu_dev(c)
        with dev._lock:   # hold the manager role: the tasks pile up
            dev._manager_active = True
        com = dev._wb_committer()
        com.error = RuntimeError("injected committer death")
        tp = DTDTaskpool(c)
        tiles = [data_create(("dead", i), payload=np.zeros((8, 8), np.float32))
                 for i in range(4)]

        def body(x):
            return x + 1.0

        body._jit_key = ("dead_committer_body",)
        for t in tiles:
            task = tp.insert_task({dev.device_type: body}, (t, INOUT))
            # every version the committer's, as of a task whose builder
            # said nothing (an inserted task's goes home at its flush)
            task._tpu_home = None
        with dev._lock:
            dev._manager_active = False
        assert tp.wait(timeout=60) is False
        assert tp.failed
        assert dev.stats["submit_retries"] == 0
        assert dev.stats["wave_fallbacks"] == 0
        # committed once or (behind the failure) not at all: never twice
        assert all(t.newest_copy().version <= 1 for t in tiles)
        com.error = None   # teardown flushes through it
    finally:
        c.fini()


def test_the_tools_hear_every_event_of_a_wave_commit(ctx):
    """With hb-check listening a wave of n tasks with k outputs fires n
    ``DEVICE_EPILOG_BEGIN``, n*k ``DATA_VERSION_BUMP`` and one
    ``HB_WB_ENQUEUE`` a ticket, each task's bumps after its own epilog
    site, in an order the checker accepts."""
    from parsec_tpu.analysis.hb import HBRecorder
    from parsec_tpu.profiling import pins

    dev = tpu_dev(ctx)
    (tasks,), tiles = _commit_tasks("qr2", 4, home=True)
    heard = []
    sites = {pins.DEVICE_EPILOG_BEGIN: "epilog",
             pins.DATA_VERSION_BUMP: "bump", pins.HB_WB_ENQUEUE: "ticket"}
    subs = [(site, lambda es, payload, kind=kind: heard.append(
        (kind, payload))) for site, kind in sites.items()]
    for site, cb in subs:
        pins.subscribe(site, cb)
    try:
        with HBRecorder() as rec:
            dev._submit_units(dev._units_of(tasks), None, False)
            dev.flush()
        assert rec.analyze() == []
    finally:
        for site, cb in subs:
            pins.unsubscribe(site, cb)
    kinds = [k for k, _p in heard]
    assert kinds.count("epilog") == 4 and kinds.count("bump") == 8
    tickets = [p["ticket"] for k, p in heard if k == "ticket"]
    # the home outputs (a), never the scratch ones (q): a ticket each
    assert len(tickets) == len(set(tickets)) == 4
    assert {p["data"] for k, p in heard if k == "ticket"} \
        == {tiles[("a", i)].data_id for i in range(4)}
    # epilog(t), its two bumps, epilog(t+1), ...; the tickets after
    assert kinds == ["epilog", "bump", "bump"] * 4 + ["ticket"] * 4
    assert [p for k, p in heard if k == "epilog"] == tasks


def test_the_lane_and_the_pump_share_residency_without_losing_a_tile(ctx):
    """The transfer lane's put runs OUTSIDE the residency lock, beside
    the pump's staging and commits of other (and of the same) tiles:
    more threads than this needs, a switch interval that interleaves
    them everywhere, a bounded time.  Whoever wins, every tile ends with
    one device copy at its newest version, one LRU entry and one
    accounted slot."""
    import sys
    import threading
    import time

    dev = tpu_dev(ctx)
    rounds, n = 12, 8
    stages = [_commit_tasks("potrf1", n, home=False)[0][0]
              for _ in range(rounds)]
    # round r+1 reads what round r wrote: the lane stages tiles that the
    # pump is committing
    tiles = {}
    for r, tasks in enumerate(stages):
        for i, t in enumerate(tasks):
            if r:
                t.body_args[0] = ("data", tiles[("o", r - 1, i)], IN)
            tiles[("x", r, i)] = t.body_args[0][1]
            tiles[("o", r, i)] = t.body_args[1][1]
    stop = threading.Event()
    errors = []

    def lane(k):
        r = 0
        while not stop.is_set():
            tasks = stages[(r + k) % rounds]
            r += 1
            try:
                dev.prestage_batch(tasks, r, dev.prestage_tiles(tasks)[0])
            except Exception as e:  # pragma: no cover - the failure
                errors.append(e)
                return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=lane, args=(k,)) for k in range(4)]
    try:
        for th in threads:
            th.start()
        deadline = time.monotonic() + 20
        for tasks in stages:
            dev._submit_units(dev._units_of(tasks), None, False)
            assert time.monotonic() < deadline
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=20)
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads)
    unique = {d.data_id: d for d in tiles.values()}
    resident = 0
    for d in unique.values():
        mine = d.get_copy(dev.data_index)
        if mine is None or mine.payload is None:
            continue
        resident += 1
        assert mine.version == d.newest_copy().version, d
        assert (d.data_id in dev._res.clean) != (d.data_id in dev._res.dirty)
    assert resident == len(dev._res.clean) + len(dev._res.dirty)
    assert set(dev._res.accounted()) \
        == set(dev._res.clean) | set(dev._res.dirty)
    assert dev.hbm_used == resident * 8 * 8 * 4
    for i in range(n):   # o(r) = 0.5 + 2 o(r-1), o(-1) = x = i + 1
        want = i + 1.0
        for _ in range(rounds):
            want = 0.5 + 2.0 * want
        got = tiles[("o", rounds - 1, i)].get_copy(dev.data_index).payload
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_body_fingerprint_memo_is_weak(ctx):
    """Cache-poisoning regression pin: the device's body-fingerprint
    memo must NOT key on id(body).  A body fingerprinted just before a
    _jit_cache local-key hit is never retained, so its id can be
    recycled by a later DIFFERENT-content body — an id-keyed memo then
    hands the new body the dead body's fingerprint and the executable
    cache serves the wrong program with plausible shapes (seen in the
    suite as bf16-class numerics in an f32 LU run).  Weak keys make the
    entry die with the body."""
    import gc

    dev = tpu_dev(ctx)

    def make(scale):
        def body(x, _s=scale):
            return x * _s
        return body

    b1 = make(1.0)
    fp1 = dev._content_fp(b1)
    assert dev._content_fp(b1) == fp1  # memo hit while alive
    assert len(dev._body_fp) >= 1
    n_before = len(dev._body_fp)
    del b1
    gc.collect()
    # the dead body's entry is GONE — nothing for a recycled id to hit
    assert len(dev._body_fp) == n_before - 1
    # and a different-content body never inherits a stale fingerprint,
    # wherever the allocator places it
    b2 = make(2.0)
    assert dev._content_fp(b2) != fp1


# ---------------------------------------------------------------------------
# value arguments: dropped, packed or positional (device/value_args.py)
# ---------------------------------------------------------------------------

def _value_tasks(body, rows):
    """Device tasks over ``rows`` of ``(tile array, out array, *values)``:
    flows ``x`` (IN) and ``o`` (INOUT), then the values, PTG layout."""
    from parsec_tpu.core.lifecycle import AccessMode
    from parsec_tpu.core.task import Chore, TaskClass
    from parsec_tpu.dsl.native_exec import _NativeDeviceTask
    from types import SimpleNamespace

    pool = SimpleNamespace(failed=False, task_done=lambda t=None: None,
                           context=None)
    tclass = TaskClass("valuetest")
    chore = Chore(DEV_TPU, hook=lambda es, t: None)
    chore.body_fn = body
    tasks = []
    for i, (x, o, *values) in enumerate(rows):
        t = _NativeDeviceTask(pool, tclass, (i,), 0)
        t.selected_chore = chore
        t.body_args = [("data", data_create(("vx", id(body), i), payload=x),
                        IN),
                       ("data", data_create(("vo", id(body), i), payload=o),
                        INOUT)]
        t.body_args += [("value", v, AccessMode.VALUE) for v in values]
        t.on_complete = lambda task: None
        tasks.append(t)
    return tasks


def _run_value_tasks(dev, tasks, alone):
    """Through the wave path (a power of two: ONE program) or one by
    one; returns the newest device payload of every ``o`` tile."""
    if alone:
        for t in tasks:
            dev._submit_one(t, None)
    else:
        dev._submit_wave(tasks, None)
    return [t.body_args[1][1].get_copy(dev.data_index).payload
            for t in tasks]


def _call_signatures(dev):
    """The argument signature of every call a program of ``dev`` got."""
    return [sig for (cf, _plan, _exe) in dev._jit_cache.values()
            for sig in cf._memo]


@pytest.mark.parametrize("exported", [False, True],
                         ids=["plain_lowering", "exported_path"])
@pytest.mark.parametrize("alone", [False, True], ids=["wave8", "alone"])
def test_unread_values_are_no_program_argument(monkeypatch, tmp_path,
                                               exported, alone):
    """A body that ignores its values gets a program whose argument list
    holds tiles only — whichever way ``compile_cache`` compiles it: the
    plain lowering prunes unused arguments by itself, the program
    compiled through its serialized form keeps every argument of
    ``Exported.call`` (one host-to-device copy a scalar a call)."""
    from parsec_tpu.utils import mca_param

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mca_param.set_param("runtime", "compile_cache_min_share_s",
                        0.0 if exported else 1e9)
    ctx = Context(nb_cores=1)
    try:
        dev = tpu_dev(ctx)

        def body(x, o, m, n, k):
            return o + x

        rows = [(np.full((8, 8), i, np.float32), np.ones((8, 8), np.float32),
                 i, i + 1, 7) for i in range(8)]
        outs = _run_value_tasks(dev, _value_tasks(body, rows), alone)
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(np.asarray(o), i + 1.0)
        sigs = _call_signatures(dev)
        assert len(sigs) == 1
        assert len(sigs[0]) == (2 if alone else 16)  # tiles only
        assert all(e[0] == "a" for e in sigs[0]), sigs[0]
        assert dev.stats["value_args_dropped"] == 24
        assert dev.stats["value_args_packed"] == 0
        assert dev.stats["value_args_positional"] == 0
        # the path asked for is the path taken
        assert bool(ctx.compile_cache.stats["bytes_written"]) == exported
    finally:
        ctx.fini()
        mca_param.params.unset("runtime", "compile_cache_min_share_s")


@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "float32"])
@pytest.mark.parametrize("alone", [False, True], ids=["wave8", "alone"])
def test_read_values_ride_one_vector_a_kind(ctx, dtype, alone):
    """A body that reads an ``int``, a ``float`` and a ``bool`` value:
    the program takes tiles plus ONE integer and ONE floating vector,
    and every output is bitwise (dtype included: the packed element is
    weak-typed like the Python scalar it replaces) what the positional
    call ``jax.jit(body)(x, o, i, f, b)`` gives."""
    import jax

    dev = tpu_dev(ctx)

    def body(x, o, i, f, b):
        return jnp.where(b, x * i + f, x - i)

    rng = np.random.default_rng(5)
    rows = [(jnp.asarray(rng.integers(-9, 9, (8, 8)), dtype=dtype),
             np.zeros((8, 8), np.float32), i - 3, 0.5 * i, i % 2 == 0)
            for i in range(8)]
    want = [jax.jit(body)(*row) for row in rows]
    outs = _run_value_tasks(dev, _value_tasks(body, rows), alone)
    for got, ref in zip(outs, want):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if dtype == "bfloat16":
        assert want[0].dtype == jnp.bfloat16  # a strong f32 would widen it
    n = 1 if alone else 8
    sigs = _call_signatures(dev)
    # (``o`` is written, never read: no argument of the program either)
    assert len(sigs) == 1 and len(sigs[0]) == n + 2
    # (int, bool) share the integer vector; the dtypes are those a
    # Python scalar traces to (the suite runs with x64 on)
    assert sigs[0][-2:] == (("a", (2 * n,), "int64", False),
                            ("a", (n,), "float64", False)), sigs[0]
    assert dev.stats["value_args_packed"] == 24
    assert dev.stats["value_args_dropped"] == 0
    assert dev.stats["value_args_positional"] == 0


@pytest.mark.parametrize("alone", [False, True], ids=["wave8", "alone"])
def test_other_value_types_stay_positional_and_are_counted(ctx, alone):
    """A numpy scalar and an array passed as values keep an argument of
    their own; the unread Python int beside them is still dropped."""
    dev = tpu_dev(ctx)

    def body(x, o, s, v, k):
        return x * s + v

    rows = [(np.ones((4, 4), np.float32), np.zeros((4, 4), np.float32),
             np.float32(i), np.arange(4, dtype=np.float32), i)
            for i in range(8)]
    outs = _run_value_tasks(dev, _value_tasks(body, rows), alone)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(
            np.asarray(o), i + np.tile(np.arange(4, dtype=np.float32), (4, 1)))
    assert dev.stats["value_args_positional"] == 16
    assert dev.stats["value_args_dropped"] == 8
    assert dev.stats["value_args_packed"] == 0
    sig, = _call_signatures(dev)
    assert len(sig) == (3 if alone else 24)   # x, s, v: ``o`` is unread


@pytest.mark.parametrize("alone", [False, True], ids=["wave8", "alone"])
def test_store_written_under_the_old_key_is_not_hit(monkeypatch, tmp_path,
                                                    alone):
    """The calling convention is part of the program's content key: an
    executable the parent stored (every value a positional scalar) is
    never loaded for the new argument list, and what the change stores
    IS hit by the next process."""
    from parsec_tpu import compile_cache as cc
    from parsec_tpu.utils import mca_param

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mca_param.set_param("runtime", "compile_cache_min_share_s", 0.0)

    def body(x, o, m, k):
        return o + x * k

    def rows():
        return [(np.full((8, 8), i, np.float32),
                 np.ones((8, 8), np.float32), i, 2) for i in range(8)]

    try:
        ctx = Context(nb_cores=1)
        try:
            dev = tpu_dev(ctx)
            n = 1 if alone else 8

            # the parent's program and call, into the same store
            def _wave(*flat):
                return tuple(body(*flat[4 * t:4 * t + 4]) for t in range(n))
            old_key = ("body", dev._content_fp(body)) if alone else \
                ("wave", dev._content_fp(body), 4, 1, 8)
            old = ctx.compile_cache.jit(body if alone else _wave, key=old_key)
            old(*[jnp.asarray(a) if isinstance(a, np.ndarray) else a
                  for row in rows()[:n] for a in row])
            stored = ctx.compile_cache.store.count()
            assert stored == 1
            ctx.compile_cache.clear_memory()
            before = dict(ctx.compile_cache.stats)
            outs = _run_value_tasks(dev, _value_tasks(body, rows()), alone)
            np.testing.assert_array_equal(np.asarray(outs[3]), 7.0)
            after = ctx.compile_cache.stats
            assert after["hits_disk"] == before.get("hits_disk", 0)
            assert after["misses"] == before["misses"] + 1
            assert ctx.compile_cache.store.count() == stored + 1
        finally:
            ctx.fini()
        ctx = Context(nb_cores=1)
        try:
            dev = tpu_dev(ctx)
            _run_value_tasks(dev, _value_tasks(body, rows()), alone)
            assert ctx.compile_cache.stats["hits_disk"] == 1
            assert ctx.compile_cache.stats["misses"] == 0
        finally:
            ctx.fini()
    finally:
        mca_param.params.unset("runtime", "compile_cache_min_share_s")


# ---------------------------------------------------------------------------
# tile arguments the program does not take; programs named by class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alone", [False, True], ids=["wave8", "alone"])
def test_an_unread_tile_is_no_program_argument(ctx, alone):
    dev = tpu_dev(ctx)

    def body(x, o, k):
        return o + 1.0          # reads neither x nor k

    rows = [(np.full((8, 8), i, np.float32), np.full((8, 8), i, np.float32),
             i) for i in range(8)]
    outs = _run_value_tasks(dev, _value_tasks(body, rows), alone)
    np.testing.assert_array_equal(np.asarray(outs[5]), 6.0)
    assert dev.stats["tile_args_dropped"] == 8
    assert dev.stats["value_args_dropped"] == 8
    per_task = {len(sig) // (1 if alone else 8)
                for sig in _call_signatures(dev)}
    assert per_task == {1}      # ``o`` alone


@pytest.mark.parametrize("exported", [False, True],
                         ids=["plain_lowering", "exported_path"])
def test_a_wave_program_carries_its_class_in_its_module_name(
        monkeypatch, tmp_path, exported):
    """``jit__wave_<class>`` on the device trace's ``XLA Modules`` line,
    also for a program compiled through its serialized form."""
    from parsec_tpu.utils import mca_param

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mca_param.set_param("runtime", "compile_cache_min_share_s",
                        0.0 if exported else 1e9)
    try:
        ctx = Context(nb_cores=1)
        try:
            dev = tpu_dev(ctx)

            def body(x, o, k):
                return o + x * k
            rows = [(np.ones((8, 8), np.float32),
                     np.ones((8, 8), np.float32), 2) for _ in range(4)]
            _run_value_tasks(dev, _value_tasks(body, rows), alone=False)
            (cf, _plan, _exe), = dev._jit_cache.values()
            exe, = cf._memo.values()
            assert exe.as_text().startswith("HloModule jit__wave_valuetest")
            assert ctx.compile_cache.store.count() == int(exported)
        finally:
            ctx.fini()
    finally:
        mca_param.params.unset("runtime", "compile_cache_min_share_s")
