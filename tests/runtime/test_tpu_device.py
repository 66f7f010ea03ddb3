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
    """ADVICE round-5 #1 pin: _submit_wave stages each pow2 chunk's
    inputs immediately before THAT chunk's dispatch — never the whole
    wave up front — so peak HBM holds one chunk's inputs, not the
    wave's.  Observed through the stage hook + the native-path EXEC
    pins: a 6-task wave (chunks 4+2) must interleave stage(4) →
    dispatch(4) → stage(2) → dispatch(2)."""
    from parsec_tpu.core.task import Chore, TaskClass
    from parsec_tpu.dsl.native_exec import _NativeDeviceTask
    from parsec_tpu.profiling import pins
    from types import SimpleNamespace

    dev = tpu_dev(ctx)
    events = []

    orig_stage = dev._stage_task_args

    def recording_stage(task, body, *tally):
        events.append(("stage", id(task)))
        return orig_stage(task, body, *tally)

    dev._stage_task_args = recording_stage

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
        dev._stage_task_args = orig_stage
        pins.unsubscribe(pins.EXEC_BEGIN, on_exec)

    kinds = [k for (k, _v) in events]
    # 6 = 4 + 2: four stages, four dispatches, two stages, two dispatches
    assert kinds == (["stage"] * 4 + ["dispatch"] * 4
                     + ["stage"] * 2 + ["dispatch"] * 2), kinds
    assert [v for (k, v) in events if k == "dispatch"] == [4, 4, 4, 4, 2, 2]


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
