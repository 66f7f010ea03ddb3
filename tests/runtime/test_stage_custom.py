"""Custom per-flow stage_in/stage_out device hooks.

Reference: ``tests/runtime/cuda/stage_custom.jdf:185-186`` +
``parsec/mca/device/device_gpu.h:62-94`` — a task overrides how a flow's
data is staged into/out of device memory (pack a strided subtile,
convert layout).  Here: ``stage_in(data, device) -> array`` makes the
flow's device copy; ``stage_out(array, data, device) -> array``
transforms the body output before it is committed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parsec_tpu import Context, DEV_TPU
from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl.ptg import INOUT, PTG


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


def test_ptg_stage_hooks_pack_strided_subtile(ctx):
    """The device body sees a PACKED even-column subtile (half the HBM
    of the full tile); stage_out scatters the result back into the full
    layout.  The odd columns must be preserved untouched."""
    dev = tpu_dev(ctx)
    N, NT = 8, 3
    dc = LocalCollection(
        "A", shape=(N, N),
        init=lambda k: np.arange(N * N, dtype=np.float64).reshape(N, N))

    calls = {"in": 0, "out": 0}

    def pack_even_cols(data, device):
        calls["in"] += 1
        host = np.asarray(data.newest_copy().payload)
        return jnp.asarray(host[:, ::2])  # strided subtile, packed

    def scatter_back(arr, data, device):
        # the staged device copy is the PACKED subtile; the home layout
        # lives in the host copy (reference stage_out sees both buffers)
        calls["out"] += 1
        full = jnp.asarray(np.asarray(data.get_copy(0).payload))
        return full.at[:, ::2].set(arr)

    ptg = PTG("stagec")
    t = ptg.task_class("t", k=f"0 .. {NT-1}")
    t.affinity("A(k)")
    t.flow("X", INOUT, "<- A(k)", "-> A(k)")
    t.stage("X", stage_in=pack_even_cols, stage_out=scatter_back)
    t.body(tpu=lambda X, k: X * 10.0)
    tp = ptg.taskpool(A=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    assert calls["in"] == NT and calls["out"] == NT
    assert dev.stats.get("custom_stage_in", 0) == NT
    assert dev.stats.get("custom_stage_out", 0) == NT
    base = np.arange(N * N, dtype=np.float64).reshape(N, N)
    expect = base.copy()
    expect[:, ::2] *= 10.0  # even columns transformed, odd untouched
    for k in range(NT):
        from parsec_tpu.dsl.dtd import stage_to_cpu

        np.testing.assert_allclose(stage_to_cpu(dc.data_of(k)), expect)


def test_jdf_stage_properties(ctx):
    """The JDF surface: BODY [stage_in = fn stage_out = fn] properties
    reach the device module (reference stage_custom.jdf syntax)."""
    from parsec_tpu.dsl import compile_jdf

    tpu_dev(ctx)
    N = 4
    src = """
A  [ type = "collection" ]
NT [ type = int ]

t(k)

k = 0 .. NT-1

: A( k )

RW X <- A( k )
     -> A( k )

BODY [ type = TPU
       stage_in = pack_half
       stage_out = unpack_half ]
{
    return X + 1.0
}
END
"""

    def pack_half(data, device):
        import jax.numpy as _jnp

        host = np.asarray(data.newest_copy().payload)
        return _jnp.asarray(host[: len(host) // 2])

    def unpack_half(arr, data, device):
        import jax.numpy as _jnp

        full = _jnp.asarray(np.asarray(data.get_copy(0).payload))
        return full.at[: full.shape[0] // 2].set(arr)

    jdf = compile_jdf(src, "stagejdf", namespace={
        "pack_half": pack_half, "unpack_half": unpack_half})
    dc = LocalCollection("A", shape=(N,), init=lambda k: np.zeros(N))
    tp = jdf.new(A=dc, NT=2)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    for k in range(2):
        got = stage_to_cpu(dc.data_of(k))
        np.testing.assert_allclose(got, [1.0, 1.0, 0.0, 0.0])


def test_packed_copy_never_served_as_home_layout(ctx):
    """A READ flow's pack hook leaves a PACKED device copy; a later
    hookless task on the same tile must NOT receive it — the default
    stage-in drops the packed copy and restages the home layout."""
    dev = tpu_dev(ctx)
    N = 8
    d_ = None
    from parsec_tpu.data import data_create

    base = np.arange(float(N * N)).reshape(N, N)
    d_ = data_create("pk", payload=base.copy())
    seen_shapes = []

    def pack(data, device):
        return jnp.asarray(np.asarray(data.newest_copy().payload)[:, ::2])

    from parsec_tpu.dsl.ptg import IN, PTG

    ptg = PTG("pkread")
    t = ptg.task_class("t", k="0 .. 0")
    t.affinity("A(0)")
    t.flow("X", IN, "<- A(0)")
    t.stage("X", stage_in=pack)  # READ-only: no stage_out needed
    t.body(tpu=lambda X, k: (seen_shapes.append(X.shape), ())[1])
    from parsec_tpu.data import LocalCollection

    dc = LocalCollection("A", shape=(N, N), init=lambda k: base.copy())
    tp = ptg.taskpool(A=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    # the body saw the packed tile, in every trace of it (the device
    # traces a body once more, abstractly, to see which values it reads)
    assert seen_shapes and set(seen_shapes) == {(N, N // 2)}
    # now a plain device task on the same tile: must see FULL layout
    from parsec_tpu.dsl import DTDTaskpool, INOUT

    tp2 = DTDTaskpool(ctx)
    tp2.insert_task({"tpu": lambda x: x + 1.0}, (dc.data_of(0), INOUT))
    assert tp2.wait(timeout=60)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(dc.data_of(0)), base + 1.0)


def test_custom_staging_preserves_dirty_device_copy(ctx):
    """A dirty (device-only) newest version must be flushed home BEFORE
    a pack hook replaces the device copy — otherwise the unpacked part
    of the newest data exists nowhere and the scatter hook reconstructs
    from stale host values."""
    tpu_dev(ctx)
    N = 8
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import INOUT, PTG

    base = np.zeros((N, N))
    dc = LocalCollection("A", shape=(N, N), init=lambda k: base.copy())

    def pack(data, device):
        return jnp.asarray(np.asarray(data.get_copy(0).payload)[:, ::2])

    def scatter(arr, data, device):
        full = jnp.asarray(np.asarray(data.get_copy(0).payload))
        return full.at[:, ::2].set(arr)

    ptg = PTG("dirtypack")
    # t1: plain device body makes the device copy the ONLY newest
    # version (+5 everywhere); t2: pack/scatter hooks on even columns
    t1 = ptg.task_class("t1", k="0 .. 0")
    t1.affinity("A(0)")
    t1.flow("X", INOUT, "<- A(0)", "-> X t2(0)")
    t1.body(tpu=lambda X, k: X + 5.0)
    t2 = ptg.task_class("t2", k="0 .. 0")
    t2.affinity("A(0)")
    t2.flow("X", INOUT, "<- X t1(0)", "-> A(0)")
    t2.stage("X", stage_in=pack, stage_out=scatter)
    t2.body(tpu=lambda X, k: X * 2.0)
    tp = ptg.taskpool(A=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60)
    from parsec_tpu.dsl.dtd import stage_to_cpu

    got = stage_to_cpu(dc.data_of(0))
    expect = np.full((N, N), 5.0)
    expect[:, ::2] = 10.0
    np.testing.assert_allclose(got, expect)  # odd columns kept t1's +5


def test_stage_in_writable_without_stage_out_fails_loudly(ctx):
    """stage_in on a writable flow with no stage_out would commit the
    packed body output as the home tile: refused, pool fails."""
    tpu_dev(ctx)
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl.ptg import INOUT, PTG

    dc = LocalCollection("A", shape=(4,), init=lambda k: np.zeros(4))
    ptg = PTG("badstage")
    t = ptg.task_class("t", k="0 .. 0")
    t.affinity("A(0)")
    t.flow("X", INOUT, "<- A(0)", "-> A(0)")
    t.stage("X", stage_in=lambda data, device: jnp.zeros(2))
    t.body(tpu=lambda X, k: X)
    tp = ptg.taskpool(A=dc)
    ctx.add_taskpool(tp)
    assert tp.wait(timeout=60) is False  # loud failure, not silent corruption
