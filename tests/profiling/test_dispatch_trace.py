"""What is inside ``parsec:dev:dispatch``, read off a profiler trace.

A program compiled through its serialized form keeps every argument of
``Exported.call``, and the executable's call path copies each Python
scalar among them to the device on its own: a ``DevicePut`` host event a
scalar a call (200-360 us each on a v5e; the CPU backend emits the same
event).  The device module hands a program no scalar, so a traced solve
shows none under its ``dev:dispatch`` spans."""

import glob
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parsec_tpu import compile_cache as cc
from parsec_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native core unavailable: {native.build_error()}")


def _host_events(trace_dir):
    """(thread line, name, start, end) of every host event, read as
    ``benchmark/trace/reduce.load_events`` reads them."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _puts_under(events, span):
    spans = [e for e in events if e[1].startswith(span)]
    puts = [e for e in events if "DevicePut" in e[1]]
    return spans, [p for p in puts
                   if any(s[0] == p[0] and s[2] <= p[2] and p[3] <= s[3]
                          for s in spans)]


def test_no_scalar_transfer_under_dev_dispatch(tmp_path):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.device.tpu import TpuDevice
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops.cholesky import cholesky_ptg

    # every program through the exported path
    cache = cc.ExecutableCache(store=cc.DiskStore(str(tmp_path / "exe")),
                               min_disk_s=0.0)
    dev = TpuDevice(types.SimpleNamespace(rank=0, nranks=1, devices=[],
                                          compile_cache=cache), index=1)
    dev.attach()

    def solve(seed):
        n, nb = 128, 32
        m = np.random.default_rng(seed).standard_normal((n, n))
        S = m @ m.T + n * np.eye(n)
        A = TiledMatrix(n, n, nb, nb, name="A",
                        dtype=np.float64).from_array(S)
        tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
        ex = NativeExecutor(tp, native_device=True, device=dev)
        assert ex.run(nthreads=2) == 20
        ex.close()

    # the control: the parent's call, a program compiled through its
    # serialized form and handed three Python scalars
    old = cache.jit(lambda a, m, n, k: a + 1.0, key=("control",))
    a = jnp.ones((8, 8))
    try:
        solve(0)  # compiles; the traced solve only dispatches
        old(a, 1, 2, 3)
        assert cache.stats["bytes_written"] > 0
        with jax.profiler.trace(str(tmp_path / "trace")):
            solve(1)
            with jax.profiler.TraceAnnotation("control:dispatch"):
                old(a, 1, 2, 3)
    finally:
        dev.detach()
    events = _host_events(tmp_path / "trace")
    spans, under = _puts_under(events, "control:dispatch")
    assert len(spans) == 1 and len(under) == 3, \
        "the backend no longer shows a scalar argument's transfer: " \
        "this test sees nothing"
    spans, under = _puts_under(events, "parsec:dev:dispatch")
    assert len(spans) >= 10
    assert under == []
    assert dev.stats["value_args_dropped"] == 2 * (4 + 6 * 2 + 6 * 2 + 4 * 3)
