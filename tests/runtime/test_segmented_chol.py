"""Panel-segmented Cholesky through the full runtime (taskpool +
scheduler + TPU device module) — the north-star execution path.

Pins: numerics vs numpy, compile count O(panels) (one specialised
program per k via ``_static_values``), in-place donation (device copy
rebinds, no per-step buffer growth in the accounted budget), and that
the tasks really flowed through the device module's eager lanes."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from parsec_tpu import Context
from parsec_tpu.ops.segmented_chol import SegmentedCholesky


def _spd(n, dtype=np.float32, seed=7):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n)).astype(dtype)
    return (M @ M.T + n * np.eye(n, dtype=dtype)).astype(dtype)


@pytest.fixture
def ctx():
    c = Context(nb_cores=2)
    yield c
    c.fini()


def test_segmented_matches_numpy(ctx):
    n, nb = 256, 64
    SPD = _spd(n)
    sc = SegmentedCholesky(ctx, n, nb, strip=128, tail=0)
    L = sc(SPD)
    ref = np.linalg.cholesky(SPD.astype(np.float64))
    assert np.max(np.abs(L - ref)) / np.max(np.abs(ref)) < 1e-4


def test_segmented_fused_tail_matches_numpy(ctx):
    """Tail fusing (last panels in one program) must not change results,
    and must shrink the task count accordingly."""
    n, nb = 256, 64
    SPD = _spd(n)
    sc = SegmentedCholesky(ctx, n, nb, strip=128, tail=128)  # fuse last 2
    assert sc.nt_tasks == n // nb - 1
    L = sc(SPD)
    ref = np.linalg.cholesky(SPD.astype(np.float64))
    assert np.max(np.abs(L - ref)) / np.max(np.abs(ref)) < 1e-4


def test_compile_scaling_law(ctx):
    """Compile scaling law (round-3 VERDICT #3): the default GENERIC
    body compiles ONE parameter-generic program for all NT tasks (traced
    k + dynamic_slice — the jdf2c one-function-per-task-class model);
    the STATIC mode keeps exactly NT per-k specialised entries."""
    n, nb = 256, 64
    sc = SegmentedCholesky(ctx, n, nb, strip=128, tail=0,
                           specialize="generic")
    before = set(sc.device._jit_cache)
    sc(_spd(n))
    added = {k for k in sc.device._jit_cache if k not in before}
    assert len(added) == 1, added
    # a second run re-uses the cached program
    sc(_spd(n, seed=8))
    assert set(sc.device._jit_cache) == before | added
    # static mode (chol's default — measured faster on TPU): one
    # program per k
    ss = SegmentedCholesky(ctx, n, nb, strip=128, tail=0,
                           specialize="static")
    before = set(ss.device._jit_cache)
    ss(_spd(n))
    added = {k for k in ss.device._jit_cache if k not in before}
    assert len(added) == n // nb, added


def test_matrix_stays_resident_and_donated(ctx):
    """The INOUT whole-matrix flow must keep ONE accounted device
    residency slot (epilog rebinds the same Data), and the input device
    array must actually be donated (consumed) by the first step."""
    n, nb = 256, 64
    SPD = _spd(n)
    sc = SegmentedCholesky(ctx, n, nb, strip=128, tail=0)
    A = jax.device_put(jax.numpy.asarray(SPD), sc.device.jdev)
    out = sc.run(A)
    np.asarray(out)  # result is real
    assert sc.device.stats["bytes_in"] == 0  # never staged via host
    if jax.default_backend() != "cpu":
        with pytest.raises(Exception):
            np.asarray(A)  # donated: consumed by step 0
    else:
        # CPU jax may ignore donation (it warns instead); the contract
        # that matters everywhere is the rebind: the Data's device copy
        # is the final output, not the input
        assert out is not A


def test_static_values_rejects_interleaved_args(ctx):
    """A _static_values body whose VALUE args do not trail the data args
    (DTD-style interleaving) must be rejected loudly, not silently baked
    wrong (suffix split would treat a trailing array as the static
    value)."""
    from types import SimpleNamespace

    from parsec_tpu.core.lifecycle import AccessMode, DEV_TPU
    from parsec_tpu.core.task import Chore, Task, TaskClass
    from parsec_tpu.data import LocalCollection

    dev = next(d for d in ctx.devices if d.mca_name == "tpu")

    def body(a, b):
        return a

    body._static_values = True
    dc = LocalCollection("Z", shape=(4,), dtype=np.float32)
    t = Task(SimpleNamespace(failed=False), TaskClass("interleaved"))
    t.body_args = [("data", dc.data_of(0), AccessMode.INOUT),
                   ("value", 3, AccessMode.VALUE),
                   ("data", dc.data_of(1), AccessMode.INOUT)]
    t.selected_chore = Chore(DEV_TPU, hook=lambda es, task: None)
    t.selected_chore.body_fn = body
    with pytest.raises(RuntimeError, match="must.*trail|trail all data"):
        dev._submit(t)


def test_segmented_store_bf16_matches_numpy(ctx):
    """bf16-STORAGE mode: the matrix lives in bf16 (half the HBM traffic
    — the binding constraint at north-star sizes); panel math upcast to
    f32.  bf16-class numerics on a generic SPD input."""
    n, nb = 256, 64
    SPD = _spd(n)
    sc = SegmentedCholesky(ctx, n, nb, strip=128, tail=0, bf16="storage")
    L = sc(SPD)
    assert L.dtype == np.float32  # __call__ upcasts the bf16 result
    ref = np.linalg.cholesky(SPD.astype(np.float64))
    rel = np.max(np.abs(L - ref)) / np.max(np.abs(ref))
    assert rel < 5e-2, rel  # bf16-class (eps ~8e-3, growth over panels)
