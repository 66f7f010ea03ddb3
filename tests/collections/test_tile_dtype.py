"""A precision a tile, and tiles born on the device: ``TiledMatrix(
tile_dtype=..., device_born=...)``; what the attach plan's key reads of
them; a tile's bytes at its own precision in a wave's ``FlowPlan`` and in
the residency's accounting; one wave signature a combination of dtypes,
named on the program's span."""

import numpy as np
import pytest

import jax.numpy as jnp

from parsec_tpu import native
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.data.data import KEPT, Data
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.datadist.matrix import LOWER
from parsec_tpu.device import scratch
from parsec_tpu.device.residency import Residency
from parsec_tpu.device.value_args import FlowPlan
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.dsl.ptg import PTG
from parsec_tpu.profiling import pins

BF16 = np.dtype(jnp.bfloat16)
F32 = np.dtype(np.float32)
INOUT, IN = AccessMode.INOUT, AccessMode.IN


def band(i, j):
    return F32 if abs(i - j) < 2 else BF16


@pytest.mark.parametrize("pick", [
    band, lambda i, j: BF16 if abs(i - j) >= 2 else None],
    ids=["every_tile_named", "none_is_the_default"])
def test_a_tile_is_stored_in_the_precision_the_map_gives_it(pick):
    A = TiledMatrix(32, 32, 8, 8, dtype=np.float32, tile_dtype=pick)
    assert A.default_dtype == F32
    assert A.dtype_of(0, 0) == A.dtype_of(2, 1) == F32
    assert A.dtype_of(3, 0) == A.dtype_of(0, 2) == BF16
    # the lazily created zero tile, from_array and to_array honour it
    assert A.data_of(3, 0).get_copy(0).payload.dtype == BF16
    assert A.data_of(3, 0).dtype == BF16 and A.data_of(1, 1).dtype == F32
    a = np.random.default_rng(0).uniform(-1, 1, (32, 32)).astype(np.float32)
    A.from_array(a)
    for (i, j) in A.tiles():
        tile = A.data_of(i, j).get_copy(0).payload
        assert tile.dtype == A.dtype_of(i, j)
        np.testing.assert_array_equal(
            tile, a[8 * i:8 * i + 8, 8 * j:8 * j + 8].astype(tile.dtype))
    back = A.to_array()
    assert back.dtype == F32
    np.testing.assert_array_equal(back[:8, :8], a[:8, :8])
    np.testing.assert_array_equal(
        back[24:, :8], a[24:, :8].astype(BF16).astype(np.float32))


def test_the_map_as_run_lengths_and_none_without_one():
    assert TiledMatrix(32, 32, 8, 8, dtype=np.float32).dtype_map() is None
    A = TiledMatrix(32, 32, 8, 8, dtype=np.float32, tile_dtype=band,
                    uplo=LOWER)
    # (0,0) (1,0) (1,1) | (2,0) | (2,1) (2,2) | (3,0) (3,1) | (3,2) (3,3)
    assert A.dtype_map() == (("float32", 3), ("bfloat16", 1), ("float32", 2),
                             ("bfloat16", 2), ("float32", 2))


def test_a_device_born_tile_has_no_host_value_and_keeps_its_users():
    A = TiledMatrix(32, 32, 8, 8, dtype=np.float32, tile_dtype=band,
                    device_born=True)
    d = A.data_of(3, 0)
    assert d.copies == {} and d.newest_copy() is None
    assert (d.shape, d.dtype, d.scratch, d.collection) == (
        (8, 8), BF16, KEPT, A)
    assert A.data_of(3, 0) is d
    assert scratch.unborn(d)
    # nobody's retirement frees it
    scratch.add_users(d, 3)
    assert d.scratch == KEPT
    assert not scratch.release(d) and d.scratch == KEPT
    # a tile nobody has written gathers as zeros; no host value is taken
    assert not A.to_array().any()
    with pytest.raises(ValueError, match="no host value"):
        A.from_array(np.zeros((32, 32), np.float32))
    with pytest.raises(ValueError, match="no.*host value"):
        TiledMatrix(8, 8, 8, 8, device_born=True,
                    init=lambda i, j, shape: np.zeros(shape))
    d.attach_copy(1, jnp.ones((8, 8), jnp.bfloat16))
    assert not scratch.unborn(d)
    assert A.to_array()[24:, :8].min() == 1.0


def test_the_plans_key_reads_the_map_and_where_the_tiles_are_born():
    fp = attach_plan._collection_fp

    def matrix(**kw):
        return TiledMatrix(32, 32, 8, 8, dtype=np.float32, **kw)

    plain = fp(matrix())
    assert plain[-2:] == (None, False)
    assert fp(matrix()) == plain
    assert fp(matrix(tile_dtype=band)) != plain
    assert fp(matrix(tile_dtype=band)) == fp(matrix(tile_dtype=band))
    assert fp(matrix(tile_dtype=band)) != fp(matrix(
        tile_dtype=lambda i, j: F32 if abs(i - j) < 3 else BF16))
    assert fp(matrix(device_born=True)) != plain
    # a map that names every tile's default is still a map (the key may
    # tell two equal shapes apart; it may never confuse two different)
    assert fp(matrix(tile_dtype=lambda i, j: F32))[-2] == (("float32", 16),)


def test_a_flow_plan_counts_a_tile_at_its_own_bytes_and_names_the_dtypes():
    hi = ((64, 64), F32, INOUT)
    lo = ((64, 64), BF16, IN)
    plan = FlowPlan((hi, lo, lo, int))
    # read and written float32 (2 x 16 KiB), two bfloat16 reads (8 KiB)
    assert plan.nbytes == 2 * 64 * 64 * 4 + 2 * 64 * 64 * 2
    assert plan.dtypes == "float32/bfloat16/bfloat16"
    assert FlowPlan((hi, ((64, 64), F32, IN), ((64, 64), F32, IN))).nbytes \
        == 4 * 64 * 64 * 4
    unborn = ("unborn", (64, 64), BF16, AccessMode.OUT)
    assert FlowPlan((unborn,)).nbytes == 64 * 64 * 2
    assert FlowPlan((unborn,)).dtypes == "bfloat16"


def test_the_residency_keeps_the_bytes_by_dtype_at_the_peak():
    stats = {}
    res = Residency(1, 1 << 30, stats, lambda victims: 0)
    tiles = [Data(("t", k), shape=(8, 8), dtype=dt)
             for k, dt in enumerate((F32, F32, BF16, BF16, BF16))]
    with res.lock:
        for d in tiles:
            res.account(d, 64 * d.dtype.itemsize)
    assert stats["tiles_by_dtype"] == {"float32": 512, "bfloat16": 384}
    with res.lock:
        res.free(tiles[0])
        res.free(tiles[2])
    # below the peak: the snapshot stands, through a clear too
    assert stats["tiles_by_dtype"] == {"float32": 512, "bfloat16": 384}
    res.clear()
    assert stats["tiles_by_dtype"] == {"float32": 512, "bfloat16": 384}
    # the next solve's first charge starts a new peak
    with res.lock:
        res.account(tiles[3], 128)
    assert stats["tiles_by_dtype"] == {"bfloat16": 128}


def _axpy_pool(dtypes):
    """``A(k) += B(k)`` over tiles whose dtypes the list gives, a pair a
    task: one class, one signature a combination."""
    nt = len(dtypes)
    pick = {(k, 0): a for k, (a, _b) in enumerate(dtypes)}
    pick.update({(k, 1): b for k, (_a, b) in enumerate(dtypes)})
    M = TiledMatrix(8 * nt, 16, 8, 8, dtype=np.float32,
                    tile_dtype=lambda i, j: pick[(i, j)])
    M.from_array(np.ones((8 * nt, 16), np.float32))
    ptg = PTG("axpy")
    t = ptg.task_class("axpy", k="0 .. NT-1")
    t.affinity("M(k, 0)")
    t.flow("A", INOUT, "<- M(k, 0)", "-> M(k, 0)")
    t.flow("B", IN, "<- M(k, 1)")
    t.body(tpu=lambda A, B, **_: A + B.astype(A.dtype))
    return M, ptg.taskpool(NT=nt, M=M)


@pytest.mark.skipif(not native.available(), reason="needs the native core")
def test_one_wave_signature_a_combination_of_dtypes_named_on_the_span():
    pins.clear()
    combos = [(F32, F32)] * 4 + [(F32, BF16)] * 4 + [(BF16, BF16)] * 2
    M, tp = _axpy_pool(combos)
    seen = []

    def begin(es, payload):
        seen.append((payload["n"], payload["dtypes"]))

    pins.subscribe("dev:wave_begin", begin)
    pins.subscribe("dev:submit_one_begin", begin)
    try:
        ex = NativeExecutor(tp, native_device=True)
        dev = ex.device
        assert ex.run() == 10
        ex.close()
    finally:
        pins.clear()
    assert dev.stats["wave_signatures"] == 3
    assert sorted(seen) == [(2, "bfloat16/bfloat16"),
                            (4, "float32/bfloat16"),
                            (4, "float32/float32")]
    assert dev.stats["tiles_by_dtype"] == {"float32": 12 * 256,
                                           "bfloat16": 8 * 128}
    out = M.to_array()
    assert out.dtype == F32 and (out[:, :8] == 2.0).all()
    # a second attach starts a new count
    M2, tp2 = _axpy_pool(combos[:4])
    ex = NativeExecutor(tp2, native_device=True, device=dev)
    ex.run()
    ex.close()
    assert dev.stats["wave_signatures"] == 4
