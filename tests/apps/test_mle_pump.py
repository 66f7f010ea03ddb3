"""One mixed-precision Matérn likelihood evaluation (``ops/mle.py``) at
small size on the CPU backend: its ``loglik`` against the dense float64
likelihood (all float32 and with a narrow band), the pump against
``Context`` tile by tile and dtype by dtype, every tile of the factor in
the precision of the map, a float32 tile with bfloat16 readers converted
once, the attach plan's key (another map: a miss; another theta: a hit),
the counts a solve is held to, and the planted faults the chip check has
to catch, each missing a limit of the configuration."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import ops_count_mle
from benchmark.drivers import pump_mle
from benchmark.reference import smle_matern_rows as ref
from parsec_tpu import Context, native
from parsec_tpu.dsl import attach_plan
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.ops import mle

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2147483999
pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")

with open(os.path.join(
        ROOT, "benchmark/configs/smle_matern_mp_nb2048_1chip.json")) as f:
    CONFIG = json.load(f)
LIMITS = CONFIG["limits"]


def problem_of(n, nb, band, seed=SEED):
    p = ref.make_problem(seed, dict(CONFIG, n=n, nb=nb, band_f32=band),
                         None, None)
    ref.prepare(p)
    return p


def solve(problem, band=None, solves=0):
    """One solve through the benchmark's own driver; ``solves`` picks
    the theta of the session's n-th solve."""
    drv = pump_mle.open(dict(CONFIG, band_f32=problem["band_f32"]), None,
                        {} if band is None else {"band_f32": band}, None,
                        "cpu")
    drv.solves = solves
    try:
        s = drv.solve(problem)
        dev_stats = dict(drv.dev.stats)
        drv.release(s)
    finally:
        drv.close()
    return s, dev_stats


def missed(numbers):
    return sorted(k for k in LIMITS if not numbers[k] <= LIMITS[k])


@pytest.mark.parametrize("n,nb", [(512, 64), (1024, 128)])
def test_all_float32_agrees_with_the_dense_float64_likelihood(n, nb):
    p = problem_of(n, nb, n // nb)
    s, _ = solve(p)
    assert s["violations"] == []
    want = ref.dense_loglik(p, s["result"]["theta"])
    got = s["result"]
    assert abs(got["loglik"] - want["loglik"]) \
        <= 1e-5 * abs(want["loglik"])
    assert abs(got["logdet"] - want["logdet"]) <= 1e-5 * abs(want["logdet"])
    assert abs(got["dot"] - want["dot"]) <= 1e-5 * abs(want["dot"])
    numbers = ref.compare(p, got)
    assert missed(numbers) == [] and numbers["offdiag_lo_error"] == 0.0


@pytest.mark.parametrize("n,nb,band", [(512, 64, 1), (512, 64, 2),
                                       (1024, 128, 2)])
def test_a_narrow_band_stays_inside_the_limit_it_states(n, nb, band):
    """With bfloat16 tiles the factor's entries there carry 8 bits: the
    likelihood's two parts move by up to a few 1e-3 of themselves (every
    entry of a bfloat16 tile is off by 2^-9 of its size, and ``loglik``
    is their difference).  2e-3 of the float64 value is what a band of 1
    reads at these sizes with room (found 2.8e-4 at n = 1024); the
    sampled reconstruction holds the configuration's own limits."""
    p = problem_of(n, nb, band)
    s, _ = solve(p)
    assert s["violations"] == []
    want = ref.dense_loglik(p, s["result"]["theta"])
    assert abs(s["result"]["loglik"] - want["loglik"]) \
        <= 2e-3 * abs(want["loglik"])
    numbers = ref.compare(p, s["result"])
    assert missed(numbers) == []
    assert 0.0 < numbers["offdiag_lo_error"]


def _through_context(cols, band):
    tp = mle.mle_taskpool(**cols, band_f32=band)
    ctx = Context(nb_cores=2)
    try:
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=300)
        for dev in ctx.devices:
            if dev.mca_name == "tpu":
                dev.flush()
                stats = dict(dev.stats)
    finally:
        ctx.fini()
    return stats


@pytest.mark.parametrize("band", [1, 2, 8])
def test_the_pump_and_the_context_path_agree_dtype_by_dtype(band):
    n, nb = 512, 64
    p = problem_of(n, nb, band)
    theta = p["theta"](0)
    cols = mle.mle_collections(n, nb, band, p["x"], p["z"], theta)
    ex = NativeExecutor(mle.mle_taskpool(**cols, band_f32=band),
                        native_device=True)
    assert ex.run() == mle.mle_ntasks(n // nb, band)
    ex.close()
    other = mle.mle_collections(n, nb, band, p["x"], p["z"], theta)
    stats = _through_context(other, band)
    assert stats["bytes_out"] == ops_count_mle.result_bytes(n)
    want = mle.band_dtype(band)
    for key in cols["A"].tiles():
        a = cols["A"].data_of(*key).newest_copy().payload
        b = other["A"].data_of(*key).newest_copy().payload
        assert a.dtype == b.dtype == want(*key)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(cols["Y"].to_array(), other["Y"].to_array(),
                               rtol=1e-5, atol=1e-6)
    assert mle.loglik_parts(cols["SC"]) == pytest.approx(
        mle.loglik_parts(other["SC"]), rel=1e-6)


@pytest.mark.parametrize("nt,band", [(8, 1), (8, 2), (8, 3), (12, 4),
                                     (8, 8), (8, 0)])
def test_every_tile_has_the_dtype_of_the_map_and_is_converted_once(
        nt, band):
    nb = 32
    p = problem_of(nt * nb, nb, band)
    s, stats = solve(p)
    assert s["violations"] == []
    twins = mle.converted_tiles(nt, band)
    assert twins == ops_count_mle.converted_tiles(nt, band)
    assert stats["convert_tiles"] == twins
    assert stats["convert_bytes"] == twins * nb * nb * 2
    # every reader of a twin: the gemm tasks of its column that write
    # bfloat16 (had they converted on their own: one conversion each)
    readers = sum(nt - 1 - (n + band) + 1
                  for k in range(nt) for n in range(k + 1, nt)
                  if n - k < band and n + band <= nt - 1)
    assert stats["convert_shared_hits"] == readers
    assert (readers > twins) == (twins > 0 and nt - band > 2)
    by = stats["tiles_by_dtype"]
    hi = ops_count_mle.f32_tiles(nt, band)
    # the float32 side also holds the vectors: locations, z, y, sums
    assert by.get("bfloat16", 0) >= (nt * (nt + 1) // 2 - hi) * nb * nb * 2
    assert by.get("float32", 0) >= hi * nb * nb * 4
    assert sum(by.values()) <= ops_count_mle.matrix_bytes(
        nt * nb, nb, band) * 1.2 + 64 * nb * nt


def test_the_counts_of_the_cell():
    assert ops_count_mle.ntasks(44, 4) == mle.mle_ntasks(44, 4) == 17362
    assert ops_count_mle.f32_tiles(44, 4) == 170
    assert ops_count_mle.converted_tiles(44, 4) == 114
    assert ops_count_mle.matrix_bytes(90112, 2048, 4) == 9730785280
    assert ops_count_mle.matrix_bytes(90112, 2048, 44) == 990 * 2 ** 24
    assert ops_count_mle.update_flops(44, 2048) == (946 + 2 * 13244) * 2.0 ** 33
    assert ops_count_mle.CLASSES == tuple(mle.mle_ptg().classes)


def test_another_map_is_a_plan_miss_and_another_theta_a_hit():
    n, nb = 256, 32
    p = problem_of(n, nb, 2)
    attach_plan.clear()

    def attach(band, theta):
        cols = mle.mle_collections(n, nb, band, p["x"], p["z"], theta)
        ex = NativeExecutor(mle.mle_taskpool(**cols, band_f32=band),
                            native_device=True)
        s = dict(ex.stats)
        ex.run()
        ex.close()
        return s["attach_plan_hits"], s["attach_plan_misses"], \
            s["attach_plan_uncacheable"]

    assert attach(2, (1.0, 0.1)) == (0, 1, 0)
    assert attach(2, (1.03, 0.097)) == (1, 0, 0)   # theta is a tile
    assert attach(3, (1.0, 0.1)) == (0, 1, 0)      # another map
    assert attach(2, (0.96, 0.104)) == (1, 0, 0)
    # two collections that differ ONLY in the map: two fingerprints
    a, b = (mle.mle_matrix(n, nb, band) for band in (2, 3))
    assert attach_plan._collection_fp(a) != attach_plan._collection_fp(b)
    assert attach_plan._collection_fp(a) == attach_plan._collection_fp(
        mle.mle_matrix(n, nb, 2))


def test_the_taskpool_refuses_a_map_that_is_not_its_band():
    p = problem_of(256, 32, 2)
    cols = mle.mle_collections(256, 32, 2, p["x"], p["z"], (1.0, 0.1))
    with pytest.raises(ValueError, match="band rule"):
        mle.mle_taskpool(**cols, band_f32=3)


def test_a_bfloat16_update_refuses_a_float32_operand():
    a = jnp.zeros((8, 8), jnp.bfloat16)
    hi = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(TypeError, match="converted once"):
        mle.gemm_tpu(a, hi.astype(jnp.bfloat16), hi)
    # lower operands for a float32 tile are used as they are
    lo = hi.astype(jnp.bfloat16)
    assert mle.gemm_tpu(hi, lo, lo).dtype == jnp.float32
    assert mle.gemm_tpu(hi, lo, hi).dtype == jnp.float32


# -- planted faults: each has to miss a limit of the configuration ----------

_sound_dcmg = mle.dcmg_tpu


def _transposed_dcmg(XM, XN, TH, C, **_):
    return _sound_dcmg(XN, XM, TH, C)


def _dropped_conversion(H, LO, **_):
    # the float32 tile's bits read as bfloat16: half of every word
    return lax.bitcast_convert_type(H, jnp.bfloat16)[..., 0]


_dropped_conversion._converts = True


@pytest.mark.parametrize("fault", ["all_bf16", "dropped_conversion",
                                   "stale_theta", "transposed_dcmg",
                                   "sound"])
def test_a_planted_fault_misses_a_limit(fault, monkeypatch):
    n, nb, band = 768, 64, 2
    p = problem_of(n, nb, band)
    if fault == "transposed_dcmg":
        monkeypatch.setattr(mle, "dcmg_tpu", _transposed_dcmg)
    if fault == "dropped_conversion":
        monkeypatch.setattr(mle, "convert_tpu", _dropped_conversion)
    s, _ = solve(p, band=0 if fault == "all_bf16" else None)
    result = s["result"]
    if fault == "stale_theta":
        # the program evaluated at the last solve's theta
        result = dict(result, theta=p["theta"](1))
    wrong = missed(ref.compare(p, result))
    if fault == "sound":
        assert wrong == [] and s["violations"] == []
    elif fault == "all_bf16":
        # the control: the float32 class's limits, not the bfloat16 one's
        assert {"diagonal_error", "offdiag_error"} <= set(wrong)
        assert "offdiag_lo_error" not in wrong
    else:
        assert wrong, fault


def test_a_missing_tile_or_a_non_finite_value_is_not_correct():
    p = problem_of(256, 32, 2)
    s, _ = solve(p)
    result = dict(s["result"])
    rows = dict(result["rows"])
    del rows[(3, 1)]
    assert set(missed(ref.compare(p, dict(result, rows=rows)))) == set(LIMITS)
    y = result["y"].copy()
    y[5] = np.nan
    assert "nonfinite_values" in missed(ref.compare(p, dict(result, y=y)))
    assert "nonfinite_values" in missed(
        ref.compare(p, dict(result, logdet=float("inf"))))
