"""Panel-wise / whole-program Cholesky (ops/panel_chol.py) — the
compile-scalable path to the BASELINE.json north star (N=32768, nb=512).

Correctness strategy: f64 runs must match numpy's factorization to
machine precision (catches structural bugs that f32 rounding would
mask); f32 runs are held to the same 2e-3 bar as the other tiled paths.
"""

import numpy as np
import pytest

import jax

from parsec_tpu.ops.panel_chol import PanelCholesky, WholeCholesky


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return m @ m.T + n * np.eye(n)


@pytest.mark.parametrize("n,nb,bucket", [(256, 32, 4), (384, 32, 3),
                                         (512, 64, 8)])
def test_bucketed_panel_f64_exact(n, nb, bucket):
    spd = _spd(n, n)
    L = PanelCholesky(n, nb, bucket=bucket)(spd)
    ref = np.linalg.cholesky(spd)
    assert np.abs(L - ref).max() / np.abs(ref).max() < 1e-12


def test_bucketed_panel_strip_mined():
    spd = _spd(256, 1)
    L = PanelCholesky(256, 32, bucket=4, strip=64)(spd)
    ref = np.linalg.cholesky(spd)
    assert np.abs(L - ref).max() / np.abs(ref).max() < 1e-12


@pytest.mark.parametrize("n,nb,strip", [(256, 32, 64), (512, 64, 128),
                                        (256, 64, 64)])
def test_whole_program_f64_exact(n, nb, strip):
    spd = _spd(n, n + 1)
    L = WholeCholesky(n, nb, strip=strip)(spd)
    ref = np.linalg.cholesky(spd)
    assert np.abs(L - ref).max() / np.abs(ref).max() < 1e-12


def test_whole_program_f32_bar():
    n, nb = 512, 64
    spd = _spd(n, 3).astype(np.float32)
    L = WholeCholesky(n, nb, strip=128)(spd)
    ref = np.linalg.cholesky(spd.astype(np.float64))
    assert np.abs(L - ref).max() / np.abs(ref).max() < 2e-3


def test_whole_program_bf16_flag():
    n, nb = 256, 64
    spd = _spd(n, 5).astype(np.float32)
    L = WholeCholesky(n, nb, bf16=True, strip=64)(spd)
    ref = np.linalg.cholesky(spd.astype(np.float64))
    assert np.abs(L - ref).max() / np.abs(ref).max() < 2e-2


def test_compile_is_o_panels_not_o_tasks():
    """The whole program traces O(NT) ops: NT=32 at n=1024/nb=32 (~5.5k
    tile-tasks in DAG terms) must lower to a jaxpr whose equation count
    scales with panels — the property that makes NT=64 compilable at
    all."""
    n, nb = 1024, 32
    wc = WholeCholesky(n, nb, strip=256)
    jaxpr = jax.make_jaxpr(wc._factorize)(
        jax.ShapeDtypeStruct((n, n), np.float32))
    neq = len(jaxpr.jaxpr.eqns)
    nt = n // nb
    # ~4 core ops + ~n/strip update ops per panel; far below the ~5.5k
    # task count the per-task unroll would emit
    assert neq < 40 * nt, f"{neq} eqns for {nt} panels"


def test_input_validation():
    with pytest.raises(ValueError):
        WholeCholesky(100, 32)
    with pytest.raises(ValueError):
        WholeCholesky(256, 32, strip=48)
    with pytest.raises(ValueError):
        PanelCholesky(100, 32)
