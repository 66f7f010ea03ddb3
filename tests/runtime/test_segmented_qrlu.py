"""Segmented QR (BCGS + CholeskyQR2) and LU (block-local pivoting)
through the full runtime — numerics vs numpy on the CPU backend."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from parsec_tpu import Context
from parsec_tpu.ops.segmented_lu import SegmentedLU
from parsec_tpu.ops.segmented_qr import SegmentedQR


@pytest.fixture
def ctx():
    c = Context(nb_cores=2)
    yield c
    c.fini()


def test_segmented_qr_matches_numpy(ctx):
    n, nb = 256, 64
    rng = np.random.default_rng(3)
    A = rng.standard_normal((n, n)).astype(np.float32)
    sq = SegmentedQR(ctx, n, nb, strip=128)
    Q, R = sq(A)
    # reconstruction + orthogonality (explicit-Q representation; numpy's
    # Q differs by column signs, so compare via Q R and Q^T Q, not Q).
    # Orthogonality of one-shot BCGS is kappa-amplified (classic CGS
    # bound): for this seed kappa(A)~1.3e3, honest f32 orth is 1e-4..2e-3
    # depending on the backend's reduction order — a <1e-4 bar only
    # passed by summation-order luck (round-5 finding)
    rec = np.max(np.abs(Q @ R - A)) / np.max(np.abs(A))
    orth = np.max(np.abs(Q.T @ Q - np.eye(n)))
    assert rec < 1e-4, rec
    assert orth < 2e-3, orth
    # R matches numpy's up to row signs
    Rn = np.linalg.qr(A.astype(np.float64), mode="r")
    assert np.allclose(np.abs(R), np.abs(Rn), atol=1e-2 * np.abs(Rn).max())


def test_segmented_lu_matches_numpy(ctx):
    n, nb = 256, 64
    rng = np.random.default_rng(4)
    A = rng.standard_normal((n, n)).astype(np.float32)
    A += n * np.eye(n, dtype=np.float32)  # diagonally dominant: nopiv-safe
    sl = SegmentedLU(ctx, n, nb, strip=128, tail=0)
    L, U = sl(A)
    rec = np.max(np.abs(L @ U - A)) / np.max(np.abs(A))
    assert rec < 1e-5, rec
    # L unit-lower, U upper by construction
    assert np.allclose(np.diag(L), 1.0)


def test_segmented_lu_fused_tail(ctx):
    n, nb = 256, 64
    rng = np.random.default_rng(5)
    A = rng.standard_normal((n, n)).astype(np.float32)
    A += n * np.eye(n, dtype=np.float32)
    sl = SegmentedLU(ctx, n, nb, strip=128, tail=128)
    assert sl.nt_tasks == n // nb - 1
    L, U = sl(A)
    rec = np.max(np.abs(L @ U - A)) / np.max(np.abs(A))
    assert rec < 1e-5, rec


def test_segmented_qr_two_flow_residency(ctx):
    """Both matrix flows (Q-in-place and R) ride the device module; no
    host staging, both residency slots released after the run."""
    n, nb = 256, 64
    rng = np.random.default_rng(6)
    A = rng.standard_normal((n, n)).astype(np.float32)
    sq = SegmentedQR(ctx, n, nb, strip=128)
    A_dev = jax.device_put(jax.numpy.asarray(A), sq.device.jdev)
    Q, R = sq.run(A_dev)
    np.asarray(Q), np.asarray(R)
    assert sq.device.stats["bytes_in"] == 0
    assert not sq.device._res.dirty and not sq.device._res.clean


def test_generic_partial_strip_coverage(ctx):
    """Regression: the generic bodies' chunk grid must cover the partial
    last strip when strip does not divide n (rows/cols past the last
    full strip boundary were silently skipped)."""
    import numpy as np

    from parsec_tpu.ops.segmented_chol import SegmentedCholesky
    from parsec_tpu.ops.segmented_lu import SegmentedLU
    from parsec_tpu.ops.segmented_qr import SegmentedQR

    n, nb, strip = 384, 64, 256  # 1.5 strips
    rng = np.random.default_rng(5)
    A = rng.standard_normal((n, n)).astype(np.float32)
    SPD = A @ A.T + n * np.eye(n, dtype=np.float32)
    Add = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
    sc = SegmentedCholesky(ctx, n, nb, strip=strip, tail=0,
                           specialize="generic")
    L = sc(SPD)
    assert np.abs(L - np.linalg.cholesky(SPD)).max() / n < 1e-3
    Q, R = SegmentedQR(ctx, n, nb, strip=strip)(A)
    assert np.abs(Q @ R - A).max() / np.abs(A).max() < 1e-3
    Lu, U = SegmentedLU(ctx, n, nb, strip=strip, tail=0)(Add)
    assert np.abs(Lu @ U - Add).max() / np.abs(Add).max() < 1e-3


def test_lu_bf16_modes(ctx):
    """The cholesky levers on getrf: bf16 operand and bf16-STORAGE
    trailing updates, gated at the bf16-class 1e-2 bar (f32 keeps 1e-3);
    both specializations agree."""
    import numpy as np

    from parsec_tpu.ops.segmented_lu import SegmentedLU

    n, nb = 512, 64
    rng = np.random.default_rng(11)
    Add = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
    for spec in ("generic", "static"):
        for bf16, bar in ((False, 1e-3), (True, 1e-2), ("storage", 1e-2)):
            sl = SegmentedLU(ctx, n, nb, tail=128, specialize=spec,
                             bf16=bf16)
            L, U = sl(Add)
            err = np.abs(
                (L.astype(np.float64) @ U.astype(np.float64)) - Add
            ).max() / np.abs(Add).max()
            assert err < bar, (spec, bf16, err)


def test_lu_panel_pivoting(ctx):
    """pivot="panel": TRUE partial pivoting over the full trailing
    column.  On a matrix whose best pivots live OUTSIDE the diagonal
    block, the nopiv-class block mode explodes (unbounded multipliers)
    while panel mode keeps every |L| multiplier <= 1 — the partial-
    pivoting guarantee — and reconstructs A[V] = L U."""
    import numpy as np

    from parsec_tpu.ops.segmented_lu import SegmentedLU

    n, nb = 256, 64
    rng = np.random.default_rng(2)
    A = rng.standard_normal((n, n)).astype(np.float32)
    A[:nb, :nb] *= 1e-6  # adversarial for block-local pivoting
    sl = SegmentedLU(ctx, n, nb, tail=64, specialize="static",
                     pivot="panel")
    L, U, V = sl(A)
    err = np.abs(L @ U - A[V]).max() / np.abs(A).max()
    assert err < 2e-3, err
    assert np.abs(np.tril(L, -1)).max() <= 1.0 + 1e-6  # |L| bounded
    assert (V != np.arange(n)).any()  # rows really moved across blocks


def test_qr_fused_tail_and_task_count(ctx):
    """Round-5: QR gets the chol/LU tail batcher — trailing panels fuse
    into one task (their device time is below per-program enqueue
    latency), leading panels stay one task each, numerics unchanged."""
    n, nb = 256, 64
    rng = np.random.default_rng(7)
    A = rng.standard_normal((n, n)).astype(np.float32)
    sq = SegmentedQR(ctx, n, nb, strip=128, tail=128)
    assert sq.nt_tasks == n // nb - 1  # last two panels fused
    Q, R = sq(A)
    rec = np.max(np.abs(Q @ R - A)) / np.max(np.abs(A))
    orth = np.max(np.abs(Q.T @ Q - np.eye(n)))
    assert rec < 1e-4, rec
    assert orth < 2e-3, orth  # kappa-amplified one-shot BCGS (see above)
    # tail=0 disables fusing: one task per panel
    assert SegmentedQR(ctx, n, nb, strip=128, tail=0).nt_tasks == n // nb


def test_qr_bf16_modes_rejected(ctx):
    """The chol/LU bf16 levers are REJECTED for QR, loudly and with the
    measured rationale: one-shot BCGS amplifies any deflation-path error
    by kappa(A) (CGS loss-of-orthogonality), so both operand-cast
    deflation (orth 0.17 at n=256) and bf16 STORAGE between panels
    (orth 0.125, f32 arithmetic, numpy oracle) fail even a 1e-1 gate
    while f32 measures 3.4e-5 — and BCGS at nb>=512 is MXU-bound, so
    the bandwidth lever buys nothing.  A builder must refuse to ship a
    mode that fails its own gate."""
    n, nb = 256, 64
    for mode in (True, "storage"):
        with pytest.raises(ValueError, match="rejected"):
            SegmentedQR(ctx, n, nb, bf16=mode)


def test_lu_fused_f32_update(ctx):
    """Round-5 (VERDICT #5): the fused single-kernel Pallas 3-pass f32
    trailing update — split-bf16 cross terms accumulated in VMEM, HIGH
    semantics with one HBM round-trip — matches the plain f32 path's
    numerics class on both specializations."""
    n, nb = 256, 64
    rng = np.random.default_rng(12)
    Add = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
    for spec in ("generic", "static"):
        sl = SegmentedLU(ctx, n, nb, strip=128, tail=128, specialize=spec,
                         fused_update=True)
        L, U = sl(Add)
        rec = np.abs(
            L.astype(np.float64) @ U.astype(np.float64) - Add
        ).max() / np.abs(Add).max()
        assert rec < 1e-3, (spec, rec)
    # the lever is f32-only: bf16 modes already run one MXU pass
    with pytest.raises(ValueError, match="f32-path"):
        SegmentedLU(ctx, n, nb, bf16="storage", fused_update=True)
