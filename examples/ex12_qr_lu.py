"""Ex12 — the QR and LU flagship taskpools.

Same PTG machinery as the dpotrf tour (ex08/ex11), two more dense
factorizations: tiled Householder QR (dense Q blocks on NEW flows — on
TPU this beats XLA's monolithic `jnp.linalg.qr` by >100x because
Householder chains are scalar-bound while tile updates are MXU matmuls)
and no-pivot LU for diagonally dominant systems (DPLASMA getrf_nopiv
analog).
"""

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))  # run without install

import numpy as np

from parsec_tpu import Context
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.ops import QRTree, run_lu, run_qr

N, NB = 128, 32


def main() -> None:
    rng = np.random.default_rng(0)

    with Context(nb_cores=4) as ctx:
        # QR: R^T R == A^T A proves the factorization without tracking Q
        A0 = rng.standard_normal((N, N))
        A = TiledMatrix(N, N, NB, NB, name="A", dtype=np.float64).from_array(A0)
        run_qr(ctx, A, use_tpu=False)
        R = A.to_array()
        resid = np.abs(R.T @ R - A0.T @ A0).max() / np.abs(A0.T @ A0).max()
        print(f"qr: {A.mt}x{A.nt} tiles, A^T A vs R^T R rel residual {resid:.2e}")
        assert resid < 1e-10

        # a tall matrix over a reduction tree: TS domains of 4 tile rows
        # under a binary TT tree (DPLASMA's hierarchical QR)
        T0 = rng.standard_normal((16 * NB, 2 * NB))
        T = TiledMatrix(16 * NB, 2 * NB, NB, NB, name="A",
                        dtype=np.float64).from_array(T0)
        run_qr(ctx, T, tree=QRTree(T.mt, T.nt, 4), use_tpu=False)
        R = T.to_array()[:2 * NB]
        resid = np.abs(R.T @ R - T0.T @ T0).max() / np.abs(T0.T @ T0).max()
        print(f"hqr: {T.mt}x{T.nt} tiles, A^T A vs R^T R rel residual {resid:.2e}")
        assert resid < 1e-10

        # LU (no pivoting, diagonally dominant): L @ U reconstructs A
        B0 = rng.standard_normal((N, N)) + N * np.eye(N)
        B = TiledMatrix(N, N, NB, NB, name="A", dtype=np.float64).from_array(B0)
        run_lu(ctx, B, use_tpu=False)
        packed = B.to_array()
        L = np.tril(packed, -1) + np.eye(N)
        U = np.triu(packed)
        resid = np.abs(L @ U - B0).max() / np.abs(B0).max()
        print(f"lu: L@U reconstruction rel residual {resid:.2e}")
        assert resid < 1e-12


if __name__ == "__main__":
    main()
