"""Ex15 — the inverse of an SPD matrix: three taskpools composed.

LAPACK's ``dpotrf`` + ``dpotri`` and DPLASMA's ``dplasma_dpoinv_sync``:
``A = L L^T`` (``cholesky_ptg``), ``W = L^-1`` (``trtri_ptg``), ``A^-1 =
W^T W`` (``lauum_ptg``), each a tile algorithm of its own over the SAME
tiles.  ``ops.poinv(A)`` composes the three pools
(``core.compound.compose``: member i+1 starts when member i has ended),
and ``NativeExecutor(poinv(A), native_device=True)`` runs the compound on
the pump path under ONE device residency: the matrix goes onto the device
once, stays there from pool to pool, and comes home once.

Run it on the CPU backend with ``JAX_PLATFORMS=cpu``.
"""

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), ".."))  # run without install

import numpy as np

from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl.native_exec import NativeExecutor
from parsec_tpu.ops import poinv


def main(n: int = 192, nb: int = 32, seed: int = 0) -> float:
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    spd = m @ m.T / n + np.eye(n)

    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32, uplo="lower")
    A.from_array(spd.astype(np.float32))
    lower_bytes = sum(4 * int(np.prod(A.tile_shape(*k))) for k in A.tiles())

    ex = NativeExecutor(poinv(A, use_cpu=False), native_device=True)
    dev = ex.device
    ran = ex.run()
    ex.close()                      # the one flush: A^-1's lower tiles

    low = np.tril(A.to_array()).astype(np.float64)
    inv = low + np.tril(low, -1).T  # the lower triangle is the result
    residual = float(np.abs(inv @ spd - np.eye(n)).max())

    s = ex.stats
    print(f"poinv: {ran} tasks in {s['members_run']} pools, "
          f"{dev.stats['bytes_in']} bytes in and {dev.stats['bytes_out']} "
          f"home for a lower matrix of {lower_bytes}; kept on the device "
          f"between pools: {s['member_kept_tiles']} tiles")
    print(f"max |A^-1 A - I| = {residual:.2e}")
    assert s["members_run"] == 3
    assert dev.stats["bytes_in"] == dev.stats["bytes_out"] == lower_bytes
    assert s["member_home_bytes"] == s["member_restaged_tiles"] == 0
    assert residual < 1e-4, residual
    return residual


if __name__ == "__main__":
    main()
