"""Tile Cholesky by task insertion: DPLASMA's ``testing_dpotrf_dtd.c``
(the calling sequence of the benchmark's ``dtd_potrf_nb1024`` cell, small).
The runtime is told nothing of the graph; a tile comes home at its flush.
"""

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "..", ".."))  # run without install

import numpy as np

from parsec_tpu import Context
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl import DTDTaskpool
from parsec_tpu.ops import cholesky_dtd


def main(n: int = 256, nb: int = 32) -> None:
    a = np.random.default_rng(0).random((n, n), dtype=np.float32) - 0.5
    spd = (a + a.T) / 2 + np.float32(0.75 * n ** 0.5) * np.eye(n, dtype=np.float32)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(spd.copy())
    with Context(nb_cores=4) as ctx:
        tp = DTDTaskpool(ctx)
        ntasks = cholesky_dtd(tp, A)        # insert_task, one a task
        assert tp.wait(timeout=120)         # the factor is on the device
        tp.flush_all(A)                     # ... and now in host tiles
        tp.close()
    L = np.tril(A.to_array()).astype(np.float64)
    err = np.abs(L @ L.T - spd).max()
    assert err < 1e-4, err
    print(f"dtd_potrf: {ntasks} tasks inserted, |L L^T - A| = {err:.2e}")


if __name__ == "__main__":
    main()
