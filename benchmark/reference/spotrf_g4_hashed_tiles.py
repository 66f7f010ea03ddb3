"""Plain reference of the four-accelerator tile spotrf configuration
(``spotrf_tile_nb4096_g4``): the closed form of ``spotrf_hashed.py`` over
host tiles of 64 MiB.

The input is DPLASMA's ``dplghe`` matrix as ``reference/spotrf_hashed.py``
defines it (``u(i, j, seed)`` from the 32-bit hash, a bump of 0.75 sqrt(n)
on the diagonal), cut into the lower tiles of a tile Cholesky: 300 tiles
of 4096 x 4096 f32 at N = 98304, 20.13 GB.  A user of this deployment
has the matrix in host memory before the factorization and has it there
again after it, so ``problem["tiles"]`` is a plain ``dict`` of host
arrays, made ONCE in set-up by ``spotrf_hashed_tiles.py``'s generator (a
jitted hash on the first of the cell's chips, the copies home a few tiles
ahead) and read by every solve of the run: the driver copies a tile into
the solve's own matrix, outside the reading, as a user's loader would fill
it.  Held once and not built anew for every solve, as the out-of-core
configuration's tiles are, because 20 GB of fresh 64 MiB host buffers a
solve, beside the 20 GB of fresh landing buffers of its result, is more
than the benchmark's machine takes back in a cycle (``PERF.md`` §6, PR
51: the machine's account of a run grew by ~20 GB a solve over the
process's own, and one run met the machine's limit).

The reference is the closed form itself: no factorization, no second
matrix.  ``samples_per_tile_row`` (4) rows of each of the 24 tile rows of
the solve's factor (drawn from the seed; the last row always among them)
are multiplied out in float64 on the host and compared with the closed
form at those rows and columns.  ``rec[r, r']`` sums over every column of
L up to ``min(r, r')``, so every tile (i, k) of the factor is read through
the sampled rows of tile row i, whichever chip bore it: ONE tile that came
home at an intermediate version (a stale peer copy read in place of the
newest, a version sent home that a later task of another chip overwrote)
moves every entry of its rows by the updates it misses.

``diagonal_error``
    max |rec[r, r] - A[r, r]| / A[r, r].
``offdiag_error``
    max over r != r' of |rec[r, r'] - A[r, r']| / sqrt(1/12), the rms of
    an off-diagonal entry.

``prepare`` and ``compare`` log the process's peak resident set beside the
host's ``MemTotal`` (a ``[bench]`` line).  Imports nothing of the program.
"""

from __future__ import annotations

import random
from typing import Any, Dict

import numpy as np

from benchmark.reference import spotrf_hashed_tiles as _tiles

closed_form, sample_rows = _tiles.closed_form, _tiles.sample_rows
OFFDIAG_RMS = _tiles.OFFDIAG_RMS
log_host_memory = _tiles.log_host_memory


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = int(config["n"]), int(config["nb"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    bump = float(config["bump"])
    nt = n // nb
    per = int(config.get("samples_per_tile_row", 4))
    rows = sample_rows(random.Random(seed), n, nb, per * nt)
    made = _tiles.HashedTiles(n, nb, bump, seed, devices[0])
    # plain arrays that own their memory: ``copy()`` is a copy
    tiles = {k: np.array(t, copy=not t.flags.writeable).view(np.ndarray)
             for k, t in made.items()}
    return {"seed": seed, "n": n, "nb": nb, "nt": nt, "rows": rows,
            "tiles": tiles, "want": closed_form(rows, n, bump, seed)}


def prepare(problem: Dict[str, Any]) -> None:
    """The closed form needs no factorization."""
    log_host_memory(problem, "prepare")


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's factor, ``{(i, j): host tile}`` for i >= j,
    gathered from the four modules' ways home."""
    bad = {"diagonal_error": float("inf"), "offdiag_error": float("inf")}
    if set(tiles) != set(problem["tiles"]):
        return bad
    n, nb = problem["n"], problem["nb"]
    rows = np.zeros((len(problem["rows"]), n), np.float64)
    for a, r in enumerate(problem["rows"]):
        i, local = divmod(int(r), nb)
        for j in range(i + 1):
            t = np.asarray(tiles[(i, j)])
            if t.shape != (nb, nb):
                return bad
            rows[a, j * nb:(j + 1) * nb] = t[local]
        rows[a, r + 1:] = 0.0  # the factor is lower-triangular
    rec = rows @ rows.T
    err = np.abs(rec - problem["want"])
    diag = np.diagonal(err) / np.diagonal(problem["want"])
    off = err - np.diag(np.diagonal(err))
    log_host_memory(problem, "compare")
    return {"diagonal_error": float(diag.max()),
            "offdiag_error": float(off.max()) / OFFDIAG_RMS}
