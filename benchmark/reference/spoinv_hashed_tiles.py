"""Plain reference of the tile SPD-inverse configuration: the closed form
of ``spotrf_hashed.py``, held as host tiles, and a residual against it.

The input is DPLASMA's ``dplghe`` matrix as ``reference/spotrf_hashed.py``
defines it (``u(i, j, seed)`` from the 32-bit hash, a bump of 0.75 sqrt(n)
on the diagonal: condition number about 8), cut into the lower tiles of
the tile algorithms: 300 tiles of 2048 x 2048 f32 at N = 49152, 5.03 GB,
built tile by tile on the device from the hash and held on the host ONCE
(``problem["tiles"]``; the driver copies them into each solve's matrix).

The reference is the closed form itself: no factorization, no inverse, no
second matrix.  A is never held whole: ``prepare`` builds the columns
``A[:, j]`` of the ``samples_per_tile_row`` sampled indices of every tile
row (drawn from the seed; the last index always among them) in float64
from the hash.  ``compare`` builds row i of W = A^-1 for the same indices
from the solve's HOME tiles by symmetry — tile row i_t up to the diagonal
tile (its lower triangle, mirrored), then tile column i_t below it — so
every tile of the result is read, and multiplies out in float64:

``inverse_residual``
    max over sampled i, j of |sum_k W[i, k] A[k, j] - [i == j]|.
``diag_residual``
    the same over i == j alone: the entries that carry the bump.

A tile left at the factor's or at ``trtri``'s version, a step skipped, a
version a step early: each moves whole rows of the product by the size of
an entry of the identity.  O(n * samples^2) a solve.  Imports nothing of
the program.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict

import numpy as np

from benchmark.reference import spotrf_hashed as _hashed
from benchmark.reference import spotrf_hashed_tiles as _tiles

uniform, seed_words = _hashed.uniform, _hashed.seed_words
sample_rows = _hashed.sample_rows

BAD = {"inverse_residual": float("inf"), "diag_residual": float("inf")}


def host_tiles(n: int, nb: int, bump: float, seed: int, jdev):
    """``{(i, j): host tile}`` for i >= j, held: each tile from the hash
    on the device (the out-of-core reference's generator, its copies
    home a few tiles ahead), kept as a plain array of its own (the
    generator's value is the reader's alone; where it is a view of the
    device's memory, the CPU backend's, it is copied)."""
    built = _tiles.HashedTiles(n, nb, bump, seed, jdev)
    return {key: t.view(np.ndarray) if t.flags.writeable else np.array(t)
            for key, t in built.items()}


def columns(rows, n: int, bump: float, seed: int) -> np.ndarray:
    """``A[:, rows]`` of the float32 matrix :func:`host_tiles` cuts, in
    float64, from the hash alone: the matrix that is inverted is the one
    the program was given, so a diagonal entry is the float32 sum of the
    hash's value and the shift."""
    r = np.asarray(rows, np.uint32)
    with np.errstate(over="ignore"):
        u = uniform(np.arange(n, dtype=np.uint32)[:, None], r[None, :],
                    *seed_words(seed))
    at = (np.asarray(rows), np.arange(len(rows)))
    u[at] += np.float32(bump * math.sqrt(n))
    return u.astype(np.float64)


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = int(config["n"]), int(config["nb"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    bump = float(config["bump"])
    nt = n // nb
    per = int(config.get("samples_per_tile_row", 4))
    rows = sample_rows(random.Random(seed), n, nb, per * nt)
    return {"seed": seed, "n": n, "nb": nb, "nt": nt, "bump": bump,
            "rows": rows,
            "tiles": host_tiles(n, nb, bump, seed, devices[0])}


def prepare(problem: Dict[str, Any]) -> None:
    """The sampled columns of A: the reference's own computation."""
    problem["cols"] = columns(problem["rows"], problem["n"],
                              problem["bump"], problem["seed"])


def inverse_rows(problem: Dict[str, Any], tiles) -> np.ndarray:
    """Rows ``problem["rows"]`` of the symmetric matrix whose lower tiles
    are ``tiles``, in float64."""
    n, nb, nt = problem["n"], problem["nb"], problem["nt"]
    w = np.zeros((len(problem["rows"]), n), np.float64)
    for a, r in enumerate(problem["rows"]):
        it, loc = divmod(int(r), nb)
        for jt in range(it):
            w[a, jt * nb:(jt + 1) * nb] = tiles[(it, jt)][loc]
        d = tiles[(it, it)]
        w[a, it * nb:it * nb + loc + 1] = d[loc, :loc + 1]
        w[a, it * nb + loc + 1:(it + 1) * nb] = d[loc + 1:, loc]
        for jt in range(it + 1, nt):
            w[a, jt * nb:(jt + 1) * nb] = tiles[(jt, it)][:, loc]
    return w


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's result, ``{(i, j): host tile}`` for i >= j:
    the lower tiles of A^-1."""
    if set(tiles) != set(problem["tiles"]):
        return dict(BAD)
    nb = problem["nb"]
    tiles = {k: np.asarray(t) for k, t in tiles.items()}
    if any(t.shape != (nb, nb) for t in tiles.values()):
        return dict(BAD)
    res = np.abs(inverse_rows(problem, tiles) @ problem["cols"]
                 - np.eye(len(problem["rows"])))
    return {"inverse_residual": float(res.max()),
            "diag_residual": float(np.diagonal(res).max())}
