"""Plain reference of the DTD tile spotrf configuration: the closed form
of ``spotrf_hashed.py`` over HOST tiles that stay.

A user of ``insert_task`` has the matrix in host memory before the first
task is inserted, and has it there again when the last tile is flushed.
So the input is DPLASMA's ``dplghe`` matrix as ``spotrf_hashed.py``
defines it, cut into the lower tiles of a tile Cholesky (820 tiles of
1024 x 1024 f32 at N = 40960: 3.44 GB) and held on the host ONCE:
``problem["tiles"]`` is a plain ``dict`` of arrays, made in set-up by
the generator of ``spotrf_hashed_tiles.py`` (a jitted hash on the device,
the copies home a few tiles ahead) and read by every solve of the run;
the driver copies a tile into the solve's own matrix, as a user's loader
would fill it.  The matrix fits the accelerator three times over, so
nothing here has to be built when it is read, as the out-of-core
configuration's tiles are.

The check is ``spotrf_hashed_tiles.py``'s, on the factor's tiles as the
flush brought them home: ``samples_per_tile_row`` rows of every tile row
multiplied out in float64 against the closed form (every tile of the
factor is read through the sampled rows of its tile row), and one number
more, for a discovered graph can lose a task where an enumerated one
cannot:

``diagonal_error``, ``offdiag_error``
    as ``spotrf_hashed_tiles.py`` defines them.  A dependency the
    inference missed (a writer announced as a reader), a tile flushed a
    version early, a task left out: each leaves a tile without at least
    one update, which moves every entry of its rows by ~5% of an entry's
    size.
``unwritten_tiles``
    the tiles of the factor whose sampled rows are, bit for bit, the
    INPUT's: nothing ever wrote them (a panel solve that was never
    inserted, a flush that brought home the tile the user handed in).
    0 on a sound solve: every tile of a Cholesky factor is written at
    least once, by its ``potrf`` or its ``trsm``.

Imports nothing of the program.
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
    as the flush left it in the user's matrix."""
    given = problem["tiles"]
    bad = {"diagonal_error": float("inf"), "offdiag_error": float("inf"),
           "unwritten_tiles": float(len(given))}
    if set(tiles) != set(given):
        return bad
    n, nb = problem["n"], problem["nb"]
    rows = np.zeros((len(problem["rows"]), n), np.float64)
    #: tiles with a sampled row that differs from the input's
    written = set()
    for a, r in enumerate(problem["rows"]):
        i, local = divmod(int(r), nb)
        for j in range(i + 1):
            t = np.asarray(tiles[(i, j)])
            if t.shape != (nb, nb):
                return bad
            rows[a, j * nb:(j + 1) * nb] = t[local]
            if (i, j) not in written \
                    and not np.array_equal(t[local], given[(i, j)][local]):
                written.add((i, j))
        rows[a, r + 1:] = 0.0  # the factor is lower-triangular
    rec = rows @ rows.T
    err = np.abs(rec - problem["want"])
    diag = np.diagonal(err) / np.diagonal(problem["want"])
    off = err - np.diag(np.diagonal(err))
    log_host_memory(problem, "compare")
    return {"diagonal_error": float(diag.max()),
            "offdiag_error": float(off.max()) / OFFDIAG_RMS,
            "unwritten_tiles": float(len(given) - len(written))}
