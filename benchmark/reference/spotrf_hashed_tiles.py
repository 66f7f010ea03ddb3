"""Plain reference of the out-of-core tile spotrf configuration: the
closed form of ``spotrf_hashed.py``, delivered as host tiles.

The input is DPLASMA's ``dplghe`` matrix as ``reference/spotrf_hashed.py``
defines it (``u(i, j, seed)`` from the 32-bit hash, a bump of 0.75 sqrt(n)
on the diagonal), cut into the lower tiles of a tile Cholesky: 990 tiles
of 2048 x 2048 f32 at N = 90112, 16.61 GB.  The matrix is LARGER than the
accelerator's memory, so nothing here ever holds it twice:

* ``problem["tiles"]`` is a mapping that BUILDS a tile from the hash when
  it is read (one jitted generator on the device, its copies home started
  a few tiles ahead of the reader).  The driver copies every tile of it
  into the solve's matrix (``_common.fresh_matrix``), so a resident
  ``dict`` of tiles would be a second 16.6 GB beside the solve's own; the
  mapping costs a solve ~16.6 GB over the link OUTSIDE its reading, as
  loading the input costs a user.  A tile that ``items()`` built belongs
  to its reader alone, so its ``copy()`` hands it over as it is
  (:class:`_Own`): the driver's copy protects tiles that must outlive the
  solve, and a second 16 MiB memcpy a tile is 1.5 s of a cycle that has
  to fit a 51 s window three times.
* the reference is the closed form itself: no factorization, no second
  matrix.  ``samples_per_tile_row`` rows of every tile row of the solve's
  factor (drawn from the seed; the last row always among them) are
  multiplied out in float64 on the host and compared with the closed form
  at those rows and columns.  ``rec[r, r']`` sums over every column of L up
  to ``min(r, r')``, so every tile (i, k) of the factor is read through the
  sampled rows of tile row i: ONE tile that came home at an intermediate
  version (an eviction's write-back never superseded) moves every entry of
  its rows by the updates it misses.  O(n * samples^2) a solve.

Two numbers, as the panel configuration defines them:

``diagonal_error``
    max |rec[r, r] - A[r, r]| / A[r, r].
``offdiag_error``
    max over r != r' of |rec[r, r'] - A[r, r']| / sqrt(1/12), the rms of
    an off-diagonal entry.

``prepare`` and ``compare`` log the process's peak resident set beside the
host's ``MemTotal`` (a ``[bench]`` line): the deployment's host memory is
part of what it costs.  Imports nothing of the program.
"""

from __future__ import annotations

import collections
import collections.abc
import math
import random
import resource
from typing import Any, Dict

import numpy as np

from benchmark.reference import spotrf_hashed as _hashed

uniform, closed_form = _hashed.uniform, _hashed.closed_form
seed_words, sample_rows = _hashed.seed_words, _hashed.sample_rows
OFFDIAG_RMS = _hashed.OFFDIAG_RMS

#: tiles whose copy home is started ahead of the reader
_AHEAD = 8


class _Own(np.ndarray):
    """A host tile that nobody but its reader holds: ``copy()`` is the
    tile itself, as a plain array."""

    def copy(self, order="C"):
        return self.view(np.ndarray)


def _own(host: np.ndarray) -> np.ndarray:
    """The generator's host value handed to the reader as its own.  Where
    numpy will not make it writable (the CPU backend's host value is a
    view of the device's memory) it stays what it was: read-only, for the
    reader to copy."""
    try:
        host.flags.writeable = True
    except ValueError:
        return host
    return host.view(_Own)


class HashedTiles(collections.abc.Mapping):
    """``{(i, j): host tile}`` for i >= j, each tile built from the hash
    on the device when it is read and never kept."""

    def __init__(self, n: int, nb: int, bump: float, seed: int, jdev):
        import jax
        import jax.numpy as jnp

        self.nt = n // nb
        self._keys = [(i, j) for i in range(self.nt) for j in range(i + 1)]
        self._known = frozenset(self._keys)
        shift = np.float32(bump * math.sqrt(n))

        @jax.jit
        def tile(i0, j0, s0, s1):
            r = (i0 + jnp.arange(nb, dtype=jnp.uint32))[:, None]
            c = (j0 + jnp.arange(nb, dtype=jnp.uint32))[None, :]
            return uniform(r, c, s0, s1, jnp) \
                + shift * (r == c).astype(jnp.float32)

        words = [jax.device_put(w, jdev) for w in seed_words(seed)]

        def start(key):
            i, j = key
            arr = tile(np.uint32(i * nb), np.uint32(j * nb), *words)
            arr.copy_to_host_async()
            return arr

        self._start = start

    def __len__(self) -> int:
        return len(self._keys)

    def __iter__(self):
        return iter(self._keys)

    def __getitem__(self, key):
        if key not in self._known:
            raise KeyError(key)
        return np.asarray(self._start(key))

    def items(self):
        """``(key, tile)`` in key order, ``_AHEAD`` tiles in flight: a
        tile is the generator's own host value, the reader's alone
        (:func:`_own`), and is gone when the reader lets go of it."""
        ahead: collections.deque = collections.deque()
        for key in self._keys:
            ahead.append((key, self._start(key)))
            if len(ahead) > _AHEAD:
                k, arr = ahead.popleft()
                yield k, _own(np.asarray(arr))
        while ahead:
            k, arr = ahead.popleft()
            yield k, _own(np.asarray(arr))


def host_memory() -> Dict[str, float]:
    """The process's peak resident set and the host's memory, in GB."""
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    total = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    total = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return {"peak_rss_gb": peak / 1e9, "mem_total_gb": total / 1e9}


def log_host_memory(problem: Dict[str, Any], where: str) -> None:
    """One ``[bench]`` line when the peak has grown by 1% since the last."""
    m = host_memory()
    if m["peak_rss_gb"] <= 1.01 * problem.get("logged_rss_gb", 0.0):
        return
    problem["logged_rss_gb"] = m["peak_rss_gb"]
    share = (100.0 * m["peak_rss_gb"] / m["mem_total_gb"]
             if m["mem_total_gb"] else float("nan"))
    print(f"[bench] host memory at {where}: peak RSS "
          f"{m['peak_rss_gb']:.3f} GB of MemTotal "
          f"{m['mem_total_gb']:.3f} GB ({share:.1f}%)", flush=True)


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = int(config["n"]), int(config["nb"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    bump = float(config["bump"])
    nt = n // nb
    per = int(config.get("samples_per_tile_row", 4))
    rows = sample_rows(random.Random(seed), n, nb, per * nt)
    tiles = HashedTiles(n, nb, bump, seed, devices[0])
    tiles[(0, 0)]  # the generator compiles in set-up
    return {"seed": seed, "n": n, "nb": nb, "nt": nt, "rows": rows,
            "tiles": tiles, "want": closed_form(rows, n, bump, seed)}


def prepare(problem: Dict[str, Any]) -> None:
    """The closed form needs no factorization."""
    log_host_memory(problem, "prepare")


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's factor, ``{(i, j): host tile}`` for i >= j."""
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
