"""Plain reference of the panel spotrf configuration: a closed form.

The input is DPLASMA's own test matrix (``dplghe``: a symmetric matrix of
uniform entries, each a function of its position and the seed, plus a bump
on the diagonal), with a smaller bump so that it is dense in every sense,

    A[i, j] = u(min(i,j), max(i,j), seed) + bump * sqrt(n) * [i == j],
    u uniform in [-1/2, 1/2), a 32-bit hash of the position and the seed.

Its off-diagonal part has the spectrum of a Wigner matrix, within
+-sqrt(n/3) ~ 0.577 sqrt(n), so ``bump`` = 0.75 makes it positive definite
with a condition number of about 8, while a row's off-diagonal weight
(sqrt(n/12)) stays comparable with its diagonal: no entry of the factor is
negligible, and every trailing-update block of every panel step moves
entries of the result by ~5% of an entry's size.  The matrix is built strip
by strip ON the device, so that 4 GiB never cross the host.

The reference is the closed form itself, computed here in numpy from the
same hash.  ``samples`` rows of the solve's factor, spread evenly over the
tile rows and drawn from the seed (the last row always among them), are
multiplied out at HIGHEST precision and compared with the closed form at
those rows and columns.  rec[r, r'] sums over every column of L up to
min(r, r'), so each block (i, k) of the factor is read through the sampled
rows of tile row i.  Two numbers:

``diagonal_error``
    max |rec[r, r] - A[r, r]| / A[r, r].  The diagonal carries the bump;
    this is the number a lower storage precision cannot meet.
``offdiag_error``
    max over r != r' of |rec[r, r'] - A[r, r']| / sqrt(1/12), the rms of
    an off-diagonal entry.  This is the number a trailing update that is
    dropped, truncated or cut to a band cannot meet.

O(n * samples) per solve and no second n x n buffer.  Imports nothing of
the program.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict

import numpy as np

_C1, _C2, _CB = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B1
OFFDIAG_RMS = math.sqrt(1.0 / 12.0)


def _fmix32(x, xp):
    """murmur3's 32-bit finalizer, on uint32 arrays of numpy or jax.numpy."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(_C1)
    x = x ^ (x >> 13)
    x = x * xp.uint32(_C2)
    return x ^ (x >> 16)


def seed_words(seed: int):
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def uniform(i, j, s0, s1, xp=np):
    """u(i, j) in [-1/2, 1/2), symmetric in (i, j); 24 bits, exact in f32.
    ``i`` and ``j`` are uint32 arrays that broadcast, ``s0`` and ``s1`` the
    seed's two uint32 words."""
    lo, hi = xp.minimum(i, j), xp.maximum(i, j)
    h = _fmix32(lo ^ s0, xp)
    h = _fmix32(h ^ (hi * xp.uint32(_CB) + s1), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -24) \
        - xp.float32(0.5)


def closed_form(rows, n: int, bump: float, seed: int) -> np.ndarray:
    """A[rows][:, rows] in float64, from the hash alone."""
    r = np.asarray(rows, np.uint32)
    with np.errstate(over="ignore"):
        a = uniform(r[:, None], r[None, :],
                    *seed_words(seed)).astype(np.float64)
    return a + np.diag(np.full(len(r), bump * math.sqrt(n)))


def sample_rows(rng: random.Random, n: int, nb: int, samples: int):
    """Evenly many rows of every tile row, the last row among them; as
    many for every seed, so that one compiled gate serves them all."""
    nt = n // nb
    per = max(1, min(nb, samples // nt))
    rows = [t * nb + r for t in range(nt)
            for r in rng.sample(range(nb), per)]
    if n - 1 not in rows:
        rows[-1] = n - 1
    return sorted(rows)


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    n, nb = int(config["n"]), int(config["nb"])
    bump = float(config["bump"])
    blk = min(2048, n)
    jdev = devices[0]
    rows = sample_rows(random.Random(seed), n, nb,
                       int(config.get("samples", 256)))
    want = closed_form(rows, n, bump, seed)
    shift = np.float32(bump * math.sqrt(n))

    @jax.jit
    def make(s0, s1):
        def strip(s, A):
            r = (s * blk + jnp.arange(blk, dtype=jnp.int32)
                 ).astype(jnp.uint32)[:, None]
            c = jnp.arange(n, dtype=jnp.uint32)[None, :]
            return lax.dynamic_update_slice(
                A, uniform(r, c, s0, s1, jnp), (s * blk, 0))

        A = lax.fori_loop(0, n // blk, strip,
                          jnp.zeros((n, n), jnp.float32))
        d = jnp.arange(n, dtype=jnp.int32)
        return A.at[d, d].add(shift)

    @jax.jit
    def gate(L, idx, want):
        rows = L[idx, :].astype(jnp.float32)
        rows = rows * (jnp.arange(n, dtype=jnp.int32)[None, :]
                       <= idx[:, None])
        rec = jnp.matmul(rows, rows.T, precision=lax.Precision.HIGHEST)
        err = jnp.abs(rec - want)
        diag = jnp.diagonal(err) / jnp.diagonal(want)
        off = err * (1.0 - jnp.eye(len(idx), dtype=jnp.float32))
        return diag.max(), off.max() / OFFDIAG_RMS

    with jax.default_device(jdev):
        idx = jax.device_put(jnp.asarray(rows, jnp.int32), jdev)
        want_dev = jax.device_put(jnp.asarray(want, jnp.float32), jdev)
        words = [jax.device_put(w, jdev) for w in seed_words(seed)]
        make(*words).block_until_ready()  # the generator compiles in set-up
    return {"seed": seed, "n": n, "rows": rows, "want": want,
            "make": lambda: make(*words).block_until_ready(),
            "gate": lambda L: [float(e) for e in gate(L, idx, want_dev)]}


def prepare(problem: Dict[str, Any]) -> None:
    """Compile the gate; the closed form needs no factorization."""
    problem["gate"](problem["make"]())


def compare(problem: Dict[str, Any], L) -> Dict[str, float]:
    n = problem["n"]
    if getattr(L, "shape", None) != (n, n):
        return {"diagonal_error": float("inf"),
                "offdiag_error": float("inf")}
    diagonal, offdiag = problem["gate"](L)
    return {"diagonal_error": diagonal, "offdiag_error": offdiag}
