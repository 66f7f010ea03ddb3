"""Plain reference of the 2D 5-point stencil configuration: exact values
on sampled windows, by the domain of dependence.

The grid is a function of position and seed alone,

    g[r, c] = u(r, c, seed) in [-1/2, 1/2), a 32-bit hash (24 bits, exact
    in f32; the finalizer of ``spotrf_hashed.py``, not symmetric here),

so any patch of it can be made without the whole.  ``problem["tiles"]``
hands out the grid as host tiles (built on the device a tile at a time
and brought home: 4.29 GB at n = 32768); the reference itself never
holds more than a patch.

After T sweeps of ``new = 0.25 * (up + down + left + right)`` with zeros
outside the grid, a w x w window is a function of the (w + 2T)^2 patch
around it, cut where the grid ends (there the zero boundary is exact;
where the cut is inside the grid the patch's rim goes wrong one point a
sweep and never reaches the window).  ``prepare`` sweeps each patch T
times in **float64 numpy** and keeps the window.  The windows, 16 x 16
each (cut at the grid's edge):

* one centred on every point where tile corners meet, the grid's own
  edge and corners included ((mt + 1) x (nt + 1) crossings: 49 inside, 28
  on an edge, 4 corners at 8 x 8 tiles): an inside crossing reads four
  tiles and every halo direction of each;
* one in the middle of every tile.

So every tile is read at five places: a tile stale by one generation
(the modes of this operator near -1 change sign every sweep and do not
decay), a halo wired to the wrong neighbour or a boundary treated as
periodic each miss a limit.  Two numbers:

``window_error``
    the largest over the windows of max |got - want| / max |want| in that
    window.
``edge_error``
    the same over the rows and columns of the windows that lie next to a
    tile edge alone (the points a halo feeds directly).

145 windows of at most 216^2 points for 100 sweeps: under a second.
Imports nothing of the program.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark.reference import spotrf_hashed as _hashed

_fmix32, seed_words, _CB = _hashed._fmix32, _hashed.seed_words, _hashed._CB

#: side of a window
WINDOW = 16


def uniform(r, c, s0, s1, xp=np):
    """u(r, c) in [-1/2, 1/2); ``r`` and ``c`` are uint32 arrays that
    broadcast, ``s0`` and ``s1`` the seed's two uint32 words."""
    h = _fmix32(r ^ s0, xp)
    h = _fmix32(h ^ (c * xp.uint32(_CB) + s1), xp)
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -24) \
        - xp.float32(0.5)


def patch(r0: int, r1: int, c0: int, c1: int, seed: int) -> np.ndarray:
    """g[r0:r1, c0:c1] in float64, from the hash alone."""
    r = np.arange(r0, r1, dtype=np.uint32)[:, None]
    c = np.arange(c0, c1, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        return uniform(r, c, *seed_words(seed)).astype(np.float64)


def sweep(g: np.ndarray, iters: int) -> np.ndarray:
    """``iters`` sweeps of the 5-point operator, zeros outside ``g``."""
    for _ in range(iters):
        pad = np.zeros((g.shape[0] + 2, g.shape[1] + 2), g.dtype)
        pad[1:-1, 1:-1] = g
        g = 0.25 * (pad[:-2, 1:-1] + pad[2:, 1:-1]
                    + pad[1:-1, :-2] + pad[1:-1, 2:])
    return g


def windows_of(m: int, n: int, mb: int, nb: int) -> List[Tuple[int, ...]]:
    """``(r0, r1, c0, c1)`` of every window of an ``m x n`` grid cut into
    ``mb x nb`` tiles: the crossings of tile edges, then the tile
    centres."""
    half = WINDOW // 2
    rows = [i * mb for i in range(m // mb + 1)]
    cols = [j * nb for j in range(n // nb + 1)]
    centres = [(r, c) for r in rows for c in cols]
    centres += [(r + mb // 2, c + nb // 2)
                for r in rows[:-1] for c in cols[:-1]]
    return [(max(0, r - half), min(m, r + half),
             max(0, c - half), min(n, c + half)) for r, c in centres]


def window_values(win, iters: int, m: int, n: int, seed: int) -> np.ndarray:
    """The exact window after ``iters`` sweeps, from its patch."""
    r0, r1, c0, c1 = win
    p0, p1 = max(0, r0 - iters), min(m, r1 + iters)
    q0, q1 = max(0, c0 - iters), min(n, c1 + iters)
    g = sweep(patch(p0, p1, q0, q1, seed), iters)
    return g[r0 - p0:r1 - p0, c0 - q0:c1 - q0]


def make_tiles(n: int, nb: int, seed: int, jdev) -> Dict[tuple, np.ndarray]:
    """The grid as host tiles, each built from the hash on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile(i0, j0, s0, s1):
        r = (i0 + jnp.arange(nb, dtype=jnp.uint32))[:, None]
        c = (j0 + jnp.arange(nb, dtype=jnp.uint32))[None, :]
        return uniform(r, c, s0, s1, jnp)

    words = [jax.device_put(w, jdev) for w in seed_words(seed)]
    nt = n // nb
    out = {}
    ahead = []
    for key in [(i, j) for i in range(nt) for j in range(nt)]:
        arr = tile(np.uint32(key[0] * nb), np.uint32(key[1] * nb), *words)
        arr.copy_to_host_async()
        ahead.append((key, arr))
        if len(ahead) > 4:
            k, a = ahead.pop(0)
            out[k] = np.asarray(a)
    for k, a in ahead:
        out[k] = np.asarray(a)
    return out


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb, iters = int(config["n"]), int(config["nb"]), int(config["iters"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    return {"seed": seed, "n": n, "nb": nb, "iters": iters,
            "tiles": make_tiles(n, nb, seed, devices[0])}


def prepare(problem: Dict[str, Any]) -> None:
    n, nb = problem["n"], problem["nb"]
    wins = windows_of(n, n, nb, nb)
    problem["windows"] = wins
    problem["want"] = [window_values(w, problem["iters"], n, n,
                                     problem["seed"]) for w in wins]


def gather(tiles, win, nb: int) -> np.ndarray:
    """The window ``win`` of the grid held as ``nb x nb`` tiles."""
    r0, r1, c0, c1 = win
    out = np.empty((r1 - r0, c1 - c0), np.float64)
    for i in range(r0 // nb, (r1 - 1) // nb + 1):
        for j in range(c0 // nb, (c1 - 1) // nb + 1):
            a0, a1 = max(r0, i * nb), min(r1, (i + 1) * nb)
            b0, b1 = max(c0, j * nb), min(c1, (j + 1) * nb)
            out[a0 - r0:a1 - r0, b0 - c0:b1 - c0] = \
                tiles[(i, j)][a0 - i * nb:a1 - i * nb, b0 - j * nb:b1 - j * nb]
    return out


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's grid after T sweeps, ``{(i, j): host
    tile}``."""
    bad = {"window_error": float("inf"), "edge_error": float("inf")}
    nb = problem["nb"]
    if set(tiles) != set(problem["tiles"]) or any(
            np.shape(t) != (nb, nb) for t in tiles.values()):
        return bad
    worst = edge = 0.0
    for win, want in zip(problem["windows"], problem["want"]):
        err = np.abs(gather(tiles, win, nb) - want)
        if not np.isfinite(err).all():
            return bad
        scale = np.abs(want).max()
        worst = max(worst, float(err.max() / scale))
        r0, r1, c0, c1 = win
        r = np.arange(r0, r1) % nb
        c = np.arange(c0, c1) % nb
        near = ((r == 0) | (r == nb - 1))[:, None] \
            | ((c == 0) | (c == nb - 1))[None, :]
        if near.any():
            edge = max(edge, float(err[near].max() / scale))
    return {"window_error": worst, "edge_error": edge}
