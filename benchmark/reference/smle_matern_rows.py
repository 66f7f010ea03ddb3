"""Plain reference of the mixed-precision Matérn likelihood configuration:
the covariance formula itself, in float64 numpy, against sampled rows of
the factor the timed solve left on the device.

**The problem** (``make_problem``, all from ``--seed``).  Locations as
ExaGeoStat's synthetic data sets draw them: a ``s x s`` grid of the unit
square, ``s = ceil(sqrt(N))``, each point moved by ``U(-0.4, 0.4)`` of a
grid step in both directions, sorted by the Morton code of its
coordinates (16 bits each).  Where N is no square the ``s^2 - N`` points
left over are dropped at random (assumed: the source's sets are squares).
The observations ``z`` are a field drawn at ``theta_0`` by random Fourier
features from the Matérn spectral density (nu = 1/2 in two dimensions:
frequencies ``g / (beta |h|)``, g and h standard normal); the source
draws ``z = L e`` with the factor of ``Sigma(theta_0)``, which at N =
90112 is the computation under test.  ``problem["theta"](i)`` is the
theta of the session's i-th solve: ``(sigma^2_0 (1 + a_i), beta_0 (1 +
b_i), 1/2)``, a_i and b_i uniform in +-``theta_step`` from (seed, i): an
optimizer's steps around theta_0, no two alike.

**The check** (``compare``) reads what the solve produced: ``R`` rows of
every tile row of the factor, gathered by the driver from the resident
tiles (``problem["local_rows"]``), the factor's diagonal, ``y`` and the
two reductions; at the solve's own theta.  The sampled rows hold, for
every tile row: a row drawn at random, the tile's last row, and the two
ends of the pair of grid neighbours that lies FARTHEST apart in Morton
order among the pairs with an end in this tile row — two points a grid
step apart whose covariance (0.97 sigma^2 at beta = 0.1) sits in a tile
far below the diagonal, where the precision map stores bfloat16.  With
``rec = L[S, :] L[S, :]^T`` and ``want = Sigma(theta)[S, S]`` in float64:

``diagonal_error``
    max |rec[r, r] - want[r, r]| / want[r, r].
``offdiag_error``
    max |rec[r, r'] - want[r, r']| / sigma^2 over the pairs r != r' whose
    tile the map stores in float32 (inside one tile row, inside the band).
``offdiag_lo_error``
    the same over the pairs whose tile the map stores in bfloat16: what a
    tile that is wrong altogether (transposed, stale, its bits read as
    another precision's) misses.  The configuration's all-bfloat16 control
    is not meant to miss it: it is that precision's own class.
``solve_residual``
    ||L[S, :] y - z[S]|| / ||z[S]||.
``logdet_error``
    the program's ``logdet`` against ``2 sum log`` of the factor's
    diagonal in float64, relative.
``nonfinite_values``
    how many of ``logdet``, ``dot`` and the entries of ``y`` are not
    finite (a non-finite likelihood fails the solve).

Imports nothing of the program.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

#: rows gathered from every tile (padded by repeating the last)
ROWS_PER_TILE = 8
#: random Fourier features of the observations' field
FEATURES = 2048


def _spread16(v: np.ndarray) -> np.ndarray:
    v = v.astype(np.uint64)
    v = (v | (v << np.uint64(8))) & np.uint64(0x00FF00FF)
    v = (v | (v << np.uint64(4))) & np.uint64(0x0F0F0F0F)
    v = (v | (v << np.uint64(2))) & np.uint64(0x33333333)
    v = (v | (v << np.uint64(1))) & np.uint64(0x55555555)
    return v


def morton(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The Morton code of points of the unit square, 16 bits a
    coordinate."""
    ix = np.clip(x * 65536.0, 0, 65535).astype(np.uint64)
    iy = np.clip(y * 65536.0, 0, 65535).astype(np.uint64)
    return _spread16(ix) | (_spread16(iy) << np.uint64(1))


def locations(n: int, rng: np.random.Generator):
    """``(x, grid)``: the n locations in Morton order (float32, n x 2)
    and each one's grid cell (n x 2 integers)."""
    s = int(math.ceil(math.sqrt(n)))
    cells = np.stack(np.meshgrid(np.arange(s), np.arange(s),
                                 indexing="ij"), -1).reshape(-1, 2)
    keep = np.sort(rng.permutation(s * s)[:n])
    cells = cells[keep]
    xy = (cells + 0.5 + rng.uniform(-0.4, 0.4, cells.shape)) / s
    order = np.argsort(morton(xy[:, 0], xy[:, 1]), kind="stable")
    return xy[order].astype(np.float32), cells[order]


def field(x: np.ndarray, sigma2: float, beta: float,
          rng: np.random.Generator) -> np.ndarray:
    """Observations at ``x``: a zero-mean field whose covariance is the
    exponential one at (sigma2, beta) up to the features' sampling."""
    g = rng.standard_normal((FEATURES, 2))
    h = rng.standard_normal((FEATURES, 1))
    w = g / (beta * np.abs(h))
    phase = rng.uniform(0.0, 2.0 * math.pi, FEATURES)
    z = np.zeros(len(x), np.float64)
    xs = x.astype(np.float64)
    for a in range(0, len(x), 8192):
        z[a:a + 8192] = np.cos(xs[a:a + 8192] @ w.T + phase).sum(1)
    return (math.sqrt(2.0 * sigma2 / FEATURES) * z).astype(np.float32)


def covariance(xa: np.ndarray, xb: np.ndarray, theta) -> np.ndarray:
    """The Matérn covariance at nu = 1/2, from the formula, in float64."""
    d = np.sqrt(((xa[:, None, :].astype(np.float64)
                  - xb[None, :, :].astype(np.float64)) ** 2).sum(-1))
    return float(theta[0]) * np.exp(-d / float(theta[1]))


def far_neighbours(cells: np.ndarray, nb: int) -> Dict[int, tuple]:
    """For every tile row the pair of grid neighbours ``(r, r')`` with an
    end in it that lies farthest apart in tile rows."""
    s = int(cells.max()) + 1
    at = -np.ones((s + 1, s + 1), np.int64)
    at[cells[:, 0], cells[:, 1]] = np.arange(len(cells))
    rows = np.arange(len(cells))
    best: Dict[int, tuple] = {}
    for dx, dy in ((1, 0), (0, 1)):
        other = at[cells[:, 0] + dx, cells[:, 1] + dy]
        ok = other >= 0
        r, o = rows[ok], other[ok]
        apart = np.abs(r // nb - o // nb)
        for ends in ((r, o), (o, r)):
            tile = ends[0] // nb
            # the farthest pair of each tile row: sort, keep the last
            order = np.lexsort((apart, tile))
            last = np.r_[tile[order][1:] != tile[order][:-1], True]
            for k in order[last]:
                i = int(tile[k])
                if i not in best or apart[k] > best[i][0]:
                    best[i] = (int(apart[k]), int(ends[0][k]),
                               int(ends[1][k]))
    return {i: (a, b) for i, (_d, a, b) in best.items()}


def sample_rows(cells: np.ndarray, n: int, nb: int,
                rng: np.random.Generator) -> np.ndarray:
    """The sampled rows, sorted: per tile row one at random, its last,
    and both ends of its farthest pair of grid neighbours; at most
    ``ROWS_PER_TILE`` a tile row."""
    nt = n // nb
    per = {i: {i * nb + int(rng.integers(nb)), (i + 1) * nb - 1}
           for i in range(nt)}
    for i, (a, b) in sorted(far_neighbours(cells, nb).items()):
        for r in (a, b):
            if len(per[r // nb]) < ROWS_PER_TILE:
                per[r // nb].add(r)
    return np.array(sorted(r for rows in per.values() for r in rows),
                    np.int64)


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = int(config["n"]), int(config["nb"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    sigma2, beta, nu = (float(v) for v in config["theta"])
    if nu != 0.5:
        raise ValueError(f"nu = {nu}: the reference is the closed form of "
                         "nu = 1/2")
    step = float(config["theta_step"])
    rng = np.random.default_rng([seed, 0])
    x, cells = locations(n, rng)
    z = field(x, sigma2, beta, rng)
    rows = sample_rows(cells, n, nb, rng)
    local = {}
    for i in range(n // nb):
        mine = rows[rows // nb == i] - i * nb
        local[i] = np.r_[mine, np.full(ROWS_PER_TILE - len(mine),
                                       mine[-1])].astype(np.int32)

    def theta(i: int):
        a, b = np.random.default_rng([seed, 1, i]).uniform(-step, step, 2)
        return (sigma2 * (1.0 + a), beta * (1.0 + b), nu)

    return {"seed": seed, "n": n, "nb": nb, "nt": n // nb, "x": x, "z": z,
            "rows": rows, "local_rows": local, "theta": theta,
            "band_f32": int(config["band_f32"])}


def prepare(problem: Dict[str, Any]) -> None:
    """What of the check does not depend on a solve's theta: which of the
    sampled pairs lie in a float32 tile of the map."""
    tile = problem["rows"] // problem["nb"]
    problem["f32_pair"] = (np.abs(tile[:, None] - tile[None, :])
                           < problem["band_f32"])


def factor_rows(problem: Dict[str, Any], tiles) -> np.ndarray:
    """The sampled rows of the factor, dense, from ``{(i, j): the
    ROWS_PER_TILE gathered rows of tile (i, j)}``."""
    n, nb = problem["n"], problem["nb"]
    rows = problem["rows"]
    out = np.zeros((len(rows), n), np.float64)
    for a, r in enumerate(rows):
        i, loc = divmod(int(r), nb)
        at = int(np.flatnonzero(problem["local_rows"][i] == loc)[0])
        for j in range(i + 1):
            out[a, j * nb:(j + 1) * nb] = tiles[(i, j)][at]
        out[a, r + 1:] = 0.0  # the factor is lower-triangular
    return out


def compare(problem: Dict[str, Any], result) -> Dict[str, float]:
    """``result``: ``theta`` (the solve's), ``rows`` ({(i, j): gathered
    rows}), ``diag`` (the factor's diagonal), ``y``, ``logdet``, ``dot``."""
    names = ("diagonal_error", "offdiag_error", "offdiag_lo_error",
             "solve_residual", "logdet_error", "nonfinite_values")
    bad = dict.fromkeys(names, float("inf"))
    nt = problem["nt"]
    if set(result["rows"]) != {(i, j) for i in range(nt)
                               for j in range(i + 1)}:
        return bad
    theta = result["theta"]
    S = problem["rows"]
    y = np.asarray(result["y"], np.float64).reshape(-1)
    scalars = np.array([result["logdet"], result["dot"]], np.float64)
    nonfinite = int((~np.isfinite(scalars)).sum() + (~np.isfinite(y)).sum())
    L = factor_rows(problem, result["rows"])
    rec = L @ L.T
    want = covariance(problem["x"][S], problem["x"][S], theta)
    err = np.abs(rec - want)
    diag = np.diagonal(err) / np.diagonal(want)
    off = err / float(theta[0])
    np.fill_diagonal(off, 0.0)
    hi = problem["f32_pair"]
    z = problem["z"][S].astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        truth = 2.0 * np.log(np.asarray(result["diag"], np.float64)).sum()
        out = {
            "diagonal_error": float(diag.max()),
            "offdiag_error": float(off[hi].max()),
            "offdiag_lo_error": float(off[~hi].max()) if (~hi).any()
            else 0.0,
            "solve_residual": float(np.linalg.norm(L @ y - z)
                                    / np.linalg.norm(z)),
            "logdet_error": float(abs(result["logdet"] - truth)
                                  / abs(truth)),
            "nonfinite_values": float(nonfinite)}
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}


def dense_loglik(problem: Dict[str, Any], theta) -> Dict[str, float]:
    """The likelihood itself in float64, dense (``numpy.linalg.cholesky``):
    what the tests and the band's choice at N = 16384 compare with.  Not
    part of ``compare``: at the cell's size it is 65 GB and an hour."""
    import scipy.linalg

    x = problem["x"]
    L = np.linalg.cholesky(covariance(x, x, theta))
    y = scipy.linalg.solve_triangular(L, problem["z"].astype(np.float64),
                                      lower=True)
    logdet = 2.0 * float(np.log(np.diagonal(L)).sum())
    dot = float(y @ y)
    n = len(x)
    return {"logdet": logdet, "dot": dot,
            "loglik": -0.5 * (dot + logdet + n * math.log(2.0 * math.pi))}
