"""Plain reference of the hierarchical tile-QR configuration: a tall
matrix, M x N with M >> N.

The input is DPLASMA's ``dplrnt``-class test matrix: dense, every entry
uniform in [-1/2, 1/2) from the seed (f32, made on the device a block of
rows at a time), cut into host tiles, all of them.

The program returns **R alone** (the upper tiles of the first N rows,
zeros everywhere else; its Q lives in scratch blocks that die on the
chip), so the check needs no Q.  With R upper triangular, ``R^T R = A^T
A`` fixes R up to the signs of its rows, whatever tree reduced the
panels, and three numbers are compared, all against float64 on the host:

``gram_error``
    for 8 columns ``s`` of every tile column (drawn from the seed, 64 at
    N=4096): the largest ``|(R^T R - A^T A)[:, s]| / |A^T A[:, s]|``
    (2-norms).  Every tile of R enters it.
``r_block_error``
    the leading block of R is the R of the leading columns:
    the R of ``A[:, :w]`` in float64 (w = 1024 at M=262144, two tile
    columns: the second has been through panel 0's updates, and 550 G
    operations keep ``prepare`` under a minute) against the same block
    of the solve, both with the rows' signs made
    canonical (diagonal >= 0), relative to the largest entry of the
    reference block.
``lower_residue``
    the largest ``|entry|`` below the diagonal of the first N rows and
    anywhere in the rows under them; its limit is 0: R is upper
    triangular exactly and every killed tile is zeros.

Also here: :func:`householder_r`, an unblocked Householder QR in plain
``jax.numpy`` at float32 under ``jax.default_matmul_precision("highest")``,
which the CPU tests hold the program against at small sizes.

Imports nothing of the program.
"""

from __future__ import annotations

import random
from typing import Any, Dict

import numpy as np

#: rows made (and reduced) at a time: 128 MiB of f32 at N=4096
ROW_BLOCK = 8192


def sizes(config: Dict[str, Any]):
    m, n, nb = int(config["m"]), int(config["n"]), int(config["nb"])
    if m % nb or n % nb or m < n:
        raise ValueError(f"m={m}, n={n} have to be multiples of nb={nb} "
                         f"with m >= n")
    return m, n, nb


def make_matrix(m: int, n: int, seed: int, jdev) -> np.ndarray:
    """The whole matrix as one host array, a block of rows at a time
    (the same entries whatever the block: the key is folded by row)."""
    import jax
    import jax.numpy as jnp

    step = min(ROW_BLOCK, m)

    @jax.jit
    def build(key, at):
        rows = jax.vmap(lambda r: jax.random.uniform(
            jax.random.fold_in(key, r), (n,), jnp.float32, -0.5, 0.5))
        return rows(at + jnp.arange(step))

    key = jax.device_put(jax.random.key(seed), jdev)
    a = np.empty((m, n), np.float32)
    for at in range(0, m, step):
        a[at:at + step] = np.asarray(build(key, at))[:m - at]
    return a


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    m, n, nb = sizes(config)
    a = make_matrix(m, n, seed, devices[0])
    mt, nt = m // nb, n // nb
    # views: a driver copies a tile before the runtime may write into it
    tiles = {(i, j): a[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
             for i in range(mt) for j in range(nt)}
    return {"seed": seed, "m": m, "n": n, "nb": nb, "mt": mt, "nt": nt,
            "a": a, "tiles": tiles,
            "samples": int(config.get("samples_per_tile_column", 8)),
            "lead": min(n, int(config.get("leading_columns", 2 * nb)))}


def prepare(problem: Dict[str, Any]) -> None:
    a, m, n, nb, nt = (problem["a"], problem["m"], problem["n"],
                       problem["nb"], problem["nt"])
    rng = random.Random(problem["seed"])
    k = min(problem["samples"], nb)
    cols = np.array(sorted(j * nb + c for j in range(nt)
                           for c in rng.sample(range(nb), k)))
    w = problem["lead"]
    # A^T A[:, S] in float64, a block of rows at a time; the R of the
    # leading columns by the same blocks (the R of stacked Rs: plain
    # numpy, no tree of the program's)
    want = np.zeros((n, len(cols)), np.float64)
    r_lead = np.zeros((0, w), np.float64)
    for r in range(0, m, ROW_BLOCK):
        blk = a[r:r + ROW_BLOCK].astype(np.float64)
        want += blk.T @ blk[:, cols]
        r_lead = np.linalg.qr(np.vstack([r_lead, blk[:, :w]]), mode="r")
    problem["cols"] = cols
    problem["want"] = want
    problem["want_norm"] = np.linalg.norm(want, axis=0)
    problem["r_lead"] = _canonical(r_lead)
    problem["r_scale"] = float(np.max(np.abs(r_lead)))


def _canonical(r: np.ndarray) -> np.ndarray:
    """Rows' signs fixed: the diagonal is not negative."""
    s = np.where(np.diagonal(r) < 0, -1.0, 1.0)
    return r * s[:, None]


NUMBERS = ("gram_error", "r_block_error", "lower_residue")


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's R as host tiles ``{(i, j): tile}``: every
    upper tile of the first nt rows (i <= j), and whichever other tiles
    it brought home."""
    nb, nt, n = problem["nb"], problem["nt"], problem["n"]
    upper = {(i, j) for i in range(nt) for j in range(i, nt)}
    if not upper <= set(tiles) <= set(problem["tiles"]):
        return {k: float("inf") for k in NUMBERS}
    residue = 0.0
    for (i, j), t in tiles.items():
        t = np.asarray(t)
        below = t if i > j else np.tril(t, -1) if i == j else None
        if below is not None:
            residue = max(residue, float(np.max(np.abs(below))))
    cols = problem["cols"]
    got = np.zeros((n, len(cols)), np.float64)
    w = problem["lead"]
    lead = np.zeros((w, w), np.float64)
    for i in range(nt):
        row = np.concatenate([np.asarray(tiles[(i, j)], np.float64)
                              for j in range(i, nt)], axis=1)
        row[:, :nb] = np.triu(row[:, :nb])
        at = i * nb
        sel = cols[cols >= at] - at
        # R^T (R[:, S]): row block i of R meets its own rows of R[:, S]
        got[at:, cols >= at] += row.T @ row[:, sel]
        if at < w:
            lead[at:at + nb, at:] = row[:, :w - at]
    gram = np.linalg.norm(got - problem["want"], axis=0) \
        / problem["want_norm"]
    block = np.max(np.abs(_canonical(lead) - problem["r_lead"])) \
        / problem["r_scale"]
    return {"gram_error": float(np.max(gram)),
            "r_block_error": float(block), "lower_residue": residue}


def householder_r(a):
    """R of ``a`` (m x n, m >= n) by unblocked Householder reflections:
    float32, every product at ``highest``.  For tests at small sizes."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    a = jnp.asarray(a, jnp.float32)
    m, n = a.shape
    rows = jnp.arange(m)

    def step(j, r):
        x = jnp.where(rows >= j, r[:, j], 0.0)
        alpha = x[j]
        norm = jnp.sqrt(jnp.sum(x * x))
        beta = jnp.where(alpha > 0, -norm, norm)
        v = x.at[j].add(-beta)
        vv = jnp.sum(v * v)
        tau = jnp.where(vv > 0, 2.0 / jnp.where(vv > 0, vv, 1.0), 0.0)
        return r - tau * jnp.outer(v, v @ r)

    with jax.default_matmul_precision("highest"):
        r = lax.fori_loop(0, n, step, a)
    return jnp.triu(r[:n])
