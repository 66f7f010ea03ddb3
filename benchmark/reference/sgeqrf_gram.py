"""Plain reference of the tile-granular sgeqrf configuration.

The input is DPLASMA's ``dplrnt``-class test matrix: square, dense,
every entry uniform in [-1/2, 1/2) from the seed (f32, made on the
device in one jitted call), cut once into host tiles, all of them.

The program returns **R alone** (upper tiles, zeros below; its Q lives
in scratch blocks that die on the chip), so the check needs no Q.  With
R upper triangular, ``R^T R = A^T A`` fixes R up to the signs of its
rows, and three numbers are compared, all against float64 on the host:

``gram_error``
    for 8 columns ``s`` of every tile column (drawn from the seed, 256
    at N=16384): the largest ``|(R^T R - A^T A)[:, s]| / |A^T A[:, s]|``
    (2-norms).  Every tile of R enters it.
``r_block_error``
    the leading block of R is the R of the leading columns:
    ``numpy.linalg.qr(A[:, :w], mode="r")`` in float64 (w = 2048 at
    N=16384) against the same block of the solve, both with the rows'
    signs made canonical (diagonal >= 0), relative to the largest entry
    of the reference block.
``lower_residue``
    the largest ``|entry|`` below the diagonal of the returned tiles;
    its limit is 0: R is upper triangular exactly.

Also here: :func:`householder_r`, an unblocked Householder QR in plain
``jax.numpy`` at float32 under ``jax.default_matmul_precision("highest")``,
which the CPU tests hold the program against at small sizes.

Imports nothing of the program.
"""

from __future__ import annotations

import random
from typing import Any, Dict

import numpy as np


def sizes(config: Dict[str, Any]):
    n, nb = int(config["n"]), int(config["nb"])
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    return n, nb


def make_matrix(n: int, seed: int, jdev):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        return jax.random.uniform(key, (n, n), jnp.float32, -0.5, 0.5)

    return build(jax.device_put(jax.random.key(seed), jdev))


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = sizes(config)
    a = np.asarray(make_matrix(n, seed, devices[0]))
    nt = n // nb
    tiles = {(i, j): np.ascontiguousarray(
        a[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
        for i in range(nt) for j in range(nt)}
    return {"seed": seed, "n": n, "nb": nb, "nt": nt, "a": a,
            "tiles": tiles,
            "samples": int(config.get("samples_per_tile_column", 8)),
            "lead": min(n, int(config.get("leading_columns", 4 * nb)))}


def prepare(problem: Dict[str, Any]) -> None:
    a, n, nb, nt = (problem.pop("a"), problem["n"], problem["nb"],
                    problem["nt"])
    rng = random.Random(problem["seed"])
    k = min(problem["samples"], nb)
    cols = np.array(sorted(j * nb + c for j in range(nt)
                           for c in rng.sample(range(nb), k)))
    # A^T A[:, S] in float64, a block of rows at a time
    want = np.zeros((n, len(cols)), np.float64)
    for r in range(0, n, 2048):
        blk = a[r:r + 2048].astype(np.float64)
        want += blk.T @ blk[:, cols]
    problem["cols"] = cols
    problem["want"] = want
    problem["want_norm"] = np.linalg.norm(want, axis=0)
    w = problem["lead"]
    r_lead = np.linalg.qr(a[:, :w].astype(np.float64), mode="r")
    problem["r_lead"] = _canonical(r_lead)
    problem["r_scale"] = float(np.max(np.abs(r_lead)))


def _canonical(r: np.ndarray) -> np.ndarray:
    """Rows' signs fixed: the diagonal is not negative."""
    s = np.where(np.diagonal(r) < 0, -1.0, 1.0)
    return r * s[:, None]


NUMBERS = ("gram_error", "r_block_error", "lower_residue")


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's R as host tiles ``{(i, j): tile}``: every
    upper tile (i <= j), and whichever lower tiles it brought home."""
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
