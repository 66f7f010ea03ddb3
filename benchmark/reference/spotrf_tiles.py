"""Plain reference of the tile-granular spotrf configurations.

The input is the seed's matrix ``M M^T + n I`` (M standard normal, f32,
made on the device in one jitted call), cut once into host tiles.  The
reference is one of two, named by the configuration's ``check``:

``factor_f64``
    ``numpy.linalg.cholesky`` of the matrix in float64 on the host; every
    lower tile of every solve is compared with it (``factor_error``: the
    largest difference over the largest entry of the reference factor).
    The reference factor is rounded once to f32 tiles so that the
    comparison of 35M entries per solve stays short; that adds at most
    6e-8 to a reading.
``sampled_reconstruction``
    no factor at all: rows of the solve's factor, drawn from the seed in
    every tile row, are multiplied out in float64 and compared with the
    same rows and columns of the matrix (``reconstruction_error``: the
    largest difference over the largest entry of the matrix).

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


def make_spd(n: int, seed: int, jdev):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        m = jax.random.normal(key, (n, n), jnp.float32)
        return (jnp.matmul(m, m.T, precision="highest")
                + n * jnp.eye(n, dtype=jnp.float32))

    return build(jax.device_put(jax.random.key(seed), jdev))


def make_problem(seed: int, config, traffic, devices) -> Dict[str, Any]:
    n, nb = sizes(config)
    spd = np.asarray(make_spd(n, seed, devices[0]))
    nt = n // nb
    tiles = {(i, j): np.ascontiguousarray(
        spd[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
        for i in range(nt) for j in range(i + 1)}
    return {"seed": seed, "n": n, "nb": nb, "nt": nt, "spd": spd,
            "tiles": tiles, "check": config["check"],
            "samples_per_tile_row": int(config.get("samples_per_tile_row",
                                                   16))}


def prepare(problem: Dict[str, Any]) -> None:
    spd, nb, nt = problem.pop("spd"), problem["nb"], problem["nt"]
    if problem["check"] == "factor_f64":
        L = np.linalg.cholesky(spd.astype(np.float64))
        problem["scale"] = max(1.0, float(np.max(np.abs(L))))
        problem["ref_tiles"] = {
            (i, j): L[i * nb:(i + 1) * nb,
                      j * nb:(j + 1) * nb].astype(np.float32)
            for (i, j) in problem["tiles"]}
    elif problem["check"] == "sampled_reconstruction":
        rng = random.Random(problem["seed"])
        k = min(problem["samples_per_tile_row"], nb)
        idx = np.array(sorted(i * nb + r for i in range(nt)
                              for r in rng.sample(range(nb), k)))
        problem["rows"] = idx
        problem["want"] = spd[np.ix_(idx, idx)].astype(np.float64)
        problem["scale"] = float(np.max(np.abs(spd)))
    else:
        raise ValueError(f"unknown check {problem['check']!r}")


def compare(problem: Dict[str, Any], tiles) -> Dict[str, float]:
    """``tiles``: the solve's factor, ``{(i, j): host tile}`` for i >= j."""
    if set(tiles) != set(problem["tiles"]):
        return {_NUMBER[problem["check"]]: float("inf")}
    if problem["check"] == "factor_f64":
        worst = 0.0
        for key, ref in problem["ref_tiles"].items():
            got = np.asarray(tiles[key], np.float32)
            if key[0] == key[1]:
                got = np.tril(got)
            worst = max(worst, float(np.max(np.abs(got - ref))))
        return {"factor_error": worst / problem["scale"]}
    nb, n = problem["nb"], problem["n"]
    rows = np.zeros((len(problem["rows"]), n), np.float64)
    for a, r in enumerate(problem["rows"]):
        i, local = divmod(int(r), nb)
        for j in range(i + 1):
            rows[a, j * nb:(j + 1) * nb] = np.asarray(tiles[(i, j)])[local]
        rows[a, r + 1:] = 0.0  # the factor is lower-triangular
    rec = rows @ rows.T
    return {"reconstruction_error":
            float(np.max(np.abs(rec - problem["want"]))) / problem["scale"]}


_NUMBER = {"factor_f64": "factor_error",
           "sampled_reconstruction": "reconstruction_error"}
