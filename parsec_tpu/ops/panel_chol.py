"""Panel-wise Cholesky: the compile-scalable path to north-star sizes.

The whole-DAG ``GraphExecutor`` jits one XLA op per task — unbeatable at
NT<=16 but O(tasks) compile (intractable at NT=64, ~45k tasks).  This
module is the TPU-native answer for large NT (BASELINE.json north star:
N=32768, nb=512): the right-looking factorization becomes NT *panel
steps*, each a jitted program whose shapes depend only on the trailing
size rounded UP to a bucket — so XLA compiles O(#buckets) programs
(typically 4-8) and every step re-uses one of them with a *traced*
panel offset (``lax.dynamic_slice`` start indices are dynamic; shapes
are static per bucket).

Per step k (panel offset k0 = k*nb, padded trailing rows R):

    D  = A[k0:k0+nb, k0:k0+nb]           # diagonal tile
    L  = chol(D);  W = inv(L)            # nb x nb — tiny, off MXU path
    P  = A[k0+nb:k0+nb+R, k0:k0+nb] @ W.T       # panel trsm as ONE gemm
    Tr = A[k0+nb:.., k0+nb:..] - P @ P.T        # symmetric rank-nb update

The update is a single (R x nb) x (nb x R) MXU gemm — both triangles are
written, which keeps the trailing matrix symmetric (so no masking is
needed anywhere) at the cost of ~2x update flops vs a tile-wise syrk.
At north-star sizes the raw MXU rate on these huge gemms more than
covers it (the benchmark's ``panel_n32768`` cell measures it).
``bf16=True`` feeds the gemm operands in bfloat16 with f32 accumulation
— the same mixed-precision recipe as the Pallas graph path, same
numerics gate.

The matrix is padded to a bucket multiple with an identity diagonal:
padded panel rows are zero => their updates are zero; the slices stay
in-bounds; the first N rows/cols are exactly the factorization of A.

Reference analog: this replaces the reference's per-task dataflow for
the regular dense case with what the TPU compiler wants — few big
static-shape programs — while the PTG/dynamic runtime remains the
general path (irregular DAGs, distribution).  Cited for parity:
/root/reference/parsec/interfaces/ptg/ptg-compiler/jdf2c.c generates
O(task classes) code, not O(tasks) — this is the same scaling law
applied to XLA programs.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except Exception:  # pragma: no cover
    jax = None


def _panel_step(A, k0, *, R: int, nb: int, bf16: bool, strip: int = 0):
    """One bucketed right-looking panel step on the padded matrix.

    ``strip > 0`` strip-mines the trailing update over column strips of
    that width (must divide R): per-step temporaries shrink from two
    R x R blocks to two R x strip blocks, which matters at north-star
    sizes — JAX dispatch is asynchronous and every enqueued step's
    temporaries must coexist in HBM, so whole-R temps OOM at N=32k while
    strip-mined steps enqueue freely."""
    f32 = A.dtype
    D = lax.dynamic_slice(A, (k0, k0), (nb, nb))
    L = jnp.linalg.cholesky(D)
    # trsm-as-matmul: invert the nb x nb factor once (off the MXU, tiny)
    # and turn the panel solve into one MXU gemm
    W = lax.linalg.triangular_solve(
        L, jnp.eye(nb, dtype=f32), lower=True, left_side=True)
    A = lax.dynamic_update_slice(A, jnp.tril(L), (k0, k0))
    if R == 0:
        return A
    P = lax.dynamic_slice(A, (k0 + nb, k0), (R, nb))
    if bf16:
        Pn = jnp.matmul(P.astype(jnp.bfloat16), W.T.astype(jnp.bfloat16),
                        preferred_element_type=f32)
    else:
        Pn = P @ W.T
    A = lax.dynamic_update_slice(A, Pn, (k0 + nb, k0))
    Pl = Pn.astype(jnp.bfloat16) if bf16 else Pn

    def update(cols, Pj):
        if bf16:
            return cols - jnp.matmul(Pl, Pj.T, preferred_element_type=f32)
        return cols - Pl @ Pj.T

    if not strip or strip >= R:
        Tr = lax.dynamic_slice(A, (k0 + nb, k0 + nb), (R, R))
        return lax.dynamic_update_slice(A, update(Tr, Pl), (k0 + nb, k0 + nb))
    if R % strip:
        raise ValueError(f"strip {strip} must divide R {R}")

    def body(j, A):
        c0 = k0 + nb + j * strip
        cols = lax.dynamic_slice(A, (k0 + nb, c0), (R, strip))
        Pj = lax.dynamic_slice(Pl, (j * strip, 0), (strip, nb))
        return lax.dynamic_update_slice(A, update(cols, Pj), (k0 + nb, c0))

    return lax.fori_loop(0, R // strip, body, A)


class PanelCholesky:
    """Bucketed panel-step factorizer.  One instance caches the jitted
    step programs (one per bucketed trailing size) and can be re-used
    across same-shape matrices."""

    def __init__(self, n: int, nb: int = 512, *, bucket: int = 8,
                 bf16: bool = False, strip: int = 0, device=None):
        from .tiles import check_tiling

        check_tiling(n, nb, op="panel cholesky")
        if bf16 == "storage":
            raise ValueError(
                "PanelCholesky does not implement bf16='storage' — use "
                "WholeCholesky or SegmentedCholesky for the bf16-storage "
                "mode (a truthy string would silently run the operand-"
                "cast mode at full-f32 HBM traffic)")
        self.n, self.nb, self.bucket, self.bf16 = n, nb, bucket, bf16
        self.nt = n // nb
        # pad so every bucketed trailing slice stays in bounds
        self.n_pad = n + (bucket - 1) * nb
        #: strip width for the trailing update; 0 = whole-R (auto: strip
        #: when the R x R temps would approach HBM scale)
        self.strip = strip if strip else (
            bucket * nb if n * n * 4 >= (4 << 30) else 0)
        if self.strip and (bucket * nb) % self.strip:
            raise ValueError(
                f"strip {self.strip} must divide bucket*nb {bucket * nb}")
        self.device = device
        self._steps: Dict[int, any] = {}

    def _step_for(self, R: int):
        fn = self._steps.get(R)
        if fn is None:
            fn = jax.jit(
                partial(_panel_step, R=R, nb=self.nb, bf16=self.bf16,
                        strip=self.strip),
                donate_argnums=(0,))
            self._steps[R] = fn
        return fn

    def _padded(self, A_np: np.ndarray):
        n, n_pad = self.n, self.n_pad
        buf = np.zeros((n_pad, n_pad), np.result_type(A_np.dtype, np.float32))
        buf[:n, :n] = A_np
        idx = np.arange(n, n_pad)
        buf[idx, idx] = 1.0  # identity padding: chol-stable, zero updates
        arr = jnp.asarray(buf)
        if self.device is not None:
            arr = jax.device_put(arr, self.device)
        return arr

    def run_padded(self, A):
        """Factorize a padded device matrix in place; returns the device
        array (lower triangle of the leading N x N is L)."""
        nb, bucket, nt = self.nb, self.bucket, self.nt
        for k in range(nt):
            trail = nt - 1 - k
            R = (math.ceil(trail / bucket) * bucket) * nb if trail else 0
            A = self._step_for(R)(A, k * nb)
        return A

    def __call__(self, A_np: np.ndarray) -> np.ndarray:
        A = self.run_padded(self._padded(A_np))
        out = np.asarray(A[: self.n, : self.n])
        return np.tril(out)


class WholeCholesky:
    """ALL panel steps traced into ONE jitted program with static slices.

    This is the north-star configuration's fast path: XLA's buffer
    assignment reuses the update temporaries across the sequential steps
    (so HBM peak is one step's working set, not #enqueued-steps of them
    — the async-dispatch pileup that OOMs the per-step path at N=32k),
    there is no bucket padding at all (exact trailing shapes per step),
    and the program is O(NT) ops — compile scales with PANELS, the same
    law as the reference's O(task classes) generated code, not with the
    O(NT^3) task count that the whole-DAG unroll pays.

    ``strip`` bounds the trailing-update temporaries (R x strip); the
    strips are unrolled statically, adding ~N/strip ops per step."""

    def __init__(self, n: int, nb: int = 512, *, bf16=False,
                 strip: int = 4096):
        from .tiles import check_tiling

        check_tiling(n, nb, op="whole cholesky")
        if strip:
            check_tiling(strip, nb, what="strip", op="whole cholesky")
        #: ``bf16``: False = storage precision; True = bf16 operand casts
        #: (f32 accumulate/storage); "storage" = the matrix lives in
        #: bf16 — HALF the HBM traffic, the binding constraint at
        #: north-star sizes (bf16-class numerics)
        self.n, self.nb, self.bf16, self.strip = n, nb, bf16, strip
        self.store_bf16 = bf16 == "storage"
        self.nt = n // nb
        self._fn = jax.jit(self._factorize, donate_argnums=(0,))

    def _factorize(self, A):
        n, nb, bf16, strip = self.n, self.nb, self.bf16, self.strip
        store = self.store_bf16
        f32 = jnp.float32 if store else A.dtype
        for k in range(self.nt):
            k0 = k * nb
            D = A[k0:k0 + nb, k0:k0 + nb].astype(f32)
            L = jnp.linalg.cholesky(D)
            W = lax.linalg.triangular_solve(
                L, jnp.eye(nb, dtype=f32), lower=True, left_side=True)
            A = A.at[k0:k0 + nb, k0:k0 + nb].set(jnp.tril(L).astype(A.dtype))
            R = n - k0 - nb
            if R == 0:
                continue
            P = A[k0 + nb:, k0:k0 + nb]
            if store:
                Pn = jnp.matmul(P.astype(f32), W.T,
                                precision=lax.Precision.HIGHEST)
                Pl = Pn.astype(jnp.bfloat16)
                A = A.at[k0 + nb:, k0:k0 + nb].set(Pl)
            elif bf16:
                Pn = jnp.matmul(P.astype(jnp.bfloat16),
                                W.T.astype(jnp.bfloat16),
                                preferred_element_type=f32)
                A = A.at[k0 + nb:, k0:k0 + nb].set(Pn)
                Pl = Pn.astype(jnp.bfloat16)
            else:
                Pn = P @ W.T
                A = A.at[k0 + nb:, k0:k0 + nb].set(Pn)
                Pl = Pn
            for c0 in range(k0 + nb, n, strip):
                w = min(strip, n - c0)
                Pj = Pl[c0 - (k0 + nb):c0 - (k0 + nb) + w, :]
                if store:
                    upd = jnp.matmul(Pl, Pj.T, preferred_element_type=f32)
                    A = A.at[k0 + nb:, c0:c0 + w].set(
                        (A[k0 + nb:, c0:c0 + w].astype(f32) - upd
                         ).astype(jnp.bfloat16))
                    continue
                if bf16:
                    upd = jnp.matmul(Pl, Pj.T, preferred_element_type=f32)
                else:
                    upd = Pl @ Pj.T
                A = A.at[k0 + nb:, c0:c0 + w].add(-upd)
        return A

    def run(self, A):
        """Factorize a device matrix (n x n) in place; donated.  In
        storage mode the input must arrive (or is cast) bf16 — an f32
        matrix would silently keep full-f32 HBM traffic with
        bf16-rounded numerics, the worst of both modes."""
        if self.store_bf16 and A.dtype != jnp.bfloat16:
            A = A.astype(jnp.bfloat16)
        return self._fn(A)

    def __call__(self, A_np: np.ndarray) -> np.ndarray:
        A = jnp.asarray(np.ascontiguousarray(A_np))
        if self.store_bf16:
            A = A.astype(jnp.bfloat16)
        out = np.asarray(self.run(A), dtype=np.float32) \
            if self.store_bf16 else np.asarray(self.run(A))
        return np.tril(out)
