#!/usr/bin/env python3
"""chip_smoke.py — one command that proves the normal path on the chip.

taskpool (``dsl``) -> scheduler (``core`` / the ``native`` pump) -> device
module (``device/tpu.py``, staging, ``compile_cache``) -> ``ops`` bodies
and Pallas kernels -> TPU, through the entry points a user calls, with
default MCA parameters and device chores only.  One process; no child
touches JAX.  It refuses to pass unless ``jax.devices()[0].platform`` is
``"tpu"``, every stage raises on failure (nothing is caught and carried
on), and every counter that means "a fallback ran" must read 0.

Stages (sizes in :class:`Sizes`; the defaults are the real ones):

* ``kernels``   every kernel of ``ops/pallas_kernels.py`` compiled by
                Mosaic (``interpret=False`` forced), each against its
                ``jax.numpy`` reference;
* ``context``   tile-granular dpotrf N=8192 nb=512 f32 (816 tasks)
                through ``Context.add_taskpool`` + ``tp.wait``;
* ``pump``      the same DAG through ``NativeExecutor(native_device=True)``
                (the zero-interpreter pump lifecycle);
* ``pallas``    the same DAG with ``use_pallas=True``: ``matmul_update``
                inside per-task and wave programs;
* ``segmented`` the north star: ``SegmentedCholesky(ctx, 32768, 512)``
                f32 on a 4 GiB matrix built on the device, gated by
                sampled reconstruction against its closed form;
* ``mesh``      (four chips present) PTG dpotrf N=16384 nb=512, 2x2
                block-cyclic, four ``Context``s over ``InprocFabric``,
                one chip per rank, device-native payloads.

Every dpotrf stage runs twice: cold (compiles included) and warm (the
same device, no compile allowed).  Timings printed here are observations
for ``CHANGES.md``; they are not benchmark numbers.

The last two lines of stdout are JSON objects: the run's summary
(``{"setup": ..., "kernels": ..., ..., "claim": null}`` — what each stage
measured and counted, for ``CHANGES.md``) and then, last, the result with
exactly these keys: ``{"ok": true, "device": {"platform": "tpu", "kind":
"...", "count": N}}``.  Without a TPU, or when a stage fails, the exit
code is non-zero and neither line is printed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable, Dict, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    #: tile-granular dpotrf: ROADMAP cell C2 (NT=16, 816 tasks)
    tile_n: int = 8192
    tile_nb: int = 512
    #: the 4 GiB f32 matrix BASELINE.json names
    seg_n: int = 32768
    seg_nb: int = 512
    #: Pallas stage: the dpotrf update tile, the stencil PTG's tile, and
    #: the (q_block, kv_block, D) block ops/attention.py passes
    kern: int = 512
    stencil: int = 512
    attn: tuple = (128, 128, 64)
    #: four-chip stage: 2x2 block-cyclic dpotrf
    mesh_n: int = 16384
    mesh_nb: int = 512
    #: Pallas stage only: False = Mosaic, forced (the chip); True = the
    #: interpreter, explicitly (the tier-1 test on the CPU backend)
    interpret: bool = False


#: the f32 bar every dpotrf variant is held to (max abs error of the
#: lower factor over max |L_ref|), and the bar for whatever runs at the
#: chip's default matmul precision (one bf16 MXU pass)
F32_BAR = 1e-3
BF16_BAR = 1e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def dpotrf_ntasks(nt: int) -> int:
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events, so "no compile in the
# warm stage" is what XLA did, not what a wrapper counted
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts backend compiles and persistent-cache hits/misses."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "xla_cache_hits",
               "/jax/compilation_cache/cache_misses": "xla_cache_misses"}

    def __init__(self):
        from jax import monitoring

        self.n = {"backend_compiles": 0, "xla_cache_hits": 0,
                  "xla_cache_misses": 0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        key = self._EVENTS.get(name)
        if key:
            self.n[key] += 1

    def _duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.n["backend_compiles"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before[k] for k, v in self.n.items()}


# ---------------------------------------------------------------------------
# fallback counters
# ---------------------------------------------------------------------------

_CACHE_FALLBACKS = ("aot_fallbacks", "serialize_errors", "local_only",
                    "blob_errors")


def fallback_counters(device, cache,
                      since: Optional[Dict[str, int]] = None
                      ) -> Dict[str, int]:
    """Every counter that means a slower path stood in for the intended
    one; all 0 on a healthy run.  ``since``: the cache's snapshot from
    before the stage, for a cache that outlives it (a Context's own
    cache is born with it)."""
    snap, since = cache.snapshot(), since or {}
    out = {k: snap.get(k, 0) - since.get(k, 0) for k in _CACHE_FALLBACKS}
    out.update({k: device.stats[k] for k in (
        "wave_fallbacks", "submit_retries", "stage_batch_fallbacks")})
    out["native_zone_missing"] = int(device._zone is None)
    return out


def require_no_fallback(stage: str, counters: Dict[str, int]) -> None:
    bad = {k: v for k, v in counters.items() if v}
    if bad:
        raise RuntimeError(f"{stage}: a fallback ran: {bad}")


def require_platform(device, platform: str) -> None:
    if device.jdev.platform != platform:
        raise RuntimeError(
            f"device module bound {device.jdev} (platform "
            f"{device.jdev.platform!r}), expected {platform!r}")


# ---------------------------------------------------------------------------
# inputs and references
# ---------------------------------------------------------------------------

def make_spd(n: int, seed: int, jdev):
    """A well-conditioned SPD matrix of order ``n`` built ON ``jdev``
    from ``seed`` (no N^2 host matmul): ``M M^T + n I``, M standard
    normal."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        m = jax.random.normal(key, (n, n), jnp.float32)
        return (jnp.matmul(m, m.T, precision="highest")
                + n * jnp.eye(n, dtype=jnp.float32))

    return build(jax.device_put(jax.random.key(seed), jdev))


def factor_error(L: np.ndarray, L_ref: np.ndarray) -> float:
    """The dpotrf gate: max |tril(L) - L_ref| over max |L_ref|."""
    scale = max(1.0, float(np.max(np.abs(L_ref))))
    return float(np.max(np.abs(np.tril(L) - L_ref))) / scale


def require_close(what: str, err: float, bar: float) -> None:
    if not np.isfinite(err) or err > bar:
        raise RuntimeError(f"{what}: error {err:.3e} exceeds {bar:.0e}")


# ---------------------------------------------------------------------------
# stage: Pallas kernels
# ---------------------------------------------------------------------------

def stage_kernels(sizes: Sizes, jdev) -> Dict[str, Any]:
    """Every kernel in ops/pallas_kernels.py at the shape its caller
    passes, ``interpret`` forced.  Each is held to two references: the
    plain ``jax.numpy`` expression at JAX's default matmul precision —
    what the kernel replaces, so they must agree closely — and the same
    expression at HIGHEST precision, which says how far the kernel's
    numerics class is from exact f32 (on a TPU an f32 dot inside a
    kernel is one bf16 MXU pass, like XLA's own default)."""
    import jax
    import jax.numpy as jnp

    from parsec_tpu.ops import pallas_kernels as pk

    interp = sizes.interpret
    keys = iter(jax.random.split(jax.device_put(jax.random.key(1), jdev),
                                 32))

    def rnd(*shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32) \
            .astype(dtype)

    def rel_err(got, want) -> float:
        err = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if g.shape != w.shape or g.dtype != w.dtype:
                raise RuntimeError(f"{g.shape}/{g.dtype} != "
                                   f"{w.shape}/{w.dtype}")
            w = np.asarray(w, np.float64)
            err = max(err, float(np.max(np.abs(
                np.asarray(g, np.float64) - w)))
                / max(1.0, float(np.max(np.abs(w)))))
        return err

    out: Dict[str, Any] = {}

    def check(name: str, got, reference: Callable, *, bar: float,
              exact_bar: float) -> None:
        """``reference(precision)`` is the jax.numpy expression."""
        err = rel_err(got, reference(None))
        exact = rel_err(got, reference(jax.lax.Precision.HIGHEST))
        log(f"kernel {name}: vs jax.numpy {err:.2e} (bar {bar:.0e}), "
            f"vs exact f32 {exact:.2e} (bar {exact_bar:.0e})")
        require_close(f"kernel {name} vs jax.numpy", err, bar)
        require_close(f"kernel {name} vs exact f32", exact, exact_bar)
        out[name] = {"err": err, "err_vs_exact": exact}

    n = sizes.kern
    C, A, B = rnd(n, n), rnd(n, n), rnd(n, n)
    check("matmul_update_f32", pk.matmul_update(C, A, B, interpret=interp),
          lambda p: C - jnp.matmul(A, B.T, precision=p),
          bar=1e-5, exact_bar=BF16_BAR)
    check("matmul_update_split_f32",
          pk.matmul_update(C, A, B, split_f32=True, interpret=interp),
          # its contract IS the 3-pass Precision.HIGH decomposition
          lambda p: C - jnp.matmul(A, B.T,
                                   precision=p or jax.lax.Precision.HIGH),
          bar=1e-4, exact_bar=1e-4)
    Ab, Bb = A.astype(jnp.bfloat16), B.astype(jnp.bfloat16)
    check("matmul_update_bf16",
          pk.matmul_update(C, Ab, Bb, interpret=interp),
          lambda p: C - jnp.matmul(Ab, Bb.T, precision=p,
                                   preferred_element_type=jnp.float32),
          bar=1e-5, exact_bar=1e-5)
    check("matmul", pk.matmul(A, B, interpret=interp),
          lambda p: jnp.matmul(A, B.T, precision=p),
          bar=1e-5, exact_bar=BF16_BAR)

    s = sizes.stencil
    old, up, down = rnd(s, s), rnd(1, s), rnd(1, s)
    left, right = rnd(s, 1), rnd(s, 1)
    padded = jnp.pad(old, 1)
    padded = padded.at[0, 1:-1].set(up[0]).at[-1, 1:-1].set(down[0])
    padded = padded.at[1:-1, 0].set(left[:, 0]).at[1:-1, -1].set(right[:, 0])
    check("stencil_5pt",
          pk.stencil_5pt(old, up, down, left, right, interpret=interp),
          lambda p: 0.25 * (padded[:-2, 1:-1] + padded[2:, 1:-1]
                            + padded[1:-1, :-2] + padded[1:-1, 2:]),
          bar=1e-6, exact_bar=1e-6)
    check("stencil_5pt_fused",
          pk.stencil_5pt_fused(old, 4, interpret=interp),
          lambda p: _stencil_reference(old, 4), bar=1e-6, exact_bar=1e-6)

    bq, bk, d = sizes.attn
    q, k, v = rnd(bq, d), rnd(bk, d), rnd(bk, d)
    acc, m, l = rnd(bq, d), rnd(bq, 1), jnp.abs(rnd(bq, 1)) + 1.0
    # offsets that put the causal diagonal THROUGH the block
    q_off, k_off, scale = bk // 2, 0, 1.0 / float(np.sqrt(d))
    check("flash_attention_block",
          pk.flash_attention_block(q, k, v, acc, m, l, q_off, k_off,
                                   causal=True, scale=scale,
                                   interpret=interp),
          lambda p: _flash_reference(q, k, v, acc, m, l, q_off, k_off,
                                     scale, p),
          bar=F32_BAR, exact_bar=BF16_BAR)
    return out


def _stencil_reference(grid, iters: int):
    import jax.numpy as jnp

    for _ in range(iters):
        p = jnp.pad(grid, 1)
        grid = 0.25 * (p[:-2, 1:-1] + p[2:, 1:-1]
                       + p[1:-1, :-2] + p[1:-1, 2:])
    return grid


def _flash_reference(q, k, v, acc, m, l, q_off: int, k_off: int,
                     scale: float, precision):
    """One causal online-softmax block update in plain jax.numpy."""
    import jax.numpy as jnp

    logits = jnp.matmul(q, k.T, precision=precision) * scale
    qpos = q_off + jnp.arange(q.shape[0])[:, None]
    kpos = k_off + jnp.arange(k.shape[0])[None, :]
    logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
    m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m - m_new)
    return (acc * corr + jnp.matmul(p, v, precision=precision), m_new,
            l * corr + p.sum(axis=-1, keepdims=True))


# ---------------------------------------------------------------------------
# stages: tile-granular dpotrf (Context, pump, pump + Pallas)
# ---------------------------------------------------------------------------

def _tiled(spd: np.ndarray, nb: int):
    from parsec_tpu.datadist import TiledMatrix

    n = spd.shape[0]
    return TiledMatrix(n, n, nb, nb, name="A",
                       dtype=np.float32).from_array(spd)


def _twice(stage: str, watch: CompileWatch, cache,
           once: Callable[[], "tuple[float, float]"],
           *, fixed_programs: bool = True) -> Dict[str, Any]:
    """Run ``once() -> (error, seconds the task graph took)`` cold then
    warm; ``*_s`` is the whole stage (building the tiled input, the run,
    bringing the result home, the check), ``*_run_s`` the graph alone.
    The warm run must hit the cache, and where the stage's program set
    is fixed by the DAG it may not compile at all.  (The Context path groups whatever is ready when its manager
    drains into waves, so a warm run can meet a wave size the cold run
    never formed: there the compiles are reported, not refused.)"""
    out: Dict[str, Any] = {}
    for phase in ("cold", "warm"):
        jax_before, cc_before = watch.snapshot(), cache.snapshot()
        t0 = time.perf_counter()
        err, run_s = once()
        out[f"{phase}_s"] = round(time.perf_counter() - t0, 3)
        out[f"{phase}_run_s"] = round(run_s, 3)
        out["err"] = err
        cc = cache.snapshot()
        out[phase] = dict(
            watch.since(jax_before),
            hits=cc["hits"] - cc_before["hits"],
            misses=cc.get("misses", 0) - cc_before.get("misses", 0))
        log(f"{stage} {phase}: {out[f'{phase}_s']} s (graph "
            f"{out[f'{phase}_run_s']} s), err {err:.2e}, {out[phase]}")
    warm = out["warm"]
    if not warm["hits"] or (fixed_programs and (
            warm["misses"] or warm["backend_compiles"])):
        raise RuntimeError(f"{stage}: the warm run compiled: {warm}")
    return out


def stage_context(sizes: Sizes, spd: np.ndarray, L_ref: np.ndarray,
                  watch: CompileWatch, platform: str) -> Dict[str, Any]:
    """dpotrf through Context.add_taskpool + tp.wait: the Python
    scheduling core and the device manager loop."""
    from parsec_tpu import Context
    from parsec_tpu.ops import cholesky_ptg

    ntasks = dpotrf_ntasks(sizes.tile_n // sizes.tile_nb)
    ctx = Context()
    try:
        dev = next(d for d in ctx.devices if d.mca_name == "tpu")
        require_platform(dev, platform)

        def once():
            A = _tiled(spd, sizes.tile_nb)
            tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(
                NT=A.mt, A=A)
            before = dev.stats["executed_tasks"]
            t0 = time.perf_counter()
            ctx.add_taskpool(tp)
            if not tp.wait(timeout=900):
                raise RuntimeError("context: dpotrf did not quiesce")
            _sync(A.data_of(A.mt - 1, A.nt - 1))
            run_s = time.perf_counter() - t0
            ran = dev.stats["executed_tasks"] - before
            if ran != ntasks:
                raise RuntimeError(
                    f"context: device executed {ran}/{ntasks} tasks")
            err = factor_error(A.to_array(), L_ref)
            require_close("context dpotrf", err, F32_BAR)
            return err, run_s

        out = _twice("context", watch, ctx.compile_cache, once,
                     fixed_programs=False)
        out["tasks"] = ntasks
        out["fallbacks"] = fallback_counters(dev, ctx.compile_cache)
        out["device"] = _device_stats(dev)
    finally:
        ctx.fini()
    require_no_fallback("context", out["fallbacks"])
    return out


def stage_pump(sizes: Sizes, spd: np.ndarray, L_ref: np.ndarray,
               watch: CompileWatch, platform: str, *,
               use_pallas: bool) -> Dict[str, Any]:
    """dpotrf through NativeExecutor(native_device=True): the native
    engine owns the lifecycle, one Python pump loop dispatches batches
    through the device module's wave path."""
    from parsec_tpu import compile_cache
    from parsec_tpu.dsl.native_exec import NativeExecutor
    from parsec_tpu.ops import cholesky_ptg

    stage = "pallas" if use_pallas else "pump"
    ntasks = dpotrf_ntasks(sizes.tile_n // sizes.tile_nb)
    cache = compile_cache.default_cache()
    since = cache.snapshot()
    shared: Dict[str, Any] = {"dev": None}

    def once():
        A = _tiled(spd, sizes.tile_nb)
        tp = cholesky_ptg(use_tpu=True, use_cpu=False,
                          use_pallas=use_pallas).taskpool(NT=A.mt, A=A)
        ex = NativeExecutor(tp, native_device=True, device=shared["dev"])
        dev = shared["dev"] = ex.device  # one device (and jit cache)
        require_platform(dev, platform)
        before = dev.stats["executed_tasks"]
        t0 = time.perf_counter()
        ran = ex.run()
        _sync(A.data_of(A.mt - 1, A.nt - 1))
        run_s = time.perf_counter() - t0
        s = ex.stats
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            raise RuntimeError(f"{stage}: not in pump mode: {s}")
        if ran != ntasks \
                or dev.stats["executed_tasks"] - before != ntasks:
            raise RuntimeError(f"{stage}: retired {ran}/{ntasks} tasks")
        shared["stats"] = dict(s)
        ex.close()  # flushes the device tiles home
        err = factor_error(A.to_array(), L_ref)
        require_close(f"{stage} dpotrf", err, F32_BAR)
        return err, run_s

    out = _twice(stage, watch, cache, once)
    dev = shared["dev"]
    out["tasks"] = ntasks
    out["executor"] = shared["stats"]
    out["fallbacks"] = fallback_counters(dev, cache, since)
    out["device"] = _device_stats(dev)
    require_no_fallback(stage, out["fallbacks"])
    if not out["device"]["wave_submits"]:
        raise RuntimeError(f"{stage}: no wave program was dispatched")
    return out


def _sync(data) -> None:
    """Wait for the newest copy of a tile (the DAG's last output): JAX
    dispatch is asynchronous, a timing without it measures the enqueue."""
    payload = data.newest_copy().payload
    if hasattr(payload, "block_until_ready"):
        payload.block_until_ready()


def _device_stats(dev) -> Dict[str, Any]:
    keep = ("executed_tasks", "wave_submits", "wave_tasks", "bytes_in",
            "bytes_out", "bytes_d2d", "evictions", "stage_batched_puts",
            "prefetched_tiles")
    out = {k: dev.stats.get(k, 0) for k in keep}
    out["jdev"] = str(dev.jdev)
    return out


# ---------------------------------------------------------------------------
# stage: north-star segmented dpotrf
# ---------------------------------------------------------------------------

def stage_segmented(sizes: Sizes, watch: CompileWatch,
                    platform: str) -> Dict[str, Any]:
    """SegmentedCholesky through the runtime on the KMS matrix
    ``A[i, j] = 2^-|i-j| + 3 [i == j]`` (provably SPD), built strip-wise
    on the device and checked by sampled reconstruction against that
    closed form — O(n * samples), no second n x n buffer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from parsec_tpu import Context
    from parsec_tpu.ops.segmented_chol import SegmentedCholesky

    n, nb = sizes.seg_n, sizes.seg_nb
    blk, nsamp = min(2048, n), min(256, n)

    ctx = Context()
    try:
        dev = next(d for d in ctx.devices if d.mca_name == "tpu")
        require_platform(dev, platform)

        @jax.jit
        def make_kms():
            def strip(i, A):
                r = i * blk + jnp.arange(blk, dtype=jnp.int32)[:, None]
                c = jnp.arange(n, dtype=jnp.int32)[None, :]
                s = jnp.exp2(-jnp.abs(r - c).astype(jnp.float32))
                return lax.dynamic_update_slice(A, s, (i * blk, 0))

            A = lax.fori_loop(0, n // blk, strip,
                              jnp.zeros((n, n), jnp.float32,
                                        device=dev.jdev))
            return A.at[jnp.arange(n), jnp.arange(n)].add(3.0)

        @jax.jit
        def gate(L):
            idx = jnp.sort(jax.random.choice(
                jax.random.key(3), n, (nsamp,), replace=False))
            rows = L[idx, :].astype(jnp.float32)
            rows = rows * (jnp.arange(n)[None, :] <= idx[:, None])
            rec = jnp.matmul(rows, rows.T, precision=lax.Precision.HIGHEST)
            d = jnp.abs(idx[:, None] - idx[None, :]).astype(jnp.float32)
            want = jnp.exp2(-d) + 3.0 * jnp.eye(nsamp, dtype=jnp.float32)
            return jnp.abs(rec - want).max() / 4.0  # max |A| = 1 + 3

        sc = SegmentedCholesky(ctx, n, nb)

        def once():
            A = make_kms().block_until_ready()
            t0 = time.perf_counter()
            L = sc.run(A).block_until_ready()
            run_s = time.perf_counter() - t0
            if L.shape != (n, n) or L.dtype != jnp.float32:
                raise RuntimeError(f"segmented: got {L.shape} {L.dtype}")
            err = float(gate(L))
            require_close("segmented dpotrf", err, BF16_BAR)
            return err, run_s

        float(gate(make_kms()))  # compile the generator and the gate
        out = _twice("segmented", watch, ctx.compile_cache, once)
        out.update(n=n, nb=nb, tasks=sc.nt_tasks,
                   fallbacks=fallback_counters(dev, ctx.compile_cache),
                   device=_device_stats(dev))
    finally:
        ctx.fini()
    require_no_fallback("segmented", out["fallbacks"])
    return out


# ---------------------------------------------------------------------------
# stage: four chips, one Context per chip
# ---------------------------------------------------------------------------

def stage_mesh(sizes: Sizes, platform: str, nranks: int = 4) -> Dict[str, Any]:
    """PTG dpotrf, 2x2 block-cyclic, one rank per chip in ONE process:
    four Contexts over InprocFabric, rank r drives
    ``jax.local_devices()[r]``, tiles cross ranks as device arrays."""
    import jax

    from parsec_tpu.datadist import TwoDimBlockCyclic
    from parsec_tpu.multirank import run_multirank_perf
    from parsec_tpu.ops import cholesky_ptg

    n, nb = sizes.mesh_n, sizes.mesh_nb
    spd = np.asarray(make_spd(n, 11, jax.local_devices()[0]))
    L_ref = np.linalg.cholesky(spd.astype(np.float64))
    ranks: Dict[int, Any] = {}

    def build(r, ctx):
        dev = next(d for d in ctx.devices if d.mca_name == "tpu")
        require_platform(dev, platform)
        ranks[r] = (ctx, dev)
        A = TwoDimBlockCyclic(n, n, nb, nb, p=2, q=2, myrank=r, name="A",
                              dtype=np.float32)
        A.from_array(spd)
        tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(
            NT=A.mt, A=A)
        return tp, A

    t0 = time.perf_counter()
    mats, stats = run_multirank_perf(nranks, build, timeout=900)
    wall = round(time.perf_counter() - t0, 3)
    ntasks = dpotrf_ntasks(n // nb)
    if stats["executed_tasks"] != ntasks:
        raise RuntimeError(
            f"mesh: executed {stats['executed_tasks']}/{ntasks} tasks")
    if not stats["bytes_d2d"]:
        raise RuntimeError("mesh: no tile crossed ranks device-to-device")
    L = np.zeros((n, n), np.float32)
    for A in mats:
        L += A.to_array()  # each rank holds its own tiles, zeros elsewhere
    err = factor_error(L, L_ref)
    require_close("mesh dpotrf", err, F32_BAR)

    per_rank, fallbacks = [], {}
    for r in range(nranks):
        ctx, dev = ranks[r]
        peak = (dev.jdev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        if not dev.stats["executed_tasks"]:
            raise RuntimeError(f"mesh: rank {r} executed nothing")
        if platform == "tpu" and peak < (n * n * 4) // (4 * nranks):
            raise RuntimeError(
                f"mesh: rank {r} peak HBM {peak} B is trivial")
        per_rank.append(dict(_device_stats(dev), rank=r, jdev_id=dev.jdev.id,
                             peak_bytes_in_use=peak))
        for k, v in fallback_counters(dev, ctx.compile_cache).items():
            fallbacks[k] = fallbacks.get(k, 0) + v
    if len({p["jdev_id"] for p in per_rank}) != nranks:
        raise RuntimeError(f"mesh: ranks share chips: {per_rank}")
    require_no_fallback("mesh", fallbacks)
    log(f"mesh: {wall} s, err {err:.2e}, d2d {stats['bytes_d2d']} B")
    return {"n": n, "nb": nb, "tasks": ntasks, "wall_s": wall, "err": err,
            "bytes_d2d": stats["bytes_d2d"],
            "activations": stats["activations"], "ranks": per_rank,
            "fallbacks": fallbacks}


# ---------------------------------------------------------------------------
# set-up report: what was built, what was read from outside the tree
# ---------------------------------------------------------------------------

def stage_setup() -> Dict[str, Any]:
    """Build the native engine from native/src in THIS run and name
    everything outside the committed tree that can change behaviour."""
    import jax
    import jaxlib

    from parsec_tpu import compile_cache, native, tuning

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    t0 = time.perf_counter()
    lib = native.build_library(force=True)
    built_s = round(time.perf_counter() - t0, 2)
    if not native.available() or native.lib_path() != lib:
        raise RuntimeError(f"native engine: {native.build_error()}")
    store = compile_cache.default_store()
    return {
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "compiler": native.compiler_version(),
        "native_lib": os.path.basename(lib), "native_build_s": built_s,
        "cache_root": compile_cache.cache_root(),
        "cache_placed_by_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "xla_cache_dir": jax.config.jax_compilation_cache_dir,
        "exe_store_entries_at_start": store.count() if store else None,
        "tuning_entries_at_start": len(tuning.default_store().entries()),
    }


def final_lines(device: Dict[str, Any], report: Dict[str, Any]):
    """The last two stdout lines of a passing run: the summary, then the
    result the driver parses — ``ok`` and ``device`` and nothing else."""
    return (json.dumps({**report, "claim": None}),
            json.dumps({"ok": True, "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"])}}))


def main(sizes: Sizes = Sizes()) -> int:
    t_start = time.perf_counter()
    import jax

    from parsec_tpu import tuning  # alone in a directory: fails HERE

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device}")
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{device['platform']!r}); this check runs on the chip only",
              file=sys.stderr)
        return 2

    watch = CompileWatch()
    report: Dict[str, Any] = {"setup": stage_setup()}
    log(f"setup: {report['setup']}")
    jdev = jax.local_devices()[0]

    report["kernels"] = stage_kernels(sizes, jdev)

    spd_dev = make_spd(sizes.tile_n, 0, jdev)
    spd = np.asarray(spd_dev)
    del spd_dev
    L_ref = np.linalg.cholesky(spd.astype(np.float64))
    report["context"] = stage_context(sizes, spd, L_ref, watch, "tpu")
    report["pump"] = stage_pump(sizes, spd, L_ref, watch, "tpu",
                                use_pallas=False)
    report["pallas"] = stage_pump(sizes, spd, L_ref, watch, "tpu",
                                  use_pallas=True)
    del spd, L_ref
    report["segmented"] = stage_segmented(sizes, watch, "tpu")
    if len(devices) >= 4:
        report["mesh"] = stage_mesh(sizes, "tpu")

    report["tuning_entries_read"] = tuning.default_store().found
    report["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    report["wall_s"] = round(time.perf_counter() - t_start, 1)
    for line in final_lines(device, report):
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
