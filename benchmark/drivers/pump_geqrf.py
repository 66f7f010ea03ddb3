"""Tile-granular sgeqrf (PLASMA's TS kernels) through
``NativeExecutor(native_device=True)``: ``pump.py``'s calling sequence
and its four clocks, over the tile-QR PTG instead of dpotrf's.  The
device (and its jit cache) lives for the whole run; each solve gets a new
executor.

A reading starts when the taskpool over host tiles is handed to the
runtime (executor construction) and has two ends: ``tile_solve_s`` when
every upper tile of R is ready on the device, ``tile_home_s`` when R's
upper tiles are host arrays after ``ex.close()`` has flushed them home.
Building the tiled matrix and the taskpool (with the shapes of its
scratch tiles) is outside a reading, as for dpotrf.  The lower tiles (the
zeros that took A's place) are read back after the reading, for the
check alone.

A program from before scratch tiles lived on the device stages the dense
Q blocks through the host (2,016 MiB each way a solve at N=16384, and
every intermediate version of every tile home: 24 s a solve, my chip
run, PR 26): it cannot hold the configuration's guarantee
``scratch_bytes_in = scratch_bytes_out = 0``, so the cell refuses it at
once instead of timing it.
"""

from __future__ import annotations

import importlib.util
import time

import jax

from benchmark import harness, ops_count_geqrf
from benchmark.drivers import _common as c

if importlib.util.find_spec("parsec_tpu.device.scratch") is None:
    raise harness.BenchError(
        "sgeqrf_tile_nb512_1chip: this program has no device-resident "
        "scratch tiles (parsec_tpu/device/scratch.py); the cell's "
        "guarantee that no tile of a NEW flow crosses the host cannot "
        "be held")

#: counters of the device module that later programs have and earlier
#: ones lack: read where they are, left out where they are not
_SCRATCH_COUNTERS = ("scratch_tiles_born", "scratch_tiles_freed",
                     "scratch_bytes_in", "scratch_bytes_out",
                     "tile_args_dropped")


def open(config, traffic, options, devices, platform):
    return PumpGeqrf(options, platform)


def geqrf_taskpool(A, options):
    """The tile-QR PTG over ``A``, device chores only; the control
    switches the lower-precision update bodies on."""
    from parsec_tpu.ops.qr import qr_ptg

    kw = {"bf16_updates": True} if options.get("bf16_updates") else {}
    nb = A.mb
    return qr_ptg(use_tpu=True, use_cpu=False, **kw).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=A.default_dtype,
        QSHAPE2=(A.default_dtype, (2 * nb, 2 * nb)))


class PumpGeqrf:
    def __init__(self, options, platform):
        from parsec_tpu import compile_cache

        self.options = options
        self.platform = platform
        self.dev = None
        self.cache = compile_cache.default_cache()
        self.pop_batches = 0

    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix
        from parsec_tpu.dsl.native_exec import NativeExecutor

        A = c.fresh_matrix(TiledMatrix, problem)
        upper = [k for k in problem["tiles"] if k[0] <= k[1]]
        lower = [k for k in problem["tiles"] if k[0] > k[1]]
        tp = geqrf_taskpool(A, self.options)
        ntasks = ops_count_geqrf.geqrf_ntasks(A.mt)
        before = self.counters()
        with jax.profiler.TraceAnnotation("bench:solve"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:attach"):
                ex = NativeExecutor(tp, native_device=True, device=self.dev)
            t1 = time.perf_counter()
            if self.dev is None:
                self.dev = ex.device
                c.require_platform(self.dev, self.platform)
            with jax.profiler.TraceAnnotation("bench:run"):
                ran = ex.run()
                c.sync(A, upper)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:flush"):
                ex.close()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(A, upper)
            t4 = time.perf_counter()
        tiles.update(c.gather_home(A, lower))

        s, after = ex.stats, self.counters()
        self.pop_batches += s["pop_batches"]
        violations = c.task_violations(before, after, ntasks,
                                       done=ran == ntasks)
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            violations.append(f"not in pump mode: {dict(s)}")
        moved = {k: after[k] - before.get(k, 0)
                 for k in ("scratch_bytes_in", "scratch_bytes_out")
                 if k in after}
        if any(moved.values()):
            violations.append(f"scratch tiles crossed the host: {moved}")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4}

    def release(self, solve) -> None:
        """``ex.close()`` detached the device: nothing stays resident."""

    def counters(self):
        out = c.device_counters([self.dev] if self.dev else [],
                                [self.cache])
        out["pop_batches"] = self.pop_batches
        stats = self.dev.stats if self.dev else {}
        for k in _SCRATCH_COUNTERS:
            if k in stats:
                out[k] = stats[k]
        return out

    def close(self) -> None:
        self.dev = None
