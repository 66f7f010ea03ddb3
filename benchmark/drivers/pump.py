"""Tile-granular dpotrf through ``NativeExecutor(native_device=True)``:
the native engine owns the task lifecycle, one Python pump loop hands
batches to the device module's wave path.  The device (and its jit
cache) lives for the whole run; each solve gets a new executor, as
``chip_smoke.py``'s ``stage_pump`` does.

A reading starts when the taskpool over host tiles is handed to the
runtime (executor construction) and has two ends: ``tile_solve_s`` when
every tile of the factor is ready on the device, ``tile_home_s`` when the
factor is back in host tiles (``ex.close()`` flushes them home).
"""

from __future__ import annotations

import time

import jax

from benchmark import ops_count
from benchmark.drivers import _common as c


def open(config, traffic, options, devices, platform):
    return Pump(options, platform)


class Pump:
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
        keys = c.local_keys(A, problem)
        tp = c.dpotrf_taskpool(A, self.options)
        ntasks = ops_count.dpotrf_ntasks(A.mt)
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
                c.sync(A, keys)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:flush"):
                ex.close()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(A, keys)
            t4 = time.perf_counter()

        s, after = ex.stats, self.counters()
        self.pop_batches += s["pop_batches"]
        violations = c.task_violations(before, after, ntasks,
                                       done=ran == ntasks)
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            violations.append(f"not in pump mode: {dict(s)}")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4}

    def release(self, solve) -> None:
        """``ex.close()`` detached the device: nothing stays resident."""

    def counters(self):
        out = c.device_counters([self.dev] if self.dev else [],
                                [self.cache])
        out["pop_batches"] = self.pop_batches
        return out

    def close(self) -> None:
        self.dev = None
