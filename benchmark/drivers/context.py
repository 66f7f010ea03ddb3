"""Tile-granular dpotrf through ``Context.add_taskpool`` + ``tp.wait``:
the Python scheduling core and the device manager.  One ``Context`` lives
for the whole run.

A reading starts at ``add_taskpool`` over host tiles and has two ends:
``tile_solve_s`` when every tile of the factor is ready on the device,
``tile_home_s`` when the device module's write-backs are flushed and the
factor is in host tiles.  After the reading the tiles' residency is
handed back (``drop_residency``), as a caller that has read its result
does, so that no solve pays for the evictions of the ones before it.
"""

from __future__ import annotations

import time

import jax

from benchmark import ops_count
from benchmark.drivers import _common as c


def open(config, traffic, options, devices, platform):
    return ContextDriver(options, platform)


class ContextDriver:
    def __init__(self, options, platform):
        from parsec_tpu import Context

        self.options = options
        self.ctx = Context()
        self.dev = c.tpu_device(self.ctx)
        c.require_platform(self.dev, platform)

    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix

        A = c.fresh_matrix(TiledMatrix, problem)
        keys = c.local_keys(A, problem)
        tp = c.dpotrf_taskpool(A, self.options)
        before = self.counters()
        with jax.profiler.TraceAnnotation("bench:solve"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:attach"):
                self.ctx.add_taskpool(tp)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:run"):
                quiesced = tp.wait(timeout=900)
                c.sync(A, keys)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:flush"):
                self.dev.flush()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(A, keys)
            t4 = time.perf_counter()

        violations = c.task_violations(
            before, self.counters(), ops_count.dpotrf_ntasks(A.mt),
            done=quiesced)
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4,
                "matrix": (A, keys)}

    def release(self, solve) -> None:
        A, keys = solve.pop("matrix")
        for k in keys:
            self.dev.drop_residency(A.data_of(*k))

    def counters(self):
        return c.device_counters([self.dev], [self.ctx.compile_cache])

    def close(self) -> None:
        self.ctx.fini()
