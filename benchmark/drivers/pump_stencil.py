"""Iterative 2D 5-point stencil through
``NativeExecutor(native_device=True)``: ``pump.py``'s calling sequence
and its clocks, over the stencil's taskpool instead of dpotrf's.  The
device (and its jit cache) lives for the whole run; each solve gets a new
executor.

A reading starts when the taskpool over host tiles is handed to the
runtime (executor construction) and has two ends: ``tile_solve_s`` when
every tile of generation T is ready on the device, ``tile_home_s`` when
they are host arrays after ``ex.close()`` has flushed them home.
Building the tiled matrices and the taskpool is outside a reading, as for
dpotrf.  Generation 0 is read from the seed's host tiles as they are (no
task writes them); generation T lands in a second matrix whose tiles have
no host value before.

What a solve is held to beside the check of its values (``violations``):
every task on the device in pump mode, every fallback counter 0, the grid
staged in ONCE and brought home ONCE, no byte of an intermediate
generation across the host, as many generations freed as born, no dirty
eviction, and an attach plan found from the session's second solve on.

A program whose stencil writes every generation into a tile of a
collection sends each of them home: T grids a solve, 429 GB at n = 32768
and T = 100 (every task's output is a last version to the runtime: no
later task WRITES the flow), and stages a second, zeroed grid in.  It
cannot hold the guarantees above, so the cell refuses it at once, by what
the PTG itself says — the ``stencil`` class's ``NEW`` flow has no
``<- NEW`` source — instead of timing it.
"""

from __future__ import annotations

import re
import time

import jax
import numpy as np

from benchmark import harness, ops_count_stencil
from benchmark.drivers import _common as c
from benchmark.drivers import pump
from parsec_tpu.ops import stencil


def born_on_the_device(ptg) -> bool:
    """The stencil's ``NEW`` flow takes a fresh tile (``<- NEW``, guarded
    or not) and not a tile of a collection for every generation."""
    flows = [f for f in ptg.classes["stencil"].flows if f.name == "NEW"]
    for dep in (flows[0].deps_in if flows else ()):
        branches = re.split(r"[?:]", dep.src.replace("<-", " "))
        if any(b.split("[")[0].strip() == "NEW" for b in branches):
            return True
    return False


if not born_on_the_device(stencil.stencil_ptg(use_tpu=True, use_cpu=False)):
    raise harness.BenchError(
        "sstencil_2d5pt_nb4096_1chip: this program's stencil writes every "
        "generation into a tile of a collection (its NEW flow has no "
        "'<- NEW' source), so every generation goes home: 100 grids "
        "(429 GB) a solve; the cell's guarantee that the grid goes in "
        "once and comes home once cannot be held")

#: counters of the device module that a solve is held to or that a
#: per-layer metric reads, beside ``_common._DEVICE_COUNTERS``
_COUNTERS = ("scratch_tiles_born", "scratch_tiles_freed",
             "scratch_bytes_in", "scratch_bytes_out", "evict_clean",
             "evict_dirty", "evict_bytes_home", "tile_args_passed",
             "tile_args_repeated")
#: of them, what must not move in a solve
_ZERO = ("scratch_bytes_in", "scratch_bytes_out", "evict_dirty",
         "evict_bytes_home")


def open(config, traffic, options, devices, platform):
    return PumpStencil(config, options, platform)


class PumpStencil(pump.Pump):
    def __init__(self, config, options, platform):
        super().__init__(options, platform)
        self.iters = int(config["iters"])
        self.solves = 0

    def taskpool(self, A, B):
        """The stencil's PTG over ``A`` -> ``B``, device chores only; the
        control switches the lower-precision sweep on."""
        kw = {"bf16_updates": True} if self.options.get("bf16_updates") \
            else {}
        return stencil.stencil_taskpool(A, self.iters, B=B, use_tpu=True,
                                        use_cpu=False, **kw)

    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix
        from parsec_tpu.dsl.native_exec import NativeExecutor

        n, nb = problem["n"], problem["nb"]
        A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32)
        for key, tile in problem["tiles"].items():
            d = A.data_of(*key)  # read only: the seed's tile as it is
            (d.get_copy(0) or d.attach_copy(0, tile)).payload = tile
        B = TiledMatrix(n, n, nb, nb, name="B", dtype=np.float32)
        keys = list(problem["tiles"])
        tp = self.taskpool(A, B)
        ntasks = ops_count_stencil.stencil_ntasks(n, nb, self.iters)
        grid = ops_count_stencil.grid_bytes(n)
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
                c.sync(B, keys)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:flush"):
                ex.close()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(B, keys)
            t4 = time.perf_counter()

        s, after = ex.stats, self.counters()
        self.pop_batches += s["pop_batches"]
        self.solves += 1
        violations = c.task_violations(before, after, ntasks,
                                       done=ran == ntasks)
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            violations.append(f"not in pump mode: {dict(s)}")
        moved = {k: after[k] - before.get(k, 0) for k in after}
        if moved["bytes_in"] != grid or moved["bytes_out"] != grid:
            violations.append(
                f"the grid is {grid} bytes: {moved['bytes_in']} went in, "
                f"{moved['bytes_out']} came home")
        bad = {k: moved[k] for k in _ZERO if moved[k]}
        if bad:
            violations.append("a generation crossed the host or a dirty "
                              f"tile was evicted: {bad}")
        if moved["scratch_tiles_born"] != moved["scratch_tiles_freed"]:
            violations.append(
                f"{moved['scratch_tiles_born']} generation tiles born, "
                f"{moved['scratch_tiles_freed']} freed")
        if s["attach_plan_uncacheable"] \
                or (self.solves > 1 and s["attach_plan_hits"] != 1):
            violations.append(f"solve {self.solves} bound no stored attach "
                              f"plan: {dict(s)}")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4}

    def counters(self):
        out = super().counters()
        stats = self.dev.stats if self.dev else {}
        for k in _COUNTERS:
            out[k] = stats.get(k, 0)
        return out
