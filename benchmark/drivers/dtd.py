"""Tile dpotrf by Dynamic Task Discovery: ``DTDTaskpool(ctx)``, the
insertions of ``ops.cholesky.cholesky_dtd`` (DPLASMA's
``testing_dpotrf_dtd.c``: one ``insert_task`` a task, the graph unknown
to the runtime until each task arrives), ``tp.wait()``,
``tp.flush_all(A)``, ``tp.close()``.  One ``Context`` and one device
module live for the whole run, a fresh pool a solve.

A reading starts when the user creates the pool over host tiles and has
two ends: ``tile_solve_s`` when ``tp.wait()`` has returned and every
tile of the factor is ready on the device, ``tile_home_s`` when
``tp.flush_all(A)`` has returned and the whole factor is in host tiles.
Insertion is INSIDE the reading: it is the user's call into the
runtime.  (The reference flushes before it waits; the cell waits first
only to have its two ends.)  After the reading the tiles' residency is
handed back, as in ``context.py``.

It refuses at once a program that has no insertion form of the
factorization, or whose DTD path cannot leave a tile on the device until
its flush (``Device.flush_home``): such a program sends every version of
every 4 MiB tile home and is a different deployment.

**The process keeps its heap.**  A flush lands 3.44 GB in host buffers
that JAX allocates anew (one ``copy_to_host_async`` a tile), and the
solve's own copy of the input is freed as they land.  glibc gives freed
memory at the top of the heap back to the system and takes it again:
on the benchmark's machine every OTHER flush got fresh pages and took
0.95 s where the others took 0.31 (my chip runs, PR 39: strictly
alternating over 4 x 8 solves, 0.93-1.00 s every time with a
``malloc_trim`` a cycle), so a window's median jumped by 8% with the
parity of its readings.  ``open`` therefore pins the allocator as a
user of such a code does with ``MALLOC_TRIM_THRESHOLD_``: never trim,
serve a tile from the heap (``mallopt``).  The first solve still pays
for fresh pages; it is a warm-up solve.

**Every program in set-up.**  Through ``Context`` the device module
forms its waves from whatever is ready when its manager looks, in
power-of-two chunks: which sizes a solve asks for depends on the
schedule, and one that no warm-up solve happened to ask for would
compile inside the window.  So ``open`` asks for all of them: behind a
task that holds the manager (a stage-in hook that waits), 63
independent tasks of each update class pile up and go out as chunks of
32, 16, 8, 4, 2 and 1, then one task of each class alone.  The
configuration's ``fixed_program_set`` rests on this.
"""

from __future__ import annotations

import ctypes
import itertools
import threading
import time

import jax
import numpy as np

from benchmark import harness, ops_count
from benchmark.drivers import _common as c

try:
    from parsec_tpu.device.device import Device
    from parsec_tpu.ops.cholesky import cholesky_dtd, dpotrf_bodies
except ImportError as e:
    raise harness.BenchError(
        "spotrf_dtd_nb1024_1chip: this program has no insertion form of "
        f"the tile Cholesky (parsec_tpu.ops.cholesky.cholesky_dtd): {e}")
if not hasattr(Device, "flush_home"):
    raise harness.BenchError(
        "spotrf_dtd_nb1024_1chip: this program's device modules cannot "
        "bring a DTD tile home at its flush (Device.flush_home): every "
        "version of every tile would go home behind the task that wrote it")

#: the pool's own counters (``DTDTaskpool.counters``), summed over the
#: run's pools
_DTD_COUNTERS = ("dtd_inserted", "dtd_edges", "dtd_renames",
                 "dtd_window_stalls", "dtd_window_stall_s", "dtd_helped",
                 "dtd_flushed_tiles", "dtd_insert_done_s")
#: the largest wave the warm-up asks for: chunks of 32 .. 1
_WARM_WAVE = 63


def keep_the_heap() -> bool:
    """glibc neither trims the heap's top nor serves a tile by ``mmap``
    (module docstring).  False where the C library has no ``mallopt``."""
    m_trim_threshold, m_mmap_threshold = -1, -3
    try:
        libc = ctypes.CDLL("libc.so.6")
        # (-1: the threshold is a size_t, and no free top reaches it)
        return bool(libc.mallopt(m_mmap_threshold, 32 << 20)
                    and libc.mallopt(m_trim_threshold, -1))
    except (OSError, AttributeError):
        return False


def open(config, traffic, options, devices, platform):
    harness.log(f"dtd: the process keeps its heap (mallopt): "
                f"{keep_the_heap()}")
    return DtdDriver(config, options, platform)


class DtdDriver:
    def __init__(self, config, options, platform):
        from parsec_tpu import Context

        self.options = {"use_pallas": bool(options.get("use_pallas", False)),
                        "bf16_updates": bool(options.get("bf16_updates",
                                                         False))}
        self.ctx = Context()
        self.dev = c.tpu_device(self.ctx)
        c.require_platform(self.dev, platform)
        self.dtd = dict.fromkeys(_DTD_COUNTERS, 0)
        self._warm_programs(int(config["nb"]))

    # ------------------------------------------------------------------
    def _warm_programs(self, nb: int) -> None:
        """Every program a solve can ask for, asked for once (module
        docstring)."""
        import jax.numpy as jnp

        from parsec_tpu.data import data_create
        from parsec_tpu.dsl import DTDTaskpool, IN, INOUT

        bodies = dpotrf_bodies(use_tpu=True, use_cpu=False, **self.options)
        eye = np.eye(nb, dtype=np.float32)
        ids = itertools.count()

        def tile(value):
            return data_create(("warm", next(ids)), payload=value.copy())

        for count in (_WARM_WAVE, 1):
            entered, opened = threading.Event(), threading.Event()

            def hold(data, owner):
                entered.set()
                opened.wait(120)
                return jnp.asarray(data.get_copy(0).payload)

            def gate(x):
                return ()  # it reads; it writes nothing

            gate._stage_in = {0: hold}
            tp = DTDTaskpool(self.ctx)
            if tp.window <= 3 * count + 2:
                # (the inserter would be held at the full window behind
                # the gate it is to open)
                raise harness.BenchError(
                    f"dtd_window_size {tp.window} is smaller than the "
                    f"warm-up's {3 * count + 2} tasks")
            made = [tile(eye[:8, :8])]
            before = self.dev.stats["executed_tasks"]
            tp.insert_task({self.dev.device_type: gate}, (made[0], IN),
                           name="gate")
            tp.context.start()
            if not entered.wait(120):
                raise harness.BenchError("the warm-up's gate never ran")
            lower, full = tile(2 * eye), tile(eye + 1)
            made += [lower, full]
            for _ in range(count):
                t, a, g = tile(eye), tile(4 * eye), tile(eye)
                made += [t, a, g]
                tp.insert_task(bodies["trsm"], (lower, IN), (t, INOUT),
                               name="trsm")
                tp.insert_task(bodies["syrk"], (a, INOUT), (full, IN),
                               name="syrk")
                tp.insert_task(bodies["gemm"], (g, INOUT), (full, IN),
                               (full, IN), name="gemm")
            p = tile(2 * eye)
            made.append(p)
            tp.insert_task(bodies["potrf"], (p, INOUT), name="potrf")
            # the workers hand the tasks to the device's queue, where
            # they wait behind the manager the gate holds
            deadline = time.monotonic() + 60
            while len(self.dev._pending) < 3 * count + 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            opened.set()
            done = tp.wait(timeout=900)
            tp.close()
            executed = self.dev.stats["executed_tasks"] - before
            if not done or executed != 3 * count + 2:
                raise harness.BenchError(
                    f"warm-up of {count}-task waves: quiesced {done}, "
                    f"executed {executed} of {3 * count + 2}")
            for d in made:
                self.dev.drop_residency(d)

    # ------------------------------------------------------------------
    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix
        from parsec_tpu.dsl import DTDTaskpool

        A = c.fresh_matrix(TiledMatrix, problem)
        keys = c.local_keys(A, problem)
        before = self.counters()
        with jax.profiler.TraceAnnotation("bench:solve"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:run"):
                tp = DTDTaskpool(self.ctx)
                inserted = cholesky_dtd(tp, A, **self.options)
                quiesced = tp.wait(timeout=900)
                c.sync(A, keys)
            t2 = time.perf_counter()
            out_before_flush = self.dev.stats["bytes_out"]
            with jax.profiler.TraceAnnotation("bench:flush"):
                tp.flush_all(A)
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(A, keys)
            t4 = time.perf_counter()
        tp.close()
        own = tp.counters()
        for k in _DTD_COUNTERS:
            self.dtd[k] += own[k]

        after = self.counters()
        ntasks = ops_count.dpotrf_ntasks(A.mt)
        violations = c.task_violations(before, after, ntasks, done=quiesced)
        if inserted != ntasks or own["dtd_inserted"] != ntasks:
            violations.append(f"{inserted} tasks inserted "
                              f"({own['dtd_inserted']} counted) of {ntasks}")
        if own["dtd_renames"]:
            violations.append(f"{own['dtd_renames']} tiles renamed: the "
                              "right-looking order has no WAR hazard")
        early = out_before_flush - before["bytes_out"]
        home = after["bytes_out"] - before["bytes_out"]
        lower = ops_count.lower_tiles_bytes(problem["n"], problem["nb"])
        if early or home != lower:
            violations.append(
                f"{early} bytes went home before the flush, {home} in all "
                f"(the factor is {lower})")
        if own["dtd_flushed_tiles"] != len(keys):
            violations.append(f"{own['dtd_flushed_tiles']} tiles flushed "
                              f"of {len(keys)}")
        if after["evictions"] != before["evictions"]:
            violations.append("a resident matrix was evicted from")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4,
                "matrix": (A, keys)}

    def release(self, solve) -> None:
        A, keys = solve.pop("matrix")
        for k in keys:
            self.dev.drop_residency(A.data_of(*k))

    def counters(self):
        out = c.device_counters([self.dev], [self.ctx.compile_cache])
        out.update(self.dtd)
        return out

    def close(self) -> None:
        self.ctx.fini()
