"""One Gaussian log-likelihood evaluation of the Matérn model, in two
precisions, through ``NativeExecutor(native_device=True)``: ``pump.py``'s
calling sequence and its clocks over ``ops/mle.py``'s taskpool.  The
device (and its jit cache) lives for the whole run; each solve gets new
collections, a new taskpool and a new executor, as an optimizer's step
does: the same locations and observations, another theta.

A reading starts when the taskpool is handed to the runtime (executor
construction) and has two ends: ``tile_solve_s`` when the likelihood's
parts (the two reductions and ``y``) are ready on the device,
``tile_home_s`` when they are host values after ``ex.close()``.  Building
the collections and the taskpool is outside a reading, as for dpotrf.
NOTHING of the matrix is inside or outside a reading at home: its tiles
are born on the device (``TiledMatrix(device_born=True)``), factored in
place and stay there; after the reading the driver gathers the rows the
check wants from the resident tiles, and ``release`` lets go of them.

What a solve is held to beside the check of its values (``violations``):
every task on the device in pump mode, every fallback counter 0, no more
staged in than the locations, the observations, theta and the two zeroed
reductions, no more brought home than ``y`` and the reductions, no
eviction, no scratch tile across the host, the matrix's tiles the only
ones left of those born, every tile of the factor in the precision of
the map, every float32 tile that a bfloat16 update reads converted once,
a finite likelihood, and an attach plan found from the session's second
solve on although theta differs.

A program without ``parsec_tpu.ops.mle``, or whose tiled matrix knows no
precision a tile and no tile born on the device, cannot run the
configuration: the cell refuses it at once (990 lazily zeroed float32
host tiles would be 16.6 GB staged in and as much brought home).
"""

from __future__ import annotations

import inspect
import math
import time

import jax
import numpy as np

from benchmark import harness, ops_count_mle
from benchmark.drivers import _common as c
from benchmark.drivers import pump
from parsec_tpu.datadist import TiledMatrix

try:
    from parsec_tpu.ops import mle
except ImportError as e:
    raise harness.BenchError(
        "smle_matern_mp_nb2048_1chip: this program has no "
        f"parsec_tpu.ops.mle ({e})")
if not {"tile_dtype", "device_born"} <= set(
        inspect.signature(TiledMatrix.__init__).parameters):
    raise harness.BenchError(
        "smle_matern_mp_nb2048_1chip: this program's TiledMatrix has one "
        "precision and host tiles: the matrix cannot be born on the device "
        "in two precisions")

#: counters of the device module that a solve is held to or that a
#: per-layer metric reads, beside ``_common._DEVICE_COUNTERS``
_COUNTERS = ("scratch_tiles_born", "scratch_tiles_freed",
             "scratch_bytes_in", "scratch_bytes_out", "evict_dirty",
             "evict_bytes_home", "convert_tiles", "convert_bytes",
             "convert_shared_hits", "wave_signatures")
#: of them, what must not move in a solve
_ZERO = ("evictions", "scratch_bytes_in", "scratch_bytes_out",
         "evict_dirty", "evict_bytes_home")


def open(config, traffic, options, devices, platform):
    return PumpMle(config, options, platform)


class PumpMle(pump.Pump):
    def __init__(self, config, options, platform):
        super().__init__(options, platform)
        #: the control stores every tile in bfloat16 (band_f32 = 0)
        self.band = int(options.get("band_f32", config["band_f32"]))
        self.solves = 0
        #: the residency's bytes at its peak, summed over the solves
        self.resident_peak_bytes = 0
        self._take = None
        self._held = None

    def solve(self, problem):
        from parsec_tpu.dsl.native_exec import NativeExecutor

        n, nb, nt = problem["n"], problem["nb"], problem["nt"]
        theta = problem["theta"](self.solves)
        cols = mle.mle_collections(n, nb, self.band, problem["x"],
                                   problem["z"], theta)
        A, Y, SC = cols["A"], cols["Y"], cols["SC"]
        tp = mle.mle_taskpool(**cols, band_f32=self.band)
        ntasks = ops_count_mle.ntasks(nt, self.band)
        ykeys = [(i, 0) for i in range(nt)]
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
                c.sync(SC, [(0, 0), (1, 0)])
                c.sync(Y, ykeys)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:flush"):
                ex.close()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                y = np.concatenate([v for _k, v in sorted(
                    c.gather_home(Y, ykeys).items())])
                logdet, dot = mle.loglik_parts(SC)
            t4 = time.perf_counter()

        s, after = ex.stats, self.counters()
        self.pop_batches += s["pop_batches"]
        self.solves += 1
        self.resident_peak_bytes += sum(
            self.dev.stats.get("tiles_by_dtype", {}).values())
        violations = c.task_violations(before, after, ntasks,
                                       done=ran == ntasks)
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            violations.append(f"not in pump mode: {dict(s)}")
        moved = {k: after[k] - before.get(k, 0) for k in after}
        most_in = ops_count_mle.input_bytes(n)
        most_home = ops_count_mle.result_bytes(n)
        if moved["bytes_in"] > most_in or moved["bytes_out"] > most_home:
            violations.append(
                f"{moved['bytes_in']} bytes went in (locations, z, theta, "
                f"two zeroed sums: {most_in}), {moved['bytes_out']} came "
                f"home (y and the two sums: {most_home})")
        bad = {k: moved[k] for k in _ZERO if moved[k]}
        if bad:
            violations.append("a tile was evicted or a scratch tile "
                              f"crossed the host: {bad}")
        kept = moved["scratch_tiles_born"] - moved["scratch_tiles_freed"]
        if kept != nt * (nt + 1) // 2:
            violations.append(
                f"{moved['scratch_tiles_born']} tiles born on the device, "
                f"{moved['scratch_tiles_freed']} freed: the matrix's "
                f"{nt * (nt + 1) // 2} should be what is left")
        twins = ops_count_mle.converted_tiles(nt, self.band)
        if moved["convert_tiles"] != twins:
            violations.append(
                f"{moved['convert_tiles']} conversions for {twins} float32 "
                "tiles with a bfloat16 reader: each is converted once")
        if s["attach_plan_uncacheable"] \
                or (self.solves > 1 and s["attach_plan_hits"] != 1):
            violations.append(f"solve {self.solves} bound no stored attach "
                              f"plan: {dict(s)}")
        if not (math.isfinite(logdet) and math.isfinite(dot)):
            violations.append(f"the likelihood is not finite: logdet "
                              f"{logdet}, dot {dot}")
        with jax.profiler.TraceAnnotation("bench:rows"):
            rows, diag, wrong = self.read_factor(A, problem)
        if wrong:
            violations.append("tiles of the factor not in the precision "
                              f"of the map: {wrong[:4]}")
        self._held = cols  # until release(): the check is outside
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": {"theta": theta, "rows": rows, "diag": diag,
                           "y": y, "logdet": logdet, "dot": dot,
                           "loglik": mle.loglik(logdet, dot, n)},
                "violations": violations, "t_done": t4}

    def read_factor(self, A, problem):
        """What the check wants of the factor, gathered where the tiles
        live: ``problem["local_rows"]`` of every tile and the diagonal.
        Returns ``(rows, diagonal, tiles of another dtype than the
        map's)``."""
        if self._take is None:
            self._take = (
                jax.jit(lambda t, at: t[at].astype(np.float32)),
                jax.jit(lambda t: np.float32(1) * t.diagonal()))
        take, diagonal = self._take
        local = {i: jax.device_put(at, self.dev.jdev)
                 for i, at in problem["local_rows"].items()}
        rows, diag, wrong = {}, [], []
        for (i, j) in A.tiles():
            tile = A.data_of(i, j).newest_copy().payload
            if tile.dtype != A.dtype_of(i, j):
                wrong.append(((i, j), str(tile.dtype)))
            rows[(i, j)] = take(tile, local[i])
            if i == j:
                diag.append(diagonal(tile))
        rows = dict(zip(rows, jax.device_get(list(rows.values()))))
        return rows, np.concatenate(jax.device_get(diag)), wrong

    def release(self, solve) -> None:
        """``ex.close()`` detached the device; the matrix's tiles, which
        nobody wrote home, go with their collection."""
        self._held = None

    def counters(self):
        out = super().counters()
        stats = self.dev.stats if self.dev else {}
        for k in _COUNTERS:
            out[k] = stats.get(k, 0)
        out["resident_peak_bytes"] = self.resident_peak_bytes
        return out

    def close(self) -> None:
        self._held = None
        super().close()
