"""Hierarchical tile sgeqrf (DPLASMA's ``dgeqrf_param`` over a reduction
tree: TS domains under a binary TT tree) of a tall matrix through
``NativeExecutor(native_device=True)``: ``pump_geqrf.py``'s calling
sequence and its four clocks, over the tile-QR PTG instantiated on the
configuration's tree.  The device (and its jit cache) lives for the whole
run; each solve gets a new executor and a new tree object.

A reading starts when the taskpool over host tiles is handed to the
runtime (executor construction) and has two ends: ``tile_solve_s`` when
every upper tile of R is ready on the device, ``tile_home_s`` when R's
upper tiles are host arrays after ``ex.close()`` has flushed every tile
home.  Building the tiled matrix, the tree object (two integers and the
domain size: its tables are the attach's to build, and a bound attach
plan builds none) and the taskpool is outside a reading.  The tiles that
are not R's (the zeros that took A's place) are read back after the
reading, for the check alone.

A program whose ``qr_ptg`` takes no tree cannot run this deployment at
all (its panel is one chain, its matrix square): the cell refuses it at
once instead of timing something else.
"""

from __future__ import annotations

import importlib.util
import inspect
import time

import jax
import numpy as np

from benchmark import harness, ops_count_geqrf_hqr
from benchmark.drivers import _common as c


def _tree_class():
    """The program's reduction tree, or the refusal."""
    if importlib.util.find_spec("parsec_tpu.ops.qr_tree") is not None:
        from parsec_tpu.ops.qr import qr_ptg
        from parsec_tpu.ops.qr_tree import QRTree

        if "tree" in inspect.signature(qr_ptg).parameters:
            return QRTree
    raise harness.BenchError(
        "sgeqrf_hqr_nb512_1chip: this program's qr_ptg takes no reduction "
        "tree (parsec_tpu/ops/qr_tree.py): it cannot factor a tall matrix "
        "by TS domains under a binary TT tree")


QRTree = _tree_class()

#: counters of the device module read where they are
_SCRATCH_COUNTERS = ("scratch_tiles_born", "scratch_tiles_freed",
                     "scratch_bytes_in", "scratch_bytes_out",
                     "tile_args_dropped")


def open(config, traffic, options, devices, platform):
    if config.get("low_tree") != "binary":
        raise harness.BenchError(
            f"low_tree {config.get('low_tree')!r}: the program's tree is "
            "TS domains under a BINARY TT tree")
    return PumpGeqrfHqr(int(config["qr_a"]), options, platform)


def fresh_matrix(problem, buffers=None):
    """A tiled M x N matrix over copies of the seed's host tiles (the
    runtime may write into a tile it is given).  ``buffers`` (key ->
    array, filled here) are the copies' memory, kept from solve to solve:
    4 GiB of newly allocated pages a solve cost a window its fifth
    reading (my chip run, PR 43); the last solve's tiles, which may live
    there, are checked before the next is made."""
    from parsec_tpu.datadist import TiledMatrix

    nb = problem["nb"]
    A = TiledMatrix(problem["m"], problem["n"], nb, nb, name="A",
                    dtype=np.float32)
    buffers = {} if buffers is None else buffers
    for key, tile in problem["tiles"].items():
        mine = buffers.get(key)
        if mine is None:
            mine = buffers[key] = np.empty_like(tile)
        np.copyto(mine, tile)
        d = A.data_of(*key)
        copy = d.get_copy(0) or d.attach_copy(0, mine)
        copy.payload = mine
    return A


def hqr_taskpool(A, tree, options):
    """The tile-QR PTG over ``A`` and ``tree``, device chores only; the
    control switches the lower-precision update bodies on."""
    from parsec_tpu.ops.qr import qr_ptg

    kw = {"bf16_updates": True} if options.get("bf16_updates") else {}
    nb = A.mb
    return qr_ptg(tree, use_tpu=True, use_cpu=False, **kw).taskpool(
        NT=A.nt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=A.default_dtype,
        QSHAPE2=(A.default_dtype, (2 * nb, 2 * nb)))


class PumpGeqrfHqr:
    def __init__(self, qr_a, options, platform):
        from parsec_tpu import compile_cache

        self.qr_a = qr_a
        self.options = options
        self.platform = platform
        self.dev = None
        self.cache = compile_cache.default_cache()
        self.pop_batches = 0
        self.plan_hits = 0
        #: the sum over the solves of each solve's high-water mark of
        #: live scratch bytes (the device's mark is put back to 0 before
        #: a solve: nothing of the last one is alive then)
        self.scratch_peak_sum = 0
        self.buffers = {}

    def solve(self, problem):
        from parsec_tpu.dsl.native_exec import NativeExecutor

        A = fresh_matrix(problem, self.buffers)
        upper = [k for k in problem["tiles"] if k[0] <= k[1]]
        rest = [k for k in problem["tiles"] if k[0] > k[1]]
        tree = QRTree(A.mt, A.nt, self.qr_a)
        tp = hqr_taskpool(A, tree, self.options)
        ntasks = ops_count_geqrf_hqr.hqr_ntasks(A.mt, A.nt, self.qr_a)
        if self.dev is not None and "scratch_bytes_peak" in self.dev.stats:
            self.dev.stats["scratch_bytes_peak"] = 0
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
        tiles.update(c.gather_home(A, rest))

        s = ex.stats
        self.pop_batches += s["pop_batches"]
        self.plan_hits += s.get("attach_plan_hits", 0)
        self.scratch_peak_sum += self.dev.stats.get("scratch_bytes_peak", 0)
        after = self.counters()
        violations = c.task_violations(before, after, ntasks,
                                       done=ran == ntasks)
        if not s["pop_batches"] or s["pumped_tasks"] != ntasks \
                or s["trampoline_entries"] or s["completion_callbacks"]:
            violations.append(f"not in pump mode: {dict(s)}")
        moved = {k: after[k] - before.get(k, 0)
                 for k in ("scratch_bytes_in", "scratch_bytes_out")}
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
        out["attach_plan_hits"] = self.plan_hits
        out["scratch_peak_sum"] = self.scratch_peak_sum
        stats = self.dev.stats if self.dev else {}
        for k in _SCRATCH_COUNTERS:
            out[k] = stats.get(k, 0)
        return out

    def close(self) -> None:
        self.dev = None
        self.buffers = {}
