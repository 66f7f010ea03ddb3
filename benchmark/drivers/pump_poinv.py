"""The tile inverse of a symmetric positive definite matrix (DPLASMA's
``dplasma_dpoinv_sync``: ``dpotrf``, ``dtrtri``, ``dlauum``, three
taskpools over the same tiles) through ONE
``NativeExecutor(poinv(A), native_device=True, device=...)``: ``pump.py``'s
driver, calling sequence and clocks, over a compound of three pools.  The
device (and its jit cache) lives for the whole run; each solve gets a new
compound and a new executor.

A reading starts when the compound over host tiles is handed to the
runtime (executor construction: the three members are planned and bound
there) and has two ends: ``tile_solve_s`` when every lower tile of A^-1
is ready on the device, ``tile_home_s`` when the lower triangle is back in
host tiles after ``ex.close()``.  Building the tiled matrix from the
seed's tiles and the three taskpool objects is outside a reading.

What a solve is held to beside ``pump.py``'s guarantees: the three
members ran, in order, every one of their tasks on the device; nothing
went home from a member whose versions a later one rewrites
(``member_home_bytes``) and no later member staged a tile in from the host
(``member_restaged_tiles``); bytes onto the device and bytes home are each
the lower matrix once; nothing was evicted.

Two refusals, both at import.  A program without ``parsec_tpu.ops.poinv``
cannot run the deployment at all.  A program whose ``NativeExecutor``
takes no compound could only run it as three executors, each of whose
``close()`` sends the matrix home for the next to stage in again: another
deployment, so the cell refuses it instead of timing that.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import harness, ops_count, ops_count_poinv
from benchmark.drivers import _common as c
from benchmark.drivers import pump

try:
    from parsec_tpu.ops import poinv
except ImportError:
    raise harness.BenchError(
        "spoinv_tile_nb2048_1chip: this program has no parsec_tpu.ops."
        "poinv (dpotrf, dtrtri and dlauum composed over one matrix)") \
        from None


def _takes_a_compound() -> None:
    """Two one-tile pools composed, through the numpy executor: a
    ``NativeExecutor`` that takes a compound runs both."""
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.dsl.native_exec import NativeExecutor

    A = TiledMatrix(2, 2, 2, 2, name="A", dtype=np.float32)
    A.from_array(np.eye(2, dtype=np.float32))
    try:
        ex = NativeExecutor(poinv(A, use_tpu=False, use_cpu=True))
        try:
            ran = ex.run()
        finally:
            ex.close()
    except (AttributeError, TypeError) as e:
        raise harness.BenchError(
            "spoinv_tile_nb2048_1chip: this program's NativeExecutor takes "
            f"no compound taskpool ({type(e).__name__}: {e}); as three "
            "executors the matrix goes home and comes back between the "
            "members") from None
    if ran != ops_count_poinv.poinv_ntasks(1) \
            or "members_run" not in ex.stats:
        raise harness.BenchError(
            "spoinv_tile_nb2048_1chip: NativeExecutor over a compound ran "
            f"{ran} tasks and counts no members")


_takes_a_compound()


def open(config, traffic, options, devices, platform):
    return PumpPoinv(options, platform)


def poinv_compound(A, options):
    """``poinv`` over ``A``, device chores only; ``options`` are the
    configuration's (its control switches the lower-precision updates of
    all three members on)."""
    return poinv(A, use_tpu=True, use_cpu=False,
                 use_pallas=bool(options.get("use_pallas", False)),
                 bf16_updates=bool(options.get("bf16_updates", False)))


class PumpPoinv(pump.Pump):
    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix
        from parsec_tpu.dsl.native_exec import NativeExecutor

        A = c.fresh_matrix(TiledMatrix, problem)
        keys = c.local_keys(A, problem)
        tp = poinv_compound(A, self.options)
        ntasks = ops_count_poinv.poinv_ntasks(A.mt)
        lower = ops_count.lower_tiles_bytes(problem["n"], problem["nb"])
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
        each = [m.nb_retired for m in tp.members]
        if s["members_run"] != ops_count_poinv.MEMBERS \
                or each != [ops_count_poinv.member_ntasks(A.mt)] * len(each):
            violations.append(f"{s['members_run']} members ran, their "
                              f"tasks retired {each}")
        if s["member_home_bytes"] or s["member_restaged_tiles"]:
            violations.append(
                f"{s['member_home_bytes']} bytes went home from a member "
                f"that was not their last writer's, "
                f"{s['member_restaged_tiles']} tiles were staged in again "
                "by a later member")
        moved = {k: after[k] - before[k]
                 for k in ("bytes_in", "bytes_out", "evictions")}
        if moved != {"bytes_in": lower, "bytes_out": lower, "evictions": 0}:
            violations.append(f"the lower matrix is {lower} bytes: {moved}")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4}
