"""Tile-granular dpotrf through ONE ``Context`` that drives several
accelerators (``testing_dpotrf -g 4``): one scheduler, one device module
per chip, tiles advised onto the chips 2D-cyclic, a device chosen per
task, tiles moved chip to chip by the runtime.

The context is built when the driver is opened and is checked there, and
a program whose ``Context`` takes no ``accelerators`` is refused when this
file is loaded, before a tile is made: a cell that the program cannot run
says so at once.

A solve is fresh host tiles (the seed's values copied into the solve's
matrix, whose 300 buffers of 64 MiB are the driver's own and are used
again by the next solve, as a user factoring again fills the matrix they
have: ``_fresh_matrix``), the advice (``advise_data_on_devices``: the
deployment's call before the factorization), then the reading:
``ctx.add_taskpool(tp)`` starts it; ``tile_solve_s`` ends when the pool
has quiesced and every tile of the factor is ready on the chip that
computed it, ``tile_home_s`` when every module's write-backs are flushed
and the factor is in host tiles.  After it residency is handed back on
every chip.  Every guarantee of the configuration's file is held here,
solve by solve.

The process's allocator is left as glibc has it (no ``mallopt``, where
``drivers/dtd.py`` pins its own): the cell's two driver sets of PR 50 lay
0.3% apart without a pin, and how a 64 MiB tile lands at home is the
finding this cell is there to show, not to tune.
"""

from __future__ import annotations

import inspect
import time

import jax
import numpy as np

from benchmark import harness, ops_count
from benchmark.drivers import _common as c
from parsec_tpu import Context

if "accelerators" not in inspect.signature(Context.__init__).parameters:
    raise harness.BenchError(
        "spotrf_tile_nb4096_g4: this program's Context takes no "
        "`accelerators`: it attaches one device module, and cannot drive "
        "four chips under one scheduler")

#: what must stay 0 beside ``_common._DEVICE_FALLBACKS``: room that could
#: not be made under a chip's budget, a tile resident and charged to
#: nobody, a copy home that took a way around
_MORE_FALLBACKS = ("wb_alias_fallbacks", "reserve_gave_up",
                   "unaccounted_tiles")
#: per-module counters the guarantees and the readers use, summed
_SUMMED = ("d2d_tiles", "peer_copies_dropped", "peer_holds_refused",
           "evict_dirty", "evict_clean", "tile_args_donated",
           "donation_refused")
_PLACED = ("selected_by_owner", "selected_by_advice", "selected_by_bytes",
           "selected_by_load")


def open(config, traffic, options, devices, platform):
    return ContextG4(config, options, devices, platform)


def advised_shares(nt: int, p: int, q: int):
    """Tasks of the lower tile Cholesky on ``nt x nt`` tiles by the
    accelerator their written tile is advised to, ``(m mod p) * q +
    (n mod q)`` for tile (m, n): potrf(k) writes (k, k), trsm(k, m)
    (m, k), syrk(k, m) (m, m), gemm(k, m, n) (m, n)."""
    share = [0] * (p * q)

    def at(m, n):
        return (m % p) * q + (n % q)

    for k in range(nt):
        share[at(k, k)] += 1
        for m in range(k + 1, nt):
            share[at(m, k)] += 1
            share[at(m, m)] += 1
            for n in range(k + 1, m):
                share[at(m, n)] += 1
    return share


class ContextG4:
    def __init__(self, config, options, devices, platform):
        self.options = options
        self.g = int(config["accelerators"])
        self.grid = tuple(config["device_grid"])
        if len(devices) < self.g:
            raise harness.BenchError(
                f"{self.g} accelerators need as many chips, got "
                f"{len(devices)}")
        #: the solve's matrix: (i, j) -> its host buffer, kept
        self._buffers = {}
        self.ctx = Context(nb_cores=2, accelerators=self.g)
        try:
            self.devs = [d for d in self.ctx.devices if d.mca_name == "tpu"]
            for dev in self.devs:
                c.require_platform(dev, platform)
            chips = {d.jdev.id for d in self.devs}
            if len(self.devs) != self.g or len(chips) != self.g \
                    or self.ctx.nranks != 1:
                raise harness.BenchError(
                    f"one Context over {self.g} accelerators: got "
                    f"{len(self.devs)} modules on chips {sorted(chips)}, "
                    f"nranks {self.ctx.nranks}")
        except BaseException:
            self.ctx.fini()
            raise

    def solve(self, problem):
        from parsec_tpu.datadist import TiledMatrix, advise_data_on_devices

        A = self._fresh_matrix(TiledMatrix, problem)
        keys = c.local_keys(A, problem)
        advise_data_on_devices(A, self.devs, self.grid, uplo="lower")
        tp = c.dpotrf_taskpool(A, self.options)
        before = self.counters()
        each0 = [self._each(d) for d in self.devs]
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
                for dev in self.devs:
                    dev.flush()
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:home"):
                tiles = c.gather_home(A, keys)
            t4 = time.perf_counter()

        after = self.counters()
        violations = c.task_violations(
            before, after, ops_count.dpotrf_ntasks(A.mt), done=quiesced)
        each = [{k: v - b[k] for k, v in self._each(d).items()}
                for d, b in zip(self.devs, each0)]
        share = [e["executed_tasks"] for e in each]
        want = advised_shares(A.mt, *self.grid)
        if share != want:
            violations.append(f"the chips executed {share} tasks, the "
                              f"advice gives them {want}")
        matrix = ops_count.lower_tiles_bytes(problem["n"], problem["nb"])
        for name in ("bytes_in", "bytes_out"):
            moved = after[name] - before[name]
            if moved != matrix:
                violations.append(f"{name} {moved}: the lower matrix once "
                                  f"is {matrix}")
        if not all(e["bytes_d2d"] > 0 for e in each):
            violations.append("a chip landed no tile chip to chip: "
                              f"{[e['bytes_d2d'] for e in each]}")
        if after["evict_dirty"] - before["evict_dirty"]:
            violations.append("a dirty tile was evicted")
        return {"times": {"tile_solve_s": t2 - t0, "tile_home_s": t4 - t0,
                          "attach_s": t1 - t0, "flush_s": t3 - t2},
                "result": tiles, "violations": violations, "t_done": t4,
                "matrix": (A, keys)}

    def _fresh_matrix(self, cls, problem):
        """``_common.fresh_matrix`` into buffers that stay: the runtime
        lands the factor in buffers of its own and lets go of these, the
        next solve fills them again (20 GB of fresh pages a solve less:
        module docstring of the configuration's reference)."""
        n, nb = problem["n"], problem["nb"]
        A = cls(n, n, nb, nb, name="A", dtype=np.float32)
        for key, tile in problem["tiles"].items():
            buf = self._buffers.get(key)
            if buf is None:
                buf = self._buffers[key] = np.empty_like(tile)
            np.copyto(buf, tile)
            d = A.data_of(*key)
            copy = d.get_copy(0) or d.attach_copy(0, buf)
            copy.payload = buf
        return A

    @staticmethod
    def _each(dev):
        return {k: dev.stats.get(k, 0)
                for k in ("executed_tasks", "bytes_d2d")}

    def release(self, solve) -> None:
        A, keys = solve.pop("matrix")
        for k in keys:
            data = A.data_of(*k)
            for dev in self.devs:
                dev.drop_residency(data)

    def counters(self):
        out = c.device_counters(self.devs, [self.ctx.compile_cache])
        for dev in self.devs:
            out["fallbacks"] += sum(dev.stats.get(k, 0)
                                    for k in _MORE_FALLBACKS)
            for k in _SUMMED:
                out[k] = out.get(k, 0) + dev.stats.get(k, 0)
        for k in _PLACED:
            out[k] = self.ctx.stats.get(k, 0)
        return out

    def close(self) -> None:
        self.ctx.fini()
