"""Tile-granular dpotrf over ``TwoDimBlockCyclic(p, q)``: one ``Context``
per chip in ONE process over ``InprocFabric``, rank r on
``jax.local_devices()[r]``, tiles crossing ranks as device arrays.  The
recipe is ``parsec_tpu.multirank.run_multirank_perf``'s; here the
contexts live for the whole run and one thread per rank drives each solve.

A reading starts when every rank has built its taskpool over its host
tiles and they hand them to their contexts together (a barrier), and has
two ends: ``tile_solve_s`` when the slowest rank's tiles are ready on its
chip, ``tile_home_s`` when the slowest rank's factor is in host tiles.
"""

from __future__ import annotations

import threading
import time

import jax

from benchmark import ops_count
from benchmark.drivers import _common as c


def open(config, traffic, options, devices, platform):
    return Mesh(config, options, devices, platform)


class Mesh:
    def __init__(self, config, options, devices, platform):
        from parsec_tpu import Context
        from parsec_tpu.comm import InprocFabric

        self.p, self.q = config["grid"]
        self.nranks = self.p * self.q
        if len(devices) < self.nranks:
            raise RuntimeError(f"{self.nranks} ranks need as many chips, "
                               f"got {len(devices)}")
        self.options = options
        ces = InprocFabric(self.nranks).endpoints()
        self.ctxs = [Context(nb_cores=2, rank=r, nranks=self.nranks,
                             comm=ces[r]) for r in range(self.nranks)]
        self.devs = [c.tpu_device(ctx) for ctx in self.ctxs]
        for dev in self.devs:
            c.require_platform(dev, platform)
        self.distinct = len({d.jdev.id for d in self.devs}) == self.nranks

    def solve(self, problem):
        from parsec_tpu.datadist import TwoDimBlockCyclic

        nr = self.nranks
        start = threading.Barrier(nr + 1)
        out = [None] * nr
        errs = []

        def rank(r):
            try:
                A = c.fresh_matrix(TwoDimBlockCyclic, problem, p=self.p,
                                   q=self.q, myrank=r)
                keys = c.local_keys(A, problem)
                tp = c.dpotrf_taskpool(A, self.options)
                start.wait()
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:attach:{r}"):
                    self.ctxs[r].add_taskpool(tp)
                t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:run:{r}"):
                    quiesced = tp.wait(timeout=900)
                    t_wait = time.perf_counter()
                    c.sync(A, keys)
                t2 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:flush:{r}"):
                    self.devs[r].flush()
                t3 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:home:{r}"):
                    tiles = c.gather_home(A, keys)
                out[r] = dict(A=A, keys=keys, tiles=tiles, ok=quiesced,
                              t=(t0, t1, t_wait, t2, t3,
                                 time.perf_counter()))
            except BaseException as e:  # surfaced after the join
                errs.append((r, e))
                start.abort()

        before = self.counters()
        per_rank0 = [d.stats["executed_tasks"] for d in self.devs]
        threads = [threading.Thread(target=rank, args=(r,))
                   for r in range(nr)]
        for t in threads:
            t.start()
        try:
            start.wait()
        except threading.BrokenBarrierError:
            pass
        with jax.profiler.TraceAnnotation("bench:solve"):
            t_start = time.perf_counter()
            for t in threads:
                t.join(timeout=960)
        if errs or any(o is None for o in out):
            raise RuntimeError(f"rank errors: {errs}")

        after = self.counters()
        violations = c.task_violations(
            before, after, ops_count.dpotrf_ntasks(problem["nt"]),
            done=all(o["ok"] for o in out))
        share = [d.stats["executed_tasks"] - b
                 for d, b in zip(self.devs, per_rank0)]
        if not all(share):
            violations.append(f"a rank executed nothing: {share}")
        if after["bytes_d2d"] - before["bytes_d2d"] <= 0:
            violations.append("no tile crossed ranks device-to-device")
        if not self.distinct:
            violations.append("ranks share chips")

        tiles = {}
        for o in out:
            tiles.update(o["tiles"])
        ends = list(zip(*(o["t"] for o in out)))
        solve_s = max(ends[3]) - t_start
        return {"times": {
            "tile_solve_s": solve_s, "tile_home_s": max(ends[5]) - t_start,
            "attach_s": max(t1 - t0 for t0, t1, *_ in
                            (o["t"] for o in out)),
            "flush_s": max(o["t"][4] - o["t"][3] for o in out),
            "rank_skew_pct": 100.0 * (max(ends[2]) - min(ends[2])) / solve_s},
            "result": tiles, "violations": violations,
            "t_done": max(ends[5]),
            "matrices": [(o["A"], o["keys"]) for o in out]}

    def release(self, solve) -> None:
        for dev, (A, keys) in zip(self.devs, solve.pop("matrices")):
            for k in keys:
                dev.drop_residency(A.data_of(*k))

    def counters(self):
        return c.device_counters(
            self.devs, [ctx.compile_cache for ctx in self.ctxs])

    def close(self) -> None:
        for ctx in self.ctxs:
            ctx.fini()
