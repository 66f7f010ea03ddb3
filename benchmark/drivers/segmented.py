"""Panel-segmented dpotrf: ``SegmentedCholesky(ctx, n, nb)`` on a matrix
that is made and stays on the device; a chain of donated panel programs.

A reading (``panel_solve_s``) is ``sc.run(A).block_until_ready()``.
"""

from __future__ import annotations

import time

import jax

from benchmark.drivers import _common as c


def open(config, traffic, options, devices, platform):
    return Segmented(config, traffic, options, platform)


class Segmented:
    def __init__(self, config, traffic, options, platform):
        from parsec_tpu import Context
        from parsec_tpu.ops.segmented_chol import SegmentedCholesky

        self.ctx = Context()
        self.dev = c.tpu_device(self.ctx)
        c.require_platform(self.dev, platform)
        self.sc = SegmentedCholesky(
            self.ctx, int(config["n"]), int(config["nb"]),
            bf16=options.get("bf16", False))

    def solve(self, problem):
        A = problem["make"]()
        before = self.counters()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:solve"), \
                jax.profiler.TraceAnnotation("bench:run"):
            L = self.sc.run(A).block_until_ready()
        t1 = time.perf_counter()
        del A  # donated
        violations = c.task_violations(before, self.counters(),
                                       self.sc.nt_tasks)
        return {"times": {"panel_solve_s": t1 - t0}, "result": L,
                "violations": violations, "t_done": t1}

    def release(self, solve) -> None:
        """``sc.run`` already dropped the result's residency slot."""

    def counters(self):
        return c.device_counters([self.dev], [self.ctx.compile_cache])

    def close(self) -> None:
        self.ctx.fini()
