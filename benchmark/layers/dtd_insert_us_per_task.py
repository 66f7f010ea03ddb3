"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
self time of the ``core:dtd_insert`` spans (one a call of
``DTDTaskpool.insert_task`` on the inserting thread: argument parsing,
the task class, the per-tile dependency inference under the tile's lock,
the hand-over of a task that is ready) per task taken by the device
module: what discovering a task costs where enumerating it
(``tile_ctx_n8192``) costs nothing.  The wait at a full window is not in
it (``dtd_window_stall_s``).  Nothing to read from a program whose DTD
carries no span."""

from benchmark.trace import spans


def read(run):
    s = spans.of_run(run)
    if s is None or "core:dtd_insert" not in s.self_ns:
        return None
    return s.self_ns["core:dtd_insert"] / 1e3 / (s.tasks * s.solves)
