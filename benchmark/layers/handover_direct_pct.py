"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Of the tasks the device managers took (``n`` of the ``dev:wave`` /
``dev:submit_one`` spans), the share a manager queued itself on the
thread that released them (``direct`` of the same spans:
``TpuDevice.keep_released``), in percent; the others came through the
scheduler and a worker: the initially ready tasks, what an insertion or a
remote activation made ready, a class that a CPU can run too.  Nothing
to read from a program whose spans carry no ``direct`` (every commit
before PR 40)."""

from benchmark.trace import reduce as tr
from benchmark.trace import spans


def read(run):
    if spans.of_run(run) is None:
        return None
    trace = spans.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    took = [sp for sp in spans.clip_spans(trace.spans, trace.windows)
            if sp.name in spans.TASK_SPANS and "direct" in sp.args]
    tasks = sum(int(sp.args.get("n", 1)) for sp in took)
    if not tasks:
        return None
    return 100.0 * sum(int(sp.args["direct"]) for sp in took) / tasks
