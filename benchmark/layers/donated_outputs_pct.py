"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Of the outputs that the device programs' epilogs commit (``outs`` of the
``dev:wave`` / ``dev:submit_one`` spans), the share written over the
input version of the same tile (``don`` of the same spans: a read-write
flow whose input nobody else reads was DONATED to the program, so the
call allocated no buffer for that output), in percent.  Not donated: a
``NEW`` flow's first version and every other output that has no input of
its own (a stencil's generations: 0), a version that somebody else still
reads or that is on its way home, and everything of a pool whose builder
said nothing (``Context`` PTG pools) or that runs on several ranks.
Nothing to read from a program whose spans carry no ``don`` (every commit
before PR 41)."""

from benchmark.trace import reduce as tr
from benchmark.trace import spans


def read(run):
    if spans.of_run(run) is None:
        return None
    trace = spans.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    took = [sp for sp in spans.clip_spans(trace.spans, trace.windows)
            if sp.name in spans.TASK_SPANS and "don" in sp.args]
    outs = sum(int(sp.args.get("outs", 0)) for sp in took)
    if not outs:
        return None
    return 100.0 * sum(int(sp.args["don"]) for sp in took) / outs
