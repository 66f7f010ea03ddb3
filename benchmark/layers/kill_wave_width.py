"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Tasks a device program of the classes geqrt, tsqrt and ttqrt: the sum of
``n`` over the ``dev:wave`` and ``dev:submit_one`` spans whose ``cls`` is
one of the three, over their number.  1 where a panel is one chain (the
square tile QR's kills come one at a time); the width of a reduction
tree's ready sets, as the pump's batches, the power-of-two rule and
``chunk_limit`` cut them.  Nothing to read from a program whose spans
carry no ``cls``."""

from benchmark import ops_count_geqrf_hqr as hqr
from benchmark.trace import reduce as tr
from benchmark.trace import spans


def read(run):
    if spans.of_run(run) is None:
        return None
    trace = spans.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    took = [int(sp.args.get("n", 1))
            for sp in spans.clip_spans(trace.spans, trace.windows)
            if sp.name in spans.TASK_SPANS
            and sp.args.get("cls") in hqr.KILLS]
    return sum(took) / len(took) if took else None
