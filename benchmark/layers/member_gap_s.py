"""layer: scheduler.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Seconds a solve between two members of a compound, over its
``pump:member_gap`` spans (two a solve of three pools): from the
predecessor's last retirement (the end of the last ``pump:done`` span
before the gap, on the pump's thread) to the successor's first
``dev:dispatch`` (the first to start after the gap began).  The span
itself ends where the successor hands its first batch to the device;
the staging walk and the program look-up up to the enqueue are the
reader's to add, since a span that ended inside ``dev:submit_batch``
would not nest.  Nothing to read from a program without the span."""

from benchmark.trace import reduce as tr
from benchmark.trace import spans


def read(run):
    if spans.of_run(run) is None:
        return None
    trace = spans.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    mine = spans.clip_spans(trace.spans, trace.windows)
    gaps = [sp for sp in mine if sp.name == "pump:member_gap"]
    if not gaps or not trace.windows:
        return None
    total = 0
    for gap in gaps:
        same = [sp for sp in mine if sp.thread == gap.thread]
        start = max((sp.end for sp in same
                     if sp.name == "pump:done" and sp.end <= gap.start),
                    default=gap.start)
        end = min((sp.start for sp in same
                   if sp.name == "dev:dispatch" and sp.start >= gap.start),
                  default=gap.end)
        total += end - start
    return total / 1e9 / len(trace.windows)
