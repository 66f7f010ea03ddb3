"""layer: scheduler.  source: the program's ``parsec-wait:*`` events in
the profiler's trace (``benchmark/trace/waits.py`` loads them).  moves:
``tile_solve_s``.
Seconds a solve that the inserting thread was held at a full window
(``wait:dtd_window``: in flight ``dtd_window_size`` tasks, until the
backlog is down to ``dtd_threshold_size``), helping to execute
meanwhile.  Long where execution is the slower side, 0.0 where
insertion is (the window never fills).  Nothing to read from a program
whose DTD carries no span."""

from benchmark.trace import reduce as tr
from benchmark.trace import spans, waits

STALL = waits.WAIT + "dtd_window"


def read(run):
    s = spans.of_run(run)
    if s is None or "core:dtd_insert" not in s.self_ns:
        return None
    trace = waits.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    stalls = [sp for sp in trace.spans if sp.name == STALL]
    held = sum(sp.end - sp.start
               for sp in spans.clip_spans(stalls, trace.windows))
    return held / 1e9 / len(trace.windows)
