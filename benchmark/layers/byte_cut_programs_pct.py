"""layer: device.  source: the program's ``parsec:*`` spans in the
profiler's trace.  moves: ``tile_solve_s``.
Of the wave programs (``dev:wave`` spans: one a program), the share whose
width the byte bound set (``cut`` = ``bytes``): the tiles its tasks bring
onto the device would have passed ``Residency.chunk_limit`` at the next
power of two, though the tasks for it were there.  The others (``cut`` =
``tasks``) took the largest power of two of the tasks their wave had
left.  0 where every wave fits its bound; where it is high, a wider
program waits for memory that the count says is not there: scratch tiles
only read that were born on the device cost nothing (PR 45), tiles with a
home and tiles written count whatever their state.  Nothing to read from a program
whose spans carry no ``cut`` (every commit before PR 45)."""

from benchmark.trace import reduce as tr
from benchmark.trace import spans


def share(took):
    """Of the ``dev:wave`` spans among ``took`` that say what cut them,
    the share the bound cut, in percent; None without one."""
    cuts = [sp.args["cut"] for sp in took
            if sp.name == "dev:wave" and "cut" in sp.args]
    return 100.0 * cuts.count("bytes") / len(cuts) if cuts else None


def read(run):
    if spans.of_run(run) is None:
        return None
    trace = spans.load(tr.find_xplane(spans.trace_dir(run.cell.name)))
    return share(spans.clip_spans(trace.spans, trace.windows))
