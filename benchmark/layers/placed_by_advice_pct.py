"""layer: device.  source: the context's ``selected_by_*`` counters.
moves: ``tile_solve_s``.  Of the tasks placed among several eligible
accelerators, the share that went where the tile they write lives
(``selected_by_owner``) or is advised to (``selected_by_advice``), in
percent; the others went by the inputs' bytes or by load.  100 is the
deployment.  Nothing to read where no task had a choice."""

_BY = ("selected_by_owner", "selected_by_advice", "selected_by_bytes",
       "selected_by_load")


def read(run):
    if not all(k in run.counters for k in _BY):
        return None
    placed = sum(run.counters[k] for k in _BY)
    if not placed:
        return None
    return 100.0 * (run.counters["selected_by_owner"]
                    + run.counters["selected_by_advice"]) / placed
