"""layer: device.  source: the profiler's trace.  moves: ``tile_solve_s``.
(busiest - idlest chip's busy seconds) over the busiest's, in percent, of
the traced solves: how evenly the device chosen per task loads the chips
of one ``Context``.  Nothing to read with one chip."""


def read(run):
    if not run.trace or len(run.trace.busy_by_chip) < 2:
        return None
    busy = list(run.trace.busy_by_chip.values())
    most = max(busy)
    return 100.0 * (most - min(busy)) / most if most else None
