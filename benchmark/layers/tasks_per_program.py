"""layer: scheduler.  source: the device module's counters.  moves:
``tile_solve_s``.  Tasks executed over device programs submitted: wave
programs plus the tasks that went alone."""


def read(run):
    c = run.counters
    programs = c["wave_submits"] + c["executed_tasks"] - c["wave_tasks"]
    return c["executed_tasks"] / programs if programs else None
