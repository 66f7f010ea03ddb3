"""layer: scheduler.  source: the taskpools' own counter
``dtd_insert_done_s`` through the driver's ``counters()``, over the
host clock's ``tile_solve_s``.  moves: ``tile_solve_s``.
The share of a solve that had passed when the last insertion returned
(pool created -> ``insert_task`` of the last task back), in percent.
Near 100 the discovery paces the solve: the graph is known only when the
work is nearly done, because the window kept the inserter back behind a
slower execution, or because inserting is the slower side
(``dtd_window_stall_s`` tells the two apart).  Low, the graph was known
early and execution alone sets the time, as in a cell whose DAG is
enumerated."""


def read(run):
    done = run.per_solve("dtd_insert_done_s")
    solve = run.median("tile_solve_s")
    if done is None or not solve:
        return None
    return 100.0 * done / solve
