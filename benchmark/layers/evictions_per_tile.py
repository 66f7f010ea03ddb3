"""layer: device.  source: the device module's ``evictions``.  moves:
``tile_solve_s``.  Device copies dropped to make room per solve over the
lower tiles of the matrix: 0 is a matrix that fits the budget, and at
least (tiles - budget) / tiles has to leave whatever the policy."""


def read(run):
    dropped = run.per_solve("evictions")
    if dropped is None:
        return None
    nt = run.size("n") // run.size("nb")
    return dropped / (nt * (nt + 1) // 2)
