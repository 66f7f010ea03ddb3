"""Comm/compute overlap measured from binary-trace timestamps — the
reference's stencil overlap study at test scale (BASELINE.json tracks
overlap % for the 64-chip stencil config; the metric pipeline is what
this pins: trace -> merged exec spans -> comm instants -> fraction)."""

import threading

import numpy as np
import pytest

from parsec_tpu import Context, native
from parsec_tpu.comm import InprocFabric
from parsec_tpu.ops.stencil import stencil_grid, stencil_taskpool
from parsec_tpu.profiling import pins
from parsec_tpu.profiling.binary import BinaryTaskProfiler, to_chrome_events
from parsec_tpu.profiling.tools import comm_overlap_fraction

pytestmark = pytest.mark.skipif(
    not native.available(), reason=f"native core unavailable: {native.build_error()}")


def test_stencil_overlap_fraction_from_trace(tmp_path):
    """2-rank stencil with halo exchanges: record exec spans + comm
    instants, dump the binary trace, and compute the overlap fraction
    offline.  Pins the metric pipeline end-to-end: events exist, the
    fraction is well-defined, and busy time is positive."""
    prof = BinaryTaskProfiler()
    k_recv = prof.trace.keyword("comm_recv")
    k_send = prof.trace.keyword("comm_send")
    subs = []

    def sub(site, cb):
        pins.subscribe(site, cb)
        subs.append((site, cb))

    sub(pins.COMM_ACTIVATE, lambda es, info: prof.trace.instant(k_send))
    sub(pins.COMM_DATA_PLD, lambda es, info: prof.trace.instant(k_recv))

    nranks, T, MT, NT, tile = 2, 6, 2, 2, 96
    grids = {}
    try:
        fabric = InprocFabric(nranks)
        ces = fabric.endpoints()
        ctxs = [Context(nb_cores=2, rank=r, nranks=nranks, comm=ces[r])
                for r in range(nranks)]
        oks = [None] * nranks

        def worker(r):
            rng = np.random.default_rng(5)
            g = rng.standard_normal((MT * tile, NT * tile))
            # row distribution (rank = i % nranks): UP/DOWN halos cross
            # ranks every iteration
            A = stencil_grid(g, MT, NT, p=nranks, myrank=r)
            grids[r] = A
            tp = stencil_taskpool(A, T, use_cpu=True)
            ctxs[r].add_taskpool(tp)
            oks[r] = tp.wait(timeout=120)

        ts = [threading.Thread(target=worker, args=(r,))
              for r in range(nranks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=150)
        assert all(oks), oks
        for c in ctxs:
            c.fini()
    finally:
        for site, cb in subs:
            pins.unsubscribe(site, cb)
        prof.uninstall()

    path = str(tmp_path / "stencil.pbt")
    prof.trace.dump(path)
    events = to_chrome_events(path)
    frac, n_comm, busy_us = comm_overlap_fraction(events)
    # halo exchanges really crossed ranks, compute really ran, and the
    # fraction is a valid probability
    assert n_comm > 0
    assert busy_us > 0
    assert 0.0 <= frac <= 1.0
    print(f"overlap fraction {frac:.2f} over {n_comm} comm events, "
          f"busy {busy_us / 1e3:.1f} ms")


def test_stencil_overlap_mesh_scale_floor():
    """The NAMED overlap config — 2D5pt stencil halo exchange — at mesh
    scale (4 ranks here; the dryrun runs 8) with device chores, via the
    shared measure_overlap helper.  Every task ran, halos crossed ranks,
    every rank both communicated and computed, and the PER-RANK mean
    (each rank's comm vs its own compute) is a share; how large is the
    host's scheduling, and no floor is held."""
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    import __graft_entry__ as ge

    stats = ge._dryrun_stencil_overlap(4)
    assert stats["tasks"] == 6 * 8 * 4
    assert stats["activations"] > 0
    assert stats["n_comm_events"] > 0
    assert 0.0 <= stats["overlap_min"] <= stats["overlap_fraction"] <= 1.0, \
        stats
