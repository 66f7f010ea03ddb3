"""Distributed panel-segmented Cholesky (round-3 VERDICT #7): the
north-star segmented formulation across ranks with device chores and
device-native panel broadcasts, plus the comm/compute overlap fraction
measured from the native binary tracer at multi-rank scale."""

import pytest

from parsec_tpu import native
from parsec_tpu.ops.segmented_chol_dist import run_dist_segmented_cholesky


def test_dist_segmented_cholesky_4ranks():
    err, stats = run_dist_segmented_cholesky(4, 256, 32)
    assert err < 1e-3, err
    nt = 256 // 32
    # every panel and every update task really ran, somewhere
    assert stats["executed_tasks"] == nt + nt * (nt - 1) // 2
    # panel broadcasts really crossed ranks...
    assert stats["activations"] > 0
    # ...and landed device-to-device (no host bounce on the inproc
    # device-capable fabric)
    assert stats["bytes_d2d"] > 0


@pytest.mark.skipif(not native.available(),
                    reason="binary tracer needs the native core")
def test_dist_segmented_cholesky_8ranks_overlap():
    """The 8-rank artifact: PER-RANK overlap from one binary trace
    stream per rank at the dryrun mesh scale.  How much comm hides
    under compute on the CPU backend is the host's scheduling, so the
    fraction is held to no floor (speed is the benchmark's to judge);
    what IS held: the numerics, that every rank both communicated and
    computed (8 per-rank fractions, each rank's comm against its OWN
    compute), and that each fraction is a share."""
    err, stats = run_dist_segmented_cholesky(8, 512, 64, trace_pins=True)
    assert err < 1e-3, err
    assert stats["n_comm_events"] > 0
    assert stats["busy_us"] > 0
    # every rank both communicated and computed: 8 per-rank fractions
    per_rank = [f for f in stats["overlap_per_rank"] if f is not None]
    assert len(per_rank) == 8, stats["overlap_per_rank"]
    assert all(0.0 <= f <= 1.0 for f in per_rank), per_rank
    assert 0.0 <= stats["overlap_min"] <= stats["overlap_fraction"] <= 1.0
