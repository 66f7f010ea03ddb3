"""TCP backend tests: real multi-PROCESS ranks over sockets (the reference
tests "multi-node" as mpiexec multi-process on one node, SURVEY.md §4 —
this is the same shape with our launcher instead of mpiexec).

Each test spawns N subprocesses running tcp_driver.py scenarios; the
scenarios self-check and print a JSON result line.
"""

import json
import os

import pytest

from parsec_tpu.comm.launch import launch

DRIVER = os.path.join(os.path.dirname(__file__), "tcp_driver.py")


def run_scenario(name, nranks, timeout=180, extra_env=None):
    results = launch(nranks, [DRIVER, name], timeout=timeout,
                     env={"JAX_PLATFORMS": "cpu", **(extra_env or {})})
    out = []
    for r in results:
        line = r.stdout.strip().splitlines()[-1]
        out.append(json.loads(line))
    assert all(o["ok"] for o in out)
    return out


def test_tcp_smoke_2ranks():
    """AM batching, one-sided GET, barrier across 2 processes."""
    out = run_scenario("smoke", 2)
    assert all(o["ams"] == 3 for o in out)
    assert all(o["get_bytes"] == 65536 * 8 for o in out)


def test_tcp_smoke_4ranks():
    out = run_scenario("smoke", 4)
    assert all(o["ams"] == 9 for o in out)


def test_tcp_ptg_chain_2ranks():
    """Cross-process PTG chain: every dependency over the real wire."""
    out = run_scenario("ptg_chain", 2)
    ks = sorted(k for o in out for k in o["seen"])
    assert ks == list(range(12))


def test_tcp_ptg_bigpayload_get():
    """Above-short-limit payloads use the one-sided GET handshake."""
    out = run_scenario("ptg_bigpayload", 2)
    assert any(o["get_issued"] >= 1 for o in out if o["rank"] != 0)


def test_tcp_dtd_gemm_4ranks():
    """Distributed DTD GEMM across 4 real processes (shadow-task protocol
    + cross-rank flush over the wire, numerics checked per local tile)."""
    out = run_scenario("dtd_gemm", 4, timeout=300)
    assert sum(o["dtd_sent"] for o in out) > 0
    assert sum(o["dtd_sent"] for o in out) == sum(o["dtd_recv"] for o in out)
    # ragged tiles straddle the short limit: both wire paths saw traffic
    assert sum(o["dtd_inline"] for o in out) > 0
    assert sum(o["dtd_get"] for o in out) > 0


def test_tcp_ptg_qr_4ranks():
    """Distributed QR over real processes: NEW-flow Q blocks and
    cross-rank write-backs on the wire."""
    run_scenario("ptg_qr", 4)


def test_tcp_barrier_then_immediate_close():
    """Regression: queued barrier releases survive an immediate close()
    (flush-on-close in the comm thread)."""
    run_scenario("barrier_close", 4)


def test_tcp_send_then_immediate_close():
    """An AM sent in the same breath as close() must reach a peer that
    starts reading only later (the FIN handshake makes close() block
    until delivery is assured)."""
    out = run_scenario("send_then_close", 4)
    assert all(o["got"] == 1 for o in out if o["rank"] != 0)


def test_tcp_perf_smoke():
    """RTT/bandwidth through the real AM path (rtt.jdf/bandwidth.jdf
    shape). Not pinned — loose sanity floors; the measured numbers are
    printed."""
    out = run_scenario("perf", 2)
    r0 = next(o for o in out if o["rank"] == 0)
    print(f"\ntcp perf: rtt={r0['rtt_us']} us, bw={r0['mb_s']} MB/s")
    assert r0["rtt_us"] < 50000
    assert r0["mb_s"] > 100


@pytest.mark.parametrize("topo,root_sends", [
    ("star", 7), ("chain", 1), ("binomial", 3),
])
def test_tcp_broadcast_topologies(topo, root_sends):
    """The test_bcast.py pins, re-run over REAL TCP processes: async GET
    payload pulls and tree forwarding from inside GET callbacks."""
    out = run_scenario("bcast", 8, timeout=240,
                       extra_env={"PARSEC_MCA_runtime_bcast_topo": topo,
                                  "PARSEC_MCA_runtime_comm_short_limit": "1024"})
    by_rank = {o["rank"]: o for o in out}
    assert sum(o["sent"] for o in out) == 7
    assert by_rank[0]["sent"] == root_sends
    assert by_rank[0]["get_adv"] == root_sends
    for r in range(1, 8):
        assert by_rank[r]["recv"] == 1
    assert all(o["mem_left"] == 0 for o in out)
    fwd = sum(o["fwd"] for o in out)
    assert (fwd == 0) if topo == "star" else (fwd > 0)


def test_tcp_dist_dpotrf_2ranks():
    """Distributed dpotrf over real TCP processes: numerics self-checked
    per rank (diagonal tiles vs numpy), and the aggregated-activation
    count is pinned — one activation per (task, remote destination rank)
    is a protocol invariant of this N/nb/grid config (reference
    check-comms pins exact counts the same way)."""
    out = run_scenario("dist_dpotrf", 2, timeout=600,
                       extra_env={"PERF_N": "256", "PERF_NB": "32",
                                  "PERF_P": "1"})
    acts = sum(o["acts"] for o in out)
    # N=256 nb=32 on a 1x2 grid: every trsm/gemm column boundary crosses
    # the two ranks — the exact count is a deterministic function of the
    # dependency structure (measured once, pinned forever)
    assert acts == 28, acts


def test_tcp_dist_segchol_2ranks():
    """Round-4: the distributed PANEL-SEGMENTED cholesky over real TCP
    processes — factored panel columns broadcast down the activation
    trees between OS processes, per-owner trailing updates, every local
    column verified against numpy on its owning rank."""
    out = run_scenario("dist_segchol", 2, timeout=600,
                       extra_env={"SEG_N": "256", "SEG_NB": "32"})
    assert all(o["err"] < 1e-3 for o in out), out
    # panel broadcasts really crossed the wire from every rank
    assert sum(o["acts"] for o in out) > 0


@pytest.mark.parametrize("nb,kinds", [
    (48, ["rdv"]),      # 18432-B tiles: every payload goes rendezvous
    (16, ["eager"]),    # 2048-B tiles: everything rides eager
])
def test_tcp_dtt_pingpong_mixed_layouts(nb, kinds):
    """dtt_bug_replicator-class regression (reference
    tests/runtime/dtt_bug_replicator.jdf): one flow ping-pongs between
    two real processes while each hop rebinds the payload to a different
    layout (F-order transposed view, stride-2 embedded view, contiguous)
    — values must survive exactly, and the per-rank payload byte sums,
    activation counts and datatype-packed sends are pinned in the
    scenario.  Parametrized around the short limit so BOTH wire paths
    (one-sided GET and inline) carry the adversarial layouts."""
    out = run_scenario("dtt_pingpong", 2, timeout=300,
                       extra_env={"DTT_NB": str(nb)})
    NT, tile = 6, nb * nb * 8
    # receiver-side byte sums: each rank took NT-1 activations of 2
    # payloads each (the scenario already pinned its own side exactly)
    assert all(o["pld_bytes"] == 2 * (NT - 1) * tile for o in out), out
    assert all(o["pld_kinds"] == kinds for o in out), out


def test_tcp_multipool_2ranks():
    """Serving-plane floor over the REAL wire: dpotrf + LU + a
    cross-rank chain run CONCURRENTLY on one context per rank; every
    local tile must be bit-identical to a solo single-process run and
    each pool's termdet must close (tcp_driver scenario_multipool)."""
    out = run_scenario("multipool", 2, timeout=420)
    assert all(o["tiles_checked"] > 0 for o in out)


def test_tcp_collectives_4ranks():
    """Runtime collectives over real sockets: allreduce (chunked ring),
    reduce-scatter, allgather, bcast — the TCP side of the inproc parity
    the coll endpoint promises (tests/runtime/test_coll.py)."""
    out = run_scenario("coll", 4, timeout=300)
    assert all(o["ops"] == 4 for o in out)
    assert all(o["segs"] > 0 for o in out)


def test_tcp_jobtrace_propagation_2ranks(tmp_path):
    """PR-15 acceptance: a job submitted through RuntimeService on a
    2-rank loopback-TCP mesh produces a merged Perfetto timeline whose
    compute, comm (eager AND rendezvous) and collective spans all carry
    the job's trace id on BOTH ranks; the merged document contains
    exactly ONE track group for the job; and `tools critpath --job`
    attributes its latency across queue/admit/run/drain."""
    import os

    from parsec_tpu.profiling import critpath
    from parsec_tpu.profiling.merge import merge_traces

    out = run_scenario("jobtrace", 2, timeout=300,
                       extra_env={"TRACE_DIR": str(tmp_path)})
    hexid = out[0]["trace_id"]
    assert all(o["trace_id"] == hexid for o in out)  # SPMD-consistent
    paths = sorted(os.path.join(str(tmp_path), f"rank{r}.pbt")
                   for r in range(2))
    assert all(os.path.exists(p) for p in paths), paths
    doc = merge_traces(paths)
    evs = doc["traceEvents"]

    for pid in (0, 1):
        execs = [e for e in evs if e.get("name") == "exec"
                 and e.get("pid") == pid and e.get("ph") in ("B", "E")]
        assert execs, f"rank {pid}: no exec spans"
        # EVERY span of the job's tasks carries the id (one job only)
        assert all(e["args"].get("trace_id") == hexid for e in execs)
        for kind in ("jobwire_eager", "jobwire_rdv", "jobwire_send"):
            hits = [e for e in evs if e.get("name") == kind
                    and e.get("pid") == pid]
            assert hits, f"rank {pid}: no {kind} events"
            assert all(e["args"]["trace_id"] == hexid for e in hits)
    coll = [e for e in evs if e.get("name") == "jobcoll"]
    assert {e.get("pid") for e in coll} == {0, 1}
    assert all(e["args"]["trace_id"] == hexid for e in coll)

    groups = [e for e in evs if e.get("name") == "process_name"
              and e.get("ph") == "M"
              and e["args"].get("name") == f"job {hexid}"]
    assert len(groups) == 1, "expected exactly one job track group"
    assert doc["metadata"]["jobs"][hexid]["ranks"] == [0, 1]

    rep = critpath.analyze(evs, job=hexid)
    assert rep["n_tasks"] > 0 and rep["job"] == hexid
    ph = rep["phases"]
    assert ph["run_us"] > 0
    for key in ("queue_us", "admit_us", "drain_us", "total_us"):
        assert ph[key] is not None and ph[key] >= 0, (key, ph)
    assert ph["total_us"] >= ph["run_us"]
