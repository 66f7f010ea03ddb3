"""Supertask fusion (dsl.fusion): partitioner invariants, fused
execution bit-identity on the dynamic and native paths, termdet/progress
accounting of N-member retirements, the lax.scan chain lowering, and the
cross-process executable-cache pin for fused programs."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.dsl import fusion as F
from parsec_tpu.dsl.ptg import PTG, IN, INOUT
from parsec_tpu.utils import mca_param


@pytest.fixture
def fusion_on():
    mca_param.params.set("runtime", "fusion", "auto")
    yield
    mca_param.params.unset("runtime", "fusion")


def _dpotrf_tp(n=128, nb=32, seed=0):
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    spd = M @ M.T + n * np.eye(n)
    A = TiledMatrix(n, n, nb, nb, name="A").from_array(spd)
    tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
    return tp, A, spd


# ---------------------------------------------------------------------------
# partitioner invariants
# ---------------------------------------------------------------------------

def test_partition_dpotrf_chains_and_waves():
    tp, A, _ = _dpotrf_tp()
    g = tp.capture(ranks=[0])
    regions = F.partition(g, tp.ptg.classes, mode="auto", max_tasks=16)
    assert regions, "dpotrf must produce fused regions"
    kinds = {r.kind for r in regions}
    assert "chain" in kinds and "wave" in kinds
    seen = set()
    for r in regions:
        assert 2 <= len(r.members) <= 16
        assert not (set(r.members) & seen), "regions must not overlap"
        seen |= set(r.members)
        if r.kind == "chain":
            # every interior member has exactly one distinct successor
            # and no remote forwards — the convexity/deadlock proof
            for m in r.members[:-1]:
                node = g.nodes[m]
                assert len({s for (_f, s, _sf) in node.out_edges}) == 1
                assert node.remote_out == 0
    # the syrk column chains end in their potrf (the tail panels round 2
    # fused by hand, now automatic)
    assert any(r.members[-1][0] == "potrf" for r in regions
               if r.kind == "chain")


def test_partition_modes_and_horizon():
    tp, _, _ = _dpotrf_tp()
    g = tp.capture(ranks=[0])
    assert F.partition(g, tp.ptg.classes, mode="off", max_tasks=16) == []
    chains = F.partition(g, tp.ptg.classes, mode="chains", max_tasks=16)
    assert chains and all(r.kind == "chain" for r in chains)
    waves = F.partition(g, tp.ptg.classes, mode="waves", max_tasks=16)
    assert waves and all(r.kind == "wave" for r in waves)
    capped = F.partition(g, tp.ptg.classes, mode="auto", max_tasks=2)
    assert capped and all(len(r.members) == 2 for r in capped)


def test_ring_rotation_never_fuses_interior():
    """Ring attention: a step that forwards K/V to another rank has
    remote successors — it must never be a region interior (burying the
    rotation would deadlock the cross-rank cycle).  Only the tail
    (last step -> attn_out) may fuse."""
    from parsec_tpu.ops.attention import ring_attention_builder

    rng = np.random.default_rng(3)
    q = rng.standard_normal((1, 8, 1, 4)).astype(np.float32)
    build, _ = ring_attention_builder(2, q, q, q, causal=True,
                                      use_cpu=False)
    tp, _ = build(0, None)
    g = tp.capture(ranks=[0])
    R = 2
    regions = F.partition(g, tp.ptg.classes, mode="auto", max_tasks=16)
    for r in regions:
        for m in r.members[:-1]:
            # interior members: never a forwarding step (s < R-1)
            assert not (m[0] == "attn_rstep" and m[1][2] < R - 1), \
                f"rotation step {m} fused as interior"
    # waves are OFF on rank-filtered captures of distributed pools
    assert all(r.kind == "chain" for r in regions)


def test_writeback_superseded_chain_truncates():
    """An interior member whose write-back tile is rewritten by a LATER
    member must not fuse ahead of it: the fused program commits only
    final values, so such a region would change observable state."""
    def body(T, **kw):
        return T + 1.0

    ptg = PTG("wbchain")
    a = ptg.task_class("a", k="0 .. 0")
    a.flow("T", INOUT, "<- D(0)", "-> T b(0)", "-> D(0)")
    a.body(tpu=body)
    b = ptg.task_class("b", k="0 .. 0")
    b.flow("T", INOUT, "<- T a(0)", "-> D(0)")
    b.body(tpu=body)
    from parsec_tpu.data.collection import LocalCollection

    D = LocalCollection("D")
    D.data_of(0).get_copy(0).payload = np.zeros((2, 2))
    tp = ptg.taskpool(D=D)
    g = tp.capture(ranks=[0])
    regions = F.partition(g, tp.ptg.classes, mode="chains", max_tasks=8)
    assert regions == [], \
        "a's write-back is superseded by b: the pair must not fuse"


def test_plan_slots_and_digest_stability():
    tp, _, _ = _dpotrf_tp()
    g = tp.capture(ranks=[0])
    regions = F.partition(g, tp.ptg.classes, mode="auto", max_tasks=16)
    plans = [F.FusedPlan(tp, g, r) for r in regions]
    for p in plans:
        assert p.slot_keys and p.out_slots
        assert all(m & int(AccessMode.INOUT) for m in p.slot_modes)
        assert getattr(p.body_fn, "_fused_n") == len(p.region.members)
    # same taskpool recaptured -> same digests (the cache identity)
    tp2, _, _ = _dpotrf_tp()
    g2 = tp2.capture(ranks=[0])
    regions2 = F.partition(g2, tp2.ptg.classes, mode="auto", max_tasks=16)
    d1 = sorted(p.digest for p in plans)
    d2 = sorted(F.FusedPlan(tp2, g2, r).digest for r in regions2)
    assert d1 == d2


# ---------------------------------------------------------------------------
# dynamic-runtime execution
# ---------------------------------------------------------------------------

def _run_dpotrf_dynamic(fuse: bool, n=128, nb=32):
    from parsec_tpu import Context

    if fuse:
        mca_param.params.set("runtime", "fusion", "auto")
    ctx = Context(nb_cores=2)
    try:
        tp, A, spd = _dpotrf_tp(n, nb)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=180), f"pool failed (fuse={fuse})"
        return A.to_array(), tp, ctx.devices
    finally:
        ctx.fini()
        mca_param.params.unset("runtime", "fusion")


def test_dynamic_dpotrf_fused_bit_identical():
    off, tp_off, _ = _run_dpotrf_dynamic(False)
    on, tp_on, devs = _run_dpotrf_dynamic(True)
    assert np.array_equal(np.tril(off), np.tril(on)), \
        "fusion changed dpotrf numerics"
    # a fused region retires N tasks at ONE completion: the progress
    # currency must agree with per-task dispatch
    assert tp_on.nb_retired == tp_off.nb_retired == 20
    assert tp_on._fusion is not None
    stats = {}
    for d in devs:
        for k in ("fused_submits", "fused_tasks"):
            stats[k] = stats.get(k, 0) + d.stats.get(k, 0)
    assert stats["fused_submits"] > 0
    assert stats["fused_tasks"] > stats["fused_submits"]


def test_dynamic_flash_attention_fused_bit_identical(fusion_on):
    from parsec_tpu import Context
    from parsec_tpu.ops.attention import run_flash_attention

    rng = np.random.default_rng(1)
    B, S, H, D = 1, 128, 2, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    kw = dict(causal=True, q_block=32, kv_block=32, use_cpu=False)

    mca_param.params.unset("runtime", "fusion")
    ctx = Context(nb_cores=2)
    try:
        off = run_flash_attention(ctx, q, k, v, **kw)
    finally:
        ctx.fini()
    mca_param.params.set("runtime", "fusion", "auto")
    ctx = Context(nb_cores=2)
    try:
        on = run_flash_attention(ctx, q, k, v, **kw)
    finally:
        ctx.fini()
    assert np.array_equal(off, on)


def test_scan_lowering_engages_and_matches():
    """Uniform attention chains lower as ONE lax.scan; the scan and
    unrolled emissions must be numerically identical."""
    from parsec_tpu.ops.attention import build_flash_attention

    rng = np.random.default_rng(7)
    q = rng.standard_normal((1, 256, 1, 8)).astype(np.float32)
    tp, _ = build_flash_attention(q, q, q, causal=False, q_block=32,
                                  kv_block=32, use_cpu=False)
    g = tp.capture(ranks=[0])
    regions = F.partition(g, tp.ptg.classes, mode="chains", max_tasks=16)
    assert regions
    scanned = [F.FusedPlan(tp, g, r, scan="auto") for r in regions]
    assert any(p._scan_segments is not None for p in scanned), \
        "uniform non-causal chains should roll into lax.scan"

    def run(scan_mode):
        from parsec_tpu import Context
        from parsec_tpu.ops.attention import run_flash_attention

        mca_param.params.set("runtime", "fusion", "chains")
        mca_param.params.set("runtime", "fusion_scan", scan_mode)
        ctx = Context(nb_cores=2)
        try:
            return run_flash_attention(
                ctx, q, q, q, causal=False, q_block=32, kv_block=32,
                use_cpu=False)
        finally:
            ctx.fini()
            mca_param.params.unset("runtime", "fusion")
            mca_param.params.unset("runtime", "fusion_scan")

    assert np.array_equal(run("off"), run("auto"))


# ---------------------------------------------------------------------------
# native path: one region = one pz_task_done
# ---------------------------------------------------------------------------

def test_native_fused_dpotrf_bit_identical():
    from parsec_tpu import native
    from parsec_tpu.dsl.native_exec import NativeExecutor

    if not native.available():
        pytest.skip(f"native core unavailable: {native.build_error()}")

    def run(fuse):
        tp, A, _ = _dpotrf_tp()
        ex = NativeExecutor(tp, native_device=True,
                            fusion="auto" if fuse else "off")
        try:
            ran = ex.run(nthreads=2)
        finally:
            ex.close()
        return A.to_array(), ran, ex

    off, ran_off, _ = run(False)
    on, ran_on, ex = run(True)
    # run() reports LOGICAL tasks: all 20, however many native nodes
    assert ran_off == ran_on == 20
    assert ex._regions, "native fusion did not partition"
    assert ex._n_native < 20, "regions must collapse native nodes"
    assert np.array_equal(np.tril(off), np.tril(on))


def test_native_fused_flash_attention():
    from parsec_tpu import native
    from parsec_tpu.ops.attention import run_flash_attention_native

    if not native.available():
        pytest.skip(f"native core unavailable: {native.build_error()}")
    rng = np.random.default_rng(2)
    q = rng.standard_normal((1, 128, 2, 8)).astype(np.float32)
    kw = dict(causal=True, q_block=32, kv_block=32)
    off = run_flash_attention_native(q, q, q, **kw)
    mca_param.params.set("runtime", "fusion", "auto")
    try:
        on = run_flash_attention_native(q, q, q, **kw)
    finally:
        mca_param.params.unset("runtime", "fusion")
    assert np.array_equal(off, on)


# ---------------------------------------------------------------------------
# executable cache: fused programs are cross-process artifacts
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, sys
import numpy as np
from parsec_tpu import Context
from parsec_tpu.utils import mca_param
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.ops.cholesky import cholesky_ptg

mca_param.params.set("runtime", "fusion", "auto")
mca_param.params.set("device", "tpu_wave_batch", 0)
rng = np.random.default_rng(5)
M = rng.standard_normal((64, 64))
spd = M @ M.T + 64 * np.eye(64)
ctx = Context(nb_cores=2)
A = TiledMatrix(64, 64, 16, 16, name="A").from_array(spd)
tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=A.mt, A=A)
ctx.add_taskpool(tp)
assert tp.wait(timeout=180)
out = {"stats": dict(ctx.compile_cache.stats),
       "sum": float(np.tril(A.to_array()).sum()),
       "fused_submits": sum(d.stats.get("fused_submits", 0)
                            for d in ctx.devices)}
ctx.fini()
print(json.dumps(out))
"""


def test_fused_programs_hit_cache_across_processes(tmp_path):
    """Acceptance: a second PROCESS running the same fused pool does
    ZERO recompiles — every fused program reloads from the persistent
    store (fused program key = member fingerprints + region shape)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _CHILD],
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=os.path.dirname(
                               os.path.dirname(os.path.dirname(
                                   os.path.abspath(__file__)))))
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert out[0]["fused_submits"] > 0
    assert out[1]["fused_submits"] == out[0]["fused_submits"]
    assert out[0]["stats"]["fused_compiles"] > 0
    assert out[0]["stats"]["misses"] > 0
    assert out[1]["stats"].get("misses", 0) == 0, \
        f"second process recompiled: {out[1]['stats']}"
    assert out[1]["stats"].get("fused_compiles", 0) == 0
    assert out[0]["sum"] == pytest.approx(out[1]["sum"], rel=0, abs=0)
