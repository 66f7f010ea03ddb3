"""Compile-once-ship-serialized: the TAG_CTL compile channel on the
in-process fabric — one trace+compile per program per MESH instead of
per rank, inline and rendezvous-chunk blob regimes, the device-path
integration, and the warm-cache lift of the PR 4 wave-batching
auto-disable."""

import numpy as np
import pytest

import jax.numpy as jnp

from parsec_tpu import compile_cache as cc
from parsec_tpu.comm.inproc import InprocFabric
from parsec_tpu.utils import mca_param


def _body(x):
    for i in range(8):
        x = jnp.sin(x @ x.T) + i
    return x


def _mesh_caches(nranks, ces, **kw):
    kw.setdefault("store", None)
    kw.setdefault("min_disk_s", 0.0)
    return [cc.ExecutableCache(rank=r, nranks=nranks, ce=ces[r], **kw)
            for r in range(nranks)]


def _drain(ces):
    for _ in range(3):
        for ce in ces:
            ce.progress_nonblocking()


def test_8rank_mesh_one_compile_per_program():
    """Acceptance pin (ISSUE 7): on the 8-rank loopback mesh, a shape
    compiled on one rank is NOT recompiled on the other seven — proven
    by broadcast + hit counters, with bit-identical results."""
    fab = InprocFabric(8)
    ces = fab.endpoints()
    caches = _mesh_caches(8, ces)
    x = jnp.ones((32, 32), jnp.float32)
    r0 = caches[0].jit(_body, key=("body", "mesh1"))(x)
    assert caches[0].stats["misses"] == 1
    assert caches[0].stats["bcast_sent"] == 7
    _drain(ces)
    for r in range(1, 8):
        rr = caches[r].jit(_body, key=("body", "mesh1"))(x)
        assert caches[r].stats["misses"] == 0, \
            f"rank {r} recompiled: {dict(caches[r].stats)}"
        assert caches[r].stats["bcast_recv"] == 1
        assert caches[r].stats["hits_bcast"] == 1
        np.testing.assert_array_equal(np.asarray(rr), np.asarray(r0))
    assert sum(c.stats["misses"] for c in caches) == 1


def test_large_blob_rides_rdv_chunks():
    """Blobs above the eager limit are advertised and pulled in
    pipelined rendezvous chunks off the registered buffer (the PR 4
    machinery), not shipped inline."""
    fab = InprocFabric(3)
    ces = fab.endpoints()
    for ce in ces:
        ce.eager_limit = 64    # every real blob exceeds this
        ce.rdv_chunk = 256     # forces a multi-chunk pull
        ce.pipeline_depth = 2
    caches = _mesh_caches(3, ces)
    x = jnp.ones((16, 16), jnp.float32)
    pulled_before = [ce.stats.get("get_bytes", 0) for ce in ces]
    caches[0].jit(_body, key=("body", "rdv1"))(x)
    _drain(ces)
    for r in (1, 2):
        caches[r].jit(_body, key=("body", "rdv1"))(x)
        assert caches[r].stats["misses"] == 0
        assert caches[r].stats["bcast_recv"] == 1
        # the blob crossed as one-sided chunk pulls, byte-exact
        assert ces[r].stats.get("get_bytes", 0) - pulled_before[r] > 0
    # use-counted registration: consumed by exactly the two peers
    assert not fab.mem, f"leaked registrations: {list(fab.mem)}"


def test_simultaneous_miss_adverts_release_registrations():
    """Two ranks that both miss and compile the same program advertise
    to each other; each peer already holds the executable, so each must
    CONSUME the other's use-counted registration (one tiny fin read)
    instead of pulling — or the serialized blob stays pinned in the
    sender's mem table forever."""
    fab = InprocFabric(2)
    ces = fab.endpoints()
    for ce in ces:
        ce.eager_limit = 64  # real blobs exceed this: advertised+registered
    caches = _mesh_caches(2, ces)
    x = jnp.ones((16, 16), jnp.float32)
    caches[0].jit(_body, key=("body", "simult"))(x)
    caches[1].jit(_body, key=("body", "simult"))(x)  # before any drain
    assert all(c.stats["misses"] == 1 for c in caches)
    _drain(ces)
    assert not fab.mem, f"leaked registrations: {list(fab.mem)}"


def test_many_chunk_pull_is_iterative():
    """The blob pump must stay iterative: on a synchronous engine
    (inproc get_part completes inside the call) a chunk count larger
    than the recursion limit would otherwise nest one frame per chunk
    and die with RecursionError."""
    fab = InprocFabric(2)
    ces = fab.endpoints()
    for ce in ces:
        ce.eager_limit = 64
        ce.rdv_chunk = 2       # a ~5 KB blob -> thousands of chunks
        ce.pipeline_depth = 2
    caches = _mesh_caches(2, ces)
    x = jnp.ones((16, 16), jnp.float32)
    caches[0].jit(_body, key=("body", "manychunks"))(x)
    _drain(ces)
    r = caches[1].jit(_body, key=("body", "manychunks"))(x)
    assert caches[1].stats["misses"] == 0, dict(caches[1].stats)
    assert caches[1].stats["bcast_recv"] == 1
    assert np.asarray(r).shape == (16, 16)
    assert not fab.mem, f"leaked registrations: {list(fab.mem)}"


def test_failed_pull_falls_back_to_local_compile():
    """A peer whose blob pull dies must compile locally — counted,
    correct, no hang."""
    fab = InprocFabric(2)
    ces = fab.endpoints()
    for ce in ces:
        ce.eager_limit = 64
    caches = _mesh_caches(2, ces)
    x = jnp.ones((16, 16), jnp.float32)
    caches[0].jit(_body, key=("body", "pullfail"))(x)
    # sabotage: drop the registration before rank 1 progresses
    fab.mem.clear()
    fab.mem_uses.clear()
    _drain(ces)
    r = caches[1].jit(_body, key=("body", "pullfail"))(x)
    assert np.asarray(r).shape == (16, 16)
    assert caches[1].stats["misses"] == 1  # local fallback compile
    assert caches[1].stats["bcast_recv"] == 0


def test_device_dpotrf_over_2rank_mesh_broadcasts(monkeypatch):
    """End-to-end through real Contexts + TpuDevice: rank 0's device
    compiles broadcast so rank 1's identical (shape, body) programs
    arrive serialized.  Disk store disabled — only the ctl channel can
    explain rank 1 compiling nothing."""
    monkeypatch.setenv("PARSEC_TPU_COMPILE_CACHE", "0")
    from parsec_tpu import Context
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg

    class _OwnRankMatrix(TiledMatrix):
        # every tile owned by the constructing rank: each virtual rank
        # factorizes its own local matrix (the broadcast is what crosses
        # the mesh, not the tiles)
        def rank_of(self, *key) -> int:
            return self.myrank

    mca_param.set_param("runtime", "compile_cache_min_share_s", 0.0)
    mca_param.set_param("device", "tpu_wave_batch", 0)
    fab = InprocFabric(2)
    ces = fab.endpoints()
    ctxs = [Context(nb_cores=2, rank=r, nranks=2, comm=ces[r])
            for r in range(2)]
    try:
        n, nb = 64, 16
        rng = np.random.default_rng(5)
        M = rng.standard_normal((n, n))
        spd = M @ M.T + n * np.eye(n)

        def run_local(ctx):
            A = _OwnRankMatrix(n, n, nb, nb, name=f"A{ctx.rank}",
                               nodes=2, myrank=ctx.rank).from_array(spd)
            tp = cholesky_ptg(use_tpu=True,
                              use_cpu=False).taskpool(NT=A.mt, A=A)
            ctx.add_taskpool(tp)
            assert tp.wait(timeout=120)

        run_local(ctxs[0])
        assert ctxs[0].compile_cache.stats["misses"] > 0
        assert ctxs[0].compile_cache.stats["bcast_sent"] > 0
        _drain(ces)
        run_local(ctxs[1])
        s1 = dict(ctxs[1].compile_cache.stats)
        assert s1.get("misses", 0) == 0, \
            f"rank 1 recompiled despite the broadcast: {s1}"
        assert s1.get("hits_bcast", 0) > 0
    finally:
        for ctx in ctxs:
            ctx.fini()
        mca_param.params.unset("runtime", "compile_cache_min_share_s")
        mca_param.params.unset("device", "tpu_wave_batch")


# ---------------------------------------------------------------------------
# the PR 4 workaround lift: wave batching on multi-rank CPU emulation
# ---------------------------------------------------------------------------

def _tpu_dev(ctx):
    from parsec_tpu import DEV_TPU

    for d in ctx.devices:
        if d.device_type == DEV_TPU:
            return d
    pytest.skip("no jax device available")


def test_wave_autodisable_ab_cold_vs_warm(monkeypatch, tmp_path):
    """A/B pin for the lifted workaround: on multi-rank CPU emulation
    the wave-batch auto-disable stays (cold cache — the per-rank
    compile explosion is real), but a WARM executable store lifts it
    (compiles reload instead of exploding).  An explicit MCA setting
    wins either way."""
    from parsec_tpu import Context

    # A: cold store -> auto-disabled
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cold"))
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    try:
        assert _tpu_dev(ctx)._wave_min == 0
    finally:
        ctx.fini()

    # B: warm store (a LOADABLE entry: recorded versions/backend match
    # this process) -> default stays enabled; an entry only a different
    # jax build could load must NOT lift the workaround
    warm_root = tmp_path / "warm"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(warm_root))
    st = cc.DiskStore(str(warm_root / "exe"))
    st.store("e" * 40, b"stale", {"versions": "jax-0.0.0/jaxlib-0.0.0",
                                  "backend": cc._platform()})
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    try:
        assert _tpu_dev(ctx)._wave_min == 0  # stale-only store is cold
    finally:
        ctx.fini()
    st.store("f" * 40, b"seed", {"versions": cc._versions(),
                                 "backend": cc._platform()})
    ctx = Context(nb_cores=1, rank=0, nranks=2)
    try:
        assert _tpu_dev(ctx)._wave_min > 0
    finally:
        ctx.fini()

    # C: explicit setting beats both directions
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cold2"))
    mca_param.set_param("device", "tpu_wave_batch", 3)
    try:
        ctx = Context(nb_cores=1, rank=0, nranks=2)
        try:
            assert _tpu_dev(ctx)._wave_min == 3
        finally:
            ctx.fini()
    finally:
        mca_param.params.unset("device", "tpu_wave_batch")


def test_single_rank_keeps_wave_batching(monkeypatch, tmp_path):
    """The auto-disable was always multi-rank-only: single-rank CPU
    contexts keep the default wave batching even with a cold cache."""
    from parsec_tpu import Context

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    ctx = Context(nb_cores=1)
    try:
        assert _tpu_dev(ctx)._wave_min > 0
    finally:
        ctx.fini()
