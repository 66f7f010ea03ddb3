"""Persistent AOT executable cache (compile_cache.py): fingerprinting,
disk round trips (including across real processes), corruption safety,
concurrent writers, the in-process zero-recompile invariant, and the
``tools cache`` CLI."""

import hashlib
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parsec_tpu import compile_cache as cc
from parsec_tpu import native as _native


@pytest.fixture
def store(tmp_path):
    return cc.DiskStore(str(tmp_path / "exe"))


@pytest.fixture
def cache(store):
    return cc.ExecutableCache(store=store, min_disk_s=0.0)


def _body(x):
    for i in range(4):
        x = jnp.sin(x @ x.T) + i
    return x


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def test_fingerprint_misses_on_shape_dtype_backend_change():
    sig32 = cc.argsig((jnp.zeros((8, 8), jnp.float32),))
    sig64 = cc.argsig((jnp.zeros((8, 8), jnp.float64),))
    sig_shape = cc.argsig((jnp.zeros((16, 8), jnp.float32),))
    key = ("body", "deadbeef")
    base = cc.fingerprint(key, sig32)
    assert cc.fingerprint(key, sig32) == base  # deterministic
    assert cc.fingerprint(key, sig64) != base  # dtype
    assert cc.fingerprint(key, sig_shape) != base  # shape
    assert cc.fingerprint(key, sig32, backend="tpu") != base  # backend
    assert cc.fingerprint(key, sig32, donate=(0,)) != base  # donation
    assert cc.fingerprint(("body", "cafe"), sig32) != base  # program


#: what ``argsig`` spells for one argument, as every executable in every
#: disk store was named: literal, so a changed spelling fails here and not
#: as a cold start of every run
_ARGSIG_PINS = [
    ("f32_array", lambda: jnp.zeros((8, 4), jnp.float32),
     ("a", (8, 4), "float32", False)),
    ("bf16_array", lambda: jnp.zeros((2,), jnp.bfloat16),
     ("a", (2,), "bfloat16", False)),
    ("i32_array", lambda: jnp.zeros((3, 1, 2), jnp.int32),
     ("a", (3, 1, 2), "int32", False)),
    ("bool_array", lambda: jnp.zeros((4,), jnp.bool_),
     ("a", (4,), "bool", False)),
    ("numpy_f64", lambda: np.zeros((5, 6), np.float64),
     ("a", (5, 6), "float64", False)),
    ("numpy_i8", lambda: np.zeros((7,), np.int8),
     ("a", (7,), "int8", False)),
    ("numpy_scalar", lambda: np.float32(1.5), ("a", (), "float32", False)),
    ("struct_bf16", lambda: jax.ShapeDtypeStruct((9, 9), jnp.bfloat16),
     ("a", (9, 9), "bfloat16", False)),
    ("struct_np_dtype",
     lambda: jax.ShapeDtypeStruct((1,), np.dtype("float32")),
     ("a", (1,), "float32", False)),
    # (the tests run with x64 on)
    ("weak_float", lambda: jnp.asarray(2.0), ("a", (), "float64", True)),
    ("weak_int", lambda: jnp.asarray(3), ("a", (), "int64", True)),
    ("python_int", lambda: 2, ("s", "int")),
    ("python_float", lambda: 2.5, ("s", "float")),
    ("python_bool", lambda: True, ("s", "bool")),
    ("python_str", lambda: "x", ("s", "str")),
    ("none", lambda: None, ("none",)),
    ("nested", lambda: (jnp.zeros((2, 2), jnp.float32), None,
                        [1, np.zeros(3, np.uint8)]),
     ("t", (("a", (2, 2), "float32", False), ("none",),
            ("t", (("s", "int"), ("a", (3,), "uint8", False)))))),
]


@pytest.mark.parametrize("make,want", [p[1:] for p in _ARGSIG_PINS],
                         ids=[p[0] for p in _ARGSIG_PINS])
def test_argsig_spelling_is_pinned(make, want):
    arg = make()
    assert cc.argsig((arg,)) == (want,)
    assert cc.argsig((arg, arg)) == (want, want)   # from the memo too


def test_argsig_pins_name_the_same_store_entries(monkeypatch):
    """The fingerprint over a pinned signature: the name such an entry
    of the disk store has had since ``CACHE_FORMAT`` 2 (under the one
    installation's versions, held here)."""
    monkeypatch.setattr(cc, "_versions", lambda: "0.9.0/0.9.0")
    sig = cc.argsig(tuple(p[1]() for p in _ARGSIG_PINS[:3]))
    assert sig == tuple(p[2] for p in _ARGSIG_PINS[:3])
    assert cc.fingerprint(("body", "deadbeef"), sig, backend="tpu") == \
        "150f4ba9eb76ae8a3fe5a8d1afd038a124821e3f"


def test_argsig_of_an_unhashable_dtype_is_still_spelled():
    class Odd:
        shape = (2,)
        dtype = ["float32"]   # no hash: spelled, never memoized
    assert cc.argsig((Odd(),)) == (("a", (2,), "['float32']", False),)


def test_code_fingerprint_tracks_code_and_closures():
    def mk(k):
        def f(x):
            return x * k
        return f

    assert cc.code_fingerprint(mk(2)) == cc.code_fingerprint(mk(2))
    assert cc.code_fingerprint(mk(2)) != cc.code_fingerprint(mk(3))

    def g(x):
        return x + 1

    def h(x):
        return x + 2

    assert cc.code_fingerprint(g) != cc.code_fingerprint(h)


def test_code_fingerprint_survives_exotic_closures():
    # ufunc dispatchers, modules, arrays — anything a body might close
    # over must fingerprint, never raise (regression: np.sin's
    # dispatcher broke the shape probe)
    arr = np.arange(8.0)

    def f(x):
        return np.sin(arr) + x

    fp = cc.code_fingerprint(f)
    assert isinstance(fp, str) and fp


# ---------------------------------------------------------------------------
# cache behavior in one process
# ---------------------------------------------------------------------------

def test_in_process_hit_and_counters(cache):
    f1 = cache.jit(_body, key=("body", "t1"))
    x = jnp.ones((8, 8), jnp.float32)
    r1 = f1(x)
    assert cache.stats["misses"] == 1
    r2 = f1(x)  # wrapper memo
    assert cache.stats["hits_mem"] == 1
    f2 = cache.jit(_body, key=("body", "t1"))  # rebuilt wrapper: LRU
    f2(x)
    assert cache.stats["hits_mem"] == 2
    assert cache.stats["misses"] == 1
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))


def test_distinct_shapes_compile_separately(cache):
    f = cache.jit(_body, key=("body", "t2"))
    f(jnp.ones((8, 8), jnp.float32))
    f(jnp.ones((16, 16), jnp.float32))
    assert cache.stats["misses"] == 2


def test_disk_round_trip_fresh_cache(store):
    c1 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    x = jnp.ones((8, 8), jnp.float32)
    r1 = c1.jit(_body, key=("body", "t3"))(x)
    assert store.count() == 1
    c2 = cc.ExecutableCache(store=store, min_disk_s=0.0)  # "new process"
    r2 = c2.jit(_body, key=("body", "t3"))(x)
    assert c2.stats["misses"] == 0
    assert c2.stats["hits_disk"] == 1
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))


def test_warm_property_flips_on_first_store(store):
    c1 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    assert not c1.warm
    c1.jit(_body, key=("body", "t4"))(jnp.ones((8, 8), jnp.float32))
    assert c1.warm
    assert cc.ExecutableCache(store=store).warm  # re-probed at init


def test_donated_program_round_trips(store):
    def f(a, b):
        return a + b, b * 2

    c1 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    a = jnp.ones((8, 8), jnp.float32)
    b = jnp.full((8, 8), 3.0, jnp.float32)
    r1 = c1.jit(f, key=("body", "t5"), donate_argnums=(0,))(a, b)
    c2 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    a2 = jnp.ones((8, 8), jnp.float32)
    r2 = c2.jit(f, key=("body", "t5"), donate_argnums=(0,))(a2, b)
    assert c2.stats["hits_disk"] == 1
    np.testing.assert_allclose(np.asarray(r1[0]), np.asarray(r2[0]))
    np.testing.assert_allclose(np.asarray(r1[1]), np.asarray(r2[1]))


# ---------------------------------------------------------------------------
# corruption safety
# ---------------------------------------------------------------------------

def _the_entry(store):
    rows = store.entries()
    assert len(rows) == 1
    return rows[0]


@pytest.mark.parametrize("damage", ["truncate", "flip", "garbage",
                                    "tail_flip", "trailing_bytes"])
def test_corrupt_entry_falls_back_to_recompile(store, damage, capfd):
    from parsec_tpu.utils import debug

    debug.set_verbose(2)  # the quiet-test default swallows warnings
    c1 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    x = jnp.ones((8, 8), jnp.float32)
    r1 = c1.jit(_body, key=("body", "t6"))(x)
    path = _the_entry(store)["path"]
    raw = open(path, "rb").read()
    if damage == "truncate":
        open(path, "wb").write(raw[: len(raw) // 2])
    elif damage == "flip":
        # flip a byte inside the portable blob (after the header line)
        cut = raw.index(b"\n") + 10
        open(path, "wb").write(
            raw[:cut] + bytes([raw[cut] ^ 0xFF]) + raw[cut + 1:])
    elif damage == "tail_flip":
        # the entry ends with the portable blob (no machine-code section
        # follows it any more): a flip near the end is a checksum miss
        open(path, "wb").write(raw[:-10] + bytes([raw[-10] ^ 0xFF])
                               + raw[-9:])
    elif damage == "trailing_bytes":
        # e.g. an entry written by the retired format with a native
        # section appended after the blob
        open(path, "wb").write(raw + b"\x7fELF-machine-code")
    else:
        open(path, "wb").write(b"not an executable at all")
    c2 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    r2 = c2.jit(_body, key=("body", "t6"))(x)
    # fell back to a fresh compile — with a readable warning, no crash
    assert c2.stats["misses"] == 1
    assert c2.stats["hits_disk"] == 0
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))
    err = capfd.readouterr().err
    assert "unreadable" in err or "recompil" in err


def test_corrupt_entry_is_removed_and_rewritten(store):
    c1 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    x = jnp.ones((8, 8), jnp.float32)
    c1.jit(_body, key=("body", "t7"))(x)
    path = _the_entry(store)["path"]
    open(path, "wb").write(b"garbage")
    c2 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    c2.jit(_body, key=("body", "t7"))(x)
    # the recompile re-stored a VALID entry
    ok, bad = store.verify()
    assert (ok, bad) == (1, [])


def test_concurrent_writers_do_not_corrupt(store):
    """N threads resolving the same program against one store: the
    entry stays valid and every thread computes the right answer."""
    x = jnp.ones((8, 8), jnp.float32)
    ref = np.asarray(cc.ExecutableCache(store=None).jit(
        _body, key=("w", 0))(x))
    errs = []

    def worker(i):
        try:
            c = cc.ExecutableCache(store=store, min_disk_s=0.0)
            r = c.jit(_body, key=("body", "t8"))(x)
            np.testing.assert_allclose(np.asarray(r), ref, rtol=1e-6)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert not errs
    ok, bad = store.verify()
    assert (ok, bad) == (1, [])


# ---------------------------------------------------------------------------
# cross-process round trip (the honest warm-disk story)
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from parsec_tpu import compile_cache as cc

def body(x):
    x = jnp.linalg.cholesky(x @ x.T + 100 * jnp.eye(16, dtype=x.dtype))
    return jnp.sin(x) + 1

store = cc.DiskStore(sys.argv[1])
cache = cc.ExecutableCache(store=store, min_disk_s=0.0)
x = jnp.ones((16, 16), jnp.float32)
r = cache.jit(body, key=("body", "xproc"))(x)
print(json.dumps({"stats": dict(cache.stats),
                  "sum": float(np.asarray(r).sum())}))
"""


def test_round_trip_across_two_processes(tmp_path):
    """Process A compiles + stores (with a LAPACK custom call in the
    body — the historical segfault case); process B must reload from
    disk with zero trace-compiles and identical numerics."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _CHILD, str(tmp_path / "exe")],
            capture_output=True, text=True, env=env, timeout=240)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert out[0]["stats"]["misses"] == 1
    assert out[1]["stats"].get("misses", 0) == 0
    assert out[1]["stats"]["hits_disk"] == 1
    assert out[0]["sum"] == pytest.approx(out[1]["sum"], rel=1e-6)


# ---------------------------------------------------------------------------
# tier-1 pin: a second in-process dpotrf performs ZERO XLA recompiles
# ---------------------------------------------------------------------------

def test_second_dpotrf_run_zero_recompiles():
    from parsec_tpu import Context
    from parsec_tpu.datadist import TiledMatrix
    from parsec_tpu.ops.cholesky import cholesky_ptg
    from parsec_tpu.utils import mca_param

    n, nb = 64, 16
    rng = np.random.default_rng(5)
    M = rng.standard_normal((n, n))
    spd = M @ M.T + n * np.eye(n)
    # wave batching OFF for this pin: ready-wave sizes depend on
    # scheduling timing, so the wave-program set is not deterministic
    # across runs — per-body programs are
    mca_param.set_param("device", "tpu_wave_batch", 0)
    ctx = Context(nb_cores=2)
    try:
        def run():
            A = TiledMatrix(n, n, nb, nb, name="A").from_array(spd)
            tp = cholesky_ptg(use_tpu=True,
                              use_cpu=False).taskpool(NT=A.mt, A=A)
            ctx.add_taskpool(tp)
            assert tp.wait(timeout=120)

        run()
        misses = ctx.compile_cache.stats["misses"]
        hits = ctx.compile_cache.hits
        assert misses > 0  # the first run did compile through the cache
        run()
        assert ctx.compile_cache.stats["misses"] == misses, \
            "second identical dpotrf run recompiled"
        assert ctx.compile_cache.hits > hits
    finally:
        ctx.fini()
        mca_param.params.unset("device", "tpu_wave_batch")


# ---------------------------------------------------------------------------
# observability: compile spans
# ---------------------------------------------------------------------------

def test_compile_pins_fire_with_kind(cache):
    from parsec_tpu.profiling import pins

    events = []

    def on(es, p):
        events.append(dict(p))

    pins.subscribe(pins.COMPILE_BEGIN, on)
    pins.subscribe(pins.COMPILE_END, on)
    try:
        f = cache.jit(_body, key=("body", "span1"))
        f(jnp.ones((8, 8), jnp.float32))
        f(jnp.ones((8, 8), jnp.float32))  # memo hit: no new span
    finally:
        pins.unsubscribe(pins.COMPILE_BEGIN, on)
        pins.unsubscribe(pins.COMPILE_END, on)
    assert len(events) == 2  # one begin + one end, hits span-free
    assert events[0]["fp"] == events[1]["fp"]
    assert events[1]["kind"] == "miss"
    assert events[1]["seconds"] > 0


@pytest.mark.skipif(not _native.available(),
                    reason="binary tracer needs the native core")
def test_compile_spans_land_in_binary_trace(tmp_path, store):
    """The PR 1 binary traces carry ``compile`` spans (critpath's
    compile bucket reads them): resolve one program under a
    RankTraceSet and find the span in the dump."""
    from parsec_tpu.profiling.binary import RankTraceSet, to_chrome_events

    ts = RankTraceSet(nranks=1).install()
    try:
        c = cc.ExecutableCache(store=store, min_disk_s=0.0)
        c.jit(_body, key=("body", "span2"))(jnp.ones((8, 8), jnp.float32))
        paths = ts.dump(str(tmp_path))
    finally:
        ts.uninstall()
        ts.close()
    evs = to_chrome_events(paths[0])
    phases = sorted(e["ph"] for e in evs if e["name"] == "compile")
    assert phases == ["B", "E"]


# ---------------------------------------------------------------------------
# tools cache CLI
# ---------------------------------------------------------------------------

def test_tools_cache_cli(tmp_path, capsys):
    from parsec_tpu.profiling.tools import main as tools_main

    root = tmp_path / "root"
    store = cc.DiskStore(str(root / "exe"))
    c = cc.ExecutableCache(store=store, min_disk_s=0.0)
    c.jit(_body, key=("body", "cli"))(jnp.ones((8, 8), jnp.float32))

    assert tools_main(["cache", "ls", "--dir", str(root)]) == 0
    out = capsys.readouterr().out
    assert "1 entry" in out
    assert tools_main(["cache", "stats", "--dir", str(root)]) == 0
    assert "entries:        1" in capsys.readouterr().out
    assert tools_main(["cache", "verify", "--dir", str(root)]) == 0
    assert "1 ok, 0 corrupt" in capsys.readouterr().out
    # corrupt it: verify flags, --delete removes
    path = store.entries()[0]["path"]
    open(path, "wb").write(b"junk")
    assert tools_main(["cache", "verify", "--dir", str(root)]) == 1
    assert tools_main(["cache", "verify", "--dir", str(root),
                       "--delete"]) == 1
    assert tools_main(["cache", "verify", "--dir", str(root)]) == 0
    # repopulate + purge
    c2 = cc.ExecutableCache(store=store, min_disk_s=0.0)
    c2.jit(_body, key=("body", "cli2"))(jnp.ones((8, 8), jnp.float32))
    assert tools_main(["cache", "purge", "--dir", str(root)]) == 0
    assert store.count() == 0


# ---------------------------------------------------------------------------
# graceful process-local path (export failures: Pallas custom calls,
# host callbacks) — counted and surfaced, never silent (ISSUE 11)
# ---------------------------------------------------------------------------

def _callback_body(x):
    # host callbacks cannot serialize through jax.export — the canonical
    # "stays process-local" program shape
    return jax.pure_callback(
        lambda a: np.asarray(a) * 2.0,
        jax.ShapeDtypeStruct(x.shape, x.dtype), x)


def test_unexportable_program_counts_local_only(store, capfd):
    from parsec_tpu.utils import debug

    debug.set_verbose(2)  # the quiet-test default swallows warnings
    c = cc.ExecutableCache(store=store, min_disk_s=0.0)
    f = c.jit(_callback_body, key=("body", "cb"))
    x = jnp.ones((4,), jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)
    assert c.stats["local_only"] == 1
    assert c.stats["serialize_errors"] == 1
    assert store.count() == 0  # nothing shareable was written
    # the one-time log names the program; a second SHAPE of the same
    # program counts again but does not re-log
    err = capfd.readouterr().err
    assert err.count("not exportable") == 1 and "'cb'" in err
    np.testing.assert_allclose(np.asarray(f(jnp.ones((8,),
                                                     jnp.float32))), 2.0)
    assert c.stats["local_only"] == 2
    assert "not exportable" not in capfd.readouterr().err
    # per-process LRU still serves it: repeat dispatches compile nothing
    misses = c.stats["misses"]
    hits = c.hits
    f2 = c.jit(_callback_body, key=("body", "cb"))
    np.testing.assert_allclose(np.asarray(f2(x)), 2.0)
    assert c.stats["misses"] == misses and c.hits > hits


def test_local_only_snapshot_reaches_health_plane(store):
    """snapshot() carries local_only, so /metrics
    (parsec_compile_local_only_total) and the
    PARSEC::COMPILE::LOCAL_ONLY gauge surface it."""
    c = cc.ExecutableCache(store=store, min_disk_s=0.0)
    c.jit(_callback_body, key=("body", "cb2"))(jnp.ones((4,),
                                                        jnp.float32))
    assert c.snapshot().get("local_only") == 1
