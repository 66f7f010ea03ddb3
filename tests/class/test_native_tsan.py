"""ThreadSanitizer build flavor of the native core (the CI smoke leg):
``PARSEC_TPU_NATIVE_TSAN=1`` must keep compiling — the async engine
(pz_graph_run_async / pz_task_done from arbitrary threads) is exactly
the code TSan exists to watch.  Loading a TSan .so needs the sanitizer
runtime preloaded, so the smoke stops at compile + symbol check."""

import os
import shutil
import subprocess

import pytest

from parsec_tpu import native


def _tsan_supported() -> bool:
    if shutil.which("g++") is None:
        return False
    probe = subprocess.run(
        ["g++", "-fsanitize=thread", "-x", "c++", "-shared", "-fPIC",
         "-o", "/dev/null", "-"],
        input="int probe(){return 0;}", capture_output=True, text=True)
    return probe.returncode == 0


def test_tsan_flavor_compiles_with_engine_symbols(tmp_path):
    if not _tsan_supported():
        pytest.skip("toolchain lacks -fsanitize=thread")
    path = native.build_tsan_library()
    assert path == native.lib_path(tsan=True)
    assert os.path.basename(path).startswith("libparsec_core_tsan-")
    nm = subprocess.run(["nm", "-D", path], capture_output=True, text=True)
    assert nm.returncode == 0
    # the async engine the sanitizer is wired for must be in the flavor,
    # and so must the pump-scheduler hot loop (ISSUE 18: pop/done batches,
    # sched config, the event drain, and the standalone ready queue)
    for sym in ("pz_graph_run_async", "pz_task_done", "pz_graph_fail",
                "pz_graph_pop_batch", "pz_graph_done_batch",
                "pz_graph_sched_config", "pz_graph_events_drain",
                "pz_rq_new", "pz_rq_push", "pz_rq_pop"):
        assert sym in nm.stdout, f"{sym} missing from TSan flavor"
    # and it IS instrumented (tsan runtime references present)
    assert "tsan" in nm.stdout or "__tsan" in nm.stdout


def test_tsan_flavor_is_a_separate_artifact():
    """The flavors must never clobber each other: the default build and
    the TSan build live at different paths."""
    if not _tsan_supported():
        pytest.skip("toolchain lacks -fsanitize=thread")
    tsan = native.build_tsan_library()
    assert "tsan" in tsan
    # the regular flavor (this process, PARSEC_TPU_NATIVE_TSAN unset)
    # still loads and is healthy
    if native.available():
        assert native.missing_symbols() == []


def test_suppressions_file_ships():
    import os

    p = native.tsan_suppressions_path()
    assert os.path.exists(p)
    body = open(p).read()
    assert "called_from_lib:libpython" in body


def _tsan_runtime() -> str:
    """Path of a preloadable libtsan runtime, or '' when absent."""
    import glob

    for pat in ("/usr/lib/*/libtsan.so.*", "/usr/lib/*/libtsan.so",
                "/usr/lib/gcc/*/*/libtsan.so"):
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[0]
    return ""


# the staging-pipeline concurrency scenario, run in a subprocess with
# the TSan runtime preloaded: PR 19's thread layout at the native
# boundary — two pump threads racing pop_batch/done_batch, a transfer-
# lane analog hammering the zone allocator (stage-in's native half),
# and a committer analog draining the lifecycle-event ring while
# retires are still being recorded.
_STAGING_SCENARIO = r"""
import ctypes, sys, threading
from parsec_tpu.native import abi

lib = ctypes.CDLL(sys.argv[1])
abi.bind(lib)

g = lib.pz_graph_new()
N = 64
ids = [lib.pz_graph_add_task(g, 0, i) for i in range(N)]
for i in range(0, N - 1, 2):          # half chains, half independent
    lib.pz_graph_add_dep(g, ids[i], ids[i + 1])
lib.pz_graph_sched_config(g, 0, 0, -1)
lib.pz_graph_events_enable(g, 1)
for t in ids:
    lib.pz_graph_task_commit(g, t)
lib.pz_graph_seal(g)

stop = threading.Event()
errors = []

# The interpreter is uninstrumented, so Thread.join's happens-before
# edge is invisible to the preloaded TSan runtime.  pz_graph_destroy
# synchronizes via the graph mutexes (lock-then-delete), which orders
# everything up to each thread's LAST mutex use — so every g-touching
# thread ends with a cap-0 events_drain (takes ev_mu) to publish its
# trailing lock-free atomic reads (the final quiesced check) too.
def _hb_fence():
    lib.pz_graph_events_drain(g, None, None, None, 0)

def pump():                           # pop/done from TWO threads
    buf = (ctypes.c_int64 * 8)()
    try:
        while not lib.pz_graph_quiesced(g):
            n = lib.pz_graph_pop_batch(g, buf, 8)
            if n > 0:
                lib.pz_graph_done_batch(g, buf, n)
        _hb_fence()
    except Exception as e:
        errors.append(e)

def stage_lane():                     # zone traffic beside the pump
    z = lib.pz_zone_new(1 << 20)
    try:
        while not stop.is_set():
            offs = [lib.pz_zone_alloc(z, 4096, 64) for _ in range(16)]
            for o in offs:
                if o >= 0:
                    lib.pz_zone_release(z, o)
            lib.pz_zone_used(z)
    except Exception as e:
        errors.append(e)
    finally:
        lib.pz_zone_destroy(z)

def committer():                      # event drain races the retires
    k = (ctypes.c_int32 * 32)()
    a = (ctypes.c_int64 * 32)()
    b = (ctypes.c_int64 * 32)()
    drained = 0
    try:
        while not stop.is_set():
            drained += lib.pz_graph_events_drain(g, k, a, b, 32)
        while lib.pz_graph_events_drain(g, k, a, b, 32):
            pass
    except Exception as e:
        errors.append(e)

threads = [threading.Thread(target=pump), threading.Thread(target=pump),
           threading.Thread(target=stage_lane),
           threading.Thread(target=committer)]
for t in threads:
    t.start()
threads[0].join(60); threads[1].join(60)
stop.set()
threads[2].join(60); threads[3].join(60)
assert not errors, errors
assert lib.pz_graph_quiesced(g), "pump did not quiesce"
lib.pz_graph_destroy(g)
print("TSAN-SCENARIO-OK")
"""


def test_tsan_staging_threads_race_free(tmp_path):
    """Run the staging-pipeline thread layout against the INSTRUMENTED
    engine: any data race in pop/done vs zone vs event-drain paths
    makes ThreadSanitizer fail the subprocess (exitcode=66)."""
    import os
    import sys

    if not _tsan_supported():
        pytest.skip("toolchain lacks -fsanitize=thread")
    rt = _tsan_runtime()
    if not rt:
        pytest.skip("no preloadable libtsan runtime")
    lib = native.build_tsan_library()
    script = tmp_path / "tsan_staging_scenario.py"
    script.write_text(_STAGING_SCENARIO)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env.update({
        "LD_PRELOAD": rt,
        "TSAN_OPTIONS": "suppressions="
                        f"{native.tsan_suppressions_path()} exitcode=66 "
                        "halt_on_error=0",
        # the scenario imports only parsec_tpu.native.abi (no jax)
        "PYTHONPATH": os.pathsep.join(
            p for p in (repo, os.environ.get("PYTHONPATH")) if p),
        "JAX_PLATFORMS": "cpu",
    })
    proc = subprocess.run(
        [sys.executable, str(script), lib],
        capture_output=True, text=True, timeout=240, env=env, cwd=repo)
    if proc.returncode != 0 and "ThreadSanitizer" not in proc.stderr:
        pytest.skip("TSan runtime refused to preload into the "
                    f"interpreter: {proc.stderr[-300:]}")
    assert "TSAN-SCENARIO-OK" in proc.stdout, (
        f"scenario failed\nstdout: {proc.stdout[-1000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")
    assert "WARNING: ThreadSanitizer" not in proc.stderr, (
        "data race in the native staging/pump paths:\n"
        + proc.stderr[-4000:])
    assert proc.returncode == 0
