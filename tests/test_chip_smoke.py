"""chip_smoke.py and the no-hidden-fallback repairs it forced.

The smoke itself only passes on a TPU; here its stage functions run at a
tiny size on the CPU backend (Pallas kernels interpreted, EXPLICITLY),
and the contracts around it are pinned: the default invocation fails
without a chip, the caches are placed from outside, a native library
that was not built from these sources is never used, Pallas never
interprets when lowered for a TPU, and the device path raises where it
used to carry on.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax

import chip_smoke as cs
from parsec_tpu import compile_cache as cc
from parsec_tpu import native
from parsec_tpu.native import abi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = cs.Sizes(tile_n=128, tile_nb=32, seg_n=256, seg_nb=32, kern=128,
                stencil=64, attn=(64, 64, 32), mesh_n=128, mesh_nb=32,
                interpret=True)


@pytest.fixture(scope="module")
def watch():
    return cs.CompileWatch()


@pytest.fixture(scope="module")
def problem():
    spd = np.asarray(cs.make_spd(TINY.tile_n, 0, jax.local_devices()[0]))
    return spd, np.linalg.cholesky(spd.astype(np.float64))


# ---------------------------------------------------------------------------
# the stages, tiny, on the CPU backend
# ---------------------------------------------------------------------------

def test_kernels_stage_tiny_interpreted():
    out = cs.stage_kernels(TINY, jax.local_devices()[0])
    assert set(out) == {
        "matmul_update_f32", "matmul_update_split_f32",
        "matmul_update_bf16", "matmul", "stencil_5pt",
        "stencil_5pt_fused", "flash_attention_block"}


def test_context_stage_tiny(watch, problem):
    out = cs.stage_context(TINY, *problem, watch, "cpu")
    assert out["tasks"] == 20 and not any(out["fallbacks"].values())
    assert out["warm"]["hits"] > 0


@pytest.mark.skipif(not native.available(), reason="needs the native core")
@pytest.mark.parametrize("use_pallas", [False, True])
def test_pump_stage_tiny(watch, problem, use_pallas):
    out = cs.stage_pump(TINY, *problem, watch, "cpu", use_pallas=use_pallas)
    assert out["executor"]["pumped_tasks"] == 20
    assert out["executor"]["trampoline_entries"] == 0
    assert out["warm"] == dict(out["warm"], misses=0, backend_compiles=0)


def test_segmented_stage_tiny(watch):
    out = cs.stage_segmented(TINY, watch, "cpu")
    assert out["err"] < cs.BF16_BAR and out["warm"]["misses"] == 0


def test_mesh_stage_tiny():
    out = cs.stage_mesh(TINY, "cpu")
    assert len({r["jdev_id"] for r in out["ranks"]}) == 4
    assert out["bytes_d2d"] > 0


def test_stage_refuses_the_wrong_platform(watch, problem):
    """Every stage asserts the platform its device module BOUND."""
    with pytest.raises(RuntimeError, match="expected 'tpu'"):
        cs.stage_context(TINY, *problem, watch, "tpu")


def test_a_nonzero_fallback_counter_fails_the_stage():
    with pytest.raises(RuntimeError, match="wave_fallbacks"):
        cs.require_no_fallback("x", {"aot_fallbacks": 0,
                                     "wave_fallbacks": 2})


# ---------------------------------------------------------------------------
# the command: fails without a chip, and alone
# ---------------------------------------------------------------------------

def _run_smoke(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_default_invocation_without_a_tpu_fails_and_prints_no_pass():
    p = _run_smoke(REPO, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run_smoke(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and '"ok"' not in p.stdout


def test_last_line_is_the_result_object_and_nothing_else():
    summary, result = cs.final_lines(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        {"setup": {}, "wall_s": 1.0})
    assert json.loads(result) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert "ok" not in json.loads(summary)
    assert summary.endswith('"claim": null}')


# ---------------------------------------------------------------------------
# a compile cache that is placed from outside
# ---------------------------------------------------------------------------

def test_cache_root_follows_jax_compilation_cache_dir(monkeypatch, tmp_path):
    monkeypatch.delenv("PARSEC_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.cache_root() == str(tmp_path)
    # every store of the runtime lives under it
    from parsec_tpu import tuning

    assert cc.default_store().dir == str(tmp_path / "exe")
    assert tuning.default_store().dir == str(tmp_path / "autotune")


def test_cache_root_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("PARSEC_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = cc.cache_root()
    assert root == os.path.join(REPO, ".parsec_tpu_cache")
    assert cc.cache_root() == root  # no pid, time or temporary name
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".parsec_tpu_cache/" in f.read().split()


def test_cache_switch_disables_or_rejects(monkeypatch):
    monkeypatch.setenv("PARSEC_TPU_COMPILE_CACHE", "0")
    assert cc.cache_root() is None and cc.default_store() is None
    # the variable used to name a directory: say so, do not ignore it
    monkeypatch.setenv("PARSEC_TPU_COMPILE_CACHE", "/some/dir")
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        cc.cache_root()


def test_no_code_sets_the_xla_cache_dir():
    """Only tests may call jax.config.update("jax_compilation_cache_dir")."""
    needle = 'update("jax_compilation_cache_dir"'
    hits = []
    for base, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "tests"]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    if needle in f.read().replace("\n", "").replace(" ", ""):
                        hits.append(os.path.relpath(path, REPO))
    assert hits == []


# ---------------------------------------------------------------------------
# the native library is what these sources build
# ---------------------------------------------------------------------------

def test_library_name_carries_the_digest_of_sources_and_flags(tmp_path):
    cmd = native.build_command()
    digest = abi.source_digest(cmd)
    assert os.path.basename(native.lib_path()) == \
        f"libparsec_core-{digest}.so"
    assert abi.source_digest(cmd + ["-DX"]) != digest  # flags
    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    with open(src / "zone.cpp", "a") as f:
        f.write("// edited\n")
    assert abi.source_digest(cmd, str(src)) != digest  # content
    assert native.lib_path(tsan=True) != native.lib_path(tsan=False)


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_a_library_from_other_sources_is_not_used(tmp_path, monkeypatch):
    """A stale or foreign .so in native/build/ — whatever its mtime —
    has another name: the build compiles afresh instead of loading it."""
    src, build = tmp_path / "src", tmp_path / "build"
    shutil.copytree(native._SRC_DIR, src)
    build.mkdir()
    stale = build / os.path.basename(native.lib_path())
    stale.write_bytes(b"not a shared library")
    legacy = build / "libparsec_core.so"
    legacy.write_bytes(b"not a shared library")
    os.utime(stale, (2**31, 2**31))  # "newer" than any source
    with open(src / "zone.cpp", "a") as f:
        f.write("// edited\n")
    monkeypatch.setattr(abi, "SRC_DIR", str(src))
    monkeypatch.setattr(native, "_SRC_DIR", str(src))
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    built = native.build_library()
    assert built not in (str(stale), str(legacy))
    with open(built, "rb") as f:
        assert f.read(4) == b"\x7fELF"
    # and the ABI lint names a library that lacks these sources' digest
    assert [f.code for f in abi.abi_findings(str(stale), str(src))
            if f.code == "ENG005"]
    assert not [f for f in abi.abi_findings(built, str(src))
                if f.code == "ENG005"]


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

def test_pallas_lowered_for_a_tpu_is_never_interpreted():
    """``interpret=None`` is resolved at LOWERING, per platform: the same
    traced program carries the Mosaic kernel when lowered for a TPU and
    the interpreter's ops when lowered for the CPU."""
    import jax.export as jex
    import jax.numpy as jnp

    from parsec_tpu.ops import pallas_kernels as pk

    t = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    fn = jax.jit(lambda C, A, B: pk.matmul_update(C, A, B))
    tpu = jex.export(fn, platforms=("tpu",))(t, t, t).mlir_module()
    cpu = jex.export(fn, platforms=("cpu",))(t, t, t).mlir_module()
    assert "tpu_custom_call" in tpu
    assert "tpu_custom_call" not in cpu


class _FakeChip:
    platform, id, device_kind = "tpu", 0, "fake"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def _bare_device(monkeypatch, jdev):
    import types

    from parsec_tpu.device.tpu import TpuDevice

    monkeypatch.setattr(jax, "local_devices", lambda: [jdev])
    return TpuDevice(types.SimpleNamespace(rank=0, nranks=1, devices=[]), 1)


def test_tpu_without_a_memory_limit_is_an_error(monkeypatch):
    with pytest.raises(RuntimeError, match="bytes_limit"):
        _bare_device(monkeypatch, _FakeChip({}))


def test_tpu_without_the_native_zone_is_an_error(monkeypatch):
    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(RuntimeError, match="native core is unavailable"):
        _bare_device(monkeypatch, _FakeChip({"bytes_limit": 1 << 30}))


def test_device_module_failing_to_attach_fails_the_context(monkeypatch):
    from parsec_tpu import Context
    from parsec_tpu.device.tpu import TpuDevice

    def broken(self):
        raise OSError("chip held by another process")

    monkeypatch.setattr(TpuDevice, "attach", broken)
    with pytest.raises(RuntimeError, match="'tpu' failed to attach"):
        Context(nb_cores=1)


def test_launcher_refuses_device_ranks(monkeypatch):
    from parsec_tpu.comm.launch import launch

    with pytest.raises(RuntimeError, match="CPU-device only"):
        launch(2, ["-c", "pass"], env={"JAX_PLATFORMS": "tpu"})
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="CPU-device only"):
        launch(2, ["-c", "pass"])
    # CPU ranks start as before
    res = launch(2, ["-c", "import os; print(os.environ['PARSEC_TPU_RANK'])"],
                 env={"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert sorted(r.stdout.strip() for r in res) == ["0", "1"]
