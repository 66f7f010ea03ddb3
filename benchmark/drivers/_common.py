"""What the drivers share: the device module's counters, the fallback
counters that must stay 0, host tiles in and out of a tiled matrix.
Calling sequences copied from ``chip_smoke.py``'s stages."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

_CACHE_FALLBACKS = ("aot_fallbacks", "serialize_errors", "local_only",
                    "blob_errors")
_DEVICE_FALLBACKS = ("wave_fallbacks", "submit_retries",
                     "stage_batch_fallbacks")
_DEVICE_COUNTERS = ("executed_tasks", "wave_submits", "wave_tasks",
                    "bytes_in", "bytes_out", "bytes_d2d", "evictions")


def tpu_device(ctx):
    return next(d for d in ctx.devices if d.mca_name == "tpu")


def require_platform(device, platform: str) -> None:
    if device.jdev.platform != platform:
        raise RuntimeError(
            f"device module bound {device.jdev} (platform "
            f"{device.jdev.platform!r}), expected {platform!r}")


def device_counters(devices, caches) -> Dict[str, float]:
    """Cumulative counters summed over the device modules, and the
    counters that mean a slower path stood in (all 0 on a healthy run)."""
    out: Dict[str, float] = {k: 0 for k in _DEVICE_COUNTERS}
    out["fallbacks"] = 0
    for dev in devices:
        for k in _DEVICE_COUNTERS:
            out[k] += dev.stats.get(k, 0)
        out["fallbacks"] += sum(dev.stats.get(k, 0)
                                for k in _DEVICE_FALLBACKS)
        out["fallbacks"] += int(dev._zone is None)
    for cache in caches:
        snap = cache.snapshot()
        out["fallbacks"] += sum(snap.get(k, 0) for k in _CACHE_FALLBACKS)
    return out


def dpotrf_taskpool(A, options: Dict[str, Any]):
    """The dpotrf PTG over ``A``, device chores only; ``options`` are the
    configuration's (its control switches the lower-precision bodies on)."""
    from parsec_tpu.ops import cholesky_ptg

    return cholesky_ptg(
        use_tpu=True, use_cpu=False,
        use_pallas=bool(options.get("use_pallas", False)),
        bf16_updates=bool(options.get("bf16_updates", False)),
    ).taskpool(NT=A.mt, A=A)


def task_violations(before, after, ntasks: int,
                    done: bool = True) -> List[str]:
    """The guarantees every solve is held to: all ``ntasks`` tasks of the
    DAG executed on the device, no fallback counter moved off 0."""
    executed = after["executed_tasks"] - before["executed_tasks"]
    out = []
    if not done or executed != ntasks:
        out.append(f"quiesced {done}, device executed {executed} of "
                   f"{ntasks} tasks")
    if after["fallbacks"]:
        out.append(f"{after['fallbacks']} fallbacks ran")
    return out


def fresh_matrix(cls, problem: Dict[str, Any], **grid):
    """A tiled matrix over copies of the seed's host tiles (the runtime
    may write into a tile it is given); only this rank's tiles."""
    n, nb = problem["n"], problem["nb"]
    A = cls(n, n, nb, nb, name="A", dtype=np.float32, **grid)
    for (i, j), tile in problem["tiles"].items():
        if A.rank_of(i, j) != A.myrank:
            continue
        tile = tile.copy()
        d = A.data_of(i, j)
        copy = d.get_copy(0) or d.attach_copy(0, tile)
        copy.payload = tile
    return A


def local_keys(A, problem) -> List[tuple]:
    return [k for k in problem["tiles"] if A.rank_of(*k) == A.myrank]


def sync(A, keys) -> None:
    """Wait until every tile of the factor is ready where it was
    computed: JAX dispatch is asynchronous."""
    for k in keys:
        payload = A.data_of(*k).newest_copy().payload
        if hasattr(payload, "block_until_ready"):
            payload.block_until_ready()


def gather_home(A, keys) -> Dict[tuple, np.ndarray]:
    """The factor as host tiles."""
    return {k: np.asarray(A.data_of(*k).newest_copy().payload)
            for k in keys}
