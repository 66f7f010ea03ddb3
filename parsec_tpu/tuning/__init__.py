"""nb / wave autotuner: search tile sizes per (op, N, dtype, device
generation) by timed short runs, persist winners next to the executable
cache, and let ``ops.*`` pick the tuned nb by default (``nb="auto"``).

"Design in Tiles" (PAPERS.md) frames tile-size selection on tile-based
many-PE accelerators as a search problem; with the executable cache
(:mod:`parsec_tpu.compile_cache`) making repeated compiles cheap, the
search becomes affordable: each candidate's programs compile once and
reload from the store on every later run — including the production run
that finally uses the winner.

Layout: one JSON file per tuning key under ``<cache_root>/autotune/``
(:func:`parsec_tpu.compile_cache.cache_root` — placed by
``JAX_COMPILATION_CACHE_DIR``, like the executable store).  Entries
record every candidate's measured seconds, the winner, and enough
metadata to judge staleness.  Corrupt files read as absent.

CLI: ``python -m parsec_tpu.profiling.tools autotune --op dpotrf
--n 1024 --nb 64,128,256`` (see ``tools autotune --help``).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from ..utils import debug

_SAFE = re.compile(r"[^A-Za-z0-9_.-]")


def _device_kind(device=None) -> str:
    """Device-generation component of a tuning key (``TPU v4`` and
    ``TPU v5e`` want different tiles; the CPU test backend is its own
    kind)."""
    if device is not None:
        kind = getattr(device, "device_kind",
                       getattr(getattr(device, "jdev", None),
                               "device_kind", None))
        if kind:
            return str(kind)
    try:
        import jax

        return str(jax.devices()[0].device_kind)
    except Exception:
        return "none"


def tune_key(op: str, n: int, dtype, device_kind: str,
             param: str = "nb") -> str:
    d = str(getattr(dtype, "name", dtype))
    raw = f"{op}_n{n}_{d}_{device_kind}_{param}"
    return _SAFE.sub("-", raw)


class TuningStore:
    """One JSON document per tuning key; atomic writes, corrupt files
    read as absent (same discipline as the executable store)."""

    def __init__(self, directory: str):
        self.dir = directory
        self._lock = threading.Lock()
        #: entries :func:`resolve_nb` found here — a run whose behaviour
        #: a stored winner could change (``nb="auto"``, fusion's
        #: ``max_tasks``) can say so
        self.found = 0

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.json")

    def load(self, key: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self._path(key)) as f:
                doc = json.load(f)
            if not isinstance(doc, dict) or "best" not in doc:
                raise ValueError("not a tuning document")
            return doc
        except FileNotFoundError:
            return None
        except (OSError, ValueError, json.JSONDecodeError) as e:
            debug.warning("tuning entry %s unreadable (%s); ignoring",
                          key, e)
            return None

    def save(self, key: str, doc: Dict[str, Any]) -> bool:
        with self._lock:
            try:
                os.makedirs(self.dir, exist_ok=True)
                tmp = f"{self._path(key)}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                os.replace(tmp, self._path(key))
                return True
            except OSError as e:
                debug.warning("tuning write of %s failed: %s", key, e)
                return False

    def entries(self) -> List[Dict[str, Any]]:
        out = []
        try:
            names = sorted(os.listdir(self.dir))
        except OSError:
            return out
        for nme in names:
            if nme.endswith(".json"):
                doc = self.load(nme[:-5])
                if doc is not None:
                    out.append(dict(doc, key=nme[:-5]))
        return out

    def purge(self) -> int:
        n = 0
        try:
            for nme in os.listdir(self.dir):
                if nme.endswith(".json"):
                    os.unlink(os.path.join(self.dir, nme))
                    n += 1
        except OSError:
            pass
        return n


_store_lock = threading.Lock()
_stores: Dict[str, TuningStore] = {}
#: in-memory fallback store when the cache root is disabled — tuning
#: results still apply within the process
_memory_docs: Dict[str, Dict[str, Any]] = {}


class _MemoryStore(TuningStore):
    def __init__(self):
        super().__init__("<memory>")

    def load(self, key):
        return _memory_docs.get(key)

    def save(self, key, doc):
        _memory_docs[key] = doc
        return True

    def entries(self):
        return [dict(d, key=k) for k, d in sorted(_memory_docs.items())]

    def purge(self):
        n = len(_memory_docs)
        _memory_docs.clear()
        return n


def default_store() -> TuningStore:
    from ..compile_cache import cache_root

    root = cache_root()
    with _store_lock:
        if root is None:
            key = "<memory>"
            st = _stores.get(key)
            if st is None:
                st = _stores[key] = _MemoryStore()
            return st
        st = _stores.get(root)
        if st is None:
            st = _stores[root] = TuningStore(
                os.path.join(root, "autotune"))
        return st


# ---------------------------------------------------------------------------
# lookup (the ``nb="auto"`` resolution path)
# ---------------------------------------------------------------------------

def resolve_nb(op: str, n: int, dtype="float32", *, device=None,
               default: Optional[int] = None,
               divides: Optional[int] = None,
               param: str = "nb",
               store: Optional[TuningStore] = None) -> Optional[int]:
    """Tuned nb for (op, n, dtype, device generation), or ``default``.
    ``divides=N`` rejects a winner that does not divide N (segmented
    drivers require it) — the default then stands.  ``param`` selects a
    non-default tuning axis (the attention graphs read ``q_block`` /
    ``kv_block`` under op ``attention``)."""
    st = store if store is not None else default_store()
    doc = st.load(tune_key(op, n, dtype, _device_kind(device), param))
    if doc is None:
        return default
    st.found += 1
    best = doc.get("best")
    if not isinstance(best, int) or best <= 0:
        return default
    if divides is not None and divides % best:
        debug.verbose(1, "tuning",
                      "tuned nb=%d for %s does not divide N=%d; using "
                      "default %r", best, op, divides, default)
        return default
    return best


def auto_nb(nb, op: str, n: int, dtype="float32", *, device=None,
            default: int = 512, divides: Optional[int] = None):
    """The ``nb="auto"`` entry point ops use: pass through explicit
    values, resolve "auto" against the tuning store."""
    if nb != "auto":
        return nb
    d = default
    if divides is not None:
        while d > 1 and divides % d:
            d //= 2
    return resolve_nb(op, n, dtype, device=device, default=d,
                      divides=divides)


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def autotune(op: str, n: int, dtype, *, param: str = "nb",
             candidates: Sequence[int],
             runner: Callable[[int], float],
             reps: int = 2, device=None,
             store: Optional[TuningStore] = None,
             meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Generic timed search: ``runner(value)`` runs one short workload
    and returns seconds; the best median over ``reps`` wins and is
    persisted.  Every candidate gets ONE untimed warmup run first — each
    tile size compiles its own program set, and without the per-
    candidate warmup the sweep would measure compile time, biased by
    candidate order (the executable cache absorbs the warmup cost on
    later sweeps).  A raising candidate is recorded as failed and
    skipped — an autotune sweep must survive an OOM-ing tile size."""
    timings: Dict[str, float] = {}
    failures: Dict[str, str] = {}
    for cand in candidates:
        samples = []
        try:
            runner(cand)  # warmup: compiles land in the cache, untimed
            for _ in range(max(1, reps)):
                samples.append(float(runner(cand)))
        except Exception as e:
            failures[str(cand)] = f"{type(e).__name__}: {e}"[:160]
            debug.warning("autotune %s=%s failed: %s", param, cand, e)
            continue
        samples.sort()
        timings[str(cand)] = samples[len(samples) // 2]
    if not timings:
        raise RuntimeError(
            f"autotune of {op} {param}: every candidate failed "
            f"({failures})")
    best = int(min(timings, key=timings.get))
    doc = {
        "op": op, "n": int(n),
        "dtype": str(getattr(dtype, "name", dtype)),
        "device_kind": _device_kind(device), "param": param,
        "best": best, "timings_s": timings, "failures": failures,
        "reps": int(reps), "created": time.time(),
        "meta": dict(meta or ()),
    }
    st = store if store is not None else default_store()
    st.save(tune_key(op, n, dtype, _device_kind(device), param), doc)
    return doc


def _default_nb_candidates(n: int) -> List[int]:
    cands = [nb for nb in (64, 128, 256, 512, 1024) if nb <= max(64, n)]
    return [nb for nb in cands if n % nb == 0] or cands[:1]


def dpotrf_runner(n: int, dtype="float32", *, nb_cores: int = 4,
                  use_device: bool = True) -> Callable[[int], float]:
    """Build the default dpotrf search workload: one dynamic-runtime
    factorization per call, fresh taskpool each time (the cost being
    tuned includes dispatch), matrix built once."""
    import numpy as np

    from ..core.context import Context
    from ..datadist import TiledMatrix
    from ..ops.cholesky import cholesky_ptg

    rng = np.random.default_rng(7)
    dt = np.dtype(dtype)
    M = rng.standard_normal((n, n)).astype(dt)
    spd = (M @ M.T + n * np.eye(n, dtype=dt)).astype(dt)
    ctx = Context(nb_cores=nb_cores)

    def run(nb: int) -> float:
        if n % nb:
            raise ValueError(f"nb={nb} does not divide N={n}")
        A = TiledMatrix(n, n, nb, nb, name="A", dtype=dt).from_array(spd)
        tp = cholesky_ptg(use_tpu=use_device,
                          use_cpu=not use_device).taskpool(NT=A.mt, A=A)
        t0 = time.perf_counter()
        ctx.add_taskpool(tp)
        if not tp.wait(timeout=600):
            raise RuntimeError("dpotrf candidate did not quiesce")
        return time.perf_counter() - t0

    run.close = ctx.fini  # type: ignore[attr-defined]
    return run


#: built-in segmented workloads, keyed by the EXACT op names the
#: drivers' ``nb="auto"`` resolution looks up — tuning one of these
#: persists under the key the next ``Segmented*(ctx, n)`` reads
_SEG_DRIVERS = {
    "dpotrf_seg": ("segmented_chol", "SegmentedCholesky"),
    "getrf_seg": ("segmented_lu", "SegmentedLU"),
    "geqrf_seg": ("segmented_qr", "SegmentedQR"),
}


def segmented_runner(op: str, n: int, dtype="float32", *,
                     nb_cores: int = 4) -> Callable[[int], float]:
    """Build the search workload for a segmented driver op
    (``dpotrf_seg`` / ``getrf_seg`` / ``geqrf_seg``): each call
    constructs the driver with an explicit nb and times one full
    factorization through the runtime, matrix built once."""
    import importlib

    import numpy as np

    from ..core.context import Context

    mod_name, cls_name = _SEG_DRIVERS[op]
    cls = getattr(importlib.import_module(f"..ops.{mod_name}",
                                          __package__), cls_name)
    rng = np.random.default_rng(7)
    dt = np.dtype(dtype)
    M = rng.standard_normal((n, n)).astype(dt)
    if op == "dpotrf_seg":
        M = (M @ M.T + n * np.eye(n, dtype=dt)).astype(dt)
    ctx = Context(nb_cores=nb_cores)

    def run(nb: int) -> float:
        if n % nb:
            raise ValueError(f"nb={nb} does not divide N={n}")
        drv = cls(ctx, n, nb=nb)
        t0 = time.perf_counter()
        drv(M)
        return time.perf_counter() - t0

    run.close = ctx.fini  # type: ignore[attr-defined]
    return run


def autotune_nb(op: str, n: int, dtype="float32", *,
                candidates: Optional[Iterable[int]] = None,
                reps: int = 2, runner: Optional[Callable] = None,
                store: Optional[TuningStore] = None) -> Dict[str, Any]:
    """Search nb for ``op`` (built-in workloads: ``dpotrf`` plus the
    segmented drivers in :data:`_SEG_DRIVERS`; other ops pass
    ``runner``)."""
    cands = list(candidates) if candidates else _default_nb_candidates(n)
    close = None
    if runner is None:
        if op == "dpotrf":
            runner = dpotrf_runner(n, dtype)
        elif op in _SEG_DRIVERS:
            runner = segmented_runner(op, n, dtype)
        else:
            raise ValueError(
                f"no built-in workload for op {op!r} (built-ins: dpotrf, "
                f"{', '.join(sorted(_SEG_DRIVERS))}); pass runner=")
        close = getattr(runner, "close", None)
    try:
        return autotune(op, n, dtype, param="nb", candidates=cands,
                        runner=runner, reps=reps, store=store)
    finally:
        if close is not None:
            close()


def attention_runner(s: int, *, d: int = 64, heads: int = 2,
                     batch: int = 1, dtype="float32", causal: bool = True,
                     nb_cores: int = 4, param: str = "q_block",
                     other_block: Optional[int] = None,
                     use_device: bool = True) -> Callable[[int], float]:
    """Build the attention block-size search workload: each call runs one
    blockwise flash-attention taskpool (``ops.attention``) through the
    dynamic runtime with the candidate value bound to ``param``
    (``q_block`` or ``kv_block``); the other block size stays at
    ``other_block`` (default 128-capped).  QKV built once."""
    import numpy as np

    from ..core.context import Context
    from ..ops.attention import run_flash_attention

    if param not in ("q_block", "kv_block"):
        raise ValueError(f"attention tunes q_block/kv_block, not {param!r}")
    rng = np.random.default_rng(7)
    dt = np.dtype(dtype)
    mk = lambda: rng.standard_normal((batch, s, heads, d)).astype(dt)
    q, k, v = mk(), mk(), mk()
    other = other_block if other_block is not None else min(128, s)
    ctx = Context(nb_cores=nb_cores)

    def run(block: int) -> float:
        if block <= 0 or block > s:
            raise ValueError(f"{param}={block} outside (0, {s}]")
        kw = {param: block,
              ("kv_block" if param == "q_block" else "q_block"): other}
        t0 = time.perf_counter()
        run_flash_attention(ctx, q, k, v, causal=causal,
                            use_tpu=use_device, use_cpu=not use_device,
                            **kw)
        return time.perf_counter() - t0

    run.close = ctx.fini  # type: ignore[attr-defined]
    return run


def _default_block_candidates(s: int) -> List[int]:
    return [b for b in (64, 128, 256, 512) if b <= s] or [s]


def autotune_attention(s: int, *, d: int = 64, heads: int = 2,
                       batch: int = 1, dtype="float32",
                       causal: bool = True,
                       candidates: Optional[Iterable[int]] = None,
                       reps: int = 2,
                       store: Optional[TuningStore] = None
                       ) -> Dict[str, Dict[str, Any]]:
    """Search ``q_block`` and ``kv_block`` for the attention graphs at
    sequence length ``s`` (two sequential single-axis sweeps; each
    winner persists under op ``attention`` with its own ``param`` — the
    EXACT keys ``q_block="auto"``/``kv_block="auto"`` read in
    :mod:`parsec_tpu.ops.attention`).  Returns ``{param: doc}``."""
    cands = list(candidates) if candidates else _default_block_candidates(s)
    docs: Dict[str, Dict[str, Any]] = {}
    for param in ("q_block", "kv_block"):
        # the kv sweep runs against the q_block WINNER, not the default,
        # so the persisted (q_block, kv_block) pair was actually timed
        # together (in that order; a full cross product is the caller's
        # candidates= job)
        other = docs["q_block"]["best"] if docs.get("q_block") else None
        runner = attention_runner(s, d=d, heads=heads, batch=batch,
                                  dtype=dtype, causal=causal, param=param,
                                  other_block=other)
        try:
            docs[param] = autotune("attention", s, dtype, param=param,
                                   candidates=cands, runner=runner,
                                   reps=reps, store=store,
                                   meta={"d": d, "heads": heads,
                                         "batch": batch,
                                         "causal": causal})
        finally:
            runner.close()
    return docs


def autotune_wave(n: int = 1024, nb: int = 64, dtype="float32", *,
                  candidates: Sequence[int] = (0, 2, 4, 8),
                  reps: int = 2,
                  store: Optional[TuningStore] = None) -> Dict[str, Any]:
    """Search the device wave-batch minimum (``device_tpu_wave_batch``)
    on a dynamic dpotrf: each candidate runs in a FRESH context (the
    device reads the parameter at attach).  The winner persists under
    param ``wave`` and is applied by setting the MCA parameter."""
    import numpy as np

    from ..core.context import Context
    from ..datadist import TiledMatrix
    from ..ops.cholesky import cholesky_ptg
    from ..utils import mca_param

    rng = np.random.default_rng(7)
    dt = np.dtype(dtype)
    M = rng.standard_normal((n, n)).astype(dt)
    spd = (M @ M.T + n * np.eye(n, dtype=dt)).astype(dt)

    def run(wave: int) -> float:
        mca_param.set_param("device", "tpu_wave_batch", int(wave))
        ctx = Context(nb_cores=4)
        try:
            A = TiledMatrix(n, n, nb, nb, name="A",
                            dtype=dt).from_array(spd)
            tp = cholesky_ptg(use_tpu=True,
                              use_cpu=False).taskpool(NT=A.mt, A=A)
            t0 = time.perf_counter()
            ctx.add_taskpool(tp)
            if not tp.wait(timeout=600):
                raise RuntimeError("wave candidate did not quiesce")
            return time.perf_counter() - t0
        finally:
            ctx.fini()

    # a user's pre-existing explicit API setting must survive the sweep
    # (unset alone would silently revert them to the default)
    restore = None
    try:
        if mca_param.source("device", "tpu_wave_batch") == "api":
            restore = mca_param.params.get("device", "tpu_wave_batch")
    except KeyError:
        pass
    try:
        return autotune("dpotrf", n, dt, param="wave",
                        candidates=list(candidates), runner=run,
                        reps=reps, store=store,
                        meta={"nb": nb})
    finally:
        if restore is not None:
            mca_param.set_param("device", "tpu_wave_batch", restore)
        else:
            mca_param.params.unset("device", "tpu_wave_batch")
