"""Persistent AOT executable cache + cross-rank compile distribution.

Compile time is the worst number in the bench trajectory (a QR program
set took 460 s to compile on the chip in round 3 while the factorization
itself runs in seconds), and on an N-rank mesh every rank pays its own
XLA compile for every (kernel, shape) pair — the PR 4 ``tpu_wave_batch``
auto-disable works around exactly that explosion.  This module kills the
cold start in three layers:

* **in-process LRU** — every jitted body / wave program / whole-DAG
  program is keyed by a :func:`fingerprint` of (task-class body code
  hash, input shapes/dtypes, donation/static args, backend kind,
  jax+jaxlib version, cache format); a second identical compile in one
  process is a dictionary lookup (pinned by the tier-1 zero-recompile
  test);

* **content-addressed disk store** — programs whose trace+lower cost at
  least ``runtime_compile_cache_min_share_s`` are serialized with
  ``jax.export`` (StableHLO; device-portable) and written atomically
  under ``<cache_root>/exe`` (see :func:`cache_root`: the directory
  ``JAX_COMPILATION_CACHE_DIR`` names, else one fixed git-ignored path
  inside the checkout).  Loads are corruption-safe: a bad magic /
  truncated blob / checksum mismatch logs one warning and falls back to
  a fresh compile — never a crash.  The root itself is XLA's own
  persistent compilation cache, so the backend-compile half of a warm
  load is a disk read too;

* **compile-once-ship-serialized** — on a multi-rank mesh the rank that
  compiles a new program broadcasts the serialized executable to its
  peers over the comm engine (a ``TAG_CTL`` ``"compile"`` op via
  :meth:`CommEngine.register_ctl`; blobs above the eager limit ride the
  PR 4 rendezvous chunk machinery through ``mem_register``/
  ``get_part``), so an N-rank mesh pays ~1 trace+compile per program
  instead of N.  Received blobs install into the peer's preload map and
  its disk store.

Serialization notes (jax/jaxlib 0.9.0): on the CPU backend, executing
a DESERIALIZED exported module before any LAPACK lowering rule ran in
the process segfaults jaxlib — :func:`_ensure_custom_call_targets` runs
once before any deserialized execution.  Donation survives the export
round-trip (re-applied via ``donate_argnums`` at AOT compile).  Programs
that fail to export (e.g. host callbacks) simply stay process-local:
counted, never fatal.
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .profiling import jobtrace
from .utils import debug, mca_param

#: bump when the entry layout / fingerprint recipe changes: old entries
#: simply stop matching (they are garbage-collected by ``tools cache
#: purge --stale``)
CACHE_FORMAT = 2
_MAGIC = b"PZEXE1"
#: in-process LRU capacity of the executable cache (live compiled
#: programs); the largest set a cell compiles is 66
MEM_ENTRIES = 512
_CTL_OP = "compile"

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _scrub(s: str) -> str:
    """Drop memory addresses from reprs: ``<fn at 0x7f..>`` must
    fingerprint identically across processes."""
    return _ADDR_RE.sub("0xX", s)


def _code_parts(code, out: List[str], depth: int = 0) -> None:
    if depth > 6:  # pathological nesting: stop, stay stable
        return
    out.append(code.co_name)
    out.append(hashlib.sha1(code.co_code).hexdigest())
    out.append(repr(code.co_names))
    out.append(repr(code.co_varnames[:code.co_argcount]))
    for const in code.co_consts:
        if hasattr(const, "co_code"):
            _code_parts(const, out, depth + 1)
        else:
            out.append(_scrub(repr(const)))


def _value_part(v, out: List[str], depth: int = 0) -> None:
    """Stable description of a closure/default value."""
    if depth > 4:
        out.append(f"<deep:{type(v).__name__}>")
        return
    if callable(v) and hasattr(v, "__code__"):
        _callable_parts(v, out)
    elif isinstance(v, np.ndarray):
        # FULL content hash: two constant tables differing only past a
        # prefix must not share a persistent-cache key (closure
        # constants are typically small; this runs once per wrapper)
        h = hashlib.sha1(np.ascontiguousarray(v).tobytes())
        out.append(f"nd:{v.shape}:{v.dtype}:{h.hexdigest()}")
    elif isinstance(v, (tuple, list)):
        out.append(f"{type(v).__name__}[")
        for x in v:
            _value_part(x, out, depth + 1)
        out.append("]")
    elif isinstance(v, dict):
        out.append("{")
        for k in sorted(v, key=repr):
            out.append(_scrub(repr(k)))
            _value_part(v[k], out, depth + 1)
        out.append("}")
    elif isinstance(v, (int, float, bool, str, bytes, complex,
                        type(None))):
        out.append(repr(v))
    else:
        try:
            # device array in a closure: hash the CONTENT when small
            # enough (a D2H sync at fingerprint time is fine — this
            # runs once per wrapper, on the compile path).  Very large
            # baked constants keep the shape/dtype identity with an
            # explicit marker: such programs can collide across
            # distinct constant contents, so the caller comment in
            # code_fingerprint's contract carries the caveat.
            shape, dtype = tuple(v.shape), v.dtype
            nbytes = int(getattr(v, "nbytes", 1 << 30))
            if nbytes <= (1 << 20):
                h = hashlib.sha1(
                    np.ascontiguousarray(np.asarray(v)).tobytes())
                out.append(f"devnd:{shape}:{dtype}:{h.hexdigest()}")
            else:
                out.append(f"devnd-large:{shape}:{dtype}")
        except Exception:
            out.append(f"<{type(v).__module__}.{type(v).__name__}>")


def _callable_parts(fn: Callable, out: List[str]) -> None:
    """Accumulate the identity parts of a callable into ``out``."""
    fn = getattr(fn, "__wrapped__", fn)
    try:
        import functools

        if isinstance(fn, functools.partial):
            out.append("partial")
            _value_part(fn.args, out)
            _value_part(fn.keywords, out)
            _callable_parts(fn.func, out)
            return
    except Exception:
        pass
    code = getattr(fn, "__code__", None)
    if code is None:
        out.append(_scrub(repr(fn)))
        return
    out.append(getattr(fn, "__qualname__", ""))
    _code_parts(code, out)
    for d in (getattr(fn, "__defaults__", None) or ()):
        _value_part(d, out)
    closure = getattr(fn, "__closure__", None) or ()
    for cell in closure:
        try:
            _value_part(cell.cell_contents, out)
        except ValueError:  # empty cell
            out.append("<empty-cell>")


def code_fingerprint(fn: Callable) -> str:
    """Stable content hash of a Python callable: bytecode (recursively
    through nested code objects), names, defaults and closure values —
    through ``functools.partial`` wrappers too.  Changing the body's
    code or a baked parameter changes the fingerprint; re-importing the
    same source does not."""
    out: List[str] = []
    _callable_parts(fn, out)
    return hashlib.sha256("|".join(out).encode()).hexdigest()[:24]


#: dtype -> its name as :func:`argsig` spells it (numpy spells a dtype's
#: name in Python, 2.6 us of the 3.4 an array argument cost: once a dtype)
_DTYPE_NAMES: Dict[Any, str] = {}


def _argsig_one(a) -> Tuple:
    if a is None:
        return ("none",)
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            name = _DTYPE_NAMES.get(dtype)
            if name is None:
                name = _DTYPE_NAMES[dtype] = str(dtype)
        except TypeError:  # a dtype-like that does not hash
            name = str(dtype)
        wk = bool(getattr(a, "weak_type", False))
        return ("a", tuple(shape), name, wk)
    if isinstance(a, (tuple, list)):
        return ("t", tuple(_argsig_one(x) for x in a))
    return ("s", type(a).__name__)


def argsig(args: Tuple) -> Tuple:
    """Light per-call signature: shapes/dtypes of array args, types of
    scalars.  Computed on the dispatch hot path — attribute access only,
    no tracing.  The tuple is part of :func:`fingerprint`, the name of
    every entry of the disk store: its spelling never changes without
    ``CACHE_FORMAT``."""
    return tuple(_argsig_one(a) for a in args)


def _platform() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "none"


def _versions() -> str:
    try:
        import jax
        import jaxlib

        return f"{jax.__version__}/{jaxlib.__version__}"
    except Exception:
        return "none"


def fingerprint(key: Any, sig: Tuple, *, donate: Tuple = (),
                backend: Optional[str] = None) -> str:
    """The content address of one executable: program key (body code
    hash + structural parts), input shapes/dtypes, donation, backend
    kind, jax+jaxlib versions, cache format."""
    parts = (CACHE_FORMAT, _versions(),
             backend if backend is not None else _platform(),
             tuple(donate), _scrub(repr(key)), sig)
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:40]


# ---------------------------------------------------------------------------
# deserialized-execution safety
# ---------------------------------------------------------------------------

_cct_done = False
_cct_lock = threading.Lock()


def _ensure_custom_call_targets() -> None:
    """On the CPU backend, executing a DESERIALIZED exported module
    before LAPACK's kernels are initialized segfaults jaxlib: the
    lowering rules that initialize them never ran in this process.
    Lower (not compile) one tiny cholesky once so the rule runs.  Other
    backends have no LAPACK custom calls; nothing to do there."""
    global _cct_done
    if _cct_done:
        return
    with _cct_lock:
        if _cct_done:
            return
        if _platform() == "cpu":
            import jax
            import jax.numpy as jnp

            jax.jit(jnp.linalg.cholesky).lower(
                jax.ShapeDtypeStruct((2, 2), jnp.float32))
        _cct_done = True


# ---------------------------------------------------------------------------
# on-disk store
# ---------------------------------------------------------------------------

#: where every cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed, git-ignored directory inside the checkout.  The path is
#: part of XLA's cache key, so it is never a temporary name, pid or time.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".parsec_tpu_cache")


def cache_root() -> Optional[str]:
    """The ONE directory every cache of this runtime lives under — XLA's
    persistent compilation cache in the root itself, the executable
    store in ``<root>/exe``, the tuning store in ``<root>/autotune`` —
    or None when the disk layer is disabled
    (``PARSEC_TPU_COMPILE_CACHE=0``).  The root is placed from outside:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else :data:`_CHECKOUT_CACHE`."""
    switch = os.environ.get("PARSEC_TPU_COMPILE_CACHE", "1").strip()
    if switch in ("", "0"):
        return None
    if switch != "1":
        raise ValueError(
            f"PARSEC_TPU_COMPILE_CACHE={switch!r}: only 0 (disable the "
            "disk layer) or 1 is accepted; the cache directory is placed "
            "with JAX_COMPILATION_CACHE_DIR")
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    return os.path.expanduser(placed) if placed else _CHECKOUT_CACHE


class DiskStore:
    """Content-addressed executable store: one ``<fp>.exe`` file per
    entry — a JSON header line (magic, format, meta, blob sha256/len)
    followed by the raw serialized executable.  Writes are atomic
    (tmp + ``os.replace``), so concurrent writers of the same entry
    cannot interleave; loads validate everything and treat any
    inconsistency as a miss."""

    def __init__(self, directory: str):
        self.dir = directory
        self._made = False

    def _ensure_dir(self) -> bool:
        if self._made:
            return True
        try:
            os.makedirs(self.dir, exist_ok=True)
            self._made = True
            return True
        except OSError as e:
            debug.warning("compile cache dir %s unusable: %s", self.dir, e)
            return False

    def path(self, fp: str) -> str:
        return os.path.join(self.dir, f"{fp}.exe")

    def store(self, fp: str, blob: bytes, meta: Dict[str, Any]) -> bool:
        """Write one entry: the portable (``jax.export``) blob."""
        if not self._ensure_dir():
            return False
        path = self.path(fp)
        if os.path.exists(path):
            return False  # content-addressed: an existing entry is this one
        header = dict(meta)
        header["format"] = CACHE_FORMAT
        header["sha256"] = hashlib.sha256(blob).hexdigest()
        header["blob_len"] = len(blob)
        header["created"] = time.time()
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(json.dumps(header, sort_keys=True).encode())
                f.write(b"\n")
                f.write(blob)
            os.replace(tmp, path)
            return True
        except OSError as e:
            debug.warning("compile cache write of %s failed: %s", fp, e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False

    def _read(self, path: str) -> Tuple[Dict[str, Any], bytes]:
        """Parse + validate one entry file; raises ValueError on any
        corruption."""
        with open(path, "rb") as f:
            magic = f.read(len(_MAGIC))
            if magic != _MAGIC:
                raise ValueError(f"bad magic {magic!r}")
            header_line = f.readline(1 << 20)
            if not header_line.endswith(b"\n"):
                raise ValueError("truncated header")
            header = json.loads(header_line)
            if header.get("format") != CACHE_FORMAT:
                raise ValueError(f"format {header.get('format')} != "
                                 f"{CACHE_FORMAT}")
            blob = f.read()
        if len(blob) != header.get("blob_len"):
            raise ValueError(f"blob length {len(blob)} != "
                             f"{header.get('blob_len')} (truncated?)")
        if hashlib.sha256(blob).hexdigest() != header.get("sha256"):
            raise ValueError("blob checksum mismatch")
        return header, blob

    def load(self, fp: str) -> Optional[Tuple[Dict[str, Any], bytes]]:
        """Validated load; a corrupt entry is logged, removed
        (best-effort) and reported as a miss — a bad cache file must
        cost one recompile, never a crash."""
        path = self.path(fp)
        try:
            if not os.path.exists(path):
                return None
        except OSError:
            return None
        try:
            return self._read(path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            debug.warning(
                "compile cache entry %s is unreadable (%s); removing and "
                "recompiling", os.path.basename(path), e)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    # -- maintenance (tools cache) --------------------------------------
    def entries(self) -> List[Dict[str, Any]]:
        out = []
        try:
            names = sorted(os.listdir(self.dir))
        except OSError:
            return out
        for n in names:
            if not n.endswith(".exe"):
                continue
            p = os.path.join(self.dir, n)
            row = {"fp": n[:-4], "path": p}
            try:
                st = os.stat(p)
                row["size"] = st.st_size
                row["mtime"] = st.st_mtime
                with open(p, "rb") as f:
                    if f.read(len(_MAGIC)) == _MAGIC:
                        row["meta"] = json.loads(f.readline(1 << 20))
            except (OSError, ValueError, json.JSONDecodeError):
                row["corrupt"] = True
            out.append(row)
        return out

    def count(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.dir)
                       if n.endswith(".exe"))
        except OSError:
            return 0

    def verify(self) -> Tuple[int, List[str]]:
        """(ok_count, [corrupt fingerprints])."""
        ok, bad = 0, []
        for row in self.entries():
            try:
                self._read(row["path"])
                ok += 1
            except (OSError, ValueError, json.JSONDecodeError):
                bad.append(row["fp"])
        return ok, bad

    def purge(self, *, stale_only: bool = False) -> int:
        n = 0
        for row in self.entries():
            if stale_only and not row.get("corrupt"):
                meta = row.get("meta") or {}
                if meta.get("format") == CACHE_FORMAT \
                        and meta.get("versions") == _versions():
                    continue
            try:
                os.unlink(row["path"])
                n += 1
            except OSError:
                pass
        return n


_store_lock = threading.Lock()
_stores: Dict[str, DiskStore] = {}


def default_store() -> Optional[DiskStore]:
    """Process-wide store singleton for the resolved cache root (None
    when the disk layer is disabled).  XLA's own persistent compilation
    cache is the root itself, so the backend-compile half of a warm load
    comes off disk too: where ``JAX_COMPILATION_CACHE_DIR`` placed it jax
    already knows; otherwise it is pointed at the fixed checkout path
    here, once."""
    root = cache_root()
    if root is None:
        return None
    with _store_lock:
        store = _stores.get(root)
        if store is None:
            store = _stores[root] = DiskStore(os.path.join(root, "exe"))
            import jax
            from jax.experimental.compilation_cache import \
                compilation_cache as xla_cache

            if jax.config.jax_compilation_cache_dir is None:
                xla_cache.set_cache_dir(root)
            # jax's default floor (1.0 s of backend compile) skips
            # exactly the mid-size programs min_share_s selects for
            # sharing — align the floors unless the user configured one
            if jax.config.jax_persistent_cache_min_compile_time_secs == 1.0:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.1)
        return store


# ---------------------------------------------------------------------------
# the cache proper
# ---------------------------------------------------------------------------

class _CachedFunction:
    """The callable :meth:`ExecutableCache.jit` returns: per concrete
    arg signature it resolves one executable through the cache layers
    and dispatches to it.  A dispatch-level failure of an AOT executable
    (aval/device mismatch an exact cache key could not see) falls back
    to a plain ``jax.jit`` of the original function — counted, never
    fatal."""

    __slots__ = ("cache", "fn", "key", "donate", "place", "_memo",
                 "_plain", "_lock")

    def __init__(self, cache: "ExecutableCache", fn: Callable, key: Any,
                 donate: Tuple[int, ...], place: Any = None):
        self.cache = cache
        self.fn = fn
        self.key = key
        self.donate = tuple(donate or ())
        #: which of a context's several accelerators the function's
        #: arguments live on (None: the one there is).  An executable is
        #: compiled for the device of its arguments, so each accelerator
        #: has its own view of the live executables; the stored,
        #: device-portable form is shared
        self.place = place
        self._memo: Dict[Tuple, Any] = {}
        self._plain = None
        self._lock = threading.Lock()

    def _plain_jit(self):
        if self._plain is None:
            import jax

            self._plain = jax.jit(self.fn, donate_argnums=self.donate)
        return self._plain

    def __call__(self, *args):
        return self.call(args)[0]

    def call(self, args: Tuple) -> Tuple[Any, Any]:
        """One call, and the executable that ran it: ``(result,
        executable)``.  A caller whose every call has this signature (an
        entry of ``TpuDevice._jit_cache``) keeps the executable and calls
        it from then on, without the signature, the look-up and the
        fallback below (:meth:`retryable` is its half of the last)."""
        sig = argsig(args)
        exe = self._memo.get(sig)
        if exe is None:
            exe = self.cache._resolve(self, sig, args)
            with self._lock:
                self._memo.setdefault(sig, exe)
        else:
            # every dispatch that needed no compile is a cache hit: the
            # zero-recompile invariants ("second run compiles nothing")
            # are pinned on hits growing while misses stay flat
            self.cache.stats["hits_mem"] += 1
        try:
            return exe(*args), exe
        except Exception as e:
            if not self.retryable(exe, e):
                raise
            # AOT dispatch mismatch (sharding/weak-type nuance the light
            # signature missed): fall back to plain jit — correctness
            # first, and count it so a systematic mismatch is visible
            self.cache.stats["aot_fallbacks"] += 1
            debug.verbose(1, "compile_cache",
                          "AOT dispatch of %r fell back to jax.jit "
                          "(%s: %s)", self.key, type(e).__name__, e)
            plain = self._plain_jit()
            with self._lock:
                self._memo[sig] = plain
            return plain(*args), plain

    def retryable(self, exe, e: Exception) -> bool:
        """May a call of ``exe`` that raised ``e`` be made again through
        the plain jit?  Only argument/aval/structure mismatches the
        light cache signature could not see may — a genuine compute-side
        failure must surface as itself, not as a second run's error (and
        the plain jit has nothing behind it).  TypeError/ValueError are
        raised at argument validation, BEFORE any buffer is donated, so
        retrying them is safe even for donating programs; a runtime
        status error from a donating program must never re-execute (the
        failed attempt may already have consumed its inputs)."""
        if exe is self._plain:
            return False
        if isinstance(e, (TypeError, ValueError)):
            return True
        if self.donate:
            return False
        # XLA dispatch rejections surface as status errors before the
        # program runs; anything else is a real execution failure
        return "INVALID_ARGUMENT" in str(e)[:300]


class ExecutableCache:
    """One cache instance per :class:`~parsec_tpu.core.context.Context`
    (plus a process-default instance for contextless users like
    ``GraphExecutor``).  Layers: per-instance LRU of live executables →
    broadcast-preloaded blobs → shared disk store → full trace+compile
    (then serialize, store, announce)."""

    def __init__(self, *, rank: int = 0, nranks: int = 1, ce=None,
                 store: Optional[DiskStore] = "default",
                 min_disk_s: Optional[float] = None,
                 bcast: Optional[bool] = None):
        self.rank = rank
        self.nranks = nranks
        self.stats: collections.Counter = collections.Counter()
        if min_disk_s is None:
            min_disk_s = float(mca_param.register(
                "runtime", "compile_cache_min_share_s", 0.05,
                help="minimum trace+serialize seconds before an "
                     "executable is shared (disk store + broadcast); "
                     "tiny kernels stay process-local"))
        self.min_disk_s = min_disk_s
        self.store = default_store() if store == "default" else store
        self._lru: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()
        self._preloaded: Dict[str, bytes] = {}
        self._lock = threading.Lock()
        if bcast is None:
            bcast = bool(mca_param.register(
                "runtime", "compile_bcast", True,
                help="broadcast serialized executables to peer ranks on "
                     "first compile (compile-once-ship-serialized)"))
        self.bcast_enabled = bool(bcast) and ce is not None and nranks > 1
        self.ce = ce if self.bcast_enabled else None
        self._pulls: Dict[str, "_BlobPull"] = {}
        #: program keys already named in the one-time LOCAL_ONLY log —
        #: an unexportable program (Pallas custom calls, host callbacks)
        #: recompiles per shape, and each occurrence counts in
        #: stats["local_only"], but the operator-facing log names each
        #: program once, not once per shape
        self._local_only_warned: set = set()
        if self.ce is not None:
            self.ce.register_ctl(_CTL_OP, self._on_ctl)

    # -- externally read properties -------------------------------------
    @property
    def persistent(self) -> bool:
        return self.store is not None

    @property
    def warm(self) -> bool:
        """True when the disk store holds entries THIS process could
        load (recorded jax/jaxlib versions match, and the backend where
        recorded) — the signal the device layer uses to lift the
        multi-rank wave-batching auto-disable (a warm store amortizes
        the per-rank compile explosion the workaround dodged).
        Deliberately coarse — workload identity is unknown at device
        attach — but a stale-version or other-backend store reads COLD:
        none of its entries can ever hit, so lifting on them would
        reintroduce the explosion."""
        if self.store is None:
            return False
        w = getattr(self, "_warm", None)
        if w is None:
            v, p = _versions(), _platform()
            w = self._warm = any(
                not row.get("corrupt")
                and (row.get("meta") or {}).get("versions") == v
                and (row.get("meta") or {}).get("backend") in (None, p)
                for row in self.store.entries())
        return w

    @property
    def hits(self) -> int:
        return (self.stats["hits_mem"] + self.stats["hits_disk"]
                + self.stats["hits_bcast"])

    def snapshot(self) -> Dict[str, int]:
        s = dict(self.stats)
        s["hits"] = self.hits
        s["bytes"] = self.stats["bytes_written"] + self.stats["bytes_read"]
        return s

    # -- public API ------------------------------------------------------
    def jit(self, fn: Callable, *, key: Any,
            donate_argnums: Tuple[int, ...] = (),
            place: Any = None) -> _CachedFunction:
        """Cache-aware replacement for ``jax.jit(fn, donate_argnums=…)``.
        ``key`` identifies the *program* (body code fingerprint plus any
        structural parts — wave arity/count, baked static values); the
        concrete input shapes/dtypes complete the cache key per call."""
        return _CachedFunction(self, fn, key, donate_argnums, place)

    def clear_memory(self) -> None:
        """Drop live executables and preloaded blobs (the disk store
        stays) — the warm-disk measurement hook."""
        with self._lock:
            self._lru.clear()
            self._preloaded.clear()

    def preload(self, fp: str, blob: bytes, *, persist: bool = True,
                meta: Optional[Dict[str, Any]] = None) -> None:
        """Install a serialized executable received from a peer: it
        satisfies the next local request for ``fp`` without a trace.
        When a disk store is available the blob lands there; the
        in-memory preload map is the storeless fallback."""
        if persist and self.store is not None:
            m = dict(meta or ())
            m.setdefault("versions", _versions())
            m["origin"] = "bcast"
            if self.store.store(fp, blob, m):
                self.stats["bytes_written"] += len(blob)
            # the entry exists (just written, or content-addressed and
            # already present): resolvable from disk, keep no duplicate
            # in memory.  No re-read — a corrupt load later falls back
            # to a recompile anyway.
            if os.path.exists(self.store.path(fp)):
                return
        with self._lock:
            self._preloaded.setdefault(fp, blob)

    # -- resolution ------------------------------------------------------
    def _lru_get(self, fp: str):
        with self._lock:
            exe = self._lru.get(fp)
            if exe is not None:
                self._lru.move_to_end(fp)
            return exe

    def _lru_put(self, fp: str, exe) -> None:
        with self._lock:
            self._lru[fp] = exe
            self._lru.move_to_end(fp)
            while len(self._lru) > MEM_ENTRIES:
                self._lru.popitem(last=False)

    def _resolve(self, cf: _CachedFunction, sig: Tuple, args: Tuple):
        fp = fingerprint(cf.key, sig, donate=cf.donate)
        live = fp if cf.place is None else f"{fp}@{cf.place}"
        exe = self._lru_get(live)
        if exe is not None:
            self.stats["hits_mem"] += 1
            return exe
        from .profiling import pins

        t0 = time.perf_counter()
        kind = "miss"
        # job trace context (profiling.jobtrace): a compile triggered
        # from inside a task body inherits the running job's trace id
        # off the worker thread — the merged timeline shows WHOSE job a
        # cold compile stalled
        with pins.span("cc:compile", rank=self.rank, fp=fp,
                       key=_short(cf.key), trace=jobtrace.current()) as sp:
            try:
                exe, kind = self._resolve_slow(cf, fp, args)
            finally:
                dt = time.perf_counter() - t0
                self.stats["compile_ns_total"] += int(dt * 1e9)
                sp.note(kind=kind, seconds=dt)
        self._lru_put(live, exe)
        return exe

    def _resolve_slow(self, cf: _CachedFunction, fp: str, args: Tuple):
        # 1) a blob a peer shipped / disk already holds
        blob = None
        with self._lock:
            blob = self._preloaded.pop(fp, None)
        src = "bcast"
        if blob is None and self.store is not None:
            loaded = self.store.load(fp)
            if loaded is not None:
                _header, blob = loaded
                src = "disk"
                self.stats["bytes_read"] += len(blob)
        if blob is not None:
            exe = self._compile_blob(blob, cf, args)
            if exe is not None:
                self.stats["hits_" + src] += 1
                return exe, "hit_" + src
            self.stats["blob_errors"] += 1
        # 2) full trace + compile — ONE trace for both the sharing
        # decision and the executable.  Export first (a trace +
        # StableHLO serialization); if that took real time the program
        # is worth sharing, and it compiles THROUGH its own serialized
        # form: deserialize → AOT-compile the exported call — so the
        # XLA persistent-cache entry this cold compile writes is keyed
        # on the SAME module every warm process (and every broadcast
        # peer) compiles, and their backend compile becomes a disk
        # read.  Tiny programs (and export failures: Pallas custom
        # calls, host callbacks) take the plain jit lowering instead —
        # re-tracing something that lowers in under min_share_s is
        # noise.
        self.stats["misses"] += 1
        if isinstance(cf.key, tuple) and cf.key and cf.key[0] == "fused":
            # fused supertask programs (dsl.fusion): counted so the
            # zero-recompile-on-warm acceptance can pin them apart from
            # ordinary per-body programs
            self.stats["fused_compiles"] += 1
        import jax

        jitted = jax.jit(cf.fn, donate_argnums=cf.donate)
        share = self.store is not None or self.bcast_enabled
        if share:
            t0 = time.perf_counter()
            blob = None
            try:
                import jax.export as jex

                blob = bytes(jex.export(jitted)(*args).serialize())
            except Exception as e:
                # the graceful process-local path: the program still gets
                # the per-process LRU (and, where jit's own lowering can
                # be reused, the XLA persistent cache) — but NOT the disk
                # store or the compile broadcast.  Count it
                # (PARSEC::COMPILE::LOCAL_ONLY / parsec_compile_local_
                # only_total) so a mesh silently paying per-rank Pallas
                # compiles is visible, and name the program once.
                self.stats["serialize_errors"] += 1
                self.stats["local_only"] += 1
                kshort = _short(cf.key)
                if kshort not in self._local_only_warned:
                    self._local_only_warned.add(kshort)
                    debug.warning(
                        "compile cache: program %r is not exportable "
                        "(%s: %s); it stays process-local — no disk "
                        "store, no compile broadcast (counted in "
                        "PARSEC::COMPILE::LOCAL_ONLY)", kshort,
                        type(e).__name__, e)
                else:
                    debug.verbose(1, "compile_cache",
                                  "program %r not serializable (%s: %s); "
                                  "staying process-local", kshort,
                                  type(e).__name__, e)
            # fused supertask programs ALWAYS share: they are the exact
            # compile-once artifacts granularity coarsening exists to
            # amortize (an N-body region re-traces N bodies per process
            # otherwise), so the tiny-program threshold does not apply
            fused = isinstance(cf.key, tuple) and cf.key \
                and cf.key[0] == "fused"
            if blob is not None \
                    and (fused
                         or time.perf_counter() - t0 >= self.min_disk_s):
                exe = self._share_blob(cf, fp, args, blob, t0)
                if exe is not None:
                    return exe, "miss"
        return jitted.lower(*args).compile(), "miss"

    def _compile_blob(self, blob: bytes, cf: _CachedFunction,
                      args: Tuple):
        """Deserialize + AOT-compile a stored executable (portable
        StableHLO form).  Failures are soft: None sends the caller to a
        fresh compile."""
        try:
            import jax
            import jax.export as jex

            _ensure_custom_call_targets()
            exp = jex.deserialize(bytearray(blob))

            def call(*a):
                return exp.call(*a)
            # under the function's own name, as the plain lowering has
            # it: a device trace tells programs apart by their module
            # names, whichever way they were compiled
            call.__name__ = getattr(cf.fn, "__name__", "call")
            exe = jax.jit(call, donate_argnums=cf.donate) \
                .lower(*args).compile()
            return exe
        except Exception as e:
            debug.warning("compile cache blob for %r failed to load (%s: "
                          "%s); recompiling", _short(cf.key),
                          type(e).__name__, e)
            return None

    def _share_blob(self, cf: _CachedFunction, fp: str, args: Tuple,
                    blob: bytes, t0: float):
        """Compile an already-serialized program through its own
        serialized form (one shared XLA-cache key for cold, warm and
        peer ranks), store + announce.  Returns the executable, or None
        when the deserialized form is unusable (caller compiles the
        direct lowering instead)."""
        exe = self._compile_blob(blob, cf, args)
        if exe is None:
            return None  # deserialized form unusable: don't store it
        meta = {"key": _short(cf.key), "versions": _versions(),
                "backend": _platform(),
                "compile_s": round(time.perf_counter() - t0, 3),
                "rank": self.rank}
        if self.store is not None:
            if self.store.store(fp, blob, meta):
                self.stats["bytes_written"] += len(blob)
            self._warm = True
        if self.bcast_enabled:
            self._announce(fp, blob, meta)
        return exe

    # -- cross-rank compile channel --------------------------------------
    def _peers(self) -> List[int]:
        return [r for r in range(self.nranks) if r != self.rank]

    def _announce(self, fp: str, blob: bytes, meta: Dict[str, Any]) -> None:
        ce = self.ce
        if ce is None:
            return
        try:
            # the advert names the job whose first miss triggered the
            # compile (0 outside any job): wire-level trace context for
            # the compile-bcast channel, mirrored into the receivers'
            # install bookkeeping
            trace = jobtrace.current()
            if len(blob) <= ce.eager_limit:
                msg = {"op": _CTL_OP, "fp": fp, "meta": meta,
                       "blob": blob, "trace": trace}
                for r in self._peers():
                    from .comm.engine import TAG_CTL

                    ce.send_am(TAG_CTL, r, msg)
            else:
                # large blob: advertise, peers pull rendezvous chunks
                # from the registered buffer (PR 4 machinery); one use
                # per peer, self-reclaiming
                handle = ("pzexe", fp)
                ce.mem_register(handle, np.frombuffer(blob, np.uint8),
                                uses=len(self._peers()))
                msg = {"op": _CTL_OP, "fp": fp, "meta": meta,
                       "size": len(blob), "trace": trace}
                for r in self._peers():
                    from .comm.engine import TAG_CTL

                    ce.send_am(TAG_CTL, r, msg)
            self.stats["bcast_sent"] += len(self._peers())
        except Exception as e:
            debug.warning("compile broadcast of %s failed: %s", fp, e)

    def _on_ctl(self, src_rank: int, msg: Dict[str, Any]) -> None:
        fp = msg.get("fp")
        if not fp:
            return
        blob = msg.get("blob")
        if blob is not None:
            self.stats["bcast_recv"] += 1
            self.preload(fp, bytes(blob), meta=msg.get("meta"))
            return
        size = int(msg.get("size", 0))
        if size <= 0:
            return
        redundant = fp in self._pulls
        if not redundant:
            try:
                with self._lock:
                    redundant = (fp in self._preloaded
                                 or fp in self._lru)
                redundant = redundant or (
                    self.store is not None
                    and os.path.exists(self.store.path(fp)))
            except OSError:
                redundant = False
        if redundant:
            # already pulling this program (simultaneous first misses on
            # several ranks) or already holding it: we will never issue
            # chunk requests toward THIS sender, so consume our use of
            # its uses=N-1 registration with one tiny fin read — or the
            # serialized blob stays pinned in its mem table forever
            try:
                self.ce.get_part(src_rank, ("pzexe", fp), 0, 1,
                                 lambda *_: None, fin=True)
            except Exception:
                pass
            return
        self._pulls[fp] = _BlobPull(self, src_rank, fp, size,
                                    msg.get("meta"))

    def _pull_done(self, fp: str, blob: Optional[bytes],
                   meta: Optional[Dict[str, Any]]) -> None:
        self._pulls.pop(fp, None)
        if blob is None:
            self.stats["bcast_pull_errors"] += 1
            return
        self.stats["bcast_recv"] += 1
        self.preload(fp, blob, meta=meta)


class _BlobPull:
    """Chunked pull of an advertised compile blob: up to
    ``pipeline_depth`` ``get_part`` requests in flight, ``rdv_chunk``
    bytes each, landing by byte offset — the same two-regime shape as
    the PR 4 payload rendezvous, minus the arena (blobs are plain host
    bytes).  The pump is iterative with the same ``_pumping`` flag
    discipline as ``remote_dep._RdvPull``: a synchronous engine
    (inproc) completing a chunk inside ``get_part`` must not recurse
    one stack frame per chunk, and cross-thread TCP completions must
    not race the window bookkeeping."""

    def __init__(self, cache: ExecutableCache, src_rank: int, fp: str,
                 size: int, meta):
        self.cache = cache
        self.src = src_rank
        self.fp = fp
        self.size = size
        self.meta = meta
        self.buf = bytearray(size)
        self.received = 0
        self.next_off = 0
        self.inflight = 0
        self.failed = False
        self.finished = False
        self.fin_issued = False
        self._lock = threading.Lock()
        self._pumping = False
        ce = cache.ce
        self.chunk = max(1, int(getattr(ce, "rdv_chunk", 256 << 10)))
        self.depth = max(1, int(getattr(ce, "pipeline_depth", 4)))
        self._pump()

    def _pump(self) -> None:
        # Re-entrant calls no-op; the flag holder loops until the window
        # is genuinely full, finished, or failed (post-clear re-check
        # catches a cross-thread completion that no-opped mid-fill).
        while True:
            with self._lock:
                if self._pumping:
                    return
                self._pumping = True
            try:
                self._fill_window()
            finally:
                with self._lock:
                    self._pumping = False
                    again = (not self.failed and not self.finished
                             and self.next_off < self.size
                             and self.inflight < self.depth)
            if not again:
                return

    def _fill_window(self) -> None:
        ce = self.cache.ce
        while True:
            with self._lock:
                if (self.failed or self.finished
                        or self.next_off >= self.size
                        or self.inflight >= self.depth):
                    return
                off = self.next_off
                ln = min(self.chunk, self.size - off)
                self.next_off = off + ln
                fin = self.next_off >= self.size
                if fin:
                    self.fin_issued = True
                self.inflight += 1
            try:
                ce.get_part(self.src, ("pzexe", self.fp), off, ln,
                            lambda part, off=off, ln=ln:
                                self._on_chunk(part, off, ln),
                            fin=fin)
            except Exception as e:
                debug.warning("compile blob pull %s chunk @%d failed: %s",
                              self.fp, off, e)
                if fin:
                    # the fin request never left this rank: un-mark it
                    # so _fail's compensating fin still releases our use
                    # of the sender's registration
                    with self._lock:
                        self.fin_issued = False
                self._on_chunk(None, off, ln)

    def _on_chunk(self, part, off: int, ln: int) -> None:
        finish = None
        with self._lock:
            self.inflight -= 1
            if self.failed or self.finished:
                return
            if part is None:
                self.failed = True
                finish = "fail"
            else:
                b = np.asarray(part).view(np.uint8).reshape(-1)
                self.buf[off:off + ln] = b[:ln].tobytes()
                self.received += ln
                if self.received >= self.size:
                    self.finished = True
                    finish = "done"
        if finish == "fail":
            self._fail()
            return
        if finish == "done":
            self.cache._pull_done(self.fp, bytes(self.buf), self.meta)
            return
        self._pump()

    def _fail(self) -> None:
        # release this consumer's use of the sender's registration: the
        # blob was registered uses=nranks-1 and self-reclaims on fin
        # requests — a pull that dies before issuing its fin would pin
        # the sender's buffer forever.  Only when the real fin was NOT
        # yet issued, or the cleanup would consume a sibling peer's use.
        # Best-effort: a vanished registration raises and there is
        # nothing left to free.
        if not self.fin_issued:
            try:
                self.cache.ce.get_part(self.src, ("pzexe", self.fp), 0,
                                       1, lambda *_: None, fin=True)
            except Exception:
                pass
        self.cache._pull_done(self.fp, None, self.meta)


def _short(key: Any) -> str:
    s = _scrub(repr(key))
    return s if len(s) <= 120 else s[:117] + "..."


# ---------------------------------------------------------------------------
# process-default instance (contextless users: GraphExecutor, tools)
# ---------------------------------------------------------------------------

_default_cache: Optional[ExecutableCache] = None
_default_lock = threading.Lock()


def default_cache() -> ExecutableCache:
    global _default_cache
    with _default_lock:
        if _default_cache is None:
            _default_cache = ExecutableCache()
        return _default_cache


def for_context(context) -> ExecutableCache:
    """Build the per-context cache (rank-aware, comm-attached when a
    multi-rank engine is present)."""
    ce = getattr(context, "comm", None)
    nranks = getattr(context, "nranks", 1)
    return ExecutableCache(rank=getattr(context, "rank", 0),
                           nranks=nranks,
                           ce=ce if nranks > 1 else None)
