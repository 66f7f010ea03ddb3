"""PTG — Parameterized Task Graph front-end.

The reference expresses PTG in ``.jdf`` files compiled ahead-of-time to C by
``parsec_ptgpp`` (``/root/reference/parsec/interfaces/ptg/ptg-compiler/``:
flex lexer ``parsec.l``, bison grammar ``parsec.y``, codegen ``jdf2c.c``).
Here the same algebraic model — task classes with integer parameter ranges,
affinity, guarded dataflow dependencies with task-reference ranges, control
flows, priorities, multiple body incarnations — is built **at runtime**: the
"compiler" constructs the task-class vtables (startup enumeration,
``data_lookup``, ``release_deps``/``iterate_successors``, data resolution
through per-class usage-counted repos) directly, with dependency
expressions written as Python expressions in a compact JDF-like syntax:

    ptg = PTG("cholesky")
    potrf = ptg.task_class("potrf", k="0 .. NT-1")
    potrf.affinity("A(k, k)")
    potrf.flow("T", INOUT,
               "<- (k == 0) ? A(k, k) : T syrk(k, k-1)",
               "-> T trsm(k+1 .. NT-1, k)",
               "-> A(k, k)")
    potrf.body(cpu=potrf_cpu, tpu=potrf_tpu)
    tp = ptg.taskpool(NT=8, A=A)     # problem-size independent, like JDF

Dependency syntax (reference JDF dependency grammar, ``parsec.y``):
  ``<-`` input, ``->`` output;
  optional guard ``(cond) ? TARGET`` or ternary ``(cond) ? T1 : T2``;
  TARGET is ``FLOW class(args)`` (task reference), ``collection(args)``
  (memory reference), ``NEW`` (fresh tile), or ``NONE``;
  an arg may be an inclusive range ``lo .. hi`` (as in JDF) — ranges in
  output deps broadcast to many successors;
  a trailing ``[key=value ...]`` property block is accepted (JDF parity)
  and stashed on the dep;
  expressions are Python, evaluated over task params + taskpool constants;
  an argument may CALL a constant's method — ``C1 tsmqr(k,
  TREE.getikill(k, nextp), n)``: arguments split at depth 0 only — and a
  parameter may run over an irregular set through a definition (``i = 0
  .. TREE.getnbgeqrf(k)-1`` then ``m = TREE.getm(k, i)``), as the
  reference's ``inline_c`` calls into a ``dplasma_qrtree_t`` do
  (``ops/qr.py``).  An object called this way says what its answers are
  a function of (``plan_fingerprint()``), or the pool gets no attach plan;
  a global may have a default worked out from the others
  (:meth:`PTG.default`: JDF's ``[hidden = on default = ...]``).

Execution model (mirrors SURVEY.md §3.2/§3.3):
* startup: enumerate the parameter space, schedule every task whose active
  input deps are all memory references (``jdf2c.c:3036``);
* ``data_lookup``/prepare_input: inputs resolve to collection tiles or to
  the producing task's deposited flow data (per-class repo, usage-counted —
  ``datarepo.c`` semantics);
* completion: deposit outputs in the repo, enumerate guard-true output task
  refs (expanding ranges), decrement each successor's counter; successors
  reaching their goal are constructed and scheduled (counter-mode tracking,
  ``parsec_internal.h:371-394``).

Symmetry requirement (as in JDF): an input dep ``<- T prod(...)`` must be
mirrored by the producer's output dep ``-> T cons(...)`` — dependency
counting and repo deposits are driven from the producer side.
"""

from __future__ import annotations

import itertools
import re
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..core.deps import DenseDepTracker, DepTracker
from ..core.lifecycle import AccessMode, HookReturn, DEV_CPU, DEV_TPU
from ..core.task import Chore, Flow, Task, TaskClass
from ..core.taskpool import Taskpool
from ..data.data import Data, data_create
from ..data.datarepo import DataRepo
from ..data.reshape import ReshapeSpec, get_copy_reshape, materialize

IN = AccessMode.IN
OUT = AccessMode.OUT
INOUT = AccessMode.INOUT
CTL = AccessMode.CTL


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------

_SAFE_BUILTINS = {
    "min": min, "max": max, "abs": abs, "int": int, "range": range,
    "len": len, "divmod": divmod, "True": True, "False": False,
}
#: shared eval globals — expression evaluation is the capture/startup hot
#: path (tens of thousands of calls per attach); a per-call dict alloc
#: is measurable there
_EVAL_GLOBALS = {"__builtins__": _SAFE_BUILTINS}

#: cumulative existence/validity predicate WORK units: one per direct
#: ``instance_exists`` evaluation (memo misses + unmemoized calls), one
#: per O(1) range-membership check inside ``valid``, and one per
#: MATERIALIZED candidate value when a parameter's range has to be
#: expanded — so an implementation that enumerates a producer's
#: parameter span scales this counter with the span.  Monotone,
#: process-wide, incremented under the GIL; read via
#: :func:`exists_eval_count` and difference around a run — the
#: deterministic replacement for the wall-clock scaling assertion of
#: tests/dsl/test_exists_stress.py.
_exists_evals = 0


def exists_eval_count() -> int:
    """Current value of the existence-predicate work counter."""
    return _exists_evals


def reset_exists_eval_count() -> int:
    """Zero the process-global existence-predicate work counter and
    return the value it had.  Tests that pin scaling laws on the counter
    (tests/dsl/test_exists_stress.py) reset it per measurement so work
    from earlier taskpools in the same process cannot bleed in."""
    global _exists_evals
    old = _exists_evals
    _exists_evals = 0
    return old


def _c_to_py(src: str) -> str:
    """Accept the C boolean operators of reference JDF expressions
    (``parsec.y`` expr grammar): ``&&`` → ``and``, ``||`` → ``or``,
    ``!`` → ``not`` (but not ``!=``). Everything else is Python.
    String literals pass through untouched."""
    out: List[str] = []
    i, n = 0, len(src)
    while i < n:
        ch = src[i]
        if ch in "\"'":
            j = i + 1
            while j < n and src[j] != ch:
                j += 2 if src[j] == "\\" else 1
            out.append(src[i : min(j + 1, n)])
            i = j + 1
        elif src.startswith("&&", i):
            out.append(" and ")
            i += 2
        elif src.startswith("||", i):
            out.append(" or ")
            i += 2
        elif ch == "!" and not src.startswith("!=", i):
            out.append(" not ")
            i += 1
        else:
            out.append(ch)
            i += 1
    return "".join(out)


class _Expr:
    """A compiled Python expression over task params + constants."""

    __slots__ = ("src", "code")

    def __init__(self, src: str):
        self.src = src.strip()
        self.code = compile(_c_to_py(self.src), f"<ptg:{self.src}>", "eval")

    def __call__(self, env: Dict[str, Any]) -> Any:
        return eval(self.code, _EVAL_GLOBALS, env)

    def __repr__(self) -> str:
        return f"_Expr({self.src!r})"


#: the constants of a pool whose value no body can change under a guard
_SCALARS = (int, float, str, type(None), np.integer, np.floating)


def _reads_only(expr: _Expr, names) -> bool:
    """True when ``expr`` reads nothing but ``names``: no attribute of
    an object, no call of a pool's function, no nested scope (a
    comprehension, a lambda) that could read anything else."""
    code = expr.code
    return all(n in names for n in code.co_names) \
        and not any(hasattr(c, "co_code") for c in code.co_consts)


def _split_top(s: str, sep: str) -> List[str]:
    """Split on ``sep`` at paren/bracket depth 0."""
    parts: List[str] = []
    depth, cur, i = 0, [], 0
    while i < len(s):
        ch = s[i]
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
        if depth == 0 and s.startswith(sep, i):
            parts.append("".join(cur))
            cur = []
            i += len(sep)
            continue
        cur.append(ch)
        i += 1
    parts.append("".join(cur))
    return parts


class _ArgExpr:
    """Scalar expression or inclusive range ``lo .. hi`` with optional
    stride ``lo .. hi .. step`` (reference jdf_expr ranges — e.g.
    strange.jdf's ``step = 0 .. N .. (N+1)``, a stride larger than the
    span yielding a single value; udf.jdf strides through inline calls
    whose side effect counts enumerations)."""

    __slots__ = ("lo", "hi", "step")

    def __init__(self, src: str):
        parts = _split_top(src, "..")
        if len(parts) == 1:
            self.lo, self.hi, self.step = _Expr(parts[0]), None, None
        elif len(parts) == 2:
            self.lo, self.hi, self.step = _Expr(parts[0]), _Expr(parts[1]), None
        elif len(parts) == 3:
            self.lo, self.hi = _Expr(parts[0]), _Expr(parts[1])
            self.step = _Expr(parts[2])
        else:
            raise ValueError(f"bad range expression {src!r}")

    def values(self, env: Dict[str, Any]) -> Iterable[int]:
        if self.hi is None:
            v = self.lo(env)
            return v if isinstance(v, range) else (v,)
        step = 1 if self.step is None else int(self.step(env))
        if step <= 0:
            raise ValueError(
                f"range {self.lo.src}..{self.hi.src} stride must be positive")
        return range(int(self.lo(env)), int(self.hi(env)) + 1, step)

    def scalar(self, env: Dict[str, Any]) -> Any:
        if self.hi is not None:
            raise ValueError(f"range {self.lo.src}..{self.hi.src} used as scalar")
        return self.lo(env)


# ---------------------------------------------------------------------------
# dependency targets & parsing
# ---------------------------------------------------------------------------

class _TaskRef:
    __slots__ = ("flow_name", "class_name", "args")

    def __init__(self, flow_name: str, class_name: str, args: List[_ArgExpr]):
        self.flow_name, self.class_name, self.args = flow_name, class_name, args


class _DataRef:
    __slots__ = ("collection_name", "args")

    def __init__(self, collection_name: str, args: List[_ArgExpr]):
        self.collection_name, self.args = collection_name, args

    def key(self, env: Dict[str, Any]) -> Tuple:
        return tuple(a.scalar(env) for a in self.args)


class _NewRef:
    __slots__ = ()


class _NoneRef:
    __slots__ = ()


_TARGET_RE = re.compile(
    r"^\s*(?:(?P<flow>[A-Za-z_]\w*)\s+)?(?P<name>[A-Za-z_]\w*)\s*\((?P<args>.*)\)\s*$",
    re.S,
)


def _parse_target(s: str):
    s = s.strip()
    if s in ("NEW", "new"):
        return _NewRef()
    if s in ("NONE", "NULL", "none"):
        return _NoneRef()
    m = _TARGET_RE.match(s)
    if not m:
        raise ValueError(f"cannot parse dependency target {s!r}")
    argsrc = m.group("args").strip()
    args = [_ArgExpr(a) for a in (_split_top(argsrc, ",") if argsrc else [])]
    if m.group("flow"):
        return _TaskRef(m.group("flow"), m.group("name"), args)
    return _DataRef(m.group("name"), args)


class _Dep:
    """One guarded dependency (reference ``jdf_dep_t``)."""

    __slots__ = ("is_input", "guard", "then", "otherwise", "props", "src")

    def __init__(self, is_input, guard, then, otherwise=None, props=None,
                 src=""):
        self.is_input = is_input
        self.guard = guard
        self.then = then
        self.otherwise = otherwise
        self.props = props or {}
        #: original dependency source text — diagnostics (analysis
        #: findings, runtime errors) point at the exact offending dep
        self.src = src

    def target(self, env: Dict[str, Any]):
        if self.guard is None:
            return self.then
        return self.then if self.guard(env) else self.otherwise


def _parse_dep(spec: str) -> _Dep:
    spec = spec.strip()
    orig = spec
    props: Dict[str, str] = {}
    pm = re.search(r"\[(.*?)\]\s*$", spec)
    if pm:
        # JDF property blocks allow spaces around '=' and parenthesized
        # values with internal spaces: normalize, then split at depth 0
        body = re.sub(r"\s*=\s*", "=", pm.group(1).strip())
        depth, cur = 0, []
        tokens: List[str] = []
        for ch in body:
            if ch in "([":
                depth += 1
            elif ch in ")]":
                depth -= 1
            if ch.isspace() and depth == 0:
                if cur:
                    tokens.append("".join(cur))
                    cur = []
            else:
                cur.append(ch)
        if cur:
            tokens.append("".join(cur))
        for kv in tokens:
            if "=" in kv:
                k, v = kv.split("=", 1)
                props[k] = v.strip('"').strip("'")
        spec = spec[: pm.start()].strip()
    if spec.startswith("<-"):
        is_input, rest = True, spec[2:].strip()
    elif spec.startswith("->"):
        is_input, rest = False, spec[2:].strip()
    else:
        raise ValueError(f"dependency must start with '<-' or '->': {spec!r}")
    qparts = _split_top(rest, "?")
    if len(qparts) == 2:
        cond = qparts[0].strip()
        if not (cond.startswith("(") and cond.endswith(")")):
            raise ValueError(f"guard must be parenthesized: {spec!r}")
        guard = _Expr(cond[1:-1])
        branches = _split_top(qparts[1], ":")
        then = _parse_target(branches[0])
        otherwise = _parse_target(branches[1]) if len(branches) == 2 else None
        return _Dep(is_input, guard, then, otherwise, props, src=orig)
    if len(qparts) > 2:
        raise ValueError(f"bad ternary in {spec!r}")
    return _Dep(is_input, None, _parse_target(rest), None, props, src=orig)


def _expand_args(args: Sequence[_ArgExpr], env: Dict[str, Any]) -> Iterable[Tuple]:
    pools = [tuple(a.values(env)) for a in args]
    return itertools.product(*pools)


def _instances_of(pc: "PTGTaskClass", args: Sequence[_ArgExpr],
                  reads: List[_Expr]) -> Tuple:
    """A dependency's reference to tasks of ``pc``, for whoever asks at
    ``prepare_input`` which of them exist: ``(the class, the arguments,
    the arguments as ONE expression where none is a range)``; every
    expression it evaluates is added to ``reads``."""
    reads.extend(e for a in args for e in (a.lo, a.hi, a.step)
                 if e is not None)
    one = None
    if all(a.hi is None for a in args):
        one = _Expr("(%s)" % "".join(f"{a.lo.src}, " for a in args))
    return pc, args, one


# ---------------------------------------------------------------------------
# declarations (problem-size independent, like a .jdf file)
# ---------------------------------------------------------------------------

class _PTGFlow:
    __slots__ = ("name", "mode", "deps_in", "deps_out", "index")

    def __init__(self, name: str, mode: AccessMode, index: int):
        self.name, self.mode, self.index = name, mode, index
        self.deps_in: List[_Dep] = []
        self.deps_out: List[_Dep] = []


class PTGTaskClass:
    """Declarative task class (reference ``jdf_function_entry_t``).

    Locals come in two kinds, in declaration order (reference ``jdf_def_t``
    list, ``parsec.y`` "definitions"): **parameters** (named in the task
    heading, each with an integer range — they form the task key) and
    **definitions** (derived scalars like ``m = t % NT``, usable in later
    ranges, dependencies, affinity, priority, and the body — the reference
    stencil JDF interleaves them between parameter ranges)."""

    def __init__(self, ptg: "PTG", name: str, params: Dict[str, str]):
        self.ptg = ptg
        self.name = name
        # (name, expr, is_param) in declaration order
        self.decls: List[Tuple[str, _ArgExpr, bool]] = [
            (k, _ArgExpr(v), True) for k, v in params.items()
        ]
        self.flows: List[_PTGFlow] = []
        self._affinity: Optional[_DataRef] = None
        self._priority: Optional[_Expr] = None
        self.bodies: Dict[str, Callable] = {}
        self.properties: Dict[str, Any] = {}
        #: per-device incarnation applicability predicates (reference
        #: BODY [evaluate = fn]: HOOK_RETURN_NEXT skips the incarnation)
        self.chore_evaluate: Dict[str, Callable] = {}
        #: flow name -> (stage_in, stage_out) custom device staging
        self.stage_hooks: Dict[str, Tuple[Optional[Callable],
                                          Optional[Callable]]] = {}
        #: taskpool-constant names passed to bodies by name (JDF globals
        #: are visible inside reference BODY blocks as C globals)
        self.body_globals: List[str] = []

    @property
    def param_names(self) -> List[str]:
        return [n for n, _, p in self.decls if p]

    @property
    def def_names(self) -> List[str]:
        return [n for n, _, p in self.decls if not p]

    def define(self, name: str, expr: str) -> "PTGTaskClass":
        """Append a derived-local definition (JDF ``name = expr`` line)."""
        self.decls.append((name, _ArgExpr(expr), False))
        return self

    def use_globals(self, *names: str) -> "PTGTaskClass":
        """Declare taskpool constants the bodies receive as keyword args."""
        self.body_globals.extend(n for n in names if n not in self.body_globals)
        return self

    def param(self, name: str, range_src: str) -> "PTGTaskClass":
        """Append a parameter range in declaration order (JDF ``k = lo..hi``
        for a name listed in the task heading)."""
        self.decls.append((name, _ArgExpr(range_src), True))
        return self

    def affinity(self, spec: str) -> "PTGTaskClass":
        t = _parse_target(spec)
        if not isinstance(t, _DataRef):
            raise ValueError("affinity must be a collection reference")
        self._affinity = t
        return self

    def priority(self, expr: str) -> "PTGTaskClass":
        self._priority = _Expr(expr)
        return self

    def flow(self, name: str, mode: AccessMode, *deps: str) -> "PTGTaskClass":
        f = _PTGFlow(name, mode, len(self.flows))
        for d in deps:
            dep = _parse_dep(d)
            (f.deps_in if dep.is_input else f.deps_out).append(dep)
        self.flows.append(f)
        return self

    def add_dep(self, flow_name: str, *deps: str) -> "PTGTaskClass":
        """Append dependencies to an EXISTING flow.  Graph-synthesis
        front-ends (:mod:`parsec_tpu.array`) build producer classes
        before their consumers exist, then mirror the consumer edges
        back onto the producer once they are known — JDF reciprocity
        demands both sides, but a synthesizer discovers them one at a
        time.  Only valid before ``taskpool()`` builds the vtables."""
        for f in self.flows:
            if f.name == flow_name:
                for d in deps:
                    dep = _parse_dep(d)
                    (f.deps_in if dep.is_input else f.deps_out).append(dep)
                return self
        raise ValueError(f"class {self.name}: no flow {flow_name!r}")

    def ctl(self, name: str, *deps: str) -> "PTGTaskClass":
        return self.flow(name, CTL, *deps)

    def body(self, cpu: Optional[Callable] = None, tpu: Optional[Callable] = None,
             **others: Callable) -> "PTGTaskClass":
        if cpu is not None:
            self.bodies[DEV_CPU] = cpu
        if tpu is not None:
            self.bodies[DEV_TPU] = tpu
        self.bodies.update(others)
        return self

    def evaluate_hook(self, device: str, fn: Callable) -> "PTGTaskClass":
        """Attach an applicability predicate to one device's incarnation
        (reference BODY ``[evaluate = fn]``, ``jdf_body_t`` evaluate
        property): ``fn(task) -> bool``; False skips this incarnation at
        device selection, like a HOOK_RETURN_NEXT evaluate."""
        self.chore_evaluate[device] = fn
        return self

    def stage(self, flow_name: str, stage_in: Optional[Callable] = None,
              stage_out: Optional[Callable] = None) -> "PTGTaskClass":
        """Custom per-flow device staging (reference BODY
        ``stage_in=``/``stage_out=`` properties reaching the GPU task,
        ``device_gpu.h:62-94``; ``tests/runtime/cuda/stage_custom.jdf``).

        ``stage_in(data, device) -> jax.Array`` replaces the default
        whole-tile H2D staging — pack a strided subtile, convert layout,
        quantize — and its result becomes the flow's device copy.
        ``stage_out(array, data, device) -> jax.Array`` transforms the
        body's output for that flow before it is committed as the new
        device copy (e.g. scatter the packed subtile back)."""
        if flow_name not in {f.name for f in self.flows}:
            raise ValueError(f"class {self.name}: no flow {flow_name!r}")
        self.stage_hooks[flow_name] = (stage_in, stage_out)
        return self

    # -- evaluation over a constants dict --------------------------------
    def env_of(self, locals_: Tuple, constants: Dict[str, Any]) -> Dict[str, Any]:
        """Bind params from the task key and evaluate definitions in
        declaration order (definitions may reference earlier locals)."""
        env = dict(constants)
        it = iter(locals_)
        for name, expr, is_param in self.decls:
            env[name] = next(it) if is_param else expr.scalar(env)
        return env

    def param_space(self, constants: Dict[str, Any]) -> Iterable[Tuple]:
        def rec(i: int, env: Dict[str, Any], acc: Tuple):
            if i == len(self.decls):
                yield acc
                return
            name, expr, is_param = self.decls[i]
            if is_param:
                for v in expr.values(env):
                    e2 = dict(env)
                    e2[name] = v
                    yield from rec(i + 1, e2, acc + (v,))
            else:
                e2 = dict(env)
                e2[name] = expr.scalar(env)
                yield from rec(i + 1, e2, acc)

        yield from rec(0, dict(constants), ())

    def valid(self, locals_: Tuple, constants: Dict[str, Any]) -> bool:
        global _exists_evals
        env = dict(constants)
        it = iter(locals_)
        for name, expr, is_param in self.decls:
            if is_param:
                v = next(it)
                vals = expr.values(env)
                if isinstance(vals, range):
                    # O(1) range membership — one work unit
                    _exists_evals += 1
                else:
                    # materialized candidates: count them, so a predicate
                    # that ENUMERATES a parameter span shows up in the
                    # counter as O(span) work (test_exists_stress pins
                    # the O(#params) law on this, not on wall-clock)
                    vals = tuple(vals)
                    _exists_evals += max(len(vals), 1)
                if v not in vals:
                    return False
                env[name] = v
            else:
                env[name] = expr.scalar(env)
        return True

    def active_input(self, f: _PTGFlow, env: Dict[str, Any]):
        t = self.active_input_dep(f, env)
        return t[1] if t is not None else None

    def active_input_dep(self, f: _PTGFlow, env: Dict[str, Any]):
        """The guard-true input dep and its target, or None."""
        for dep in f.deps_in:
            t = dep.target(env)
            if t is not None and not isinstance(t, _NoneRef):
                return dep, t
        return None

    def input_defined(self, f: _PTGFlow, env: Dict[str, Any]) -> bool:
        """True when some input dep *matches* under env — including an
        explicit NONE branch ("this flow has no input here", defined).
        False means no guard matched at all: with dynamic guards
        (choice.jdf) the route simply isn't decided yet."""
        for dep in f.deps_in:
            if dep.target(env) is not None:
                return True
        return False

    def goal_of(self, locals_: Tuple, constants: Dict[str, Any],
                memo: Optional[Dict] = None) -> int:
        """Counter-mode dependency goal. Data flows have exactly one active
        source (guarded alternatives, JDF single-assignment); CTL flows
        *gather*: every guard-true dep contributes one dependency per
        instance of its (possibly ranged) task reference (reference
        controlgather semantics).  ``memo`` forwards to
        :meth:`instance_exists` (existence is constants-only, cacheable
        even under dynamic guards)."""
        env = self.env_of(locals_, constants)
        goal = 0
        for f in self.flows:
            if f.mode == CTL:
                for dep in f.deps_in:
                    t = dep.target(env)
                    if isinstance(t, _TaskRef):
                        src_pc = self.ptg.classes[t.class_name]
                        for locs in _expand_args(t.args, env):
                            if len(locs) == len(src_pc.param_names) and src_pc.valid(locs, constants):
                                goal += 1
            else:
                t = self.active_input(f, env)
                if isinstance(t, _TaskRef):
                    # an input whose producer reference falls OUTSIDE the
                    # producer's parameter space does not exist — it must
                    # not count toward the goal (reference complex_deps:
                    # FCT3(i,k,j>k) reads FCT2(i,j,k), valid only on the
                    # diagonal; off-diagonal instances run without it).
                    # Arg-evaluation errors PROPAGATE — _resolve_input
                    # evaluates the same expressions unguarded, and the
                    # two must agree or goals desync from resolution.
                    src_pc = self.ptg.classes[t.class_name]
                    locs = tuple(a.scalar(env) for a in t.args)
                    if src_pc.instance_exists(locs, constants, memo):
                        goal += 1
        return goal

    def instance_exists(self, key: Tuple, constants: Dict[str, Any],
                        memo: Optional[Dict] = None) -> bool:
        """True when ``key`` names a real instance of this class — the
        ONE predicate behind goal counting, input resolution and capture
        (a dep referencing a non-instance does not exist; reference
        complex_deps off-diagonal corner).

        This is a direct predicate evaluation — O(#params) with O(1)
        range-membership per param (``valid`` walks the declarations, it
        never enumerates the producer's parameter space), matching the
        reference's O(1) predecessor predicates in generated code
        (``jdf2c.c``).  ``memo`` (the taskpool's per-instance dict, safe
        because existence depends only on the taskpool constants, never
        on dynamic guard state) bounds even that to one evaluation per
        distinct (class, key) under guard-heavy webs that re-derive the
        same reference per input.

        Every DIRECT evaluation (memo miss included) bumps the module
        counter read by :func:`exists_eval_count` — tests pin the O(1)
        law on that counter instead of wall-clock (timing-ratio
        assertions flake on loaded hosts)."""
        global _exists_evals
        if memo is not None:
            mk = (self.name, key)
            r = memo.get(mk)
            if r is None:
                _exists_evals += 1
                r = memo[mk] = (len(key) == len(self.param_names)
                                and self.valid(key, constants))
            return r
        _exists_evals += 1
        return len(key) == len(self.param_names) and self.valid(key, constants)

    def rank_of(self, locals_: Tuple, constants: Dict[str, Any]) -> int:
        if self._affinity is None:
            return 0
        env = self.env_of(locals_, constants)
        dc = constants[self._affinity.collection_name]
        return dc.rank_of(*self._affinity.key(env))

    def priority_of(self, locals_: Tuple, constants: Dict[str, Any]) -> int:
        if self._priority is None:
            return 0
        return int(self._priority(self.env_of(locals_, constants)))


class PTG:
    """A PTG definition. ``taskpool(**constants)`` instantiates it — the
    analogue of the generated ``parsec_<name>_new(...)``, reusable with
    different problem sizes."""

    def __init__(self, name: str, *, dep_storage: Optional[str] = None,
                 **constants: Any):
        self.name = name
        #: dependency-storage backend: "hash" | "dense" | None (= the
        #: ``runtime_dep_storage`` MCA param; reference: ``jdf2c -M``
        #: dynamic-hash-table vs index-array, ``ptg-compiler/main.c:37``)
        self.dep_storage = dep_storage
        self.constants: Dict[str, Any] = dict(constants)
        #: name -> fn(constants): a global nobody has to give (reference
        #: JDF ``NAME [hidden = on default = "descA->mt"]``), worked out
        #: from the others, in declaration order, when a taskpool is made
        self.defaults: Dict[str, Callable[[Dict[str, Any]], Any]] = {}
        self.classes: Dict[str, PTGTaskClass] = {}

    def default(self, name: str,
                fn: Callable[[Dict[str, Any]], Any]) -> "PTG":
        """Declare the global ``name`` optional: ``fn(constants)`` gives
        its value where ``taskpool()`` (or ``verify()``) was given none."""
        self.defaults[name] = fn
        return self

    def globals_of(self, given: Dict[str, Any]) -> Dict[str, Any]:
        """The globals a taskpool made with ``given`` has: the
        definition's own, ``given`` over them, the defaults of the rest."""
        merged = dict(self.constants)
        merged.update(given)
        for name, fn in self.defaults.items():
            if name not in merged:
                merged[name] = fn(merged)
        return merged

    def task_class(self, name: str, **params: str) -> PTGTaskClass:
        c = PTGTaskClass(self, name, params)
        self.classes[name] = c
        return c

    def taskpool(self, termdet: Optional[str] = None,
                 **constants: Any) -> "PTGTaskpool":
        return PTGTaskpool(self, self.globals_of(constants), termdet=termdet)

    def verify(self, globals_: Optional[Dict[str, Any]] = None, *,
               level: str = "full", ignore: Sequence[str] = (),
               known: Optional[Iterable[str]] = None,
               collections: Optional[set] = None,
               max_tasks: Optional[int] = None,
               **more: Any):
        """Ahead-of-time graph verification (the jdfc sanity-check
        analogue): enumerate the parameter space under the given concrete
        globals WITHOUT executing any task body and check edge
        reciprocity, data hazards, cycles/liveness, and expression/
        affinity sanity.  Returns a list of
        :class:`parsec_tpu.analysis.Finding` (empty = clean).

        ``level``: ``"full"`` (default) runs every check; ``"static"``
        runs only source-level lint (no parameter-space enumeration —
        usable before concrete problem sizes are known).  ``ignore``
        suppresses finding codes (e.g. ``("PTG021",)`` for graphs with
        dynamic guards, whose held-back tasks are released at runtime by
        their producers).  ``known``/``collections`` name the symbols a
        later taskpool() call will supply (without them, a no-globals
        static verify treats every referenced symbol as known — a bare
        PTG declares its globals only implicitly, so unbound-symbol
        checks need either concrete globals or a declared name set).
        ``max_tasks`` caps the instance enumeration (PTG050 beyond it).
        Extra keyword arguments are graph globals, mirroring
        ``taskpool(**constants)``.  See ``docs/USERGUIDE.md`` "Linting
        your graph"."""
        from ..analysis import verify_ptg
        from ..analysis.linter import collection_names, free_symbols

        kw: Dict[str, Any] = {"level": level, "ignore": ignore}
        if max_tasks is not None:
            kw["max_tasks"] = max_tasks
        if globals_ is None and not more:
            # no concrete globals: static-only lint of the definition.
            # The symbol/collection universe comes from the caller, or
            # defaults to "everything the definition references" —
            # structural checks (PTG033/034/035) still run in full.
            if known is None:
                known = free_symbols(self) | set(self.constants)
            if collections is None:
                collections = collection_names(self)
            return verify_ptg(self, None, known=known,
                              collections=collections, **kw)
        if known is not None:
            kw["known"] = known
        if collections is not None:
            kw["collections"] = collections
        return verify_ptg(self, {**(globals_ or {}), **more}, **kw)


# ---------------------------------------------------------------------------
# the instantiated taskpool (what jdf2c generates)
# ---------------------------------------------------------------------------

class PTGTaskpool(Taskpool):
    def __init__(self, ptg: PTG, constants: Dict[str, Any],
                 termdet: Optional[str] = None):
        super().__init__(name=ptg.name, termdet=termdet)
        self.taskpool_type = Taskpool.TYPE_PTG
        self.ptg = ptg
        self.constants = constants
        self.deps = self._make_dep_tracker()
        self.repos: Dict[str, DataRepo] = {}
        self._built: Dict[str, TaskClass] = {}
        self._local_cache: Dict[str, List[Tuple]] = {}
        #: per-class (lo, hi) parameter bounding box, filled by _local_space
        self._class_box: Dict[str, Tuple] = {}
        self._new_tiles: Dict[Tuple, Data] = {}
        self._new_lock = threading.Lock()
        #: exactly-once guard for GOAL-0 tasks: the chunked startup scan
        #: and a producer release (possible with dynamic guards) may both
        #: decide to schedule one — whoever claims first wins
        self._source_claims: set = set()
        self._claims_lock = threading.Lock()
        #: (class_name, key) -> bool existence memo shared by goal
        #: counting and repo-miss resolution (VERDICT r04 #9): existence
        #: depends only on the taskpool constants, so one evaluation per
        #: distinct reference suffices for the taskpool's lifetime (GIL
        #: makes the dict get/set safe; a racing double-compute is
        #: idempotent)
        self._exists_memo: Dict[Tuple[str, Tuple], bool] = {}
        #: supertask-fusion table (dsl.fusion.FusionTable), built at
        #: attach when ``runtime_fusion`` is on: routes fused members'
        #: releases to region counters and dispatches each region as ONE
        #: device chore; None = per-task dispatch (the default)
        self._fusion = None
        for pc in ptg.classes.values():
            self.repos[pc.name] = DataRepo(nb_flows=len(pc.flows))
            self._build_class(pc)
        self.startup_hook = self._startup
        # the PTG manages task accounting itself: either a full pre-count
        # at attach (dense mode needs the class boxes anyway) or the
        # chunked startup scan's incremental adds (reference
        # task_startup_iter/chunk, parsec.c:669-676) — never per-schedule
        # auto counting (undiscovered tasks must hold the counter)
        self.auto_count = False
        self._counted = False

    def capture(self, ranks: Optional[Sequence[int]] = None):
        """Materialize this taskpool's full DAG (see
        :func:`parsec_tpu.dsl.graph.capture`): the entry point of every
        whole-graph consumer — XLA lowering, the native executor (CPU
        chores or ``native_device=True`` dispatch), ptg→dtd replay."""
        from .graph import capture as _capture

        return _capture(self, ranks)

    def run_native(self, *, nthreads: int = 4, native_device: bool = False,
                   device=None) -> int:
        """Execute this (unstarted) taskpool on the native C++ engine —
        dependency counting, scheduling and termination never enter the
        interpreter.  ``native_device=True`` additionally dispatches
        accelerator BODYs through the TPU device manager as ASYNC chores
        whose completions release successors natively (``pz_task_done``);
        see :class:`parsec_tpu.dsl.native_exec.NativeExecutor`."""
        from .native_exec import run_native as _run_native

        return _run_native(self, nthreads=nthreads,
                           native_device=native_device, device=device)

    def _make_dep_tracker(self):
        """Pick the dependency-storage backend (reference: per-class
        ``-M`` choice between dynamic hash table and dense index-array,
        ``ptg-compiler/main.c:37`` / ``parsec_internal.h:359-362``).

        Dense class boxes are registered later, as a by-product of the
        ``_count_local`` enumeration (no extra pass over the task space).
        """
        from ..utils.mca_param import params

        mode = self.ptg.dep_storage
        if mode is None:
            mode = params.register(
                "runtime", "dep_storage", "hash",
                choices=["hash", "dense"], level=5,
                help="PTG dependency-tracking storage: dynamic hash table "
                     "or dense index-array over each class's parameter box")
        if mode not in ("hash", "dense"):
            raise ValueError(
                f"PTG {self.ptg.name}: unknown dep_storage {mode!r} "
                "(expected 'hash' or 'dense')")
        return DenseDepTracker() if mode == "dense" else DepTracker()

    def _count_local(self, rank: int) -> int:
        self._local_cache.clear()
        n = sum(len(self._local_space(pc, rank)) for pc in self.ptg.classes.values())
        if isinstance(self.deps, DenseDepTracker):
            for name, box in self._class_box.items():
                self.deps.register_class(name, box)
        return n

    def attached(self, context) -> None:
        self._maybe_lint()
        self._maybe_fuse(context)
        if isinstance(self.deps, DenseDepTracker):
            # dense mode: class boxes must be registered before ANY
            # release (a counter split across the hash fallback and the
            # dense array would never reach its goal), and the same
            # enumeration yields the exact local count — scan up front
            self.tdm.taskpool_set_nb_tasks(self, self._count_local(context.rank))
            self._counted = True
        else:
            # hash mode: no pre-scan — the chunked startup pass counts
            # local tasks incrementally while the first chunks already
            # execute (add_taskpool holds a runtime action across
            # startup, so the transiently-small count cannot quiesce)
            self.tdm.taskpool_set_nb_tasks(self, 0)
            self._counted = False
        if context.nranks > 1:
            n_wb = self._count_expected_writebacks(context.rank)
            if n_wb:
                self.tdm.taskpool_addto_runtime_actions(self, n_wb)
        super().attached(context)

    def _maybe_fuse(self, context) -> None:
        """Attach-time supertask fusion (``runtime_fusion`` MCA): carve
        the captured local subgraph into convex chain/wave regions and
        dispatch each as one device chore (see :mod:`..dsl.fusion`).  A
        partitioner failure disables fusion loudly instead of killing
        the attach — per-task dispatch is always a correct fallback."""
        from ..utils import debug
        from .fusion import build_fusion_table, fusion_mode

        self._fusion = None
        if fusion_mode() in ("", "off"):
            return
        try:
            self._fusion = build_fusion_table(self, context)
        except Exception as e:
            debug.warning("taskpool %s: fusion disabled (%s: %s)",
                          self.ptg.name, type(e).__name__, e)
            self._fusion = None

    def _maybe_lint(self) -> None:
        """Opt-in startup verification (``PARSEC_TPU_LINT``): ``1``/``warn``
        prints findings to stderr and continues; ``strict``/``2`` raises
        on error-severity findings before any task is scheduled.
        ``PARSEC_TPU_LINT_IGNORE`` suppresses codes (comma/space
        separated, e.g. ``PTG021`` for dynamic-guard graphs, whose
        held-back tasks are legitimate) so strict mode stays usable on
        apps with a documented false positive.  Off by default — the
        verifier re-enumerates the parameter space, which is lint-scale
        work, not production-attach work."""
        import os

        mode = os.environ.get("PARSEC_TPU_LINT", "").strip().lower()
        if mode in ("", "0", "off"):
            return
        from ..analysis import verify_ptg
        from ..analysis.findings import LintError, errors_of
        from ..utils import debug

        ignore = tuple(
            c for c in os.environ.get("PARSEC_TPU_LINT_IGNORE", "")
            .replace(",", " ").split() if c)
        findings = verify_ptg(self.ptg, self.constants, ignore=ignore)
        for f in findings:
            debug.warning("lint %s: %s", self.ptg.name, f)
        if mode in ("strict", "2") and errors_of(findings):
            raise LintError(
                f"PARSEC_TPU_LINT=strict: taskpool {self.ptg.name} has "
                f"{len(errors_of(findings))} lint error(s)", findings)

    # -- vtable construction (the jdf2c analogue) ------------------------
    def _build_class(self, pc: PTGTaskClass) -> None:
        taken = {f.name for f in pc.flows} | {n for n, _, _ in pc.decls}
        clash = [n for n in pc.body_globals if n in taken]
        if clash:
            raise ValueError(
                f"class {pc.name}: use_globals names {clash} collide with "
                "a flow or local — bodies would receive the wrong value")
        flows = [Flow(f.name, f.mode, f.index) for f in pc.flows]
        tc = TaskClass(pc.name, flows=flows, nb_parameters=len(pc.param_names))
        tc.prepare_input = self._make_prepare_input(pc)
        tc.release_deps = self._make_release_deps(pc)
        for dev_type, fn in pc.bodies.items():
            if dev_type == DEV_CPU:
                chore = Chore(DEV_CPU, _make_cpu_hook(pc, fn))
            else:
                chore = Chore(dev_type, _accel_hook)
                chore.body_fn = _wrap_device_body(pc, fn)
            chore.evaluate = pc.chore_evaluate.get(dev_type)
            tc.add_chore(chore)
        self._built[pc.name] = tc
        self.add_task_class(tc)

    def _local_space(self, pc: PTGTaskClass, rank: Optional[int] = None) -> List[Tuple]:
        if rank is None:
            rank = self.context.rank if self.context else 0
        cached = self._local_cache.get(pc.name)
        if cached is None:
            cached = []
            lo = hi = None
            for loc in pc.param_space(self.constants):
                if lo is None:
                    lo, hi = list(loc), list(loc)
                else:
                    for i, v in enumerate(loc):
                        if v < lo[i]:
                            lo[i] = v
                        if v > hi[i]:
                            hi[i] = v
                if pc.rank_of(loc, self.constants) == rank:
                    cached.append(loc)
            if lo is not None:
                self._class_box[pc.name] = tuple(
                    (int(a), int(b)) for a, b in zip(lo, hi))
            self._local_cache[pc.name] = cached
        return cached

    #: local tasks discovered per accounting/scheduling step of the
    #: chunked startup scan (reference task_startup_chunk, parsec.c:669)
    STARTUP_CHUNK = 256

    def _startup(self, context, tp) -> List[Task]:
        from ..utils import debug

        if self._counted:
            # dense mode pre-scanned at attach: the cache holds the local
            # space, counts are final — just pick the sources
            out = []
            for pc in self.ptg.classes.values():
                undefined = claimed = 0
                for loc in self._local_space(pc):
                    if pc.goal_of(loc, self.constants, self._exists_memo) != 0:
                        continue
                    if not self._is_startup(pc, loc, goal_known_zero=True):
                        undefined += 1
                    elif self._claim_source(pc.name, loc):
                        # same exactly-once claim as the chunked branch: with
                        # dynamic guards a producer release can race this scan
                        t = self._route_source(pc, loc)
                        if t is not None:
                            out.append(t)
                    else:
                        claimed += 1  # a producer beat the scan to it: fine
                self._warn_undefined(pc, undefined, claimed)
            return out

        # chunked startup (the default): ONE pass over the task space per
        # class doing local-count + source detection, releasing each chunk
        # to the schedulers as it is found — execution overlaps the
        # remainder of the enumeration instead of waiting for three full
        # prescans (reference task_startup_iter/chunk, jdf2c.c:3036).
        # Like the reference's chunked startup, tasks of earlier chunks
        # already RUN while later locs are scanned, so dynamic guards
        # (bodies mutating state guards read) must not change startup
        # MEMBERSHIP — dynamic-input tasks are held back via the
        # `undefined` path and released by their producers.  The deps.peek
        # guard below closes the residual window: a task some already-
        # running producer released into is never also scheduled as a
        # source.
        from ..core import scheduling

        myrank = context.rank if context is not None else 0
        for pc in self.ptg.classes.values():
            cached: List[Tuple] = []
            ready: List[Task] = []
            pending = 0
            undefined = claimed = 0
            for loc in pc.param_space(self.constants):
                if pc.rank_of(loc, self.constants) != myrank:
                    continue
                cached.append(loc)
                pending += 1
                if pc.goal_of(loc, self.constants, self._exists_memo) == 0:
                    if not self._is_startup(pc, loc, goal_known_zero=True):
                        undefined += 1
                    elif self._claim_source(pc.name, loc):
                        t = self._route_source(pc, loc)
                        if t is not None:
                            ready.append(t)
                    else:
                        claimed += 1  # a producer beat the scan to it: fine
                if pending >= self.STARTUP_CHUNK:
                    # count BEFORE scheduling: a chunk task retiring
                    # instantly must never see an unaccounted self
                    self.tdm.taskpool_addto_nb_tasks(self, pending)
                    pending = 0
                    if ready:
                        scheduling.schedule_ready(context, None, ready)
                        ready = []
            if pending:
                self.tdm.taskpool_addto_nb_tasks(self, pending)
            if ready:
                scheduling.schedule_ready(context, None, ready)
            self._local_cache[pc.name] = cached
            self._warn_undefined(pc, undefined, claimed)
        return []

    def _route_source(self, pc: PTGTaskClass, loc: Tuple):
        """Claimed startup source → a schedulable task: the task itself
        normally; for a fused member, one region-readiness event (the
        supertask, exactly once, when the region's last event lands)."""
        if self._fusion is not None:
            handled, supertask = self._fusion.route_ready(pc.name, loc)
            if handled:
                return supertask
        return self._make_task(pc, loc)

    def _claim_source(self, name: str, locs: Tuple) -> bool:
        """Atomically claim the right to schedule a goal-0 task.  Closes
        the race between the chunked startup scan and a concurrent
        producer release firing into the same task (dynamic guards):
        release_counter's delete-on-fire leaves nothing for a peek to
        see, so exactly-once needs its own claim."""
        key = (name, locs)
        with self._claims_lock:
            if key in self._source_claims:
                return False
            self._source_claims.add(key)
            return True

    def _warn_undefined(self, pc: PTGTaskClass, undefined: int,
                        claimed: int = 0) -> None:
        from ..utils import debug

        if undefined:
            # goal 0 but some readable flow had no matched input dep:
            # legitimate with dynamic guards (a producer releases the
            # task later), a guaranteed hang if the guards are static
            debug.verbose(
                2, "ptg",
                "%s: %d task(s) held back from startup — a readable "
                "flow matched no input dep; if its guards are static, "
                "add an explicit '<- NONE' fallback", pc.name, undefined)
        if claimed:
            # benign and expected under dynamic guards: a producer release
            # scheduled these before the scan reached them — NOT a missing
            # input dep, so keep it out of the '<- NONE' diagnostic
            debug.verbose(
                3, "ptg",
                "%s: %d source task(s) already claimed by producer "
                "releases during the startup scan", pc.name, claimed)

    def _is_startup(self, pc: PTGTaskClass, loc: Tuple,
                    goal_known_zero: bool = False) -> bool:
        """A task starts immediately only when its dependency goal is zero
        AND every readable flow that declares input deps has a guard-true
        one right now.  With *dynamic* guards (reference choice.jdf: guards
        read state written by other tasks' bodies) all guards of a flow can
        be false at enqueue time — such a task is NOT a source; its
        producer releases it later, re-evaluating the goal then.  Treating
        it as startup would execute it twice (startup + release)."""
        if not goal_known_zero and pc.goal_of(loc, self.constants, self._exists_memo) != 0:
            return False
        env = pc.env_of(loc, self.constants)
        for f in pc.flows:
            if f.mode == CTL or not (f.mode & AccessMode.IN):
                continue
            if f.deps_in and not pc.input_defined(f, env):
                return False
        return True

    def _make_task(self, pc: PTGTaskClass, locals_: Tuple) -> Task:
        return Task(self, self._built[pc.name], locals_,
                    priority=pc.priority_of(locals_, self.constants))

    # -- data resolution -------------------------------------------------
    def _home_rule(self, pc: PTGTaskClass):
        """Which outputs of a ``pc`` task are LAST versions on this rank
        (``Task._tpu_home``: the device module sends only those home),
        as far as the class alone says it: ``(always, guarded)``.  A
        version nobody overwrites is the tile's last: a writable flow is
        NOT home where an active output dependency hands the tile to a
        successor that exists and writes it (local or remote: the
        writer's version supersedes this one and finds its own way
        home), and every other writable flow is, whether or not the PTG
        spells a terminal ``-> A(m, n)``.  ``always``: the positions in
        ``body_args`` (a flow's index) of the flows none of whose
        dependencies can name a writer; ``guarded``: for the others,
        ``(position, ((guard, then, otherwise), ...))`` with a branch
        that names a writer as ``(its class, its arguments, the
        arguments as ONE expression where none is a range)`` and any
        other as None, decided a task by :meth:`_last_versions`.
        ``always`` is None where a dependency that can name a writer
        reads anything but the task's key and the pool's scalar
        constants (dynamic guards, :meth:`_is_startup`): its value at
        ``prepare_input`` is not its value at release, the class cannot
        know, and every version goes to the committer as it did."""
        classes = self.ptg.classes
        reads: List[_Expr] = []

        def writer(t):
            spc = classes.get(t.class_name) \
                if isinstance(t, _TaskRef) else None
            if spc is None or not any(
                    sf.name == t.flow_name and sf.mode != CTL
                    and sf.mode & AccessMode.OUT for sf in spc.flows):
                return None
            return _instances_of(spc, t.args, reads)

        always: List[int] = []
        guarded: List[Tuple[int, Tuple]] = []
        for f in pc.flows:
            if f.mode == CTL or not (f.mode & AccessMode.OUT):
                continue
            deps = []
            for dep in f.deps_out:
                then, otherwise = writer(dep.then), writer(dep.otherwise)
                if then is not None or otherwise is not None:
                    deps.append((dep.guard, then, otherwise))
                    if dep.guard is not None:
                        reads.append(dep.guard)
            if deps:
                guarded.append((f.index, tuple(deps)))
            else:
                always.append(f.index)
        if reads:
            static = self._static_names(pc)
            if not all(_reads_only(e, static) for e in reads):
                return None, ()
        return tuple(always), tuple(guarded)

    def _static_names(self, pc: PTGTaskClass) -> set:
        """The names whose value is one and the same whenever an
        expression of a ``pc`` task is evaluated: the scalar constants
        of the pool, the task's parameters, and the definitions that
        read only those."""
        names = set(_SAFE_BUILTINS)
        names.update(k for k, v in self.constants.items()
                     if isinstance(v, _SCALARS))
        for name, expr, is_param in pc.decls:
            if is_param or all(_reads_only(e, names)
                               for e in (expr.lo, expr.hi, expr.step)
                               if e is not None):
                names.add(name)
            else:
                names.discard(name)  # (a definition shadows a constant)
        return names

    def _last_versions(self, always: Tuple[int, ...], guarded: Tuple,
                       env: Dict[str, Any]) -> Tuple[int, ...]:
        """``Task._tpu_home`` of the task whose environment is ``env``:
        ``always`` and, of the ``guarded`` flows (:meth:`_home_rule`),
        those whose active output dependencies name no writer that
        exists — the evaluation :meth:`_release_deps_core` makes, in the
        same environment, and through the same memo: what is asked here
        is not worked out again there.  A ranged dependency whose range
        is empty hands the tile to nobody."""
        home = always
        for pos, deps in guarded:
            for guard, then, otherwise in deps:
                w = then if guard is None or guard(env) else otherwise
                if w is not None and self._existing(w, env, 1):
                    break  # superseded: a later task's to send home
            else:
                home += (pos,)
        return home

    def _existing(self, ref: Tuple, env: Dict[str, Any], enough: int) -> int:
        """How many of the instances that ``ref`` names
        (:func:`_instances_of`) exist, as seen from the task whose
        environment is ``env``; counted no further than ``enough``."""
        spc, args, one = ref
        consts, memo = self.constants, self._exists_memo
        locs = one(env) if one is not None else None
        if locs is None or range in map(type, locs):
            n = 0
            for ls in _expand_args(args, env):
                if spc.instance_exists(ls, consts, memo):
                    n += 1
                    if n >= enough:
                        break
            return n
        there = memo.get((spc.name, locs))
        if there is None:
            there = spc.instance_exists(locs, consts, memo)
        return int(there)

    def _donate_rule(self, pc: PTGTaskClass):
        """Which read-write inputs of a ``pc`` task are its ALONE
        (``Task._tpu_donate``: the device module may let the task's
        program write the output over that version's array), as far as
        the class alone says it: ``_donations``'s rule
        (``dsl/attach_plan.py``), read from the classes' dependencies
        instead of a captured graph.  ``{source: how}`` by the input
        dependency's target (the ``_TaskRef`` / ``_DataRef`` that
        ``active_input_dep`` returns: one object a branch), for the
        sources of the ``INOUT`` flows that can be donated at all:

        * a **producer's flow** that the producer WRITES (a flow it only
          read forwards a version whose other readers share it):
          ``(the producer's class, its key as one expression, the
          producer flow's output dependencies)``, each dependency
          ``(guard, then, otherwise)`` with a branch that names tasks
          as :func:`_instances_of` has them, a branch that names a
          collection's tile as the ``_DataRef`` it is, any other as
          None; decided a task by :meth:`_sole_reader`;
        * **the collection's tile** (a first version, staged from its
          home: what is donated is the device's private copy of it):
          True, where every direct ``<- X(...)`` source of that
          collection, in every class of the PTG, is on a flow that
          writes — two such flows on one tile un-ordered would be a race
          in the PTG itself, and a PTG that reads a tile of the
          collection read-only anywhere keeps its first versions.

        A ``NEW`` tile, no source, a ranged source: absent, never
        donated.  None where a dependency that decides reads anything
        but a task's key and the pool's scalar constants
        (:meth:`_home_rule`): the class cannot know, and
        ``_tpu_donate`` stays None (``commits_donate_unknown``)."""
        classes, consts = self.ptg.classes, self.constants
        inout = AccessMode.INOUT
        #: the collections (as objects: two names may be one) some flow
        #: reads from memory without writing
        read_only = {id(consts.get(t.collection_name))
                     for c in classes.values() for f in c.flows
                     if f.mode == CTL or not (f.mode & AccessMode.OUT)
                     for dep in f.deps_in for t in (dep.then, dep.otherwise)
                     if isinstance(t, _DataRef)}
        rule: Dict[Any, Any] = {}
        mine: List[_Expr] = []
        for f in pc.flows:
            if f.mode == CTL or f.mode & inout != inout:
                continue
            for dep in f.deps_in:
                if dep.guard is not None:
                    mine.append(dep.guard)
                for t in (dep.then, dep.otherwise):
                    if isinstance(t, _DataRef):
                        if id(consts.get(t.collection_name)) not in read_only:
                            rule[t] = True
                        continue
                    spc = classes.get(t.class_name) \
                        if isinstance(t, _TaskRef) else None
                    sf = next((x for x in spc.flows
                               if x.name == t.flow_name), None) \
                        if spc is not None else None
                    if sf is None or sf.mode == CTL \
                            or not (sf.mode & AccessMode.OUT):
                        continue
                    key = _instances_of(spc, t.args, mine)[2]
                    if key is None:
                        continue
                    theirs: List[_Expr] = []
                    outs = self._handed_on(sf, theirs)
                    static = self._static_names(spc)
                    if outs is None or not all(_reads_only(e, static)
                                               for e in theirs):
                        return None
                    rule[t] = (spc, key, outs)
        static = self._static_names(pc)
        if not all(_reads_only(e, static) for e in mine):
            return None
        return rule

    def _handed_on(self, sf: _PTGFlow, reads: List[_Expr]):
        """The output dependencies of a producer's flow as
        :meth:`_sole_reader` reads them (:meth:`_donate_rule`), every
        expression they evaluate added to ``reads``; None where one
        names a class nobody declared."""
        classes = self.ptg.classes
        outs = []
        for out in sf.deps_out:
            branches = []
            for r in (out.then, out.otherwise):
                if isinstance(r, _TaskRef):
                    rpc = classes.get(r.class_name)
                    if rpc is None:
                        return None
                    r = _instances_of(rpc, r.args, reads)
                elif isinstance(r, _DataRef):
                    reads.extend(a.lo for a in r.args)
                else:
                    r = None
                branches.append(r)
            if branches != [None, None]:
                outs.append((out.guard, *branches))
                if out.guard is not None:
                    reads.append(out.guard)
        return tuple(outs)

    def _sole_reader(self, how: Tuple, env: Dict[str, Any],
                     data: Data) -> bool:
        """Whether the task whose environment is ``env`` is the only
        consumer of the version it was handed as ``data``
        (:meth:`_donate_rule`, a producer's flow): the producer's active
        output dependencies of that flow, evaluated in the PRODUCER's
        environment — what :meth:`_release_deps_core` evaluated when it
        handed the version on, through the same memo — name exactly one
        instance that exists, this one (a ranged dependency counts every
        instance of its range), and none lands the version in a
        collection tile other than the flow's own."""
        spc, key, outs = how
        consts = self.constants
        locs = key(env)
        if not spc.instance_exists(locs, consts, self._exists_memo):
            return False  # (no producer: the tile was made here)
        penv = spc.env_of(locs, consts)
        n = 0
        for guard, then, otherwise in outs:
            r = then if guard is None or guard(penv) else otherwise
            if r is None:
                continue
            if type(r) is _DataRef:
                if consts[r.collection_name].data_of(*r.key(penv)) \
                        is not data:
                    return False
                continue
            n += self._existing(r, penv, 2 - n)
            if n > 1:
                return False
        return n == 1

    def _make_prepare_input(self, pc: PTGTaskClass):
        home_always, home_guarded = self._home_rule(pc)
        sole_rule = self._donate_rule(pc)

        def prepare_input(es, task: Task) -> HookReturn:
            env = pc.env_of(task.locals, self.constants)
            specs: List[Tuple[str, Any, AccessMode]] = []
            # (several ranks: the device module donates nothing,
            # TpuDevice._may_donate, and nothing is worked out for it.
            # Several ACCELERATORS of one rank: the rule counts the TASKS
            # that consume a version, whichever chip runs them, so "this
            # task alone" holds across chips as it stands; who else holds
            # the ARRAY, a peer module's landing among them, is the device
            # module's to see: ``_not_sole``, ``Data.claim_for_donation``)
            ctx = self.context
            sole = sole_rule if ctx is not None and ctx.nranks <= 1 \
                else None
            donate: Tuple[int, ...] = ()
            for f in pc.flows:
                if f.mode == CTL:
                    specs.append(("ctl", None, CTL))
                    continue
                dt = pc.active_input_dep(f, env)
                dep, target = dt if dt is not None else (None, None)
                data = self._resolve_input(pc, f, target, env, task)
                if (data is not None and dep is not None and dep.props
                        and not isinstance(target, _NewRef)):
                    # dep-level reshape request (reference
                    # parsec_get_copy_reshape_from_dep, parsec_reshape.c);
                    # input-side reshape only makes sense for read-only
                    # flows — a writable flow would divert its writes into
                    # the converted copy and corrupt the home tile
                    rspec = ReshapeSpec.from_props(dep.props, self.constants)
                    if rspec is not None:
                        if f.mode & AccessMode.OUT:
                            raise ValueError(
                                f"{pc.name}.{f.name}: reshape props "
                                f"{dep.props} on a writable flow are not "
                                "supported (reads would be diverted)")
                        data = materialize(get_copy_reshape(data, rspec))
                specs.append(("data", data, f.mode))
                task.data_in[f.index] = data.newest_copy() if data is not None else None
                if sole is not None and data is not None:
                    how = sole.get(target)
                    if how is not None and (
                            how is True
                            or self._sole_reader(how, env, data)):
                        donate += (f.index,)
            for name in pc.param_names + pc.def_names + pc.body_globals:
                specs.append(("value", env[name], AccessMode.VALUE))
            task.body_args = specs
            task._tpu_home = self._last_versions(
                home_always, home_guarded, env) if home_guarded \
                else home_always
            if sole is not None:
                task._tpu_donate = donate
            return HookReturn.DONE

        return prepare_input

    def _resolve_input(self, pc: PTGTaskClass, f: _PTGFlow, target, env, task: Task) -> Optional[Data]:
        if target is None or isinstance(target, _NoneRef):
            if f.mode & AccessMode.OUT:
                return self._new_tile(pc, f, task.locals)  # pure output, no source
            return None
        if isinstance(target, _NewRef):
            return self._new_tile(pc, f, task.locals)
        if isinstance(target, _DataRef):
            dc = self.constants[target.collection_name]
            return dc.data_of(*target.key(env))
        # task reference: producer deposited the flow data in its repo
        src_pc = self.ptg.classes[target.class_name]
        key = tuple(a.scalar(env) for a in target.args)
        entry = self.repos[src_pc.name].consume(key)
        if entry is None:
            # miss: either an out-of-range producer reference (the input
            # does not exist — goal_of excluded it; rare, so the
            # existence scan runs only here, off the hot path) or a real
            # asymmetric-deps bug
            if not src_pc.instance_exists(key, self.constants, self._exists_memo):
                if f.mode & AccessMode.OUT:
                    return self._new_tile(pc, f, task.locals)
                return None
            raise RuntimeError(
                f"{task!r}: producer {target.class_name}{key} left no repo "
                f"entry for flow {target.flow_name!r} (asymmetric deps?)")
        src_flow = next(sf for sf in src_pc.flows if sf.name == target.flow_name)
        data = entry.copies[src_flow.index]
        if data is None:
            raise RuntimeError(
                f"{task!r}: producer {target.class_name}{key} deposited no "
                f"data for flow {target.flow_name!r}")
        return data

    def new_tile_spec(self, pc_name: str, flow_name: str) -> Tuple[Tuple, Any]:
        """(shape, dtype) for a flow's ``<- NEW`` tile: a ``[shape=…]`` /
        ``[dtype=…]`` / ``[type=NAME]`` property block on the NEW dep wins
        (NAME resolves through the taskpool constants, so shapes may
        depend on problem parameters); otherwise the taskpool-wide
        ``TILE_SHAPE``/``TILE_DTYPE`` constants."""
        shape = self.constants.get("TILE_SHAPE", (1,))
        dtype = self.constants.get("TILE_DTYPE", np.float64)
        pc = self.ptg.classes.get(pc_name)
        if pc is not None:
            for f in pc.flows:
                if f.name != flow_name:
                    continue
                for dep in f.deps_in:
                    # NEW may sit in either branch of a guarded dep
                    if not (isinstance(dep.then, _NewRef)
                            or isinstance(dep.otherwise, _NewRef)):
                        continue
                    if dep.props:
                        rspec = ReshapeSpec.from_props(dep.props, self.constants)
                        if rspec is not None:
                            shape = rspec.shape or shape
                            dtype = rspec.dtype or dtype
                break
        return tuple(shape), dtype

    def _new_tile(self, pc: PTGTaskClass, f: _PTGFlow, locals_: Tuple) -> Data:
        """The scratch tile (device/scratch.py) of a flow with no source:
        no payload, born where this task runs, its users declared from
        the flow's out-dependencies."""
        from ..device import scratch

        key = (pc.name, tuple(locals_), f.name)
        with self._new_lock:
            d = self._new_tiles.get(key)
            if d is None:
                shape, dtype = self.new_tile_spec(pc.name, f.name)
                d = scratch.new(key, shape, dtype)
                scratch.add_users(d, self._scratch_users(pc, f, locals_))
                self._new_tiles[key] = d
            return d

    def _scratch_users(self, pc: PTGTaskClass, f: _PTGFlow,
                       locals_: Tuple) -> int:
        """The tasks on this rank that will use the tile of ``f``: this
        one and, down the chain of its out-dependencies, every successor
        (the enumeration of :meth:`_release_deps_core`).  One more,
        never released, where a task writes the tile into a collection
        or sends it to another rank: that is read after its epilog."""
        env = pc.env_of(locals_, self.constants)
        myrank = self.context.rank if self.context else 0
        n, kept = 1, False
        for dep in f.deps_out:
            t = dep.target(env)
            if t is None or isinstance(t, (_NoneRef, _NewRef)):
                continue
            if isinstance(t, _DataRef):
                kept = True
                continue
            succ_pc = self.ptg.classes[t.class_name]
            sf = next(x for x in succ_pc.flows if x.name == t.flow_name)
            for locs in _expand_args(t.args, env):
                if len(locs) != len(succ_pc.param_names) \
                        or not succ_pc.valid(locs, self.constants):
                    continue
                if succ_pc.rank_of(locs, self.constants) != myrank:
                    kept = True  # sent from release_deps, like a write-back
                    continue
                n += self._scratch_users(succ_pc, sf, locs)
        return n + kept

    # -- completion / successor release ----------------------------------
    def _make_release_deps(self, pc: PTGTaskClass):
        def release_deps(es, task: Task) -> List[Task]:
            flow_data: List[Optional[Data]] = [None] * len(pc.flows)
            if task.body_args is not None:
                for f in pc.flows:
                    if f.mode != CTL:
                        flow_data[f.index] = task.body_args[f.index][1]
            return self._release_deps_core(pc, task.locals, flow_data,
                                           task.priority)

        return release_deps

    def _release_deps_core(self, pc: PTGTaskClass, locals_: Tuple,
                           flow_data: List[Optional[Data]],
                           priority: int,
                           origin_region=None) -> List[Task]:
        """Successor release for one (possibly virtual) completed task:
        write-backs, repo deposits, remote activations, and dependency-
        counter decrements.  ``flow_data[f.index]`` is the Data behind
        each non-CTL flow.  ``origin_region`` (a member-tid set) is the
        supertask release path: successors INSIDE the producer's own
        fused region are skipped entirely — they executed inside the
        fused program, never consume the repo, and must not be released
        (a decrement would double-schedule the region)."""
        env = pc.env_of(locals_, self.constants)
        repo = self.repos[pc.name]
        fusion = self._fusion
        memo = self._exists_memo
        entry = None
        nb_consumers = 0
        myrank = self.context.rank if self.context else 0
        succ_list: List[Tuple[PTGTaskClass, Tuple]] = []
        # per-destination-rank output masks + one payload per flow:
        # ONE aggregated activation per rank, however many successors
        # live there (reference parsec_remote_deps_t, remote_dep.h:132)
        rank_masks: Dict[int, int] = {}
        flow_payloads: Dict[int, np.ndarray] = {}
        for f in pc.flows:
            data = None
            if f.mode != CTL:
                data = flow_data[f.index]
            for dep in f.deps_out:
                t = dep.target(env)
                if t is None or isinstance(t, (_NoneRef, _NewRef)):
                    continue
                if isinstance(t, _DataRef):
                    if f.mode != CTL:
                        # CTL flows carry no data: never written back,
                        # and _count_expected_writebacks skips them too
                        # (count and send conditions must be identical
                        # or the owner's termdet never quiesces)
                        self._write_back(t, env, data)
                    continue
                succ_pc = self.ptg.classes[t.class_name]
                for locs in _expand_args(t.args, env):
                    # (asked once a successor, whoever asks first: its
                    # other producers, its consumers' goals, and
                    # _last_versions for a writer)
                    if not succ_pc.instance_exists(locs, self.constants,
                                                   memo):
                        continue
                    if origin_region is not None \
                            and (t.class_name, locs) in origin_region:
                        continue  # intra-region edge: handled in-program
                    rank = succ_pc.rank_of(locs, self.constants)
                    if rank != myrank:
                        rank_masks[rank] = rank_masks.get(rank, 0) | (1 << f.index)
                        if (f.mode != CTL and data is not None
                                and f.index not in flow_payloads):
                            src = data.newest_copy()
                            if src is not None:
                                # raw (possibly device-resident):
                                # converted for the transport below
                                flow_payloads[f.index] = src.payload
                        continue
                    if f.mode != CTL:
                        if entry is None:
                            entry = repo.lookup_and_create(locals_)
                        entry.copies[f.index] = data
                        nb_consumers += 1
                    succ_list.append((succ_pc, locs))
        if entry is not None:
            repo.set_usage_limit(locals_, nb_consumers)
        # remote successors: one aggregated activation per rank, routed
        # down the broadcast topology (reference
        # parsec_remote_dep_activate + propagate, SURVEY.md §3.4)
        if rank_masks:
            comm = self.context.comm if self.context else None
            if comm is None:
                raise RuntimeError(
                    f"task {pc.name}{locals_} has remote successors on "
                    f"ranks {sorted(rank_masks)} but the context has no "
                    "comm engine")
            if not getattr(comm, "device_payloads", False):
                # serializing transport: overlap the D2H copies of
                # every device-resident flow, then convert once each
                # (device-capable fabrics ship jax.Arrays untouched —
                # the receiver lands them device-to-device)
                from ..comm.payload import prefetch_to_host, to_wire

                prefetch_to_host(flow_payloads.values())
                flow_payloads = {k: to_wire(v)
                                 for k, v in flow_payloads.items()}
            comm.remote_dep.send_activations(
                self, pc.name, locals_, rank_masks, flow_payloads,
                priority=priority)
        ready: List[Task] = []
        for succ_pc, locs in succ_list:
            if fusion is not None:
                ext = fusion.ext_goal(succ_pc.name, locs)
                if ext is not None:
                    # fused member: its counter runs with the EXTERNAL
                    # goal (intra-region producers never fire), and
                    # readiness feeds the region, not a per-task
                    # schedule.  ext-goal-0 members need the same
                    # exactly-once claim as unfused goal-0 successors:
                    # a goal-0 counter fires on EVERY release, and a
                    # duplicate region event would over-decrement the
                    # waiting count and dispatch the supertask early
                    became, _ = self.deps.release_counter(
                        (succ_pc.name, locs), ext)
                    if became and (ext != 0 or self._claim_source(
                            succ_pc.name, locs)):
                        _, supertask = fusion.route_ready(
                            succ_pc.name, locs)
                        if supertask is not None:
                            ready.append(supertask)
                    continue
            goal = succ_pc.goal_of(locs, self.constants, self._exists_memo)
            became, _ = self.deps.release_counter((succ_pc.name, locs), goal)
            if became and (goal != 0
                           or self._claim_source(succ_pc.name, locs)):
                # goal-0 successors (dynamic guards) race the chunked
                # startup scan: the claim keeps execution exactly-once
                ready.append(self._make_task(succ_pc, locs))
        return ready

    def _write_back(self, t: _DataRef, env, data: Optional[Data]) -> None:
        dc = self.constants[t.collection_name]
        key = t.key(env)
        if self.context is not None and self.context.nranks > 1:
            owner = dc.rank_of(*key)
            if owner != self.context.rank:
                # final value of a remotely-owned home tile: ship it to
                # the owner (who pre-counted it as a runtime action).  A
                # flow that resolved to no data still sends a payload-less
                # retire so the owner's count drains — count and send must
                # stay in lockstep or the owner hangs in wait().
                src = data.newest_copy() if data is not None else None
                self.context.comm.remote_dep.send_writeback(
                    self, t.collection_name, key,
                    src.payload if src is not None else None,
                    owner)
                return
        if data is None:
            return
        home = dc.data_of(*key)
        if home is data:
            return  # flow aliases its home tile
        src = data.newest_copy()
        if src is None:
            return
        dst = home.get_copy(0)
        buf = np.asarray(src.payload)
        if dst is None or dst.payload is None:
            home.attach_copy(0, np.array(buf))
        else:
            np.copyto(dst.payload, buf)
        home.version_bump(0)

    def incoming_writeback(self, cname: str, key: Tuple, payload) -> None:
        """Receiver half of the cross-rank final write-back: store the
        arrived value into the home tile and retire one expected-arrival
        runtime action (armed in :meth:`attached`).  ``payload=None`` is a
        pure retire: the producer's flow resolved to no data, but the
        arrival was pre-counted so it must still drain the counter."""
        if payload is not None:
            from ..data.data import land_into_home

            land_into_home(self.constants[cname].data_of(*key), payload)
        self.tdm.taskpool_addto_runtime_actions(self, -1)

    def _count_expected_writebacks(self, rank: int) -> int:
        """How many remote tasks write their final flow value into a tile
        *I* own — each is one pre-counted termdet runtime action."""
        n = 0
        for pc in self.ptg.classes.values():
            # static pre-filter: only deps that CAN resolve to a data
            # reference matter here — classes without any skip the whole
            # parameter space, others skip env construction per dep
            wb_deps = [
                (f, dep)
                for f in pc.flows if f.mode != CTL
                for dep in f.deps_out
                if isinstance(dep.then, _DataRef)
                or isinstance(getattr(dep, "otherwise", None), _DataRef)
            ]
            if not wb_deps:
                continue
            for loc in pc.param_space(self.constants):
                if pc.rank_of(loc, self.constants) == rank:
                    continue  # local task: local write-back
                env = pc.env_of(loc, self.constants)
                for _f, dep in wb_deps:
                    t = dep.target(env)
                    if isinstance(t, _DataRef):
                        dc = self.constants[t.collection_name]
                        if dc.rank_of(*t.key(env)) == rank:
                            n += 1
        return n

    def incoming_activation(
        self,
        *,
        src_class: str,
        src_locals: Tuple,
        mask: int,
        flow_data: Dict[int, Any],
    ) -> None:
        """Receiver half of the aggregated activation protocol (reference
        ``remote_dep_release_incoming``): re-derive which of MY tasks the
        masked output flows of ``(src_class, src_locals)`` release — the
        reference model: the receiver runs iterate_successors itself, so
        successor lists never travel the wire — deposit the arrived flow
        payloads in the producer-class repo (usage-limited to the local
        consumer count, like the local release path), and decrement
        dependency counters.

        Guards are re-evaluated HERE from (locals, constants); like the
        reference, dynamic guards reading body-mutated state must be
        rank-local or producer and consumer can disagree."""
        pc = self.ptg.classes[src_class]
        env = pc.env_of(src_locals, self.constants)
        myrank = self.context.rank if self.context else 0
        repo = self.repos[src_class]
        entry = None
        nb_consumers = 0
        ready: List[Task] = []
        for f in pc.flows:
            if not (mask >> f.index) & 1:
                continue
            payload = flow_data.get(f.index)
            deposited = False
            for dep in f.deps_out:
                t = dep.target(env)
                if t is None or isinstance(t, (_NoneRef, _NewRef, _DataRef)):
                    continue  # write-backs are the producer's business
                succ_pc = self.ptg.classes[t.class_name]
                for locs in _expand_args(t.args, env):
                    if len(locs) != len(succ_pc.param_names):
                        continue
                    if not succ_pc.valid(locs, self.constants):
                        continue
                    if succ_pc.rank_of(locs, self.constants) != myrank:
                        continue
                    if f.mode != CTL and payload is not None:
                        if not deposited:
                            if entry is None:
                                entry = repo.lookup_and_create(src_locals)
                            if entry.copies[f.index] is None:
                                entry.copies[f.index] = self._deposit_payload(
                                    (src_class, src_locals, f.index), payload)
                            deposited = True
                        nb_consumers += 1
                    if self._fusion is not None:
                        ext = self._fusion.ext_goal(t.class_name, locs)
                        if ext is not None:
                            # remote producers are always external to a
                            # (rank-local) fused region: decrement the
                            # member's EXTERNAL goal and feed the region
                            # (ext-goal-0 members carry the same
                            # exactly-once claim as the local path —
                            # goal-0 counters fire on every release)
                            became, _ = self.deps.release_counter(
                                (t.class_name, locs), ext)
                            if became and (ext != 0 or self._claim_source(
                                    t.class_name, locs)):
                                _, supertask = self._fusion.route_ready(
                                    t.class_name, locs)
                                if supertask is not None:
                                    ready.append(supertask)
                            continue
                    goal = succ_pc.goal_of(locs, self.constants, self._exists_memo)
                    became, _ = self.deps.release_counter(
                        (t.class_name, locs), goal)
                    if became and (goal != 0
                                   or self._claim_source(t.class_name, locs)):
                        ready.append(self._make_task(succ_pc, locs))
        if entry is not None:
            repo.set_usage_limit(src_locals, nb_consumers)
        if ready and self.context is not None:
            self.context.schedule(ready, es=self.context.current_es())

    def _deposit_payload(self, key, payload):
        """Land an arrived flow payload.  A device-resident arrival (a
        device-capable fabric shipped a ``jax.Array``) is attached AS-IS:
        a device consumer's stage-in turns it into a direct
        device-to-device ``device_put`` (ICI-class on multi-chip, no host
        numpy — SURVEY §5.8) INSIDE the device manager, where HBM
        accounting and LRU mutation are single-threaded; a CPU consumer's
        ``stage_to_cpu`` normalizes it to a writable host array lazily.
        Landing it eagerly here would mutate residency state from the
        comm thread and bypass the budget."""
        return data_create(key, payload=payload)


# ---------------------------------------------------------------------------
# body hooks
# ---------------------------------------------------------------------------

def _accel_hook(es, task):
    return task.selected_device.kernel_scheduler(es, task)


def _wrap_device_body(pc: PTGTaskClass, fn: Callable):
    """The device module passes positional args (non-CTL flows, then
    params); re-map to the uniform keyword signature body(FLOW=..., k=...)."""
    names = ([f.name for f in pc.flows if f.mode != CTL]
             + pc.param_names + pc.def_names + pc.body_globals)

    def wrapped(*pos):
        return fn(**dict(zip(names, pos)))

    wrapped.__name__ = getattr(fn, "__name__", pc.name)
    # stable identity across taskpool instantiations: the device module's
    # jit cache keys on this so one XLA compile serves every taskpool
    # built from the same (body, flow-signature) pair
    wrapped._jit_key = getattr(fn, "_jit_key", (fn, tuple(names)))
    # forward the device-module opt-ins (see TpuDevice._submit): local
    # values baked statically into the trace / donated array positions
    # ... ``_converts``: its outputs are lower-precision twins; ``_zeros``:
    # the outputs that are exact zeros whatever goes in
    for attr in ("_static_values", "_donate_args", "_converts", "_zeros"):
        if hasattr(fn, attr):
            setattr(wrapped, attr, getattr(fn, attr))
    # ... and ``_batched``: the form that runs a wave of the body as one
    # kernel takes the same keywords, each stacked over the wave's tasks
    # or handed over once (``ValuePlan.stacked_args``)
    form = getattr(fn, "_batched", None)
    if form is not None:
        def batched(*pos):
            return form(**dict(zip(names, pos)))

        wrapped._batched = batched
    if pc.stage_hooks:
        # per-flow custom staging, indexed by the data-arg position the
        # device module sees (non-CTL flow declaration order)
        data_flows = [f.name for f in pc.flows if f.mode != CTL]
        wrapped._stage_in = {
            i: si for i, name in enumerate(data_flows)
            for si, _ in (pc.stage_hooks.get(name, (None, None)),)
            if si is not None}
        wrapped._stage_out = {
            i: so for i, name in enumerate(data_flows)
            for _, so in (pc.stage_hooks.get(name, (None, None)),)
            if so is not None}
    return wrapped


def _make_cpu_hook(pc: PTGTaskClass, fn: Callable):
    # reference BODY blocks see `this_task` implicitly; here it is opt-in
    # by naming it in the body signature (CPU incarnations only)
    try:
        import inspect

        wants_this_task = "this_task" in inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins / C callables
        wants_this_task = False

    def cpu_hook(es, task: Task) -> HookReturn:
        from .dtd import stage_to_cpu

        kw: Dict[str, Any] = {}
        writable: List[Data] = []
        for f in pc.flows:
            if f.mode == CTL:
                continue
            data: Optional[Data] = task.body_args[f.index][1]
            if data is None:
                kw[f.name] = None
                continue
            arr = stage_to_cpu(data)
            data.transfer_ownership(0, f.mode & AccessMode.INOUT)
            kw[f.name] = arr
            if f.mode & AccessMode.OUT:
                writable.append(data)
        values = [s[1] for s in task.body_args if s[0] == "value"]
        kw.update(zip(pc.param_names + pc.def_names + pc.body_globals, values))
        if wants_this_task:
            kw["this_task"] = task
        result = fn(**kw)
        if isinstance(result, HookReturn):
            # reference BODY semantics: a body may return a hook status —
            # ASYNC (e.g. recursive_invoke spawned a nested pool that owns
            # completion), NEXT (decline this incarnation), AGAIN — those
            # bypass the commit, which is the eventual completer's
            # business.  DONE falls THROUGH: the normal post-body commit
            # (payload rebinds + version bumps) must still run.
            if result is not HookReturn.DONE:
                return result
            result = None
        if result is not None:
            outs = result if isinstance(result, (tuple, list)) else (result,)
            if len(outs) != len(writable):
                raise ValueError(
                    f"{task!r}: body returned {len(outs)} outputs for "
                    f"{len(writable)} writable flows")
            for data, new in zip(writable, outs):
                data.get_copy(0).payload = np.asarray(new)
        for data in writable:
            data.version_bump(0)
        return HookReturn.DONE

    return cpu_hook
