"""The attach plan: what ``NativeExecutor(native_device=True)`` derives of
a taskpool that does not depend on its tiles, computed once per *shape*.

The reference pays none of this at run time: a PTG is compiled ahead of
time and ``parsec_dpotrf_New`` enumerates nothing.  Here the DAG is
captured by evaluating every dependency expression
(:func:`parsec_tpu.dsl.graph.capture`) and every flow is resolved to the
tile behind it; a loop over factorizations of one shape used to pay that
at the head of every solve.  An :class:`AttachPlan` keeps the answers in
native-id order and in flat tuples and arrays; a later executor over a
taskpool of the same shape only *binds* it (``NativeExecutor._bind``):
one ``data_of`` a distinct tile, one ``scratch.new`` a ``NEW`` chain, one
task object a task, one bulk call into the native graph.

A plan holds **no** taskpool, collection, ``Data``, array, device or
executor: a plan that kept the previous solve's collection alive would
keep its host tiles alive.

:func:`plan_key` is what a captured graph is a function of, as far as the
program can vouch for it: the PTG's source text, every constant by value,
every collection by what placement reads of it, the captured ranks and
the fusion configuration.  Whatever it cannot vouch for makes the pool
*uncacheable*: captured, resolved and bound as any first solve, stored
nowhere.  Plans live in a small LRU (:data:`PLAN_CACHE_SIZE`); nothing
switches this off, behaviour depends only on whether the key was seen.
"""

from __future__ import annotations

import ast
import collections
import ctypes
import functools
import heapq
import sys
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..core.lifecycle import AccessMode, DEV_CPU
from ..device.residency import NEVER, UNKNOWN
from .graph import TaskGraph, source_tile
from .ptg import (CTL, PTG, _ArgExpr, _DataRef, _SAFE_BUILTINS, _TaskRef,
                  _c_to_py)

TaskId = Tuple[str, Tuple]

#: plans kept (least recently bound goes first).  The out-of-core DAG
#: (15,180 tasks, 42,570 edges) is 11.5 MB of small tuples (PERF.md §6)
PLAN_CACHE_SIZE = 8

#: the pump's ``(batch, depth)`` where nobody says (the defaults of
#: ``runtime_native_drain`` and ``runtime_stage_depth``)
PUMP_WINDOW = (128, 2)

#: ``AttachPlan.tasks[i][3]``: a flow with no tile behind it
NO_DATA = -1
#: ... and a CTL flow (a placeholder in ``body_args``)
CTL_FLOW = -2


class Uncacheable(Exception):
    """The fingerprint cannot vouch for this taskpool; ``args[0]`` says
    for what."""


class AttachPlan:
    """One shape's resolved DAG.  Positions count tasks in capture
    order; a tile *slot* is an index into :attr:`tiles`; a native node is
    a task or a fused region, numbered from 0 (the bind adds the id the
    native graph gave the first)."""

    __slots__ = (
        "key", "ptg_name", "classes", "device_class", "has_cpu_bodies",
        "nodes", "tasks", "succs", "tiles", "tile_users",
        "node_of", "native", "native_prio", "edge_pred", "edge_succ",
        "roots", "fused", "next_use", "next_at", "written")

    def __init__(self):
        #: the fingerprint it is stored under; None = bound, never stored
        self.key: Optional[Tuple] = None
        self.ptg_name = ""
        #: class names, by the class index the task rows carry
        self.classes: Tuple[str, ...] = ()
        #: per class index: the class has an accelerator BODY
        self.device_class: Tuple[bool, ...] = ()
        self.has_cpu_bodies = False
        #: task id ``(class name, locals)`` -> position
        self.nodes: Dict[TaskId, int] = {}
        #: per task ``(class index, locals, priority, flow slots, value
        #: specs, home positions, write-backs, donated positions)``: one
        #: slot (or :data:`NO_DATA` / :data:`CTL_FLOW`) per declared flow,
        #: the ready-made ``("value", v, VALUE)`` specs of params then
        #: defs, ``_tpu_home``, ``(source slot, home slot)`` pairs, and
        #: ``_tpu_donate`` (:func:`_donations`)
        self.tasks: Tuple[Tuple, ...] = ()
        #: per task its successors' positions, one per captured edge
        #: (what ``_emit_trace_edges`` publishes)
        self.succs: Tuple[Tuple[int, ...], ...] = ()
        #: per slot ``("data", collection name, key)`` or ``("new",
        #: producer tid, flow name)``: ``source_tile``'s answers
        self.tiles: Tuple[Tuple, ...] = ()
        #: per slot the users a scratch tile is born with (0: has a home)
        self.tile_users: Tuple[int, ...] = ()
        #: the slots of the collection tiles that some task writes: what
        #: a pool composed BEFORE this one must not send home
        #: (``NativeExecutor._attach_members``)
        self.written: Tuple[int, ...] = ()
        #: per task its native node
        self.node_of: Tuple[int, ...] = ()
        #: per native node the task's position, or ``~i`` for
        #: ``fused[i]``
        self.native: Tuple[int, ...] = ()
        self.native_prio = (ctypes.c_int32 * 0)()
        #: the de-duplicated native edges, in declaration order
        self.edge_pred = (ctypes.c_int64 * 0)()
        self.edge_succ = (ctypes.c_int64 * 0)()
        self.roots: Tuple[int, ...] = ()
        #: per fused region ``(FusedPlan, slot per program argument,
        #: member positions, write-backs)``
        self.fused: Tuple[Tuple, ...] = ()
        #: when a tile is read NEXT (:func:`_next_uses`): per native
        #: node one entry per position of its ``body_args`` that may hold
        #: a tile (a task's declared flows, a fused region's program
        #: arguments), the node's at ``next_at[node]``: the rank of the
        #: next node that reads the tile behind that position,
        #: ``residency.NEVER``, or ``residency.UNKNOWN``
        self.next_use: Tuple[int, ...] = ()
        self.next_at: Tuple[int, ...] = ()

    def nbytes(self) -> int:
        """Bytes the plan keeps (containers and the small values in
        them; shared objects once)."""
        seen = set()

        def size(o) -> int:
            if id(o) in seen or isinstance(o, (type, AccessMode)):
                return 0
            seen.add(id(o))
            n = sys.getsizeof(o)
            if isinstance(o, dict):
                n += sum(size(k) + size(v) for k, v in o.items())
            elif isinstance(o, (tuple, list)):
                n += sum(size(x) for x in o)
            return n

        return sum(size(getattr(self, s)) for s in self.__slots__)


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

_AST_OK = (ast.Expression, ast.BoolOp, ast.BinOp, ast.UnaryOp, ast.Compare,
           ast.IfExp, ast.Call, ast.Name, ast.Constant, ast.Tuple,
           ast.Subscript, ast.Slice, ast.expr_context, ast.operator,
           ast.unaryop, ast.boolop, ast.cmpop)


@functools.lru_cache(maxsize=4096)
def _expr_asks(src: str) -> Optional[frozenset]:
    """The names whose methods the expression calls (``TREE.nextpiv(k,
    p, m)``: ``{"TREE"}``), when it is otherwise arithmetic over names:
    no other attribute, no other call but the evaluator's own builtins
    (an inline call may read anything).  None: nothing vouches for it.
    An object asked this way has to say what its answers are a function
    of (:func:`_const_fp`: ``plan_fingerprint``)."""
    try:
        tree = ast.parse(_c_to_py(src).strip(), mode="eval")
    except SyntaxError:
        return None
    asked, methods = set(), set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Call):
            f = n.func
            if n.keywords:
                return None
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                asked.add(f.value.id)
                methods.add(id(f))
            elif not (isinstance(f, ast.Name) and f.id in _SAFE_BUILTINS):
                return None
    for n in ast.walk(tree):
        if not isinstance(n, _AST_OK) and id(n) not in methods:
            return None
    return frozenset(asked)


def _body_fp(fn) -> Tuple:
    from .fusion import _body_fp as content_fp

    try:
        return (content_fp(fn), bool(getattr(fn, "_static_values", False)),
                repr(getattr(fn, "_donate_args", None)))
    except Exception as e:
        raise Uncacheable(f"body {fn!r}: {type(e).__name__}") from None


def _ptg_fp(ptg: PTG) -> Tuple[Tuple, set]:
    """The definition's text, every expression vouched for by
    :func:`_expr_asks`, and the names of the objects they ask."""
    asked: set = set()

    def src(e) -> Optional[str]:
        if e is None:
            return None
        names = _expr_asks(e.src)
        if names is None:
            raise Uncacheable(f"expression {e.src!r}")
        asked.update(names)
        return e.src

    def arg(a: _ArgExpr) -> Tuple:
        return (src(a.lo), src(a.hi), src(a.step))

    def dep(d) -> str:
        if not d.src:
            raise Uncacheable("a dependency without its source text")
        src(d.guard)
        for t in (d.then, d.otherwise):
            if isinstance(t, (_TaskRef, _DataRef)):
                for a in t.args:
                    arg(a)
        return d.src

    out: List[Tuple] = [("ptg", ptg.name)]
    for pc in ptg.classes.values():
        aff = pc._affinity
        out.append((
            pc.name,
            tuple((n, arg(e), p) for n, e, p in pc.decls),
            tuple((f.name, int(f.mode),
                   tuple(dep(d) for d in f.deps_in),
                   tuple(dep(d) for d in f.deps_out))
                  for f in pc.flows),
            src(pc._priority),
            None if aff is None else (
                aff.collection_name, tuple(arg(a) for a in aff.args)),
            tuple(sorted((dt, _body_fp(fn))
                         for dt, fn in pc.bodies.items())),
            tuple(pc.body_globals),
            tuple(sorted(pc.stage_hooks)), tuple(sorted(pc.chore_evaluate)),
            tuple(sorted((str(k), _const_fp(k, v))
                         for k, v in pc.properties.items()))))
    return tuple(out), asked


_PLAIN = (int, float, complex, str, bytes, bool, type(None))


def _collection_fp(dc) -> Optional[Tuple]:
    """What placement (and a ``NEW`` tile's default shape) reads of a
    collection of a known kind; None for any other object.  Exact types:
    a subclass may place its tiles by a rule of its own."""
    from ..data.collection import LocalCollection
    from ..datadist.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                   TwoDimBlockCyclic, VectorTwoDimCyclic)

    t = type(dc)
    if t in (TiledMatrix, TwoDimBlockCyclic, SymTwoDimBlockCyclic,
             VectorTwoDimCyclic):
        # (the precision map and where the tiles are born shape the
        # tasks' signatures and what a bind makes of a tile)
        return (t.__name__, dc.m, dc.n, dc.mb, dc.nb, dc.mt, dc.nt,
                np.dtype(dc.default_dtype).str, dc.uplo, dc.nodes, dc.myrank,
                tuple(getattr(dc, a, None) for a in ("p", "q", "kp", "kq")),
                dc.dtype_map(), dc.device_born)
    if t is LocalCollection:
        return (t.__name__, dc.tile_shape, np.dtype(dc.default_dtype).str,
                dc.nodes, dc.myrank)
    return None


def _const_fp(name, v) -> Tuple:
    t = type(v)
    if t in _PLAIN:
        return (t.__name__, v)
    if t is tuple:
        return ("tuple",) + tuple(_const_fp(name, x) for x in v)
    if isinstance(v, np.generic):
        return ("np", v.dtype.str, v.item())
    if isinstance(v, np.dtype):
        return ("dtype", v.str)
    if isinstance(v, type):
        return ("type", v.__module__, v.__qualname__)
    fp = _collection_fp(v)
    if fp is None:
        # an object the expressions ask (a reduction tree) vouches for
        # itself: everything its answers depend on
        own = getattr(t, "plan_fingerprint", None)
        if own is None:
            raise Uncacheable(f"constant {name!r} ({t.__name__})")
        fp = ("asks", t.__module__, t.__qualname__) + tuple(own(v))
    return fp


def plan_key(tp, ranks: Iterable[int], fusion: Tuple,
             window: Tuple[int, int]) -> Tuple:
    """The fingerprint of everything a captured, partitioned and
    resolved graph of ``tp`` is a function of (``window``: the pump's
    batches, which rank the tasks: :func:`build_plan`); raises
    :class:`Uncacheable` where it cannot vouch for some part."""
    ptg_fp, asked = _ptg_fp(tp.ptg)
    for name in sorted(asked):
        v = tp.constants.get(name)
        if getattr(type(v), "plan_fingerprint", None) is None:
            raise Uncacheable(f"a method of {name!r} "
                              f"({type(v).__name__}) is called")
    return ("attach-plan-3", ptg_fp,
            tuple(sorted((str(k), _const_fp(k, v))
                         for k, v in tp.constants.items())),
            tuple(sorted(ranks)), fusion, tuple(window))


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

_plans: "collections.OrderedDict[Tuple, AttachPlan]" = \
    collections.OrderedDict()
_plans_lock = threading.Lock()


def lookup(key: Tuple) -> Optional[AttachPlan]:
    with _plans_lock:
        plan = _plans.get(key)
        if plan is not None:
            _plans.move_to_end(key)
        return plan


def store(plan: AttachPlan) -> None:
    with _plans_lock:
        _plans[plan.key] = plan
        _plans.move_to_end(plan.key)
        while len(_plans) > PLAN_CACHE_SIZE:
            _plans.popitem(last=False)


def clear() -> None:
    """Forget every stored plan (tests; a process that is done with a
    family of shapes)."""
    with _plans_lock:
        _plans.clear()


def stored() -> List[AttachPlan]:
    with _plans_lock:
        return list(_plans.values())


# ---------------------------------------------------------------------------
# capture -> plan
# ---------------------------------------------------------------------------

_VALUE = AccessMode.VALUE


def build_plan(tp, g: TaskGraph, regions=(),
               window: Optional[Tuple[int, int]] = PUMP_WINDOW) -> AttachPlan:
    """Resolve the captured graph ``g`` of ``tp`` (and its fused
    ``regions``) into a plan: every per-task fact the bind needs, with
    flows resolved to tile slots, the contracted native graph, and when
    each tile is read next in the order a pump of ``window = (batch,
    depth)`` runs that graph (``native_exec._pump_window``; None: a
    plan without that table, whose tiles' next uses nobody knows)."""
    ptg_classes = tp.ptg.classes
    consts = tp.constants
    plan = AttachPlan()
    plan.ptg_name = tp.ptg.name
    order = list(g.nodes)
    nodes = plan.nodes = {tid: i for i, tid in enumerate(order)}

    tiles: List[Tuple] = []
    users: List[int] = []
    slot_of: Dict[Tuple, int] = {}

    def slot(srckey: Tuple, use: int = 0) -> int:
        """The slot of a resolved chain, with ``use`` more users of it
        where it is a scratch tile."""
        if srckey[0] == "remote":
            # a chain that leaves the captured partition: this single-
            # rank executor cannot resolve it
            raise RuntimeError(
                f"flow source {srckey[1]}/{srckey[2]} is on another rank; "
                "use NativeDistExecutor for rank-filtered captures")
        s = slot_of.get(srckey)
        if s is None:
            s = slot_of[srckey] = len(tiles)
            tiles.append(srckey)
            users.append(0)
        if srckey[0] == "new":
            users[s] += use
        return s

    # per class: index, flow table and which flows a successor rewrites
    cls_index: Dict[str, int] = {}
    cls_flows: Dict[str, Tuple] = {}
    flow_mode: Dict[Tuple[str, str], Any] = {}
    for name, pc in ptg_classes.items():
        for f in pc.flows:
            flow_mode[(name, f.name)] = f.mode
    device_class: List[bool] = []

    region_of = {m: r for r in regions for m in r.members}
    rows: List[Tuple] = []
    succs: List[Tuple[int, ...]] = []
    wbs_of: List[Tuple] = []
    #: per task the flows whose OUTPUT version somebody besides its task
    #: readers holds: it goes home, or a cross-tile write-back reads it
    kept_of: Dict[TaskId, set] = {}
    for tid in order:
        cname, locs = tid
        pc = ptg_classes[cname]
        ci = cls_index.get(cname)
        if ci is None:
            ci = cls_index[cname] = len(cls_index)
            cls_flows[cname] = tuple(pc.flows)
            device_class.append(any(dt != DEV_CPU for dt in pc.bodies))
            if not pc.bodies:
                raise ValueError(f"native_exec: class {cname} has no body")
        node = g.nodes[tid]
        fused = tid in region_of
        # a task's own arguments use its scratch tiles once each; a
        # fused member's are the region's (declared below, per slot)
        use = 0 if fused else 1
        flows = []
        for f in cls_flows[cname]:
            if f.mode == CTL:
                flows.append(CTL_FLOW)
            elif node.flow_sources.get(f.name) is None \
                    and not (f.mode & AccessMode.OUT):
                flows.append(NO_DATA)
            else:
                flows.append(slot(source_tile(g, tid, f.name), use))
        env = pc.env_of(locs, consts) if pc.def_names else None
        values = tuple(("value", v, _VALUE) for v in locs) + tuple(
            ("value", env[n], _VALUE) for n in pc.def_names)
        # the outputs that go home: declared written back and taken on
        # by no successor that writes the tile again
        rewritten = {f for (f, succ, sf) in node.out_edges
                     if flow_mode[(succ[0], sf)] & AccessMode.OUT}
        home_names = {f for (f, _c, _k) in node.write_backs} - rewritten
        home = tuple(f.index for f in cls_flows[cname]
                     if f.name in home_names)
        # cross-tile write-backs (the chain's source is not the home
        # tile): the source is read after the task's epilog, so it is
        # one more user, never released
        wbs = []
        for (fname, cname2, key) in node.write_backs:
            src = source_tile(g, tid, fname)
            hkey = ("data", cname2, tuple(key))
            if src != hkey:
                wbs.append((slot(src, use), slot(hkey)))
                home_names.add(fname)
        kept_of[tid] = home_names
        wbs_of.append(tuple(wbs))
        rows.append((ci, locs, node.priority, tuple(flows), values, home,
                     () if fused else tuple(wbs)))
        succs.append(tuple(nodes[s] for (_f, s, _sf) in node.out_edges))
    donated = _donations(g, order, cls_flows, flow_mode, kept_of)
    rows = [row + (() if tid in region_of else donate,)
            for row, tid, donate in zip(rows, order, donated)]
    plan.classes = tuple(cls_index)
    plan.device_class = tuple(device_class)
    plan.has_cpu_bodies = not all(device_class)
    plan.tasks = tuple(rows)
    plan.succs = tuple(succs)

    # native nodes: one a task, ONE a fused region (at its first member)
    node_of: List[int] = []
    native: List[int] = []
    prio: List[int] = []
    fused_rows: List[Tuple] = []
    region_node: Dict[int, int] = {}
    for i, tid in enumerate(order):
        reg = region_of.get(tid)
        if reg is None:
            node_of.append(len(native))
            native.append(i)
            prio.append(rows[i][2])
            continue
        nid = region_node.get(reg.index)
        if nid is None:
            nid = region_node[reg.index] = len(native)
            native.append(~len(fused_rows))
            prio.append(max(g.nodes[m].priority for m in reg.members))
            fused_rows.append(
                _fused_row(tp, g, reg, slot, tiles, nodes, wbs_of))
        node_of.append(nid)
    plan.node_of = tuple(node_of)
    plan.native = tuple(native)
    plan.fused = tuple(fused_rows)
    plan.tiles = tuple(tiles)
    plan.tile_users = tuple(users)
    out = int(AccessMode.OUT)
    plan.written = tuple(sorted({
        s for tid, row in zip(order, rows)
        for s, f in zip(row[3], cls_flows[tid[0]])
        if s >= 0 and int(f.mode) & out and tiles[s][0] == "data"}))

    # contracted edges are DEDUPLICATED: add_dep is symmetric (one
    # in-degree per declared edge, one release per succs entry), so
    # collapsing parallel region->target edges to one stays balanced
    # while shaving native succs slots and atomic releases
    pred: List[int] = []
    succ: List[int] = []
    seen = set()
    has_pred = set()
    for i in range(len(order)):
        me = node_of[i]
        for s in succs[i]:
            tgt = node_of[s]
            if tgt == me:
                continue  # intra-region edge: runs inside the program
            if regions and (me, tgt) in seen:
                continue
            seen.add((me, tgt))
            pred.append(me)
            succ.append(tgt)
            has_pred.add(tgt)
    plan.native_prio = (ctypes.c_int32 * len(prio))(*prio)
    plan.edge_pred = (ctypes.c_int64 * len(pred))(*pred)
    plan.edge_succ = (ctypes.c_int64 * len(succ))(*succ)
    plan.roots = tuple(n for n in range(len(native)) if n not in has_pred)

    if window is None:
        return plan
    # when each tile is read next: per native node its tile positions'
    # (slot, the flow reads it, the node's class runs on the device)
    def reads(mode) -> bool:
        return int(mode) & int(AccessMode.INOUT) != int(AccessMode.OUT)

    cls_reads = [tuple(reads(f.mode) for f in cls_flows[cname])
                 for cname in plan.classes]
    touches: List[List[Tuple[int, bool, bool]]] = []
    for pos in native:
        if pos < 0:
            fp, slots = fused_rows[~pos][:2]
            touches.append([(s, reads(m), True)
                            for s, m in zip(slots, fp.slot_modes)])
        else:
            ci, flows = rows[pos][0], rows[pos][3]
            on_device = device_class[ci]
            touches.append([(s, r, on_device)
                            for s, r in zip(flows, cls_reads[ci])])
    plan.next_use, plan.next_at = _next_uses(
        touches, _pump_rank(prio, pred, succ, plan.roots, *window),
        len(tiles))
    return plan


def _donations(g: TaskGraph, order: List[TaskId], cls_flows, flow_mode,
               kept_of) -> List[Tuple[int, ...]]:
    """Per task (in ``order``) its ``_tpu_donate``: the positions of the
    read-write flows whose INPUT version the task is the only consumer
    of, so that a device program may write the flow's output over it.

    A version is born where a task WRITES a flow (or is the tile as its
    collection holds it) and is read by whoever names that flow as the
    source of one of its own.  The task is its only consumer when the
    producer's flow has this one task reader — one captured edge, none
    that leaves a rank-filtered capture — the producer neither sends the
    version home nor lands it in another tile (``kept_of``: of a chain's
    write-back only the LAST version goes home, the one no successor
    rewrites), and the producer wrote it (a flow it only read forwards a
    version its own producer's other readers share).  A tile's first
    version counts when one flow in the whole graph names the
    collection's tile as its source.  What the graph cannot know — who
    else holds the array on the device — is the staging walk's to check
    (``TpuDevice._not_sole``)."""
    inout = int(AccessMode.INOUT)
    out = int(AccessMode.OUT)
    first: Dict[Tuple, int] = collections.Counter(
        src for node in g.nodes.values()
        for src in node.flow_sources.values()
        if src is not None and src[0] == "data")
    readers = {tid: collections.Counter(f for (f, _s, _sf) in node.out_edges)
               for tid, node in g.nodes.items()}
    donated: List[Tuple[int, ...]] = []
    for tid in order:
        node = g.nodes[tid]
        mine = []
        for f in cls_flows[tid[0]]:
            if f.mode == CTL or int(f.mode) & inout != inout:
                continue
            src = node.flow_sources.get(f.name)
            if src is None or src[0] == "new":
                continue
            if src[0] == "data":
                sole = first[src] == 1
            else:
                _, ptid, pflow = src
                prod = g.nodes.get(ptid)
                sole = (prod is not None and not prod.remote_out
                        and readers[ptid][pflow] == 1
                        and int(flow_mode[(ptid[0], pflow)]) & out
                        and pflow not in kept_of[ptid])
            if sole:
                mine.append(f.index)
        donated.append(tuple(mine))
    return donated


def _pump_rank(prio: List[int], pred: List[int], succ: List[int],
               roots: Iterable[int], batch: int, depth: int) -> List[int]:
    """Per native node its place in the order the pump runs the graph
    (``native_exec._pump_loop``), replayed: up to ``depth`` batches of
    up to ``batch`` ready nodes are popped — the highest priority first,
    the one ready longest among equals (the native ``SchedQ``'s ``prio``
    discipline, ``native/src/graph.cpp``) — before the oldest batch
    runs and releases its successors.  On one device the pump's order is
    a function of the graph and these two numbers alone.  (Replayed one
    node at a time, the priority-greedy linearisation, a ready frontier
    drains in another order than the pump's: 45% more evictions in the
    out-of-core dpotrf, ``PERF.md`` §6, PR 33.)"""
    n = len(prio)
    succs: List[List[int]] = [[] for _ in range(n)]
    missing = [0] * n
    for a, b in zip(pred, succ):
        succs[a].append(b)
        missing[b] += 1
    ready = [(-prio[r], k, r) for k, r in enumerate(roots)]
    heapq.heapify(ready)
    seq = len(ready)
    rank = [0] * n
    at = 0
    window: "collections.deque[List[int]]" = collections.deque()
    while True:
        while ready and len(window) < depth:
            window.append([heapq.heappop(ready)[2]
                           for _ in range(min(batch, len(ready)))])
        if not window:
            return rank
        nodes = window.popleft()
        for node in nodes:
            rank[node] = at
            at += 1
        for node in nodes:
            for s in succs[node]:
                missing[s] -= 1
                if not missing[s]:
                    heapq.heappush(ready, (-prio[s], seq, s))
                    seq += 1


def _next_uses(touches: List[List[Tuple[int, bool, bool]]], rank: List[int],
               nslots: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """``(next_use, next_at)`` of a plan.  ``touches``: per native node,
    per position of its ``body_args`` that may hold a tile, ``(slot,
    the node reads it, the node runs on the device)``; ``rank``: per node
    its place in the order the nodes tend to run.

    The next use of a tile after a node is the rank of the next node, in
    rank order, that touches its slot — :data:`NEVER` where there is
    none, or where that node only overwrites it (the version here is
    dead by then).  A slot that a CPU body touches is :data:`UNKNOWN`
    throughout: such a task never passes the device's staging walk, so
    nothing would move the tile's next use past it."""
    #: per slot its readers and writers as (rank, reads)
    users: List[List[Tuple[int, bool]]] = [[] for _ in range(nslots)]
    host_slots = set()
    for node, flows in enumerate(touches):
        for s, reads, on_device in flows:
            if s < 0:
                continue
            users[s].append((rank[node], reads))
            if not on_device:
                host_slots.add(s)
    #: per slot, rank -> what follows it
    after: List[Dict[int, int]] = []
    for s, us in enumerate(users):
        nxt: Dict[int, int] = {}
        if s not in host_slots:
            # (a node that touches a slot by two flows reads it if either
            # does: from the far end its reading flow comes first)
            us.sort()
            follows, at, at_reads = NEVER, -1, False
            for r, reads in reversed(us):
                if r != at:
                    if at >= 0:
                        follows = at if at_reads else NEVER
                    at, at_reads = r, reads
                    nxt[r] = follows
        after.append(nxt)
    next_use: List[int] = []
    next_at: List[int] = []
    for node, flows in enumerate(touches):
        next_at.append(len(next_use))
        r = rank[node]
        next_use.extend(after[s].get(r, UNKNOWN) if s >= 0 else UNKNOWN
                        for s, _reads, _dev in flows)
    return tuple(next_use), tuple(next_at)


def _fused_row(tp, g: TaskGraph, region, slot, tiles, nodes,
               wbs_of) -> Tuple:
    """One fused region: its program (:class:`..dsl.fusion.FusedPlan`,
    which keeps no taskpool), a slot per program argument, and the
    cross-tile write-backs of EVERY member, landed at the one
    completion; per home tile only the LAST member's landing survives
    (earlier ones would be superseded anyway)."""
    from .fusion import FusedPlan

    fp = FusedPlan(tp, g, region)
    slots = []
    for key in fp.slot_keys:
        if key[0] == "ext":
            # ("ext", producer tid, producer flow): the producer's
            # threaded tile, as its own dispatch would resolve it
            key = source_tile(g, key[1], key[2])
        slots.append(slot(tuple(key), 1))
    members = tuple(nodes[m] for m in region.members)
    last: Dict[int, Tuple[int, int]] = {}
    for m in members:
        for (src, home) in wbs_of[m]:
            last[home] = (src, home)
    for (src, _home) in last.values():
        slot(tiles[src], 1)  # read after the epilog: never released
    return (fp, tuple(slots), members, tuple(last.values()))
