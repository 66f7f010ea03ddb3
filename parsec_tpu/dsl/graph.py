"""Static task-graph capture for PTG taskpools.

The reference never materialises the whole DAG — it is implicit in the
generated ``iterate_successors`` code.  Capturing it explicitly enables
three subsystems that the reference implements as separate machinery:

* the ``iterators_checker`` PINS module
  (``/root/reference/parsec/mca/pins/iterators_checker/``) — validating at
  runtime that released successors match the declared dependencies;
* the ``ptg_to_dtd`` PINS module (``mca/pins/ptg_to_dtd/``) — replaying a
  PTG taskpool through the DTD engine as a DSL-equivalence harness;
* the whole-DAG XLA lowering (TPU-native: compile the entire tile DAG into
  one jitted program — the analogue of CUDA-graph capture, but done by the
  XLA compiler with full fusion/overlap freedom).

Capture cost is O(tasks + edges) expression evaluations (284,000
``eval`` calls for the 11,440 tasks of a tile QR at NT=32).  It began as
a test/lowering tool; since the pump path (``dsl/native_exec.py``) it
stood at the head of every solve, and since the attach plan
(``dsl/attach_plan.py``) at the head of every FIRST solve of a shape: a
later solve of that shape binds the stored plan and captures nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

from ..core.lifecycle import AccessMode
from .ptg import (
    CTL,
    PTGTaskClass,
    PTGTaskpool,
    _DataRef,
    _NewRef,
    _NoneRef,
    _TaskRef,
    _expand_args,
)

TaskId = Tuple[str, Tuple]  # (class name, locals)


class PTGDefinitionView:
    """Duck-typed stand-in for a ``PTGTaskpool`` carrying only what
    :func:`capture` reads (``.ptg`` and ``.constants``) — lets the static
    verifier capture a bare PTG definition against concrete globals
    without instantiating a taskpool (no dep trackers, repos, taskpool
    ids, or MCA parameter registration)."""

    __slots__ = ("ptg", "constants")

    def __init__(self, ptg, constants: Dict[str, Any]):
        self.ptg = ptg
        self.constants = dict(constants)


class TaskNode:
    __slots__ = ("tid", "priority", "rank", "in_edges", "out_edges",
                 "flow_sources", "write_backs", "remote_out")

    def __init__(self, tid: TaskId, priority: int, rank: int):
        self.tid = tid
        self.priority = priority
        self.rank = rank
        #: flow name -> ("data", collection_name, key) | ("task", producer
        #: tid, producer flow) | ("new",) | None
        self.flow_sources: Dict[str, Optional[Tuple]] = {}
        #: (flow name, collection name, key) final write-backs
        self.write_backs: List[Tuple[str, str, Tuple]] = []
        #: edges as (my flow, successor tid, successor flow)
        self.out_edges: List[Tuple[str, TaskId, str]] = []
        #: predecessor count (dependency goal)
        self.in_edges: int = 0
        #: successor edges leaving a rank-filtered capture (valid tasks
        #: placed on OTHER ranks).  Invisible in ``out_edges``, but
        #: load-bearing for consumers reasoning about convexity — the
        #: fusion partitioner must not bury a mid-chain remote forward
        #: (ring attention's K/V rotation) inside a fused region
        self.remote_out: int = 0


class TaskGraph:
    def __init__(self, tp: PTGTaskpool):
        self.taskpool = tp
        self.nodes: Dict[TaskId, TaskNode] = {}

    def successors(self, tid: TaskId) -> List[TaskId]:
        return [s for (_f, s, _sf) in self.nodes[tid].out_edges]

    def topo_order(self) -> List[TaskId]:
        """Kahn topological order, priority-aware among ready nodes.
        Large DAGs run through the native C++ engine when available."""
        try:
            from .. import native

            if native.available() and len(self.nodes) > 256:
                return self._topo_order_native(native)
        except Exception:
            pass
        import heapq

        indeg = {tid: n.in_edges for tid, n in self.nodes.items()}
        seq = 0  # tie-break: insertion order keeps the heap deterministic
        heap = []
        for tid, d in indeg.items():
            if d == 0:
                heap.append((-self.nodes[tid].priority, seq, tid))
                seq += 1
        heapq.heapify(heap)
        out: List[TaskId] = []
        while heap:
            _, _, tid = heapq.heappop(heap)
            out.append(tid)
            # in_edges (goal_of) counts one per declared dep instance, which
            # is exactly how out_edges are enumerated — decrement per edge
            for (_f, succ, _sf) in self.nodes[tid].out_edges:
                indeg[succ] -= 1
                if indeg[succ] == 0:
                    heapq.heappush(heap, (-self.nodes[succ].priority, seq, succ))
                    seq += 1
        if len(out) != len(self.nodes):
            stuck = [t for t, d in indeg.items() if d > 0]
            raise RuntimeError(f"task graph has a cycle or broken deps: stuck={stuck[:5]}")
        return out

    def _topo_order_native(self, native) -> List[TaskId]:
        g = native.NativeGraph()
        tids = list(self.nodes)
        index = {}
        for i, tid in enumerate(tids):
            index[tid] = g.add_task(priority=self.nodes[tid].priority)
        for tid in tids:
            me = index[tid]
            for (_f, succ, _sf) in self.nodes[tid].out_edges:
                g.add_dep(me, index[succ])
        try:
            order = g.order()
        except RuntimeError as e:
            raise RuntimeError(f"task graph has a cycle or broken deps: {e}") from e
        finally:
            g.close()
        return [tids[i] for i in order]


def find_cycle(g: TaskGraph) -> List[TaskId]:
    """One concrete dependency cycle of the captured DAG, or ``[]`` when
    the graph is acyclic.  Runs Kahn first (cheap), then walks the
    leftover subgraph — every node surviving peeling sits on or behind a
    cycle, so an iterative DFS from any of them must close one."""
    indeg = {tid: n.in_edges for tid, n in g.nodes.items()}
    frontier = [tid for tid, d in indeg.items() if d == 0]
    while frontier:
        tid = frontier.pop()
        for (_f, succ, _sf) in g.nodes[tid].out_edges:
            indeg[succ] -= 1
            if indeg[succ] == 0:
                frontier.append(succ)
    stuck = {tid for tid, d in indeg.items() if d > 0}
    if not stuck:
        return []
    # every stuck node has at least one stuck PREDECESSOR (its residual
    # in-degree comes from an unpeeled producer), so walking predecessors
    # always closes a cycle — stuck SUCCESSORS need not exist (a node
    # merely downstream of a cycle is stuck too, and may be a sink)
    pred: Dict[TaskId, TaskId] = {}
    for tid in stuck:
        for (_f, succ, _sf) in g.nodes[tid].out_edges:
            if succ in stuck and succ not in pred:
                pred[succ] = tid
    path: List[TaskId] = []
    on_path: Dict[TaskId, int] = {}
    tid = min(stuck)  # deterministic pick
    while tid not in on_path:
        on_path[tid] = len(path)
        path.append(tid)
        tid = pred[tid]
    cycle = path[on_path[tid]:]
    cycle.reverse()  # predecessor walk found it backwards
    return cycle


def capture(tp: PTGTaskpool, ranks: Optional[Iterable[int]] = None) -> TaskGraph:
    """Evaluate every task's dependency expressions and materialise the DAG.

    ``ranks=None`` captures all tasks; otherwise only tasks whose affinity
    maps into ``ranks`` (matching each rank's local view).
    """
    g = TaskGraph(tp)
    consts = tp.constants
    rankset = set(ranks) if ranks is not None else None

    # pass 1: nodes — also record the GLOBAL placement map (every valid
    # task's rank), which distributed consumers (native_dist's remote-
    # edge planner) would otherwise re-derive with a second full
    # param-space scan
    g.global_ranks = {}
    for pc in tp.ptg.classes.values():
        for loc in pc.param_space(consts):
            rank = pc.rank_of(loc, consts)
            g.global_ranks[(pc.name, loc)] = rank
            if rankset is not None and rank not in rankset:
                continue
            tid = (pc.name, loc)
            g.nodes[tid] = TaskNode(tid, pc.priority_of(loc, consts), rank)

    # pass 2: edges + sources (driven from each node's own deps)
    for tid, node in g.nodes.items():
        pc = tp.ptg.classes[tid[0]]
        loc = tid[1]
        env = pc.env_of(loc, consts)
        for f in pc.flows:
            # input source (a CTL flow carries no data and may gather
            # over ranges: its predecessors' output deps are its edges)
            src = None if f.mode == CTL else pc.active_input(f, env)
            if src is None or isinstance(src, _NoneRef):
                node.flow_sources[f.name] = ("new",) if (f.mode & AccessMode.OUT) else None
            elif isinstance(src, _NewRef):
                node.flow_sources[f.name] = ("new",)
            elif isinstance(src, _DataRef):
                node.flow_sources[f.name] = ("data", src.collection_name, src.key(env))
            else:  # _TaskRef
                key = tuple(a.scalar(env) for a in src.args)
                if (src.class_name, key) not in g.global_ranks:
                    # out-of-range producer reference: the input does not
                    # exist (reference complex_deps off-diagonal corner)
                    node.flow_sources[f.name] = \
                        ("new",) if (f.mode & AccessMode.OUT) else None
                else:
                    node.flow_sources[f.name] = (
                        "task", (src.class_name, key), src.flow_name)
            # output edges
            for dep in f.deps_out:
                t = dep.target(env)
                if t is None or isinstance(t, (_NoneRef, _NewRef)):
                    continue
                if isinstance(t, _DataRef):
                    node.write_backs.append((f.name, t.collection_name, t.key(env)))
                    continue
                succ_pc = tp.ptg.classes[t.class_name]
                for locs in _expand_args(t.args, env):
                    if len(locs) != len(succ_pc.param_names):
                        continue
                    # membership in g.nodes subsumes valid(): pass 1
                    # built the node set FROM the class param spaces
                    stid = (t.class_name, locs)
                    if stid in g.nodes:
                        node.out_edges.append((f.name, stid, t.flow_name))
                    elif stid in g.global_ranks:
                        # valid successor on another rank: count it so
                        # rank-filtered consumers see the true out-degree
                        node.remote_out += 1

    # pass 3: in-degrees tallied from the captured edges (NOT goal_of: a
    # rank-filtered capture must count only edges whose producer is in the
    # capture, or the topological order could never retire cross-rank
    # consumers; remote releases arrive outside this subgraph)
    for node in g.nodes.values():
        for (_f, succ, _sf) in node.out_edges:
            g.nodes[succ].in_edges += 1
    return g


def source_tile(g: TaskGraph, tid: TaskId, flow_name: str):
    """Follow a flow's input chain to its ultimate memory source.

    Returns ``("data", collection_name, key)`` or ``("new", producer_tid,
    flow)`` — the identity that aliases across the producer/consumer chain
    (PTG flows thread one datum through in-place bodies).

    Memoized with path compression on the graph (long dpotrf-style
    chains are walked once, not once per consumer); callers resolve
    sources only AFTER capture completes, so the memo never observes a
    half-built graph.
    """
    memo = g.__dict__.setdefault("_src_memo", {})
    key = (tid, flow_name)
    hit = memo.get(key)
    if hit is not None:
        return hit
    seen = set()
    path = []
    cur, cflow = tid, flow_name
    while True:
        if (cur, cflow) in seen:
            raise RuntimeError(f"cyclic flow chain at {cur}/{cflow}")
        seen.add((cur, cflow))
        path.append((cur, cflow))
        hit = memo.get((cur, cflow))
        if hit is not None:
            break
        src = g.nodes[cur].flow_sources.get(cflow)
        if src is None or src[0] == "new":
            hit = ("new", cur, cflow)
            break
        if src[0] == "data":
            hit = src
            break
        _, ptid, pflow = src
        if ptid not in g.nodes:
            # the chain leaves a rank-filtered capture: the flow's value
            # arrives from a REMOTE producer (native_dist resolves these
            # from deposited activation payloads)
            hit = ("remote", ptid, pflow)
            break
        cur, cflow = ptid, pflow
    for k in path:
        memo[k] = hit
    return hit
