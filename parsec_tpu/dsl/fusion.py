"""Supertask fusion: automatic granularity coarsening over the captured
static graph — the missing middle between per-task dispatch and
whole-DAG capture.

Every dispatch-bound number in the trajectory points at task
granularity: per-task dynamic dispatch pays ~0.5 ms/task of host-side
bookkeeping (round-5 wave A/B on the chip) and the task-graph flash attention ran at
0.40x of the one-program SPMD loop (round 11), while whole-DAG
``GraphExecutor`` capture forfeits multi-pool composition, serving, and
comm overlap.  This module adds the middle regime, in the spirit of
"Design in Tiles" (auto-selected granularity per target) and AXI4MLIR
(host-dispatch amortization as the first-order offload lever):

* :func:`partition` groups **convex, same-device regions** of the
  captured :class:`~parsec_tpu.dsl.graph.TaskGraph` into *supertasks* —

  - **linear carry chains**: maximal paths where every interior member
    has exactly ONE distinct successor (the attention ``(g, i)``
    online-softmax chain over ``s``, dpotrf syrk/gemm panel chains).
    That single-successor rule is what makes a chain convex *and*
    deadlock-free by construction: every path out of the region leaves
    from its last member, so a cross-region cycle would imply a cycle
    in the original DAG;
  - **independent same-class waves**: same class, same dependency
    level (longest path from a source) — level-equal tasks can have no
    path between them, so the region is convex and region-to-region
    edges strictly increase levels;

* :class:`FusedPlan` lowers a region to ONE jitted program (unrolled
  dataflow via the same step machinery as ``dsl/xla_lower.py``, or a
  ``lax.scan`` for uniform chains), compiled through the PR-7
  :class:`~parsec_tpu.compile_cache.ExecutableCache` under a content key
  of member body fingerprints + region shape — a second process reloads
  the serialized executable instead of re-tracing;

* the runtimes dispatch each region as ONE ASYNC chore: the dynamic
  PTG runtime through a synthetic supertask task class
  (``dsl/ptg.py``), the native engine as one native node whose
  completion signals ``pz_task_done`` once for N member tasks
  (``dsl/native_exec.py``).  Edges crossing a region boundary stay
  ordinary runtime dependencies — remote deps, collectives, priorities
  and multi-pool fairness are untouched, and ring attention's
  fabric-overlapped K/V rotation stays OUTSIDE the fused regions (an
  interior member may not forward data mid-chain; the partitioner's
  single-successor rule rejects exactly those nodes).

MCA knobs (framework ``runtime``):

* ``runtime_fusion`` = ``off`` (default) | ``auto`` | ``chains`` |
  ``waves`` — what the partitioner may fuse.  ``auto`` fuses both and
  consults the PR-7 :class:`~parsec_tpu.tuning.TuningStore` for the
  fusion horizon (op ``fusion``, param ``max_tasks``) so the
  granularity is autotunable per device generation;
* ``runtime_fusion_scan`` = ``auto`` | ``off`` | ``on`` — lower uniform
  chains as one ``lax.scan`` instead of unrolling (compile time O(1)
  in chain length); ``auto`` requires equal member shapes.

Like every whole-graph consumer (``GraphExecutor``, ``run_native``,
ptg→dtd), fusion requires a statically-capturable graph: dynamic guards
whose truth changes while the pool runs must not alter membership.
"""

from __future__ import annotations

import hashlib
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..core.lifecycle import AccessMode, DEV_CPU
from ..utils import debug, mca_param
from .graph import TaskGraph

CTL = AccessMode.CTL

#: body -> content fingerprint, shared across EVERY plan build (weak
#: keys — the device module's _body_fp comment explains why id() keys
#: are a correctness bug); region digests re-fingerprint the same few
#: class bodies hundreds of times otherwise
_body_fp_memo: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _body_fp(body) -> str:
    from ..compile_cache import code_fingerprint

    try:
        fp = _body_fp_memo.get(body)
    except TypeError:
        return code_fingerprint(body)
    if fp is None:
        fp = code_fingerprint(body)
        try:
            _body_fp_memo[body] = fp
        except TypeError:
            pass
    return fp

#: fusion horizon (max member tasks per fused region) used when the
#: tuning store has no entry for this device generation
DEFAULT_HORIZON = 16
#: minimum uniform-chain length worth rolling into a lax.scan
SCAN_MIN = 4

TaskId = Tuple[str, Tuple]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def fusion_mode() -> str:
    """Resolved ``runtime_fusion`` MCA value."""
    return str(mca_param.register(
        "runtime", "fusion", "off",
        choices=["off", "auto", "chains", "waves"], level=3,
        help="supertask fusion over captured graphs: off | auto (chains "
             "+ waves, tuning-store horizon) | chains | waves"))


def fusion_max_tasks(device=None) -> int:
    """Region-size horizon: the tuning store's per-device-generation
    entry, or :data:`DEFAULT_HORIZON`."""
    try:
        from .. import tuning

        got = tuning.resolve_nb("fusion", 0, "any", device=device,
                                param="max_tasks",
                                default=DEFAULT_HORIZON)
        return int(got or DEFAULT_HORIZON)
    except Exception:
        return DEFAULT_HORIZON


def fusion_scan_mode() -> str:
    return str(mca_param.register(
        "runtime", "fusion_scan", "auto",
        choices=["auto", "off", "on"], level=5,
        help="lower uniform fused chains as one lax.scan (auto: only "
             "when member shapes are provably equal)"))


def class_fusible(pc) -> bool:
    """Is a PTG task class eligible for device-fused regions?  It must
    carry an accelerator BODY free of per-task device specializations
    (static-value baking, donation, custom staging) and declare no
    input-side reshape properties — the fused program resolves dataflow
    itself and cannot replay those hooks per member."""
    accel = [(dt, fn) for dt, fn in pc.bodies.items() if dt != DEV_CPU]
    if not accel:
        return False
    _dt, fn = accel[0]
    if getattr(fn, "_static_values", False) or \
            getattr(fn, "_donate_args", None):
        return False
    if pc.stage_hooks:
        return False
    from .ptg import _NewRef

    for f in pc.flows:
        for dep in f.deps_in:
            if dep.props and not (isinstance(dep.then, _NewRef)
                                  or isinstance(dep.otherwise, _NewRef)):
                return False  # input reshape request: per-task machinery
    return True


def class_device_type(pc) -> Optional[str]:
    for dt in pc.bodies:
        if dt != DEV_CPU:
            return dt
    return None


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------

class Region:
    """One fused region: topologically-ordered member task ids."""

    __slots__ = ("index", "kind", "members", "member_set")

    def __init__(self, index: int, kind: str, members: List[TaskId]):
        self.index = index
        self.kind = kind  # "chain" | "wave"
        self.members = list(members)
        self.member_set: Set[TaskId] = set(members)

    def __repr__(self) -> str:
        return (f"Region#{self.index}({self.kind}, {len(self.members)} "
                f"tasks: {self.members[0]}..{self.members[-1]})")


def _distinct_succs(node) -> Set[TaskId]:
    return {s for (_f, s, _sf) in node.out_edges}


def region_source(g: TaskGraph, member_set: Set[TaskId], tid: TaskId,
                  fname: str) -> Tuple:
    """Identity of a member flow's value at the REGION boundary: walk the
    flow chain while it stays inside the region.  Returns
    ``("data", cname, key)`` / ``("new", creator_tid, flow)`` /
    ``("ext", producer_tid, producer_flow)`` — the key that both dedups
    program I/O slots and resolves to one backing ``Data`` (PTG threads
    one datum through a flow chain, so equal keys mean equal tiles)."""
    cur, cf = tid, fname
    while True:
        src = g.nodes[cur].flow_sources.get(cf)
        if src is None or src[0] == "new":
            return ("new", cur, cf)
        if src[0] == "data":
            return ("data", src[1], tuple(src[2]))
        _, ptid, pflow = src
        if ptid not in member_set:
            return ("ext", ptid, pflow)
        cur, cf = ptid, pflow


def _writeback_safe(g: TaskGraph, classes, members: List[TaskId]) -> int:
    """Longest safe prefix of a candidate chain: a member with a
    write-back (or a data-ref output) must be the LAST region writer of
    that tile, or the dynamic runtime's intermediate write-back would be
    superseded differently than the fused program's final commit.
    Returns the length of the longest prefix with no violation."""
    n = len(members)
    while n >= 2:
        pref = members[:n]
        pset = set(pref)
        last_writer: Dict[Tuple, int] = {}
        for mi, tid in enumerate(pref):
            pc = classes[tid[0]]
            for f in pc.flows:
                if f.mode == CTL or not (f.mode & AccessMode.OUT):
                    continue
                key = region_source(g, pset, tid, f.name)
                last_writer[key] = mi
            for (fname, cname, wkey) in g.nodes[tid].write_backs:
                last_writer.setdefault(("data", cname, tuple(wkey)), mi)
        bad = None
        for mi, tid in enumerate(pref):
            for (fname, cname, wkey) in g.nodes[tid].write_backs:
                pc = classes[tid[0]]
                f = next(fl for fl in pc.flows if fl.name == fname)
                if f.mode == CTL:
                    continue
                key = region_source(g, pset, tid, fname)
                if last_writer.get(key, mi) > mi:
                    bad = mi
                    break
            if bad is not None:
                break
        if bad is None:
            return n
        n = bad + 1 if bad >= 1 else 1
    return max(n, 1)


def _slots_consistent(g: TaskGraph, classes, members: List[TaskId]) -> bool:
    """Reject a candidate region where two DIFFERENT boundary slots
    alias one underlying tile and at least one member writes it: the
    fused program reads every slot at region entry, so an in-region
    writer's update would be invisible to a member reading the tile
    through the other slot (the dynamic runtime orders those accesses
    by dependencies; the fused program must not weaken that)."""
    from .graph import source_tile

    pset = set(members)
    by_full: Dict[Tuple, Set[Tuple]] = {}
    writers: Set[Tuple] = set()
    for tid in members:
        pc = classes[tid[0]]
        for f in pc.flows:
            if f.mode == CTL:
                continue
            key = region_source(g, pset, tid, f.name)
            try:
                full = source_tile(g, tid, f.name)
            except RuntimeError:
                return False  # cyclic flow chain: never fuse
            by_full.setdefault(full, set()).add(key)
            if f.mode & AccessMode.OUT:
                writers.add(full)
    for full, keys in by_full.items():
        if len(keys) > 1 and full in writers:
            return False
    return True


def partition(g: TaskGraph, classes, *, mode: str, max_tasks: int,
              eligible: Optional[Callable[[str], bool]] = None,
              wave_min: int = 2) -> List[Region]:
    """Partition the captured graph into fused regions (multi-member
    only; unassigned nodes keep per-task dispatch).  ``classes`` is the
    PTG class dict; ``eligible(class_name)`` gates membership (defaults
    to :func:`class_fusible` over ``classes``).  Safe by construction:
    chains fuse only single-distinct-successor interiors, waves only
    level-equal same-class groups — and a contracted-graph cycle check
    backstops the proof (a detected cycle disables fusion loudly)."""
    if mode in ("", "off") or not g.nodes:
        return []
    if eligible is None:
        eligible = lambda name: class_fusible(classes[name])  # noqa: E731
    elig_memo: Dict[str, bool] = {}

    def ok(tid: TaskId) -> bool:
        name = tid[0]
        e = elig_memo.get(name)
        if e is None:
            e = elig_memo[name] = bool(eligible(name))
        return e

    max_tasks = max(2, int(max_tasks))
    order = g.topo_order()
    assigned: Set[TaskId] = set()
    regions: List[Region] = []

    def devtype(tid: TaskId) -> Optional[str]:
        pc = classes.get(tid[0])
        return class_device_type(pc) if pc is not None else None

    if mode in ("auto", "chains"):
        for tid in order:
            if tid in assigned or not ok(tid):
                continue
            chain = [tid]
            cur = tid
            dt0 = devtype(tid)
            rank0 = g.nodes[tid].rank
            while len(chain) < max_tasks:
                node = g.nodes[cur]
                succs = _distinct_succs(node)
                if len(succs) != 1 or node.remote_out:
                    # an interior member must have exactly ONE distinct
                    # successor GLOBALLY: a mid-chain remote forward
                    # (the ring-attention K/V rotation) buried inside a
                    # region would only fire at region completion —
                    # serializing the rotation at best, deadlocking the
                    # cross-rank cycle at worst
                    break
                nxt = next(iter(succs))
                if nxt in assigned or not ok(nxt) \
                        or devtype(nxt) != dt0 \
                        or g.nodes[nxt].rank != rank0:
                    break
                chain.append(nxt)
                cur = nxt
            n = _writeback_safe(g, classes, chain)
            chain = chain[:n]
            if len(chain) >= 2 and _slots_consistent(g, classes, chain):
                regions.append(Region(len(regions), "chain", chain))
                assigned.update(chain)

    # waves rely on the LEVEL argument for convexity, and levels are
    # computed over the captured edges only: on a rank-filtered capture
    # of a distributed pool, a remote round-trip (member -> remote ->
    # member) is invisible and could close a cycle between level-equal
    # tasks.  Waves therefore require the FULL graph (single-rank pools
    # capture everything); chains stay safe everywhere via the global
    # single-successor rule above.
    full_capture = len(getattr(g, "global_ranks", g.nodes)) == len(g.nodes)
    if mode in ("auto", "waves") and full_capture:
        level: Dict[TaskId, int] = {t: 0 for t in order}
        for t in order:
            lt = level[t]
            for (_f, succ, _sf) in g.nodes[t].out_edges:
                if level[succ] < lt + 1:
                    level[succ] = lt + 1
        groups: Dict[Tuple, List[TaskId]] = {}
        for t in order:
            if t in assigned or not ok(t):
                continue
            groups.setdefault((t[0], level[t], g.nodes[t].rank),
                              []).append(t)
        for key in sorted(groups, key=repr):
            g_members = sorted(groups[key])
            for i in range(0, len(g_members), max_tasks):
                wave = g_members[i:i + max_tasks]
                if len(wave) >= max(2, wave_min) \
                        and _slots_consistent(g, classes, wave):
                    regions.append(Region(len(regions), "wave", wave))
                    assigned.update(wave)

    if regions and _contracted_has_cycle(g, regions):
        debug.warning(
            "fusion: contracted region graph has a cycle (%d regions) — "
            "fusion disabled for this graph", len(regions))
        return []
    return regions


def _contracted_has_cycle(g: TaskGraph, regions: List[Region]) -> bool:
    """Kahn over the region-contracted graph (safety net: impossible by
    construction, catastrophic if ever violated — a cyclic contraction
    deadlocks the pool)."""
    rep: Dict[TaskId, Any] = {}
    for r in regions:
        for m in r.members:
            rep[m] = ("r", r.index)
    nodes: Set[Any] = set()
    edges: Dict[Any, Set[Any]] = {}
    indeg: Dict[Any, int] = {}
    for tid, node in g.nodes.items():
        u = rep.get(tid, tid)
        nodes.add(u)
        for (_f, succ, _sf) in node.out_edges:
            v = rep.get(succ, succ)
            if u == v:
                continue
            outs = edges.setdefault(u, set())
            if v not in outs:
                outs.add(v)
                indeg[v] = indeg.get(v, 0) + 1
                nodes.add(v)
    frontier = [u for u in nodes if indeg.get(u, 0) == 0]
    seen = 0
    while frontier:
        u = frontier.pop()
        seen += 1
        for v in edges.get(u, ()):
            indeg[v] -= 1
            if indeg[v] == 0:
                frontier.append(v)
    return seen != len(nodes)


# ---------------------------------------------------------------------------
# lowering: region -> one jitted program
# ---------------------------------------------------------------------------

class _FStep:
    __slots__ = ("tid", "cname", "locs", "body", "params", "resolvers",
                 "flow_names", "writable")

    def __init__(self, tid, cname, locs, body, params, resolvers,
                 flow_names, writable):
        self.tid = tid
        self.cname = cname
        self.locs = locs
        self.body = body
        self.params = params
        #: [(flow name, ("slot", idx) | ("val", producer_tid, flow)
        #:   | ("none",))]
        self.resolvers = resolvers
        self.flow_names = flow_names
        self.writable = writable


class FusedPlan:
    """Lowering of one region against a taskpool's constants: the I/O
    slot structure, per-member steps, the fused program callable, and
    the content digest keying the executable cache.

    ``slots`` is the ordered program I/O: one entry per distinct
    region-boundary tile (``region_source`` identity), each with the
    union of member access modes.  The program takes one array per slot
    positionally and returns the final value of every writable slot in
    slot order — exactly the :class:`~parsec_tpu.device.tpu.TpuDevice`
    body contract, so a supertask dispatches like any other device
    chore."""

    def __init__(self, tp, g: TaskGraph, region: Region, *,
                 scan: Optional[str] = None):
        from ..compile_cache import _scrub

        self.region = region
        self.tp = tp
        classes = tp.ptg.classes
        consts = tp.constants
        pset = region.member_set
        scan = scan if scan is not None else fusion_scan_mode()

        slot_index: Dict[Tuple, int] = {}
        slot_keys: List[Tuple] = []
        slot_modes: List[int] = []
        #: per member: {flow name -> slot key or None}; release needs the
        #: backing Data of every flow, including internally-threaded ones
        self.member_flow_slots: List[Dict[str, Optional[Tuple]]] = []
        steps: List[_FStep] = []
        member_pos = {tid: i for i, tid in enumerate(region.members)}
        self.device_type = class_device_type(classes[region.members[0][0]])

        for tid in region.members:
            pc = classes[tid[0]]
            env = pc.env_of(tid[1], consts)
            body = next(fn for dt, fn in pc.bodies.items()
                        if dt != DEV_CPU)
            params = {n: env[n] for n in (pc.param_names + pc.def_names
                                          + pc.body_globals)}
            resolvers: List[Tuple] = []
            flow_names: List[str] = []
            writable: List[str] = []
            fslots: Dict[str, Optional[Tuple]] = {}
            for f in pc.flows:
                if f.mode == CTL:
                    continue
                flow_names.append(f.name)
                if f.mode & AccessMode.OUT:
                    writable.append(f.name)
                src = g.nodes[tid].flow_sources.get(f.name)
                if src is None and not (f.mode & AccessMode.OUT):
                    resolvers.append((f.name, ("none",)))
                    fslots[f.name] = None
                    continue
                if src is not None and src[0] == "task" \
                        and src[1] in pset:
                    resolvers.append((f.name, ("val", src[1], src[2])))
                    fslots[f.name] = region_source(g, pset, tid, f.name)
                    continue
                key = region_source(g, pset, tid, f.name)
                fslots[f.name] = key
                idx = slot_index.get(key)
                if idx is None:
                    idx = slot_index[key] = len(slot_keys)
                    slot_keys.append(key)
                    slot_modes.append(0)
                slot_modes[idx] |= int(f.mode & AccessMode.INOUT)
                resolvers.append((f.name, ("slot", idx)))
            # every writable flow also writes its slot (threaded tiles:
            # interior flows share the creator's slot)
            for fname in writable:
                key = fslots.get(fname)
                if key is not None and key not in slot_index:
                    idx = slot_index[key] = len(slot_keys)
                    slot_keys.append(key)
                    slot_modes.append(0)
                if key is not None:
                    slot_modes[slot_index[key]] |= int(AccessMode.OUT)
            steps.append(_FStep(tid, tid[0], tid[1], body, params,
                                resolvers, flow_names, writable))
            self.member_flow_slots.append(fslots)

        self.steps = steps
        self.slot_keys = slot_keys
        self.slot_modes = slot_modes
        self.slot_index = slot_index
        self.out_slots = [i for i, m in enumerate(slot_modes)
                          if m & AccessMode.OUT]
        #: final writer per out slot: (member tid, flow name) — the key
        #: the program's ``vals`` dict uses
        last_writer: Dict[int, Tuple[TaskId, str]] = {}
        for mi, step in enumerate(steps):
            for fname in step.writable:
                key = self.member_flow_slots[mi].get(fname)
                if key is not None:
                    last_writer[self.slot_index[key]] = (step.tid, fname)
        self.slot_writer = last_writer
        self.priority = max(
            classes[t[0]].priority_of(t[1], consts)
            for t in region.members)
        self.classes_of = []
        for t in region.members:
            if t[0] not in self.classes_of:
                self.classes_of.append(t[0])
        self.name = f"fused[{'+'.join(self.classes_of)}]"

        # --- content digest: member fingerprints + region shape --------
        h = hashlib.sha256()
        for step in steps:
            fp = _body_fp(step.body)
            h.update(repr((step.cname, step.locs, fp,
                           sorted((k, _scrub(repr(v)))
                                  for k, v in step.params.items()),
                           step.resolvers, step.writable)).encode())
        h.update(repr(("slots", slot_keys, slot_modes,
                       self.out_slots,
                       sorted(last_writer.items()))).encode())
        h.update(repr(("region", region.kind,
                       len(region.members))).encode())
        self.digest = h.hexdigest()[:32]

        self._scan_segments = self._plan_scan(scan) \
            if scan != "off" else None
        self.body_fn = self._build_program()
        # the taskpool reference is only needed while PLANNING (scan
        # shape probes); a cached plan outliving its build taskpool must
        # not retain that pool's collections in memory
        self.tp = None

    # -- scan detection --------------------------------------------------
    def _slot_shape(self, idx: int) -> Optional[Tuple]:
        key = self.slot_keys[idx]
        try:
            if key[0] == "data":
                d = self.tp.constants[key[1]].data_of(*key[2])
                c = d.newest_copy()
                p = getattr(c, "payload", None)
                if p is not None:
                    return (tuple(p.shape), str(p.dtype))
            elif key[0] == "new":
                shape, dtype = self.tp.new_tile_spec(key[1][0], key[2])
                return (tuple(shape), str(np.dtype(dtype)))
        except Exception:
            return None
        return None

    def _plan_scan(self, scan_mode: str):
        """Detect one maximal uniform run covering steps [0, k): same
        body, identical resolver pattern with carries threaded
        step-to-step, per-step slots all shape-equal.  Returns
        ``(k, carries, const_flows, perstep_flows)`` or None."""
        steps = self.steps
        if len(steps) < (2 if scan_mode == "on" else SCAN_MIN):
            return None
        s0 = steps[0]
        k = 1
        while k < len(steps) and steps[k].body is s0.body \
                and steps[k].cname == s0.cname \
                and steps[k].flow_names == s0.flow_names \
                and steps[k].writable == s0.writable \
                and list(steps[k].params) == list(s0.params):
            k += 1
        if k < (2 if scan_mode == "on" else SCAN_MIN):
            return None
        carries: List[str] = []
        const_flows: Dict[str, int] = {}
        perstep: Dict[str, List[int]] = {}
        for fi, (fname, r0) in enumerate(s0.resolvers):
            rs = [steps[i].resolvers[fi][1] for i in range(k)]
            if all(r[0] == "val" and r[1] == steps[i - 1].tid
                   and r[2] == fname
                   for i, r in enumerate(rs) if i > 0) \
                    and rs[0][0] == "slot" and fname in s0.writable:
                carries.append(fname)
            elif all(r[0] == "slot" for r in rs) \
                    and len({r[1] for r in rs}) == 1:
                const_flows[fname] = rs[0][1]
            elif all(r[0] == "slot" for r in rs) \
                    and len({r[1] for r in rs}) == k:
                perstep[fname] = [r[1] for r in rs]
            else:
                return None
        if set(carries) != set(s0.writable):
            return None
        if scan_mode == "auto":
            for fname, idxs in perstep.items():
                shapes = {self._slot_shape(i) for i in idxs}
                if len(shapes) != 1 or None in shapes:
                    return None
        for p in s0.params:
            for i in range(k):
                if not isinstance(steps[i].params[p],
                                  (int, float, bool, np.integer,
                                   np.floating)):
                    return None
        carry0 = {f: steps[0].resolvers[
            s0.flow_names.index(f)][1][1] for f in carries}
        return (k, carries, const_flows, perstep, carry0)

    # -- program emission ------------------------------------------------
    def _build_program(self):
        steps = self.steps
        out_slots = tuple(self.out_slots)
        slot_writer = self.slot_writer
        seg = self._scan_segments
        fused_n = len(self.region.members)

        def run_steps(env: Dict[int, Any], vals: Dict, lo: int,
                      hi: int) -> None:
            for step in steps[lo:hi]:
                kw: Dict[str, Any] = {}
                for fname, r in step.resolvers:
                    if r[0] == "none":
                        kw[fname] = None
                    elif r[0] == "slot":
                        kw[fname] = env[r[1]]
                    else:
                        kw[fname] = vals[(r[1], r[2])]
                for fname in step.flow_names:
                    vals[(step.tid, fname)] = kw[fname]
                kw.update(step.params)
                outs = step.body(**kw)
                if outs is None:
                    outs = ()
                elif not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                if len(outs) != len(step.writable):
                    raise ValueError(
                        f"fused member {step.tid}: body returned "
                        f"{len(outs)} outputs for {len(step.writable)} "
                        "writable flows")
                for fname, o in zip(step.writable, outs):
                    vals[(step.tid, fname)] = o

        if seg is None:
            def fused_body(*arrays):
                env = dict(enumerate(arrays))
                vals: Dict = {}
                run_steps(env, vals, 0, len(steps))
                return tuple(vals[slot_writer[i]] for i in out_slots)
        else:
            k, carries, const_flows, perstep, carry0 = seg
            s0 = steps[0]
            pkeys = list(s0.params)

            def fused_body(*arrays):
                import jax
                import jax.numpy as jnp

                env = dict(enumerate(arrays))
                vals: Dict = {}
                xs_flows = {f: jnp.stack([env[i] for i in idxs])
                            for f, idxs in perstep.items()}
                xs_params = {p: jnp.asarray(
                    [steps[i].params[p] for i in range(k)])
                    for p in pkeys}
                consts_kw = {f: env[i] for f, i in const_flows.items()}

                def scan_step(carry, xs):
                    kw = dict(zip(carries, carry))
                    kw.update(consts_kw)
                    kw.update({f: xs[0][f] for f in xs_flows})
                    kw.update({p: xs[1][p] for p in pkeys})
                    outs = s0.body(**kw)
                    if not isinstance(outs, (tuple, list)):
                        outs = (outs,)
                    om = dict(zip(s0.writable, outs))
                    return tuple(om[f] for f in carries), None

                carry = tuple(env[carry0[f]] for f in carries)
                carry, _ = jax.lax.scan(scan_step, carry,
                                        (xs_flows, xs_params))
                fin = dict(zip(carries, carry))
                last = steps[k - 1].tid
                for f in carries:
                    vals[(last, f)] = fin[f]
                # non-carry flows of the scanned run that later steps
                # read: only the LAST step's values can be consumed
                # (interior members have a single successor)
                for f, idxs in perstep.items():
                    vals[(last, f)] = xs_flows[f][k - 1]
                for f, i in const_flows.items():
                    vals[(last, f)] = env[i]
                run_steps(env, vals, k, len(steps))
                return tuple(vals[slot_writer[i]] for i in out_slots)

        fused_body.__name__ = self.name
        fused_body._jit_key = ("fused", self.digest)
        fused_body._content_key = ("fused", self.digest)
        fused_body._fused_n = fused_n
        fused_body._fused_classes = tuple(self.classes_of)
        return fused_body


# ---------------------------------------------------------------------------
# dynamic-runtime integration (used by dsl/ptg.py)
# ---------------------------------------------------------------------------

class _LiveRegion:
    __slots__ = ("region", "plan", "waiting", "lock", "supertask",
                 "ext_goals")

    def __init__(self, region: Region, plan: FusedPlan):
        self.region = region
        self.plan = plan
        self.waiting = 0
        self.lock = threading.Lock()
        self.supertask = None
        self.ext_goals: Dict[TaskId, int] = {}


class FusionTable:
    """Per-taskpool fusion state for the DYNAMIC runtime: member →
    region routing, region readiness counters (a region fires when every
    member's EXTERNAL dependency goal is met), and the synthetic
    supertask task classes dispatched as one ASYNC device chore.

    Member release accounting: a fused member's dependency counter runs
    with its EXTERNAL goal (total goal minus intra-region in-edges) —
    intra-region producers never execute individually, so their releases
    never arrive.  Each member that becomes externally-ready (or is
    claimed as a startup source) decrements the region's ``waiting``
    count; the transition to zero schedules the supertask.  A fused
    region retires all N member tasks at ONE completion
    (``Task.fused_n`` → ``Taskpool.task_done``)."""

    def __init__(self, tp, regions: List[Region], plans: List[FusedPlan],
                 analysis: List[Tuple[Dict[TaskId, int], int]]):
        self.tp = tp
        self._member: Dict[TaskId, _LiveRegion] = {}
        self.live: List[_LiveRegion] = []
        for region, plan, (ext_goals, waiting) in zip(regions, plans,
                                                      analysis):
            lr = _LiveRegion(region, plan)
            lr.ext_goals = ext_goals
            lr.waiting = waiting
            lr.supertask = self._build_supertask(lr)
            for m in region.members:
                self._member[m] = lr
            self.live.append(lr)

    # -- routing ---------------------------------------------------------
    def ext_goal(self, name: str, locs: Tuple) -> Optional[int]:
        lr = self._member.get((name, tuple(locs)))
        if lr is None:
            return None
        return lr.ext_goals[(name, tuple(locs))]

    def route_ready(self, name: str, locs: Tuple):
        """One external-readiness event for a member (counter fired, or
        a startup source was claimed).  Returns ``(handled, supertask)``
        — ``handled`` False when the task is not fused (caller builds
        an ordinary task); the supertask is non-None exactly once, on
        the region's last event."""
        lr = self._member.get((name, tuple(locs)))
        if lr is None:
            return False, None
        with lr.lock:
            lr.waiting -= 1
            fire = lr.waiting == 0
        return True, (lr.supertask if fire else None)

    # -- the synthetic supertask class -----------------------------------
    def _build_supertask(self, lr: _LiveRegion):
        from ..core.task import Chore, Flow, Task, TaskClass
        from .ptg import _accel_hook

        tp = self.tp
        plan = lr.plan
        flows = [Flow(f"t{i}", AccessMode(m) if m else AccessMode.IN, i)
                 for i, m in enumerate(plan.slot_modes)]
        tc = TaskClass(plan.name, flows=flows, nb_parameters=1)
        tc.prepare_input = self._make_prepare(lr)
        tc.release_deps = self._make_release(lr)
        chore = Chore(plan.device_type, _accel_hook)
        chore.body_fn = plan.body_fn
        tc.add_chore(chore)
        task = Task(tp, tc, locals_=(lr.region.index,),
                    priority=plan.priority)
        task.fused_n = len(lr.region.members)
        return task

    def _resolve_slot(self, key: Tuple):
        """Slot key → backing Data, via the same machinery the member
        tasks would use individually: collection tiles directly, NEW
        tiles through the taskpool's shared new-tile table, external
        producers through their class repo (deposited locally at the
        producer's release, or by ``incoming_activation`` for remote
        producers)."""
        tp = self.tp
        if key[0] == "data":
            return tp.constants[key[1]].data_of(*key[2])
        if key[0] == "new":
            (cname, locs), fname = key[1], key[2]
            pc = tp.ptg.classes[cname]
            f = next(fl for fl in pc.flows if fl.name == fname)
            return tp._new_tile(pc, f, locs)
        # ("ext", producer tid, producer flow)
        _, (pcname, plocs), pflow = key
        src_pc = tp.ptg.classes[pcname]
        entry = tp.repos[pcname].consume(plocs)
        if entry is None:
            if not src_pc.instance_exists(plocs, tp.constants,
                                          tp._exists_memo):
                return None
            raise RuntimeError(
                f"fused region: producer {pcname}{plocs} left no repo "
                f"entry for flow {pflow!r} (asymmetric deps?)")
        src_flow = next(sf for sf in src_pc.flows if sf.name == pflow)
        data = entry.copies[src_flow.index]
        if data is None:
            raise RuntimeError(
                f"fused region: producer {pcname}{plocs} deposited no "
                f"data for flow {pflow!r}")
        return data

    def _make_prepare(self, lr: _LiveRegion):
        from ..core.lifecycle import HookReturn

        plan = lr.plan

        def prepare_input(es, task) -> HookReturn:
            # repo USAGE accounting must match the per-task runtime:
            # one consume per member flow that directly references an
            # external producer (the producer counted each of them)
            slot_data: List[Any] = [None] * len(plan.slot_keys)
            consumed: Set[Tuple] = set()
            for mi, step in enumerate(plan.steps):
                for fname, key in plan.member_flow_slots[mi].items():
                    if key is None:
                        continue
                    idx = plan.slot_index.get(key)
                    direct = any(
                        r[0] == "slot" and r[1] == idx
                        for fn_, r in step.resolvers if fn_ == fname)
                    if key[0] == "ext" and direct \
                            and (mi, fname) not in consumed:
                        consumed.add((mi, fname))
                        d = self._resolve_slot(key)
                        if idx is not None and slot_data[idx] is None:
                            slot_data[idx] = d
            for idx, key in enumerate(plan.slot_keys):
                if slot_data[idx] is None:
                    slot_data[idx] = self._resolve_slot(key)
            task.body_args = [
                ("data", slot_data[i],
                 AccessMode(plan.slot_modes[i]) if plan.slot_modes[i]
                 else AccessMode.IN)
                for i in range(len(plan.slot_keys))]
            for i, d in enumerate(slot_data):
                task.data_in[i] = d.newest_copy() if d is not None \
                    else None
            #: member flow index -> Data, for the per-member release
            flow_data = []
            for mi, step in enumerate(plan.steps):
                fd: Dict[str, Any] = {}
                for fname, key in plan.member_flow_slots[mi].items():
                    if key is None:
                        fd[fname] = None
                        continue
                    idx = plan.slot_index.get(key)
                    fd[fname] = slot_data[idx] if idx is not None \
                        else self._resolve_slot(key)
                flow_data.append(fd)
            task.user = flow_data
            return HookReturn.DONE

        return prepare_input

    def _make_release(self, lr: _LiveRegion):
        plan = lr.plan
        tp = self.tp
        classes = tp.ptg.classes

        def release_deps(es, task):
            ready: List[Any] = []
            flow_data = task.user or [{} for _ in plan.steps]
            for mi, step in enumerate(plan.steps):
                pc = classes[step.cname]
                fd = flow_data[mi]
                by_index = [None] * len(pc.flows)
                for f in pc.flows:
                    if f.mode != CTL:
                        by_index[f.index] = fd.get(f.name)
                ready.extend(tp._release_deps_core(
                    pc, step.locs, by_index, task.priority,
                    origin_region=lr.region.member_set))
            return ready

        return release_deps


def analyze_regions(tp, g: TaskGraph, regions: List[Region],
                    scan: Optional[str] = None):
    """Per-region lowering + external-goal analysis:
    ``(plans, [(ext_goals, waiting)])`` — everything a FusionTable needs
    beyond the live taskpool, and everything worth CACHING across
    same-shaped taskpools."""
    consts = tp.constants
    classes = tp.ptg.classes
    plans = [FusedPlan(tp, g, r, scan=scan) for r in regions]
    analysis: List[Tuple[Dict[TaskId, int], int]] = []
    for region in regions:
        intra: Dict[TaskId, int] = {m: 0 for m in region.members}
        for m in region.members:
            for (_f, succ, _sf) in g.nodes[m].out_edges:
                if succ in region.member_set:
                    intra[succ] = intra.get(succ, 0) + 1
        ext_goals: Dict[TaskId, int] = {}
        waiting = 0
        for m in region.members:
            pc = classes[m[0]]
            goal = pc.goal_of(m[1], consts, tp._exists_memo)
            ext = goal - intra.get(m, 0)
            if ext < 0:
                raise RuntimeError(
                    f"fusion: member {m} external goal {ext} < 0 "
                    "(asymmetric deps? lint the graph)")
            ext_goals[m] = ext
            if ext > 0 or goal == 0:
                waiting += 1
        if waiting <= 0:
            raise RuntimeError(
                f"fusion: region {region!r} has no external release "
                "events; it could never fire")
        analysis.append((ext_goals, waiting))
    return plans, analysis


class _CachedFusion:
    __slots__ = ("regions", "plans", "analysis", "placement", "scalars")

    def __init__(self, regions, plans, analysis, placement, scalars):
        self.regions = regions
        self.plans = plans
        self.analysis = analysis
        self.placement = placement
        self.scalars = scalars


#: PTG definition -> {config key -> _CachedFusion}.  Capture +
#: partition + lowering cost real milliseconds per attach; a serving
#: mesh (or a bench rep loop) instantiates many taskpools from ONE
#: definition, and the partition depends only on the definition, the
#: scalar constants and the placement map — all validated on reuse.
_fusion_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_fusion_cache_lock = threading.Lock()


def _scalar_constants(constants: Dict[str, Any]) -> Tuple:
    return tuple(sorted(
        (k, v) for k, v in constants.items()
        if isinstance(v, (int, float, str, bool, np.integer,
                          np.floating))))


def _placement_of(tp) -> Dict[TaskId, int]:
    """Pass-1 global placement map (the cheap ~20% of a capture; same
    construction as ``graph.capture`` pass 1 and the native executor's
    rebind validation)."""
    consts = tp.constants
    out: Dict[TaskId, int] = {}
    for pc in tp.ptg.classes.values():
        for loc in pc.param_space(consts):
            out[(pc.name, loc)] = pc.rank_of(loc, consts)
    return out


def build_fusion_table(tp, context) -> Optional[FusionTable]:
    """Attach-time entry point for the dynamic runtime: capture this
    rank's subgraph, partition, lower, and build the table — or None
    when fusion is off, nothing fuses, or no capable device is
    attached.  The (partition, plans, goals) triple is cached per PTG
    definition and revalidated against the new pool's scalar constants
    and placement map, so repeated same-shaped pools (the serving
    pattern) pay one cheap enumeration instead of a full rebuild."""
    mode = fusion_mode()
    if mode in ("", "off"):
        return None
    rank = getattr(context, "rank", 0)
    nranks = getattr(context, "nranks", 1)
    classes = tp.ptg.classes
    devices = [d for d in getattr(context, "devices", ())
               if getattr(d, "enabled", True)]
    devtypes = {d.device_type for d in devices}
    accel = next((d for d in devices if d.device_type != DEV_CPU), None)
    horizon = fusion_max_tasks(device=accel)
    scan = fusion_scan_mode()
    key = (rank, nranks, mode, horizon, scan,
           tuple(sorted(devtypes)))
    scalars = _scalar_constants(tp.constants)

    with _fusion_cache_lock:
        per = _fusion_cache.get(tp.ptg)
        cached = per.get(key) if per else None
    if cached is not None and cached.scalars == scalars \
            and cached.placement == _placement_of(tp):
        if not cached.regions:
            return None
        return FusionTable(tp, cached.regions, cached.plans,
                           cached.analysis)

    g = tp.capture(ranks=[rank])

    def eligible(name: str) -> bool:
        pc = classes[name]
        dt = class_device_type(pc)
        return dt is not None and dt in devtypes and class_fusible(pc)

    regions = partition(g, classes, mode=mode, max_tasks=horizon,
                        eligible=eligible)
    plans, analysis = analyze_regions(tp, g, regions, scan=scan) \
        if regions else ([], [])
    with _fusion_cache_lock:
        per = _fusion_cache.get(tp.ptg)
        if per is None:
            per = {}
            _fusion_cache[tp.ptg] = per
        per[key] = _CachedFusion(regions, plans, analysis,
                                 dict(g.global_ranks), scalars)
    if not regions:
        return None
    table = FusionTable(tp, regions, plans, analysis)
    debug.verbose(2, "fusion",
                  "%s: fused %d regions covering %d/%d tasks",
                  tp.ptg.name, len(regions),
                  sum(len(r.members) for r in regions), len(g.nodes))
    return table
