"""Whole-DAG XLA lowering: compile an entire PTG taskpool into ONE jitted
XLA program — the TPU-native execution mode for regular task graphs.

Rationale (TPU-first design, no reference equivalent): the reference runtime
dispatches every task individually because CPU/GPU execution is host-driven;
on TPU the same DAG can be handed to the XLA compiler *whole*.  Capture the
static graph (:mod:`parsec_tpu.dsl.graph` — the same capture that feeds the
iterators checker), emit every task body in topological order as pure
functional dataflow, and ``jax.jit`` the result with input donation:

* zero per-task runtime overhead — no Python dispatch, no scheduler locks;
* XLA fuses elementwise tails into the MXU matmuls and overlaps
  HBM traffic with compute across *task* boundaries, which the dynamic
  runtime cannot see;
* donation lets the factorization run in place in HBM.

This is the analogue of CUDA-graph capture in spirit, but stronger: the
compiler reorders and fuses across the whole DAG instead of replaying a
fixed stream order.

The dynamic runtime remains the right tool for irregular DAGs, multi-pool
composition, and distributed execution; ``GraphExecutor`` is the fast path
for regular single-chip (or SPMD-sharded) taskpools.  Task bodies must have
a functional incarnation (the ``tpu`` chore convention: kwargs by flow name
+ params, returning new arrays for writable flows).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.lifecycle import AccessMode, DEV_CPU, DEV_TPU
from ..utils import debug
from .graph import TaskGraph, capture
from .ptg import CTL, PTGTaskpool


class _Step:
    __slots__ = ("tid", "body", "flow_inputs", "flow_names", "writable", "params", "write_backs")

    def __init__(self, tid, body, flow_inputs, flow_names, writable, params, write_backs):
        self.tid = tid
        self.body = body
        #: [(flow name, source tuple)] for non-CTL flows
        self.flow_inputs = flow_inputs
        self.flow_names = flow_names
        self.writable = writable
        self.params = params
        self.write_backs = write_backs


class GraphExecutor:
    """Compile a PTG taskpool's DAG into one jitted XLA computation.

    ``executor = GraphExecutor(tp)`` then ``outs = executor()`` (pulls tile
    values from the taskpool's collections and writes results back) or
    ``outs = executor.apply(feeds)`` for explicit array feeds.
    """

    def __init__(
        self,
        tp: PTGTaskpool,
        *,
        device_type: str = DEV_TPU,
        donate: bool = True,
        jit: bool = True,
        batch_levels: bool = False,
        cache=None,
    ):
        """``batch_levels=True`` groups same-class tasks at the same
        dependency level and vmaps the body over each group: the emitted
        program shrinks from O(tasks) ops to O(levels) *batched* ops, so
        compile time scales to large task counts (measured: 40s vs 65s at
        816 tasks, with the gap widening superlinearly). The gather/
        scatter around each group costs extra HBM traffic — measured
        ~2.6x slower at N=8192 — so this is the compile-scalability mode
        for very large NT, not the default perf path (round-1 chip
        run).
        Ragged members fall back to per-task emission automatically."""
        import jax

        self.taskpool = tp
        self.graph: TaskGraph = capture(tp)
        order = self.graph.topo_order()
        self.batch_levels = batch_levels
        #: groups that fell back to per-task emission (observable so a
        #: silently-unbatched program can be diagnosed)
        self.batch_fallbacks = 0


        plan: List[_Step] = []
        homes_in: List[Tuple[str, Tuple]] = []
        homes_out: List[Tuple[str, Tuple]] = []
        seen_in, seen_out = set(), set()
        for tid in order:
            pc = tp.ptg.classes[tid[0]]
            node = self.graph.nodes[tid]
            body = pc.bodies.get(device_type) or pc.bodies.get("tpu")
            if body is None:
                raise ValueError(
                    f"class {tid[0]} has no functional ({device_type!r}) body; "
                    "whole-DAG lowering needs functional incarnations")
            flow_inputs, flow_names, writable = [], [], []
            for f in pc.flows:
                if f.mode == CTL:
                    continue
                src = node.flow_sources.get(f.name)
                flow_inputs.append((f.name, src))
                flow_names.append(f.name)
                if f.mode & AccessMode.OUT:
                    writable.append(f.name)
                if src is not None and src[0] == "data":
                    hk = (src[1], tuple(src[2]))
                    if hk not in seen_in:
                        seen_in.add(hk)
                        homes_in.append(hk)
            penv = pc.env_of(tid[1], tp.constants)
            params = {n: penv[n]
                      for n in pc.param_names + pc.def_names + pc.body_globals}
            wbs = [(fn_, cn, tuple(k)) for (fn_, cn, k) in node.write_backs]
            for (_fn, cn, k) in wbs:
                hk = (cn, k)
                if hk not in seen_out:
                    seen_out.add(hk)
                    homes_out.append(hk)
            plan.append(_Step(tid, body, flow_inputs, flow_names, writable, params, wbs))

        self.input_keys: List[Tuple[str, Tuple]] = homes_in
        self.output_keys: List[Tuple[str, Tuple]] = homes_out
        self._plan = plan

        # dependency level per task (longest path from a source): steps in
        # one level are mutually independent, so same-class groups can be
        # emitted as ONE vmapped op
        self._level_plan: Optional[List[List[_Step]]] = None
        if batch_levels:
            step_of = {s.tid: s for s in plan}
            level: Dict[Tuple, int] = {tid: 0 for tid in order}
            for tid in order:
                lt = level[tid]
                for (_f, succ, _sf) in self.graph.nodes[tid].out_edges:
                    if level[succ] < lt + 1:
                        level[succ] = lt + 1
            nlev = 1 + max(level.values(), default=0)
            buckets: List[List[_Step]] = [[] for _ in range(nlev)]
            for tid in order:
                buckets[level[tid]].append(step_of[tid])
            self._level_plan = buckets

        def run(*in_arrays):
            env: Dict[Tuple[str, Tuple], Any] = dict(zip(self.input_keys, in_arrays))
            vals: Dict[Tuple[Tuple, str], Any] = {}
            for step in plan:
                kwargs = resolve_kwargs(step, env, vals)
                kw = dict(kwargs)
                kw.update(step.params)
                record_outputs(step, kwargs, step.body(**kw), env, vals)
            return tuple(env[k] for k in self.output_keys)

        def resolve_kwargs(step, env, vals):
            import jax.numpy as jnp

            kwargs: Dict[str, Any] = {}
            for fname, src in step.flow_inputs:
                if src is None:
                    v = None
                elif src[0] == "data":
                    v = env[(src[1], tuple(src[2]))]
                elif src[0] == "new":
                    shp, dt = tp.new_tile_spec(step.tid[0], fname)
                    v = jnp.zeros(shp, dt)
                else:
                    v = vals[(src[1], src[2])]
                kwargs[fname] = v
            return kwargs

        def record_outputs(step, kwargs, outs, env, vals):
            for fname in step.flow_names:  # read flows pass through
                vals[(step.tid, fname)] = kwargs[fname]
            if outs is not None:
                outs = outs if isinstance(outs, (tuple, list)) else (outs,)
                if len(outs) != len(step.writable):
                    raise ValueError(
                        f"{step.tid}: body returned {len(outs)} values for "
                        f"{len(step.writable)} writable flows")
                for fname, out in zip(step.writable, outs):
                    vals[(step.tid, fname)] = out
            for (fname, cn, k) in step.write_backs:
                env[(cn, k)] = vals[(step.tid, fname)]

        def run_batched(*in_arrays):
            import jax as _jax
            import jax.numpy as jnp

            env: Dict[Tuple[str, Tuple], Any] = dict(zip(self.input_keys, in_arrays))
            vals: Dict[Tuple[Tuple, str], Any] = {}
            for steps in self._level_plan:
                # bucket by (class, per-flow shape/dtype signature): all
                # members of a bucket run as ONE vmapped body
                groups: Dict[Tuple, List[Tuple[_Step, Dict[str, Any]]]] = {}
                for step in steps:
                    kwargs = resolve_kwargs(step, env, vals)
                    sig = (step.tid[0], tuple(
                        (fn_, None if kwargs[fn_] is None
                         else (tuple(kwargs[fn_].shape), str(kwargs[fn_].dtype)))
                        for fn_ in step.flow_names))
                    groups.setdefault(sig, []).append((step, kwargs))
                for members in groups.values():
                    if len(members) == 1:
                        step, kwargs = members[0]
                        kw = dict(kwargs)
                        kw.update(step.params)
                        record_outputs(step, kwargs, step.body(**kw), env, vals)
                        continue
                    step0 = members[0][0]
                    arr_flows = [fn_ for fn_ in step0.flow_names
                                 if members[0][1][fn_] is not None]
                    none_flows = [fn_ for fn_ in step0.flow_names
                                  if members[0][1][fn_] is None]
                    try:
                        stacked = {fn_: jnp.stack([kw[fn_] for _s, kw in members])
                                   for fn_ in arr_flows}
                        # params identical across the group pass through as
                        # plain Python scalars (keeps weak typing exactly
                        # like per-task emission); only differing values
                        # are stacked and vmapped
                        const_params, pstack = {}, {}
                        for p in step0.params:
                            vs = [s.params[p] for s, _kw in members]
                            if all(v == vs[0] for v in vs[1:]):
                                const_params[p] = vs[0]
                            else:
                                pstack[p] = jnp.asarray(vs)

                        def grouped(flows, params, _body=step0.body,
                                    _none=tuple(none_flows),
                                    _const=const_params):
                            kw = dict(flows)
                            kw.update({n: None for n in _none})
                            kw.update(_const)
                            kw.update(params)
                            return _body(**kw)

                        outs = _jax.vmap(grouped)(stacked, pstack)
                    except (TypeError, ValueError, IndexError) as e:
                        # ragged member (stack shape mismatch) or
                        # non-traceable scalar use (jax concretization
                        # errors subclass TypeError; non-concrete boolean
                        # indexing subclasses IndexError): emit this group
                        # per-task instead.  Anything else — a genuine
                        # body bug, OOM — propagates.
                        self.batch_fallbacks += 1
                        debug.verbose(
                            2, "xla_lower",
                            "batch_levels: group of %d %s tasks fell back "
                            "to per-task emission (%s: %s)",
                            len(members), step0.body.__name__,
                            type(e).__name__, e)
                        for step, kwargs in members:
                            kw = dict(kwargs)
                            kw.update(step.params)
                            record_outputs(step, kwargs, step.body(**kw), env, vals)
                        continue
                    for i, (step, kwargs) in enumerate(members):
                        if outs is None:
                            member_outs = None  # zero writable flows
                        else:
                            outs_t = (outs if isinstance(outs, (tuple, list))
                                      else (outs,))
                            member_outs = tuple(o[i] for o in outs_t)
                        record_outputs(step, kwargs, member_outs, env, vals)
            return tuple(env[k] for k in self.output_keys)

        entry_fn = run_batched if batch_levels else run
        if jit:
            donate_argnums = ()
            if donate:
                donate_argnums = tuple(
                    i for i, k in enumerate(self.input_keys) if k in seen_out)
            # compile through the executable cache: the whole-DAG program
            # is keyed by a content digest of the plan (per-step body code
            # hash + params + dataflow + I/O keys), so an identical
            # taskpool rebuilt in this process is a dictionary hit and a
            # rebuild in a NEW process reloads the serialized executable
            # from the persistent store instead of paying the full XLA
            # cold compile (a QR program set took 460 s to compile on
            # the chip in round 3)
            from ..compile_cache import default_cache

            self.cache = cache if cache is not None else default_cache()
            self.program_digest = self._plan_digest(tp)
            self.donate_argnums = donate_argnums
            self._fn = self.cache.jit(
                entry_fn,
                key=("graph", self.program_digest, batch_levels,
                     donate_argnums),
                donate_argnums=donate_argnums)
        else:
            self.cache = None
            self.program_digest = None
            self.donate_argnums = ()
            self._fn = entry_fn

    def _plan_digest(self, tp) -> str:
        """Content digest of the emitted program: every step's body code
        fingerprint, resolved params, dataflow sources and write-backs,
        plus the executor's input/output key order and NEW-tile specs.
        Anything that changes the traced program must land here — a
        collision would serve a stale executable, so when in doubt,
        include it."""
        import hashlib

        from ..compile_cache import _scrub, code_fingerprint

        h = hashlib.sha256()
        body_fps: Dict[int, str] = {}
        for step in self._plan:
            fp = body_fps.get(id(step.body))
            if fp is None:
                fp = body_fps[id(step.body)] = code_fingerprint(step.body)
            h.update(repr((step.tid, fp,
                           sorted((k, _scrub(repr(v)))
                                  for k, v in step.params.items()),
                           step.flow_inputs, step.writable,
                           step.write_backs)).encode())
            for fname, src in step.flow_inputs:
                if src is not None and src[0] == "new":
                    h.update(repr(
                        ("new", fname,
                         tp.new_tile_spec(step.tid[0], fname))).encode())
        h.update(repr(("io", self.input_keys, self.output_keys)).encode())
        return h.hexdigest()[:32]

    # ------------------------------------------------------------------
    def apply(self, feeds: Dict[Tuple[str, Tuple], Any]) -> Dict[Tuple[str, Tuple], Any]:
        """Run on explicit arrays: ``feeds[(collection_name, key)] = array``."""
        import numpy as np

        ins = [feeds[k] for k in self.input_keys]
        for i in self.donate_argnums:
            # a donated numpy feed can be zero-copied by the transfer
            # and then OVERWRITTEN in place by the program — never write
            # through to the caller's array (device/tpu.py
            # private_device_put has the full story)
            if isinstance(ins[i], np.ndarray):
                from ..device.staging import private_device_put

                ins[i] = private_device_put(ins[i], guard=ins[i])
        outs = self._fn(*ins)
        return dict(zip(self.output_keys, outs))

    def _collection(self, name: str):
        dc = self.taskpool.constants.get(name)
        if dc is None:
            raise KeyError(f"collection {name!r} not in taskpool constants")
        return dc

    def __call__(self, *, write_back: bool = True, block: bool = False):
        """Pull input tiles from the taskpool's collections, execute, and
        (by default) store result arrays back into the collection tiles as
        device-resident copies."""
        import jax.numpy as jnp

        import numpy as np

        donated = {self.input_keys[i] for i in self.donate_argnums}
        feeds = {}
        for (cname, key) in self.input_keys:
            d = self._collection(cname).data_of(*key)
            c = d.newest_copy()
            if c is None:
                raise RuntimeError(f"tile {cname}{key} has no valid copy")
            if (cname, key) in donated and isinstance(c.payload, np.ndarray):
                # the collection RETAINS this numpy payload at its
                # current version: a donated zero-copy view would let
                # the program overwrite it in place (device/tpu.py
                # private_device_put)
                from ..device.staging import private_device_put

                feeds[(cname, key)] = private_device_put(
                    c.payload, guard=c.payload)
            else:
                feeds[(cname, key)] = jnp.asarray(c.payload)
        outs = self.apply(feeds)
        if block:
            for v in outs.values():
                getattr(v, "block_until_ready", lambda: None)()
        if write_back:
            for (cname, key), arr in outs.items():
                d = self._collection(cname).data_of(*key)
                c = d.get_copy(0)
                if c is None:
                    d.attach_copy(0, arr)
                else:
                    c.payload = arr
                d.version_bump(0)
        return outs
