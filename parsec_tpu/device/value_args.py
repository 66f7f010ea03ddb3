"""How a task's value arguments reach its body inside a device program,
and which of its tile arguments the program takes at all.

A task's ``("value", v, VALUE)`` specs used to ride every call of its
program as one positional Python scalar each, and the executable's call
path copies each such scalar to the device on its own (a ``DevicePut`` of
200-360 us on a v5e: 1,903 of them a solve of the 816-task dpotrf, for
integers no kernel reads).  A :class:`ValuePlan` decides, once per
(body, argument signature), what becomes of each ``int``/``float``/
``bool`` value instead:

* **dropped** — the body's trace does not read it: it is no argument of
  the program; inside the trace the body gets a placeholder of the
  value's own Python type;
* **packed** — the trace reads it: all such ``int``/``bool`` values of
  the program's tasks travel in ONE host integer vector, all ``float``
  values in one floating vector, and the trace hands the body the
  element as the abstract value a Python scalar traces to (shape ``()``,
  weak-typed), so promotion inside bodies is what it was;
* **positional** — a value of any other type (a numpy scalar, an array)
  stays an argument of its own.

The same trace decides a task's TILE arguments.  A tile the device
module has nothing to stage for arrives as a ``jax.ShapeDtypeStruct``
(a scratch tile no task has written yet, ``device/scratch.py``; a
write-only flow): it is
never an argument of the program, and inside the trace the body gets
zeros of that shape — a body that reads them reads zeros, one that does
not costs nothing.  A staged tile the trace never reads is dropped from
the program's arguments the same way.  Both count as ``tiles_dropped``.

The plan never leans on ``jit``'s own pruning of unused arguments: a
program compiled through its serialized form (``compile_cache.
_compile_blob``) keeps every argument of ``Exported.call``.

A :class:`FlowPlan` is the same idea one step earlier, for the device
module's own host work: what the tasks of one wave signature share about
their argument lists (which positions are tiles to stage, which are
written, which are no argument at all) is worked out once a signature,
in plain ints and bools, and the staging walk and the commit of every
chunk (and of every task that goes out alone) read it instead of asking
each task's ``AccessMode`` again.
"""

from typing import Any, Iterator, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.lifecycle import AccessMode

_DROP, _INT, _BOOL, _FLOAT = "d", "i", "b", "f"
_UNBORN, _UNREAD = "z", "t"   # a tile: nothing was staged / never read


def _read_leaves(body, args: Sequence[Any]) -> Tuple[List[bool], Tuple]:
    """For each argument of ``body``: does the trace read any of its
    leaves?  One abstract trace (``make_jaxpr`` moves no data); an
    argument that is input to no equation and is no output is unread.
    With them, ``(shape, dtype)`` of every output of the body, in
    order."""
    jaxpr = jax.make_jaxpr(body)(*args).jaxpr
    used = {id(v) for eqn in jaxpr.eqns for v in eqn.invars}
    used.update(id(v) for v in jaxpr.outvars)
    read, at = [], 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        read.append(any(id(v) in used for v in jaxpr.invars[at:at + n]))
        at += n
    return read, tuple((tuple(v.aval.shape), v.aval.dtype)
                       for v in jaxpr.outvars)


#: what a :class:`FlowPlan` step does with one position of ``body_args``
VALUE, SCRATCH, ABSENT, PLACEHOLDER, READ, HOOKED, UNPAIRED, LATE = range(8)
_OUT, _INOUT = int(AccessMode.OUT), int(AccessMode.INOUT)


class FlowPlan:
    """What one signature (``TpuDevice._wave_signature``) fixes about
    its tasks' ``body_args``, for THE staging walk (of a chunk of a
    wave, or of a task that goes out alone) and the commit.

    ``steps`` has one ``(how, position, access, extra)`` per
    position that contributes an argument, in order: a ``VALUE`` rides
    as it is; ``SCRATCH`` (a per-task scratch allocation) becomes zeros
    of ``extra = (shape, dtype)``; ``ABSENT`` is a guarded-off flow
    (``None``); ``PLACEHOLDER`` is a tile there is nothing to stage for
    (a scratch tile nobody has written, a write-only flow): ``extra`` is
    the ``jax.ShapeDtypeStruct`` every task hands the program's
    :class:`ValuePlan`; ``READ`` is a tile to find on the device or stage
    in.  ``access`` is the flow's ``IN``/``OUT`` bits as a plain int:
    with the ``OUT`` bit the commit takes an output for it.

    Three more, of tasks that never ride a wave: ``HOOKED`` is a flow
    with a custom ``stage_in`` hook (``extra``: the hook's result IS the
    flow's device copy); ``UNPAIRED`` is such a flow that is written
    and has no ``stage_out`` hook, which the walk refuses; ``LATE`` is a
    write-only flow whose shape neither its tile nor a copy of it says:
    the walk stages the tile itself.  ``out_hooks`` holds one ``stage_out`` hook (or None)
    per output, or is None when no output has one.

    ``donates``: the read-write tiles whose INPUT version the program
    may write where it stands, as ``(position in body_args, index in the
    staged argument list, index among the task's outputs)`` — of the
    positions whoever built the tasks named (``Task._tpu_donate``: this
    task is the version's only consumer; the pump's attach plan, a DTD
    insertion or ``PTGTaskpool._donate_rule`` on the ``Context`` route
    says so), those that are a plain ``READ``
    with the ``OUT`` bit and a known shape.  Part of the signature: the
    tasks of one chunk donate the same positions."""

    __slots__ = ("steps", "nout", "reads", "read_bytes", "out_hooks",
                 "nbytes", "dtypes", "donates")

    def __init__(self, flows: Sequence[Any], donate: Sequence[int] = ()):
        """``flows``: the signature without its body key.  A tile is
        ``(shape, dtype, mode)``, with ``"unborn"`` before them for a
        scratch tile nobody has written and the flow's ``(stage_in,
        stage_out)`` hooks after them where it has any; ``shape`` is
        None where nothing says it.  ``donate``: ``Task._tpu_donate``
        of the signature's tasks."""
        steps: List[Tuple[int, int, int, Any]] = []
        donates: List[Tuple[int, int, int]] = []
        out_hooks: List[Any] = []
        nbytes = 0
        read_bytes: List[Tuple[int, int]] = []
        dtypes: List[str] = []
        for pos, f in enumerate(flows):
            if f is None:
                steps.append((ABSENT, pos, 0, None))
            elif isinstance(f, type):
                steps.append((VALUE, pos, 0, None))
            elif isinstance(f, str):
                continue  # "ctl" and the like: no argument
            elif f[0] == "scratch":
                steps.append((SCRATCH, pos, 0, (f[1], f[2])))
            else:
                unborn = f[0] == "unborn"
                shape, dtype, mode, *hooks = f[1:] if unborn else f
                si, so = hooks or (None, None)
                access = int(mode) & _INOUT
                if si is not None:
                    # the body would compute on the PACKED representation
                    # and the commit would take it for the home-layout
                    # tile — silently wrong; loud is the contract
                    how = UNPAIRED if access & _OUT and so is None \
                        else HOOKED
                    extra = si
                elif not (unborn or access == _OUT):
                    how, extra = READ, None
                elif shape is None:
                    how, extra = LATE, None
                else:
                    how, extra = PLACEHOLDER, jax.ShapeDtypeStruct(
                        shape, np.dtype(dtype))
                if how == READ and access & _OUT and pos in donate \
                        and shape is not None and dtype is not None:
                    donates.append((pos, len(steps), len(out_hooks)))
                steps.append((how, pos, access, extra))
                dtypes.append("?" if dtype is None else np.dtype(dtype).name)
                if shape is not None and dtype is not None:
                    # (a tile counts at its own precision's bytes)
                    tile = int(np.prod(shape)) * np.dtype(dtype).itemsize
                    # a tile read takes a buffer, a tile written a new one
                    nbytes += tile * ((how == READ) + bool(access & _OUT))
                    if how == READ and not access & _OUT:
                        read_bytes.append((pos, tile))
                if access & _OUT:
                    out_hooks.append(so)
        self.steps = tuple(steps)
        #: outputs a task's commit takes
        self.nout = sum(1 for s in steps if s[2] & _OUT)
        #: positions of the tiles a task needs resident before it runs
        #: (a hooked flow's packed layout is the hook's business)
        self.reads = tuple(s[1] for s in steps if s[0] == READ)
        self.out_hooks = tuple(out_hooks) if any(out_hooks) else None
        #: device bytes one task's tiles take, read and written (where
        #: the signature says their shapes): the most a task can cost its
        #: wave's chunk (``TpuDevice._submit_wave``)
        self.nbytes = nbytes
        #: ``(position, bytes)`` of the tiles ``nbytes`` counts that are
        #: read and not written: what a task costs its chunk LESS where
        #: such a tile was born on the device and is there still
        #: (``TpuDevice._born_here``; a read-write tile keeps
        #: counting twice: its input is what a donation gives back, and
        #: the count does not know of one)
        self.read_bytes = tuple(read_bytes)
        #: the tile flows' precisions in order, for the program's span
        #: (no comma: an event's arguments are a comma-separated list)
        self.dtypes = "/".join(dtypes)
        self.donates = tuple(donates)


class ValuePlan:
    """The calling convention of one device program: which positions of
    a task's argument list are arguments of the program (``keep``), and
    how every other position is rebuilt inside the trace."""

    __slots__ = ("routes", "keep", "int_at", "float_at", "dropped",
                 "packed", "positional", "tiles_dropped", "tag", "outs")

    def __init__(self, body, args: Sequence[Any], nvalues: int):
        """``args``: one task's staged argument list (``TpuDevice.
        _stage_chunk``); ``nvalues``: how many of them are value specs.
        A Python scalar among ``args`` can only be a value."""
        scalars = [i for i, a in enumerate(args)
                   if type(a) in (int, float, bool)]
        unborn = [i for i, a in enumerate(args)
                  if isinstance(a, jax.ShapeDtypeStruct)]
        tiles = [i for i, a in enumerate(args) if isinstance(a, jax.Array)]
        #: ``(shape, dtype)`` of the body's outputs (None: not traced)
        read, self.outs = _read_leaves(body, args) if scalars or tiles \
            else ((), None)
        #: per position: None (an argument of the program), or (how,
        #: index in its vector | placeholder)
        routes: List[Any] = [None] * len(args)
        int_at: List[int] = []
        float_at: List[int] = []
        for i in scalars:
            t = type(args[i])
            if not read[i]:
                routes[i] = (_DROP, t())
            elif t is float:
                routes[i] = (_FLOAT, len(float_at))
                float_at.append(i)
            else:
                routes[i] = (_BOOL if t is bool else _INT, len(int_at))
                int_at.append(i)
        for i in unborn:
            routes[i] = (_UNBORN, args[i])
        for i in tiles:
            if not read[i]:
                routes[i] = (_UNREAD, jax.ShapeDtypeStruct(
                    args[i].shape, args[i].dtype))
        self.routes = tuple(routes)
        #: positions that ride the integer / the floating vector
        self.int_at, self.float_at = tuple(int_at), tuple(float_at)
        self.keep = tuple(i for i, r in enumerate(routes) if r is None)
        self.packed = len(int_at) + len(float_at)
        self.dropped = len(scalars) - self.packed
        self.positional = nvalues - len(scalars)
        #: tile arguments that are no argument of the program
        self.tiles_dropped = sum(
            1 for r in routes if r and r[0] in (_UNBORN, _UNREAD))
        #: part of the program's content key: an executable stored for
        #: another argument list is never loaded for this one.  Empty
        #: when every argument is passed as it always was.
        #: (A tile that is no argument is not in the call's signature:
        #: its shape and dtype, which the zeros inside the trace take,
        #: are named here.)
        self.tag = ("vargs", "".join(
            r[0] if r else "-" for r in routes)) if any(routes) else ()
        if self.tiles_dropped:
            self.tag += tuple(
                (tuple(r[1].shape), str(r[1].dtype)) for r in routes
                if r and r[0] in (_UNBORN, _UNREAD))

    def flatten(self, tasks_args: Sequence[Sequence[Any]]) -> List[Any]:
        """The program's argument list for these tasks: each task's kept
        arguments in order, then the integer vector, then the floating
        one (each only if the plan packs such values)."""
        keep = self.keep
        flat = [a[i] for a in tasks_args for i in keep]
        for at, pytype in ((self.int_at, int), (self.float_at, float)):
            if at:
                # the dtype such a scalar traces to under the process's
                # x64 setting; an int beyond it raises here as it did
                # at the call
                flat.append(np.array(
                    [a[i] for a in tasks_args for i in at],
                    dtype=jax.dtypes.canonicalize_dtype(pytype)))
        return flat

    def bodies_args(self, flat: Sequence[Any],
                    ntasks: int) -> Iterator[List[Any]]:
        """Inside the trace: each task's full argument list, rebuilt
        from the program's arguments."""
        nkeep, nint, nfloat = (len(self.keep), len(self.int_at),
                               len(self.float_at))
        vecs = flat[ntasks * nkeep:]
        ivec = vecs[0] if nint else None
        fvec = vecs[-1] if nfloat else None
        for t in range(ntasks):
            kept = iter(flat[t * nkeep:(t + 1) * nkeep])
            args = []
            for how, x in (r or (None, None) for r in self.routes):
                if how is None:
                    args.append(next(kept))
                elif how == _DROP:
                    args.append(x)
                elif how in (_UNBORN, _UNREAD):
                    args.append(jnp.zeros(x.shape, x.dtype))
                elif how == _FLOAT:
                    args.append(_weak(fvec[t * nfloat + x]))
                elif how == _BOOL:  # a Python bool traces strong-typed
                    args.append(ivec[t * nint + x] != 0)
                else:
                    args.append(_weak(ivec[t * nint + x]))
            yield args

    def stacked_args(self, flat: Sequence[Any], ntasks: int) -> List[Any]:
        """Inside the trace of a program that runs its tasks as ONE
        batched call: per position, the tasks' arguments stacked along a
        new leading axis where they differ from task to task (an argument
        of the program, a packed value), and the one thing every task
        gets alike handed over once (a dropped value's placeholder, the
        zeros of a tile that is no argument, an absent flow)."""
        tasks = list(self.bodies_args(flat, ntasks))
        return [tasks[0][i]
                if tasks[0][i] is None
                or (r is not None and r[0] in (_DROP, _UNBORN, _UNREAD))
                else jnp.stack([args[i] for args in tasks])
                for i, r in enumerate(self.routes)]

    def donate(self, argnums: Sequence[int],
               ntasks: int = 1) -> Tuple[int, ...]:
        """The donated positions of a program of ``ntasks`` tasks, in
        its argument list: every task's ``argnums`` (positions of its
        staged argument list) that are arguments of the program."""
        keep = self.keep
        one = [keep.index(i) for i in argnums if i in keep]
        return tuple(t * len(keep) + k for t in range(ntasks) for k in one)

    def aliased(self, args: Sequence[Any], donates) -> List[int]:
        """Of a :class:`FlowPlan`'s ``donates``, the positions in the
        staged argument list ``args`` whose tile the body's matching
        output can be written over: the same shape and dtype (anything
        else XLA could alias to nothing, and the input would die for no
        buffer saved)."""
        outs = self.outs or ()
        return [ai for (_pos, ai, oi) in donates
                if oi < len(outs) and outs[oi] == (
                    tuple(args[ai].shape), args[ai].dtype)]


def _weak(x):
    """``x`` as the weak-typed scalar a Python number traces to."""
    return lax.convert_element_type_p.bind(
        x, new_dtype=x.dtype, weak_type=True, sharding=None)
