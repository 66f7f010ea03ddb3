"""What the runtime's threads waited for in a traced run, and how long
they were off the CPU inside each span.

Since PR 34 every ``parsec:*`` event of a profiler session carries
``cpu_us``, the CPU time of its thread between begin and end
(``parsec_tpu.profiling.pins``): an event's duration minus ``cpu_us`` is
the time the thread was OFF the CPU inside it — blocked in a call, or
waiting for a lock or for the GIL.  And the places where a thread can do
nothing but wait are events of their own, ``parsec-wait:<what>``
(``pins.wait`` / ``pins.held``; ``docs/TRACING.md`` "Waits"):
``res_lock`` and ``dev_lock`` with the ``holder``'s span, ``wb_capacity``
with ``pending_mb``, ``d2h_start`` with ``n`` and ``bytes``.  They are NOT
``parsec:*`` events, so ``spans.py`` does not see them and its self
times are what they were; here they are loaded beside the spans, under
the names ``wait:<what>``, as the children they are.

* **window, clipping, nesting, the idlest chip**: ``spans.py``'s, by
  import.  A span cut at a window's edge keeps the same share of its
  ``cpu_us`` as of its duration.
* **timed spans**: the thread-CPU clock is read within a budget
  (``pins._cpu_tree``: every span where a read costs 0.25 us; whole trees
  of spans for 0.5% of the wall time where it costs 17 us, as on the
  benchmark's machine), so only SOME events carry ``cpu_us``: a span
  does if and only if its parent does.  Every number made of CPU time is
  a ratio over the timed spans alone; a wait's own time, which is wall
  time, is of every wait.  On that machine the clock also ticks at 10
  ms: one span's ``cpu_us`` is 0 or 10,000 there, and a sum means
  something from a few hundred ms on (its error: the square root of its
  ticks).
* **self off-CPU time**: a span's duration minus its ``cpu_us``, minus
  the same of its children on the thread — waits included, so what is
  left to a span that calls nothing which blocks is its wait for the GIL
  (and whatever else took the CPU from the thread).
* **submitting threads**: the host lines that carry ``dev:submit_batch``,
  ``dev:wave`` or ``dev:submit_one`` in the window: the pump, or the
  workers that were device manager.  A per-task or per-program number is
  over every rank's; a number of ONE thread's time (``copy_start_s``,
  ``gil_wait_pct``, ``idle_in_wait_pct``) is of the submitting threads of
  the idlest chip's rank (rank r drives chip r), whose idle time
  ``spans.idle_of`` reads.

A trace whose ``parsec:*`` spans carry no ``cpu_us`` (a program from
before PR 34) gives ``None`` and the five readers leave their metrics
out; a program that has the code and waited for nothing reads 0.0.
``python3 -m benchmark.trace.waits <.xplane.pb> [chips]`` prints the
waits by ``what`` and ``holder``, each span's wall / CPU / off-CPU self
time, what the spans that waited were made of, and the GIL share by
thread.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

from benchmark.trace import reduce as tr
from benchmark.trace import spans

WAIT_PREFIX = "parsec-wait:"
WAIT = "wait:"                      # the name a wait is loaded under
SUBMITTING = ("dev:submit_batch", "dev:wave", "dev:submit_one")
# spans in which the thread calls something that blocks by design: their
# off-CPU time is no wait for the GIL
BLOCKING = {"dev:dispatch", "dev:h2d", "dev:block", "dev:poll",
            "dev:writeback", "dev:flush", "dev:detach", "pump:stage_wait",
            "cc:compile"}
BLOCKING_PREFIXES = ("comm:", WAIT)


@dataclasses.dataclass
class Span(spans.Span):
    #: of its thread, inside the window; ``None``: the span is not timed
    cpu_ns: Optional[float] = None
    self_cpu_ns: float = 0.0   # without its children's (timed spans)

    @property
    def timed(self) -> bool:
        return self.cpu_ns is not None

    @property
    def off_ns(self) -> float:
        return self.end - self.start - self.cpu_ns

    @property
    def self_off_ns(self) -> float:
        return self.self_ns - self.self_cpu_ns


def load(path: str) -> spans.Trace:
    """``spans.load`` with the ``parsec-wait:*`` events among the spans,
    named ``wait:<what>``: one pass over the ``.xplane.pb``."""
    from jax.profiler import ProfileData

    found: List[spans.Span] = []
    windows: List[tr.Interval] = []
    device: Dict[int, List[tr.Interval]] = {}
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            chip = int(plane.name[len(tr.DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    device.setdefault(chip, []).extend(
                        (s, e) for _, s, e in tr._events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread += 1
                for ev in line.events:
                    name = ev.name
                    if name == tr.WINDOW_SPAN:
                        s = int(ev.start_ns)
                        windows.append((s, s + int(ev.duration_ns)))
                        continue
                    if name.startswith(spans.PREFIX):
                        name = name[len(spans.PREFIX):]
                    elif name.startswith(WAIT_PREFIX):
                        name = WAIT + name[len(WAIT_PREFIX):]
                    else:
                        continue
                    s = int(ev.start_ns)
                    found.append(spans.Span(
                        name, s, s + int(ev.duration_ns), thread,
                        dict(ev.stats)))
    return spans.Trace(found, tr.merge(windows), device)


def is_wait(name: str) -> bool:
    return name.startswith(WAIT)


def blocks(name: str) -> bool:
    """Whether the thread calls, in this span itself, something that
    blocks by design."""
    return name in BLOCKING or name.startswith(BLOCKING_PREFIXES)


def nested(trace: spans.Trace) -> Optional[List[Span]]:
    """The spans and waits inside the windows, nested per thread, each
    with its CPU time and its self CPU time; ``None`` where no
    ``parsec:*`` span carries ``cpu_us``."""
    if not any("cpu_us" in sp.args for sp in trace.spans
               if not is_wait(sp.name)):
        return None
    out: List[Span] = []
    for sp in trace.spans:
        whole = sp.end - sp.start
        cpu_us = sp.args.get("cpu_us")
        for c in spans.clip_spans([sp], trace.windows):
            out.append(Span(
                c.name, c.start, c.end, c.thread, c.args,
                cpu_ns=None if cpu_us is None or not whole
                else float(cpu_us) * 1e3 * (c.end - c.start) / whole))
    out = spans.nest(out)
    for sp in out:
        if sp.timed:
            sp.self_cpu_ns += sp.cpu_ns
            if sp.parent is not None and sp.parent.timed:
                sp.parent.self_cpu_ns -= sp.cpu_ns
    return out


def submitting_threads(nest: List[Span]) -> Dict[int, int]:
    """Thread -> the rank it submits for."""
    return {sp.thread: int(sp.args.get("rank", 0)) for sp in nest
            if sp.name in SUBMITTING}


def innermost_waits(nest: List[Span], threads) -> List[tr.Interval]:
    """The stretches in which the innermost event of one of ``threads``
    is a wait, merged."""
    children: Dict[int, List[tr.Interval]] = {}
    for sp in nest:
        if sp.parent is not None and is_wait(sp.parent.name):
            children.setdefault(id(sp.parent), []).append((sp.start, sp.end))
    own: List[tr.Interval] = []
    for sp in nest:
        if is_wait(sp.name) and sp.thread in threads:
            own.extend(spans.subtract(
                [(sp.start, sp.end)], tr.merge(children.get(id(sp), []))))
    return tr.merge(own)


@dataclasses.dataclass
class Waits:
    solves: int
    tasks: float                # per solve, as ``spans.Summary`` counts
    programs: float             # per solve: ``dev:dispatch`` spans
    chip: int                   # the idlest of the chips used
    rank: int                   # the rank that drives it
    wait_ns: Dict[str, int]     # what -> Σ on every submitting thread
    own_wait_ns: Dict[str, int]  # what -> Σ on the idlest chip's
    dispatch_off_ns: float      # Σ duration - cpu_us of the TIMED
    dispatch_timed: int         # ``dev:dispatch`` spans, and their number
    gil_off_ns: float           # self off-CPU of the timed spans that call
    gil_wall_ns: float          # nothing that blocks, and their self time
    idle_ns: int                # of the idlest chip
    idle_wait_ns: int           # of it, under an innermost wait

    @property
    def res_lock_wait_us_per_task(self) -> float:
        return (self.wait_ns.get("res_lock", 0) / 1e3
                / (self.tasks * self.solves))

    @property
    def copy_start_s(self) -> float:
        return self.own_wait_ns.get("d2h_start", 0) / 1e9 / self.solves

    @property
    def dispatch_blocked_us_per_program(self) -> float:
        if not self.dispatch_timed:
            return 0.0
        return max(0.0, self.dispatch_off_ns) / 1e3 / self.dispatch_timed

    @property
    def gil_wait_pct(self) -> float:
        if self.gil_wall_ns <= 0:
            return 0.0
        return 100.0 * max(0.0, self.gil_off_ns) / self.gil_wall_ns

    @property
    def idle_in_wait_pct(self) -> float:
        return 100.0 * self.idle_wait_ns / self.idle_ns \
            if self.idle_ns else 0.0


def gil_of(nest: List[Span], threads) -> Tuple[float, float]:
    """``(self off-CPU, self wall)`` of the timed spans of ``threads`` in
    which the thread calls nothing that blocks by design."""
    off = wall = 0.0
    for sp in nest:
        if sp.timed and sp.thread in threads and not blocks(sp.name):
            off += sp.self_off_ns
            wall += sp.self_ns
    return off, wall


def summarize(trace: spans.Trace, chips: int,
              nest: Optional[List[Span]] = None) -> Optional[Waits]:
    """``None`` where there is nothing to read: spans without ``cpu_us``
    (or none at all), or no task taken in the window.  ``nest`` is
    :func:`nested` of the trace, for a caller that has it."""
    if not trace.windows:
        raise RuntimeError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    if nest is None:
        nest = nested(trace)
    if nest is None:
        return None
    tasks = sum(int(sp.args.get("n", 1)) for sp in nest
                if sp.name in spans.TASK_SPANS)
    programs = sum(sp.name == "dev:dispatch" for sp in nest)
    if not tasks or not programs:
        return None
    chip, idle = spans.idle_of(trace, chips)
    rank = sorted(trace.device)[:chips].index(chip)
    threads = submitting_threads(nest)
    own = {t for t, r in threads.items() if r == rank} if chips > 1 \
        else set(threads)
    wait_ns: Dict[str, int] = {}
    own_wait_ns: Dict[str, int] = {}
    for sp in nest:
        if is_wait(sp.name) and sp.thread in threads:
            what = sp.name[len(WAIT):]
            wait_ns[what] = wait_ns.get(what, 0) + sp.end - sp.start
            if sp.thread in own:
                own_wait_ns[what] = own_wait_ns.get(what, 0) \
                    + sp.end - sp.start
    gil_off, gil_wall = gil_of(nest, own)
    idle = tr.merge(idle)
    solves = len(trace.windows)
    dispatched = [sp for sp in nest if sp.name == "dev:dispatch" and sp.timed]
    return Waits(
        solves=solves, tasks=tasks / solves, programs=programs / solves,
        chip=chip, rank=rank, wait_ns=wait_ns, own_wait_ns=own_wait_ns,
        dispatch_off_ns=sum(sp.off_ns for sp in dispatched),
        dispatch_timed=len(dispatched),
        gil_off_ns=gil_off, gil_wall_ns=gil_wall,
        idle_ns=tr.length(idle),
        idle_wait_ns=tr.length(spans.intersect(
            idle, innermost_waits(nest, own))))


_parsed: Dict[Tuple[str, float], Optional[Waits]] = {}


def of_run(run) -> Optional[Waits]:
    """The waits of a traced run's own trace, parsed once per process;
    ``None`` for an untraced run, a trace that is not there, or a
    program whose spans carry no ``cpu_us``."""
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(spans.trace_dir(run.cell.name))
    except RuntimeError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed[key] = summarize(load(path), run.cell.chips)
    return _parsed[key]


# ---------------------------------------------------------------------------
# for a reader of one trace: python3 -m benchmark.trace.waits <.xplane.pb>
# ---------------------------------------------------------------------------

def _ms(ns: float, solves: int) -> str:
    return f"{ns / 1e6 / solves:>11.3f}"


def report(trace: spans.Trace, chips: int) -> str:
    nest = nested(trace) if trace.windows else None
    w = summarize(trace, chips, nest)
    if w is None:
        return ("nothing to read: no parsec:* span of the bench:solve "
                "windows carries cpu_us (a program from before PR 34), or "
                "none took a task")
    threads = submitting_threads(nest)
    n = w.solves
    wall = sum(sp.self_ns for sp in nest)
    timed = sum(sp.self_ns for sp in nest if sp.timed)
    out = [f"{n} solves, {w.tasks:g} tasks and {w.programs:g} device "
           f"programs a solve; the idlest chip is {w.chip} (rank {w.rank})",
           f"timed (cpu_us read, within the clock's budget): "
           f"{sum(sp.timed for sp in nest)} of {len(nest)} events, "
           f"{100 * timed / wall if wall else 0:.1f}% of the spans' time; "
           "every CPU and off-CPU time below is of the timed spans ALONE, "
           "every wall time of all", "",
           "res_lock_wait_us_per_task %.3f, copy_start_s %.4f, "
           "dispatch_blocked_us_per_program %.1f (of %d timed programs), "
           "gil_wait_pct %.2f, idle_in_wait_pct %.2f" % (
               w.res_lock_wait_us_per_task, w.copy_start_s,
               w.dispatch_blocked_us_per_program, w.dispatch_timed,
               w.gil_wait_pct, w.idle_in_wait_pct)]

    # the waits, by what, where and for whom
    rows: Dict[Tuple[str, str, str, str], List[float]] = {}
    for sp in nest:
        if not is_wait(sp.name):
            continue
        where = "submitting" if sp.thread in threads else "other"
        holder = str(sp.args.get("holder", "-"))
        under = sp.parent.name if sp.parent is not None else "-"
        row = rows.setdefault((sp.name, where, holder, under),
                              [0, 0.0, 0, 0])
        row[0] += 1
        row[1] += sp.end - sp.start
        row[2] += int(sp.args.get("n", 0))
        row[3] += int(sp.args.get("bytes", 0))
    out += ["", f"{'wait':<18}{'thread':<12}{'holder':<16}{'under':<18}"
            f"{'per solve':>10}{'wall ms':>11}"
            "   (a solve; n and MB where the wait carries them)"]
    for key in sorted(rows, key=lambda k: -rows[k][1]):
        cnt, ns, nn, nbytes = rows[key]
        extra = f"   n={nn / n:g} MB={nbytes / 1e6 / n:.1f}" if nn else ""
        out.append(f"{key[0]:<18}{key[1]:<12}{key[2]:<16}{key[3]:<18}"
                   f"{cnt / n:>10.1f}{_ms(ns, n)}{extra}")
    if not rows:
        out.append("(no thread waited)")

    # each span's self time, and of its timed part the split
    for title, keep in (("submitting threads", True),
                        ("other threads", False)):
        by: Dict[str, List[float]] = {}
        for sp in nest:
            if (sp.thread in threads) is keep:
                row = by.setdefault(sp.name, [0, 0.0, 0.0, 0.0])
                row[0] += 1
                row[1] += sp.self_ns
                if sp.timed:
                    row[2] += sp.self_ns
                    row[3] += sp.self_cpu_ns
        out += ["", f"{title:<22}{'per solve':>10}{'self ms':>11}"
                f"{'timed ms':>11}{'CPU ms':>11}{'off-CPU ms':>11}"
                f"{'off %':>8}"]
        for name in sorted(by, key=lambda k: -by[k][1]):
            cnt, ns, tns, cpu = by[name]
            out.append(f"{name:<22}{cnt / n:>10.1f}{_ms(ns, n)}{_ms(tns, n)}"
                       f"{_ms(cpu, n)}{_ms(tns - cpu, n)}"
                       f"{100 * (tns - cpu) / tns if tns else 0:>8.1f}")

    # a span that waited: what `spans.py` reads as its self time is the
    # waits under it, its own CPU time and its own time off the CPU
    made: Dict[str, Dict[str, float]] = {}
    for sp in nest:
        if not sp.timed or sp.thread not in threads:
            continue
        if not is_wait(sp.name):
            m = made.setdefault(sp.name, {})
            for k, v in (("cpu", sp.self_cpu_ns), ("off", sp.self_off_ns),
                         ("wall", sp.end - sp.start), ("allcpu", sp.cpu_ns)):
                m[k] = m.get(k, 0.0) + v
        elif sp.parent is not None:
            m = made.setdefault(sp.parent.name, {})
            m[sp.name] = m.get(sp.name, 0.0) + sp.end - sp.start
            m["waits_off"] = m.get("waits_off", 0.0) + sp.off_ns
    out += ["", "timed spans of the submitting threads that waited (ms a "
            "solve): self time as spans.py reads it = the waits under the "
            "span + its own CPU + its own off-CPU (the remainder: the GIL);",
            "wall - cpu_us of the whole span = the waits' off-CPU + the "
            "other children's off-CPU + the same remainder"]
    for name, m in sorted(made.items()):
        kinds = sorted(k for k in m if is_wait(k))
        if not kinds:
            continue
        waits_wall = sum(m[k] for k in kinds)
        off_all = m["wall"] - m["allcpu"]
        out.append(
            f"  {name}: self {(waits_wall + m['cpu'] + m['off']) / 1e6 / n:.3f}"
            " = " + " + ".join(f"{k} {m[k] / 1e6 / n:.3f}" for k in kinds)
            + f" + CPU {m['cpu'] / 1e6 / n:.3f}"
            f" + off-CPU {m['off'] / 1e6 / n:.3f}")
        out.append(
            f"  {'':<{len(name)}}  wall {m['wall'] / 1e6 / n:.3f} - cpu "
            f"{m['allcpu'] / 1e6 / n:.3f} = {off_all / 1e6 / n:.3f} = waits "
            f"{m['waits_off'] / 1e6 / n:.3f} + other children "
            f"{(off_all - m['waits_off'] - m['off']) / 1e6 / n:.3f}"
            f" + remainder {m['off'] / 1e6 / n:.3f}")

    # the GIL, by thread
    out += ["", "off-CPU share of the self time of the timed spans that "
            "call nothing which blocks (gil_wait_pct), by submitting "
            "thread:",
            f"  {'thread':>6}{'rank':>6}{'tasks':>10}{'timed ms':>11}"
            f"{'off-CPU ms':>11}{'gil %':>8}"]
    for t in sorted(threads, key=lambda t: (threads[t], t)):
        off, ns = gil_of(nest, {t})
        took = sum(int(sp.args.get("n", 1)) for sp in nest
                   if sp.thread == t and sp.name in spans.TASK_SPANS)
        out.append(f"  {t:>6}{threads[t]:>6}{took / n:>10.1f}{_ms(ns, n)}"
                   f"{_ms(off, n)}{100 * off / ns if ns else 0:>8.2f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(report(load(sys.argv[1]),
                 int(sys.argv[2]) if len(sys.argv) > 2 else 1))
