"""The runtime's own spans in a traced run: self time per span, counts
from the spans' arguments, and the idle time of the chip cut among them.

The program enters ``jax.profiler.TraceAnnotation("parsec:<name>", ...)``
at its own phases (``parsec_tpu.profiling.pins.span``; the names and
arguments are listed in ``docs/TRACING.md``), so in a ``--trace 1`` run
they lie on the host lines of the ``.xplane.pb`` beside the benchmark's
``bench:*`` spans, on the clock of the device's ``XLA Ops`` line.
``reduce.load_events`` folds the host lines into one list of names; here
each line (one thread) is kept apart and the arguments are read.

``harness.Run`` carries no path, so the trace is found where
``harness.run_cell`` writes it, ``<root>/.bench_trace/<cell>``, and parsed
once per process.  A program without these spans (every commit before
PR 24) gives ``None`` and the readers leave their metrics out.

* **window**: the ``bench:solve`` spans, as in ``reduce``; spans are
  clipped to them.
* **self time**: a span's duration minus what its children on the same
  thread cover.
* **attribution of idle time**: each idle interval of the idlest chip
  (the busy union of ``reduce.summarize``) is cut among the spans that
  cover it: on every thread the innermost span counts, and where threads
  disagree the classes win in the order dispatch, submit, transfer,
  scheduler.  What no span covers is ``unattributed``; the five shares
  sum to 100.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark.trace import reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIX = "parsec:"

# the classes of the attribution, in the order in which they win
CLASSES = ("dispatch", "submit", "transfer", "sched")
CLASS_OF = {
    "dev:dispatch": "dispatch", "cc:compile": "dispatch",
    "dev:submit_batch": "submit", "dev:wave": "submit",
    "dev:submit_one": "submit", "dev:stage_args": "submit",
    "dev:jit": "submit", "dev:epilog": "submit", "dev:poll": "submit",
    "dev:block": "submit", "dev:flush": "submit", "dev:detach": "submit",
    "dev:stage_in": "transfer", "dev:writeback": "transfer",
    "dev:h2d": "transfer", "pump:stage_wait": "transfer",
    "comm:send": "transfer", "comm:recv": "transfer"}
# every other pump:*, core:* and attach:* span is the scheduler's

SCHED_PREFIXES = ("pump:", "core:")
SUBMIT_SPANS = ("dev:submit_batch", "dev:wave", "dev:submit_one",
                "dev:stage_args", "dev:jit", "dev:epilog")
TASK_SPANS = ("dev:wave", "dev:submit_one")   # their ``n``: tasks taken


@dataclasses.dataclass
class Span:
    name: str                 # without the ``parsec:`` prefix
    start: int                # ns
    end: int
    thread: int               # one host line of the trace
    args: Dict[str, Any]
    self_ns: int = 0
    parent: Optional["Span"] = None


@dataclasses.dataclass
class Trace:
    spans: List[Span]                       # the program's, unclipped
    windows: List[tr.Interval]              # merged ``bench:solve`` spans
    device: Dict[int, List[tr.Interval]]    # chip -> operations


def class_of(name: str) -> str:
    return CLASS_OF.get(name, "sched")


def load(path: str) -> Trace:
    """Every ``parsec:*`` span with its thread and arguments, the windows
    and the device operations of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
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
                    elif name.startswith(PREFIX):
                        s = int(ev.start_ns)
                        spans.append(Span(name[len(PREFIX):], s,
                                          s + int(ev.duration_ns), thread,
                                          dict(ev.stats)))
    return Trace(spans, tr.merge(windows), device)


def clip_spans(spans: List[Span], windows: List[tr.Interval]) -> List[Span]:
    """The parts of the spans inside the (disjoint) windows: a span that
    crosses a window's edge is cut there."""
    out = []
    for sp in spans:
        for s, e in tr.clip([(sp.start, sp.end)], windows):
            out.append(dataclasses.replace(sp, start=s, end=e))
    return out


def nest(spans: List[Span]) -> List[Span]:
    """Sets ``parent`` and ``self_ns`` of every span from the nesting on
    its thread (a child is cut to its parent); returns them sorted."""
    spans = sorted(spans, key=lambda sp: (sp.thread, sp.start, -sp.end))
    stack: List[Span] = []
    for sp in spans:
        while stack and (stack[-1].thread != sp.thread
                         or stack[-1].end <= sp.start):
            stack.pop()
        sp.parent = stack[-1] if stack else None
        if sp.parent is not None:
            sp.end = min(sp.end, sp.parent.end)
        sp.self_ns = sp.end - sp.start
        if sp.parent is not None:
            sp.parent.self_ns -= sp.self_ns
        stack.append(sp)
    return spans


def intersect(a: List[tr.Interval],
              b: List[tr.Interval]) -> List[tr.Interval]:
    """The parts of ``a`` inside ``b``; both merged (one sweep: a trace
    has thousands of idle gaps and of spans)."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[tr.Interval], b: List[tr.Interval]) -> List[tr.Interval]:
    """The parts of ``a`` outside ``b``; both merged."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        at, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if e > at:
            out.append((at, e))
    return out


def innermost(spans: List[Span]) -> Dict[str, List[tr.Interval]]:
    """Class -> the stretches in which a span of that class is the
    innermost of its thread, over all threads and merged.  ``spans`` come
    from :func:`nest`."""
    children: Dict[int, List[tr.Interval]] = {}
    for sp in spans:
        if sp.parent is not None:
            children.setdefault(id(sp.parent), []).append(
                (sp.start, sp.end))
    by_class: Dict[str, List[tr.Interval]] = {c: [] for c in CLASSES}
    for sp in spans:
        own = subtract([(sp.start, sp.end)],
                       tr.merge(children.get(id(sp), [])))
        by_class[class_of(sp.name)].extend(own)
    return {c: tr.merge(v) for c, v in by_class.items()}


def attribute(idle: List[tr.Interval],
              spans: List[Span]) -> Dict[str, int]:
    """Nanoseconds of ``idle`` (disjoint) under each class, each counted
    once: a stretch that spans of several classes cover on different
    threads goes to the first of :data:`CLASSES`."""
    covers = innermost(spans)
    left = tr.merge(idle)
    out = {}
    for c in CLASSES:
        under = intersect(left, covers[c])
        out[c] = tr.length(under)
        left = subtract(left, under)
    out["unattributed"] = tr.length(left)
    return out


@dataclasses.dataclass
class Summary:
    solves: int
    tasks: float                  # per solve, from the spans' own ``n``
    programs: float               # per solve: ``dev:dispatch`` spans
    self_ns: Dict[str, int]       # span name -> self time, all solves
    total_ns: Dict[str, int]      # span name -> duration, all solves
    waited_us: float              # the sum of ``waited_us``, all solves
    h2d_wait_ns: int              # ``dev:h2d`` under ``dev:stage_args``
    idle_ns: Dict[str, int]       # class -> idle time of the idlest chip

    def _per_task(self, ns: float) -> float:
        return ns / 1e3 / (self.tasks * self.solves)

    @property
    def sched_us_per_task(self) -> float:
        return self._per_task(sum(
            v for k, v in self.self_ns.items()
            if k.startswith(SCHED_PREFIXES) and k != "pump:stage_wait"))

    @property
    def submit_us_per_task(self) -> float:
        return self._per_task(sum(self.self_ns.get(k, 0)
                                  for k in SUBMIT_SPANS))

    @property
    def stage_wait_us_per_task(self) -> float:
        return self._per_task(self.total_ns.get("pump:stage_wait", 0)
                              + self.h2d_wait_ns)

    @property
    def queue_wait_us_per_task(self) -> float:
        return self.waited_us / (self.tasks * self.solves)

    @property
    def dispatch_us_per_program(self) -> float:
        return (self.total_ns.get("dev:dispatch", 0) / 1e3
                / (self.programs * self.solves))

    @property
    def writeback_s(self) -> float:
        return self.total_ns.get("dev:writeback", 0) / 1e9 / self.solves

    def idle_pct(self, cls: str) -> float:
        return 100.0 * self.idle_ns[cls] / sum(self.idle_ns.values())


def idle_of(trace: Trace, chips: int) -> Tuple[int, List[tr.Interval]]:
    """The idlest of the chips used and its idle intervals inside the
    windows: the busy union ``reduce.summarize`` takes."""
    used = sorted(trace.device)[:chips]
    if len(used) < chips:
        raise RuntimeError(f"the trace has device planes {used}, the cell "
                           f"uses {chips} chips")
    busy = {chip: tr.merge(tr.clip(trace.device[chip], trace.windows))
            for chip in used}
    idlest = min(used, key=lambda chip: tr.length(busy[chip]))
    return idlest, tr.gaps(busy[idlest], trace.windows)


def summarize(trace: Trace, chips: int) -> Optional[Summary]:
    """``None`` where the program has no spans (or took no task in the
    window): there is nothing to read."""
    if not trace.windows:
        raise RuntimeError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    spans = nest(clip_spans(trace.spans, trace.windows))
    tasks = sum(int(sp.args.get("n", 1)) for sp in spans
                if sp.name in TASK_SPANS)
    programs = sum(sp.name == "dev:dispatch" for sp in spans)
    if not tasks or not programs:
        return None
    idle = idle_of(trace, chips)[1]
    self_ns: Dict[str, int] = {}
    total_ns: Dict[str, int] = {}
    for sp in spans:
        self_ns[sp.name] = self_ns.get(sp.name, 0) + sp.self_ns
        total_ns[sp.name] = total_ns.get(sp.name, 0) + sp.end - sp.start
    solves = len(trace.windows)
    return Summary(
        solves=solves, tasks=tasks / solves, programs=programs / solves,
        self_ns=self_ns, total_ns=total_ns,
        waited_us=float(sum(sp.args.get("waited_us", 0) for sp in spans
                            if sp.name in TASK_SPANS)),
        h2d_wait_ns=sum(sp.end - sp.start for sp in spans
                        if sp.name == "dev:h2d" and sp.parent is not None
                        and sp.parent.name == "dev:stage_args"),
        idle_ns=attribute(idle, spans))


def trace_dir(cell_name: str) -> str:
    """Where ``harness.run_cell`` writes a cell's trace."""
    return os.path.join(ROOT, ".bench_trace", cell_name)


_parsed: Dict[Tuple[str, float], Optional[Summary]] = {}


def of_run(run) -> Optional[Summary]:
    """The summary of a traced run's own trace, parsed once per process;
    ``None`` for an untraced run, a trace that is not there, or a program
    without spans."""
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(trace_dir(run.cell.name))
    except RuntimeError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed[key] = summarize(load(path), run.cell.chips)
    return _parsed[key]


# ---------------------------------------------------------------------------
# for a reader of one trace: python3 -m benchmark.trace.spans <.xplane.pb>
# ---------------------------------------------------------------------------

def chain(sp: Span) -> str:
    """``dev:dispatch < dev:wave(cls=gemm n=64 ...) < dev:submit_batch(...)``:
    a span and what it is nested in, with their arguments (``pool`` and
    ``rank`` left out)."""
    parts = []
    while sp is not None:
        args = " ".join(f"{k}={v}" for k, v in sp.args.items()
                        if k not in ("pool", "rank"))
        parts.append(f"{sp.name}({args})" if args else sp.name)
        sp = sp.parent
    return " < ".join(parts)


def cover(gap: tr.Interval, spans: List[Span]) -> str:
    """The span chain that names an idle gap: the innermost span that
    covers at least half of it (as ``reduce.name_gap`` chooses), or
    failing that the span that covers most of it.  A ``dev:wave``'s
    staging is named with it, because its ``host_tiles`` say whether the
    program waited for tiles."""
    over = [(min(gap[1], sp.end) - max(gap[0], sp.start), sp)
            for sp in spans]
    over = [(o, sp) for o, sp in over if o > 0]
    if not over:
        return "no span"
    half = [(sp.end - sp.start, i) for i, (o, sp) in enumerate(over)
            if 2 * o >= gap[1] - gap[0]]
    sp = over[min(half)[1]][1] if half else max(
        over, key=lambda o_sp: o_sp[0])[1]
    text = chain(sp)
    wave = sp
    while wave is not None and wave.name not in TASK_SPANS:
        wave = wave.parent
    staged = [s for s in spans if s.parent is wave
              and s.name == "dev:stage_args"] if wave is not None else []
    if staged and sp is not staged[0]:
        text += f" ; its {chain(staged[0]).split(' < ')[0]}"
    return text


def report(trace: Trace, chips: int, top: int = 10) -> str:
    s = summarize(trace, chips)
    if s is None:
        return "no parsec:* span took a task in the bench:solve windows"
    spans = nest(clip_spans(trace.spans, trace.windows))
    counts: Dict[str, int] = {}
    for sp in spans:
        counts[sp.name] = counts.get(sp.name, 0) + 1
    out = [f"{s.solves} solves, {s.tasks:g} tasks and {s.programs:g} device "
           "programs a solve", "",
           f"{'span':<20}{'per solve':>10}{'total ms':>12}{'self ms':>12}"
           "   (a solve)"]
    for name in sorted(counts, key=lambda n: -s.self_ns[n]):
        out.append(f"{name:<20}{counts[name] / s.solves:>10.1f}"
                   f"{s.total_ns[name] / 1e6 / s.solves:>12.3f}"
                   f"{s.self_ns[name] / 1e6 / s.solves:>12.3f}")
    out += ["", "per task: sched %.1f us, submit %.1f us, stage wait %.1f "
            "us, queue wait %.1f us; dispatch %.1f us a program; "
            "write-back %.4f s a solve" % (
                s.sched_us_per_task, s.submit_us_per_task,
                s.stage_wait_us_per_task, s.queue_wait_us_per_task,
                s.dispatch_us_per_program, s.writeback_s),
            "idle time of the idlest chip, %.4f s a solve: " % (
                sum(s.idle_ns.values()) / 1e9 / s.solves)
            + ", ".join(f"{c} {s.idle_pct(c):.2f}%" for c in s.idle_ns)]
    idlest, idle = idle_of(trace, chips)
    gaps = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    out += ["", f"longest idle gaps of chip {idlest}:"]
    out += [f"  {(g[1] - g[0]) / 1e6:9.3f} ms  {cover(g, spans)}"
            for g in gaps]
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(report(load(sys.argv[1]),
                 int(sys.argv[2]) if len(sys.argv) > 2 else 1))
