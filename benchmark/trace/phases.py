"""Where the device module's host time goes INSIDE its spans: the laps.

``submit_us_per_task`` is one number over the self time of six spans
(``spans.SUBMIT_SPANS``).  Since PR 48 five of them carry ``laps``
(``parsec_tpu.profiling.pins._Span.lap``; the names, their order and
what each holds are ``docs/TRACING.md``'s table and its section "Laps"):
``laps="walk:41200/put:3000/sole:800/own:6500"``, nanoseconds, in the
order the stretches ran, the first from the span's own start.  A lap is a
field of a span that is there, so ``spans.py`` and ``waits.py`` read what
they read; here the laps are laid over their span as intervals on the
profiler's clock, and what a child span or a ``parsec-wait:*`` event
covers of a lap is taken out of it, as self time does.

* **window, clipping, nesting, the idlest chip**: ``spans.py``'s, by
  import; the events are ``waits.load``'s (the spans and the waits).  A
  lap is laid from its span's UNCLIPPED start and then cut at the
  window's edges.
* **own time** of a lap: its interval less every direct child of its
  span, waits included.  ``waits`` is what the ``wait:*`` children took
  of it.  A lap whose name recurs in one span (a chunk that a refused
  donation split into several programs) stands again in ``laps``, is
  laid where it ran, and sums under its name.
* **the drain and the hand-over** of the ``Context`` path run under no
  span; their time is stamped on the first ``dev:wave`` /
  ``dev:submit_one`` of the drain (``hand_us`` with ``handed``,
  ``units_us``) and read from there.  For the idle time under them they
  are laid immediately before that span.
* **cover**: the laps under the six spans, waits left IN (the spans' self
  time, ``submit_us_per_task``'s numerator, has them in), over that self
  time from the same trace.  ``dev:jit`` has no lap: its self time is
  the remainder's largest named part.
* **idle under a lap**: the idle time of the idlest chip
  (``spans.idle_of``) inside the lap's own intervals on the submitting
  threads of that chip's rank: ``idle_in_submit_pct`` cut by lap.

A trace whose spans carry no ``laps`` (a program from before PR 48) gives
``None`` and the eleven readers leave their metrics out.
``python3 -m benchmark.trace.phases <.xplane.pb> [chips]`` prints, a lap:
microseconds a task and a program, seconds a solve, the waits under it
and the idle seconds under it; then the eleven metrics and what no lap
covers, by span.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

from benchmark.trace import reduce as tr
from benchmark.trace import spans
from benchmark.trace import waits

#: a lap's key: (the span it is a field of, its name); ``dev:wave`` and
#: ``dev:submit_one`` are one span here: a program's
Lap = Tuple[str, str]
PROGRAM = "dev:wave"
SPAN_OF = {"dev:submit_one": PROGRAM}
#: the laps in the order ``docs/TRACING.md`` lists them (the report's)
LAPS: Dict[str, Tuple[str, ...]] = {
    "dev:submit_batch": ("units", "waves", "retry"),
    PROGRAM: ("room", "stage", "key", "flatten", "call", "count", "commit"),
    "dev:stage_args": ("walk", "put", "sole", "own"),
    "dev:epilog": ("hooks", "commit", "settle", "home", "zeros", "complete")}
#: what the drain's stamps are laid as, for the idle time under them
DRAIN = "(drain)"


def parse_laps(text: str) -> List[Tuple[str, int]]:
    """``"walk:41200/put:3000"`` -> ``[("walk", 41200), ("put", 3000)]``."""
    out = []
    for part in str(text).split("/"):
        name, _, ns = part.rpartition(":")
        if name:
            out.append((name, int(ns)))
    return out


def lay(sp: spans.Span) -> List[Tuple[str, tr.Interval]]:
    """The laps of a span as intervals from its start, none past its end."""
    at = sp.start
    out = []
    for name, ns in parse_laps(sp.args["laps"]):
        end = min(at + ns, sp.end)
        out.append((name, (at, end)))
        at = end
    return out


@dataclasses.dataclass
class Phases:
    solves: int
    tasks: float                  # per solve, as ``spans.Summary`` counts
    programs: float               # per solve: ``dev:dispatch`` spans
    count: Dict[Lap, int]         # spans that carry the lap, all solves
    own_ns: Dict[Lap, int]        # children and waits taken out
    wait_ns: Dict[Lap, int]       # what the waits took of it
    idle_ns: Dict[Lap, int]       # idle time of the idlest chip under it
    self_ns: Dict[str, int]       # ``spans.SUBMIT_SPANS``: self time
    units_us: float               # Σ of the stamps, all solves
    hand_us: float
    handed: int
    stamped: int                  # task spans that carry a stamp
    idle_total_ns: int

    def _own(self, span: str, *names: str) -> int:
        return sum(self.own_ns.get((span, n), 0) for n in names)

    def _per_task(self, ns: float) -> float:
        return ns / 1e3 / (self.tasks * self.solves)

    @property
    def submit_units_us_per_task(self) -> float:
        return self._per_task(
            self._own("dev:submit_batch", "units", "waves", "retry")
            + self.units_us * 1e3)

    @property
    def units_alone_us_per_task(self) -> float:
        """``_units_of`` itself: the pump's lap and the manager's stamp."""
        return self._per_task(self._own("dev:submit_batch", "units")
                              + self.units_us * 1e3)

    @property
    def submit_key_us_per_task(self) -> float:
        return self._per_task(self._own(PROGRAM, "room", "key")
                              + self.self_ns.get("dev:jit", 0))

    @property
    def submit_flatten_us_per_task(self) -> float:
        return self._per_task(self._own(PROGRAM, "flatten"))

    @property
    def submit_count_us_per_task(self) -> float:
        return self._per_task(self._own(PROGRAM, "count"))

    @property
    def stage_walk_us_per_task(self) -> float:
        return self._per_task(self._own("dev:stage_args", "walk"))

    @property
    def stage_own_us_per_task(self) -> float:
        return self._per_task(self._own("dev:stage_args", "put", "sole",
                                        "own"))

    @property
    def epilog_commit_us_per_task(self) -> float:
        return self._per_task(self._own("dev:epilog", "hooks", "commit",
                                        "settle"))

    @property
    def epilog_home_us_per_task(self) -> float:
        return self._per_task(self._own("dev:epilog", "home", "zeros"))

    @property
    def epilog_complete_us_per_task(self) -> float:
        return self._per_task(self._own("dev:epilog", "complete"))

    @property
    def handover_us_per_task(self) -> Optional[float]:
        """``None`` where no span carries a stamp (the pump path) or the
        managers handed nothing over."""
        return self.hand_us / self.handed if self.handed else None

    @property
    def covered_ns(self) -> Dict[str, int]:
        """Span name -> its laps, waits left in (``dev:wave`` and
        ``dev:submit_one`` together under ``dev:wave``)."""
        out: Dict[str, int] = {}
        for part in (self.own_ns, self.wait_ns):
            for (span, _name), ns in part.items():
                out[span] = out.get(span, 0) + ns
        return out

    @property
    def submit_laps_cover_pct(self) -> float:
        total = sum(self.self_ns.get(k, 0) for k in spans.SUBMIT_SPANS)
        return 100.0 * sum(self.covered_ns.values()) / total if total \
            else 0.0


def summarize(trace: spans.Trace, chips: int) -> Optional[Phases]:
    """``None`` where there is nothing to read: no span carries ``laps``,
    or none took a task in the window.  ``trace`` is ``waits.load``'s."""
    if not trace.windows:
        raise RuntimeError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    if not any("laps" in sp.args for sp in trace.spans):
        return None
    own = [sp for sp in trace.spans if not waits.is_wait(sp.name)]
    inside = spans.nest(spans.clip_spans(own, trace.windows))
    tasks = sum(int(sp.args.get("n", 1)) for sp in inside
                if sp.name in spans.TASK_SPANS)
    programs = sum(sp.name == "dev:dispatch" for sp in inside)
    if not tasks or not programs:
        return None
    self_ns: Dict[str, int] = {}
    for sp in inside:
        if sp.name in spans.SUBMIT_SPANS:
            self_ns[sp.name] = self_ns.get(sp.name, 0) + sp.self_ns

    chip, idle = spans.idle_of(trace, chips)
    idle = tr.merge(idle)
    rank = sorted(trace.device)[:chips].index(chip)
    # the spans as they ran (a lap is laid from the unclipped start), with
    # the waits among them as the children they are
    whole = spans.nest([dataclasses.replace(sp) for sp in trace.spans])
    threads = waits.submitting_threads(inside)
    mine = {t for t, r in threads.items() if r == rank} if chips > 1 \
        else set(threads)
    children: Dict[int, List[spans.Span]] = {}
    for sp in whole:
        if sp.parent is not None:
            children.setdefault(id(sp.parent), []).append(sp)

    count: Dict[Lap, int] = {}
    own_ns: Dict[Lap, int] = {}
    wait_ns: Dict[Lap, int] = {}
    under: Dict[Lap, List[tr.Interval]] = {}
    units_us = hand_us = 0.0
    handed = stamped = 0

    for sp in whole:
        if "laps" in sp.args:
            name = SPAN_OF.get(sp.name, sp.name)
            kids = children.get(id(sp), [])
            covered = tr.merge([(k.start, k.end) for k in kids])
            waited = tr.merge([(k.start, k.end) for k in kids
                               if waits.is_wait(k.name)])
            for lap, stretch in lay(sp):
                stretch = tr.clip([stretch], trace.windows)
                if not stretch:
                    continue
                key = (name, lap)
                count[key] = count.get(key, 0) + 1
                left = spans.subtract(stretch, covered)
                own_ns[key] = own_ns.get(key, 0) + tr.length(left)
                wait_ns[key] = wait_ns.get(key, 0) + tr.length(
                    spans.intersect(stretch, waited))
                if sp.thread in mine:
                    under.setdefault(key, []).extend(left)
        if "hand_us" in sp.args and sp.name in spans.TASK_SPANS \
                and tr.clip([(sp.start, sp.end)], trace.windows):
            stamped += 1
            units = float(sp.args.get("units_us", 0.0))
            hand = float(sp.args["hand_us"])
            units_us += units
            hand_us += hand
            handed += int(sp.args.get("handed", 0))
            mid = sp.start - int(units * 1e3)
            # (laid for the idle time under them alone: their time is
            # the stamps')
            for lap, stretch in (("units_us", (mid, sp.start)),
                                 ("hand_us", (mid - int(hand * 1e3), mid))):
                if sp.thread in mine:
                    under.setdefault((DRAIN, lap), []).extend(
                        tr.clip([stretch], trace.windows))
    solves = len(trace.windows)
    return Phases(
        solves=solves, tasks=tasks / solves, programs=programs / solves,
        count=count, own_ns=own_ns, wait_ns=wait_ns,
        idle_ns={key: tr.length(spans.intersect(idle, tr.merge(v)))
                 for key, v in under.items()},
        self_ns=self_ns, units_us=units_us, hand_us=hand_us, handed=handed,
        stamped=stamped, idle_total_ns=tr.length(idle))


_parsed: Dict[Tuple[str, float], Optional[Phases]] = {}


def of_run(run) -> Optional[Phases]:
    """The laps of a traced run's own trace, parsed once per process;
    ``None`` for an untraced run, a trace that is not there, or a program
    whose spans carry no ``laps``."""
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(spans.trace_dir(run.cell.name))
    except RuntimeError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed[key] = summarize(waits.load(path), run.cell.chips)
    return _parsed[key]


METRICS = ("submit_units_us_per_task", "submit_key_us_per_task",
           "submit_flatten_us_per_task", "submit_count_us_per_task",
           "stage_walk_us_per_task", "stage_own_us_per_task",
           "epilog_commit_us_per_task", "epilog_home_us_per_task",
           "epilog_complete_us_per_task", "handover_us_per_task",
           "submit_laps_cover_pct")


# ---------------------------------------------------------------------------
# for a reader of one trace: python3 -m benchmark.trace.phases <.xplane.pb>
# ---------------------------------------------------------------------------

def report(trace: spans.Trace, chips: int) -> str:
    p = summarize(trace, chips) if trace.windows else None
    if p is None:
        return ("nothing to read: no parsec:* span of the bench:solve "
                "windows carries laps (a program from before PR 48), or "
                "none took a task")
    n = p.solves
    every = p.tasks * n
    out = [f"{n} solves, {p.tasks:g} tasks and {p.programs:g} device "
           f"programs a solve; idle time of the idlest chip "
           f"{p.idle_total_ns / 1e9 / n:.4f} s a solve", "",
           f"{'span':<18}{'lap':<10}{'per solve':>10}{'us/task':>10}"
           f"{'us/program':>12}{'s/solve':>10}{'waits s':>10}"
           f"{'idle s':>10}   (own time: children and waits out; idle: "
           "of the idlest chip, under it)"]
    for span, names in LAPS.items():
        seen = [k[1] for k in p.own_ns if k[0] == span]
        for lap in [x for x in names if x in seen] \
                + sorted(set(seen) - set(names)):
            key = (span, lap)
            ns = p.own_ns[key]
            out.append(
                f"{span:<18}{lap:<10}{p.count[key] / n:>10.1f}"
                f"{ns / 1e3 / every:>10.2f}"
                f"{ns / 1e3 / (p.programs * n):>12.1f}"
                f"{ns / 1e9 / n:>10.4f}"
                f"{p.wait_ns.get(key, 0) / 1e9 / n:>10.4f}"
                f"{p.idle_ns.get(key, 0) / 1e9 / n:>10.4f}")
    if p.stamped:
        out += ["", f"the managers' loop, stamped on {p.stamped / n:.1f} "
                "task spans a solve (laid before them for the idle time):"]
        for lap, us in (("hand_us", p.hand_us), ("units_us", p.units_us)):
            out.append(
                f"{DRAIN:<18}{lap:<10}{p.stamped / n:>10.1f}"
                f"{us / every:>10.2f}{us / (p.programs * n):>12.1f}"
                f"{us / 1e6 / n:>10.4f}{'':>10}"
                f"{p.idle_ns.get((DRAIN, lap), 0) / 1e9 / n:>10.4f}")
        out.append(f"{p.handed / n:g} tasks handed over a solve")
    out += ["", "the metrics:"]
    for m in METRICS:
        v = getattr(p, m)
        out.append(f"  {m:<30}" + ("nothing to read" if v is None
                                   else f"{v:.2f}"))
    out.append(f"  {'(units alone)':<30}{p.units_alone_us_per_task:.2f}")
    covered = p.covered_ns
    out += ["", "what no lap covers of the six spans' self time "
            "(us a task):"]
    for name in spans.SUBMIT_SPANS:
        mine = p.self_ns.get(name, 0)
        if name == "dev:submit_one":
            continue   # (its laps are counted with dev:wave's)
        if name == PROGRAM:
            mine += p.self_ns.get("dev:submit_one", 0)
        left = mine - covered.get(name, 0)
        out.append(f"  {name:<18}self {mine / 1e3 / every:>8.2f}   "
                   f"not under a lap {left / 1e3 / every:>8.2f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(report(waits.load(sys.argv[1]),
                 int(sys.argv[2]) if len(sys.argv) > 2 else 1))
