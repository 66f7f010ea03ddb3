"""From the profiler's ``.xplane.pb`` to busy seconds, idle share, the
device operations that took most time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<chip>``; their ``XLA Ops`` line holds one event per
operation that ran on the chip.  Host planes (``/host:...``) hold the
benchmark's own ``bench:*`` annotations and the runtime's host events on
the same clock.  The traced window is the union of the ``bench:solve``
spans: the readings, without the checks between them.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # start_ns, end_ns
Event = Tuple[str, int, int]        # name, start_ns, end_ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:solve"


@dataclasses.dataclass
class Events:
    device: Dict[int, List[Event]]  # chip -> operations
    host: List[Event]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler left no .xplane.pb in {trace_dir}")
    return found[-1]


def load_events(path: str) -> Events:
    from jax.profiler import ProfileData

    device: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(chip, []).extend(
                        (op_name(n), s, e) for n, s, e in _events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Events(device=device, host=host)


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the operation
    without its number, so that one kind of operation sums up."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def merge(intervals: List[Interval]) -> List[Interval]:
    """The union of intervals as disjoint, sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], windows: List[Interval]) -> List[Interval]:
    """The parts of ``intervals`` inside the (disjoint) ``windows``."""
    out = []
    for s, e in intervals:
        for ws, we in windows:
            lo, hi = max(s, ws), min(e, we)
            if hi > lo:
                out.append((lo, hi))
    return out


def length(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], windows: List[Interval]) -> List[Interval]:
    """The idle stretches of each window; ``busy`` merged and clipped."""
    out = []
    for ws, we in windows:
        at = ws
        for s, e in busy:
            if e <= ws or s >= we:
                continue
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if we > at:
            out.append((at, we))
    return out


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: Interval, phases: List[Event],
             others: List[Event]) -> str:
    """``<phase>:<host event>``: the benchmark's span (``phases``, other
    than the window's own) and the runtime's host event (``others``)
    that cover it."""
    def best(events):
        """The innermost event that covers at least half of the gap, or
        failing that the one that covers most of it."""
        over = [(_overlap(gap, (s, e)), e - s, n) for n, s, e in events]
        over = [o for o in over if o[0] > 0]
        if not over:
            return "none"
        half = [o for o in over if 2 * o[0] >= gap[1] - gap[0]]
        if half:
            return min(half, key=lambda o: o[1])[2]
        return max(over)[2]

    phase = best(phases).replace("bench:", "")
    return f"{phase}:{best(others)}".replace(" ", "_")


@dataclasses.dataclass
class Summary:
    window_s: float                 # the traced window
    solves: int                     # bench:solve spans in it
    busy_by_chip: Dict[int, float]  # seconds an operation ran, per chip
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def busy_s(self) -> float:
        """Averaged over the chips used."""
        return sum(self.busy_by_chip.values()) / len(self.busy_by_chip)

    @property
    def idle_pct_worst(self) -> float:
        return 100.0 * (1.0 - min(self.busy_by_chip.values())
                        / self.window_s)

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def summarize(events: Events, chips: int, top: int = 10) -> Summary:
    windows = merge([(s, e) for n, s, e in events.host if n == WINDOW_SPAN])
    if not windows:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    used = sorted(events.device)[:chips]
    if len(used) < chips:
        raise RuntimeError(f"the trace has device planes {used}, the cell "
                           f"uses {chips} chips")
    busy: Dict[int, List[Interval]] = {}
    by_op: Dict[str, int] = {}
    for chip in used:
        ops = events.device[chip]
        busy[chip] = merge(clip([(s, e) for _, s, e in ops], windows))
        for name, s, e in ops:
            inside = length(clip([(s, e)], windows))
            if inside:
                by_op[name] = by_op.get(name, 0) + inside
    if not any(length(b) for b in busy.values()):
        raise RuntimeError("no operation ran on the device in the window")
    idlest = min(used, key=lambda chip: length(busy[chip]))
    longest = sorted(gaps(busy[idlest], windows),
                     key=lambda g: g[0] - g[1])[:top]
    phases = [ev for ev in events.host if ev[0].startswith("bench:")
              and not ev[0].startswith(WINDOW_SPAN)]
    others = [ev for ev in events.host if not ev[0].startswith("bench:")]
    return Summary(
        window_s=length(windows) / 1e9, solves=len(windows),
        busy_by_chip={chip: length(busy[chip]) / 1e9 for chip in used},
        device_ops=[(n, ns / 1e9) for n, ns in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[(name_gap(g, phases, others), (g[1] - g[0]) / 1e9)
                   for g in longest])
