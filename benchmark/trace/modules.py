"""Device time by PROGRAM in a traced run: which task class a stretch of
device time belongs to.

Beside its ``XLA Ops`` line a device plane of the ``.xplane.pb`` has an
``XLA Modules`` line: one event per execution of a compiled program,
named ``<module>(<fingerprint>)``.  The device module names a wave's
program after its task class (``jit__wave_tsmqr``) and a task alone runs
under its body's name (``jit_tsqrt_tpu``), so the events split the chip's
time by class without a span on the program's hot path
(``docs/TRACING.md``, "Device programs by class").  A program from before
that naming is ``jit__wave`` or ``jit_call`` and belongs to no class.

As in ``reduce`` the window is the union of the ``bench:solve`` spans and
events are clipped to it.  Parsed once per process; ``None`` where the
run was not traced or the trace is not there.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.trace import reduce as tr
from benchmark.trace import spans

MODULES_LINE = "XLA Modules"


def module_name(event_name: str) -> str:
    """``jit__wave_tsmqr(8391506378187674414)`` -> ``jit__wave_tsmqr``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def class_of(module: str, classes: Iterable[str]) -> Optional[str]:
    """The task class a module's name carries: ``jit__wave_<class>`` (a
    wave) or ``jit_<class>_...`` / ``jit_<class>`` (a task alone, named
    after its body); the longest class that fits."""
    for cls in sorted(classes, key=len, reverse=True):
        if module == f"jit__wave_{cls}" or module == f"jit_{cls}" \
                or module.startswith(f"jit_{cls}_"):
            return cls
    return None


@dataclasses.dataclass
class Modules:
    solves: int
    seconds: Dict[str, float]   # module name -> device seconds in the windows
    runs: Dict[str, int]        # module name -> executions in the windows

    def seconds_of(self, classes: Iterable[str],
                   among: Iterable[str]) -> Optional[float]:
        """Device seconds a solve of the programs of ``classes``, where
        ``among`` are all the DAG's classes; ``None`` if no program in
        the trace carries any of them."""
        classes, among = set(classes), list(among)
        hit = [m for m in self.seconds if class_of(m, among) in classes]
        if not hit:
            return None
        return sum(self.seconds[m] for m in hit) / self.solves


def load(path: str, chips: int = 1) -> Modules:
    from jax.profiler import ProfileData

    windows: List[tr.Interval] = []
    events: Dict[int, List[Tuple[str, int, int]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            chip = int(plane.name[len(tr.DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    events.setdefault(chip, []).extend(tr._events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                windows.extend((s, e) for n, s, e in tr._events(line)
                               if n == tr.WINDOW_SPAN)
    windows = tr.merge(windows)
    if not windows:
        raise RuntimeError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    seconds: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    for chip in sorted(events)[:chips]:
        for name, s, e in events[chip]:
            inside = tr.length(tr.clip([(s, e)], windows))
            if inside:
                m = module_name(name)
                seconds[m] = seconds.get(m, 0.0) + inside / 1e9
                runs[m] = runs.get(m, 0) + 1
    return Modules(solves=len(windows), seconds=seconds, runs=runs)


_parsed: Dict[Tuple[str, float], Modules] = {}


def of_run(run) -> Optional[Modules]:
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(spans.trace_dir(run.cell.name))
    except RuntimeError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed[key] = load(path, run.cell.chips)
    return _parsed[key]


if __name__ == "__main__":
    import sys

    m = load(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    print(f"{m.solves} solves; device seconds and executions a solve:")
    for name in sorted(m.seconds, key=lambda n: -m.seconds[n]):
        print(f"  {m.seconds[name] / m.solves:10.6f} s "
              f"{m.runs[name] / m.solves:9.1f}  {name}")
