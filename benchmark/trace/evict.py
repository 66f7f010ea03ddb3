"""The ``parsec:dev:evict`` spans of a traced run: what the device
module notes on an eviction (``victims``, ``dirty``, ``bytes_home``,
``wait_us``; ``docs/TRACING.md``), summed inside the ``bench:solve``
windows.  ``spans.Summary`` keeps no span's arguments, so this reads the
trace's spans itself (``spans.load``), once per process.  A trace
without the span (a program from before it, or a solve that evicted
nothing) gives ``None`` and the readers leave their metrics out."""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

from benchmark.trace import reduce as tr
from benchmark.trace import spans

SPAN = "dev:evict"


@dataclasses.dataclass
class Evictions:
    solves: int
    spans: int        # evictions that found victims, all solves
    victims: int
    dirty: int        # of them written home first
    bytes_home: int
    wait_us: int      # of the write-backs' collects
    total_ns: int     # the spans' own duration


def summarize(trace: spans.Trace) -> Optional[Evictions]:
    """``None`` for a trace with no ``dev:evict`` span in its solves."""
    if not trace.windows:
        return None
    mine = [sp for sp in spans.clip_spans(trace.spans, trace.windows)
            if sp.name == SPAN]
    if not mine:
        return None

    def total(key: str) -> int:
        return sum(int(sp.args.get(key, 0)) for sp in mine)
    return Evictions(
        solves=len(trace.windows),
        spans=sum(int(sp.args.get("victims", 0)) > 0 for sp in mine),
        victims=total("victims"), dirty=total("dirty"),
        bytes_home=total("bytes_home"), wait_us=total("wait_us"),
        total_ns=sum(sp.end - sp.start for sp in mine))


_parsed: Dict[Tuple[str, float], Optional[Evictions]] = {}


def of_run(run) -> Optional[Evictions]:
    """The eviction summary of a traced run's own trace; ``None`` for an
    untraced run, a trace that is not there, or a program without the
    span."""
    if not run.trace:
        return None
    try:
        path = tr.find_xplane(spans.trace_dir(run.cell.name))
    except RuntimeError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed[key] = summarize(spans.load(path))
    return _parsed[key]


if __name__ == "__main__":
    import sys

    print(summarize(spans.load(sys.argv[1])))
