"""``lfq`` — local flat queues with stealing (the default scheduler).

Reference: ``/root/reference/parsec/mca/sched/lfq`` — per-thread bounded
hierarchical buffers (``hbbuffer``) with NUMA-ordered stealing and a global
overflow dequeue (``sched_local_queues_utils.h:22-36``).

Here: per-worker deque used LIFO by its owner (cache affinity), FIFO by
stealers; a bounded local capacity spills to a shared global deque, which is
also where ``distance > 0`` schedules land directly.
"""

from __future__ import annotations

import collections
import threading
from typing import List, Optional

from ...utils import register_component
from .base import Scheduler

#: max tasks in a worker-local queue before spilling to the global dequeue
LOCAL_CAP = 256


@register_component("sched")
class SchedLFQ(Scheduler):
    mca_name = "lfq"
    mca_priority = 20

    def install(self, context) -> None:
        super().install(context)
        self._locals: List[collections.deque] = []
        self._local_locks: List[threading.Lock] = []
        self._global: collections.deque = collections.deque()
        self._global_lock = threading.Lock()
        for _ in range(context.nb_workers):
            self._locals.append(collections.deque())
            self._local_locks.append(threading.Lock())
        #: steal order per worker: nearest neighbours first (ring distance
        #: stands in for the reference's NUMA hierarchy)
        n = context.nb_workers
        self._steal_order = [
            [(i + d) % n for d in range(1, n)] for i in range(n)
        ]

    def schedule(self, es, tasks, distance: int = 0) -> None:
        if not tasks:
            return
        # priority-sort within the batch like hbbuffer's sorted push
        if len(tasks) > 1:
            tasks = sorted(tasks, key=lambda t: -t.priority)
        i = es.worker_id if es is not None else 0
        if distance == 0 and es is not None and i < len(self._locals):
            dq, lk = self._locals[i], self._local_locks[i]
            with lk:
                room = LOCAL_CAP - len(dq)
                take = tasks[:room] if room > 0 else []
                for t in reversed(take):
                    dq.appendleft(t)  # LIFO end
            spill = tasks[len(take):] if take else tasks
        else:
            spill = tasks
        if spill:
            with self._global_lock:
                self._global.extend(spill)

    def select(self, es) -> Optional["object"]:
        i = es.worker_id
        dq, lk = self._locals[i], self._local_locks[i]
        with lk:
            if dq:
                return dq.popleft()  # own LIFO end
        # global overflow next (tasks explicitly pushed far)
        with self._global_lock:
            if self._global:
                return self._global.popleft()
        # steal: FIFO end of victims, nearest first
        for v in self._steal_order[i]:
            vdq, vlk = self._locals[v], self._local_locks[v]
            if not vdq:
                continue
            with vlk:
                if vdq:
                    es.stats["steals"] += 1
                    return vdq.pop()  # victim's FIFO end
        return None

    def pending_estimate(self) -> int:
        return len(self._global) + sum(len(d) for d in self._locals)

    def remove(self, context) -> None:
        self._locals.clear()
        self._global.clear()
