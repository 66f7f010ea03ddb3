"""Compound taskpools: sequential composition via on_complete chaining.

Reference: ``/root/reference/parsec/compound.c`` (``parsec_compose`` :96) —
a compound taskpool runs its members one after another; member *i+1* is
enqueued when member *i* terminates.
"""

from __future__ import annotations

from typing import List, Optional

from .taskpool import Taskpool


class CompoundTaskpool(Taskpool):
    def __init__(self, *members: Taskpool, name: str = "compound"):
        super().__init__(name=name)
        self.taskpool_type = Taskpool.TYPE_COMPOUND
        self.members: List[Taskpool] = list(members)
        self._next = 0
        # compound owns one synthetic "task" per member so the local termdet
        # fires only after the last member finishes
        self.tdm.taskpool_set_nb_tasks(self, len(self.members))

    def add(self, tp: Taskpool) -> "CompoundTaskpool":
        self.members.append(tp)
        self.tdm.taskpool_addto_nb_tasks(self, 1)
        return self

    def attached(self, context) -> None:
        # base attach does the bookkeeping (context, progress baseline;
        # the _known_nb_tasks branch is a no-op here — the member count
        # was set in __init__), then the first member launches
        super().attached(context)
        self._launch_next()

    def startup(self, context):
        return []

    def _launch_next(self) -> None:
        if self._next >= len(self.members):
            return
        member = self.members[self._next]
        self._next += 1
        # serving-plane identity propagates to members at launch: the
        # compound may have been submitted through a RuntimeService
        # (tenant + composed priority base set at admission) AFTER
        # construction, so member tasks inherit the tenant's fairness
        # weight / job priority and the per-tenant observability slices
        # (scheduler bins, trace tenant tags, progress()) see them
        if self.tenant is not None:
            member.tenant = self.tenant
            member.tenant_weight = self.tenant_weight
            member.job_priority = self.job_priority
            member.priority_base = self.priority_base
        prev_cb = member.on_complete

        def chain(tp, _prev=prev_cb):
            if _prev is not None:
                _prev(tp)
            # retire through task_done (not a bare tdm decrement): the
            # health plane's progress()/watchdog read nb_retired, and a
            # compound that never counts retirements reads as "0/N tasks
            # retired, never released" in a stall diagnosis
            self.task_done()
            self._launch_next()

        member.on_complete = chain
        assert self.context is not None
        self.context.add_taskpool(member)


def compose(a: Taskpool, b: Taskpool, *more: Taskpool) -> CompoundTaskpool:
    """Reference ``parsec_compose(compound.c:96)``: folds compounds
    (``compose(a, b, c)`` is ``compose(compose(a, b), c)``)."""
    if not isinstance(a, CompoundTaskpool):
        a = CompoundTaskpool(a)
    for tp in (b, *more):
        a.add(tp)
    return a
