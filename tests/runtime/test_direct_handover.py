"""The hand-over of a task that a device manager releases (PR 40).

A thread that is an accelerator device's manager completes tasks inside
``_manager_loop``; what it releases and only its device can run stays
with the device (``TpuDevice.keep_released``: ``handed_direct``) and the
manager queues it itself between two drains, everything else goes
through the scheduler (``handed_sched``), and the manager never keeps a
successor in its own ``es.next_task``.  CPU backend: the same machinery as on a chip.
"""

import itertools
import sys
import threading

import numpy as np
import pytest

from parsec_tpu import (Chore, Context, DEV_CPU, DEV_TPU, HookReturn, Task,
                        TaskClass, Taskpool)
from parsec_tpu.core import scheduling
from parsec_tpu.core.lifecycle import AccessMode
from parsec_tpu.data import data_create
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl import DTDTaskpool
from parsec_tpu.dsl.dtd import stage_to_cpu
from parsec_tpu.ops import cholesky_dtd
from parsec_tpu.profiling import pins

INOUT = AccessMode.INOUT
_ids = itertools.count()


def tpu_dev(ctx):
    return next(d for d in ctx.devices if d.device_type == DEV_TPU)


def accel_hook(es, task):
    return task.selected_device.kernel_scheduler(es, task)


def device_chore(fn):
    chore = Chore(DEV_TPU, accel_hook)
    chore.body_fn = fn
    return chore


def handed(dev):
    return dev.stats["handed_direct"], dev.stats["handed_sched"]


class Fan:
    """A hand-built pool through the raw core: a root, ``width`` middles
    that each add 1 to a tile of their own, all released by the root;
    each middle releases a tail over the same tile.  Every class has a
    device chore; ``cpu_beside`` gives the tails a CPU chore as well,
    ``opaque`` makes them CPU tasks with an opaque ``body_args`` (what a
    DTD comm task is)."""

    def __init__(self, width, tails="device", prepare=None):
        self.width = width
        self.tp = tp = Taskpool("fan", nb_tasks=1 + 2 * width)
        self.tiles = [data_create(("fan", next(_ids)),
                                  payload=np.zeros(8, np.float32))
                      for _ in range(width + 1)]
        self.ran_on_cpu = []
        root = TaskClass("root", chores=[device_chore(lambda x: x + 1.0)])
        mid = TaskClass("mid", chores=[device_chore(lambda x: x + 1.0)])
        tail_chores = [device_chore(lambda x: x + 1.0)]

        def cpu_tail(es, task):
            self.ran_on_cpu.append(task.locals[0])
            return HookReturn.DONE

        if tails == "cpu_beside":
            tail_chores.append(Chore(DEV_CPU, cpu_tail))
        elif tails == "opaque":
            tail_chores = [Chore(DEV_CPU, cpu_tail)]
        tail = TaskClass("tail", chores=tail_chores)
        tail.prepare_input = prepare
        self.classes = {"root": root, "mid": mid, "tail": tail}

        def make(tc, k, tile):
            t = Task(tp, tc, (k,))
            t.body_args = ("src", "dst") if tc is tail and tails == "opaque" \
                else [("data", tile, INOUT)]
            return t

        root.release_deps = lambda es, task: [
            make(mid, k, self.tiles[k + 1]) for k in range(width)]
        mid.release_deps = lambda es, task: [
            make(tail, task.locals[0], self.tiles[task.locals[0] + 1])]
        for tc in (root, mid, tail):
            tp.add_task_class(tc)
        tp.startup_hook = lambda ctx, tp_: [make(root, 0, self.tiles[0])]


@pytest.fixture
def selected():
    """Every task a ``core:select`` returned."""
    got = []

    def on_select(es, task):
        if task is not None:
            got.append(task)

    pins.subscribe(pins.SELECT_END, on_select)
    yield got
    pins.unsubscribe(pins.SELECT_END, on_select)


@pytest.fixture
def parked():
    """``es.next_task`` as every ``core:schedule`` left it: on a thread
    that manages a device, and on the others."""
    seen = {"manager": [], "worker": []}

    def on_schedule_end(es, batch):
        if es is not None:
            who = "manager" if es.managing is not None else "worker"
            seen[who].append(es.next_task)

    pins.subscribe(pins.SCHEDULE_END, on_schedule_end)
    yield seen
    pins.unsubscribe(pins.SCHEDULE_END, on_schedule_end)


# -- the direct route --------------------------------------------------------

@pytest.mark.parametrize("nb_cores", [1, 4])
def test_a_device_only_dag_never_goes_back_to_the_scheduler(
        nb_cores, selected, parked):
    width = 12
    with Context(nb_cores=nb_cores) as ctx:
        dev = tpu_dev(ctx)
        fan = Fan(width)
        ctx.add_taskpool(fan.tp)
        assert ctx.wait(timeout=120)
        # all but the initially ready root: released by the manager
        assert handed(dev) == (2 * width, 0)
        assert dev.stats["executed_tasks"] == 1 + 2 * width
        for k in range(width):
            np.testing.assert_array_equal(stage_to_cpu(fan.tiles[k + 1]), 2.0)
    assert [t.task_class.name for t in selected] == ["root"]
    assert parked["manager"] and not any(parked["manager"])


def test_the_dtd_cholesky_is_handed_over_but_for_its_first_task(selected):
    """One core: nothing runs while the program inserts, so every task but
    ``potrf(0)`` is released by the thread that manages the device."""
    nt, nb = 6, 16
    n = nt * nb
    a = np.random.default_rng(3).random((n, n), dtype=np.float32) - 0.5
    M = (a + a.T) / 2 + np.float32(0.75 * n ** 0.5) * np.eye(n, dtype=np.float32)
    with Context(nb_cores=1) as ctx:
        dev = tpu_dev(ctx)
        A = TiledMatrix(n, n, nb, nb, name="A",
                        dtype=np.float32).from_array(M.copy())
        tp = DTDTaskpool(ctx)
        ntasks = cholesky_dtd(tp, A, use_tpu=True, use_cpu=False)
        assert tp.wait(timeout=300)
        tp.flush_all(A)
        tp.close()
        assert handed(dev) == (ntasks - 1, 0)
    assert len(selected) == 1 and selected[0].task_class.name == "potrf"


# -- what still goes through the scheduler -----------------------------------

@pytest.mark.parametrize("tails", ["cpu_beside", "opaque"])
def test_a_task_a_cpu_can_run_goes_through_the_scheduler(tails, selected):
    width = 6
    with Context(nb_cores=2) as ctx:
        dev = tpu_dev(ctx)
        fan = Fan(width, tails=tails)
        ctx.add_taskpool(fan.tp)
        assert ctx.wait(timeout=120)
        # the middles directly, every tail through a worker
        assert handed(dev) == (width, width)
    assert sorted(t.task_class.name for t in selected) == \
        ["root"] + ["tail"] * width
    if tails == "opaque":
        assert sorted(fan.ran_on_cpu) == list(range(width))


def test_a_manager_parks_nothing_and_a_cpu_worker_still_keeps_its_next(
        parked):
    """The kept-next fast path is the workers': a CPU chain runs through
    it, a device manager's completions never use it."""
    with Context(nb_cores=2) as ctx:
        fan = Fan(8, tails="cpu_beside")
        ctx.add_taskpool(fan.tp)
        assert ctx.wait(timeout=120)
        tp = Taskpool("chain", nb_tasks=20)
        step = TaskClass("step", chores=[
            Chore(DEV_CPU, lambda es, task: HookReturn.DONE)])
        step.release_deps = lambda es, task: (
            [Task(tp, step, (task.locals[0] + 1,))]
            if task.locals[0] + 1 < 20 else [])
        tp.add_task_class(step)
        tp.startup_hook = lambda ctx_, tp_: [Task(tp_, step, (0,))]
        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=60)
    assert parked["manager"] and not any(parked["manager"])
    assert sum(t is not None for t in parked["worker"]) >= 19


# -- prepare_input on the direct route ---------------------------------------

def test_a_raising_prepare_input_fails_the_successors_pool():
    calls = []

    def prepare(es, task):
        calls.append(task.locals[0])
        raise RuntimeError("no input for this one")

    with Context(nb_cores=2) as ctx:
        dev = tpu_dev(ctx)
        fan = Fan(1, prepare=prepare)
        done = []
        task_done = fan.tp.task_done
        fan.tp.task_done = lambda task=None: (done.append(task),
                                              task_done(task))[1]
        ctx.add_taskpool(fan.tp)
        assert fan.tp.wait(timeout=60) is False
        assert fan.tp.failed
        # the successor's own failure, not the completing task's epilog
        assert "body raised" in fan.tp.fail_reason
        assert "epilog" not in fan.tp.fail_reason
        assert calls == [0]
        assert dev.stats["handed_direct"] == 2
        # root, the middle that released the tail, and the tail itself:
        # each retired once
        names = [t.task_class.name for t in done]
        assert sorted(names) == ["mid", "root", "tail"]
        assert all(t.retired for t in done if t.task_class.name != "tail")


@pytest.mark.parametrize("first", [HookReturn.AGAIN, HookReturn.ASYNC])
def test_prepare_input_again_and_async_are_honoured(first):
    """As on a worker: AGAIN pushes the task to the scheduler at a
    distance, ASYNC leaves it to whoever schedules it again."""
    calls = []
    waiting = []
    asked = threading.Event()

    def prepare(es, task):
        calls.append(es.managing is not None)
        if len(calls) == 1:
            if first == HookReturn.ASYNC:
                waiting.append(task)
                asked.set()
            return first
        return HookReturn.DONE

    with Context(nb_cores=2) as ctx:
        dev = tpu_dev(ctx)
        fan = Fan(1, prepare=prepare)
        ctx.add_taskpool(fan.tp)
        ctx.start()
        if first == HookReturn.ASYNC:
            assert asked.wait(60)
            assert not fan.tp.wait(timeout=0.2)  # nobody runs it meanwhile
            ctx.schedule(waiting)
        assert fan.tp.wait(timeout=60)
        # asked first on the manager's thread, then on a worker's
        assert calls == [True, False]
        assert handed(dev) == (2, 0)
        assert dev.stats["executed_tasks"] == 3
        np.testing.assert_array_equal(stage_to_cpu(fan.tiles[1]), 2.0)


# -- a failed pool -----------------------------------------------------------

def test_a_failed_pools_released_tasks_are_dropped_not_queued():
    with Context(nb_cores=1) as ctx:
        dev = tpu_dev(ctx)
        fan = Fan(4)
        ctx.add_taskpool(fan.tp)
        assert ctx.wait(timeout=60)
        before = dev.stats["executed_tasks"]
        # a manager releases tasks of a pool that has failed meanwhile
        es = ctx.streams[0]
        pool = Taskpool("gone", nb_tasks=3)
        pool.failed = True
        late = [Task(pool, fan.classes["mid"], (k,)) for k in range(3)]
        for t in late:
            t.body_args = [("data", fan.tiles[0], INOUT)]
        es.managing = dev
        try:
            scheduling.schedule_ready(ctx, es, late)
        finally:
            es.managing = None
        assert len(dev._pending) == 0
        assert es.next_task is None
        assert dev.stats["handed_sched"] == 3
        # ... and the scheduler's copies are discarded at selection
        assert ctx._next_task(es) is None
        assert dev.stats["executed_tasks"] == before


# -- the wave set is a function of the DAG -----------------------------------

def _dtd_solve(ctx, M, nb, window):
    dev = tpu_dev(ctx)
    n = M.shape[0]
    A = TiledMatrix(n, n, nb, nb, name="A",
                    dtype=np.float32).from_array(M.copy())
    before = dict(dev.stats)
    tp = DTDTaskpool(ctx)
    tp.window, tp.threshold = window, window // 2
    cholesky_dtd(tp, A, use_tpu=True, use_cpu=False)
    assert tp.wait(timeout=300)
    tp.flush_all(A)
    tp.close()
    grew = {k: dev.stats[k] - before.get(k, 0)
            for k in ("wave_submits", "wave_tasks", "executed_tasks",
                      "handed_direct", "handed_sched")}
    return np.tril(A.to_array()), grew


@pytest.mark.parametrize("n,nb,window", [(256, 32, 16), (512, 64, 32)])
def test_the_dtd_factor_and_its_wave_programs_repeat(monkeypatch, n, nb,
                                                     window):
    """``tests/dsl/test_dtd_potrf.py``'s sizes: five solves form the same
    wave programs and give the same bits, and the factor is bit for bit
    the one the scheduler's route gives."""
    rng = np.random.default_rng(n)
    a = rng.random((n, n), dtype=np.float32) - np.float32(0.5)
    M = (a + a.T) / 2 + np.float32(0.75 * np.sqrt(n)) * np.eye(
        n, dtype=np.float32)
    with Context(nb_cores=1) as ctx:
        runs = [_dtd_solve(ctx, M, nb, window) for _ in range(5)]
        first, grew = runs[0]
        assert grew["handed_direct"] > 0
        for L, g in runs[1:]:
            assert g == grew
            assert np.array_equal(L, first)
        # the parent's route: every released task through the scheduler
        monkeypatch.setattr(type(tpu_dev(ctx)), "keep_released",
                            lambda self, task: False)
        by_sched, g = _dtd_solve(ctx, M, nb, window)
        assert g["handed_direct"] == 0
        assert np.array_equal(by_sched, first)


# -- workers and the manager at the same queue --------------------------------

def test_workers_and_the_manager_queue_side_by_side():
    """More threads than cores and a short switch interval: the inserting
    thread and the workers queue ready tasks under the device's lock while
    the manager queues what it released without one; every task runs once
    and the factor is the one-core factor."""
    n, nb, window = 512, 32, 64
    rng = np.random.default_rng(40)
    a = rng.random((n, n), dtype=np.float32) - np.float32(0.5)
    M = (a + a.T) / 2 + np.float32(0.75 * np.sqrt(n)) * np.eye(
        n, dtype=np.float32)
    with Context(nb_cores=1) as ctx:
        alone, grew = _dtd_solve(ctx, M, nb, window)
    ntasks = grew["executed_tasks"]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with Context(nb_cores=8) as ctx:
            for _ in range(3):
                L, g = _dtd_solve(ctx, M, nb, window)
                assert g["executed_tasks"] == ntasks
                assert 0 < g["handed_direct"] < ntasks
                assert g["handed_sched"] == 0
                assert np.array_equal(L, alone)
            assert not tpu_dev(ctx)._pending and not tpu_dev(ctx)._released
    finally:
        sys.setswitchinterval(interval)
