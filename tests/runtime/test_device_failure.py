"""Device execution errors must fail the taskpool, never complete with
garbage.

Round-3 VERDICT Weak #2: a raising TPU submit used to log the error and
``complete_execution`` the task anyway — successors then consumed a
zeros-placeholder/stale tile and the pool quiesced "successfully" with
wrong numerics (the r03 driver artifact lost its entire panel stage to
exactly this).  The reference treats a hook ERROR as fatal
(``/root/reference/parsec/scheduling.c:512``).  The contract now:

* a transient submit error is retried ONCE with fresh state;
* a persistent error fails the pool — ``wait()`` returns False, and no
  successor of the failed task ever runs.
"""

import numpy as np
import pytest

from parsec_tpu import Context, DEV_CPU, DEV_TPU
from parsec_tpu.data import data_create
from parsec_tpu.dsl import DTDTaskpool, IN, INOUT


@pytest.fixture
def ctx():
    c = Context(nb_cores=2)
    yield c
    c.fini()


def tpu_dev(ctx):
    for d in ctx.devices:
        if d.device_type == DEV_TPU:
            return d
    pytest.skip("no jax device available")


def test_persistent_submit_failure_fails_pool(ctx):
    """A device body that always raises: the pool must FAIL (wait() ->
    False) and the downstream CPU successor must never observe the
    placeholder value."""
    tpu_dev(ctx)
    d = data_create("x", payload=np.full(8, 7.0))
    tp = DTDTaskpool(ctx)
    seen = []

    def ok_dev(x):
        return x + 1.0  # -> 8.0

    def broken_dev(x):
        raise RuntimeError("injected device failure")

    def consumer(x):
        seen.append(np.asarray(x).copy())

    tp.insert_task({DEV_TPU: ok_dev}, (d, INOUT))
    tp.insert_task({DEV_TPU: broken_dev}, (d, INOUT))
    tp.insert_task({DEV_CPU: consumer}, (d, IN))
    assert tp.wait(timeout=60) is False  # loud failure, prompt return
    assert tp.failed
    # the successor of the failed task never ran — no garbage consumed
    assert seen == []


def test_transient_submit_failure_retried_once(ctx):
    """The first submit raising (a transient PJRT error) must not zero the
    run: one retry with fresh state completes the task normally."""
    dev = tpu_dev(ctx)
    d = data_create("y", payload=np.full(8, 1.0))
    tp = DTDTaskpool(ctx)
    fails = [1]

    def flaky(x):
        if fails[0]:
            fails[0] -= 1
            raise RuntimeError("transient device error")
        return x + 2.0

    tp.insert_task({DEV_TPU: flaky}, (d, INOUT))
    assert tp.wait(timeout=60) is True
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(d), 3.0)
    assert dev.stats["executed_tasks"] == 1


def test_failure_mid_dag_leaves_prior_results_intact(ctx):
    """Tasks upstream of the failure complete normally; the failure only
    prevents the failed task's own successors."""
    tpu_dev(ctx)
    a = data_create("a", payload=np.full(4, 1.0))
    b = data_create("b", payload=np.full(4, 1.0))
    tp = DTDTaskpool(ctx)

    def inc(x):
        return x + 1.0

    def broken(x):
        raise RuntimeError("boom")

    tp.insert_task({DEV_TPU: inc}, (a, INOUT))   # independent, fine
    tp.insert_task({DEV_TPU: broken}, (b, INOUT))
    assert tp.wait(timeout=60) is False
    from parsec_tpu.dsl.dtd import stage_to_cpu

    np.testing.assert_allclose(stage_to_cpu(a), 2.0)
    # b's version never advanced: no placeholder was committed
    np.testing.assert_allclose(stage_to_cpu(b), 1.0)
