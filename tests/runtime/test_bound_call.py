"""A device program's call asks for its signature once
(``TpuDevice._dispatch``): an entry of ``_jit_cache`` whose local key
holds its arguments' signature keeps the executable its first call
resolved and calls it from then on; the cache's safety net (the plain
``jax.jit`` for what an exact key could not see, never a second run of a
donating program) stays where it was."""

import numpy as np
import pytest

import jax

from parsec_tpu import Context
from parsec_tpu.datadist import TiledMatrix
from parsec_tpu.dsl.native_exec import NativeExecutor

from tests.runtime.test_tpu_device import (_run_value_tasks, _value_tasks,
                                           tpu_dev)


@pytest.fixture
def dev():
    c = Context(nb_cores=1)
    try:
        yield tpu_dev(c)
    finally:
        c.fini()


def _rows(n, start=0):
    return [(np.full((8, 8), start + i, np.float32),
             np.ones((8, 8), np.float32), start + i) for i in range(n)]


def _body(x, o, i):
    return o + x


def _calls(dev):
    return dev.stats["calls_bound"], dev.stats["calls_signed"]


def _resolved(cache):
    """Executables compiled or loaded (the session's disk store may hold
    a program an earlier test compiled)."""
    return cache.stats["misses"] + cache.stats["hits_disk"]


class _Refusing:
    """Stands where an executable stood and refuses every call the way
    PJRT refuses arguments it was not compiled for."""

    def __init__(self, error):
        self.error = error
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        raise self.error


def _plant(dev, refusing):
    """``refusing`` in place of the one bound executable of ``dev``, in
    its entry and behind the signature in the cache's memo."""
    (key, (cf, plan, exe)), = dev._jit_cache.items()
    assert exe is not None
    dev._jit_cache[key] = (cf, plan, refusing)
    (sig, memo_exe), = cf._memo.items()
    assert memo_exe is exe
    cf._memo[sig] = refusing
    return key, cf


@pytest.mark.parametrize("alone", [False, True], ids=["wave4", "alone"])
def test_the_second_call_of_an_entry_is_bound_and_counts_a_hit(dev, alone):
    cache = dev._ccache
    programs = 4 if alone else 1
    outs = _run_value_tasks(dev, _value_tasks(_body, _rows(4)), alone)
    # tasks alone share one entry: the first binds it
    assert _calls(dev) == (programs - 1, 1)
    assert _resolved(cache) == 1
    hits = cache.stats["hits_mem"]
    assert hits == programs - 1
    (_cf, _plan, exe), = dev._jit_cache.values()
    assert isinstance(exe, jax.stages.Compiled)
    outs += _run_value_tasks(dev, _value_tasks(_body, _rows(4, start=4)),
                             alone)
    assert _calls(dev) == (2 * programs - 1, 1)
    # one hit a call that needed no compile, as the signed call counts
    assert cache.stats["hits_mem"] == hits + programs
    assert _resolved(cache) == 1
    assert cache.stats["aot_fallbacks"] == 0
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), i + 1.0)


def test_static_values_keep_the_signed_call(dev):
    """Their local key is ``(body, values)``: it says nothing of the
    shapes, so nothing is bound."""
    def body(x, o, k):
        return o + x * k
    body._static_values = True
    for shape in ((8, 8), (8, 8), (4, 4)):
        rows = [(np.ones(shape, np.float32), np.ones(shape, np.float32), 3)]
        out, = _run_value_tasks(dev, _value_tasks(body, rows), alone=True)
        np.testing.assert_array_equal(np.asarray(out), 4.0)
    assert _calls(dev) == (0, 3)
    (_cf, plan, exe), = dev._jit_cache.values()
    assert plan is None and exe is None
    assert _resolved(dev._ccache) == 2
    assert dev._ccache.stats["hits_mem"] == 1


def test_a_tile_of_another_dtype_unbinds_and_is_resolved_again(dev):
    """The local key reads the FIRST task's arguments; a later task of
    the chunk whose tile is not what the shared ``FlowPlan`` says reaches
    the bound executable, which refuses it: the entry goes once through
    the signed path, which finds (here: compiles) the executable of the
    arguments as they are."""
    _run_value_tasks(dev, _value_tasks(_body, _rows(4)), alone=False)
    assert _calls(dev) == (0, 1)
    tasks = _value_tasks(_body, _rows(4, start=4))
    odd = tasks[2].body_args[0][1]
    odd.get_copy(0).payload = np.full((8, 8), 6.0, np.float64)
    outs = _run_value_tasks(dev, tasks, alone=False)
    assert len(dev._jit_cache) == 1
    assert _calls(dev) == (0, 2)
    assert _resolved(dev._ccache) == 2
    assert dev._ccache.stats["aot_fallbacks"] == 0
    assert dev.stats["wave_fallbacks"] == 0
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), i + 5.0)
    # bound to what the signed call gave, which refuses float32 there
    _run_value_tasks(dev, _value_tasks(_body, _rows(4)), alone=False)
    assert _calls(dev) == (0, 3)
    assert _resolved(dev._ccache) == 2


def test_a_refused_bound_call_falls_back_once_to_the_plain_jit(dev):
    """What the light signature cannot see (a sharding or weak-type
    nuance): the bound call raises TypeError, the entry goes once through
    the signed path, whose executable refuses too, and that falls back to
    the plain ``jax.jit`` and counts it — exactly as before; from then on
    the entry is bound to the plain jit."""
    _run_value_tasks(dev, _value_tasks(_body, _rows(4)), alone=False)
    refusing = _Refusing(TypeError(
        "Argument types differ from the types for which this computation "
        "was compiled"))
    key, cf = _plant(dev, refusing)
    outs = _run_value_tasks(dev, _value_tasks(_body, _rows(4, start=4)),
                            alone=False)
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), i + 5.0)
    assert refusing.calls == 2      # bound, then behind the signature
    assert dev._ccache.stats["aot_fallbacks"] == 1
    assert dev.stats["wave_fallbacks"] == 0
    assert _calls(dev) == (0, 2)
    assert dev._jit_cache[key][2] is cf._plain
    outs = _run_value_tasks(dev, _value_tasks(_body, _rows(4, start=8)),
                            alone=False)
    np.testing.assert_array_equal(np.asarray(outs[3]), 12.0)
    assert _calls(dev) == (1, 2)
    assert refusing.calls == 2
    assert dev._ccache.stats["aot_fallbacks"] == 1


@pytest.mark.parametrize("donates", [True, False],
                         ids=["donating", "functional"])
def test_a_donating_programs_runtime_error_is_not_run_again(dev, donates):
    """A status error of a program that donates may have consumed its
    inputs: it surfaces as itself, from the bound call as from the signed
    one.  The same error of a program that donates nothing is the
    dispatch's rejection and takes the plain jit."""
    def body(x, o, i):
        return o + x
    if donates:
        body._donate_args = (1,)
    first, second = _value_tasks(body, _rows(2))
    dev._submit(first)
    assert _calls(dev) == (0, 1)
    refusing = _Refusing(RuntimeError("INVALID_ARGUMENT: planted"))
    key, cf = _plant(dev, refusing)
    assert cf.donate == ((1,) if donates else ())
    if donates:
        with pytest.raises(RuntimeError, match="planted"):
            dev._submit(second)
        assert refusing.calls == 1
        assert dev._ccache.stats["aot_fallbacks"] == 0
        assert dev._jit_cache[key][2] is refusing
        assert _calls(dev) == (0, 1)
    else:
        dev._submit(second)
        assert refusing.calls == 2
        assert dev._ccache.stats["aot_fallbacks"] == 1
        out = second.body_args[1][1].get_copy(dev.data_index).payload
        np.testing.assert_array_equal(np.asarray(out), 2.0)


def _qr_solve(dev=None, nt=4, nb=8):
    """One tile QR through the pump on ``dev`` (a new device without
    one): the device, the tasks run, what the solve added to its
    counters."""
    from parsec_tpu.ops.qr import qr_ptg

    n = nt * nb
    M = np.random.default_rng(11).standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix(n, n, nb, nb, name="A", dtype=np.float32).from_array(M)
    tp = qr_ptg(use_tpu=True, use_cpu=False).taskpool(
        NT=A.mt, A=A, TILE_SHAPE=(nb, nb), TILE_DTYPE=A.default_dtype,
        QSHAPE2=(A.default_dtype, (2 * nb, 2 * nb)))
    ex = NativeExecutor(tp, native_device=True, device=dev)
    dev = ex.device
    before = dict(dev.stats)
    ran = ex.run()
    ex.close()
    moved = {k: dev.stats[k] - before.get(k, 0) for k in
             ("calls_bound", "calls_signed", "wave_submits", "wave_tasks")}
    R = np.triu(A.to_array())
    np.testing.assert_allclose(R.T @ R, M.T @ M, rtol=2e-4, atol=2e-4)
    return dev, ran, moved


def test_a_second_pump_solve_signs_no_call():
    """Every entry was bound in the first solve: a steady solve's
    programs (the waves and the tasks that go out alone) are all bound
    calls, and the cache counts a hit for each."""
    dev, ran, first = _qr_solve()
    assert first["calls_signed"] == len(dev._jit_cache) > 0
    assert first["calls_bound"] > 0
    hits = dev._ccache.stats["hits_mem"]
    resolved = _resolved(dev._ccache)
    _dev, ran2, second = _qr_solve(dev)
    assert ran2 == ran
    assert second["calls_signed"] == 0
    singles = ran - second["wave_tasks"]
    assert singles > 0
    assert second["calls_bound"] == second["wave_submits"] + singles
    assert dev._ccache.stats["hits_mem"] - hits == second["calls_bound"]
    assert _resolved(dev._ccache) == resolved
    assert dev._ccache.stats["aot_fallbacks"] == 0
