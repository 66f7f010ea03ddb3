"""The program's spans as the profiler records them: the tiny pump,
Context and 2x2 solves on the CPU backend under ``jax.profiler``, read
back with ``benchmark/trace/spans.py``.  The host plane holds every span
of ``docs/TRACING.md``'s table, nested as the table says, and every span
of one solve carries that solve's ``pool``.  Counts, never times: the
CPU backend gives no device number."""

import collections
import shutil

import jax
import pytest

from benchmark import harness, ops_count
from benchmark.trace import reduce as tr
from benchmark.trace import spans as sp
from parsec_tpu import native
from parsec_tpu.profiling import pins
from parsec_tpu.utils import mca_param

from bench_testlib import tiny_cell

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native core")

WAVE_CHILDREN = {"dev:stage_args", "dev:jit", "dev:dispatch", "dev:epilog"}


def traced_solves(workload, out, solves=2):
    """Two warm solves, then ``solves`` under the profiler, as the
    harness traces a cell (host tracer 2, the Python tracer off)."""
    cell = tiny_cell(workload)
    devices = jax.devices()
    problem = cell.reference.make_problem(2147483999, cell.config,
                                          cell.traffic, devices[:cell.chips])
    cell.reference.prepare(problem)
    session = harness.Session(cell, devices, "cpu")
    try:
        for _ in range(2):
            assert session.solve(problem)["ok"]
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            for _ in range(solves):
                assert session.solve(problem)["ok"]
        finally:
            jax.profiler.stop_trace()
    finally:
        session.close()
    trace = sp.load(tr.find_xplane(str(out)))
    nt = cell.config["n"] // cell.config["nb"]
    return trace, sp.nest(sp.clip_spans(trace.spans, trace.windows)), \
        ops_count.dpotrf_ntasks(nt)


def parents(spans):
    out = collections.defaultdict(set)
    for s in spans:
        out[s.name].add(s.parent.name if s.parent else None)
    return out


def pools_per_window(trace, spans):
    return [{s.args["pool"] for s in spans
             if "pool" in s.args and ws <= s.start < we}
            for ws, we in trace.windows]


def tasks_taken(spans):
    return sum(s.args["n"] for s in spans if s.name in sp.TASK_SPANS)


@pytest.fixture(scope="module")
def pump(tmp_path_factory):
    noop = lambda es, p: None  # noqa: E731  arms the lifecycle-event drain
    pins.subscribe(pins.NATIVE_TASK_DONE, noop)
    try:
        return traced_solves("tile_pump_n8192",
                             tmp_path_factory.mktemp("pump"))
    finally:
        pins.unsubscribe(pins.NATIVE_TASK_DONE, noop)


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return traced_solves("tile_ctx_n8192", tmp_path_factory.mktemp("ctx"))


def test_pump_solve_holds_every_span_of_its_path(pump):
    _, spans, _ = pump
    names = {s.name for s in spans}
    assert names >= {"attach:build", "attach:partition", "pump:pop",
                     "pump:stage_wait", "pump:land", "pump:retire",
                     "pump:done", "pump:events", "dev:submit_batch",
                     "dev:wave", "dev:submit_one", "dev:stage_in",
                     "dev:h2d", "dev:writeback", "dev:detach"} \
        | WAVE_CHILDREN
    # the scheduling core never touches a pumped task
    assert not any(n.startswith("core:") for n in names)


def test_pump_spans_nest_as_the_table_says(pump):
    _, spans, _ = pump
    up = parents(spans)
    for name in ("pump:pop", "pump:stage_wait", "pump:land", "pump:retire",
                 "pump:done", "pump:events", "dev:submit_batch",
                 "attach:build", "dev:stage_in"):
        assert up[name] == {None}, name
    assert up["attach:partition"] == {"attach:build"}
    assert up["dev:wave"] == up["dev:submit_one"] == {"dev:submit_batch"}
    for name in WAVE_CHILDREN:
        assert up[name] <= {"dev:wave", "dev:submit_one"}, name
    assert up["dev:h2d"] <= {"dev:stage_in", "dev:stage_args"}
    # one driving thread; the lane and the committer are other threads
    pump_thread = {s.thread for s in spans if s.name.startswith("pump:")}
    assert len(pump_thread) == 1
    assert {s.thread for s in spans if s.name == "dev:dispatch"} == \
        pump_thread
    lane = {s.thread for s in spans if s.name == "dev:stage_in"}
    assert lane and not lane & pump_thread   # each solve starts its own


def test_pump_spans_are_per_batch_and_carry_its_number(pump):
    trace, spans, ntasks = pump
    by = collections.Counter(s.name for s in spans)
    batches = by["dev:submit_batch"]
    assert 2 <= batches < 2 * ntasks     # per batch, never per task
    for name in ("pump:stage_wait", "pump:land", "pump:retire", "pump:done",
                 "pump:events"):
        assert by[name] == batches, name
    numbers = {s.args["batch"] for s in spans
               if s.name == "dev:submit_batch"}
    assert {s.args["batch"] for s in spans
            if s.name == "dev:stage_in"} == numbers
    assert by["dev:dispatch"] == by["dev:wave"] + by["dev:submit_one"]
    assert tasks_taken(spans) == ntasks * len(trace.windows)
    assert sum(s.args["n"] for s in spans
               if s.name == "pump:pop") == ntasks * len(trace.windows)
    for s in spans:
        if s.name == "dev:stage_args":
            assert s.args["tiles"] >= s.args["host_tiles"] >= 0
        if s.name in ("dev:wave", "dev:submit_one"):
            assert s.args["cls"] in ("potrf", "trsm", "syrk", "gemm")
            assert s.args["waited_us"] == 0   # no ready queue to wait in


def test_every_span_of_a_solve_carries_that_solves_pool(pump, ctx):
    for trace, spans, _ in (pump, ctx):
        pools = pools_per_window(trace, spans)
        assert len(pools) == 2
        assert all(len(p) == 1 for p in pools), pools
        assert pools[0] != pools[1]
        assert all(s.args["rank"] == 0 for s in spans)


def test_context_solve_holds_every_span_of_its_path(ctx):
    trace, spans, ntasks = ctx
    names = {s.name for s in spans}
    assert names >= {"attach:build", "core:select", "core:prepare_input",
                     "core:schedule", "core:complete_exec",
                     "core:release_deps", "dev:poll", "dev:flush",
                     "dev:writeback", "dev:h2d"} | WAVE_CHILDREN
    assert names & {"dev:wave", "dev:submit_one"}
    assert not any(n.startswith("pump:") or n == "dev:submit_batch"
                   for n in names)
    by = collections.Counter(s.name for s in spans)
    solves = len(trace.windows)
    assert tasks_taken(spans) == ntasks * solves
    # per task only where the core had a pair before
    for name in ("core:prepare_input", "core:complete_exec",
                 "core:release_deps"):
        assert by[name] == ntasks * solves, name
    assert by["core:schedule"] <= (ntasks + 1) * solves


def test_context_spans_nest_as_the_table_says(ctx):
    _, spans, _ = ctx
    up = parents(spans)
    assert up["core:complete_exec"] == {"dev:epilog"}
    assert up["core:release_deps"] == {"core:complete_exec"}
    assert up["core:schedule"] <= {"attach:build", "dev:epilog",
                                   "core:complete_exec"}
    assert up["core:select"] == up["core:prepare_input"] == {None}
    assert up["dev:wave"] | up["dev:submit_one"] == {None}
    for name in WAVE_CHILDREN:
        assert up[name] <= {"dev:wave", "dev:submit_one"}, name
    assert up["dev:h2d"] == {"dev:stage_args"}
    # the ready-queue wait is stamped on the spans that took the tasks
    waits = [s.args["waited_us"] for s in spans if s.name in sp.TASK_SPANS]
    assert all(w >= 0 for w in waits) and sum(waits) > 0


def test_lane_completion_shows_poll_and_block(tmp_path):
    mca_param.set_param("device", "tpu_eager_complete", 0)
    try:
        _, spans, _ = traced_solves("tile_ctx_n8192", tmp_path, solves=1)
    finally:
        mca_param.params.unset("device", "tpu_eager_complete")
    up = parents(spans)
    # without eager completion the tasks retire from the poll; the manager
    # blocks on the oldest program only in a spin that retired nothing
    assert up["dev:poll"] == {None}
    assert "dev:poll" in up["core:complete_exec"]
    assert up.get("dev:block", {None}) == {None}


def test_mesh_solve_shows_the_transport_spans_of_every_rank(tmp_path):
    trace, spans, ntasks = traced_solves("tile_2x2_n16384", tmp_path,
                                         solves=1)
    assert tasks_taken(spans) == ntasks
    # (a tiny attach is over before the driver's main thread opens the
    # window that the rank threads' barrier starts)
    for name in ("comm:send", "comm:recv", "dev:dispatch",
                 "core:complete_exec"):
        assert {s.args["rank"] for s in spans if s.name == name} == \
            {0, 1, 2, 3}, name
    for s in spans:
        if s.name.startswith("comm:"):
            assert s.args["peer"] != s.args["rank"]
            assert s.args["bytes"] >= 0 and s.args["qdepth"] >= 0
    # one solve: one pool per rank's context
    assert len({s.args["pool"] for s in spans if "pool" in s.args}) <= 4


def test_a_compile_is_a_span_with_its_kind(tmp_path):
    import jax.numpy as jnp

    from parsec_tpu import compile_cache

    cache = compile_cache.default_cache()
    with jax.profiler.trace(str(tmp_path)):
        f = cache.jit(lambda x: x * 3 + 1, key=("body", "span-test"))
        f(jnp.ones((8, 8), jnp.float32))
        f(jnp.ones((8, 8), jnp.float32))  # in-process hit: no span
    got = [s for s in sp.load(tr.find_xplane(str(tmp_path))).spans
           if s.name == "cc:compile"]
    assert len(got) == 1
    assert got[0].args["kind"] in ("miss", "hit_disk", "hit_bcast")
    assert got[0].args["rank"] == 0 and got[0].args["fp"]
