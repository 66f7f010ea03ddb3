"""The general harness: one cell in, one result line out.

Everything that belongs to one configuration, one traffic mix, one
execution path or one per-layer metric lives in a file of its own, found
by the name ``BENCHMARK.json`` gives it (``README.md`` says how to add
one).  This file holds what every cell shares: the look for the chip, the
closed loop of solves, the median over the readings, the check of every
solve against the configuration's plain reference, and the result line.

From the program it takes only the system under test, driven through the
modules in ``drivers/``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class BenchError(RuntimeError):
    """The run cannot give a result: exit non-zero, print none."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

def load_module(path: str):
    name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_file(root: str, paths: List[str], rel: str) -> str:
    """``rel`` under the first of the benchmark's directories that has it."""
    for p in paths:
        cand = os.path.join(root, p, rel)
        if os.path.isfile(cand):
            return cand
    raise BenchError(f"no file {rel!r} under any of {paths}")


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_reader(root: str, paths: List[str], metric: str) -> str:
    """``layers/<metric>.py``; a quantity split by the end-to-end metric
    it moves (``device_idle_pct.panel``) shares ``layers/<quantity>.py``."""
    try:
        return find_file(root, paths, f"layers/{metric}.py")
    except BenchError:
        if "." not in metric:
            raise
        return find_file(root, paths, f"layers/{metric.split('.')[0]}.py")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    driver: Any        # module under drivers/
    reference: Any     # module under reference/
    readers: Dict[str, Any]  # per-layer metric name -> module under layers/


def load_cell(root: str, workload: str,
              spec: Optional[Dict[str, Any]] = None) -> Cell:
    if spec is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise BenchError(f"no workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next((c for c in spec["configs"]
                      if c["name"] == w["config"]), None)
    if cfg_entry is None:
        raise BenchError(f"{workload}: no configuration {w['config']!r}")
    paths = spec["paths"]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(find_file(root, paths, f"traffic/{w['traffic']}.json")) as f:
        traffic = json.load(f)
    sized = sorted(set(traffic) & ({"n", "nb"} | set(config["reduced"])))
    if sized:
        raise BenchError(f"traffic {w['traffic']!r} sets {sized}: a size "
                         "belongs to the configuration's file alone")
    if int(traffic.get("warmup_solves", 0)) < 2:
        raise BenchError(f"traffic {w['traffic']!r}: warmup_solves has to "
                         "be 2 or more whole solves")
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        driver=load_module(find_file(
            root, paths, f"drivers/{traffic['driver']}.py")),
        reference=load_module(find_file(root, paths, config["reference"])),
        readers={m["name"]: load_module(find_reader(root, paths, m["name"]))
                 for m in per_layer})


def load_peaks(root: str, paths: List[str], device_kind: str) -> Dict[str, Any]:
    with open(find_file(root, paths, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise BenchError(
            f"device_kind {device_kind!r} is not in peaks.json "
            f"({sorted(table)}): add its published peaks with their source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts backend compiles and persistent-cache hits and misses."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "xla_cache_hits",
               "/jax/compilation_cache/cache_misses": "xla_cache_misses"}

    def __init__(self):
        from jax import monitoring

        self.n = {"backend_compiles": 0, "xla_cache_hits": 0,
                  "xla_cache_misses": 0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        key = self._EVENTS.get(name)
        if key:
            self.n[key] += 1

    def _duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.n["backend_compiles"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before[k] for k, v in self.n.items()}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def median(values: List[float]) -> float:
    if not values:
        raise BenchError("median of no readings")
    return float(statistics.median(values))


def spread_of(readings: List[Dict[str, float]]) -> Dict[str, List[float]]:
    """For a reader of the log: each time's minimum, quartiles and maximum
    over the window's readings, and the medians of its first and last
    third (a drift inside the window shows as their difference)."""
    out = {}
    for key in sorted({k for r in readings for k in r}):
        v = [r[key] for r in readings if key in r]
        third = max(1, len(v) // 3)
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [v[0]] * 3
        out[key] = [min(v), *q, max(v), median(v[:third]),
                    median(v[-third:])]
    return out


def within_limits(numbers: Dict[str, float],
                  limits: Dict[str, float]) -> bool:
    """Every number compared has a limit of its own in the configuration."""
    for name, value in numbers.items():
        if name not in limits:
            raise BenchError(f"compared number {name!r} has no limit in the "
                             f"configuration ({sorted(limits)})")
        if not math.isfinite(value) or value > limits[name]:
            return False
    return True


def keep_worst(worst: Dict[str, float], numbers: Dict[str, float]) -> None:
    """The largest of each compared number so far; one that is not
    finite sticks."""
    for k, v in numbers.items():
        old = worst.get(k, 0.0)
        if math.isfinite(old):
            worst[k] = max(old, v) if math.isfinite(v) else v


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets to read."""
    cell: Cell
    readings: List[Dict[str, float]]   # one dict of times per solve
    counters: Dict[str, float]         # the drivers' counters over the window
    solves: int                        # solves those counters cover
    compiles: Dict[str, int]           # {"window": n, "setup": n, ...}
    memory: Dict[str, float]           # bytes, fullest chip
    peaks: Optional[Dict[str, Any]]
    trace: Any = None                  # trace.reduce.Summary of a traced run

    def median(self, key: str) -> Optional[float]:
        vals = [r[key] for r in self.readings if key in r]
        return median(vals) if vals else None

    def size(self, key: str) -> int:
        """A size of the configuration."""
        return int(self.cell.config[key])

    def per_solve(self, counter: str) -> Optional[float]:
        if counter not in self.counters or not self.solves:
            return None
        return self.counters[counter] / self.solves


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

def look_for_devices(cell: Cell, platform: str):
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(
            f"{cell.name}: jax.devices()[0].platform is "
            f"{devices[0].platform!r}, the cell runs on {platform!r} only")
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX "
                         f"reports {len(devices)}")
    return devices


class Session:
    """A cell's driver, opened once, and the loop that drives it."""

    def __init__(self, cell: Cell, devices, platform: str, *,
                 control: bool = False):
        self.cell = cell
        options = dict(cell.config.get("options", {}))
        if control:
            options.update(cell.config["control"]["options"])
        self.driver = cell.driver.open(cell.config, cell.traffic, options,
                                       devices[:cell.chips], platform)
        self.limits = cell.config["limits"]

    def solve(self, problem) -> Dict[str, Any]:
        """One reading, checked outside it.  Returns the times, the
        numbers compared and whatever guarantee the solve broke."""
        import jax

        solve = self.driver.solve(problem)  # spans bench:solve itself
        with jax.profiler.TraceAnnotation("bench:check"):
            numbers = self.cell.reference.compare(problem, solve["result"])
        ok = within_limits(numbers, self.limits) and not solve["violations"]
        self.driver.release(solve)
        del solve["result"]
        gc.collect()  # every solve starts from the same heap
        return {"times": solve["times"], "numbers": numbers,
                "violations": solve["violations"], "ok": ok,
                "t_done": solve["t_done"]}

    def close(self) -> None:
        self.driver.close()


def _memory(devices) -> Dict[str, float]:
    peak = in_use = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0))
        in_use = max(in_use, stats.get("bytes_in_use", 0))
    return {"peak_bytes": peak, "bytes_in_use": in_use}


def run_cell(root: str, cell: Cell, seed: int, seconds: float, trace: bool,
             *, platform: str = "tpu", paths: Optional[List[str]] = None,
             t_process: Optional[float] = None,
             control: bool = False) -> Dict[str, Any]:
    """Set up, warm up, measure for ``seconds``, check, reduce.  ``platform``
    is ``"tpu"`` for every real run; the tests rehearse tiny traffic with
    ``"cpu"`` and read no device number from it."""
    t_process = time.perf_counter() if t_process is None else t_process
    import jax

    devices = look_for_devices(cell, platform)
    paths = paths or ["benchmark"]
    peaks = (load_peaks(root, paths, devices[0].device_kind)
             if platform == "tpu" else None)
    used = devices[:cell.chips]
    watch = CompileWatch()
    setup = {"backend_s": time.perf_counter() - t_process}

    t = time.perf_counter()
    from parsec_tpu import native  # built from native/src on first use

    if not native.available():
        raise BenchError(f"native engine: {native.build_error()}")
    setup["native_s"] = time.perf_counter() - t

    t = time.perf_counter()
    problem = cell.reference.make_problem(seed, cell.config, cell.traffic,
                                          used)
    setup["problem_s"] = time.perf_counter() - t
    t = time.perf_counter()
    cell.reference.prepare(problem)  # the plain reference: not set-up
    reference_s = time.perf_counter() - t

    t = time.perf_counter()
    session = Session(cell, devices, platform, control=control)
    setup["driver_s"] = time.perf_counter() - t
    failures: List[Dict[str, Any]] = []
    worst: Dict[str, float] = {}

    def one() -> Dict[str, Any]:
        s = session.solve(problem)
        keep_worst(worst, s["numbers"])
        return s

    try:
        t = time.perf_counter()
        warmups = int(cell.traffic["warmup_solves"])
        uncounted_failed = 0  # failed solves that are not readings
        for _ in range(warmups):
            t_cycle = time.perf_counter()
            s = one()
            cycle = time.perf_counter() - t_cycle
            if not s["ok"]:
                failures.append(s)
                uncounted_failed += 1
        setup["warmup_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t_process - reference_s
        log("setup %.3f s: " % setup_s + ", ".join(
            f"{k[:-2]} {v:.3f}" for k, v in setup.items())
            + f"; the plain reference took {reference_s:.3f} s beside it")
        compiles_setup = watch.snapshot()

        # ---- the window: solves back to back, one client ------------
        readings: List[Dict[str, float]] = []
        trace_dir = os.path.join(root, ".bench_trace", cell.name)
        discard = int(cell.traffic.get("discard_solves", 0))
        traced = int(cell.traffic.get("traced_solves", 3))
        counters0 = None
        cpu0 = sum(os.times()[:2])
        t_window = time.perf_counter()
        t_end = t_window + seconds
        solves = 0
        while True:
            now = time.perf_counter()
            if now + cycle > t_end and (readings or solves > discard):
                break
            if solves == discard:
                counters0 = session.driver.counters()
                compiles0 = watch.snapshot()
                if trace:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.host_tracer_level = 2
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
            s = one()
            solves += 1
            cycle = time.perf_counter() - now
            # a reading is a solve that completed inside the window,
            # after the traffic's discards
            counted = solves > discard and (s["t_done"] <= t_end
                                            or not readings)
            if counted:
                readings.append(s["times"])
            if not s["ok"]:
                failures.append(s)
                uncounted_failed += not counted
            if trace and len(readings) >= traced:
                break
        if trace:
            jax.profiler.stop_trace()
        window_s = time.perf_counter() - t_window
        cpu_s = sum(os.times()[:2]) - cpu0
        counters1 = session.driver.counters()
        compiles = watch.since(compiles0)
        memory = _memory(used)
    finally:
        session.close()

    counted = solves - discard
    run = Run(cell=cell, readings=readings,
              counters={k: counters1[k] - counters0[k] for k in counters1},
              solves=counted,
              # a program loaded from the persistent cache also reports a
              # backend compile: in the window either is a stall; before
              # it, only the misses are compiles
              compiles={"window": compiles["backend_compiles"],
                        "setup": compiles_setup["xla_cache_misses"],
                        "setup_loads": compiles_setup["xla_cache_hits"]},
              memory=memory, peaks=peaks)
    fixed = bool(cell.config.get("fixed_program_set"))
    compiled_late = fixed and run.compiles["window"] > 0
    for name, value in worst.items():
        log(f"compared {name}: worst of {warmups + solves} solves "
            f"{value:.4e}, limit {session.limits[name]:.1e}")
    for f in failures[:5]:
        log(f"FAILED solve: numbers {f['numbers']}, "
            f"violations {f['violations']}")
    if compiled_late:
        log(f"FAILED: {run.compiles['window']} compiles inside the window "
            "of a configuration whose program set is fixed")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory["peak_bytes"])}
    result: Dict[str, Any] = {
        "correct": not failures and not compiled_late,
        "attempted": len(readings) + uncounted_failed,
        "failed": len(failures), "metrics": {}, "device": device}
    if trace:
        from benchmark.trace import reduce as trace_reduce

        run.trace = trace_reduce.summarize(
            trace_reduce.load_events(trace_reduce.find_xplane(trace_dir)),
            chips=cell.chips)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    else:
        run_level = {"setup_s": setup_s,
                     "peak_hbm_gb": memory["peak_bytes"] / 1e9}
        for m in cell.end_to_end:
            value = run.median(m["name"])
            if value is None:
                value = run_level.get(m["name"])
            if value is None:
                raise BenchError(f"{cell.name}: nothing measured "
                                 f"{m['name']!r}")
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    log("detail " + json.dumps(dict(
        workload=cell.name, seed=seed, compared=worst,
        limits=session.limits, setup=dict(setup, reference_s=reference_s),
        compiles=run.compiles, window_solves=solves,
        median_of=len(readings), readings=spread_of(readings),
        # this process's CPU seconds per second of the window: a run held
        # back by a neighbour on its host reads slow at the same CPU
        window_s=window_s, cpu_per_s=cpu_s / window_s)))
    return result


def main(root: str, workload: str, seed: int, seconds: float, trace: bool,
         *, t_process: Optional[float] = None) -> int:
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        cell = load_cell(root, workload, spec)
        result = run_cell(root, cell, seed, seconds, trace,
                          paths=spec["paths"], t_process=t_process)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
