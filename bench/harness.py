"""The benchmark harness: one run of one cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
harness reads the configuration from its ``file``, the traffic mix from
``bench/traffic/<traffic>.json``, and hands both to the driver that the
mix names (``bench/drivers/<driver>.py``). Each metric is read by its own
reader, ``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric
means adding files and entries; no file here changes.

A run: require the chips, turn on the persistent compilation cache, let the
driver set up and warm every shape (``setup_s`` runs from process start to
the window), measure for ``--seconds``, check what the window produced
against the plain reference, and print one JSON result line last.

A driver module holds ``SPANS`` (the span names it opens), ``KERNELS``
(kernel name -> pattern of its trace ops), ``setup(cell, seed, spans)``,
``window(state, seconds)`` (a record with ``attempted`` and ``failed``),
``check(state, record)`` (a list of ``Check``) and ``close(state)``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent
TRACE_DIR_NAME = ".bench_trace"     # inside the checkout, git-ignored
WINDOW_SPAN = "bench.window"


class BenchError(RuntimeError):
    """The cell cannot be run as asked (missing file, wrong device)."""


# -- cells ---------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def metrics(self, traced: bool) -> List[Dict[str, Any]]:
        """The metric entries this cell reports: its end-to-end metrics, or
        with ``traced`` its per-layer ones."""
        e2e = [m for m in self.end_to_end
               if self.name in m.get("workloads", [self.name])]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.per_layer
                if self.name in m.get("workloads", [self.name])
                and m["moves"] in moved]


def load_json(path: Path) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.exists():
        raise BenchError(f"no BENCHMARK.json in {root}")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.exists():
        raise BenchError(f"no traffic file {traffic_path}")
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(traffic_path),
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])


def load_module(path: Path, name: str):
    """Import a driver or metric file of ``bench/`` by its path: they are
    found by the names in BENCHMARK.json, and metric names carry dots."""
    if not path.exists():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def driver_for(cell: Cell, root: Path = ROOT):
    name = cell.traffic["driver"]
    return load_module(root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


# -- devices -------------------------------------------------------------------
def require_devices(chips: int) -> list:
    """The TPU devices a cell runs on. Exits non-zero, before anything runs,
    when JAX finds no TPU or fewer than ``chips``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", file=sys.stderr,
          flush=True)
    if dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {dev.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def configure_compilation_cache() -> str:
    """Keep JAX's persistent compilation cache where the program's
    ``repro.compile_cache`` puts it (``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), and cache every program, however quick to
    compile, so that a second run of a cell compiles nothing and the window
    of any run compiles nothing that set-up warmed."""
    import jax
    from repro.compile_cache import configure_compile_cache
    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Pallas TPU kernel carries its Mosaic module, source locations
    # included, inside the compiled program, and with full tracebacks a
    # location holds the whole call stack: the same kernel shape called
    # from set-up and from the window would be two programs, and the
    # window's would compile inside it.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return where


# -- spans and counters --------------------------------------------------------
class Spans:
    """The benchmark's own host spans around calls into each layer: each is a
    ``jax.profiler.TraceAnnotation`` (so the trace shows it) and is timed on
    the host clock."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        dt = time.perf_counter() - t0
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.count[name] = self.count.get(name, 0) + 1

    def reset(self) -> None:
        self.seconds.clear()
        self.count.clear()


class CompileEvents:
    """Counts JAX's compile events between ``start`` and ``stop``:
    ``compiles`` is every backend compile request (a persistent-cache hit
    included), ``cache_misses`` those that the persistent cache could not
    serve (JAX records one when it writes the new program to the cache),
    and ``written`` names those programs."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"
    LOGGER = "jax._src.compilation_cache"

    def __init__(self):
        self.compiles = 0
        self.cache_misses = 0
        self.written: List[str] = []
        self._handler = None
        self._level = None

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if event == self.MISS:
            self.cache_misses += 1

    def start(self) -> None:
        import jax
        import logging
        self.compiles = self.cache_misses = 0
        self.written = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        written = self.written

        class Written(logging.Handler):
            def emit(self, record):
                if record.msg.startswith("Writing %s") and record.args:
                    written.append(str(record.args[0]))

        logger = logging.getLogger(self.LOGGER)
        self._level = logger.level
        self._handler = Written(logging.DEBUG)
        logger.addHandler(self._handler)
        logger.setLevel(logging.DEBUG)

    def stop(self) -> None:
        import jax
        import logging
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        logger = logging.getLogger(self.LOGGER)
        logger.removeHandler(self._handler)
        logger.setLevel(self._level)


# -- a run ---------------------------------------------------------------------
@dataclass
class Check:
    """One number compared with its limit: the run is correct when every
    value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Run:
    """What metric readers read."""
    cell: Cell
    setup_s: float
    window: Any                    # the driver's window record
    spans: Spans
    compiles: CompileEvents
    peaks: Dict[str, float]
    trace: Any = None              # trace.Reduction in a traced run


def read_metrics(run: Run, traced: bool, root: Path = ROOT) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    for m in run.cell.metrics(traced):
        value = metric_reader(m["name"], root).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t_start: float, root: Path = ROOT) -> Dict[str, Any]:
    """Run one cell once; returns the result object (``checks`` last)."""
    cell = load_cell(workload, root)
    driver = driver_for(cell, root)
    devices = require_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind, root)
    print(f"compile cache: {configure_compilation_cache()}", file=sys.stderr,
          flush=True)
    import jax
    spans = Spans()
    compiles = CompileEvents()
    state = driver.setup(cell, seed, spans)
    trace_dir = root / TRACE_DIR_NAME / workload
    try:
        spans.reset()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
        setup_s = time.perf_counter() - t_start
        compiles.start()
        try:
            with spans.span(WINDOW_SPAN):
                record = driver.window(state, seconds)
        finally:
            compiles.stop()
        if traced:
            jax.profiler.stop_trace()
        print(f"window: {compiles.compiles} compile requests, "
              f"{compiles.cache_misses} persistent-cache misses "
              f"{compiles.written[:20]}", file=sys.stderr, flush=True)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory_peak(devices)}
        checks = driver.check(state, record)
    finally:
        driver.close(state)
    run = Run(cell=cell, setup_s=setup_s, window=record, spans=spans,
              compiles=compiles, peaks=peaks)
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": record.attempted, "failed": record.failed,
    }
    breakdown = None
    if traced:
        from bench.trace import reduce_trace
        run.trace = reduce_trace(
            str(trace_dir), WINDOW_SPAN, driver.SPANS, driver.KERNELS)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops(10),
                     "idle_gaps": run.trace.top_idle(10)}
    result["metrics"] = read_metrics(run, traced, root)
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: Dict[str, Any]) -> None:
    """The checks as the last lines on standard error, then the result as
    the last line on standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
