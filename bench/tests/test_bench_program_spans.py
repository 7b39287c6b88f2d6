"""The reduction of the program's own spans: time and count of each span
name in the window, device idle charged to the innermost program span, on
the thread that holds the window; and the ten metrics that read it."""
import random
from types import SimpleNamespace

import pytest

from bench_testlib import ROOT, cpu_harness, run, tiny_root
from bench import program_spans
from bench.harness import metric_reader
from bench.program_spans import (OUTSIDE, SPANS, ProgramSpans,
                                 innermost_each, reduce_spans, window_thread)
from bench.trace import Event, innermost

DEV = "/device:TPU:0"
READERS = ("attend_ms_per_step", "dispatch_ms_per_call", "fetch_ms_per_call",
           "decode_ms_per_token", "replica_ms_per_token",
           "pool_write_ms_per_write", "restore_ms_per_page",
           "offload_ms_per_page", "admission_wait_ms_per_token",
           "device_idle.dispatch")


def metric(name):
    return metric_reader(name, ROOT).read


def nested_spans(rng, lo, hi, depth):
    """Random spans that nest, as one thread's spans do: siblings apart,
    each child strictly inside its parent."""
    out, t = [], lo
    while depth and t < hi:
        a = t + rng.uniform(0, (hi - lo) / 3)
        b = a + rng.uniform(0, (hi - lo) / 2)
        if b >= hi:
            break
        out.append(Event(rng.choice(SPANS), a, b - a))
        out += nested_spans(rng, a + 1e-3, b - 1e-3, depth - 1)
        t = b + 1e-3
    return out


@pytest.mark.parametrize("seed", range(5))
def test_sweep_matches_innermost(seed):
    rng = random.Random(seed)
    spans = nested_spans(rng, 0.0, 1000.0, 4)
    assert len(spans) > 3
    points = [rng.uniform(-10.0, 1010.0) for _ in range(500)]
    want = [innermost(spans, t) for t in points]
    got = innermost_each(spans, points)
    assert got == [OUTSIDE if w == "(no benchmark span)" else w
                   for w in want]


def test_spans_of_other_threads_are_left_out():
    window = Event("bench.window", 0, 100)
    mine = [window, Event("serve.attend", 10, 30),
            Event("serving.attend", 10, 30), Event("serving.fetch", 20, 5)]
    other = [Event("serving.attend", 0, 100),
             Event("memory.admission_wait", 40, 20)]
    w, spans = window_thread([other, mine, []])
    assert w is window
    assert [s.name for s in spans] == ["serving.attend", "serving.fetch"]
    with pytest.raises(ValueError):
        window_thread([other])
    with pytest.raises(ValueError):
        window_thread([mine, [window]])


def test_reduction_by_hand():
    # window 100-200; device busy 100-110, 140-150, 195-205
    ops = {DEV: [Event("%a", 95, 15), Event("%b", 140, 10),
                 Event("%c", 195, 10)]}
    spans = [Event("serving.attend", 90, 60),        # clipped: 100-150
             Event("serving.dispatch", 105, 30),     # 105-135
             Event("serving.fetch", 135, 14),        # 135-149
             Event("serving.decode", 160, 10),
             Event("kvcache.pool_write", 190, 20),   # clipped: 190-200
             Event("serving.decode", 250, 10)]       # outside the window
    red = reduce_spans(ops, spans, (100, 200))
    assert red.span_ns == {"serving.attend": 50, "serving.dispatch": 30,
                           "serving.fetch": 14, "serving.decode": 10,
                           "kvcache.pool_write": 10}
    assert red.count == {"serving.attend": 1, "serving.dispatch": 1,
                         "serving.fetch": 1, "serving.decode": 1,
                         "kvcache.pool_write": 1}
    # gaps 110-140 (midpoint 125: dispatch) and 150-195 (172.5: none)
    assert red.idle_ns == {"serving.dispatch": 30, OUTSIDE: 45}
    assert red.devices == 1
    assert red.idle_share("serving.dispatch") == pytest.approx(30.0)
    assert red.ms("serving.attend") == pytest.approx(50e-6)
    assert red.ms_per_span("serving.fetch") == pytest.approx(14e-6)
    assert red.ms("kvcache.restore") is None
    assert red.ms_per_span("kvcache.restore") is None


def test_idle_is_averaged_over_devices():
    # device 0 idles 40-100 (midpoint 70, in the dispatch), device 1 never
    ops = {DEV: [Event("%a", 0, 40)], "/device:TPU:1": [Event("%b", 0, 100)]}
    red = reduce_spans(ops, [Event("serving.dispatch", 30, 60)], (0, 100))
    assert red.idle_ns == {"serving.dispatch": 60}
    assert red.idle_share("serving.dispatch") == pytest.approx(30.0)
    assert reduce_spans({}, [], (0, 100)).idle_share("x") is None


def hand_run(spans, steps=4, tokens=10):
    """A run whose window held ``steps`` steps and ``tokens`` tokens."""
    window = SimpleNamespace(steps=[None] * steps, tokens=tokens)
    return SimpleNamespace(trace=object(), window=window,
                           cell=SimpleNamespace(name="hand"), spans=spans)


def test_each_reader_by_hand(monkeypatch):
    red = ProgramSpans(
        window_ns=(0, 1e9), devices=1,
        span_ns={"serving.attend": 80e6, "serving.dispatch": 60e6,
                 "serving.fetch": 8e6, "serving.decode": 25e6,
                 "serving.replicate": 5e6, "kvcache.pool_write": 6e6,
                 "kvcache.restore": 12e6, "kvcache.offload": 3e6,
                 "memory.admission_wait": 2e6},
        count={"serving.attend": 4, "serving.dispatch": 16,
               "serving.fetch": 16, "serving.decode": 4,
               "serving.replicate": 10, "kvcache.pool_write": 12,
               "kvcache.restore": 3, "kvcache.offload": 3,
               "memory.admission_wait": 1},
        idle_ns={"serving.dispatch": 550e6, OUTSIDE: 100e6})
    monkeypatch.setattr(program_spans, "for_run", lambda run: run.spans)
    run_ = hand_run(red)
    want = {"attend_ms_per_step": 80 / 4, "dispatch_ms_per_call": 60 / 16,
            "fetch_ms_per_call": 8 / 16, "decode_ms_per_token": 25 / 10,
            "replica_ms_per_token": 5 / 10, "pool_write_ms_per_write": 6 / 12,
            "restore_ms_per_page": 12 / 3, "offload_ms_per_page": 3 / 3,
            "admission_wait_ms_per_token": 2 / 10,
            "device_idle.dispatch": 55.0}
    assert set(want) == set(READERS)
    for name, value in want.items():
        assert metric(name)(run_) == pytest.approx(value), name
    # no grant waited: 0.0, not silence; no restore: nothing to read
    for name in ("memory.admission_wait", "kvcache.restore"):
        del red.span_ns[name], red.count[name]
    assert metric("admission_wait_ms_per_token")(run_) == 0.0
    assert metric("restore_ms_per_page")(run_) is None
    # a CPU trace has no device: no idle share
    red.devices = 0
    assert metric("device_idle.dispatch")(run_) is None
    # no program spans at all (a program that opens none): every reader is
    # silent
    for name in READERS:
        assert metric(name)(hand_run(None)) is None, name


def test_untraced_run_reads_nothing():
    run_ = SimpleNamespace(trace=None, cell=SimpleNamespace(name="x"),
                           window=SimpleNamespace(steps=[1], tokens=1))
    assert program_spans.for_run(run_) is None
    for name in READERS:
        assert metric(name)(run_) is None, name


def record(trace_dir, program):
    """A CPU trace of a window with the benchmark's span in it and, with
    ``program``, the program's spans; returns the harness's reduction."""
    import jax
    import jax.numpy as jnp
    from bench.trace import reduce_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("serve.attend"):
            if program:
                with jax.profiler.TraceAnnotation("serving.attend"):
                    with jax.profiler.TraceAnnotation("serving.dispatch",
                                                      node=0):
                        x = jnp.ones(8).sum()
                    with jax.profiler.TraceAnnotation("serving.fetch",
                                                      node=0):
                        x.block_until_ready()
            else:
                jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    return reduce_trace(str(trace_dir), "bench.window", ["serve.attend"], {})


def test_reads_a_recorded_trace_once_and_only_the_runs(tmp_path,
                                                       monkeypatch):
    from bench import harness
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    cell_dir = tmp_path / harness.TRACE_DIR_NAME / "cell"
    run_ = SimpleNamespace(cell=SimpleNamespace(name="cell"),
                           window=SimpleNamespace(steps=[1], tokens=1))
    run_.trace = record(cell_dir, program=True)
    red = program_spans.for_run(run_)
    assert red.count == {"serving.attend": 1, "serving.dispatch": 1,
                         "serving.fetch": 1}
    assert red.ms("serving.attend") >= red.ms("serving.dispatch") > 0
    assert program_spans.for_run(run_) is red          # cached
    # a trace that is not the run's (another window) reads nothing
    other = SimpleNamespace(**vars(run_))
    other.trace = SimpleNamespace(window_ns=(0.0, 1.0))
    assert program_spans.for_run(other) is None
    # a program without the spans reads nothing either
    run_.trace = record(cell_dir, program=False)
    assert program_spans.for_run(run_) is None
    assert metric("admission_wait_ms_per_token")(run_) is None


def test_traced_tiny_cell_reads_the_program_spans(tmp_path, monkeypatch):
    """The tier run by the benchmark on the CPU: every program-span metric
    of a cell that spills reads a number (the CPU has no device plane)."""
    harness = cpu_harness(monkeypatch)
    root = tiny_root(tmp_path)
    monkeypatch.setattr(harness, "ROOT", root)
    result = run(harness, root, "tiny-burst", seconds=0.3, traced=True)
    assert result["correct"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    for name in READERS:
        if name == "device_idle.dispatch":
            assert name not in metrics
            continue
        assert metrics[name] >= 0, name
    assert metrics["dispatch_ms_per_call"] > 0
    assert metrics["attend_ms_per_step"] <= \
        metrics["attend_host_ms_per_step"] * 1.05


@pytest.mark.parametrize("cell", ["kv-burst-spill", "kv-decode-fit"])
def test_recorded_chip_trace_excerpt(cell):
    """Excerpts of traces recorded on one TPU v5e, as
    ``jax.profiler.ProfileData`` read them (op names cut to their head):
    forty restores of a resume in ``kv-burst-spill``, one whole engine step
    of ``kv-decode-fit``; the window thread's spans, the benchmark's among
    them."""
    import json
    import os
    from bench.trace import gaps, reduce_events, union
    path = os.path.join(os.path.dirname(__file__),
                        "chip_program_spans_excerpt.json")
    with open(path) as f:
        rec = json.load(f)[cell]
    ops = {d: [Event(*e) for e in evs] for d, evs in rec["ops"].items()}
    every = [Event(*e) for e in rec["spans"]]
    spans = [s for s in every if s.name in SPANS]
    window = tuple(rec["window"])
    red = reduce_spans(ops, spans, window)
    bench = reduce_events(ops, {}, [s for s in every if s not in spans],
                          window, {})
    # the idle time is the same, only charged to finer spans; each gap's
    # span is the one bench.trace.innermost finds among the program spans
    assert sum(red.idle_ns.values()) == pytest.approx(
        sum(bench.idle_ns.values()))
    busy = union((e.start_ns, e.end_ns) for e in ops["/device:TPU:0"])
    idle = gaps(busy, *window)
    mids = [(a + b) / 2 for a, b in idle]
    assert innermost_each(spans, mids) == [
        OUTSIDE if n == "(no benchmark span)" else n
        for n in (innermost(spans, t) for t in mids)]

    def within(child, parent):
        return all(any(p.start_ns <= c.start_ns and c.end_ns <= p.end_ns
                       for p in spans if p.name == parent)
                   for c in spans if c.name == child)
    if cell == "kv-burst-spill":
        # every restore found its slab on the host: one pool write each
        assert red.count["kvcache.restore"] == 40
        assert red.count["kvcache.pool_write"] == 40
        assert within("kvcache.pool_write", "kvcache.restore")
        assert within("kvcache.offload", "kvcache.restore")
        assert red.idle_ns["kvcache.offload"] > 0
    else:
        # 4 layers over 4 nodes: 16 kernel calls, 16 pages committed
        assert red.count["serving.attend"] == 4
        assert red.count["serving.dispatch"] == 16
        assert red.count["serving.fetch"] == 16
        assert red.count["kvcache.pool_write"] == 16
        assert within("serving.dispatch", "serving.attend")
        assert within("serving.fetch", "serving.attend")
        assert within("kvcache.pool_write", "serving.decode")
        serve = 100.0 * sum(bench.idle_ns.values()) / (window[1] - window[0])
        assert 0 < red.idle_share("serving.dispatch") <= serve
        # children apart inside their parent: their sum is within it
        assert red.ms("serving.dispatch") + red.ms("serving.fetch") <= \
            red.ms("serving.attend")
