"""The trace reduction: busy time as a union of op intervals, idle gaps
charged to the benchmark span around them, kernels matched by name."""
import json
import os

import pytest

from bench_testlib import ROOT  # noqa: F401  (puts bench/ on the path)
from bench.trace import (OUTSIDE, Event, gaps, innermost, op_label,
                         reduce_events, union)

KERNELS = {"paged_attention": r"^%tpu_custom_call"}


def test_union_merges_overlaps_and_touching_intervals():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        (0, 4), (5, 7), (8, 9)]


def test_gaps_cover_what_busy_leaves_of_the_window():
    busy = [(2, 4), (6, 7)]
    assert gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert gaps(busy, 3, 6.5) == [(4, 6)]
    assert gaps([], 0, 1) == [(0, 1)]


def test_innermost_span_wins():
    spans = [Event("bench.window", 0, 100), Event("serve.attend", 10, 20),
             Event("serve.commit", 40, 5)]
    assert innermost(spans, 15) == "serve.attend"
    assert innermost(spans, 50) == "bench.window"
    assert innermost(spans[1:], 90) == OUTSIDE


def test_op_label_names_program_op_and_shape():
    name = ("%copy.3 = f32[32,256,16,2,8,128]{5,4,3,2,1,0:T(8,128)} "
            "copy(f32[32,256,16,2,8,128]{5,4,3,2,1,0:T(8,128)} %args_0_.1)")
    assert op_label(name, "jit_scatter(6147753343828278966)") == \
        "jit_scatter/copy.3 f32[32,256,16,2,8,128]"


def test_reduce_events_by_hand():
    # device 0: ops 0-10 and 5-15 overlap (busy 15), a kernel 30-40, an op
    # sticking out of the window 95-120 (counts 5); window 0-100
    ops = {"/device:TPU:0": [
        Event("%fusion.1 = f32[4]{0} fusion(x)", 0, 10),
        Event("%copy.1 = f32[4]{0} copy(x)", 5, 10),
        Event("%tpu_custom_call.1 = f32[2,8,128]{2,1,0} custom-call(a)", 30,
              10),
        Event("%copy.2 = f32[4]{0} copy(x)", 95, 25),
    ]}
    modules = {"/device:TPU:0": [Event("jit_scatter(1)", 0, 15),
                                 Event("jit_wrapped(2)", 30, 10),
                                 Event("jit_copy(3)", 95, 25)]}
    spans = [Event("serve.commit", 0, 20), Event("serve.attend", 20, 40),
             Event("serve.attend", 60, 30)]
    red = reduce_events(ops, modules, spans, (0, 100), KERNELS)
    assert red.busy_ns == {"/device:TPU:0": 15 + 10 + 5}
    assert red.kernel_ns == {"paged_attention": 10}
    assert red.kernel_calls == {"paged_attention": 1}
    assert red.op_ns["jit_wrapped/tpu_custom_call.1 f32[2,8,128]"] == 10
    assert red.op_ns["jit_copy/copy.2 f32[4]"] == 5
    assert red.non_kernel_ns() == 10 + 10 + 5
    # gaps 15-30 (midpoint 22.5: serve.attend), 40-95 (midpoint 67.5:
    # the second serve.attend)
    assert red.idle_ns == {"serve.attend": 15 + 55}
    assert red.window_s == pytest.approx(1e-7)
    assert red.busy_s() == pytest.approx(30e-9)


def test_reduce_events_averages_busy_over_devices():
    ops = {"/device:TPU:0": [Event("%a = f32[1]{0} add(x)", 0, 50)],
           "/device:TPU:1": [Event("%b = f32[1]{0} add(x)", 0, 10)]}
    red = reduce_events(ops, {}, [], (0, 100), KERNELS)
    assert red.busy_s() == pytest.approx(30e-9)
    assert red.idle_ns == {OUTSIDE: 50 + 90}


def test_recorded_chip_trace_excerpt():
    """An excerpt of a trace recorded on one TPU v5e: two engine steps of
    the serving tier, four layers each (device ops, programs and the
    benchmark's spans, as ``jax.profiler.ProfileData`` read them)."""
    path = os.path.join(os.path.dirname(__file__),
                        "chip_trace_excerpt.json")
    with open(path) as f:
        rec = json.load(f)
    ops = {d: [Event(*e) for e in evs] for d, evs in rec["ops"].items()}
    mods = {d: [Event(*e) for e in evs] for d, evs in rec["modules"].items()}
    spans = [Event(*e) for e in rec["spans"]]
    red = reduce_events(ops, mods, spans, tuple(rec["window"]), KERNELS)
    n_kernel = sum(e.name.startswith("%tpu_custom_call")
                   for evs in ops.values() for e in evs)
    assert n_kernel > 0
    assert red.kernel_calls["paged_attention"] == n_kernel
    busy = red.busy_s()
    assert 0 < busy < red.window_s
    idle = sum(red.idle_ns.values()) / 1e9
    assert idle + busy == pytest.approx(red.window_s)
    assert "serve.attend" in red.idle_ns


def test_read_trace_finds_host_spans(tmp_path):
    """A trace recorded here on the CPU: the benchmark's spans are read back
    from the host planes (a CPU has no device plane)."""
    import jax
    import jax.numpy as jnp
    from bench.trace import find_xplane, read_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("serve.attend"):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    _ops, _mods, spans = read_trace(find_xplane(str(tmp_path)),
                                    {"bench.window", "serve.attend"})
    names = sorted(s.name for s in spans)
    assert names == ["bench.window", "serve.attend"]
    window = next(s for s in spans if s.name == "bench.window")
    attend = next(s for s in spans if s.name == "serve.attend")
    assert window.start_ns <= attend.start_ns <= attend.end_ns <= window.end_ns
