"""Metric arithmetic: window accounting, inter-token gaps, the p95, the
paged-attention bytes and the roofline share."""
from types import SimpleNamespace

import pytest

from bench_testlib import ROOT
from bench.costs import paged_attention_cost
from bench.drivers.kv_serve import Step, Window
from bench.harness import metric_reader


def metric(name):
    return metric_reader(name, ROOT).read


def window(ends, running, resumed=None, t0=0.0):
    w = Window(t0=t0)
    for k, (t, r) in enumerate(zip(ends, running)):
        w.step_end.append(t)
        w.steps.append(Step(list(r), list((resumed or {}).get(k, [])), {}))
    return w


def test_tokens_per_s_counts_every_token_and_every_second():
    # three steps of 2, 2 and 3 tokens; the last crosses the deadline and
    # still counts, with the time it took
    w = window([1.0, 2.0, 3.5], [[1, 2], [1, 2], [1, 2, 3]], t0=0.5)
    assert w.tokens == 7
    run = SimpleNamespace(window=w)
    assert metric("tokens_per_s")(run) == pytest.approx(7 / 3.0)


def test_gaps_skip_first_token_and_pauses():
    # session 1 runs steps 0-3; session 2 runs 0, pauses, resumes at 2;
    # session 3 joins at step 3 (its first in-window token has no gap)
    w = window([1.0, 2.0, 4.0, 4.5],
               [[1, 2], [1], [1, 2], [1, 2, 3]], resumed={2: [2]})
    assert sorted(w.gaps_s()) == sorted([1.0, 2.0, 0.5,    # session 1
                                         0.5])             # session 2, 2->3


def test_itl_p95_is_nearest_rank_over_all_gaps():
    p95 = metric_reader("itl_ms_p95", ROOT).p95
    assert p95(list(range(1, 101))) == 95
    assert p95([3.0]) == 3.0
    assert p95([1, 2]) == 2
    # twenty gaps: eighteen of 1 s and two of 1.5 s; the 19th is the p95
    ends = [k + 0.5 * (k >= 5) + 0.5 * (k >= 15) for k in range(21)]
    run = SimpleNamespace(window=window(ends, [[1]] * 21))
    assert metric("itl_ms_p95")(run) == pytest.approx(1500.0)
    ends = [k + 0.5 * (k >= 5) for k in range(21)]
    run = SimpleNamespace(window=window(ends, [[1]] * 21))
    assert metric("itl_ms_p95")(run) == pytest.approx(1000.0)


def test_paged_attention_cost_by_hand():
    # two sequences of 3 and 5 tokens, 4 heads over 2 KV heads, D=8, f32:
    # K and V: 8 tokens * 2 * 2 * 8 * 4 bytes; q and out: 2 * 2 * 4 * 8 * 4
    flops, nbytes = paged_attention_cost([3, 5], heads=4, kv_heads=2,
                                         head_dim=8, itemsize=4)
    assert nbytes == 8 * 2 * 2 * 8 * 4 + 2 * 2 * 4 * 8 * 4
    assert flops == 4 * 8 * 4 * 8
    assert paged_attention_cost([], 4, 2, 8, 4) == (0, 0)


def test_roofline_share_and_its_silence():
    from bench.trace import Reduction
    red = Reduction(window_ns=(0, 1e9), kernel_ns={"paged_attention": 2e6},
                    busy_ns={"/device:TPU:0": 5e8})
    w = SimpleNamespace(bytes=819e6, flops=1e9, tokens=10)
    run = SimpleNamespace(trace=red, window=w, peaks={
        "hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    # 819 MB at 819 GB/s is 1 ms, against 2 ms of kernel time
    assert metric("paged_attn_roofline")(run) == pytest.approx(50.0)
    assert metric("device_idle.serve")(run) == pytest.approx(50.0)
    red.kernel_ns = {}
    assert metric("paged_attn_roofline")(run) is None
    run.trace = None
    for name in ("paged_attn_roofline", "device_idle.serve",
                 "pool_ops_device_ms_per_token"):
        assert metric(name)(run) is None


def test_span_and_counter_metrics():
    w = SimpleNamespace(tokens=40, steps=[None] * 4, fetches=10)
    run = SimpleNamespace(
        window=w, spans=SimpleNamespace(seconds={"serve.commit": 0.2,
                                                 "serve.attend": 8.0}),
        compiles=SimpleNamespace(compiles=7))
    assert metric("commit_host_ms_per_token")(run) == pytest.approx(5.0)
    assert metric("attend_host_ms_per_step")(run) == pytest.approx(2000.0)
    assert metric("hbm_restores_per_token")(run) == pytest.approx(0.25)
    assert metric("window_compiles")(run) == 7
