"""The KV driver and the harness around it, at tiny sizes on the CPU: the
plan, set-up and steps through the real serving tier, the result line, a
cell added by files alone, and the refusals (no chip, no program)."""
import json
import subprocess
import sys

import numpy as np
import pytest

from bench_testlib import (ROOT, cpu_harness, dump, env_cpu, load, run,
                           tiny_root)
from bench.drivers.kv_serve import (Planner, context_quantiles,
                                    draw_bursts, draw_sessions)


def test_context_quantiles_are_log_uniform_midpoints():
    assert context_quantiles(1, 256, 1024) == [512]
    assert context_quantiles(3, 512, 2048) == [645, 1024, 1625]


def test_every_seed_draws_the_same_sizes():
    """Every seed gets the same plan (sizes, arrivals, bursts, from
    ``plan_seed``) with other session ids."""
    traffic = load(ROOT / "bench" / "traffic" / "burst-spill.json")
    affinity = lambda s: s % 4   # noqa: E731
    plans, ids = [], []
    for seed in (1, 2, 2**31 + 5):
        rng = np.random.default_rng(seed)
        plan_rng = np.random.default_rng(traffic["plan_seed"])
        sessions = draw_sessions(traffic, 4, affinity, rng, plan_rng)
        assert [affinity(s) for s, node, _c in sessions] == \
            [node for _s, node, _c in sessions]
        homes = [node for _s, node, _c in sessions]
        assert all(homes.count(n) == traffic["sessions"] // 4
                   for n in range(4))
        bursts = draw_bursts(traffic, [s for s, _n, _c in sessions],
                             plan_rng)
        assert sorted(bursts[sessions[0][0]]) == \
            sorted(bursts[sessions[1][0]])
        assert min(bursts[sessions[0][0]]) == traffic["burst_tokens"][0]
        assert max(bursts[sessions[0][0]]) == traffic["burst_tokens"][1]
        plans.append([(node, c, tuple(bursts[s])) for s, node, c in sessions])
        ids.append([s for s, _n, _c in sessions])
    assert plans[0] == plans[1] == plans[2]
    assert ids[0] != ids[1] != ids[2]


def test_planner_pauses_resumes_and_keeps_the_budget():
    # node 0: sessions 1-4 (8, 8, 8, 4 tokens), two run at a time, budget
    # 6 pages of 4 tokens; bursts of 2 tokens
    sessions = [(1, 0, 8), (2, 0, 8), (3, 0, 8), (4, 0, 4), (5, 1, 4)]
    bursts = {s: [2] for s, _n, _c in sessions}
    p = Planner(sessions, page_tokens=4, running_per_node=2, page_budget=6,
                bursts=bursts)
    assert p.running == {0: [1, 2], 1: [5]}
    assert list(p.waiting[0]) == [3, 4]
    steps = [p.step() for _ in range(4)]
    assert steps[0].running == [1, 5, 2]          # interleaved over nodes
    assert steps[0].lengths == {1: 9, 5: 5, 2: 9}
    # session 1 was one token from its pause: after step 0 it waits, and
    # the longest waiter, 3, fits the budget and resumes
    assert steps[1].resumed == [3]
    assert 1 not in steps[1].running and 3 in steps[1].running
    for st in steps:
        on0 = [s for s in st.running if s in (1, 2, 3, 4)]
        assert sum(-(-st.lengths[s] // 4) for s in on0) <= 6 + 2
    again = Planner(sessions, 4, 2, 6, bursts)
    assert [again.step().running for _ in range(4)] == \
        [st.running for st in steps]


def test_planner_without_pauses_runs_everyone():
    sessions = [(1, 0, 5), (2, 1, 5), (3, 0, 5)]
    p = Planner(sessions, 4, None, None, None)
    st = p.step()
    assert st.running == [1, 2, 3] and st.resumed == []
    assert p.step().lengths == {1: 7, 2: 7, 3: 7}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny-burst", "tiny-fit"])
def test_setup_and_steps_are_correct(root, cell, monkeypatch):
    harness = cpu_harness(monkeypatch)
    result = run(harness, root, cell, seed=2**31 + 99, seconds=0.3)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s"}
    assert result["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    checks = result["checks"]
    assert checks["page_mismatch"] == {"value": 0, "limit": 0}
    assert 0 <= checks["attn_gap"]["value"] < checks["attn_gap"]["limit"]


def test_same_seed_same_inputs(root, monkeypatch):
    harness = cpu_harness(monkeypatch)
    from bench.drivers import kv_serve
    cell = harness.load_cell("tiny-burst", root)
    plans = []
    for seed in (77, 77, 78):
        state = kv_serve.setup(cell, seed, harness.Spans())
        plans.append([(st.running, st.lengths) for st in state.plan])
        kv_serve.close(state)
    assert plans[0] == plans[1]
    assert plans[0] != plans[2]


def test_traced_run_reports_per_layer_metrics(root, monkeypatch):
    harness = cpu_harness(monkeypatch)
    result = run(harness, root, "tiny-burst", seconds=0.3, traced=True)
    assert result["correct"]
    assert list(result)[-2:] == ["breakdown", "checks"]
    metrics = result["metrics"]
    assert "tokens_per_s" not in metrics
    assert metrics["window_compiles"]["value"] > 0
    assert metrics["commit_host_ms_per_token"]["value"] > 0
    # a CPU has no device plane: the device metrics have nothing to read
    assert "paged_attn_roofline" not in metrics
    assert "device_idle.serve" not in metrics
    assert result["device"]["busy_s"] == 0.0


def test_cell_added_by_files_and_entries(root, tmp_path, monkeypatch):
    """A new mix, a new metric and a new cell: new files and new entries in
    BENCHMARK.json, and no file of the benchmark changes."""
    import shutil
    new = tmp_path / "checkout"
    shutil.copytree(root, new)
    before = {p: p.read_bytes() for p in (new / "bench").rglob("*")
              if p.is_file()}
    traffic = load(new / "bench" / "traffic" / "tiny-fit.json")
    traffic.update(sessions=4, context_tokens=[4, 12])
    dump(traffic, new / "bench" / "traffic" / "dummy-mix.json")
    (new / "bench" / "metrics" / "dummy_sessions.py").write_text(
        "def read(run):\n    return float(len(run.window.steps[0].running))\n")
    spec = load(new / "BENCHMARK.json")
    spec["workloads"].append({"name": "dummy", "config": "tiny-kv",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "dummy_sessions", "unit": "sessions",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "tokens_per_s",
                              "workloads": ["dummy"]})
    dump(spec, new / "BENCHMARK.json")
    for p, data in before.items():
        assert p.read_bytes() == data
    harness = cpu_harness(monkeypatch)
    result = run(harness, new, "dummy", traced=True)
    assert result["correct"]
    assert result["metrics"]["dummy_sessions"] == {"value": 4.0,
                                                   "unit": "sessions"}


def test_no_tpu_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "kv-decode-fit", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env_cpu(), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_paths_alone_are_not_enough(tmp_path):
    """Without the program beside it the benchmark exits non-zero and
    prints no result."""
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env_cpu(), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
