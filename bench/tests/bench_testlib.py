"""Shared set-up of the benchmark's CPU tests: a copy of the benchmark with
tiny cells added the way a later change adds cells (new files and new
entries), and a harness steered onto the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_CONFIG = dict(num_layers=2, kv_heads=2, head_dim=8, page_tokens=4,
                   hbm_pages_per_node=16, host_budget_pages=2,
                   host_pool_bytes_per_node=8 << 20)
TINY_TRAFFIC = {
    "tiny-burst": dict(sessions=8, homing="hot_node", context_tokens=[8, 24],
                       running_per_node=2, running_page_budget=12,
                       burst_tokens=[1, 2], warmup_steps=1,
                       horizon_steps=4, plan_seed=3),
    "tiny-fit": dict(sessions=8, homing="even", context_tokens=[8, 24],
                     running_per_node=None, running_page_budget=None,
                     burst_tokens=None, warmup_steps=1, horizon_steps=4,
                     plan_seed=3),
}


def load(path: Path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def dump(obj, path: Path) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp: Path) -> Path:
    """A checkout-like copy of the benchmark with the cells ``tiny-burst``
    and ``tiny-fit`` (tiny KV geometry) added by files and entries."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = load(ROOT / "BENCHMARK.json")
    cfg = load(ROOT / "bench" / "configs" / "kv-minitron-8b.json")
    cfg.update(TINY_CONFIG, name="tiny-kv")
    dump(cfg, root / "bench" / "configs" / "tiny-kv.json")
    spec["configs"].append({"name": "tiny-kv", "source": "test",
                            "file": "bench/configs/tiny-kv.json",
                            "reduced": [], "why": "test"})
    for name, params in TINY_TRAFFIC.items():
        traffic = dict(driver="kv_serve", **params)
        dump(traffic, root / "bench" / "traffic" / f"{name}.json")
        spec["workloads"].append({"name": name, "config": "tiny-kv",
                                  "traffic": name, "chips": 1,
                                  "why": "test"})
        for m in spec["per_layer"]:
            m["workloads"].append(name)
    dump(spec, root / "BENCHMARK.json")
    return root


def cpu_harness(monkeypatch):
    """The harness with its look for a chip, its compilation cache and its
    peaks table steered onto the CPU."""
    import jax
    from bench import harness
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices()[:1])
    monkeypatch.setattr(harness, "configure_compilation_cache",
                        lambda: "off")
    monkeypatch.setattr(harness, "peaks_for", lambda kind, root=None: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    return harness


def run(harness, root: Path, cell: str, seed: int = 12345,
        seconds: float = 0.01, traced: bool = False):
    import time
    return harness.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                            root=root)


def env_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env
