"""chip_smoke.py at tiny sizes on the CPU: every phase's checks, the
four-node pool placement on four virtual devices, the refusal to run
without a TPU, and where the persistent compilation cache goes."""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SRC = os.path.join(REPO, "src")


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module     # dataclasses look it up by name
    spec.loader.exec_module(module)
    return module


smoke = _load_smoke()

TINY_SERVING = smoke.ServingSize(
    num_layers=2, kv_heads=2, head_dim=8, page_tokens=4, hbm_pages=12,
    num_seqs=8, prompt_tokens=16, decode_steps=8, warmup_steps=2,
    host_budget_pages=2)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    env.update(extra)
    return env


def test_serving_phase_spills_fails_over_and_verifies():
    out = smoke.run_serving(TINY_SERVING)
    assert out["failovers"] >= 1
    assert all(out["spills"][level] > 0 for level in
               ("hbm_to_host", "host_puts", "host_to_remote",
                "remote_fetches"))
    assert out["decode_compiles"] == 0
    assert out["attention"]["sessions"] > 0
    assert out["attention"]["err_f64"] <= smoke.ATTN_ATOL
    assert out["pool_bytes"] == 4 * 12 * 2 * 4 * 2 * 2 * 8 * 4


def test_kernel_phase_matches_float64_on_ragged_pages():
    size = smoke.KernelSize(batch=4, heads=8, kv_heads=2, head_dim=128,
                            pool_pages=32, max_pages=6)
    out = smoke.run_kernel(3, size, require_custom_call=False)
    assert out["err_f64"] <= smoke.ATTN_ATOL
    assert out["err_xla"] <= smoke.ATTN_ATOL
    assert not out["tpu_custom_call"]     # interpreted on the CPU


def test_analytics_phase_matches_numpy():
    out = smoke.run_analytics(5, 40_000)
    assert out["groups"] > 0 and out["join_rows"] > 0
    assert out["recovered_shards"] >= 1


def test_proc_phase_sigkill_and_revive():
    out = smoke.run_proc(7, 30_000)
    assert out["groups"] > 0


FOUR_DEVICES = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
import chip_smoke as smoke
assert len(jax.devices()) == 4, jax.devices()
out = smoke.run_serving(smoke.ServingSize(**{size!r}),
                        require_distinct_devices=True)
print("POOL_DEVICES", len(out["pool_devices"]),
      len(out["attention"]["devices"]), out["failovers"])
"""


def test_four_nodes_place_pools_on_four_devices():
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = FOUR_DEVICES.format(repo=REPO,
                                 size=dataclasses.asdict(TINY_SERVING))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("POOL_DEVICES")][-1]
    pools, attended, failovers = map(int, line.split()[1:])
    assert pools == 4 and attended > 1 and failovers >= 1


def test_main_exits_nonzero_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    printed = capsys.readouterr().out
    assert '"ok"' not in printed
    assert "platform=cpu" in printed


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


CACHE_PROBE = r"""
import os
import jax, jax.numpy as jnp
from repro.compile_cache import REPO_CACHE_DIR, configure_compile_cache
where = configure_compile_cache()
print("CACHE", where, jax.config.jax_compilation_cache_dir)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
else:
    assert where == str(REPO_CACHE_DIR)
"""


def test_compile_cache_follows_the_environment(tmp_path):
    env = _cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"CACHE {tmp_path} {tmp_path}" in out.stdout
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))


def test_compile_cache_defaults_to_the_fixed_repo_dir():
    env = _cpu_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    want = os.path.join(REPO, ".jax_cache")
    assert f"CACHE {want} {want}" in out.stdout
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_result_line_names_the_device_and_nothing_more():
    chips = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
             for _ in range(4)]
    doc = json.loads(smoke.result_line(chips))
    assert doc == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
