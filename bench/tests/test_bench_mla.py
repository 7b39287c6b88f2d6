"""The latent (MLA) KV cell's benchmark files at tiny sizes on the CPU: the
driver's set-up and steps through the real serving tier on a tiny latent
cell, ``reference_mla`` against the program, ``costs_mla`` against hand
counts, and planted faults (a wrong layer, rope and nope channels swapped,
values from the wrong channels, the float8 control) that turn ``correct``
false."""
import math

import numpy as np
import pytest

from bench_testlib import ROOT, cpu_harness, dump, load, run, tiny_root

TINY_MLA = dict(num_layers=2, num_hidden_layers=2, latent_dim=40,
                value_dim=32, q_heads=8, page_tokens=4,
                hbm_pages_per_node=16, host_budget_pages=2,
                host_pool_bytes_per_node=8 << 20)
TINY_MLA_TRAFFIC = dict(driver="kv_serve_mla", sessions=8, homing="even",
                        context_tokens=[6, 20], running_per_node=None,
                        running_page_budget=None, burst_tokens=None,
                        warmup_steps=1, horizon_steps=4, plan_seed=5)
CELL = "tiny-mla"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-like copy of the benchmark with the latent cell
    ``tiny-mla`` added by files and entries, as the real one was."""
    root = tiny_root(tmp_path_factory.mktemp("bench"))
    spec = load(root / "BENCHMARK.json")
    cfg = load(ROOT / "bench" / "configs" / "kv-deepseek-v3-mla.json")
    cfg.update(TINY_MLA, name="tiny-mla")
    dump(cfg, root / "bench" / "configs" / "tiny-mla.json")
    dump(TINY_MLA_TRAFFIC, root / "bench" / "traffic" / "tiny-mla.json")
    spec["configs"].append({"name": "tiny-mla", "source": "test",
                            "file": "bench/configs/tiny-mla.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-mla",
                              "traffic": "tiny-mla", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        if m["name"].startswith("mla_"):
            m["workloads"].append(CELL)
    dump(spec, root / "BENCHMARK.json")
    return root


def test_tiny_cell_runs_and_is_correct(root, monkeypatch):
    harness = cpu_harness(monkeypatch)
    result = run(harness, root, CELL, seed=2**31 + 11, seconds=0.3)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["checks"]) == {"length_mismatch", "page_mismatch",
                                     "replica_mismatch", "attn_rel_gap",
                                     "ref_rel_gap"}
    assert set(result["metrics"]) == {"tokens_per_s", "itl_ms_p95",
                                      "setup_s"}


def _wrong_layer(monkeypatch):
    """Each call's query is the next layer's, against this layer's pages."""
    from repro.runtime.serving import ServingTier
    real = ServingTier._query
    monkeypatch.setattr(ServingTier, "_query", lambda self, s, layer: real(
        self, s, (layer + 1) % self.num_layers))


def _latent_attention_with(monkeypatch, query=None, value_from=0):
    """The program's latent attention, written plainly, with the query's
    channels rearranged by ``query`` and the values read from channel
    ``value_from`` on (the scores still over every channel)."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops

    def altered(q, kv_pages, block_tables, lengths, *, value_dim, scale,
                impl="kernel"):
        if query is not None:
            q = query(q, value_dim)
        B = q.shape[0]
        page, channels = kv_pages.shape[1:]
        T = block_tables.shape[1] * page
        kv = kv_pages[jnp.maximum(block_tables, 0)].reshape(
            B, T, channels).astype(jnp.float32)
        s = jnp.einsum("bhc,btc->bht", q.astype(jnp.float32), kv,
                       precision="highest") * scale
        s = jnp.where(jnp.arange(T)[None, None, :] < lengths[:, None, None],
                      s, -1e30)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        o = jnp.einsum("bht,btv->bhv", p,
                       kv[..., value_from:value_from + value_dim],
                       precision="highest")
        return (o / p.sum(-1, keepdims=True)).astype(q.dtype)

    monkeypatch.setattr(ops, "paged_latent_attention", altered)


def _rope_nope_swapped(monkeypatch):
    """The query's rope channels put first, its no-rope channels after."""
    import jax.numpy as jnp
    _latent_attention_with(monkeypatch, query=lambda q, v: jnp.concatenate(
        [q[..., v:], q[..., :v]], -1))


def _values_shifted(monkeypatch):
    """Values read from the last ``value_dim`` channels, not the first."""
    _latent_attention_with(monkeypatch,
                           value_from=TINY_MLA["latent_dim"]
                           - TINY_MLA["value_dim"])


def _written_plainly(monkeypatch):
    """No fault: the plain attention of ``_latent_attention_with`` itself,
    so that the faults above are the only change they make."""
    _latent_attention_with(monkeypatch)


FAULTS = {
    "wrong_layer": _wrong_layer,
    "rope_nope_swapped": _rope_nope_swapped,
    "values_shifted": _values_shifted,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(root, fault, monkeypatch):
    harness = cpu_harness(monkeypatch)
    FAULTS[fault](monkeypatch)
    result = run(harness, root, CELL, seed=2**31 + 12, seconds=0.3)
    assert not result["correct"]
    c = result["checks"]["attn_rel_gap"]
    assert c["value"] > c["limit"]
    assert all(result["checks"][k]["value"] == 0 for k in
               ("length_mismatch", "page_mismatch", "replica_mismatch"))


def test_plain_attention_in_the_kernels_place_is_correct(root, monkeypatch):
    """The harness the faults above plant, with no fault in it."""
    harness = cpu_harness(monkeypatch)
    _written_plainly(monkeypatch)
    result = run(harness, root, CELL, seed=2**31 + 12, seconds=0.3)
    assert result["correct"], result["checks"]


def test_control_is_not_correct(root, monkeypatch):
    """The reference with its inputs rounded to float8, the precision below
    the configuration's bfloat16, in the latent kernel's place."""
    from bench.control_mla import control_latent_attention
    harness = cpu_harness(monkeypatch)
    dtype = load(root / "bench" / "configs" / "tiny-mla.json")["dtype"]
    with control_latent_attention(dtype):
        result = run(harness, root, CELL, seed=2**31 + 13, seconds=0.3)
    assert not result["correct"]
    c = result["checks"]["attn_rel_gap"]
    assert c["limit"] < c["value"] < np.inf
    assert result["checks"]["ref_rel_gap"]["value"] <= \
        result["checks"]["ref_rel_gap"]["limit"]


def test_traced_run_reports_the_mla_metrics(root, monkeypatch):
    harness = cpu_harness(monkeypatch)
    monkeypatch.setattr(harness, "ROOT", root)   # where the trace lands
    result = run(harness, root, CELL, seed=2**31 + 14, seconds=0.3,
                 traced=True)
    assert result["correct"], result["checks"]
    metrics = result["metrics"]
    # the CPU trace has no TPU ops: the device metrics have nothing to read
    assert "mla_attn_roofline" not in metrics
    for name in ("mla_dispatch_ms_per_call", "mla_fetch_ms_per_call"):
        assert metrics[name]["value"] > 0, name


# -- the reference against the program ----------------------------------------
def test_reference_content_is_the_programs():
    """``reference_mla`` rebuilds the tier's latent pages and queries from
    the contract alone, bit for bit, in numpy and in jax.numpy."""
    import jax.numpy as jnp
    import ml_dtypes
    from repro.runtime.serving import expected_latent_slab, latent_query
    from bench.reference_mla import (page_matches, query_np,
                                     session_latent_np, value_jnp, value_np)
    bf16 = ml_dtypes.bfloat16
    for s in (0, 7, 2**31 - 2):
        slab = expected_latent_slab(s, 2, 11, num_layers=3, page_tokens=4,
                                    latent_dim=40, dtype=bf16)
        assert page_matches(slab, s, 2, 11, 4)
        assert not page_matches(slab, s, 2, 10, 4)     # token 10 is live
        assert not page_matches(slab, s + 1, 2, 11, 4)
        for layer in range(3):
            np.testing.assert_array_equal(
                slab[layer, :3], session_latent_np(s, layer, 11, 40,
                                                   bf16)[8:11])
        np.testing.assert_array_equal(latent_query(s, 11, 1, 8, 40, bf16),
                                      query_np(s, 11, 1, 8, 40, bf16))
    idx = (1, 2**31 - 2, np.arange(70)[:, None], 3, 5,
           np.arange(576)[None, :])
    np.testing.assert_array_equal(np.asarray(value_jnp(*idx)),
                                  value_np(*idx))
    v = value_np(*idx)
    assert v.min() >= -1 and v.max() < 1 and abs(v.mean()) < 0.01
    assert np.asarray(value_jnp(*idx)).dtype == jnp.float32


def test_reference_attention_is_the_programs():
    """The tier's latent attention, through its kernel and XLA paths, equals
    ``reference_mla``'s float32 and float64 references on the contract's
    content, at 8 heads, 32 + 8 channels and 4-token pages."""
    from repro.runtime.cluster import Cluster
    from repro.runtime.serving import LatentLayout, ServingTier
    from bench.reference_mla import (attention_f64, query_np, rel_gap,
                                     session_latent_np, steps_attention_jnp)
    scale = 0.3
    cluster = Cluster(4, node_capacity=8 << 20, page_size=1 << 14,
                      replication_factor=1, admission=True)
    tier = ServingTier(cluster, num_layers=2, page_tokens=4,
                       layout=LatentLayout(40, 32, 8, scale),
                       hbm_pages_per_node=16)
    try:
        tier.admit({3: 9, 4: 14})
        tier.decode([3, 4], steps=2)
        for layer in range(2):
            for impl in ("kernel", "xla"):
                out = tier.attend([3, 4], layer, impl=impl)
                for s in (3, 4):
                    n = tier.sessions[s].length
                    f64 = attention_f64(
                        query_np(s, n, layer, 8, 40, np.float32),
                        session_latent_np(s, layer, n, 40, np.float32),
                        32, scale)
                    f32 = np.asarray(steps_attention_jnp(
                        s, layer, np.array([n], np.uint32), 32, 8, 40, 32,
                        scale, np.float32))[0]
                    assert out[s].shape == (8, 32)
                    assert rel_gap(out[s], f64) < 1e-5
                    assert rel_gap(f32, f64) < 1e-5
    finally:
        tier.close()
        cluster.shutdown()


# -- costs ----------------------------------------------------------------------
def test_costs_are_counted_by_hand():
    from bench.costs_mla import latent_attention_cost
    flops, nbytes = latent_attention_cost([3, 5], heads=4, latent_dim=10,
                                          value_dim=8, itemsize=2)
    # 8 tokens of one 10-channel vector, read once; q (4 x 10) and the
    # output (4 x 8) of each of the 2 sequences
    assert nbytes == 8 * 10 * 2 + 2 * (4 * 10 + 4 * 8) * 2
    # q . kv over 10 channels and p . v over 8, a multiply-add each
    assert flops == 2 * 8 * 4 * (10 + 8)
    # DeepSeek-V3 at its widths: 1,152 bytes and 278,528 FLOPs a token
    f, b = latent_attention_cost([1], 128, 576, 512, 2)
    assert f == 278528 and b - 128 * (576 + 512) * 2 == 1152


def test_config_keeps_the_published_widths():
    """The configuration's derived sizes follow from the published keys."""
    cfg = load(ROOT / "bench" / "configs" / "kv-deepseek-v3-mla.json")
    assert cfg["latent_dim"] == cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    assert cfg["value_dim"] == cfg["kv_lora_rank"]
    assert cfg["q_heads"] == cfg["num_attention_heads"] == 128
    rope = cfg["rope_scaling"]
    mscale = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    assert cfg["softmax_scale"] == pytest.approx(mscale ** 2 / math.sqrt(qk),
                                                 rel=1e-12)
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 4
    assert cfg["published"]["num_hidden_layers"] == 61
