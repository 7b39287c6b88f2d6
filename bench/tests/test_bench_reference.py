"""The plain references against the program at tiny sizes."""
import numpy as np
import pytest

from bench_testlib import ROOT  # noqa: F401
from bench.reference import (attention_f64, page_matches, page_values,
                             paged_attention_jnp, query_value,
                             session_attention, token_values)


@pytest.mark.parametrize("seq_id", [0, 7, 2**31 - 2, 3_000_000_017])
def test_page_reference_is_the_tiers_oracle(seq_id):
    from repro.runtime.serving import expected_page_slab, token_value
    for page, length in [(0, 3), (1, 8), (2, 9)]:
        slab = expected_page_slab(seq_id, page, length, num_layers=2,
                                  page_tokens=4, kv_heads=2, head_dim=3)
        assert page_matches(slab, seq_id, page, length, 4)
        bad = slab.copy()
        bad[1, 0, 1, 1, 2] += 1.0 / 997
        assert not page_matches(bad, seq_id, page, length, 4)
    assert query_value(seq_id, 11) == token_value(seq_id, 11)
    assert np.array_equal(page_values(seq_id, 0, 2, 4),
                          np.array([*token_values(seq_id, [0, 1]), 0, 0],
                                   np.float32))


def test_attention_reference_matches_program_reference():
    """Over a tiny paged pool: the program's gather reference, the
    benchmark's jnp reference and the float64 reference agree."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ref import paged_attention_ref
    rng = np.random.default_rng(0)
    pool = rng.standard_normal((6, 4, 2, 2, 8)).astype(np.float32)
    q = rng.standard_normal((2, 4, 8)).astype(np.float32)
    tables = np.array([[3, 1, -1], [0, 5, 2]], np.int32)
    lengths = np.array([6, 11], np.int32)
    prog = np.asarray(paged_attention_ref(jnp.asarray(q), jnp.asarray(pool),
                                          jnp.asarray(tables),
                                          jnp.asarray(lengths)))
    mine = np.asarray(paged_attention_jnp(jnp.asarray(q), jnp.asarray(pool),
                                          jnp.asarray(tables),
                                          jnp.asarray(lengths)))
    for b in range(2):
        used = tables[b][tables[b] >= 0]
        kv = pool[used].reshape(-1, 2, 2, 8)[:lengths[b]]
        want = attention_f64(q[b], kv[:, 0], kv[:, 1])
        assert np.abs(prog[b] - want).max() < 1e-5
        assert np.abs(mine[b] - want).max() < 1e-5


def test_lower_passes_are_less_exact():
    """The control's inputs rounded to bfloat16, then to float8, read
    further from float64 at each step down."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    pool = rng.uniform(0, 1, (8, 4, 2, 2, 16)).astype(np.float32)
    q = rng.uniform(0, 1, (1, 2, 16)).astype(np.float32)
    tables = np.arange(8, dtype=np.int32)[None]
    lengths = np.array([32], np.int32)
    want = attention_f64(q[0], pool.reshape(-1, 2, 2, 16)[:, 0],
                         pool.reshape(-1, 2, 2, 16)[:, 1])
    err = {}
    for lower in (None, jnp.bfloat16, jnp.float8_e4m3fn):
        got = np.asarray(paged_attention_jnp(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(lengths), lower=lower))[0]
        err[str(lower)] = np.abs(got - want).max()
    assert err["None"] < err[str(jnp.bfloat16)] < err[str(jnp.float8_e4m3fn)]


def test_session_attention_is_the_tiers_attention():
    """The tier's own attention over its pages (tiny pool, CPU interpreter)
    matches the reference built from the contract alone."""
    from repro.runtime.cluster import Cluster
    from repro.runtime.serving import ServingTier
    cluster = Cluster(2, node_capacity=8 << 20, page_size=4096,
                      replication_factor=1)
    tier = ServingTier(cluster, num_layers=2, page_tokens=4, kv_heads=2,
                       head_dim=8, hbm_pages_per_node=8)
    try:
        tier.admit({11: 9, 12: 5})
        tier.decode([11, 12], 2)
        out = tier.attend([11, 12], layer=1)
        for s in (11, 12):
            n = tier.sessions[s].length
            want = session_attention(s, n, 2, 2, 8)
            assert np.abs(out[s] - want).max() < 1e-5
            kv = np.broadcast_to(
                token_values(s, np.arange(n)).astype(np.float32)[:, None,
                                                                 None],
                (n, 2, 8))
            q = np.full((2, 8), query_value(s, n), np.float32)
            assert np.abs(attention_f64(q, kv, kv) - want).max() < 1e-12
    finally:
        tier.close()
        cluster.shutdown()
