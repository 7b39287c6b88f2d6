"""Kernel sweeps: shapes × dtypes, assert_allclose vs the pure-jnp oracles
(each Pallas kernel validated with interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.linear_scan.ops import diag_scan, gla_scan
from repro.kernels.linear_scan.ref import diag_scan_ref, gla_scan_ref
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_latent_attention)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.shuffle_dispatch.ops import combine, compute_slots, dispatch
from repro.kernels.shuffle_dispatch.ref import combine_ref, dispatch_ref

RNG = np.random.default_rng(42)


def _t(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-5, atol=3e-5)


FLASH_CASES = [
    # B, H, KH, Tq, Tk, D, causal, window
    (1, 4, 2, 64, 64, 32, True, None),
    (2, 4, 4, 40, 72, 16, True, None),
    (1, 2, 1, 64, 64, 32, False, None),
    (1, 2, 2, 96, 96, 32, True, 32),
    (1, 8, 4, 128, 128, 64, True, None),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_kernel_sweep(case, dtype):
    B, H, KH, Tq, Tk, D, causal, window = case
    q, k, v = _t((B, H, Tq, D), dtype), _t((B, KH, Tk, D), dtype), \
        _t((B, KH, Tk, D), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    ker = flash_attention(q, k, v, causal=causal, window=window,
                          impl="kernel", block_q=32, block_k=32)
    xla = flash_attention(q, k, v, causal=causal, window=window, impl="xla",
                          block_k=32)
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(xla, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_xla_grads_match_naive():
    q, k, v = _t((1, 4, 48, 16), jnp.float32), _t((1, 2, 48, 16),
                                                  jnp.float32), \
        _t((1, 2, 48, 16), jnp.float32)

    def loss_x(q, k, v):
        return (flash_attention(q, k, v, impl="xla", block_k=16) ** 2).sum()

    def loss_r(q, k, v):
        return (attention_ref(q, k, v) ** 2).sum()

    gx = jax.grad(loss_x, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
    for a, b in zip(gx, gr):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


PAGED_CASES = [
    (2, 4, 2, 32, 16, 8, 4),
    (1, 8, 8, 16, 8, 16, 3),
    (3, 4, 1, 64, 32, 8, 6),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_kernel_sweep(case, dtype):
    B, H, KH, D, P, page, maxp = case
    q = _t((B, H, D), dtype)
    kv = _t((P, page, 2, KH, D), dtype)
    bts, lens = [], []
    for b in range(B):
        n = RNG.integers(1, maxp + 1)
        pages = RNG.choice(P, size=n, replace=False)
        bt = np.full(maxp, -1, np.int32)
        bt[:n] = pages
        bts.append(bt)
        lens.append(RNG.integers((n - 1) * page + 1, n * page + 1))
    bt = jnp.asarray(np.stack(bts))
    ln = jnp.asarray(np.array(lens, np.int32))
    ref = paged_attention_ref(q, kv, bt, ln)
    ker = paged_attention(q, kv, bt, ln, impl="kernel")
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_kernel_compiles_once_per_shape(backend_compiles):
    """The kernel path keeps one compiled program per shape for the whole
    process: repeated calls of a shape compile nothing, and a new
    ``max_pages`` compiles once. (A geometry no other test uses, so that
    neither shape is compiled before this test.)"""
    B, H, KH, D, P, page = 2, 6, 3, 16, 12, 4
    q = _t((B, H, D), jnp.float32)
    kv = _t((P, page, 2, KH, D), jnp.float32)
    ln = jnp.asarray(np.array([2 * page + 1, 3 * page], np.int32))
    # row b holds pages 5b, 5b + 1, ...: the live pages are the same at
    # either width
    tables = {mp: jnp.asarray(5 * np.arange(B, dtype=np.int32)[:, None]
                              + np.arange(mp, dtype=np.int32))
              for mp in (3, 5)}
    outs = []
    for call in range(3):
        before = len(backend_compiles)
        outs.append(paged_attention(q, kv, tables[3], ln, impl="kernel"))
        outs[-1].block_until_ready()
        if call:
            assert len(backend_compiles) == before, f"call {call} compiled"
    for out in outs[1:]:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(outs[0]))
    before = len(backend_compiles)
    wide = paged_attention(q, kv, tables[5], ln, impl="kernel")
    wide.block_until_ready()
    assert len(backend_compiles) == before + 1
    # pages past each length are masked: the wider table reads the same
    np.testing.assert_allclose(np.asarray(wide), np.asarray(outs[0]),
                               **_tol(jnp.float32))


LATENT_CASES = [
    # B, H, latent (value + rope), value, pool pages, page, max pages
    (3, 8, 40, 32, 24, 4, 6),
    (2, 4, 40, 32, 16, 4, 5),
    (1, 8, 24, 16, 8, 8, 3),
]


def _plain_latent_attention(q, kv, tables, lengths, value_dim, scale):
    """Per sequence and head, in float64: softmax over scale * q . kv of the
    leading ``value_dim`` channels, over the sequence's valid tokens."""
    q, kv = np.asarray(q, np.float64), np.asarray(kv, np.float64)
    out = []
    for b, n in enumerate(np.asarray(lengths)):
        toks = kv[np.asarray(tables)[b]].reshape(-1, kv.shape[-1])[:n]
        s = scale * q[b] @ toks.T
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append(p @ toks[:, :value_dim] / p.sum(-1, keepdims=True))
    return np.stack(out)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", LATENT_CASES)
def test_paged_latent_kernel_sweep(case, dtype):
    """The latent kernel (interpreted) and its XLA path against a plain
    reference over ragged lengths: one token into a page, a page exactly
    full, and tables padded past the live pages."""
    B, H, C, V, P, page, maxp = case
    q = _t((B, H, C), dtype)
    kv = _t((P, page, C), dtype)
    pages = RNG.permutation(P)[:B * maxp].reshape(B, maxp).astype(np.int32)
    live = [1, maxp, max(1, maxp - 2)][:B]
    lens = [n * page - cut for n, cut in zip(live, (page - 1, 0, 1))]
    tables = np.where(np.arange(maxp)[None, :] < np.array(live)[:, None],
                      pages, -1).astype(np.int32)
    scale = C ** -0.5
    want = _plain_latent_attention(q.astype(jnp.float32), kv.astype(
        jnp.float32), np.maximum(tables, 0), lens, V, scale)
    for impl in ("kernel", "xla"):
        got = paged_latent_attention(q, kv, jnp.asarray(tables),
                                     jnp.asarray(np.array(lens, np.int32)),
                                     value_dim=V, scale=scale, impl=impl)
        assert got.shape == (B, H, V) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [(2, 64, 16, 16), (1, 100, 8, 32),
                                  (3, 32, 32, 32)])
def test_diag_scan_sweep(case, dtype):
    B, T, D, chunk = case
    a = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=(B, T, D)))), dtype)
    b = _t((B, T, D), dtype)
    h0 = _t((B, D), dtype)
    h_ref, hT_ref = diag_scan_ref(a, b, h0)
    h_k, hT_k = diag_scan(a, b, h0, impl="kernel", chunk=chunk)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(h_k, np.float32),
                               np.asarray(h_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hT_k, np.float32),
                               np.asarray(hT_ref, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("case", [(2, 32, 16, 16, 16), (1, 64, 32, 16, 16),
                                  (2, 48, 8, 24, 16)])
def test_gla_scan_sweep(case, dtype):
    B, T, Dk, Dv, chunk = case
    r, k = _t((B, T, Dk), dtype), _t((B, T, Dk), dtype)
    v = _t((B, T, Dv), dtype)
    w = jnp.asarray(-np.exp(RNG.normal(size=(B, T, Dk)) * 0.5), dtype)
    u = _t((B, Dk), dtype)
    o_ref, S_ref = gla_scan_ref(r, k, v, w, u)
    for impl in ("kernel", "xla_chunked"):
        o, S = gla_scan(r, k, v, w, u, impl=impl, chunk=chunk)
        np.testing.assert_allclose(o, o_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(S, S_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("case", [(64, 32, 4, 2, 32), (128, 16, 8, 1, 24),
                                  (96, 64, 16, 6, 16)])
def test_shuffle_dispatch_sweep(case):
    T, D, E, K, C = case
    x = _t((T, D), jnp.float32)
    eid = jnp.asarray(RNG.integers(0, E, size=(T, K)), jnp.int32)
    gates = jnp.asarray(RNG.random(size=(T, K)), jnp.float32)
    slot = compute_slots(eid, E, C)
    dref = dispatch_ref(x, eid, slot, E, C)
    dker = dispatch(x, eid, slot, E, C, impl="kernel")
    np.testing.assert_allclose(dker, dref, rtol=1e-5, atol=1e-5)
    y = _t((E, C, D), jnp.float32)
    cref = combine_ref(y, eid, slot, gates)
    cker = combine(y, eid, slot, gates, T, impl="kernel")
    np.testing.assert_allclose(cker, cref, rtol=1e-5, atol=1e-5)


def test_compute_slots_capacity_semantics():
    eid = jnp.asarray([[0], [0], [0], [1]], jnp.int32)
    slot = compute_slots(eid, num_experts=2, capacity=2)
    assert slot[0, 0] == 0 and slot[1, 0] == 1
    assert slot[2, 0] == 2   # over capacity -> dropped downstream
    assert slot[3, 0] == 0


def test_dispatch_combine_roundtrip_identity():
    """With K=1, no drops and gate=1, combine(dispatch(x)) == x."""
    T, D, E, C = 32, 8, 4, 32
    x = _t((T, D), jnp.float32)
    eid = jnp.asarray(RNG.integers(0, E, size=(T, 1)), jnp.int32)
    slot = compute_slots(eid, E, C)
    buf = dispatch(x, eid, slot, E, C, impl="kernel")
    back = combine(buf, eid, slot, jnp.ones((T, 1)), T, impl="kernel")
    np.testing.assert_allclose(back, x, rtol=1e-6, atol=1e-6)
