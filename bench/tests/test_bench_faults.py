"""The check fails when the timed path is broken underneath it: each fault
a KV cell can have, planted in the program, and the control (the plain
reference in lower precision in the kernel's place) turn ``correct``
false. The harness's look for a chip is skipped; the rest of a run is as on
the chip, at tiny sizes."""
import numpy as np
import pytest

from bench_testlib import cpu_harness, run, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _state_unchanged(monkeypatch):
    from repro.runtime.serving import ServingTier
    monkeypatch.setattr(ServingTier, "_decode_commit", lambda self, sess: None)


def _half_batch(monkeypatch):
    from repro.runtime.serving import ServingTier
    decode = ServingTier.decode
    monkeypatch.setattr(ServingTier, "decode", lambda self, ids, steps=1:
                        decode(self, ids[:len(ids) // 2], steps))


def _no_exchange(monkeypatch):
    from repro.runtime.serving import ServingTier
    monkeypatch.setattr(ServingTier, "_sync_replica",
                        lambda self, sess, page, slab: None)


def _token_altered(monkeypatch):
    from repro.runtime import serving
    real = serving.expected_page_slab

    def altered(seq_id, page_index, length, **kw):
        slab = real(seq_id, page_index, length, **kw)
        t = length - 1 - page_index * kw["page_tokens"]
        if 0 <= t < kw["page_tokens"]:
            slab[:, t] += 1.0 / 64           # the newest token, as written
        return slab

    monkeypatch.setattr(serving, "expected_page_slab", altered)


def _answer_altered(monkeypatch):
    from repro.kernels.paged_attention import ops
    real = ops.paged_attention

    def altered(*args, **kw):
        return real(*args, **kw).at[0, 0, 0].add(1.0 / 64)

    monkeypatch.setattr(ops, "paged_attention", altered)


FAULTS = {
    "state_unchanged": (_state_unchanged, "length_mismatch"),
    "half_batch": (_half_batch, "length_mismatch"),
    "no_exchange": (_no_exchange, "replica_mismatch"),
    "token_altered": (_token_altered, "page_mismatch"),
    "answer_altered": (_answer_altered, "attn_gap"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(root, fault, monkeypatch):
    harness = cpu_harness(monkeypatch)
    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    result = run(harness, root, "tiny-burst", seed=2**31 + 7, seconds=0.3)
    assert not result["correct"]
    c = result["checks"][caught_by]
    assert c["value"] > c["limit"]


def test_control_is_not_correct(root, monkeypatch):
    """The reference with its inputs rounded to the precision below the
    configuration's, in the kernel's place."""
    from bench.control import control_attention
    from bench_testlib import load
    harness = cpu_harness(monkeypatch)
    dtype = load(root / "bench" / "configs" / "tiny-kv.json")["dtype"]
    with control_attention(dtype):
        result = run(harness, root, "tiny-burst", seed=2**31 + 8,
                     seconds=0.3)
    assert not result["correct"]
    c = result["checks"]["attn_gap"]
    assert c["limit"] < c["value"] < np.inf
    assert all(result["checks"][k]["value"] == 0 for k in
               ("length_mismatch", "page_mismatch", "replica_mismatch"))
