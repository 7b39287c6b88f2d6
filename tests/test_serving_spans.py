"""The serving tier's own profiler spans: recorded on the CPU, read back
with ``jax.profiler.ProfileData``. A pool of four pages a node, three
sessions of two pages each on one node, so attention evicts and restores;
a ballast on the node's admission cap makes one decode's grant wait."""
import glob
import os

import numpy as np
import pytest

from repro.runtime.cluster import Cluster
from repro.runtime.serving import ServingTier

SPANS = ("serving.attend", "serving.dispatch", "serving.fetch",
         "serving.decode", "serving.replicate", "kvcache.pool_write",
         "kvcache.restore", "kvcache.offload", "memory.admission_wait")
PROMPT = 8          # two full pages of four tokens


def _run_tier():
    """Admit, decode and attend on one node's over-subscribed pool; returns
    the attention outputs, every session's page slabs and the restores
    that found a slab (``fetches``) during the decode and attention steps."""
    cluster = Cluster(4, node_capacity=8 << 20, page_size=1 << 14,
                      replication_factor=1, admission=True,
                      admission_timeout_s=0.02)
    tier = ServingTier(cluster, num_layers=2, page_tokens=4, kv_heads=2,
                       head_dim=8, hbm_pages_per_node=4)
    try:
        node = tier._affinity(0)
        seqs = [s for s in range(200) if tier._affinity(s) == node][:3]
        tier.admit({s: PROMPT for s in seqs})
        cache = tier._shards[node].cache
        f0 = cache.stats["fetches"]
        memory = cluster.nodes[node].memory
        ballast = memory.reserve(memory.admission.cap)
        try:
            tier.decode([seqs[0]], 1)     # a new page: its grant waits
        finally:
            ballast.release()
        outs = []
        for step in range(2):
            for s in seqs:
                tier.decode([s], 1)
                for layer in range(2):
                    outs.append(tier.attend([s], layer)[s])
        fetches = cache.stats["fetches"] - f0
        slabs = {s: tier.sequence_slabs(s) for s in seqs}
        assert all(tier.verify(s) for s in seqs)
        return outs, slabs, fetches, node
    finally:
        tier.close()
        cluster.shutdown()


def _host_lines(trace_dir):
    """Each host thread's spans of ``SPANS`` as (name, start, end, stats)."""
    import jax
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name in SPANS]
                if evs:
                    lines.append(evs)
    return lines


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        result = _run_tier()
    finally:
        jax.profiler.stop_trace()
    return result, _host_lines(trace_dir)


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def _spans(lines, name):
    return [e for line in lines for e in line if e[0] == name]


def test_every_span_appears(traced):
    _result, lines = traced
    seen = {e[0] for line in lines for e in line}
    assert seen == set(SPANS)


@pytest.mark.parametrize("child,parent", [
    ("serving.dispatch", "serving.attend"),
    ("serving.fetch", "serving.attend"),
    ("kvcache.offload", "kvcache.restore"),
    ("kvcache.pool_write", "kvcache.restore"),
])
def test_spans_nest(traced, child, parent):
    """Each child span lies inside a parent span of its own thread (an
    offload or a pool write also happens outside restores: on prefill and
    commit)."""
    _result, lines = traced
    nested = [c for line in lines for c in line if c[0] == child
              and _inside(c, [p for p in line if p[0] == parent])]
    assert nested
    if parent == "serving.attend":
        assert len(nested) == len(_spans(lines, child))


def test_restores_that_found_a_slab_are_the_fetches(traced):
    (_outs, _slabs, fetches, _node), lines = traced
    # a restore writes the pool only when the host tier held the slab; the
    # window starts at the decode after admission
    first = min(e[1] for e in _spans(lines, "memory.admission_wait"))
    found = 0
    for line in lines:
        writes = [e for e in line if e[0] == "kvcache.pool_write"]
        found += sum(r[1] >= first and any(
            r[1] <= w[1] and w[2] <= r[2] for w in writes)
            for r in line if r[0] == "kvcache.restore")
    assert fetches > 0
    assert found == fetches


def test_spans_carry_node_and_session(traced):
    (_outs, _slabs, _fetches, node), lines = traced
    for name in ("serving.dispatch", "serving.fetch"):
        assert {e[3].get("node") for e in _spans(lines, name)} == {node}
    for e in _spans(lines, "serving.replicate"):
        assert "node" in e[3] and "seq" in e[3]
    for e in _spans(lines, "kvcache.restore"):
        assert "seq" in e[3]


def test_tracing_changes_no_output(traced):
    (outs, slabs, fetches, _node), _lines = traced
    outs2, slabs2, fetches2, _ = _run_tier()
    assert fetches2 == fetches
    assert len(outs2) == len(outs)
    for a, b in zip(outs, outs2):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert slabs2.keys() == slabs.keys()
    for s in slabs:
        assert [x.tobytes() for x in slabs[s]] == \
            [x.tobytes() for x in slabs2[s]]
