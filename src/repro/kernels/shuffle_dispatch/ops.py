"""Jit'd wrappers + slot assignment for MoE shuffle dispatch/combine."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from .kernel import combine_kernel, dispatch_kernel
from .ref import combine_ref, dispatch_ref

# Fused hash-partition + incremental-CRC host pass (PR 7): the numpy-only
# implementation lives with the columnar page code so the cluster runtime can
# fall back to it when this package's jax import is unavailable; re-exported
# here so kernels/ stays the single import point for dispatch math.
from ...core.columnar import fused_partition_crc as host_partition_crc  # noqa: F401,E402


def host_dispatch_plan(partition_ids: np.ndarray, num_partitions: int
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side slot assignment for node-to-node shuffle transfers — the CPU
    analogue of :func:`compute_slots`: one stable pass groups a batch by
    destination partition. Returns ``(order, counts, offsets)`` such that
    ``batch[order][offsets[p]:offsets[p+1]]`` is partition ``p``'s contiguous
    slice (the runtime ``Cluster`` shuffle routes map output with this)."""
    partition_ids = np.asarray(partition_ids)
    order = np.argsort(partition_ids, kind="stable")
    counts = np.bincount(partition_ids, minlength=num_partitions)
    offsets = np.empty(len(counts) + 1, np.int64)
    offsets[0] = 0
    np.cumsum(counts, out=offsets[1:])
    return order, counts, offsets


def compute_slots(expert_id: jnp.ndarray, num_experts: int,
                  capacity: int) -> jnp.ndarray:
    """Position of each (token, k) within its expert's capacity buffer.

    Tokens beyond capacity get slot >= capacity (dropped downstream) — the
    'virtual shuffle buffer is full' case. expert_id: [T, K] -> slots [T, K].
    """
    T, K = expert_id.shape
    flat = expert_id.reshape(-1)                             # priority order
    onehot = (flat[:, None] == jnp.arange(num_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # exclusive count
    slot = jnp.take_along_axis(
        pos, jnp.clip(flat, 0, num_experts - 1)[:, None], axis=1)[:, 0]
    slot = jnp.where(flat >= 0, slot, -1)
    return slot.reshape(T, K)


def dispatch(x: jnp.ndarray, expert_id: jnp.ndarray, slot: jnp.ndarray,
             num_experts: int, capacity: int, *,
             impl: str = "xla") -> jnp.ndarray:
    if impl == "kernel":
        return dispatch_kernel(x, expert_id, slot, num_experts, capacity,
                               interpret=interpret_mode())
    if impl == "xla":
        return dispatch_ref(x, expert_id, slot, num_experts, capacity)
    raise ValueError(impl)


def combine(y: jnp.ndarray, expert_id: jnp.ndarray, slot: jnp.ndarray,
            gates: jnp.ndarray, num_tokens: int, *,
            impl: str = "xla") -> jnp.ndarray:
    if impl == "kernel":
        return combine_kernel(y, expert_id, slot, gates, num_tokens,
                              interpret=interpret_mode())
    if impl == "xla":
        return combine_ref(y, expert_id, slot, gates)
    raise ValueError(impl)
