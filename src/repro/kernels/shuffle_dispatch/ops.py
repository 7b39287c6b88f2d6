"""Jit'd wrappers + slot assignment for MoE shuffle dispatch/combine."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import combine_kernel, dispatch_kernel
from .ref import combine_ref, dispatch_ref


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
