"""Host-memory offload of the outer-optimizer state (paper §V).

Between outer steps the anchor model ``θ_{t−r}`` and the momentum ``M`` are
dead weight in HBM (they are touched once every ``r`` inner steps). The paper
offloads them to host memory; on TPU the equivalent is JAX memory kinds:
``device_put`` onto a sharding with ``memory_kind="pinned_host"``.

Each device offloads only its own shard (the paper's "avoid redundant data
movement" note) — this falls out for free because we offload the sharded
arrays as-is, preserving their sharding but switching the memory kind.

A device without a ``pinned_host`` memory cannot offload: :func:`to_host`
then raises rather than leave the state in HBM while the run believes it
was moved.
"""

from __future__ import annotations

import functools
from typing import Any

import jax


@functools.cache
def supports_offload() -> bool:
    dev = jax.devices()[0]
    return any(m.kind == "pinned_host" for m in dev.addressable_memories())


def _with_memory_kind(sharding, kind: str):
    return sharding.with_memory_kind(kind)


def to_host(tree: Any) -> Any:
    """Move a pytree of arrays to pinned host memory (keeps sharding)."""
    if not supports_offload():
        dev = jax.devices()[0]
        raise RuntimeError(
            f"outer-state offload needs a pinned_host memory on "
            f"{dev.platform} ({dev.device_kind}); it has "
            f"{[m.kind for m in dev.addressable_memories()]}")

    def move(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.device_put(x, _with_memory_kind(x.sharding, "pinned_host"))

    return jax.tree.map(move, tree)


def to_device(tree: Any) -> Any:
    """Bring an offloaded pytree back to device HBM."""
    def move(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.device_put(x, _with_memory_kind(x.sharding, "device"))

    return jax.tree.map(move, tree)


def offload_bytes(tree: Any) -> int:
    """HBM bytes freed by offloading ``tree`` (for the memory report)."""
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
        if hasattr(x, "size")
    )
