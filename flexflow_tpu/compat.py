"""Host-memory placement that depends on the BACKEND, not the jax version.

The ``pinned_host`` memory kind is not addressable everywhere (XLA:CPU
exposes only ``unpinned_host``).  :func:`host_memory_kind` reports the
host-side memory kind the running backend actually addresses
(preferring ``pinned_host``), and :func:`with_host_memory` places a
sharding there, returning None when the backend has no host memory
space at all so callers keep device placement instead of crashing.

Every consumer (the host-placed parameter paths in model.py and
ops/linear.py, and the tests that pin host placement) imports from
here.
"""

from __future__ import annotations

import functools
from typing import Optional


@functools.lru_cache(maxsize=1)
def host_memory_kind() -> Optional[str]:
    """The host-side memory kind this backend addresses: ``pinned_host``
    where available, else ``unpinned_host``, else None (no host memory
    space — callers keep device placement).  Cached: the answer is a
    property of the process's backend."""
    import jax

    kinds = {m.kind for m in jax.local_devices()[0].addressable_memories()}
    for kind in ("pinned_host", "unpinned_host"):
        if kind in kinds:
            return kind
    return None


def with_host_memory(sharding):
    """``sharding`` re-pointed at the backend's host memory space, or
    None when the backend has none (the caller's fallback is device
    placement — model._resolve_host_placements warns and keeps the
    device sharding)."""
    kind = host_memory_kind()
    if kind is None:
        return None
    return sharding.with_memory_kind(kind)


__all__ = ["host_memory_kind", "with_host_memory"]
