"""Memory-space placement primitives for the tiered memory subsystem.

One capability story for every backend:

- A device that exposes a host memory space beside its own (``device`` and
  ``pinned_host`` / ``unpinned_host`` — the TPU, and under the installed
  JAX the CPU backend too) moves arrays between them with
  ``jax.device_put`` and a memory kind (async DMA over PCIe on the TPU).
  Inside jit, ``jax.device_put(x, jax.memory.Space.Host)`` lowers to an XLA
  host-memory (``S(5)``) placement the latency-hiding scheduler can stream
  around.
- A backend that reports NO separate host space falls back to
  :class:`HostBuffer` for eager moves — a numpy-resident leaf that carries
  its logical tier and original sharding so restore is exact (``HostBuffer``
  is not a ``jax.Array``, so ``jax.live_arrays`` no longer counts it).

``offloaded_memory_kinds`` reports LOGICAL tier kinds: a leaf in its
device's default memory reports ``device`` whatever the backend names it, a
host-kind ``jax.Array`` or ``HostBuffer`` reports its host kind.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Set, Tuple

import jax
import numpy as np
from jax.memory import Space

PINNED = "pinned_host"
UNPINNED = "unpinned_host"

_KIND_CACHE: Dict[Any, Tuple[str, frozenset]] = {}


def _device_kinds(device=None) -> Tuple[str, frozenset]:
    """(default memory kind, all addressable kinds) for ``device``."""
    if device is None:
        device = jax.local_devices()[0]
    cached = _KIND_CACHE.get(device)
    if cached is not None:
        return cached
    default = device.default_memory().kind
    kinds = frozenset(m.kind for m in device.addressable_memories())
    _KIND_CACHE[device] = (default, kinds)
    return default, kinds


def default_memory_kind(device=None) -> str:
    return _device_kinds(device)[0]


def supports_memory_kind(kind: str, device=None) -> bool:
    return kind in _device_kinds(device)[1]


def host_memory_kind(device=None, pin: bool = True) -> Optional[str]:
    """The host-tier memory kind this backend can actually address, or None
    when the backend has no separate host space (such backends use the
    :class:`HostBuffer` fallback instead)."""
    default, kinds = _device_kinds(device)
    want = PINNED if pin else UNPINNED
    if want in kinds and want != default:
        return want
    # pin preference degrades rather than failing (e.g. a backend with only
    # an unpinned host space)
    other = UNPINNED if pin else PINNED
    if other in kinds and other != default:
        return other
    return None


# --------------------------------------------------------------------------- #
# in-jit annotations (traced values)
# --------------------------------------------------------------------------- #
def to_host(x, pin: bool = True):
    """Place a value in host memory: under a trace the ``Space.Host``
    annotation (XLA host placement; ``pin`` has no in-jit spelling), on a
    concrete array a real move to the host memory kind."""
    if isinstance(x, jax.core.Tracer):
        return jax.device_put(x, Space.Host)
    return _leaf_to_host(x, pin)


def to_device(x):
    """Place a value back into device (HBM) memory — the inverse of
    :func:`to_host`."""
    if isinstance(x, jax.core.Tracer):
        return jax.device_put(x, Space.Device)
    return _leaf_to_device(x)


def tree_to_host(tree, pin: bool = True):
    return jax.tree.map(lambda x: to_host(x, pin), tree)


def tree_to_device(tree):
    return jax.tree.map(to_device, tree)


# --------------------------------------------------------------------------- #
# eager moves (committed arrays)
# --------------------------------------------------------------------------- #
class HostBuffer:
    """A host-tier pytree leaf on backends without a separate host memory
    space: numpy residency + the logical memory kind + the sharding needed
    to restore the exact device layout. Quacks enough like an array
    (``shape``/``dtype``/``nbytes``/``__array__``) that generic consumers
    (checkpoint savers, byte accounting) keep working, but is NOT a
    ``jax.Array`` — host-tier leaves leave the device allocator for real."""

    __slots__ = ("data", "memory_kind", "sharding")

    def __init__(self, data: np.ndarray, memory_kind: str = PINNED,
                 sharding=None):
        self.data = data
        self.memory_kind = memory_kind
        self.sharding = sharding

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes)

    def __array__(self, dtype=None):
        return np.asarray(self.data, dtype)

    def __repr__(self):
        return (f"HostBuffer(shape={self.data.shape}, "
                f"dtype={self.data.dtype}, kind={self.memory_kind})")


def _leaf_to_host(leaf, pin: bool):
    if not isinstance(leaf, jax.Array):
        return leaf
    kind = host_memory_kind(pin=pin)
    logical = PINNED if pin else UNPINNED
    if kind is not None:
        sh = leaf.sharding
        if getattr(sh, "memory_kind", None) == kind:
            return leaf
        return jax.device_put(leaf, sh.with_memory_kind(kind))
    # no host memory space: numpy residency, exact-restore metadata
    return HostBuffer(np.asarray(leaf), logical, sharding=leaf.sharding)


def _leaf_to_device(leaf):
    if isinstance(leaf, HostBuffer):
        if leaf.sharding is not None:
            return jax.device_put(leaf.data, leaf.sharding)
        return jax.device_put(leaf.data)
    if not isinstance(leaf, jax.Array):
        return leaf
    sh = leaf.sharding
    kind = getattr(sh, "memory_kind", None)
    default = default_memory_kind()
    if kind is None or kind == default:
        return leaf
    return jax.device_put(leaf, sh.with_memory_kind(default))


def move_tree(tree: Any, tier: str, pin: bool = True) -> Any:
    """Eagerly move every array leaf of ``tree`` onto ``tier`` (``"host"``
    or ``"device"``). Host moves use real memory kinds where the backend has
    them and :class:`HostBuffer` numpy residency otherwise; device moves
    invert either representation exactly (bit-identical roundtrip)."""
    if tier == "host":
        return jax.tree.map(lambda l: _leaf_to_host(l, pin), tree)
    if tier == "device":
        return jax.tree.map(_leaf_to_device, tree)
    raise ValueError(f"unknown placement tier {tier!r} (host|device)")


def offloaded_memory_kinds(tree: Any) -> Set[str]:
    """The set of LOGICAL memory kinds the array leaves of ``tree`` occupy:
    ``device`` for leaves in their device's default memory (whatever the
    backend names it), the host kind for host-tier leaves (real memory-kind
    arrays AND :class:`HostBuffer` fallbacks)."""
    kinds: Set[str] = set()
    default = default_memory_kind()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, HostBuffer):
            kinds.add(leaf.memory_kind)
        elif isinstance(leaf, jax.Array):
            kind = getattr(leaf.sharding, "memory_kind", None)
            kinds.add("device" if kind is None or kind == default else kind)
    return kinds
