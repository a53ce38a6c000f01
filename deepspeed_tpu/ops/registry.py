"""Op registry — the TPU analog of the reference's OpBuilder system.

The reference's ``op_builder/builder.py`` (``OpBuilder.load()`` :116,526,545)
JIT-compiles CUDA/C++ extensions on demand, with per-vendor fallbacks. On TPU
the same role is: each logical op (attention, rms_norm, rotary, quantize,
optimizer updates, ...) has one or more *implementations* — a pure-XLA
reference implementation (always available, differentiable, any backend) and
optionally a Pallas kernel (TPU) or a C++ XLA custom call. Selection order:
explicit override > pallas-on-TPU > xla. The platform decides and nothing
else does: on the chip a kernel that cannot lower raises at compile time, and
one that has no layout for the mesh its program spans raises at trace time —
no implementation is ever swapped in behind either.

Usage::

    @register("rms_norm", backend="xla")
    def rms_norm_xla(x, weight, eps): ...

    rms_norm = get_op("rms_norm")   # resolved at call site
"""

from __future__ import annotations

import functools
import math
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm.mesh import BATCH_AXES  # axis names only; no mesh state is read

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_OVERRIDES: Dict[str, str] = {}

_PREFERENCE = ("native", "pallas", "xla")


def register(name: str, backend: str = "xla",
             rows: Optional[int] = None) -> Callable[[Callable], Callable]:
    """``rows`` is a Pallas kernel's one layout over a mesh: its first
    ``rows`` positional arguments and every result are arrays whose leading
    dimension is independent rows (the batch); the rest is whole on every
    device. See :func:`_per_device`."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(name, {})[backend] = \
            _per_device(name, fn, rows) if backend == "pallas" else fn
        return fn

    return deco


def _per_device(name: str, kernel: Callable, rows: Optional[int]) -> Callable:
    """A Pallas kernel is one device's program: Mosaic refuses to lower one
    the SPMD partitioner would have to split ("cannot be automatically
    partitioned"). So where the program being traced spans a mesh
    (``MeshManager.activate`` is what tells the trace), the kernel runs under
    a ``shard_map`` with its rows over the data-parallel axes — the layout
    every model family gives the batch — and with no ``rows``, with mesh axes
    this layout does not cover, or inside a region that is manual over only
    some axes (Mosaic refuses a kernel there whatever wraps it), it RAISES.
    A trace with no mesh context (the inference engines) calls the kernel as
    is, and over several devices Mosaic's own refusal is the error."""

    @functools.wraps(kernel)
    def call(*args, **kwargs):
        mesh = jax.sharding.get_abstract_mesh()  # of the trace in progress
        auto = {a: n for a, n in mesh.shape.items()
                if n > 1 and a not in mesh.manual_axes}
        if not auto:  # one device, or the caller's own manual region
            return kernel(*args, **kwargs)
        batch = tuple(a for a in BATCH_AXES if a in auto)
        is_array = [isinstance(a, (jax.Array, np.ndarray)) for a in args]
        if (rows is None or mesh.manual_axes or len(batch) < len(auto)
                or any(isinstance(v, (jax.Array, np.ndarray))
                       for v in kwargs.values())
                or any(a.shape[0] % math.prod(auto.values())
                       for a in args[:rows])):
            layout = "none yet" if rows is None else (
                f"the leading dim of its first {rows} arguments over "
                f"{BATCH_AXES}, keyword arguments static, not from inside "
                f"a partly manual region")
            raise NotImplementedError(
                f"op '{name}': the Pallas kernel is a per-device program "
                f"and cannot run on these arguments in a program over mesh "
                f"axes {auto} (its layout: {layout}). The XLA reference is "
                f"an explicit choice: set_backend('{name}', 'xla') or "
                f"DSTPU_OP_{name.upper()}=xla")

        def body(*arrays):
            it = iter(arrays)
            return kernel(*(next(it) if arr else a
                            for a, arr in zip(args, is_array)), **kwargs)

        by_rows, whole = P(batch), P()
        return jax.shard_map(
            body, out_specs=by_rows, check_vma=False,
            in_specs=tuple(by_rows if i < rows else whole
                           for i, arr in enumerate(is_array) if arr),
        )(*(a for a, arr in zip(args, is_array) if arr))

    return call


def set_backend(name: str, backend: Optional[str]) -> None:
    """Force a specific implementation (None clears the override)."""
    if backend is None:
        _OVERRIDES.pop(name, None)
    else:
        _OVERRIDES[name] = backend


def on_tpu() -> bool:
    """THE platform test of the op tier (kernel selection, interpret mode).
    A backend that fails to initialize raises here; it is never read as
    'cpu'."""
    return jax.default_backend() == "tpu"


def available_backends(name: str) -> Dict[str, Callable]:
    return dict(_REGISTRY.get(name, {}))


def _resolve(name: str) -> Tuple[str, Callable]:
    impls = _REGISTRY.get(name)
    if not impls:
        raise KeyError(f"no implementations registered for op '{name}'")
    override = _OVERRIDES.get(name) or os.environ.get(f"DSTPU_OP_{name.upper()}")
    if override:
        if override not in impls:
            raise KeyError(f"op '{name}' has no '{override}' implementation "
                           f"(available: {list(impls)})")
        return override, impls[override]
    for backend in (_PREFERENCE if on_tpu() else ("xla",)):
        if backend in impls:
            return backend, impls[backend]
    raise KeyError(f"op '{name}' has no implementation for platform "
                   f"{jax.default_backend()!r} (registered: {list(impls)})")


def get_op(name: str) -> Callable:
    return _resolve(name)[1]


def resolved() -> Dict[str, str]:
    """op -> the backend a call resolves to right now, for every op; "none"
    for an op that has no implementation for this platform (a call of it
    raises: ``ops/pallas/sparse_attention.py`` registers a kernel alone, and
    a survey of every op must not fall over the one it cannot run)."""
    out = {}
    for name in sorted(_REGISTRY):
        try:
            out[name] = _resolve(name)[0]
        except KeyError:
            out[name] = "none"
    return out


def op(name: str) -> Callable:
    """Late-binding callable: resolves the implementation at each call."""

    @functools.wraps(get_op)
    def dispatch(*args, **kwargs):
        return get_op(name)(*args, **kwargs)

    dispatch.__name__ = name
    return dispatch


def backend_of(name: str) -> str:
    """The backend a call of op ``name`` resolves to right now (what
    :func:`resolved` says of every op, of one)."""
    return _resolve(name)[0]
