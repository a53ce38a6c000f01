"""Shared helpers for the Pallas kernel tier."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ..registry import on_tpu


def interpret() -> bool:
    """Run kernels through the Pallas interpreter off-TPU (tests select the
    pallas backend explicitly on the CPU mesh). On the chip this is False,
    so a kernel Mosaic cannot lower fails the compile."""
    return not on_tpu()


def mxu_dot(a, b, dimension_numbers, preferred_element_type=jnp.float32):
    """``lax.dot_general`` for kernel bodies. The precision is pinned to
    DEFAULT unless both operands are float32: Mosaic has no bf16 matmul at
    fp32 contract precision ("Bad lhs type"), and that is what an enclosing
    ``jax.default_matmul_precision("highest")`` would ask of every dot in
    its scope. bf16 products are exact on the MXU either way; accumulation
    stays ``preferred_element_type``."""
    both_f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, dimension_numbers,
        precision=None if both_f32 else jax.lax.Precision.DEFAULT,
        preferred_element_type=preferred_element_type)


def dim_semantics(*sem: str):
    """CompilerParams marking grid dims parallel/arbitrary. Accumulation
    dims (scratch carried across iterations) must be 'arbitrary'; truly
    independent dims marked 'parallel' let Mosaic partition them across
    TensorCores (a no-op on single-core v5e, significant on multi-core
    generations) and relax ordering constraints."""
    return pltpu.CompilerParams(dimension_semantics=sem)


def row_block(n_rows: int) -> int:
    """Largest power-of-two row-block (≤256) that divides n_rows."""
    for b in (256, 128, 64, 32, 16, 8):
        if n_rows % b == 0:
            return b
    return 1


def pad_rows(x2, multiple: int = 8):
    """Pad the leading (row) axis up to ``multiple`` and return the original
    row count. Mosaic rejects blocks whose second-to-last dim is neither %8
    nor the full array dim, so decode-sized row counts (1..7, odd) must be
    padded before a row-blocked pallas_call; callers slice the output back
    with the returned ``n``. Rows are independent in every kernel that uses
    this (norms, group quantization), so the pad rows are dead compute."""
    n = x2.shape[0]
    pad = (-n) % multiple
    if pad:
        x2 = jnp.pad(x2, ((0, pad),) + ((0, 0),) * (x2.ndim - 1))
    return x2, n
