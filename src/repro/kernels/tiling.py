"""Block-shape rules shared by the Pallas TPU kernels.

Mosaic accepts a block whose last two dimensions are multiples of the
(8, 128) sublane × lane tile — (32, 128) for int8 — or equal to the whole
array dimension.  The kernels pick aligned blocks with :func:`fit_block`
and zero-pad operands up to a whole number of blocks (slicing the result
back), so awkward network shapes such as a 1000-class head tile exactly.
Elementwise and pooling kernels see their operands as ``rows × 128`` lanes
(:func:`lane_rows`) rather than as 1-D arrays, whose XLA layout (``T(1024)``)
Mosaic blocks cannot match.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp

LANES = 128
SUBLANES = 32  # int8 sublane tile; a multiple of the 8 (f32) / 16 (bf16) tiles


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def fit_block(dim: int, block: int, align: int) -> Tuple[int, int]:
    """(block, padded dim): an ``align``-multiple block of at most
    ``max(block, align)`` and ``dim`` rounded up to a whole number of them."""
    b = min(round_up(block, align), round_up(dim, align))
    return b, round_up(dim, b)


def pad_to(a: jnp.ndarray, shape: Sequence[int]) -> jnp.ndarray:
    """Zero-pad ``a`` at the high end of each axis up to ``shape``."""
    widths = [(0, s - d) for d, s in zip(a.shape, shape)]
    return jnp.pad(a, widths) if any(w for _, w in widths) else a


def lane_rows(a: jnp.ndarray, block_rows: int) -> Tuple[jnp.ndarray, int]:
    """``(..., P)`` → ``(..., R, 128)``, zero-padded so that R is a whole
    number of ``SUBLANES``-aligned row blocks; returns the array and the
    row block."""
    p = a.shape[-1]
    br, r = fit_block(-(-p // LANES), block_rows, SUBLANES)
    a = pad_to(a, (*a.shape[:-1], r * LANES))
    return a.reshape(*a.shape[:-1], r, LANES), br


def load32(ref) -> jnp.ndarray:
    """Read a kernel ref, widening narrow integers to int32: the VPU has no
    8/16-bit integer ALU ops.  Callers cast results back to the output
    dtype, which wraps like the oracle."""
    x = ref[...]
    return x.astype(jnp.int32) if jnp.issubdtype(x.dtype, jnp.integer) else x
