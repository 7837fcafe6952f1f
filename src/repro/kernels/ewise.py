"""Pallas TPU kernels: elementwise map ops (vecadd / ReLU).

PIMSAB executes these as one-micro-op-per-bit SIMD streams across all
bitlines (op intensity ~0, DRAM-bound — Fig. 11's vecadd row); on the TPU
they are trivial VPU maps.  They exist in the registry mainly to give the
conformance suite and the architecture-simulator backend an elementwise
lowering (`map_add` / `relu` in the tensor DSL) next to the MAC-shaped
kernels.

Tiling: operands are flattened, zero-padded and laid out as rows × 128 lanes
(``tiling.lane_rows``; a 1-D block cannot match XLA's ``T(1024)`` layout of
a 1-D array); the grid streams ``block``-row blocks through VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.api import register_kernel
from repro.kernels.tiling import LANES, lane_rows, load32


def _add_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = (load32(x_ref) + load32(y_ref)).astype(o_ref.dtype)


def _relu_kernel(x_ref, o_ref):
    x = load32(x_ref)
    o_ref[...] = jnp.maximum(x, jnp.zeros_like(x)).astype(o_ref.dtype)


def _blocked_rows(kernel, args, block: int, interpret: bool) -> jnp.ndarray:
    x = args[0]
    tiles, brs = zip(*(lane_rows(a.reshape(x.size), block) for a in args))
    r, br = tiles[0].shape[0], brs[0]
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[spec] * len(tiles),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, LANES), x.dtype),
        interpret=interpret,
    )(*tiles)
    return out.reshape(r * LANES)[: x.size].reshape(x.shape)


@register_kernel("ewise_add", oracle=ref.ewise_add_ref)
def ewise_add(
    x: jnp.ndarray, y: jnp.ndarray, *, block: int = 512, interpret: bool = False
) -> jnp.ndarray:
    """x + y, any matching shapes/dtype."""
    assert x.shape == y.shape, (x.shape, y.shape)
    return _blocked_rows(_add_kernel, (x, y.astype(x.dtype)), block, interpret)


@register_kernel("relu", oracle=ref.relu_ref)
def relu(x: jnp.ndarray, *, block: int = 512, interpret: bool = False) -> jnp.ndarray:
    """max(x, 0)."""
    return _blocked_rows(_relu_kernel, (x,), block, interpret)
