"""Pallas TPU kernels: elementwise map ops (vecadd / ReLU).

PIMSAB executes these as one-micro-op-per-bit SIMD streams across all
bitlines (op intensity ~0, DRAM-bound — Fig. 11's vecadd row); on the TPU
they are trivial VPU maps.  They exist in the registry mainly to give the
conformance suite and the architecture-simulator backend an elementwise
lowering (`map_add` / `relu` in the tensor DSL) next to the MAC-shaped
kernels.

Tiling: a 4-D ``(N, C, H, W)`` operand is mapped over its channels-last rows
``(N·H, W·C)`` (``tiling.nhwc_rows``, path ``channels_last``): the layout
the conv and the pools read and write, so the transposes at the wrapper's
edges cancel against its neighbours' inside the Program's jit, and W·C is
lane-dense where C alone is not.  Any other rank is flattened, zero-padded
and laid out as rows × 128 lanes (``tiling.lane_rows``, path
``lane_rows``).  Either way the grid streams blocks of whole rows through
VMEM, about ``block`` × 128 elements each.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.api import register_kernel
from repro.kernels.tiling import (
    LANES, count_path, lane_rows, load32, nchw_from_rows, nhwc_rows, sublane_tile,
)


def _add_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = (load32(x_ref) + load32(y_ref)).astype(o_ref.dtype)


def _relu_kernel(x_ref, o_ref):
    x = load32(x_ref)
    o_ref[...] = jnp.maximum(x, jnp.zeros_like(x)).astype(o_ref.dtype)


def _map_rows(kernel, rows, br: int, interpret: bool) -> jnp.ndarray:
    """``kernel`` over ``br``-row blocks of the ``(R, L)`` operands; the
    last block may run past R (its rows there are dropped)."""
    r, lanes = rows[0].shape
    spec = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, br),),
        in_specs=[spec] * len(rows),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, lanes), rows[0].dtype),
        interpret=interpret,
    )(*rows)


def _map(name: str, kernel, args, block: int, interpret: bool) -> jnp.ndarray:
    """``kernel`` over ``args``, on the path the operands' rank selects."""
    x = args[0]
    path = "channels_last" if x.ndim == 4 else "lane_rows"
    count_path(name, path)
    with jax.named_scope(path):
        if path == "channels_last":
            rows = [nhwc_rows(a) for a in args]
            (r, lanes), unit = rows[0].shape, sublane_tile(x.dtype)
            br = max(unit, block * LANES // lanes // unit * unit)
            out = _map_rows(kernel, rows, min(br, r), interpret)
            return nchw_from_rows(out, *x.shape)
        tiles, brs = zip(*(lane_rows(a.reshape(x.size), block) for a in args))
        out = _map_rows(kernel, tiles, brs[0], interpret)
        return out.reshape(out.size)[: x.size].reshape(x.shape)


@register_kernel("ewise_add", oracle=ref.ewise_add_ref)
def ewise_add(
    x: jnp.ndarray, y: jnp.ndarray, *, block: int = 2048, interpret: bool = False
) -> jnp.ndarray:
    """x + y, any matching shapes/dtype."""
    assert x.shape == y.shape, (x.shape, y.shape)
    return _map("ewise_add", _add_kernel, (x, y.astype(x.dtype)), block, interpret)


@register_kernel("relu", oracle=ref.relu_ref)
def relu(x: jnp.ndarray, *, block: int = 2048, interpret: bool = False) -> jnp.ndarray:
    """max(x, 0)."""
    return _map("relu", _relu_kernel, (x,), block, interpret)
