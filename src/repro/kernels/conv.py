"""Pallas TPU kernels: the DL-network layer set (conv / pool / integer gemm).

These are the kernels an end-to-end integer CNN (the ResNet18-style model in
``repro.models.resnet``) is built from.  On the TPU they all reduce to the
MXU/VPU primitives; on the pimsab backend the same registry names lower onto
the paper's architecture (``repro.kernels.pimsab_backend``):

* ``conv2d``      — im2col (the §V-A layout contract lives in
  ``ref.im2col``) followed by the bit-sliced MXU matmul
  (``bitslice_matmul.wide_matmul``); pimsab runs the identical patch matrix
  through the ``mac`` gemm pipeline.
* ``int_matmul``  — raw-integer (M, K) × (K, N) with int32 accumulation: the
  network-head matmul whose activations arrive as another kernel's integer
  output; the kernel slices them itself.
* ``maxpool2d`` / ``avgpool2d`` / ``global_avgpool`` — window reductions over
  the ``ref.pool_patches`` window matrix; pimsab folds max via CmpGE +
  masked copy and average via the constant-operand MAC plus a shift-read
  divide.

``x_bits`` / ``w_bits`` are *static precision hints*: the pimsab lowering
sizes its fields from them (program mode cannot calibrate precision from
values), and the TPU matmuls take one int8 slice per 8 bits of them (four
slices without a hint).  The oracles ignore them.

Tiling: the matmuls follow ``bitslice_matmul`` (aligned blocks, operands
zero-padded, e.g. the 1000-class head to 1024 columns).  The pools transpose
the window matrix to (K, P), lay P out as rows × 128 lanes
(``tiling.lane_rows``) and block the rows; the K window slabs of one block
sit in VMEM together.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.api import register_kernel
from repro.kernels.bitslice_matmul import wide_matmul
from repro.kernels.tiling import LANES, lane_rows, load32


def _pool_max_kernel(p_ref, o_ref):
    o_ref[...] = jnp.max(load32(p_ref), axis=0).astype(o_ref.dtype)


def _pool_sum_kernel(p_ref, o_ref, *, acc_dtype):
    o_ref[...] = jnp.sum(p_ref[...].astype(acc_dtype), axis=0)


def _blocked_pool(kernel, patches: jnp.ndarray, out_dtype, block: int, interpret: bool):
    """Reduce the (P, K) window matrix over K.  The window axis leads, so
    each grid step reduces K (block × 128) slabs elementwise on the VPU."""
    p, k = patches.shape
    slabs, br = lane_rows(patches.T, block)          # (K, R, 128)
    r = slabs.shape[1]
    out = pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[pl.BlockSpec((k, br, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANES), out_dtype),
        interpret=interpret,
    )(slabs)
    return out.reshape(r * LANES)[:p]


def _acc_dtype(x: jnp.ndarray):
    return jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32


# ---------------------------------------------------------------------------
# registered kernels
# ---------------------------------------------------------------------------


@register_kernel("conv2d", oracle=ref.conv2d_ref)
def conv2d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    stride: int = 1,
    padding: int = 0,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    block: Tuple[int, int, int] = (256, 256, 256),
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) × (OC, C, KH, KW) → (N, OC, OH, OW) via im2col + MXU.

    Integer inputs accumulate in int32 (wrapping, like the oracle); float
    inputs in float32.  ``x_bits``/``w_bits`` set the slice counts.
    """
    n, c, h, hw = x.shape
    oc, c2, kh, kw = w.shape
    assert c == c2, (c, c2)
    oh, ow = ref.conv2d_out_hw(h, hw, kh, kw, stride, padding)
    patches = ref.im2col(x, kh, kw, stride, padding)          # (N·OH·OW, C·KH·KW)
    wm = w.reshape(oc, c * kh * kw).transpose()               # (C·KH·KW, OC)
    out = wide_matmul(patches, wm, x_bits=x_bits, w_bits=w_bits,
                      block=block, interpret=interpret)
    return out.reshape(n, oh, ow, oc).transpose(0, 3, 1, 2)


@register_kernel("int_matmul", oracle=ref.int_matmul_ref)
def int_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    block: Tuple[int, int, int] = (256, 256, 256),
    interpret: bool = False,
) -> jnp.ndarray:
    """(M, K) × (K, N) raw-integer matmul, int32 accumulation (wrapping)."""
    return wide_matmul(x.astype(jnp.int32), w.astype(jnp.int32), x_bits=x_bits,
                       w_bits=w_bits, block=block, interpret=interpret)


@register_kernel("maxpool2d", oracle=ref.maxpool2d_ref)
def maxpool2d(
    x: jnp.ndarray,
    *,
    window: int = 2,
    stride: Optional[int] = None,
    block: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C, OH, OW) window max (no padding)."""
    s = stride or window
    n, c, h, w = x.shape
    oh, ow = ref.conv2d_out_hw(h, w, window, window, s, 0)
    patches = ref.pool_patches(x, window, s)
    out = _blocked_pool(_pool_max_kernel, patches, x.dtype, block, interpret)
    return out.reshape(n, c, oh, ow)


@register_kernel("avgpool2d", oracle=ref.avgpool2d_ref)
def avgpool2d(
    x: jnp.ndarray,
    *,
    window: int = 2,
    block: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C, OH, OW) window average, stride == window.

    Integer inputs floor-divide by the window count — the semantics the
    bit-serial machine gets for free by reading the sum accumulator at a
    wordline offset (an arithmetic right shift).
    """
    n, c, h, w = x.shape
    oh, ow = ref.conv2d_out_hw(h, w, window, window, window, 0)
    patches = ref.pool_patches(x, window, window)
    s = _blocked_pool(
        functools.partial(_pool_sum_kernel, acc_dtype=_acc_dtype(x)),
        patches, _acc_dtype(x), block, interpret,
    )
    return ref._pool_mean(s, window * window).reshape(n, c, oh, ow)


@register_kernel("global_avgpool", oracle=ref.global_avgpool_ref)
def global_avgpool(
    x: jnp.ndarray, *, block: int = 128, interpret: bool = False
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C) spatial average (integer: floor-divide by H·W)."""
    n, c, h, w = x.shape
    flat = x.reshape(n * c, h * w)
    s = _blocked_pool(
        functools.partial(_pool_sum_kernel, acc_dtype=_acc_dtype(x)),
        flat, _acc_dtype(x), block, interpret,
    )
    return ref._pool_mean(s, h * w).reshape(n, c)
