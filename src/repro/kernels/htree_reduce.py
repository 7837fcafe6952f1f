"""Pallas TPU kernel: log-depth pairwise (H-tree) reduction.

Reduces (N, D) → (D,) over the leading axis in the H-tree's summation order:
adjacent pairs first, then pairs-of-pairs — log₂(N) levels.  This is the
numerical twin of PIMSAB's intra-tile H-tree partial-sum reduction (and of
``dist.collectives.htree_allreduce`` at mesh level); it differs from a serial
(ring-order) sum in floating point, so tests pin the tree order explicitly.

Tiling: grid over lane-aligned D blocks (D zero-padded to a multiple of 128);
each step copies its (N, 128) slab into a 32-bit VMEM scratch and halves it
log₂(N) times in place — each level reads the even and odd rows with stride-2
ref loads (Mosaic lowers strided loads from a 128-lane, 32-bit ref, not
strided slices of a value) and rounds the sums back to the input dtype.
N is the "CRAM lanes" axis (≤ a few hundred), so N·bd·4B stays well under
VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref
from repro.kernels.api import register_kernel
from repro.kernels.tiling import LANES, pad_to, round_up


def _kernel(x_ref, o_ref, y_ref):
    dt = x_ref.dtype
    y_ref[...] = x_ref[...].astype(y_ref.dtype)  # (n, bd)
    h = y_ref.shape[0] // 2
    while h >= 1:
        pair = y_ref[pl.ds(0, h, stride=2), :] + y_ref[pl.ds(1, h, stride=2), :]
        y_ref[pl.ds(0, h), :] = pair.astype(dt).astype(y_ref.dtype)
        h //= 2
    o_ref[...] = y_ref[pl.ds(0, 1), :].astype(dt)


def _wide(dtype):
    """The 32-bit scratch dtype (strided loads need 32-bit data); each
    level rounds back to the input dtype, so sums match the oracle's."""
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


@register_kernel("htree_reduce", oracle=ref.htree_reduce_ref)
def htree_reduce(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """x: (N, D) → (D,), N a power of two."""
    n, d = x.shape
    assert n & (n - 1) == 0, f"H-tree needs power-of-two lanes, got {n}"
    bd, dp = LANES, round_up(d, LANES)
    out = pl.pallas_call(
        _kernel,
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, bd), _wide(x.dtype))],
        interpret=interpret,
    )(pad_to(x, (n, dp)))
    return out[0, :d]
