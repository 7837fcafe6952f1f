"""Pallas TPU kernel: bit-sliced integer matmul with int32 accumulation.

PIMSAB's bit-serial computation adapted to the TPU memory/compute hierarchy:
the MXU's int8 path is the "massively parallel PE array", a radix-256 slice is
the hardware-native analogue of the paper's 1-bit plane, and the (s, t) slice
loop is the bit-serial loop.  Adaptive precision = fewer slices; ``mul_const``
zero-bit skipping = statically dropping all-zero slice pairs
(``api.skip_pairs`` / ``api.zero_slice_pairs`` compute them from concrete
operands at trace time).

The same kernel carries the registry's other integer matmuls (``int_matmul``,
the attention kernels; ``conv2d`` sums its taps in its own kernel in
``kernels/conv.py``): the MXU has no int32 × int32 path, so
:func:`wide_matmul` splits int32 operands into int8 slices and sums the
shifted slice-pair products in int32, which wraps mod 2³² exactly like the
int32 oracles.

Tiling: grid (M/bm, N/bn, K/bk), K innermost so the (bm, bn) accumulator
lives in VMEM scratch across the K sweep.  Blocks are at most 256/256/256,
bm a multiple of 32 (the int8 sublane tile) and bk, bn multiples of 128;
operands are zero-padded to whole blocks and the result sliced back.  Per-step
VMEM: Sx·bm·bk + Sw·bk·bn int8 + bm·bn int32 ≈ 0.5 MB at 8-bit — comfortable
next to double-buffered prefetch in ~16 MB VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref
from repro.kernels.api import active_pairs, bitslice_matmul_oracle, register_kernel
from repro.kernels.tiling import LANES, SUBLANES, fit_block, pad_to

SLICE_BITS = 8
ACC_BITS = 32


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int, slice_bits: int,
            pairs: Tuple[Tuple[int, int], ...], out_shift: int):
    """x_ref: (Sx, bm, bk); w_ref: (Sw, bk, bn); o_ref/acc_ref: (bm, bn)."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for s, t in pairs:  # the bit-serial loop, unrolled (static slice counts)
        prod = jax.lax.dot_general(
            x_ref[s],
            w_ref[t],
            (((1,), (0,)), ((), ())),
            preferred_element_type=acc_ref.dtype,
        )
        if s + t:
            prod = prod << (slice_bits * (s + t))
        acc_ref[...] += prod

    @pl.when(k_step == n_k - 1)
    def _flush():
        acc = acc_ref[...]
        o_ref[...] = jnp.right_shift(acc, out_shift) if out_shift else acc


def sliced_matmul(
    x_slices: jnp.ndarray,
    w_slices: jnp.ndarray,
    *,
    pairs: Tuple[Tuple[int, int], ...],
    slice_bits: int = SLICE_BITS,
    out_shift: int = 0,
    block: Tuple[int, int, int] = (256, 256, 256),
    interpret: bool = False,
) -> jnp.ndarray:
    """(Sx, M, K) × (Sw, K, N) → (M, N) = Σ_{(s,t) ∈ pairs} (x_s @ w_t) <<
    slice_bits·(s+t), then arithmetically ``>> out_shift``.

    int8 slices accumulate in int32; float operands (one "slice" each, pairs
    ``((0, 0),)``) in float32.  Any M, N, K: operands are zero-padded to the
    aligned blocks (module docstring) and the result is sliced back.
    """
    sx, m, k = x_slices.shape
    sw, k2, n = w_slices.shape
    assert k == k2, (k, k2)
    acc = jnp.int32 if jnp.issubdtype(x_slices.dtype, jnp.integer) else jnp.float32
    bm, mp = fit_block(m, block[0], SUBLANES)
    bn, np_ = fit_block(n, block[1], LANES)
    bk, kp = fit_block(k, block[2], LANES)
    n_k = kp // bk
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, slice_bits=slice_bits,
                          pairs=tuple(pairs), out_shift=out_shift),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((sx, bm, bk), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((sw, bk, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), acc),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        interpret=interpret,
    )(pad_to(x_slices, (sx, mp, kp)), pad_to(w_slices, (sw, kp, np_)))
    return out[:m, :n]


def slices_for_bits(bits: Optional[int]) -> int:
    """int8 slices that hold every signed ``bits``-bit value exactly (all
    four — exact mod 2³² — without a precision hint)."""
    n_max = ACC_BITS // SLICE_BITS
    if bits is None:
        return n_max
    n = 1
    while n < n_max and ref.slice_range(SLICE_BITS * n)[1] < (1 << (bits - 1)) - 1:
        n += 1
    return n


def int_slices(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Balanced int8 digits ``d_s`` of int32 ``x``: ``x ≡ Σ_s d_s·2^(8s)``
    (mod 2³²), and exactly when ``x`` lies in ``ref.slice_range(8n)``.

    Unlike ``ref.to_slices`` nothing is clamped: the top digit is truncated
    to int8, which only drops multiples of 2^(8n)."""
    rem = x.astype(jnp.int32)
    half, mask = 1 << (SLICE_BITS - 1), (1 << SLICE_BITS) - 1
    digits = []
    for _ in range(n - 1):
        d = jnp.bitwise_and(rem + half, mask) - half
        digits.append(d)
        rem = jnp.right_shift(rem - d, SLICE_BITS)
    digits.append(rem)
    return jnp.stack([d.astype(jnp.int8) for d in digits])


def wide_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    out_shift: int = 0,
    block: Tuple[int, int, int] = (256, 256, 256),
    interpret: bool = False,
) -> jnp.ndarray:
    """(M, K) @ (K, N) for the integer registry kernels, int32 wraparound
    included, on the MXU's int8 path.

    Each operand splits into ``slices_for_bits`` int8 slices (the static
    precision hints; four without one).  Pairs with s + t ≥ 4 only add
    multiples of 2³², so they are never issued: 1 pair for two 8-bit
    operands, 10 for two unhinted int32 ones.  Float operands take one
    float32 pass.
    """
    if not jnp.issubdtype(x.dtype, jnp.integer):
        return sliced_matmul(
            x.astype(jnp.float32)[None], w.astype(jnp.float32)[None],
            pairs=((0, 0),), block=block, interpret=interpret,
        )
    nx, nw = slices_for_bits(x_bits), slices_for_bits(w_bits)
    pairs = tuple(p for p in active_pairs(nx, nw)
                  if SLICE_BITS * sum(p) < ACC_BITS)
    return sliced_matmul(
        int_slices(x, nx), int_slices(w, nw), pairs=pairs,
        out_shift=out_shift, block=block, interpret=interpret,
    )


@register_kernel("bitslice_matmul", oracle=bitslice_matmul_oracle)
def bitslice_matmul(
    x_slices: jnp.ndarray,
    w_slices: jnp.ndarray,
    *,
    slice_bits: int = SLICE_BITS,
    block: Tuple[int, int, int] = (256, 256, 256),
    skip: Tuple[Tuple[int, int], ...] = (),
    interpret: bool = False,
) -> jnp.ndarray:
    """(Sx, M, K) int8 × (Sw, K, N) int8 → (M, N) int32.

    ``skip`` lists (s, t) slice pairs statically known to contribute zero
    (PIMSAB zero-bit skipping) — their MXU passes are never issued: the
    unrolled shift list is exactly ``api.active_pairs(Sx, Sw, skip)``.
    """
    return sliced_matmul(
        x_slices, w_slices,
        pairs=active_pairs(x_slices.shape[0], w_slices.shape[0], skip),
        slice_bits=slice_bits, block=block, interpret=interpret,
    )
