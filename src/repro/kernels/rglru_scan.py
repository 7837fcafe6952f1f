"""Pallas TPU kernel: RG-LRU linear recurrence  h_t = a_t · h_{t-1} + b_t.

The decode/long-context hot loop of the RecurrentGemma blocks.  The weakness
of the XLA lowering is that ``associative_scan`` materializes every tree level
in HBM (O(T·W·log T) traffic); this kernel streams (a, b) chunks through VMEM
once — O(T·W) — carrying h in a VMEM scratch across sequential grid steps
(TPU grid iteration order is sequential, last axis fastest, which Pallas
guarantees; interpret mode preserves it).

Grid: (B, W/bw, T/bt).  Every ref is 2-D in its last two axes: h0 is viewed
as (B, 1, W) and the h-scratch is (1, bw), so each block's last two
dimensions are (8, 128)-aligned or whole; step i reads and writes one
(1, bw) row.  T and W are zero-padded to whole blocks (padded steps and
lanes only produce outputs that are sliced away).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref
from repro.kernels.api import register_kernel
from repro.kernels.tiling import LANES, fit_block, pad_to


def _kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, bt: int):
    t_step = pl.program_id(2)

    @pl.when(t_step == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    def body(i, h):
        row = pl.ds(i, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, bt, body, h_ref[...])


@register_kernel("rglru_scan", oracle=ref.rglru_scan_ref)
def rglru_scan(
    a: jnp.ndarray,
    b: jnp.ndarray,
    h0: jnp.ndarray,
    *,
    block_t: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """a, b: (B, T, W) fp32; h0: (B, W).  Returns hs: (B, T, W)."""
    bsz, t, w = a.shape
    bt, tp = fit_block(t, block_t, 8)
    bw, wp = fit_block(w, block_w, LANES)
    grid = (bsz, wp // bw, tp // bt)  # T innermost: h carries across chunks
    seq = pl.BlockSpec((1, bt, bw), lambda i, j, k: (i, k, j))
    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=grid,
        in_specs=[seq, seq, pl.BlockSpec((1, 1, bw), lambda i, j, k: (i, 0, j))],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((bsz, tp, wp), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), a.dtype)],
        interpret=interpret,
    )(pad_to(a, (bsz, tp, wp)), pad_to(b, (bsz, tp, wp)),
      pad_to(h0.reshape(bsz, 1, w), (bsz, 1, wp)))
    return out[:, :t, :w]
