"""Pallas TPU kernels: transformer decode attention + KV cache.

The serving subsystem's kernel set (registry names match the pimsab
lowerings in :mod:`repro.kernels.pimsab_backend`):

* ``attention_qk``   — (M, D) × (T, D) → (M, T) int32 scores q·Kᵀ
* ``softmax_fixedpoint`` — bit-exact integer row softmax (SOFTMAX_F-frac out)
* ``attention_pv``   — (M, T) × (T, Dv) → (M, Dv), accumulator >> shift
* ``decode_gemv``    — (M, K) × (K,) → (M,) single-token projection
* ``kv_append``      — one-hot row scatter into a (T, D) cache

Everything is integer end to end: the fixed-point softmax's divides are a
restoring-division loop (no int division on the VPU, and it mirrors the
bit-serial machine's masked conditional-subtract divider), and every ``>>``
is arithmetic, matching the pimsab shifted-window reads bit for bit.

Tiling: the three matmuls (``attention_qk``, ``attention_pv``,
``decode_gemv``) run on the bit-sliced MXU kernel
(``bitslice_matmul.wide_matmul``): the MXU has no int32 × int32 path, so
their int32 operands split into int8 slices — as many as the static
precision hints ask for, four without one — whose shifted products sum in
int32 and wrap like the oracles.  ``softmax_fixedpoint`` blocks rows (a
multiple of 8, rows zero-padded) and keeps each whole row in VMEM;
``kv_append`` takes its selector as a (T, 1) column.
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
from repro.kernels.tiling import fit_block, pad_to


# ---------------------------------------------------------------------------
# attention_qk
# ---------------------------------------------------------------------------


@register_kernel("attention_qk", oracle=ref.attention_qk_ref)
def attention_qk(
    q: jnp.ndarray, k: jnp.ndarray, *,
    q_bits: Optional[int] = None, k_bits: Optional[int] = None,
    out_bits: Optional[int] = None,
    block: Tuple[int, int, int] = (256, 256, 256), interpret: bool = False,
) -> jnp.ndarray:
    """(M, D) query block × (T, D) key cache → (M, T) int32 scores q·Kᵀ.

    ``q_bits``/``k_bits`` set the slice counts; ``out_bits`` is the
    pimsab score-field hint (see the oracle's docstring for its overflow
    contract).  ``block`` is the (M, T, D) matmul block.
    """
    del out_bits
    assert q.shape[1] == k.shape[1], (q.shape, k.shape)
    return wide_matmul(q, k.T, x_bits=q_bits, w_bits=k_bits, block=block,
                       interpret=interpret)


# ---------------------------------------------------------------------------
# softmax_fixedpoint
# ---------------------------------------------------------------------------


def _softmax_kernel(x_ref, o_ref, *, sigma: int):
    f, kk, fi = ref.SOFTMAX_F, ref.SOFTMAX_K, ref.SOFTMAX_FI
    x = x_ref[...]
    t = x - jnp.max(x, axis=-1, keepdims=True)
    tcl = jnp.maximum(t, -(1 << (f + sigma)))
    u = jnp.right_shift(tcl, sigma)
    w = u + (1 << f) + jnp.right_shift(u * u, f + 1)
    for _ in range(kk):
        w = jnp.right_shift(w * w, f)
    s = jnp.sum(w, axis=-1, keepdims=True)
    # q = 2^(FI+F) // s by restoring division — the quotient fits FI+1 bits
    # (s >= 2^F always: the max element's exponential is exactly 2^F), and
    # the VPU has no integer divide; this also mirrors the machine's masked
    # conditional-subtract divider exactly.
    r = jnp.full_like(s, 1 << (fi + f))
    q = jnp.zeros_like(s)
    for b in range(fi, -1, -1):
        c = s << b
        ge = r >= c
        r = jnp.where(ge, r - c, r)
        q = jnp.where(ge, q + (1 << b), q)
    o_ref[...] = jnp.right_shift(w * q, fi)


@register_kernel("softmax_fixedpoint", oracle=ref.softmax_fixedpoint_ref)
def softmax_fixedpoint(
    x: jnp.ndarray, *, in_frac: int, in_bits: Optional[int] = None,
    block_r: int = 128, interpret: bool = False,
) -> jnp.ndarray:
    """Bit-exact fixed-point row softmax of (R, T) integers with ``in_frac``
    fraction bits → int32 probabilities with ``SOFTMAX_F`` fraction bits
    (identical recipe to the oracle / the pimsab machine, shift for shift).
    Rows are zero-padded to whole ``block_r``-row blocks."""
    del in_bits
    f, kk = ref.SOFTMAX_F, ref.SOFTMAX_K
    in_frac = int(in_frac)
    if in_frac < f - kk:
        raise NotImplementedError(
            f"softmax_fixedpoint needs in_frac >= {f - kk} (got {in_frac})"
        )
    r, t = x.shape
    br, rp = fit_block(r, block_r, 8)
    kernel = functools.partial(_softmax_kernel, sigma=in_frac - f + kk)
    out = pl.pallas_call(
        kernel,
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, t), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, t), jnp.int32),
        interpret=interpret,
    )(pad_to(x.astype(jnp.int32), (rp, t)))
    return out[:r]


# ---------------------------------------------------------------------------
# attention_pv
# ---------------------------------------------------------------------------


@register_kernel("attention_pv", oracle=ref.attention_pv_ref)
def attention_pv(
    p: jnp.ndarray, v: jnp.ndarray, *, shift: int = ref.SOFTMAX_F,
    p_bits: Optional[int] = None, v_bits: Optional[int] = None,
    block: Tuple[int, int, int] = (256, 256, 256), interpret: bool = False,
) -> jnp.ndarray:
    """(M, T) probabilities × (T, Dv) value cache → (M, Dv) int32, with the
    int32 accumulator arithmetically shifted right by ``shift`` (floor) —
    renormalizing ``SOFTMAX_F``-fraction probabilities to the value scale.
    ``p_bits``/``v_bits`` set the slice counts."""
    assert p.shape[1] == v.shape[0], (p.shape, v.shape)
    return wide_matmul(p, v, x_bits=p_bits, w_bits=v_bits, out_shift=int(shift),
                       block=block, interpret=interpret)


# ---------------------------------------------------------------------------
# decode_gemv
# ---------------------------------------------------------------------------


@register_kernel("decode_gemv", oracle=ref.decode_gemv_ref)
def decode_gemv(
    w: jnp.ndarray, x: jnp.ndarray, *,
    w_bits: Optional[int] = None, x_bits: Optional[int] = None,
    block: Tuple[int, int, int] = (256, 256, 256), interpret: bool = False,
) -> jnp.ndarray:
    """(M, K) weights × (K,) activation → (M,) int32 single-token decode
    projection (the pimsab lowering rides the activation down the RF
    constant path; here it is a width-1 MXU matmul, the activation column
    zero-padded to 128 lanes).  ``w_bits``/``x_bits`` set the slice counts."""
    m, k = w.shape
    assert x.shape == (k,), (x.shape, k)
    out = wide_matmul(w, x.reshape(k, 1), x_bits=w_bits, w_bits=x_bits,
                      block=block, interpret=interpret)
    return out.reshape(m)


# ---------------------------------------------------------------------------
# kv_append
# ---------------------------------------------------------------------------


def _kv_append_kernel(c_ref, n_ref, s_ref, o_ref):
    o_ref[...] = jnp.where(s_ref[...] != 0, n_ref[...], c_ref[...])


@register_kernel("kv_append", oracle=ref.kv_append_ref)
def kv_append(
    cache: jnp.ndarray, new: jnp.ndarray, onehot: jnp.ndarray, *,
    interpret: bool = False,
) -> jnp.ndarray:
    """(T, D) cache with the row selected by the one-hot (T,) ``onehot``
    replaced by the (D,) ``new`` row (all-zero selector → no-op).  The
    pimsab lowering latches the selector into the PE mask and, as a
    ``ResidentState`` updater, performs the scatter in place on reserved
    CRAM wordlines.  Here one whole-array block, with the selector as a
    (T, 1) int32 column."""
    t, d = cache.shape
    assert new.shape == (d,), (new.shape, d)
    assert onehot.shape == (t,), (onehot.shape, t)
    return pl.pallas_call(
        _kv_append_kernel,
        in_specs=[
            pl.BlockSpec((t, d), lambda: (0, 0)),
            pl.BlockSpec((1, d), lambda: (0, 0)),
            pl.BlockSpec((t, 1), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), cache.dtype),
        interpret=interpret,
    )(cache, new.astype(cache.dtype).reshape(1, d), onehot.astype(jnp.int32).reshape(t, 1))
