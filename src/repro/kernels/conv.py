"""Pallas TPU kernels: the DL-network layer set (conv / pool / integer gemm).

These are the kernels an end-to-end integer CNN (the ResNet18-style model in
``repro.models.resnet``) is built from.  On the TPU they all reduce to the
MXU/VPU primitives; on the pimsab backend the same registry names lower onto
the paper's architecture (``repro.kernels.pimsab_backend``):

* ``conv2d``      — an implicit GEMM that never builds the patch matrix:
  the wrapper takes the int32 input channels-last and zero-pads it, splits
  a stride-s conv into its s² stride phases, and cuts each phase into int8
  digits once, at the activation's size.  The Pallas kernel sums the KH·KW
  taps itself: each tap is a shifted row window of the flattened (N, Hq,
  Wq) grid, and the windows of one digit sit side by side in VMEM as one
  (rows, KH·KW·C) operand of the MXU (path ``taps``).  A channel count too
  narrow to feed the MXU per tap (the RGB stem) takes a channels-last int8
  patch slab of KH·KW static slices as one tap (path ``patches``).
  ``tiling.path_counts`` counts the paths traced.  The pimsab backend still
  lowers through ``ref.im2col``, the §V-A layout contract, onto the ``mac``
  gemm pipeline.
* ``int_matmul``  — raw-integer (M, K) × (K, N) with int32 accumulation: the
  network-head matmul whose activations arrive as another kernel's integer
  output; the kernel slices them itself.
* ``maxpool2d`` / ``avgpool2d`` / ``global_avgpool`` — window reductions of
  the channels-last input inside the kernel, over its KH·KW strided views:
  no window matrix (path ``channels_last``).  pimsab folds max via CmpGE +
  masked copy and average via the constant-operand MAC plus a shift-read
  divide, over the ``ref.pool_patches`` window matrix.

``x_bits`` / ``w_bits`` are *static precision hints*: the pimsab lowering
sizes its fields from them (program mode cannot calibrate precision from
values), and the TPU matmuls take one int8 slice per 8 bits of them (four
slices without a hint).  The oracles ignore them.

Tiling: ``int_matmul`` follows ``bitslice_matmul`` (aligned blocks,
operands zero-padded, e.g. the 1000-class head to 1024 columns).  The conv
kernel's grid is (OC blocks, row blocks, C blocks), C the reduction swept
into the (bm, bn) int32 output block.  A step holds, per stride phase and
digit, a row block (bm, C ≤ 512) int8 and the next hb rows its taps reach
(hb ≥ (KH-1)·Wq + KW-1, sublane-aligned), the Sx blocks together about
512 KB; the step's weights (Sw, T·C, bn ≤ 256) int8, at most 1.2 MB at
8-bit weights; the output block; and the (bm, T·C) operand of one digit,
about 1.2 MB: by these sizes some 6 MB of VMEM with double buffering.

Between the kernels an activation is channels-last: the conv's wrapper
transposes its input to NHWC and its output back, and relu, the residual
add and the pools map over the rows ``(N·H, W·C)`` of the same NHWC array
(``tiling.nhwc_rows``).  Each wrapper keeps the NCHW contract at its edges,
and inside the Program's one jit a wrapper's closing transpose meets the
next one's opening transpose, which XLA folds away.  The conv's row width
Wq is a whole 8-row tile (:func:`_tile_rows`), so that the NHWC arrays it
pads and crops share one layout with the kernel's ``(rows, C)`` operands.  A pool's grid step holds
whole images, about ``block`` × 128 input elements: their H rows first reduce
over the window rows ``s·i + ky``, read as KH strided views of one 128-lane
column at a time (Mosaic reads strided rows from a 128-lane ref only), then
over the KW lane slices ``(s·j + kx)·C``, C wide.  The global pool is the
window pool whose one window is the whole map.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.api import active_pairs, register_kernel
from repro.kernels.bitslice_matmul import ACC_BITS, SLICE_BITS, slices_for_bits, wide_matmul
from repro.kernels.tiling import (
    LANES, SUBLANES, count_path, fit_block, nchw_from_rows, nhwc_rows, pad_to, round_up,
    sublane_tile,
)


# ---------------------------------------------------------------------------
# conv2d as an implicit GEMM
# ---------------------------------------------------------------------------

_MIN_TAP_C = LANES // 2   # one tap fills at least half of the MXU's contraction
_X_BLOCK_BYTES = 1 << 19  # digits of one row block: Sx · rows · C bytes

def _digits(a: jnp.ndarray, n: int) -> List[jnp.ndarray]:
    """The ``n`` int8 digits ``bitslice_matmul.int_slices`` gives an integer
    ``a``, as separate arrays, each one elementwise function of the value:
    each digit reaches the kernel as its own operand, so XLA writes it in
    one pass with no stacking copy.  A float ``a`` is its own float32 digit.

    With ``y = a + Σ_{s<n-1} 128·256^s`` (wrapping), digit ``s < n-1`` is
    byte ``s`` of ``y`` less 128 and the top digit ``y >> 8(n-1)``,
    truncated to int8."""
    if not jnp.issubdtype(a.dtype, jnp.integer):
        return [a.astype(jnp.float32)]
    half = 1 << (SLICE_BITS - 1)
    y = a.astype(jnp.int32) + jnp.int32(sum(half << (SLICE_BITS * s) for s in range(n - 1)))
    low = [jnp.bitwise_and(jnp.right_shift(y, SLICE_BITS * s), (1 << SLICE_BITS) - 1) - half
           for s in range(n - 1)]
    return [d.astype(jnp.int8) for d in low + [jnp.right_shift(y, SLICE_BITS * (n - 1))]]


def _phase_taps(kh: int, kw: int, s: int, wq: int):
    """The taps of a stride-``s`` conv as stride-1 taps over its ``s × s``
    stride phases, each a flattened ``(N, Hq, Wq)`` grid.

    Phase (a, b) holds input pixels ``(s·i + a, s·j + b)`` at grid position
    (i, j), so kernel position (ky, kx) is a tap of phase
    ``(ky mod s, kx mod s)`` at row offset ``(ky // s)·Wq + kx // s``.
    Returns the phases (a, b) some tap reads (a 1×1 stride-2 conv reads
    phase (0, 0) alone) and the taps as (phase, row offset), in kernel
    position order."""
    reads = [(ky % s, kx % s) for ky in range(kh) for kx in range(kw)]
    phases = sorted(set(reads))
    taps = tuple((phases.index((ky % s, kx % s)), (ky // s) * wq + kx // s)
                 for ky in range(kh) for kx in range(kw))
    return phases, taps


def _tile_rows(wq: int) -> int:
    """The grid's row width: ``wq`` rounded up to a whole 8-row tile, so
    that ``(N, Hq, Wq, C)`` and its ``(N·Hq·Wq, C)`` rows share one layout
    and XLA pads the input and crops the output in place instead of
    relaying them out channels-major; ``wq`` itself where that would add
    more than a quarter of the rows (a 7×7 map's 9 → 16)."""
    aligned = round_up(wq, 8)
    return aligned if 4 * aligned <= 5 * wq else wq


def _row_blocks(r: int, sx: int, bc: int, hb: int, cap: int) -> Tuple[int, int]:
    """(bm, nb): ``nb`` row blocks of ``bm`` rows covering ``r``; bm a
    multiple of the halo ``hb`` (or of the sublane tile), one block's digits
    about ``_X_BLOCK_BYTES`` and at most ``cap`` rows."""
    unit = hb or SUBLANES
    rows = max(unit, min(cap, _X_BLOCK_BYTES // (sx * bc)) // unit * unit)
    nb = -(-r // rows)
    return round_up(-(-r // nb), unit), nb


def _tap_kernel(*refs, halos: Tuple[bool, ...], taps: Tuple[Tuple[int, int], ...],
                pairs: Tuple[Tuple[int, int], ...], bm: int, slice_bits: int):
    """Per phase and digit an x block ``(bm, bc)`` and, where the phase's
    taps reach past it, the next rows ``(hb, bc)``; w_ref ``(Sw, 1, T·bc,
    bn)``; o_ref ``(bm, bn)``, the accumulator across the C sweep (grid
    axis 2).

    Per digit, the taps' shifted row windows are laid side by side in VMEM,
    so the MXU sums the taps within one ``(bm, T·bc)`` contraction."""
    *x_refs, w_ref, o_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    n_x = len(x_refs) // sum(2 if h else 1 for h in halos)
    blocks, it = [], iter(x_refs)
    for halo in halos:
        blocks.append([(next(it), next(it) if halo else None) for _ in range(n_x)])
    for s in sorted({s for s, _ in pairs}):
        wins = [main[...] if nxt is None else jnp.concatenate([main[...], nxt[...]], axis=0)
                for main, nxt in (digits[s] for digits in blocks)]
        cols = [wins[p][off:off + bm] for p, off in taps]
        lhs = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        part = None
        for t in [t for ss, t in pairs if ss == s]:  # the bit-serial loop, unrolled
            prod = jax.lax.dot_general(lhs, w_ref[t, 0], (((1,), (0,)), ((), ())),
                                       preferred_element_type=o_ref.dtype)
            if t:
                prod = prod << (slice_bits * t)
            part = prod if part is None else part + prod
        o_ref[...] += (part << (slice_bits * s)) if s else part


def _tap_call(phases, w_t: jnp.ndarray, *, taps: Tuple[Tuple[int, int], ...],
              pairs: Tuple[Tuple[int, int], ...], r: int, bm: int, nb: int, hb: int,
              bc: int, bn: int, interpret: bool) -> jnp.ndarray:
    """``(r, OCp)``: row ``p`` is the sum over taps (phase, off) and slice
    pairs (s, t) of ``phases[phase][s][p + off] @ w_t[t, :, tap]``, shifted
    by 8·(s+t).

    ``phases``: per phase its digits, ``(≥ nb·bm + hb, Cp)`` each; ``w_t``:
    ``(Sw, Cp/bc, T·bc, OCp)``, the taps' weight slabs side by side per C
    block.
    A row block carries the next ``hb`` rows (``hb`` ≥ its phase's largest
    offset) so that a tap is a static row slice in VMEM."""
    sw, nc, tk, ocp = w_t.shape
    reach = [max(off for tp, off in taps if tp == p) for p in range(len(phases))]
    in_specs, args = [], []
    for p, digits in enumerate(phases):
        for d in digits:
            args.append(d)
            in_specs.append(pl.BlockSpec((bm, bc), lambda j, i, k: (i, k)))
            if reach[p]:
                args.append(d)
                in_specs.append(pl.BlockSpec(
                    (hb, bc), lambda j, i, k, q=bm // hb: ((i + 1) * q, k)))
    acc = jnp.int32 if jnp.issubdtype(w_t.dtype, jnp.integer) else jnp.float32
    return pl.pallas_call(
        functools.partial(_tap_kernel, halos=tuple(bool(h) for h in reach), taps=taps,
                          pairs=pairs, bm=bm, slice_bits=SLICE_BITS),
        grid=(ocp // bn, nb, nc),
        in_specs=in_specs + [pl.BlockSpec((sw, 1, tk, bn), lambda j, i, k: (0, k, 0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, ocp), acc),  # the last block's rows past r are dropped
        interpret=interpret,
    )(*args, w_t)


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
    block: Tuple[int, int, int] = (2048, 256, 512),
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) × (OC, C, KH, KW) → (N, OC, OH, OW) as an implicit GEMM.

    Integer inputs accumulate in int32 (wrapping, like the oracle); float
    inputs in float32.  ``x_bits``/``w_bits`` set the slice counts.
    ``block`` caps the (rows, OC, C) blocks of the kernel.
    """
    n, c, h, hw = x.shape
    oc, c2, kh, kw = w.shape
    assert c == c2, (c, c2)
    s, pad = stride, padding
    oh, ow = ref.conv2d_out_hw(h, hw, kh, kw, s, pad)
    if jnp.issubdtype(x.dtype, jnp.integer):
        nx, nw = slices_for_bits(x_bits), slices_for_bits(w_bits)
        pairs = tuple(p for p in active_pairs(nx, nw) if SLICE_BITS * sum(p) < ACC_BITS)
    else:
        nx = nw = 1
        pairs = ((0, 0),)
    path = "taps" if kh * kw == 1 or c >= _MIN_TAP_C else "patches"
    count_path("conv2d", path)
    with jax.named_scope(path):
        if path == "taps":
            hq, wq = -(-(h + 2 * pad) // s), -(-(hw + 2 * pad) // s)
            wq = _tile_rows(wq)
            phases, taps = _phase_taps(kh, kw, s, wq)
            hi = (hq * s - h - pad, wq * s - hw - pad)
        else:
            hq, wq, taps, hi = oh, ow, ((0, 0),), (pad, pad)
        kc = c if path == "taps" else kh * kw * c   # channels of one tap
        bc, cp = (kc, kc) if kc <= block[2] else fit_block(kc, block[2], LANES)
        bn, ocp = (oc, oc) if oc <= block[1] else fit_block(oc, block[1], LANES)
        reach = max(off for _, off in taps)
        hb = round_up(reach, SUBLANES) if reach else 0
        r = n * hq * wq
        bm, nb = _row_blocks(r, nx, bc, hb, block[0])
        # channels-last and zero-padded: the border, and whole zero images
        # past the last so that every row block and its halo lie inside
        extra = -(-(nb * bm + hb - r) // (hq * wq))
        xp = jnp.pad(x.transpose(0, 2, 3, 1), ((0, extra), (pad, hi[0]), (pad, hi[1]),
                                               (0, cp - c if path == "taps" else 0)))
        rows = (n + extra) * hq * wq
        if path == "taps":  # the stride phases, each split into int8 digits once
            xs = [_digits(jax.lax.slice(xp, (0, a, b, 0), xp.shape, (1, s, s, 1))
                          .reshape(rows, cp), nx) for a, b in phases]
        else:  # per digit, KH·KW strided slices side by side: one tap
            xs = [[pad_to(jnp.concatenate(
                [jax.lax.slice(d, (0, dy, dx, 0),
                               (n + extra, dy + s * (oh - 1) + 1, dx + s * (ow - 1) + 1, c),
                               (1, s, s, 1)) for dy in range(kh) for dx in range(kw)],
                axis=-1).reshape(rows, kc), (rows, cp)) for d in _digits(xp, nx)]]
        t_n, nc = len(taps), cp // bc
        w_taps = w.transpose(2, 3, 1, 0).reshape(t_n, kc, oc)   # tap order (ky, kx), then c
        w_t = (jnp.stack(_digits(pad_to(w_taps, (t_n, cp, ocp)), nw))
               .reshape(nw, t_n, nc, bc, ocp).transpose(0, 2, 1, 3, 4)
               .reshape(nw, nc, t_n * bc, ocp))
        out = _tap_call(xs, w_t, taps=taps, pairs=pairs, r=r, bm=bm, nb=nb, hb=hb, bc=bc,
                        bn=bn, interpret=interpret)
        return out[:, :oc].reshape(n, hq, wq, oc)[:, :oh, :ow].transpose(0, 3, 1, 2)


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


# ---------------------------------------------------------------------------
# pools over channels-last rows
# ---------------------------------------------------------------------------


def _acc_dtype(x: jnp.ndarray):
    return jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32


def _images_per_block(n: int, h: int, oh: int, lanes: int, in_dtype, out_dtype,
                      block: int) -> int:
    """Whole images per grid step, about ``block`` × 128 input elements:
    a multiple of the count whose ``H`` input and ``OH`` output rows fill
    whole tiles, or all ``n``."""
    tin, tout = sublane_tile(in_dtype), sublane_tile(out_dtype)
    unit = math.lcm(tin // math.gcd(h, tin), tout // math.gcd(oh, tout))
    return min(n, max(1, block * LANES // (h * lanes) // unit) * unit)


def _window_kernel(x_ref, o_ref, col_ref, *, op, images: int, h: int, oh: int, ow: int,
                   c: int, window: Tuple[int, int], stride: Tuple[int, int]):
    """The window reduction ``op`` of ``images`` whole images: x_ref
    ``(images·H, W·C)``, o_ref ``(images·OH, OW·C)``, both channels-last
    rows.  Rows first: each 128-lane column of an image is copied into
    col_ref ``(H, 128)`` (Mosaic reads strided rows from a 128-lane ref
    only) and its window rows ``sh·i + ky`` are read as KH strided views.
    Then columns: the KW lane slices at ``(sw·j + kx)·C``, C wide."""
    wc, (kh, kw), (sh, sw) = x_ref.shape[1], window, stride
    for b in range(images):
        cols = []
        for q in range(0, wc, LANES):
            width = min(LANES, wc - q)
            col_ref[:, :width] = x_ref[b * h:(b + 1) * h, q:q + width].astype(col_ref.dtype)
            views = [col_ref[pl.ds(ky, oh, stride=sh), :][:, :width] for ky in range(kh)]
            cols.append(functools.reduce(op, views))
        r = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        out = [functools.reduce(op, [r[:, (sw * j + kx) * c:(sw * j + kx + 1) * c]
                                     for kx in range(kw)]) for j in range(ow)]
        o_ref[b * oh:(b + 1) * oh, :] = jnp.concatenate(out, axis=1).astype(o_ref.dtype)


def _window_pool(name: str, op, x: jnp.ndarray, window: Tuple[int, int],
                 stride: Tuple[int, int], out_dtype, block: int, interpret: bool,
                 finish=lambda rows: rows) -> jnp.ndarray:
    """``(N, C, H, W)`` → ``(N, C, OH, OW)``: ``op`` over each ``(KH, KW)``
    window of the channels-last rows in ``out_dtype``, then ``finish`` on
    the result rows."""
    n, c, h, w = x.shape
    oh, ow = (h - window[0]) // stride[0] + 1, (w - window[1]) // stride[1] + 1
    count_path(name, "channels_last")
    bn = _images_per_block(n, h, oh, w * c, x.dtype, out_dtype, block)
    with jax.named_scope("channels_last"):
        rows = pl.pallas_call(
            functools.partial(_window_kernel, op=op, images=bn, h=h, oh=oh, ow=ow, c=c,
                              window=window, stride=stride),
            grid=(pl.cdiv(n, bn),),
            in_specs=[pl.BlockSpec((bn * h, w * c), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn * oh, ow * c), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n * oh, ow * c), out_dtype),
            scratch_shapes=[pltpu.VMEM((h, LANES), _acc_dtype(x))],
            interpret=interpret,
        )(nhwc_rows(x))
    return nchw_from_rows(finish(rows), n, c, oh, ow)


@register_kernel("maxpool2d", oracle=ref.maxpool2d_ref)
def maxpool2d(
    x: jnp.ndarray,
    *,
    window: int = 2,
    stride: Optional[int] = None,
    block: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C, OH, OW) window max (no padding)."""
    s = stride or window
    return _window_pool("maxpool2d", jnp.maximum, x, (window, window), (s, s), x.dtype, block,
                        interpret)


@register_kernel("avgpool2d", oracle=ref.avgpool2d_ref)
def avgpool2d(
    x: jnp.ndarray,
    *,
    window: int = 2,
    block: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C, OH, OW) window average, stride == window.

    Integer inputs floor-divide by the window count — the semantics the
    bit-serial machine gets for free by reading the accumulator at a
    wordline offset (an arithmetic right shift).
    """
    return _window_pool("avgpool2d", jnp.add, x, (window, window), (window, window),
                        _acc_dtype(x), block, interpret,
                        finish=lambda s: ref._pool_mean(s, window * window))


@register_kernel("global_avgpool", oracle=ref.global_avgpool_ref)
def global_avgpool(
    x: jnp.ndarray, *, block: int = 2048, interpret: bool = False
) -> jnp.ndarray:
    """(N, C, H, W) → (N, C) spatial average (integer: floor-divide by H·W):
    the window pool whose one window is the whole map."""
    n, c, h, w = x.shape
    return _window_pool("global_avgpool", jnp.add, x, (h, w), (h, w), _acc_dtype(x), block,
                        interpret, finish=lambda s: ref._pool_mean(s, h * w)).reshape(n, c)
