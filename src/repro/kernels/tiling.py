"""Block-shape rules shared by the Pallas TPU kernels.

Mosaic accepts a block whose last two dimensions are multiples of the
(8, 128) sublane × lane tile — (32, 128) for int8 — or equal to the whole
array dimension.  The kernels pick aligned blocks with :func:`fit_block`
and zero-pad operands up to a whole number of blocks (slicing the result
back), so awkward network shapes such as a 1000-class head tile exactly.

Between the ResNet kernels an activation is channels-last: the wrappers of
the 4-D kernels see an ``(N, C, H, W)`` array as its ``(N·H, W·C)`` rows
(:func:`nhwc_rows`, :func:`nchw_from_rows`).  W·C is lane-dense where C is
not (C = 64 would pad every 128-lane tile by half): a multiple of 128 at
every ResNet-18 stage, where NCHW's ``(8, 128)`` tiles over (H, W) pad W
up to 128 lanes.  Each wrapper's closing transpose back
to NCHW meets the next wrapper's opening one inside the Program's jit, and
XLA folds the pair away.  Other ranks are flattened to ``rows × 128`` lanes
(:func:`lane_rows`), not left 1-D: a 1-D array's XLA layout (``T(1024)``)
Mosaic blocks cannot match.

:func:`path_counts` counts, at trace time, which path each kernel call
took (``conv2d/taps``, ``relu/channels_last``, ...).
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Sequence, Tuple

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


def nhwc_rows(x: jnp.ndarray) -> jnp.ndarray:
    """``(N, C, H, W)`` → its channels-last rows ``(N·H, W·C)``."""
    n, c, h, w = x.shape
    return x.transpose(0, 2, 3, 1).reshape(n * h, w * c)


def nchw_from_rows(rows: jnp.ndarray, n: int, c: int, h: int, w: int) -> jnp.ndarray:
    """The inverse of :func:`nhwc_rows`: ``(N·H, W·C)`` → ``(N, C, H, W)``."""
    return rows.reshape(n, h, w, c).transpose(0, 3, 1, 2)


def sublane_tile(dtype) -> int:
    """Rows of one ``(rows, 128)`` tile of ``dtype``: 8 at 32 bits, 16 at
    16, 32 at 8."""
    return max(8, SUBLANES // jnp.dtype(dtype).itemsize)


def load32(ref) -> jnp.ndarray:
    """Read a kernel ref, widening narrow integers to int32: the VPU has no
    8/16-bit integer ALU ops.  Callers cast results back to the output
    dtype, which wraps like the oracle."""
    x = ref[...]
    return x.astype(jnp.int32) if jnp.issubdtype(x.dtype, jnp.integer) else x


_PATH_COUNTS: Counter = Counter()


def count_path(kernel: str, path: str) -> None:
    _PATH_COUNTS[f"{kernel}/{path}"] += 1


def path_counts() -> Dict[str, int]:
    """How many calls of each kernel were traced on each of its paths
    (``"<kernel>/<path>"``) since :func:`reset_path_counts`."""
    return dict(_PATH_COUNTS)


def reset_path_counts() -> None:
    _PATH_COUNTS.clear()
