"""Plain reference for ``resnet18``: the integer ResNet-18 in ``jax.numpy``.

It imports nothing of the program.  Same semantics as the configuration
states: int8-range images and weights, int32 accumulation that wraps,
3x3 stem, 2x2 max pool, BasicBlocks with 1x1 projection shortcuts, a global
average pool that floor-divides its int32 sum, and an int32 head.  It runs on
the host CPU, whose integer convolutions are exact.

``bits`` below 8 is the control: images and weights cut to their top
``bits`` bits, the nearest precision below the stated int8 (int4).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32


def _cut(a, bits: int, stated: int):
    """Keep the top ``bits`` of a ``stated``-bit integer (arithmetic shift)."""
    shift = stated - bits
    return (a >> shift) << shift if shift > 0 else a


def _conv(x, w, stride: int):
    pad = w.shape[-1] // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), preferred_element_type=I32)


def forward(cfg: Dict[str, Any], params, x, bits: int = 8):
    cut = partial(_cut, bits=bits, stated=cfg["weight_bits"])
    h = jax.nn.relu(_conv(_cut(x, bits, cfg["input_bits"]), cut(params["stem"]), 1))
    h = jax.lax.reduce_window(h, jnp.iinfo(I32).min, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            y = jax.nn.relu(_conv(h, cut(blk["conv1"]), stride))
            y = _conv(y, cut(blk["conv2"]), 1)
            idn = _conv(h, cut(blk["proj"]), stride) if "proj" in blk else h
            h = jax.nn.relu(y + idn)
    n, c, hh, ww = h.shape
    pooled = jnp.floor_divide(jnp.sum(h.reshape(n, c, hh * ww), axis=-1), hh * ww)
    return jax.lax.dot(pooled, cut(params["head"]), preferred_element_type=I32)


def logits(cfg: Dict[str, Any], params, images: np.ndarray, bits: int = 8) -> np.ndarray:
    """(N, num_classes) int32 logits of host ``images`` on the host CPU."""
    cpu = jax.devices("cpu")[0]
    p, x = jax.device_put((params, np.asarray(images, np.int32)), cpu)
    return np.asarray(jax.jit(partial(forward, cfg, bits=bits))(p, x))
