"""Shared model building blocks: norms, RoPE, and the (optionally bit-plane
quantized) linear layer.

The quantized path is the TPU-native form of PIMSAB's bit-serial-aware
computation: integer tensors are decomposed into ``slice_bits``-wide slices
(radix-2**slice_bits bit-slicing — the MXU int8 path plays the role of the
paper's 1-bit PE array), plane-pair matmuls run with int32 accumulation, and
results are recombined with shifts.  Adaptive precision = fewer slices;
``mul_const`` zero-bit skipping = statically dropping all-zero weight slices.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import api
from repro.kernels.api import PrecisionSpec, SlicedTensor

Params = Dict[str, Any]


class BlockKind(NamedTuple):
    """One temporal-mixer kind of a transformer block (a name in
    ``ModelConfig.block_pattern``): every decision the model makes by kind.
    ``h`` is the block's normed input, (B, S, D) or a decode step's (B, 1, D).

    * ``init(key, cfg, dtype)`` -> the mixer's parameters, kept under
      ``param`` in the block; with ``ffn`` the block also carries an FFN
      (when ``cfg.d_ff`` > 0);
    * ``seq(params, h, cfg, flags, positions)`` -> ``(y, entries)``: the
      sequence form, causal over ``positions`` (1, S), with the raw cache
      entries of every position;
    * ``cache(cfg, batch, max_len, flags)`` -> the empty decode-cache entry;
    * ``to_decode(entries, cfg, s, max_len, flags)`` -> the entries a prefill
      of ``s`` positions keeps, in the decode layout;
    * ``decode(params, h, cfg, entry, pos)`` -> ``(y, new entry)``: the decode
      form, one token at position ``pos``.

    Both forms trace under the name scope ``scope`` when it is set."""

    param: str
    scope: Optional[str]
    ffn: bool
    init: Callable
    seq: Callable
    cache: Callable
    to_decode: Callable
    decode: Callable


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V3's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN's inverse frequencies over ``dim`` rotary dims (``dim / 2`` of
    them), as DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` builds them:
    the dims that turn fewer than ``beta_slow`` times over the original
    context are interpolated by ``factor``, those that turn more than
    ``beta_fast`` times are kept, with a linear ramp between."""

    def correction_dim(rotations):
        return dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extra / yarn.factor * ramp + extra * (1 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,S,1,hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantized (bit-sliced) linear — PIMSAB adaptive precision on the MXU
# ---------------------------------------------------------------------------


def quantize_weight(w: jnp.ndarray, bits: int = 8) -> Params:
    """Symmetric per-output-channel int quantization of a (..., d_in, d_out)
    weight (leading axes: scan-group stacking)."""
    wf = w.astype(jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / qmax  # (..., 1, d_out)
    scale = jnp.maximum(scale, 1e-8)
    w_q = jnp.clip(jnp.round(wf / scale), -qmax - 1, qmax).astype(jnp.int8)
    return {"w_q": w_q, "w_scale": scale.astype(jnp.float32)}


def _dynamic_act_quant(x: jnp.ndarray, bits: int):
    qmax = 2 ** (bits - 1) - 1
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / qmax
    scale = jnp.maximum(scale, 1e-8)
    x_q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax).astype(jnp.int8)
    return x_q, scale


def int_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray) -> jnp.ndarray:
    """int8 × int8 → int32 matmul (one bit-slice plane-pair pass on the MXU)."""
    return jax.lax.dot_general(
        x_q,
        w_q,
        (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def quant_linear(
    p: Params, x: jnp.ndarray, spec: PrecisionSpec = PrecisionSpec.int8
) -> jnp.ndarray:
    """Bit-sliced integer linear: dynamic act quant + int32 accumulation.

    When the spec fits one slice pair (act/weight bits ≤ slice_bits, the
    int8 serving default) this is a single MXU pass; wider specs go through
    :func:`repro.kernels.api.matmul` over ``SlicedTensor`` operands, which
    splits into slices, skips statically-zero ones, and recombines with
    shifts.
    """
    if spec.single_pass:
        x_q, x_scale = _dynamic_act_quant(x, spec.act_bits)
        acc = int_matmul(x_q, p["w_q"])
        out = acc.astype(jnp.float32) * x_scale * p["w_scale"]
    else:
        lead = x.shape[:-1]
        x_st = SlicedTensor.quantize(x.reshape(-1, x.shape[-1]), spec)
        w_st = SlicedTensor.from_int(
            p["w_q"].astype(jnp.int32), spec.weight_bits,
            slice_bits=spec.slice_bits, scale=p["w_scale"].reshape(-1),
        )
        out = api.matmul(x_st, w_st).reshape(*lead, -1)
    if "b" in p:
        out = out + p["b"].astype(jnp.float32)
    return out.astype(x.dtype)


def dequantized(p: Params, dtype) -> jnp.ndarray:
    """A linear's weight in ``dtype``, whether it is stored plain or as int8
    with its scales (for products with activations that are not quantized)."""
    if "w_q" in p:
        return (p["w_q"].astype(jnp.float32) * p["w_scale"]).astype(dtype)
    return p["w"].astype(dtype)


def linear(
    p: Params, x: jnp.ndarray, spec: Optional[PrecisionSpec] = None
) -> jnp.ndarray:
    """Dispatch: quantized (bit-slice) if the param leaf is quantized."""
    if "w_q" in p:
        return quant_linear(p, x, spec or PrecisionSpec.int8)
    out = x @ p["w"]
    if "b" in p:
        out = out + p["b"]
    return out


def linear_init(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    p: Params = {"w": dense_init(key, d_in, d_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


# ---------------------------------------------------------------------------
# Program-built blocks (trace → compile-once → execute)
# ---------------------------------------------------------------------------


def _matmul_relu_chain(x_st: SlicedTensor, w_st: SlicedTensor) -> jnp.ndarray:
    # scale-less operands: the integer accumulator feeds relu directly, so on
    # the pimsab backend the intermediate stays CRAM-resident (DRAM elided)
    return api.relu(api.matmul(x_st, w_st))


_matmul_relu = api.trace(_matmul_relu_chain, name="quant_linear_relu")


def quant_linear_relu(
    p: Params, x: jnp.ndarray, spec: Optional[PrecisionSpec] = None
) -> jnp.ndarray:
    """``relu(x @ W)`` over a quantized weight, built as one traced Program.

    The matmul→relu chain compiles once per (shape, PrecisionSpec, backend)
    signature and replays through the cached Executor; on the pimsab backend
    the linear's accumulator never round-trips through DRAM before the relu.
    Scales factor out of relu (they are positive by construction), so the
    program runs in the raw integer domain and dequantizes afterwards.
    Falls back to the eager composition for tracers (under ``jax.jit``),
    unquantized params, or a bias (relu doesn't commute with ``+ b``).
    """
    spec = spec or PrecisionSpec.int8
    if "w_q" not in p or "b" in p or api.static_value(x) is None:
        return jnp.maximum(linear(p, x, spec), 0)
    lead = x.shape[:-1]
    x_st = SlicedTensor.quantize(x.reshape(-1, x.shape[-1]), spec)
    x_raw = SlicedTensor(  # scale-less view: keep zero-slice skip metadata
        slices=x_st.slices, slice_bits=x_st.slice_bits,
        orig_bits=x_st.orig_bits, zero_slices=x_st.zero_slices,
    )
    w_st = SlicedTensor.from_int(
        p["w_q"].astype(jnp.int32), spec.weight_bits, slice_bits=spec.slice_bits
    )
    raw = _matmul_relu(x_raw, w_st)
    out = raw.astype(jnp.float32) * x_st.scale.reshape(-1, 1) * p["w_scale"].reshape(1, -1)
    return out.reshape(*lead, -1).astype(x.dtype)


def maybe_quantize_tree(params, cfg, path: str = "") -> Any:
    """Transform a param tree for serving: every linear {'w': ...} leaf-dict
    becomes {'w_q': int8, 'w_scale': f32} (PIMSAB: weights live bit-sliced).

    Embedding and normalization weights stay high-precision (they are
    gathered, not matmul'd).
    """
    if not cfg.quant.enabled:
        return params
    spec = PrecisionSpec.from_quant_config(cfg.quant)
    skip = ("embed", "norm", "scale", "lambda", "conv", "gate_bias", "router")

    def rec(node, path):
        if isinstance(node, dict):
            # ndim 2 = plain linear; ndim 3 = scan-stacked (G, d_in, d_out) —
            # per-group quantization; lax.scan slices both w_q and w_scale;
            # ndim 4 = scan-stacked experts (G, E, d_in, d_out)
            if "w" in node and node["w"].ndim in (2, 3, 4) and not any(s in path for s in skip):
                q = quantize_weight(node["w"], spec.weight_bits)
                if "b" in node:
                    q["b"] = node["b"]
                return q
            return {k: rec(v, f"{path}/{k}") for k, v in node.items()}
        return node

    return rec(params, path)


# ---------------------------------------------------------------------------
# activations / losses
# ---------------------------------------------------------------------------


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def swiglu_init(key, d: int, f: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": linear_init(ks[0], d, f, dtype),
        "w_up": linear_init(ks[1], d, f, dtype),
        "w_down": linear_init(ks[2], f, d, dtype),
    }


def swiglu_ffn(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The gated SwiGLU MLP ``W_down(silu(W_gate x) * W_up x)``."""
    return linear(p["w_down"], swiglu(linear(p["w_gate"], x), linear(p["w_up"], x)))


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Mean token cross-entropy; logits over padded vocab are masked."""
    lf = logits.astype(jnp.float32)
    if lf.shape[-1] > vocab:
        pad = lf.shape[-1] - vocab
        lf = lf - jnp.pad(jnp.zeros((vocab,)), (0, pad), constant_values=1e9)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
