"""Plain reference for ``kimi-k2``: one chip's share of Kimi-K2-Instruct's
forward pass in ``jax.numpy``, float32 at the highest matmul precision.

It imports nothing of the program; it reads the configuration file and the
weights the benchmark made from the seed (int8 with a float32 scale per
output channel, as they are served).  After DeepSeek-V3's published
modelling code (arXiv:2412.19437, the layer Kimi-K2 uses), it computes:

* the token embedding, unscaled;
* per layer, RMSNorm and multi-head latent attention in its published
  expanded form: ``q = W_qb · RMSNorm(W_qa · x)``; ``[c, k_pe] = W_kva ·
  x``; per head ``[k_nope, v] = W_kvb · RMSNorm(c)``; the rotary embedding
  with YaRN's frequencies on ``q_pe`` and the shared ``k_pe``, its pairs
  interleaved and rotated as complex numbers; scores ``(q_nope·k_nope +
  q_pe·k_pe) · qk_head_dim^-0.5 · mscale²``, a causal softmax, the readout
  and ``W_o``; a few heads at a time, so that it fits;
* then RMSNorm and the first layer's SwiGLU MLP; in the others the expert
  layer: sigmoid scores of the router, each token's top-8 of the scores
  plus the correction bias, as weights the chosen scores normalised to sum
  1 and times ``routed_scaling_factor``; of the 384 experts the part of the
  8 held here (experts 0-7), each computed on every token and weighted by
  what the token gave it (0 if it did not choose it); plus the shared
  expert;
* a final RMSNorm and the logits of the untied head, over the vocabulary
  slice.

Every linear but ``W_kvb`` as the configuration states it: activations
quantized per row to ``act_bits`` and multiplied exactly in integers with
the int8 weights, then scaled back.  ``W_kvb`` multiplies the unquantized
latent by its dequantized weight.  ``bits`` below 8 is the control: weights
and activations re-quantized to that many bits (int4, the precision below
the stated int8); ``W_kvb``'s weight too.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEADS_AT_A_TIME = 4


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _quant(x, bits: int, axis: int):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax, 1e-8)
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax).astype(jnp.int8), s


def _weight(p, bits: int, stated: int):
    w_q, w_s = p["w_q"], p["w_scale"]
    if bits != stated:  # the control: the served weights cut to ``bits``
        w_q, w_s = _quant(w_q.astype(F32) * w_s, bits, axis=-2)
    return w_q, w_s


def _linear(p, x, bits: int, stated: int):
    w_q, w_s = _weight(p, bits, stated)
    x_q, x_s = _quant(x, bits, axis=-1)
    acc = jax.lax.dot(x_q, w_q, preferred_element_type=jnp.int32)
    return acc.astype(F32) * x_s * w_s


def _swiglu(p, x, lin):
    return lin(p["w_down"], jax.nn.silu(lin(p["w_gate"], x)) * lin(p["w_up"], x))


def _yarn(cfg):
    """Inverse frequencies and the cos/sin factor of DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``, and the softmax scale's mscale."""
    dim, base, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"] / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    def mscale(m):
        return 0.1 * m * math.log(rs["factor"]) + 1.0 if rs["factor"] > 1 else 1.0

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = extra / rs["factor"] * (1 - keep) + extra * keep
    return inv, mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]), mscale(rs["mscale_all_dim"])


def _rope(x, inv, m):
    """x: (L, ..., r), pairs interleaved, rotated as complex numbers."""
    L = x.shape[0]
    ang = jnp.arange(L, dtype=F32)[:, None] * jnp.asarray(inv, F32)  # (L, r/2)
    rot = (jnp.cos(ang) + 1j * jnp.sin(ang)) * m
    rot = rot.reshape((L,) + (1,) * (x.ndim - 2) + (-1,))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    z = z * rot
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape).astype(F32)


def _attention(cfg, p, x, lin, bits, inv, m_rope, m_all):
    L = x.shape[0]
    h, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    eps, c = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    q = lin(p["wq_b"], _rms(lin(p["wq_a"], x), p["q_norm"]["scale"], eps)).reshape(L, h, nope + rope)
    kv = lin(p["wkv_a"], x)
    latent = _rms(kv[:, :c], p["kv_norm"]["scale"], eps)
    k_pe = _rope(kv[:, c:], inv, m_rope)  # (L, rope), shared by every head
    w_q, w_s = _weight(p["wkv_b"], bits, cfg["weight_bits"])
    kvb = (latent @ (w_q.astype(F32) * w_s)).reshape(L, h, nope + vd)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], inv, m_rope)
    scale = (nope + rope) ** -0.5 * m_all * m_all
    causal = jnp.tril(jnp.ones((L, L), bool))

    def heads(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * HEADS_AT_A_TIME, HEADS_AT_A_TIME, axis=1)
        kb = sl(kvb)
        s = (jnp.einsum("qhn,khn->hqk", sl(q_nope), kb[..., :nope])
             + jnp.einsum("qhr,kr->hqk", sl(q_pe), k_pe)) * scale
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", pr, kb[..., nope:])

    o = jax.lax.map(heads, jnp.arange(h // HEADS_AT_A_TIME))  # (blocks, L, heads, v)
    o = jnp.moveaxis(o, 0, 1).reshape(L, h * vd)
    return lin(p["wo"], o)


def _experts(cfg, p, x, lin):
    """The router over every expert; the held experts' part, and the shared expert."""
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first = cfg["deployment"]["first_held_expert"]
    scores = jax.nn.sigmoid(x @ p["router"]["w"])
    _, idx = jax.lax.top_k(scores + p["router"]["bias"], k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]

    def expert(out, e):
        pe = jax.tree_util.tree_map(lambda a: a[e], {n: p[n] for n in ("w_gate", "w_up", "w_down")})
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return out + _swiglu(pe, x, lin) * weight[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    return out + _swiglu(p["shared"], x, lin)


def logits(cfg: Dict[str, Any], params, tokens, bits: int):
    """(L, vocab) float32 logits of one sequence of ``L`` token ids."""
    eps = cfg["rms_norm_eps"]
    lin = partial(_linear, bits=bits, stated=cfg["weight_bits"])
    inv, m_rope, m_all = _yarn(cfg)
    x = params["embed"]["w"][tokens].astype(F32)

    def layer(x, p, moe):
        x = x + _attention(cfg, p["attn"], _rms(x, p["ln1"]["scale"], eps), lin, bits, inv,
                           m_rope, m_all)
        h = _rms(x, p["ln2"]["scale"], eps)
        return x + (_experts(cfg, p["ffn"], h, lin) if moe else _swiglu(p["ffn"], h, lin)), None

    x, _ = jax.lax.scan(partial(layer, moe=False), x, params["dense_blocks"]["00_mla"])
    x, _ = jax.lax.scan(partial(layer, moe=True), x, params["blocks"]["00_mla"])
    out = lin(params["lm_head"], _rms(x, params["final_norm"]["scale"], eps))
    return out[:, : cfg["vocab_size"]]


@partial(jax.jit, static_argnums=(0, 4))
def _gaps(cfg_items, params, tokens, served, bits):
    """Per position: how far the served token's logit lies below the
    reference's best, and how far the token the ``bits`` control ranks first
    lies below it."""
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    with jax.default_matmul_precision("highest"):
        ref = logits(cfg, params, tokens, bits=cfg["weight_bits"])
        best = jnp.max(ref, axis=-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        if bits == cfg["weight_bits"]:
            return gap, gap
        ctl = jnp.argmax(logits(cfg, params, tokens, bits=bits), axis=-1)
        return gap, best - jnp.take_along_axis(ref, ctl[:, None], axis=-1)[:, 0]


def _hashable(cfg: Dict[str, Any]):
    def scalar(v):
        return isinstance(v, (int, float, str, bool))

    return tuple(sorted(
        (k, tuple(sorted((a, b) for a, b in v.items() if scalar(b))) if isinstance(v, dict) else v)
        for k, v in cfg.items() if scalar(v) or k in ("rope_scaling", "deployment")))


def served_gaps(cfg: Dict[str, Any], params, prompt: np.ndarray, served: np.ndarray,
                length: int, control_bits: int = 0):
    """Gaps at each served token of one request: the logit of the token the
    program served below the reference's largest, at the position that
    produced it; and, with ``control_bits``, the same for the token that the
    control ranks first at that position.  The sequence (prompt, then every
    served token but the last) is right-padded to ``length``, one compiled
    shape; causal attention keeps the padding out of every position read."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n, start = len(served), len(prompt) - 1
    tokens = np.zeros(length, np.int32)
    tokens[: len(seq)] = seq
    target = np.zeros(length, np.int32)
    target[start: start + n] = served
    gap, ctl = _gaps(_hashable(cfg), params, jnp.asarray(tokens), jnp.asarray(target),
                     control_bits or cfg["weight_bits"])
    return np.asarray(gap)[start: start + n], np.asarray(ctl)[start: start + n]
