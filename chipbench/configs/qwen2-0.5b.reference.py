"""Plain reference for ``qwen2-0.5b``: the decoder's full forward pass in
``jax.numpy``, float32 at the highest matmul precision.

It imports nothing of the program; it reads the configuration file and the
weights the benchmark made from the seed (int8 with a float32 scale per
output channel, as they are served).  What it computes:

* token embedding times sqrt(hidden_size) -- a departure from Qwen2, which
  does not scale it, kept because the program under test scales it
  (``PERF.md`` lists it as an open question);
* 24 blocks of RMSNorm, grouped-query attention with q/k/v biases and
  rotary embedding (theta 1e6, halves rotated), a causal softmax, and a
  SwiGLU MLP, each with its residual;
* every linear as the configuration states it: activations quantized per
  row to ``act_bits`` and multiplied exactly in integers with the int8
  weights, then scaled back;
* a final RMSNorm and logits against the tied embedding.

``bits`` below 8 is the control: weights and activations re-quantized to
that many bits (int4, the precision below the stated int8).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _quant(x, bits: int, axis: int):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax, 1e-8)
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax).astype(jnp.int8), s


def _linear(p, x, bits: int, stated: int):
    w_q, w_s = p["w_q"], p["w_scale"]
    if bits != stated:  # the control: the served weights cut to ``bits``
        w_q, w_s = _quant(w_q.astype(F32) * w_s, bits, axis=-2)
    x_q, x_s = _quant(x, bits, axis=-1)
    acc = jax.lax.dot(x_q, w_q, preferred_element_type=jnp.int32)
    out = acc.astype(F32) * x_s * w_s
    return out + p["b"].astype(F32) if "b" in p else out


def _rope(x, theta: float):
    """x: (L, H, hd); rotates the two halves of each head."""
    L, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(L, dtype=F32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def logits(cfg: Dict[str, Any], params, tokens, bits: int):
    """(L, vocab) float32 logits of one sequence of ``L`` token ids."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, stated = d // h, cfg["rms_norm_eps"], cfg["weight_bits"]
    lin = partial(_linear, bits=bits, stated=stated)
    L = tokens.shape[0]
    emb = params["embed"]["w"][: cfg["vocab_size"]].astype(F32)
    x = emb[tokens] * math.sqrt(d)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def layer(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(lin(p["attn"]["wq"], a).reshape(L, h, hd), cfg["rope_theta"])
        k = _rope(lin(p["attn"]["wk"], a).reshape(L, kv, hd), cfg["rope_theta"])
        v = lin(p["attn"]["wv"], a).reshape(L, kv, hd)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v).reshape(L, h * hd)
        x = x + lin(p["attn"]["wo"], o)
        m = _rms(x, p["ln2"]["scale"], eps)
        f = p["ffn"]
        x = x + lin(f["w_down"], jax.nn.silu(lin(f["w_gate"], m)) * lin(f["w_up"], m))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["00_attn"])
    return _rms(x, params["final_norm"]["scale"], eps) @ emb.T


@partial(jax.jit, static_argnums=(0, 4))
def _gaps(cfg_items, params, tokens, served, bits):
    """Per position: how far the served token's logit lies below the
    reference's best, and how far the token the ``bits`` control ranks first
    lies below it."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        ref = logits(cfg, params, tokens, bits=cfg["weight_bits"])
        best = jnp.max(ref, axis=-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        if bits == cfg["weight_bits"]:
            return gap, gap
        ctl = jnp.argmax(logits(cfg, params, tokens, bits=bits), axis=-1)
        return gap, best - jnp.take_along_axis(ref, ctl[:, None], axis=-1)[:, 0]


def _hashable(cfg: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))


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
