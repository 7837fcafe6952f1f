"""Attention: GQA full/causal, flash-style chunked (online softmax), windowed
local, cross, and single-token decode; and the ``attn`` and ``local_attn``
block kinds built on them (``BLOCK_KINDS``), with the encoder's bidirectional
form and the decoder's cross-attention.

Long sequences never materialize O(S^2) score tensors: ``chunked_attention``
scans KV chunks carrying (max, denom, acc) — the standard online-softmax
recurrence.  With ``triangular=True`` the causal schedule only visits chunks
j ≤ i (halves attention FLOPs vs. the masked-full baseline; this is one of the
§Perf hillclimb levers).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.api import PrecisionSpec
from repro.models.common import BlockKind, Params, apply_rope, dtype_of, linear, linear_init

NEG_INF = -1e30

# Decode-state precision (PIMSAB adaptive precision on the KV cache): the
# int8 preset matches the MXU's native slice width — one plane pair per
# score/readout contraction.  A future RunFlags lever can lower this.
KV_SPEC = PrecisionSpec.int8


def _gqa_fold(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,Hq,d) -> (B,S,Hkv,G,d)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _scaled(scores, d: int, scale: Optional[float]):
    """Scores times the softmax scale: 1/sqrt(d) unless ``scale`` is given."""
    return scores / math.sqrt(d) if scale is None else scores * scale


def _direct_attention(q, k, v, mask, scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,S,Hkv,G,d); k: (B,T,Hkv,d); v: (B,T,Hkv,dv); mask: (S,T) bool or None."""
    d = q.shape[-1]
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k).astype(jnp.float32)
    scores = _scaled(scores, d, scale)
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bthd->bshgd", probs, v)


def _chunk_update(carry, qc, kc, vc, mask, scale: Optional[float] = None):
    """Online-softmax update for one (q-chunk, kv-chunk) pair.

    carry = (m, l, acc): running max (B,H,G,Sq), denom, accumulator.
    """
    m, l, acc = carry
    d = qc.shape[-1]
    s = _scaled(jnp.einsum("bshgd,bthd->bhgst", qc, kc).astype(jnp.float32), d, scale)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgst,bthd->bshgd", p.astype(qc.dtype), vc).astype(jnp.float32)
    acc_new = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
    return m_new, l_new, acc_new


# Remat per chunk-pair: without this, the backward pass keeps every chunk's
# (B,H,G,cq,ck) score/prob residuals alive at once (O(S^2) fp32 again — the
# thing chunking exists to avoid).  Recomputing one chunk matmul in the bwd is
# the standard flash-attention trade.
_chunk_update_nomask = jax.checkpoint(lambda carry, qc, kc, vc: _chunk_update(carry, qc, kc, vc, None))
_chunk_update_masked = jax.checkpoint(_chunk_update)


def _pair_mask(i: int, j: int, chunk: int, causal: bool, window: int):
    """Static (chunk, chunk) mask for q-chunk i vs kv-chunk j, or None if the
    pair is fully allowed.  window > 0 limits lookback to ``window`` tokens."""
    idx = jnp.arange(chunk)
    qpos = i * chunk + idx[:, None]
    kpos = j * chunk + idx[None, :]
    # j == i needs the diagonal mask; j > i (only visited by the masked-full
    # baseline schedule) is fully in the future and the same mask zeroes it
    need_causal = causal and j >= i
    # farthest lookback in this pair: (i - j) * chunk + (chunk - 1)
    need_window = window > 0 and (i - j + 1) * chunk - 1 > window
    if not need_causal and not need_window:
        return None
    mask = jnp.ones((chunk, chunk), bool)
    if need_causal:
        mask &= qpos >= kpos
    if need_window:
        mask &= (qpos - kpos) <= window
    return mask


def chunked_attention(
    q, k, v, *, causal: bool, chunk: int, triangular: bool, window: int = 0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Flash-style (banded) attention.  q: (B,S,Hkv,G,d); k: (B,T,Hkv,d);
    v: (B,T,Hkv,dv).

    Python loop over q-chunks (static), lax.scan over unmasked interior
    kv-chunks.  ``triangular`` skips j > i chunks for causal attention (no
    masked-out FLOPs issued); ``window`` > 0 additionally skips chunks fully
    outside the local-attention band — O(S·W) instead of O(S²).
    """
    b, s, hkv, g, _ = q.shape
    d = v.shape[-1]
    update_nomask, update_masked = _chunk_update_nomask, _chunk_update_masked
    if scale is not None:
        update_masked = jax.checkpoint(partial(_chunk_update, scale=scale))
        update_nomask = jax.checkpoint(
            lambda carry, qc, kc, vc: _chunk_update(carry, qc, kc, vc, None, scale))
    t = k.shape[1]
    assert s % chunk == 0, (s, chunk)
    t_pad = (-t) % chunk
    if t_pad:  # KV not chunk-aligned (e.g. cross-attention into a 1500-frame
        # encoder): pad and mask the tail keys out of the last chunk
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    nq, nk = s // chunk, (t + t_pad) // chunk
    valid_t = t
    k_chunks = k.reshape(b, nk, chunk, hkv, k.shape[-1])
    v_chunks = v.reshape(b, nk, chunk, hkv, d)

    def pair_mask(i, j):
        m = _pair_mask(i, j, chunk, causal, window)
        if t_pad and j == nk - 1:
            colm = jnp.broadcast_to(
                (j * chunk + jnp.arange(chunk))[None, :] < valid_t, (chunk, chunk)
            )
            m = colm if m is None else (m & colm)
        return m
    outs = []
    for i in range(nq):
        qc = q[:, i * chunk : (i + 1) * chunk]
        m = jnp.full((b, hkv, g, chunk), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hkv, g, chunk), jnp.float32)
        acc = jnp.zeros((b, chunk, hkv, g, d), jnp.float32)
        hi = (i + 1) if (causal and triangular) else nk
        lo = 0
        if window > 0:
            lo = max(0, i - (window + chunk - 1) // chunk)
        if causal and triangular:
            masked_js = [j for j in range(lo, hi) if pair_mask(i, j) is not None]
            plain_js = [j for j in range(lo, hi) if j not in masked_js]
            if plain_js:
                # contiguous interior chunks via scan (they share no mask)
                sel_k = jnp.moveaxis(k_chunks[:, plain_js[0] : plain_js[-1] + 1], 1, 0)
                sel_v = jnp.moveaxis(v_chunks[:, plain_js[0] : plain_js[-1] + 1], 1, 0)

                def body(carry, kv):
                    kc, vc = kv
                    return update_nomask(carry, qc, kc, vc), None

                (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), (sel_k, sel_v))
            for j in masked_js:
                m, l, acc = update_masked(
                    (m, l, acc), qc, k_chunks[:, j], v_chunks[:, j], pair_mask(i, j)
                )
        else:
            # masked-full baseline: every kv chunk in [lo, hi) visited,
            # causality/banding purely by masks (extra FLOPs issued)
            for j in range(lo, hi):
                mask = pair_mask(i, j)
                if mask is None:
                    m, l, acc = update_nomask((m, l, acc), qc, k_chunks[:, j], v_chunks[:, j])
                else:
                    m, l, acc = update_masked((m, l, acc), qc, k_chunks[:, j], v_chunks[:, j], mask)
        out = acc / jnp.moveaxis(l, -1, 1)[..., None]
        outs.append(out.astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def full_attention(q, k, v, *, causal: bool, chunk: int, triangular: bool, flash_threshold: int,
                   window: int = 0, scale: Optional[float] = None) -> jnp.ndarray:
    """Entry point.  q: (B,S,Hq,d), k: (B,T,Hkv,d), v: (B,T,Hkv,dv) ->
    (B,S,Hq,dv).  Scores are scaled by ``scale``, 1/sqrt(d) by default."""
    b, s, hq, _ = q.shape
    hkv, d = k.shape[2], v.shape[-1]
    qf = _gqa_fold(q, hkv)
    if s <= flash_threshold and k.shape[1] <= flash_threshold and not window:
        mask = None
        if causal:
            t = k.shape[1]
            mask = (jnp.arange(s)[:, None] + (t - s)) >= jnp.arange(t)[None, :]
        out = _direct_attention(qf, k, v, mask, scale)
    else:
        cw = min(chunk, s)
        out = chunked_attention(
            qf, k, v, causal=causal, chunk=cw, triangular=triangular, window=window, scale=scale
        )
    return out.reshape(b, s, hq, d)


def local_attention(q, k, v, window: int) -> jnp.ndarray:
    """Causal windowed attention: each query sees the previous ``window``
    tokens.  q: (B,S,Hq,d), k/v: (B,S,Hkv,d).  Implemented as chunked
    attention over (previous, self) chunks with chunk == window: O(S·W).
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    w = min(window, s)
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    n = sp // w
    qf = _gqa_fold(q, hkv).reshape(b, n, w, hkv, hq // hkv, d)
    kc = k.reshape(b, n, w, hkv, d)
    vc = v.reshape(b, n, w, hkv, d)
    # keys: previous chunk ++ self chunk
    kprev = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    kk = jnp.concatenate([kprev, kc], axis=2)  # (b,n,2w,hkv,d)
    vv = jnp.concatenate([vprev, vc], axis=2)
    qpos = jnp.arange(w)[:, None] + w  # position within 2w frame
    kpos = jnp.arange(2 * w)[None, :]
    mask = (qpos >= kpos) & (qpos - kpos < w + 1)  # (w, 2w)
    # chunk 0 has no real previous chunk — its first-w frame is zero padding
    is_first = (jnp.arange(n) == 0)[:, None, None]
    mask = mask[None] & ~(is_first & (kpos < w)[None])  # (n, w, 2w)
    # dims: s = w queries, t = 2w keys, h = hkv groups, g = q-per-kv
    scores = jnp.einsum("bnshgd,bnthd->bnhgst", qf, kk).astype(jnp.float32) / math.sqrt(d)
    scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhgst,bnthd->bnshgd", probs, vv)
    out = out.reshape(b, sp, hq, d)
    return out[:, :s]


def _kv_qmax(spec: PrecisionSpec) -> int:
    """The int8 cache stores 8-bit payloads; narrower specs use fewer of
    those bits (adaptive precision), wider ones would silently saturate."""
    if spec.act_bits > 8:
        raise ValueError(
            f"int8 KV cache holds at most 8-bit payloads, got act_bits={spec.act_bits}"
        )
    return 2 ** (spec.act_bits - 1) - 1


def quantize_kv(x: jnp.ndarray, spec: PrecisionSpec = PrecisionSpec.int8):
    """Per-(b, t, h) symmetric integer quantization of a (B,T,H,d) tensor —
    PIMSAB adaptive precision on decode state (``spec.act_bits`` wide)."""
    qmax = _kv_qmax(spec)
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / qmax, 1e-8)  # (B,T,H)
    xq = jnp.clip(jnp.round(xf / s[..., None]), -qmax, qmax).astype(jnp.int8)
    return xq, s


def decode_attention_int8(
    q1, k_q, v_q, k_s, v_s, valid_len=None, spec: PrecisionSpec = PrecisionSpec.int8
) -> jnp.ndarray:
    """Integer decode attention (PIMSAB bit-serial attention on the MXU):
    scores and readout run int8×int8→int32; scales re-applied afterwards.

    q1: (B,1,Hq,d) float; k_q/v_q: (B,T,Hkv,d) int8; k_s/v_s: (B,T,Hkv) f32.
    """
    qmax = _kv_qmax(spec)
    b, _, hq, d = q1.shape
    hkv = k_q.shape[2]
    qf = _gqa_fold(q1, hkv)[:, 0].astype(jnp.float32)  # (B,Hkv,G,d)
    qs = jnp.maximum(jnp.max(jnp.abs(qf), axis=-1) / qmax, 1e-8)  # (B,Hkv,G)
    qq = jnp.clip(jnp.round(qf / qs[..., None]), -qmax, qmax).astype(jnp.int8)
    iscores = jnp.einsum("bhgd,bthd->bhgt", qq, k_q, preferred_element_type=jnp.int32)
    scores = iscores.astype(jnp.float32) * qs[..., None] * jnp.moveaxis(k_s, 1, -1)[:, :, None]
    scores = scores / math.sqrt(d)
    if valid_len is not None:
        t = k_q.shape[1]
        scores = jnp.where(
            jnp.arange(t)[None, None, None] < valid_len[:, None, None, None], scores, NEG_INF
        )
    probs = jax.nn.softmax(scores, axis=-1)
    # fold the per-row v-scale into the probabilities (both per (b,t,h)),
    # then one int8-payload contraction — no bf16 cache materialization
    pw = probs * jnp.moveaxis(v_s, 1, -1)[:, :, None]  # (B,Hkv,G,T)
    out = jnp.einsum("bhgt,bthd->bhgd", pw, v_q.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q1.dtype)


def decode_attention(q1, k_cache, v_cache, valid_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One-token decode: q1 (B,1,Hq,d) vs cache (B,T,Hkv,d)."""
    b, _, hq, d = q1.shape
    hkv = k_cache.shape[2]
    qf = _gqa_fold(q1, hkv)[:, 0]  # (B,Hkv,G,d)
    scores = jnp.einsum("bhgd,bthd->bhgt", qf, k_cache).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    if valid_len is not None:
        t = k_cache.shape[1]
        scores = jnp.where(jnp.arange(t)[None, None, None] < valid_len[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q1.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, v_cache)
    return out.reshape(b, 1, hq, d)


# ---------------------------------------------------------------------------
# the attention block kinds: global (``attn``) and windowed (``local_attn``,
# whose decode cache is a ring buffer of ``cfg.window`` positions)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": linear_init(ks[0], cfg.d_model, cfg.q_dim, dtype, bias=cfg.qkv_bias),
        "wk": linear_init(ks[1], cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wv": linear_init(ks[2], cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wo": linear_init(ks[3], cfg.q_dim, cfg.d_model, dtype),
    }


def _qkv(p: Params, x: jnp.ndarray, cfg):
    """x (B, S, D) -> per-head q (B,S,Hq,hd), k and v (B,S,Hkv,hd), unrotated."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    return (linear(p["wq"], x).reshape(b, s, cfg.n_heads, hd),
            linear(p["wk"], x).reshape(b, s, cfg.n_kv_heads, hd),
            linear(p["wv"], x).reshape(b, s, cfg.n_kv_heads, hd))


def _attn_seq(p: Params, x: jnp.ndarray, cfg, flags, positions: jnp.ndarray,
              window: bool = False, causal: bool = True):
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q, k = apply_rope(q, positions, cfg.rope_theta), apply_rope(k, positions, cfg.rope_theta)
    if not window:
        out = full_attention(q, k, v, causal=causal, chunk=flags.attn_chunk,
                             triangular=flags.triangular_attn,
                             flash_threshold=flags.flash_threshold)
    elif s <= 2 * cfg.window and s <= flags.flash_threshold:
        out = local_attention(q, k, v, cfg.window)  # small-S direct band
    else:
        out = full_attention(q, k, v, causal=causal, chunk=min(flags.attn_chunk, cfg.window),
                             triangular=flags.triangular_attn,
                             flash_threshold=0,  # always banded-chunked
                             window=cfg.window)
    return linear(p["wo"], out.reshape(b, s, cfg.q_dim)), {"k": k, "v": v}


def _kv_len(cfg, max_len: int, window: bool) -> int:
    return min(cfg.window, max_len) if window else max_len


def _kv_cache(cfg, batch: int, max_len: int, flags, window: bool = False) -> Params:
    shp = (batch, _kv_len(cfg, max_len, window), cfg.n_kv_heads, cfg.resolved_head_dim)
    if flags.quant_kv:
        # PIMSAB adaptive precision on state: int8 payload + per-(b,t,h)
        # scales; scores/readout run on the integer path (bit-serial attn)
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_scale": jnp.zeros(shp[:3], jnp.float32),
                "v_scale": jnp.zeros(shp[:3], jnp.float32)}
    return {"k": jnp.zeros(shp, dtype_of(cfg)), "v": jnp.zeros(shp, dtype_of(cfg))}


def _kv_to_decode(entries: Params, cfg, s: int, max_len: int, flags,
                  window: bool = False) -> Params:
    """The last ``w`` positions' keys and values (B, w, Hkv, hd), or all of
    them padded to ``w``; int8 with scales under ``flags.quant_kv``."""
    w = _kv_len(cfg, max_len, window)

    def fit(kv):
        return kv[:, s - w:s] if s >= w else jnp.pad(kv, ((0, 0), (0, w - s), (0, 0), (0, 0)))

    out = {"k": fit(entries["k"]), "v": fit(entries["v"])}
    if flags.quant_kv:
        for n in ("k", "v"):
            out[n], out[f"{n}_scale"] = quantize_kv(out[n], KV_SPEC)
    return out


def kv_write(entry: Params, k: jnp.ndarray, v: jnp.ndarray, slot) -> Params:
    """The decode step's cache write: the new token's keys and values (B, 1,
    Hkv, hd) at ``slot``, quantized first in an int8 cache."""
    with jax.named_scope("kv_write"):
        rows = {"k": k, "v": v}
        if "k_scale" in entry:  # int8 KV cache (PIMSAB adaptive precision)
            (k, k_scale), (v, v_scale) = quantize_kv(k, KV_SPEC), quantize_kv(v, KV_SPEC)
            rows = {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale}
        return {**entry, **{n: jax.lax.dynamic_update_slice_in_dim(entry[n], row, slot, axis=1)
                            for n, row in rows.items()}}


def _attn_decode(p: Params, h: jnp.ndarray, cfg, entry: Params, pos, window: bool = False):
    b = h.shape[0]
    q, k, v = _qkv(p, h, cfg)
    posb = jnp.full((b, 1), pos, jnp.int32)
    q, k = apply_rope(q, posb, cfg.rope_theta), apply_rope(k, posb, cfg.rope_theta)
    if window:
        # ring buffer: all slots < valid are live (order irrelevant w/ RoPE
        # applied at insert time)
        w = entry["k"].shape[1]
        slot, valid = pos % w, jnp.minimum(pos + 1, w) * jnp.ones((b,), jnp.int32)
    else:
        slot, valid = pos, (pos + 1) * jnp.ones((b,), jnp.int32)
    new = kv_write(entry, k, v, slot)
    if "k_scale" in new:
        out = decode_attention_int8(q, new["k"], new["v"], new["k_scale"], new["v_scale"],
                                    valid, KV_SPEC)
    else:
        out = decode_attention(q, new["k"], new["v"], valid)
    return linear(p["wo"], out.reshape(b, 1, cfg.q_dim)), new


def _attention_kind(window: bool) -> BlockKind:
    return BlockKind(param="attn", scope="attn", ffn=True, init=attn_init,
                     seq=partial(_attn_seq, window=window),
                     cache=partial(_kv_cache, window=window),
                     to_decode=partial(_kv_to_decode, window=window),
                     decode=partial(_attn_decode, window=window))


BLOCK_KINDS = {"attn": _attention_kind(False), "local_attn": _attention_kind(True)}

# an encoder's blocks: global attention without the causal mask
BIDIRECTIONAL = BLOCK_KINDS["attn"]._replace(seq=partial(_attn_seq, causal=False))


def cross_kv(p: Params, enc_out: jnp.ndarray, cfg) -> Params:
    """The encoder output's keys and values (B, T, Hkv, hd) for one block."""
    b, t, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    return {"k": linear(p["wk"], enc_out).reshape(b, t, cfg.n_kv_heads, hd),
            "v": linear(p["wv"], enc_out).reshape(b, t, cfg.n_kv_heads, hd)}


def cross_attention(p: Params, x: jnp.ndarray, kv: Params, cfg) -> jnp.ndarray:
    """x (B, S, D), a sequence or a decode step, attending to ``kv``."""
    b, s, _ = x.shape
    q = linear(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.resolved_head_dim)
    out = full_attention(q, kv["k"], kv["v"], causal=False, chunk=2048, triangular=False,
                         flash_threshold=8192)
    return linear(p["wo"], out.reshape(b, s, cfg.q_dim))
