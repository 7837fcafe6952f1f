"""Multi-head latent attention (DeepSeek-V2/V3, Kimi-K2), after DeepSeek-V3's
published modelling code:

* queries ``q = W_qb · RMSNorm(W_qa · x)``, per head ``qk_nope_head_dim``
  plain dims and ``qk_rope_head_dim`` rotary ones;
* ``[c_kv, k_pe] = W_kva · x``; the cache holds ``RMSNorm(c_kv)`` (the
  latent) and the rotated ``k_pe``, one of each per position, shared by every
  head;
* per head ``[k_nope, v] = W_kvb · c_kv``; scores ``(q_nope·k_nope +
  q_pe·k_pe) · qk_head_dim^-0.5 · mscale²`` (YaRN's temperature).

Two forms of the same arithmetic.  The sequence form (prefill, training)
expands the latent through ``W_kvb`` into per-head keys and values, under the
name scope ``mla_expand``.  The decode form absorbs ``W_kvb`` into the query
and the output (``mla_absorb``): it attends over the latent cache directly
and never expands it.  ``W_kvb`` multiplies the latent unquantized (its
weight may be stored int8 with scales), so both forms agree up to rounding.
``BLOCK_KINDS`` holds the ``mla`` block kind built on them.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import NEG_INF, full_attention
from repro.models.common import (
    BlockKind,
    Params,
    dequantized,
    dtype_of,
    linear,
    linear_init,
    rmsnorm,
    rmsnorm_init,
    rope_freqs,
    yarn_freqs,
    yarn_mscale,
)


def mla_init(key, cfg, dtype) -> Params:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "wq_a": linear_init(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "wq_b": linear_init(ks[1], m.q_lora_rank, h * m.qk_head_dim, dtype),
        "wkv_a": linear_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wkv_b": linear_init(ks[3], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": linear_init(ks[4], h * m.v_head_dim, d, dtype),
    }


def cache_entry(cfg, batch: int, max_len: int, dtype) -> Dict[str, jnp.ndarray]:
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


def _empty_entry(cfg, batch: int, max_len: int, flags) -> Dict[str, jnp.ndarray]:
    if flags.quant_kv:
        raise ValueError("latent attention keeps a bfloat16 latent cache (quant_kv unsupported)")
    return cache_entry(cfg, batch, max_len, dtype_of(cfg))


def _to_decode(entries: Params, cfg, s: int, max_len: int, flags) -> Dict[str, jnp.ndarray]:
    """The prompt's latents and rotary keys, padded to ``max_len`` positions."""
    return {n: jnp.pad(entries[n], ((0, 0), (0, max_len - s), (0, 0))) for n in ("c_kv", "k_pe")}


def softmax_scale(cfg) -> float:
    scale = cfg.mla.qk_head_dim ** -0.5
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    return scale


def rope(x: jnp.ndarray, positions: jnp.ndarray, cfg) -> jnp.ndarray:
    """Rotary embedding of x (B, S, H, r), rotating the interleaved pairs
    (x0, x1), (x2, x3), ... as the published code does: it de-interleaves
    them into halves and rotates the halves (the output keeps that order)."""
    r, y = x.shape[-1], cfg.yarn
    freqs = rope_freqs(r, cfg.rope_theta) if y is None else yarn_freqs(r, cfg.rope_theta, y)
    mscale = 1.0 if y is None else yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _project(p: Params, x: jnp.ndarray, cfg, positions: jnp.ndarray):
    """x (B, S, D) -> q_nope (B,S,H,n), rotated q_pe (B,S,H,r), the latent
    (B,S,c) and the rotated shared key k_pe (B,S,r)."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q = linear(p["wq_b"], rmsnorm(p["q_norm"], linear(p["wq_a"], x), cfg.norm_eps))
    q = q.reshape(b, s, h, m.qk_head_dim)
    kv = linear(p["wkv_a"], x)
    c_kv = rmsnorm(p["kv_norm"], kv[..., : m.kv_lora_rank], cfg.norm_eps)
    k_pe = rope(kv[..., None, m.kv_lora_rank:], positions, cfg)[:, :, 0]
    n = m.qk_nope_head_dim
    return q[..., :n], rope(q[..., n:], positions, cfg), c_kv, k_pe


def _kv_b(p: Params, cfg, dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """W_kvb as (latent, heads, dims): its key part and its value part."""
    m = cfg.mla
    w = dequantized(p["wkv_b"], dtype).reshape(m.kv_lora_rank, cfg.n_heads, -1)
    return w[..., : m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]


def mla_attention(p: Params, x: jnp.ndarray, cfg, flags, positions: jnp.ndarray):
    """Sequence form, causal.  Returns (y, cache entries of every position)."""
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_pe, c_kv, k_pe = _project(p, x, cfg, positions)
    with jax.named_scope("mla_expand"):
        wk, wv = _kv_b(p, cfg, c_kv.dtype)
        k_nope = jnp.einsum("bsc,chn->bshn", c_kv, wk)
        v = jnp.einsum("bsc,chm->bshm", c_kv, wv)
        k_pe_h = jnp.broadcast_to(k_pe[:, :, None], (b, s, h, k_pe.shape[-1]))
        out = full_attention(
            jnp.concatenate([q_nope, q_pe], axis=-1),
            jnp.concatenate([k_nope, k_pe_h], axis=-1),
            v,
            causal=True,
            chunk=flags.attn_chunk,
            triangular=flags.triangular_attn,
            flash_threshold=flags.flash_threshold,
            scale=softmax_scale(cfg),
        )
    y = linear(p["wo"], out.reshape(b, s, h * cfg.mla.v_head_dim))
    return y, {"c_kv": c_kv, "k_pe": k_pe}


def mla_decode(p: Params, x: jnp.ndarray, cfg, entry: Params, pos) -> Tuple[jnp.ndarray, Params]:
    """Decode form: x (B, 1, D) at position ``pos``; writes the latent and the
    rotary key at ``pos`` (``kv_write``) and attends over the cache through
    the absorbed ``W_kvb``.  Returns (y, new cache entry)."""
    b = x.shape[0]
    posb = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_pe, c_kv, k_pe = _project(p, x, cfg, posb)
    new = dict(entry)
    with jax.named_scope("kv_write"):
        new["c_kv"] = jax.lax.dynamic_update_slice_in_dim(entry["c_kv"], c_kv, pos, axis=1)
        new["k_pe"] = jax.lax.dynamic_update_slice_in_dim(entry["k_pe"], k_pe, pos, axis=1)
    cache_dt = new["c_kv"].dtype
    with jax.named_scope("mla_absorb"):
        wk, wv = _kv_b(p, cfg, jnp.float32)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(jnp.float32), wk)
        scores = jnp.einsum("bhc,btc->bht", q_lat.astype(cache_dt), new["c_kv"],
                            preferred_element_type=jnp.float32)
        scores += jnp.einsum("bhr,btr->bht", q_pe[:, 0].astype(cache_dt), new["k_pe"],
                             preferred_element_type=jnp.float32)
        scores = scores * softmax_scale(cfg)
        live = jnp.arange(scores.shape[-1]) <= pos
        probs = jax.nn.softmax(jnp.where(live, scores, NEG_INF), axis=-1)
        o_lat = jnp.einsum("bht,btc->bhc", probs.astype(cache_dt), new["c_kv"],
                           preferred_element_type=jnp.float32)
        out = jnp.einsum("bhc,chm->bhm", o_lat, wv)
    y = linear(p["wo"], out.reshape(b, 1, -1).astype(x.dtype))
    return y, new


BLOCK_KINDS = {"mla": BlockKind(param="attn", scope="attn", ffn=True, init=mla_init,
                                seq=mla_attention, cache=_empty_entry, to_decode=_to_decode,
                                decode=mla_decode)}
