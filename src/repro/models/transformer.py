"""Composable transformer: one model assembly covering all 10 assigned
architectures (dense GQA, MoE, RG-LRU hybrid, xLSTM, enc-dec audio, VLM).

The layer stack is the config's ``block_pattern`` tiled to ``n_layers`` and
executed as ``lax.scan`` over *pattern groups* (params stacked on a leading
group axis) so the HLO stays depth-independent.  Three entry points:

* ``forward``     — full-sequence logits (training / evaluation).
* ``prefill``     — full-sequence forward that also returns the decode cache.
* ``decode_step`` — one token in, one token out, cache updated in place.

Each block's attention and feed-forward run under the ``jax.named_scope``
``attn`` and ``ffn``, the logits under ``lm_head``, and the decode step's
cache update under ``attn/kv_write``: a profiler trace's device ops carry
these names, so a step's device time splits by part (ops outside them, such
as norms and the scan's stacking of the cache, carry none).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.api import PrecisionSpec
from repro.models import frontend
from repro.models.attention import (
    decode_attention,
    full_attention,
    local_attention,
)
from repro.models.common import (
    Params,
    apply_rope,
    dense_init,
    dtype_of,
    linear,
    linear_init,
    rmsnorm,
    rmsnorm_init,
    softmax_cross_entropy,
    swiglu,
)
from repro.models.moe import moe_ffn, moe_init
from repro.models.recurrent import (
    CONV_K,
    mlstm_block_apply,
    mlstm_full_state_init,
    rglru_block_apply,
    rglru_state_init,
    slstm_block_apply,
    slstm_state_init,
)
from repro.models.runtime import DEFAULT_FLAGS, RunFlags
from repro.dist.sharding import MeshRules, act_spec, cache_entry_spec, constrain

# Decode-state precision (PIMSAB adaptive precision on the KV cache): the
# int8 preset matches the MXU's native slice width — one plane pair per
# score/readout contraction.  A future RunFlags lever can lower this.
KV_SPEC = PrecisionSpec.int8

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg, dtype, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], d, cfg.q_dim, dtype, bias=cfg.qkv_bias),
        "wk": linear_init(ks[1], d, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wv": linear_init(ks[2], d, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wo": linear_init(ks[3], cfg.q_dim, d, dtype),
    }
    return p


def _ffn_init(key, cfg, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": linear_init(ks[0], d, f, dtype),
        "w_up": linear_init(ks[1], d, f, dtype),
        "w_down": linear_init(ks[2], f, d, dtype),
    }


def _block_init(key, cfg, kind: str, dtype, decoder: bool) -> Params:
    """One block = norm + temporal mixer (+ cross-attn) (+ norm + FFN)."""
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if kind in ("attn", "local_attn"):
        p["attn"] = _attn_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        from repro.models.recurrent import rglru_block_init

        p["mixer"] = rglru_block_init(ks[0], cfg, dtype)
    elif kind == "mlstm":
        from repro.models.recurrent import mlstm_block_init

        p["mixer"] = mlstm_block_init(ks[0], cfg, dtype)
    elif kind == "slstm":
        from repro.models.recurrent import slstm_block_init

        p["mixer"] = slstm_block_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if decoder and cfg.is_encdec:
        p["lnx"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = _attn_init(ks[1], cfg, dtype)
    if cfg.d_ff > 0 and kind in ("attn", "local_attn", "rglru"):
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = moe_init(ks[2], cfg, dtype) if cfg.is_moe else _ffn_init(ks[2], cfg, dtype)
    return p


def _stack_groups(key, cfg, dtype, n_groups: int, pattern, decoder: bool) -> Params:
    """Init per group then stack leaves on a leading (G, ...) axis."""
    gkeys = jax.random.split(key, n_groups)

    def one_group(k):
        pk = jax.random.split(k, len(pattern))
        return {
            f"{i:02d}_{kind}": _block_init(pk[i], cfg, kind, dtype, decoder)
            for i, kind in enumerate(pattern)
        }

    groups = [one_group(k) for k in gkeys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *groups)


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = dtype_of(cfg)
    ks = jax.random.split(key, 8)
    vp = cfg.padded_vocab()
    params: Params = {
        "embed": {"w": dense_init(ks[0], vp, cfg.d_model, dtype, scale=0.02)},
        "blocks": _stack_groups(
            ks[1], cfg, dtype, cfg.pattern_groups(), cfg.block_pattern, decoder=True
        ),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(ks[2], cfg.d_model, vp, dtype, scale=0.02)}
    if cfg.is_encdec:
        params["enc_blocks"] = _stack_groups(
            ks[3], cfg, dtype, cfg.n_enc_layers, ("attn",), decoder=False
        )
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
        params["audio_adapter"] = frontend.audio_adapter_init(ks[4], cfg, dtype)
    if cfg.frontend == "vision":
        params["vision_adapter"] = frontend.vision_adapter_init(ks[5], cfg, dtype)
    return params


def params_shape(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct tree, no allocation (for the dry-run)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


def param_bytes(tree) -> int:
    return sum(
        int(np_prod(l.shape)) * l.dtype.itemsize for l in jax.tree_util.tree_leaves(tree)
    )


def np_prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


# ---------------------------------------------------------------------------
# block application (sequence form)
# ---------------------------------------------------------------------------


def _attn_apply(
    p: Params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    flags: RunFlags,
    positions: jnp.ndarray,
    kind: str,
    causal: bool,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = linear(p["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear(p["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if kind == "local_attn":
        if s <= 2 * cfg.window and s <= flags.flash_threshold:
            out = local_attention(q, k, v, cfg.window)  # small-S direct band
        else:
            out = full_attention(
                q, k, v,
                causal=causal,
                chunk=min(flags.attn_chunk, cfg.window),
                triangular=flags.triangular_attn,
                flash_threshold=0,  # always banded-chunked
                window=cfg.window,
            )
    else:
        out = full_attention(
            q,
            k,
            v,
            causal=causal,
            chunk=flags.attn_chunk,
            triangular=flags.triangular_attn,
            flash_threshold=flags.flash_threshold,
        )
    y = linear(p["wo"], out.reshape(b, s, cfg.q_dim))
    return y, {"k": k, "v": v}


def _cross_apply(p: Params, x: jnp.ndarray, enc_kv: Dict[str, jnp.ndarray], cfg) -> jnp.ndarray:
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
    out = full_attention(
        q, enc_kv["k"], enc_kv["v"], causal=False, chunk=2048, triangular=False, flash_threshold=8192
    )
    return linear(p["wo"], out.reshape(b, s, cfg.q_dim))


def _cross_kv(p: Params, enc_out: jnp.ndarray, cfg) -> Dict[str, jnp.ndarray]:
    b, t, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    return {
        "k": linear(p["wk"], enc_out).reshape(b, t, cfg.n_kv_heads, hd),
        "v": linear(p["wv"], enc_out).reshape(b, t, cfg.n_kv_heads, hd),
    }


def _ffn_apply(p: Params, x: jnp.ndarray, cfg, flags: RunFlags, rules) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.is_moe:
        groups = flags.routing_groups or (rules.dp if rules is not None else 1)
        tokens = x.shape[0] * x.shape[1]
        while tokens % groups:
            groups -= 1
        return moe_ffn(p, x, cfg, groups)
    return linear(p["w_down"], swiglu(linear(p["w_gate"], x), linear(p["w_up"], x))), jnp.float32(0)


def _block_apply_seq(
    p: Params,
    x: jnp.ndarray,
    kind: str,
    cfg: ModelConfig,
    flags: RunFlags,
    rules: Optional[MeshRules],
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray],
    causal: bool,
    states: Optional[Params] = None,
) -> Tuple[jnp.ndarray, Params, jnp.ndarray]:
    """Returns (x_out, new_cache_entries, aux_loss)."""
    aux = jnp.float32(0)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    cache_out: Params = {}
    if kind in ("attn", "local_attn"):
        with jax.named_scope("attn"):
            y, kv = _attn_apply(p["attn"], h, cfg, flags, positions, kind, causal)
        cache_out.update(kv)
    elif kind == "rglru":
        y, st = rglru_block_apply(p["mixer"], h, cfg, states)
        cache_out.update(st)
    elif kind == "mlstm":
        y, st = mlstm_block_apply(p["mixer"], h, cfg, states, chunk=flags.attn_chunk if flags.attn_chunk <= 256 else 256)
        cache_out.update(st)
    elif kind == "slstm":
        y, st = slstm_block_apply(p["mixer"], h, cfg, states)
        cache_out.update(st)
    x = x + y
    if "cross" in p and enc_out is not None:
        hx = rmsnorm(p["lnx"], x, cfg.norm_eps)
        kvx = _cross_kv(p["cross"], enc_out, cfg)
        x = x + _cross_apply(p["cross"], hx, kvx, cfg)
        cache_out["cross_k"], cache_out["cross_v"] = kvx["k"], kvx["v"]
    if "ffn" in p:
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        with jax.named_scope("ffn"):
            y2, a = _ffn_apply(p["ffn"], h2, cfg, flags, rules)
        x = x + y2
        aux = aux + a
    return x, cache_out, aux


# ---------------------------------------------------------------------------
# forward (train / no-cache evaluation)
# ---------------------------------------------------------------------------


def _embed_tokens(params: Params, tokens: jnp.ndarray, cfg) -> jnp.ndarray:
    x = params["embed"]["w"][tokens]
    return x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)


def _run_encoder(params: Params, cfg, flags, rules, frame_embeds: jnp.ndarray) -> jnp.ndarray:
    x = frontend.embed_frames(params["audio_adapter"], frame_embeds.astype(dtype_of(cfg)))
    t = x.shape[1]
    positions = jnp.arange(t)[None]

    def body(carry, gp):
        h, _, _ = _block_apply_seq(
            gp["00_attn"], carry, "attn", cfg, flags, rules, positions, None, causal=False
        )
        return h, None

    if flags.scan_layers:
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    else:
        for gi in range(cfg.n_enc_layers):
            gp = jax.tree_util.tree_map(lambda l: l[gi], params["enc_blocks"])
            x, _ = body(x, gp)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    flags: RunFlags = DEFAULT_FLAGS,
    rules: Optional[MeshRules] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence logits.  Returns (logits, aux_loss)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        x = frontend.fuse_patches(params["vision_adapter"], x, batch["patch_embeds"])
    x = constrain(x, rules, act_spec(b, rules) if rules else None)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(params, cfg, flags, rules, batch["enc_embeds"])
    positions = jnp.arange(s)[None]
    pattern = cfg.block_pattern

    def one_block(pb, xx, pos_arg, enc_arg, kind):
        out, _, a = _block_apply_seq(
            pb, xx, kind, cfg, flags, rules, pos_arg, enc_arg, causal=True
        )
        return out, a

    # Remat per *block* (not per pattern group): a group can be 13 layers
    # (recurrentgemma) and rematerializing it whole keeps every layer's
    # intermediates live in the backward at once.
    blocked = {
        kind: (jax.checkpoint(partial(one_block, kind=kind)) if flags.remat else partial(one_block, kind=kind))
        for kind in set(pattern)
    }

    def group_body(carry, gp):
        x, aux = carry
        for i, kind in enumerate(pattern):
            x, a = blocked[kind](gp[f"{i:02d}_{kind}"], x, positions, enc_out)
            aux = aux + a
        x = constrain(x, rules, act_spec(b, rules) if rules else None)
        return (x, aux), None

    if flags.scan_layers:
        (x, aux), _ = jax.lax.scan(group_body, (x, jnp.float32(0)), params["blocks"])
    else:
        carry = (x, jnp.float32(0))
        g = cfg.pattern_groups()
        for gi in range(g):
            gp = jax.tree_util.tree_map(lambda l: l[gi], params["blocks"])
            carry, _ = group_body(carry, gp)
        x, aux = carry
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    return logits, aux


def _lm_head(params: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return x @ params["embed"]["w"].T
        return linear(params["lm_head"], x)  # handles the int8 bit-sliced head


def loss_fn(params, cfg, batch, flags=DEFAULT_FLAGS, rules=None):
    logits, aux = forward(params, cfg, batch, flags, rules)
    ce = softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------


def _cache_entry_shape(cfg, kind: str, batch: int, max_len: int, flags=DEFAULT_FLAGS) -> Dict[str, Any]:
    hd, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    dt = dtype_of(cfg)

    def kv_entry(length):
        shp = (batch, length, hkv, hd)
        if flags.quant_kv:
            # PIMSAB adaptive precision on state: int8 payload + per-(b,t,h)
            # scales; scores/readout run on the integer path (bit-serial attn)
            return {
                "k": jnp.zeros(shp, jnp.int8),
                "v": jnp.zeros(shp, jnp.int8),
                "k_scale": jnp.zeros((batch, length, hkv), jnp.float32),
                "v_scale": jnp.zeros((batch, length, hkv), jnp.float32),
            }
        return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}

    if kind == "attn":
        entry = kv_entry(max_len)
    elif kind == "local_attn":
        entry = kv_entry(min(cfg.window, max_len))
    elif kind == "rglru":
        entry = dict(rglru_state_init(cfg, batch))
    elif kind == "mlstm":
        entry = dict(mlstm_full_state_init(cfg, batch))
    elif kind == "slstm":
        entry = dict(slstm_state_init(cfg, batch))
    else:
        raise ValueError(kind)
    if cfg.is_encdec and kind == "attn":
        xshp = (batch, cfg.enc_seq_len, hkv, hd)
        entry["cross_k"] = jnp.zeros(xshp, dt)
        entry["cross_v"] = jnp.zeros(xshp, dt)
    return entry


def init_cache(cfg: ModelConfig, batch: int, max_len: int, flags: RunFlags = DEFAULT_FLAGS) -> Params:
    """Decode cache: stacked (G, ...) per pattern position + position scalar."""

    def stacked(kind):
        g = cfg.pattern_groups()
        entry = _cache_entry_shape(cfg, kind, batch, max_len, flags)
        return jax.tree_util.tree_map(lambda l: jnp.broadcast_to(l, (g,) + l.shape), entry)

    return {
        "pos": jnp.zeros((), jnp.int32),
        "blocks": {
            f"{i:02d}_{kind}": stacked(kind) for i, kind in enumerate(cfg.block_pattern)
        },
    }


def cache_shape(cfg: ModelConfig, batch: int, max_len: int, flags: RunFlags = DEFAULT_FLAGS) -> Params:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, flags))


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    flags: RunFlags = DEFAULT_FLAGS,
    rules: Optional[MeshRules] = None,
    max_len: Optional[int] = None,
) -> Tuple[Params, jnp.ndarray]:
    """Run the prompt, return (cache, last-token logits)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_len = max_len or s
    x = _embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        x = frontend.fuse_patches(params["vision_adapter"], x, batch["patch_embeds"])
    x = constrain(x, rules, act_spec(b, rules) if rules else None)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(params, cfg, flags, rules, batch["enc_embeds"])
    positions = jnp.arange(s)[None]
    pattern = cfg.block_pattern

    def group_body(x, gp):
        entries = {}
        for i, kind in enumerate(pattern):
            x, cache_new, _ = _block_apply_seq(
                gp[f"{i:02d}_{kind}"], x, kind, cfg, flags, rules, positions, enc_out, causal=True
            )
            entries[f"{i:02d}_{kind}"] = _seq_cache_to_decode_cache(
                cache_new, kind, cfg, s, max_len, flags
            )
        x = constrain(x, rules, act_spec(b, rules) if rules else None)
        return x, entries

    if flags.scan_layers:
        x, stacked_entries = jax.lax.scan(group_body, x, params["blocks"])
    else:  # unrolled (cost-analysis correction path / perf experiments)
        entries_list = []
        for gi in range(cfg.pattern_groups()):
            gp = jax.tree_util.tree_map(lambda l: l[gi], params["blocks"])
            x, e = group_body(x, gp)
            entries_list.append(e)
        stacked_entries = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *entries_list)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x[:, -1:], cfg)[:, 0]
    cache = {"pos": jnp.asarray(s, jnp.int32), "blocks": stacked_entries}
    return cache, logits


def _seq_cache_to_decode_cache(
    entries: Params, kind: str, cfg, s: int, max_len: int, flags: RunFlags = DEFAULT_FLAGS
) -> Params:
    """Convert full-sequence block outputs into decode-cache layout."""
    from repro.models.attention import quantize_kv

    def finish(kv_dict):
        if not flags.quant_kv:
            return kv_dict
        out = {}
        for n in ("k", "v"):
            q, sc = quantize_kv(kv_dict[n], KV_SPEC)
            out[n], out[f"{n}_scale"] = q, sc
        for n in ("cross_k", "cross_v"):
            if n in kv_dict:
                out[n] = kv_dict[n]
        return out

    if kind == "attn":
        out = {}
        for n in ("k", "v"):
            kv = entries[n]  # (B,S,Hkv,hd)
            pad = max_len - s
            if pad > 0:
                kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))
            out[n] = kv
        for n in ("cross_k", "cross_v"):
            if n in entries:
                out[n] = entries[n]
        return finish(out)
    if kind == "local_attn":
        w = min(cfg.window, max_len)
        out = {}
        for n in ("k", "v"):
            kv = entries[n]
            if s >= w:
                out[n] = kv[:, s - w : s]
            else:
                out[n] = jnp.pad(kv, ((0, 0), (0, w - s), (0, 0), (0, 0)))
        return finish(out)
    # recurrent kinds: states pass through
    return dict(entries)


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def _attn_decode(p, h, cfg, entry, pos, kind, rules):
    from repro.models.attention import decode_attention_int8, quantize_kv

    b = h.shape[0]
    hd = cfg.resolved_head_dim
    q = linear(p["wq"], h).reshape(b, 1, cfg.n_heads, hd)
    k = linear(p["wk"], h).reshape(b, 1, cfg.n_kv_heads, hd)
    v = linear(p["wv"], h).reshape(b, 1, cfg.n_kv_heads, hd)
    posb = jnp.full((b, 1), pos, jnp.int32)
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    if kind == "local_attn":
        w = entry["k"].shape[1]
        slot = pos % w
        valid = jnp.minimum(pos + 1, w) * jnp.ones((b,), jnp.int32)
        # ring buffer: all slots < valid are live (order irrelevant w/ RoPE
        # applied at insert time)
    else:
        slot = pos
        valid = (pos + 1) * jnp.ones((b,), jnp.int32)
    new_entry = dict(entry)
    if "k_scale" in entry:  # int8 KV cache (PIMSAB adaptive precision)
        with jax.named_scope("kv_write"):
            kq, ks = quantize_kv(k, KV_SPEC)
            vq, vs = quantize_kv(v, KV_SPEC)
            new_entry["k"] = jax.lax.dynamic_update_slice_in_dim(entry["k"], kq, slot, axis=1)
            new_entry["v"] = jax.lax.dynamic_update_slice_in_dim(entry["v"], vq, slot, axis=1)
            new_entry["k_scale"] = jax.lax.dynamic_update_slice_in_dim(entry["k_scale"], ks, slot, axis=1)
            new_entry["v_scale"] = jax.lax.dynamic_update_slice_in_dim(entry["v_scale"], vs, slot, axis=1)
        out = decode_attention_int8(
            q, new_entry["k"], new_entry["v"], new_entry["k_scale"], new_entry["v_scale"],
            valid, KV_SPEC,
        )
    else:
        with jax.named_scope("kv_write"):
            new_entry["k"] = jax.lax.dynamic_update_slice_in_dim(entry["k"], k, slot, axis=1)
            new_entry["v"] = jax.lax.dynamic_update_slice_in_dim(entry["v"], v, slot, axis=1)
        out = decode_attention(q, new_entry["k"], new_entry["v"], valid)
    y = linear(p["wo"], out.reshape(b, 1, cfg.q_dim))
    return y, new_entry


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Params,
    tokens: jnp.ndarray,
    flags: RunFlags = DEFAULT_FLAGS,
    rules: Optional[MeshRules] = None,
) -> Tuple[Params, jnp.ndarray]:
    """tokens: (B, 1).  Returns (new_cache, logits (B, vocab))."""
    b = tokens.shape[0]
    pos = cache["pos"]
    x = _embed_tokens(params, tokens, cfg)
    pattern = cfg.block_pattern

    def group_body(x, scan_in):
        gp, gcache = scan_in
        new_entries = {}
        for i, kind in enumerate(pattern):
            key = f"{i:02d}_{kind}"
            p, entry = gp[key], gcache[key]
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            if kind in ("attn", "local_attn"):
                with jax.named_scope("attn"):
                    y, new_entry = _attn_decode(p["attn"], h, cfg, entry, pos, kind, rules)
            elif kind == "rglru":
                y, st = rglru_block_apply(p["mixer"], h, cfg, entry)
                new_entry = st
            elif kind == "mlstm":
                y, st = mlstm_block_apply(p["mixer"], h, cfg, entry)
                new_entry = st
            elif kind == "slstm":
                y, st = slstm_block_apply(p["mixer"], h, cfg, entry)
                new_entry = st
            x = x + y
            if "cross" in p:
                hx = rmsnorm(p["lnx"], x, cfg.norm_eps)
                enc_kv = {"k": entry["cross_k"], "v": entry["cross_v"]}
                xq = linear(p["cross"]["wq"], hx).reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim)
                out = decode_attention(xq, enc_kv["k"], enc_kv["v"])
                x = x + linear(p["cross"]["wo"], out.reshape(b, 1, cfg.q_dim))
                new_entry["cross_k"], new_entry["cross_v"] = entry["cross_k"], entry["cross_v"]
            if "ffn" in p:
                h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
                with jax.named_scope("ffn"):
                    y2, _ = _ffn_apply(p["ffn"], h2, cfg, flags, rules)
                x = x + y2
            new_entries[key] = new_entry
        return x, new_entries

    if flags.scan_layers:
        x, new_blocks = jax.lax.scan(group_body, x, (params["blocks"], cache["blocks"]))
    else:
        blocks_list = []
        for gi in range(cfg.pattern_groups()):
            gp = jax.tree_util.tree_map(lambda l: l[gi], params["blocks"])
            gc = jax.tree_util.tree_map(lambda l: l[gi], cache["blocks"])
            x, nb = group_body(x, (gp, gc))
            blocks_list.append(nb)
        new_blocks = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *blocks_list)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x, cfg)[:, 0]
    new_cache = {"pos": pos + 1, "blocks": new_blocks}
    return new_cache, logits
