"""Composable transformer: one model assembly covering all 10 assigned
architectures (dense GQA, latent attention with MoE, RG-LRU hybrid, xLSTM,
enc-dec audio, VLM).

The layer stack is the config's ``block_pattern`` tiled to ``n_layers`` and
executed as ``lax.scan`` over *pattern groups* (params stacked on a leading
group axis) so the HLO stays depth-independent.  A model with leading dense
layers (``first_dense_layers``, DeepSeek-V3's ``first_k_dense_replace``)
keeps them in a stack of their own, ``dense_blocks``, scanned before
``blocks``; parameters and cache mirror the two.  Three entry points:

* ``forward``     — full-sequence logits (training / evaluation).
* ``prefill``     — full-sequence forward that also returns the decode cache.
* ``decode_step`` — one token in, one token out, cache updated in place.

Each block's attention and feed-forward run under the ``jax.named_scope``
``attn`` and ``ffn`` (``moe`` for an expert layer, with ``moe/route``,
``moe/experts`` and ``moe/shared`` inside), the logits under ``lm_head``,
and the decode step's cache update under ``attn/kv_write``; latent attention
adds ``attn/mla_expand`` (sequence form) and ``attn/mla_absorb`` (decode).
A profiler trace's device ops carry these names, so a step's device time
splits by part (ops outside them, such as norms and the scan's stacking of
the cache, carry none).

A MoE model's decode cache also counts, on the device, the held experts
that at least one live lane's token chose, summed over its expert layers and
decode steps since the prefill (``expert_slots_used``); ``live_lanes``
(B,) bool, all true after the prefill, says which lanes' tokens count (a
server sets a retired lane's false: the pad token it decodes serves no one).
"""
from __future__ import annotations

import dataclasses
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
    swiglu_ffn,
    swiglu_init,
)
from repro.models import mla
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


def _block_init(key, cfg, kind: str, dtype, decoder: bool, dense: bool = False) -> Params:
    """One block = norm + temporal mixer (+ cross-attn) (+ norm + FFN); a
    ``dense`` block of a MoE model has a plain MLP of ``dense_d_ff``."""
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if kind in ("attn", "local_attn"):
        p["attn"] = _attn_init(ks[0], cfg, dtype)
    elif kind == "mla":
        p["attn"] = mla.mla_init(ks[0], cfg, dtype)
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
    if cfg.d_ff > 0 and kind in ("attn", "local_attn", "rglru", "mla"):
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if dense:
            p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.dense_d_ff, dtype)
        elif cfg.is_moe:
            p["ffn"] = moe_init(ks[2], cfg, dtype)
        else:
            p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def _stacks(cfg) -> Tuple[Tuple[str, Tuple[str, ...], int], ...]:
    """The layer stacks in order: (params/cache key, pattern, groups)."""
    tiled = ("blocks", cfg.block_pattern, cfg.pattern_groups())
    if cfg.first_dense_layers:
        return (("dense_blocks", cfg.block_pattern[:1], cfg.first_dense_layers), tiled)
    return (tiled,)


def _scan_stack(body, carry, xs, flags: RunFlags, groups: int):
    """``lax.scan`` of ``body`` over a stack's groups, or the same unrolled
    (``flags.scan_layers`` off: cost-analysis correction, experiments)."""
    if flags.scan_layers:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for gi in range(groups):
        carry, y = body(carry, jax.tree_util.tree_map(lambda l: l[gi], xs))
        ys.append(y)
    return carry, jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ys)


def _stack_groups(key, cfg, dtype, n_groups: int, pattern, decoder: bool,
                  dense: bool = False) -> Params:
    """Init per group then stack leaves on a leading (G, ...) axis."""
    gkeys = jax.random.split(key, n_groups)

    def one_group(k):
        pk = jax.random.split(k, len(pattern))
        return {
            f"{i:02d}_{kind}": _block_init(pk[i], cfg, kind, dtype, decoder, dense)
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
    if cfg.first_dense_layers:
        params["dense_blocks"] = _stack_groups(
            ks[6], cfg, dtype, cfg.first_dense_layers, cfg.block_pattern[:1], decoder=True,
            dense=True,
        )
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


def _ffn_apply(p: Params, x: jnp.ndarray, cfg, flags: RunFlags, rules, counted=None):
    """Returns (y, aux_loss, held experts a ``counted`` token chose); an
    expert layer is one whose parameters hold a router."""
    if "router" in p:
        groups = flags.routing_groups or (rules.dp if rules is not None else 1)
        tokens = x.shape[0] * x.shape[1]
        while tokens % groups:
            groups -= 1
        return moe_ffn(p, x, cfg, groups, counted)
    return swiglu_ffn(p, x), jnp.float32(0), jnp.int32(0)


def _ffn_scope(p: Params) -> str:
    return "moe" if "router" in p else "ffn"


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
    elif kind == "mla":
        with jax.named_scope("attn"):
            y, kv = mla.mla_attention(p["attn"], h, cfg, flags, positions)
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
        with jax.named_scope(_ffn_scope(p["ffn"])):
            y2, a, _ = _ffn_apply(p["ffn"], h2, cfg, flags, rules)
        x = x + y2
        aux = aux + a
    return x, cache_out, aux


# ---------------------------------------------------------------------------
# forward (train / no-cache evaluation)
# ---------------------------------------------------------------------------


def _embed_tokens(params: Params, tokens: jnp.ndarray, cfg) -> jnp.ndarray:
    x = params["embed"]["w"][tokens]
    if not cfg.embed_scale:
        return x
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
        for kind in set(cfg.block_pattern)
    }

    def group_body(carry, gp, pattern):
        x, aux = carry
        for i, kind in enumerate(pattern):
            x, a = blocked[kind](gp[f"{i:02d}_{kind}"], x, positions, enc_out)
            aux = aux + a
        x = constrain(x, rules, act_spec(b, rules) if rules else None)
        return (x, aux), None

    carry = (x, jnp.float32(0))
    for key, pattern, groups in _stacks(cfg):
        carry, _ = _scan_stack(partial(group_body, pattern=pattern), carry, params[key], flags, groups)
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
    elif kind == "mla":
        if flags.quant_kv:
            raise ValueError("latent attention keeps a bfloat16 latent cache (quant_kv unsupported)")
        entry = mla.cache_entry(cfg, batch, max_len, dt)
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
    """Decode cache: per stack, stacked (G, B, ...) per pattern position; the
    position scalar; for a MoE model the count of held experts chosen and
    the lanes whose tokens it counts."""

    def stacked(kind, g):
        entry = _cache_entry_shape(cfg, kind, batch, max_len, flags)
        return jax.tree_util.tree_map(lambda l: jnp.broadcast_to(l, (g,) + l.shape), entry)

    cache = {"pos": jnp.zeros((), jnp.int32)}
    for key, pattern, groups in _stacks(cfg):
        cache[key] = {f"{i:02d}_{kind}": stacked(kind, groups) for i, kind in enumerate(pattern)}
    if cfg.is_moe:
        cache["expert_slots_used"] = jnp.zeros((), jnp.int32)
        cache["live_lanes"] = jnp.ones((batch,), bool)
    return cache


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
    """Run the prompt, return (cache, last-token logits).

    With ``flags.prefill_block`` (lanes) below the batch, the prompts run a
    block of lanes at a time, each block through every layer, into one cache:
    the peak memory is one block's activations."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_len = max_len or s
    blk = flags.prefill_block
    if blk and blk < b:
        return _prefill_blocked(params, cfg, batch, flags, rules, max_len, blk)
    x = _embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        x = frontend.fuse_patches(params["vision_adapter"], x, batch["patch_embeds"])
    x = constrain(x, rules, act_spec(b, rules) if rules else None)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(params, cfg, flags, rules, batch["enc_embeds"])
    positions = jnp.arange(s)[None]

    def group_body(x, gp, pattern):
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

    stacks = {}
    for key, pattern, groups in _stacks(cfg):
        x, stacks[key] = _scan_stack(partial(group_body, pattern=pattern), x, params[key], flags, groups)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x[:, -1:], cfg)[:, 0]
    cache = {"pos": jnp.asarray(s, jnp.int32), **stacks}
    if cfg.is_moe:
        cache["expert_slots_used"] = jnp.zeros((), jnp.int32)
        cache["live_lanes"] = jnp.ones((b,), bool)
    return cache, logits


def _prefill_blocked(params, cfg, batch, flags, rules, max_len: int, blk: int):
    """``prefill`` a block of ``blk`` lanes at a time, into one cache."""
    b, s = batch["tokens"].shape
    if b % blk:
        raise ValueError(f"prefill_block={blk} does not divide the batch of {b}")
    unblocked = dataclasses.replace(flags, prefill_block=0)

    def block(cache, i):
        lanes = {k: jax.lax.dynamic_slice_in_dim(v, i * blk, blk, axis=0) for k, v in batch.items()}
        part, logits = prefill(params, cfg, lanes, unblocked, rules, max_len)
        for key, _, _ in _stacks(cfg):  # lanes sit on axis 1, after the group axis
            cache[key] = jax.tree_util.tree_map(
                lambda full, new: jax.lax.dynamic_update_slice_in_dim(
                    full, new.astype(full.dtype), i * blk, axis=1),
                cache[key], part[key])
        return cache, logits

    cache, logits = jax.lax.scan(block, init_cache(cfg, b, max_len, unblocked), jnp.arange(b // blk))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache, logits.reshape(b, -1)


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

    if kind == "mla":
        return {n: jnp.pad(entries[n], ((0, 0), (0, max_len - s), (0, 0))) for n in ("c_kv", "k_pe")}
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
    live = cache["live_lanes"][:, None] if cfg.is_moe else None

    def group_body(x, scan_in, pattern):
        gp, gcache = scan_in
        new_entries = {}
        used = jnp.int32(0)
        for i, kind in enumerate(pattern):
            key = f"{i:02d}_{kind}"
            p, entry = gp[key], gcache[key]
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            if kind in ("attn", "local_attn"):
                with jax.named_scope("attn"):
                    y, new_entry = _attn_decode(p["attn"], h, cfg, entry, pos, kind, rules)
            elif kind == "mla":
                with jax.named_scope("attn"):
                    y, new_entry = mla.mla_decode(p["attn"], h, cfg, entry, pos)
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
                with jax.named_scope(_ffn_scope(p["ffn"])):
                    y2, _, u = _ffn_apply(p["ffn"], h2, cfg, flags, rules, live)
                x = x + y2
                if cfg.is_moe:
                    used = used + u
            new_entries[key] = new_entry
        return x, ((new_entries, used) if cfg.is_moe else new_entries)

    stacks, used = {}, jnp.int32(0)
    for key, pattern, groups in _stacks(cfg):
        x, stacks[key] = _scan_stack(partial(group_body, pattern=pattern), x,
                                     (params[key], cache[key]), flags, groups)
        if cfg.is_moe:
            stacks[key], u = stacks[key]
            used = used + jnp.sum(u)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x, cfg)[:, 0]
    new_cache = {"pos": pos + 1, **stacks}
    if cfg.is_moe:
        new_cache["expert_slots_used"] = cache["expert_slots_used"] + used
        new_cache["live_lanes"] = cache["live_lanes"]
    return new_cache, logits
