"""Composable transformer: one model assembly covering all 10 assigned
architectures (dense GQA, latent attention with MoE, RG-LRU hybrid, xLSTM,
enc-dec audio, VLM).  It is built around three single points:

* **The block kind.**  Every kind in a config's ``block_pattern`` is one
  :class:`~repro.models.common.BlockKind` entry in ``KINDS``, kept beside its
  math (``attention.py``: ``attn``, ``local_attn``; ``mla.py``: ``mla``;
  ``recurrent.py``: ``rglru``, ``mlstm``, ``slstm``): its parameters, its
  sequence and decode forms, its empty decode-cache entry, the conversion of
  a prefill's entries to that layout, and whether the block has an FFN.
  Nothing here branches on a kind's name.
* **The block** (``_block``), one body for both forms: norm, the kind's mixer,
  cross-attention to the encoder when the model has one (in the decode form,
  to the cached ``cross_k``/``cross_v``), then FFN or MoE.
* **The sequence pass** (``_sequence``) of ``forward`` and ``prefill``:
  embedding, vision prefix, encoder, every stack, final norm.

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

import contextlib
import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, frontend, mla, recurrent
from repro.models.common import (
    BlockKind,
    Params,
    dense_init,
    dtype_of,
    linear,
    rmsnorm,
    rmsnorm_init,
    softmax_cross_entropy,
    swiglu_ffn,
    swiglu_init,
)
from repro.models.moe import moe_ffn, moe_init
from repro.models.runtime import DEFAULT_FLAGS, RunFlags
from repro.dist.sharding import MeshRules, act_spec, constrain

# every block kind, by its name; a module holding a kind's math keeps its entry
KINDS: Dict[str, BlockKind] = {**attention.BLOCK_KINDS, **mla.BLOCK_KINDS, **recurrent.BLOCK_KINDS}

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, cfg, kind: BlockKind, dtype, decoder: bool, dense: bool = False) -> Params:
    """One block = norm + temporal mixer (+ cross-attn) (+ norm + FFN); a
    ``dense`` block of a MoE model has a plain MLP of ``dense_d_ff``."""
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, dtype), kind.param: kind.init(ks[0], cfg, dtype)}
    if decoder and cfg.is_encdec:
        p["lnx"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attention.attn_init(ks[1], cfg, dtype)
    if cfg.d_ff > 0 and kind.ffn:
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if dense:
            p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.dense_d_ff, dtype)
        elif cfg.is_moe:
            p["ffn"] = moe_init(ks[2], cfg, dtype)
        else:
            p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def _blocks(pattern: Tuple[str, ...]) -> Tuple[Tuple[str, BlockKind], ...]:
    """A pattern group's blocks: (params/cache key, kind).  The one place a
    kind's name is looked up."""
    return tuple((f"{i:02d}_{name}", KINDS[name]) for i, name in enumerate(pattern))


def _stacks(cfg) -> Tuple[Tuple[str, Tuple[Tuple[str, BlockKind], ...], int], ...]:
    """The layer stacks in order: (params/cache key, a group's blocks, groups)."""
    tiled = ("blocks", _blocks(cfg.block_pattern), cfg.pattern_groups())
    if cfg.first_dense_layers:
        return (("dense_blocks", _blocks(cfg.block_pattern[:1]), cfg.first_dense_layers), tiled)
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
        return {bkey: _block_init(pk[i], cfg, kind, dtype, decoder, dense)
                for i, (bkey, kind) in enumerate(_blocks(pattern))}

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


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


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


def _block(
    p: Params,
    x: jnp.ndarray,
    kind: BlockKind,
    cfg: ModelConfig,
    flags: RunFlags,
    rules: Optional[MeshRules],
    at: jnp.ndarray,
    entry: Optional[Params] = None,
    enc_out: Optional[jnp.ndarray] = None,
    counted: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Params, jnp.ndarray, jnp.ndarray]:
    """One block, in either form: norm, the kind's mixer, cross-attention
    when the block has it, then FFN or MoE.

    The sequence form (no ``entry``) runs positions ``at`` (1, S) and
    attends to the encoder's output ``enc_out``; the decode form runs one
    token at position ``at`` against its cache ``entry`` and attends to the
    cached ``cross_k``/``cross_v``.  Returns (x, the cache entries: the
    sequence form's raw ones or the decode form's new entry, aux loss, held
    experts a ``counted`` token chose)."""
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    with jax.named_scope(kind.scope) if kind.scope else contextlib.nullcontext():
        if entry is None:
            y, new = kind.seq(p[kind.param], h, cfg, flags, at)
        else:
            y, new = kind.decode(p[kind.param], h, cfg, entry, at)
    x = x + y
    if "cross" in p:
        hx = rmsnorm(p["lnx"], x, cfg.norm_eps)
        if entry is None:
            kv = attention.cross_kv(p["cross"], enc_out, cfg)
        else:
            kv = {"k": entry["cross_k"], "v": entry["cross_v"]}
        x = x + attention.cross_attention(p["cross"], hx, kv, cfg)
        new["cross_k"], new["cross_v"] = kv["k"], kv["v"]
    aux, used = jnp.float32(0), jnp.int32(0)
    if "ffn" in p:
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        with jax.named_scope("moe" if "router" in p["ffn"] else "ffn"):
            y2, a, used = _ffn_apply(p["ffn"], h2, cfg, flags, rules, counted)
        x = x + y2
        aux = aux + a
    return x, new, aux, used


# ---------------------------------------------------------------------------
# the sequence pass: forward (train / no-cache evaluation) and prefill
# ---------------------------------------------------------------------------


def _embed_tokens(params: Params, tokens: jnp.ndarray, cfg) -> jnp.ndarray:
    x = params["embed"]["w"][tokens]
    if not cfg.embed_scale:
        return x
    return x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)


def _run_encoder(params: Params, cfg, flags, rules, frame_embeds: jnp.ndarray) -> jnp.ndarray:
    x = frontend.embed_frames(params["audio_adapter"], frame_embeds.astype(dtype_of(cfg)))
    positions = jnp.arange(x.shape[1])[None]

    def body(x, gp):
        return _block(gp["00_attn"], x, attention.BIDIRECTIONAL, cfg, flags, rules, positions)[0], None

    x, _ = _scan_stack(body, x, params["enc_blocks"], flags, cfg.n_enc_layers)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _sequence(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    flags: RunFlags,
    rules: Optional[MeshRules],
    max_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Params]:
    """The pass of ``forward`` and ``prefill`` over the whole sequence, up to
    the final norm.  ``forward`` (no ``max_len``) checkpoints each block
    under ``flags.remat`` and sums the aux loss; ``prefill`` keeps each
    block's entries in the decode cache's layout for ``max_len`` positions.
    Returns (x, the aux loss or the stacks' cache entries)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    keep = max_len is not None
    x = _embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        x = frontend.fuse_patches(params["vision_adapter"], x, batch["patch_embeds"])
    x = constrain(x, rules, act_spec(b, rules) if rules else None)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(params, cfg, flags, rules, batch["enc_embeds"])
    positions = jnp.arange(s)[None]

    def one_block(pb, xx, pos_arg, enc_arg, kind):
        out, entries, a, _ = _block(pb, xx, kind, cfg, flags, rules, pos_arg, enc_out=enc_arg)
        return out, (entries if keep else a)

    # Remat per *block* (not per pattern group): a group can be 13 layers
    # (recurrentgemma) and rematerializing it whole keeps every layer's
    # intermediates live in the backward at once.
    blocked = {kind: partial(one_block, kind=kind) for _, kind in _blocks(cfg.block_pattern)}
    if flags.remat and not keep:
        blocked = {kind: jax.checkpoint(f) for kind, f in blocked.items()}

    def group_body(carry, gp, blocks):
        x, aux = carry
        kept = {}
        for key, kind in blocks:
            x, out = blocked[kind](gp[key], x, positions, enc_out)
            if keep:  # the cross-attention keys and values pass as they are
                kept[key] = {**out, **kind.to_decode(out, cfg, s, max_len, flags)}
            else:
                aux = aux + out
        x = constrain(x, rules, act_spec(b, rules) if rules else None)
        return (x, aux), kept

    carry, stacks = (x, None if keep else jnp.float32(0)), {}
    for key, blocks, groups in _stacks(cfg):
        carry, stacks[key] = _scan_stack(partial(group_body, blocks=blocks), carry, params[key],
                                         flags, groups)
    x, aux = carry
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), (stacks if keep else aux)


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jnp.ndarray],
    flags: RunFlags = DEFAULT_FLAGS,
    rules: Optional[MeshRules] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence logits.  Returns (logits, aux_loss)."""
    x, aux = _sequence(params, cfg, batch, flags, rules)
    return _lm_head(params, x, cfg), aux


def _lm_head(params: Params, x: jnp.ndarray, cfg) -> jnp.ndarray:
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return x @ params["embed"]["w"].T
        return linear(params["lm_head"], x)  # handles the int8 bit-sliced head


def loss_fn(params, cfg, batch, flags=DEFAULT_FLAGS, rules=None):
    logits, aux = forward(params, cfg, batch, flags, rules)
    ce = softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


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
    b, s = batch["tokens"].shape
    max_len = max_len or s
    blk = flags.prefill_block
    if blk and blk < b:
        return _prefill_blocked(params, cfg, batch, flags, rules, max_len, blk)
    x, stacks = _sequence(params, cfg, batch, flags, rules, max_len)
    logits = _lm_head(params, x[:, -1:], cfg)[:, 0]
    return _cache(cfg, b, jnp.asarray(s, jnp.int32), stacks), logits


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


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------


def _cache(cfg, batch: int, pos: jnp.ndarray, stacks: Params) -> Params:
    """A decode cache: the position, each stack's entries and, for a MoE
    model, the count of held experts chosen (none yet) and the lanes whose
    tokens it counts (all)."""
    cache = {"pos": pos, **stacks}
    if cfg.is_moe:
        cache["expert_slots_used"] = jnp.zeros((), jnp.int32)
        cache["live_lanes"] = jnp.ones((batch,), bool)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, flags: RunFlags = DEFAULT_FLAGS) -> Params:
    """Decode cache: per stack, stacked (G, B, ...) per pattern position; the
    position scalar; for a MoE model the count of held experts chosen and
    the lanes whose tokens it counts."""

    def stacked(kind, g):
        entry = kind.cache(cfg, batch, max_len, flags)
        if cfg.is_encdec:  # the decoder block's cross-attention keys and values
            xshp = (batch, cfg.enc_seq_len, cfg.n_kv_heads, cfg.resolved_head_dim)
            entry["cross_k"] = jnp.zeros(xshp, dtype_of(cfg))
            entry["cross_v"] = jnp.zeros(xshp, dtype_of(cfg))
        return jax.tree_util.tree_map(lambda l: jnp.broadcast_to(l, (g,) + l.shape), entry)

    pos = jnp.zeros((), jnp.int32)
    stacks = {key: {bkey: stacked(kind, groups) for bkey, kind in blocks}
              for key, blocks, groups in _stacks(cfg)}
    return _cache(cfg, batch, pos, stacks)


def cache_shape(cfg: ModelConfig, batch: int, max_len: int, flags: RunFlags = DEFAULT_FLAGS) -> Params:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, flags))


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Params,
    tokens: jnp.ndarray,
    flags: RunFlags = DEFAULT_FLAGS,
    rules: Optional[MeshRules] = None,
) -> Tuple[Params, jnp.ndarray]:
    """tokens: (B, 1).  Returns (new_cache, logits (B, vocab))."""
    pos = cache["pos"]
    x = _embed_tokens(params, tokens, cfg)
    live = cache["live_lanes"][:, None] if cfg.is_moe else None

    def group_body(x, scan_in, blocks):
        gp, gcache = scan_in
        new_entries = {}
        used = jnp.int32(0)
        for key, kind in blocks:
            x, new_entries[key], _, u = _block(gp[key], x, kind, cfg, flags, rules, pos,
                                               entry=gcache[key], counted=live)
            used = used + u
        return x, ((new_entries, used) if cfg.is_moe else new_entries)

    stacks, used = {}, jnp.int32(0)
    for key, blocks, groups in _stacks(cfg):
        x, stacks[key] = _scan_stack(partial(group_body, blocks=blocks), x,
                                     (params[key], cache[key]), flags, groups)
        if cfg.is_moe:
            stacks[key], u = stacks[key]
            used = used + jnp.sum(u)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_head(params, x, cfg)[:, 0]
    new_cache = {**cache, "pos": pos + 1, **stacks}
    if cfg.is_moe:
        new_cache["expert_slots_used"] = cache["expert_slots_used"] + used
    return new_cache, logits
