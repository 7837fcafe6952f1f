"""Mixture-of-Experts FFN: dropless, over the experts this chip holds.

The router ranks all ``cfg.n_experts`` experts for every token.  The layer
holds the experts ``[cfg.first_held_expert, + cfg.n_held_experts)`` (all of
them unless the config says otherwise, as expert parallelism would divide
them) and computes their part of the result, plus the shared experts, which
every chip computes alike.  Nothing is dropped: every (token, held expert)
pair the router picks is computed.

Two dispatches, chosen by the number of tokens.  Up to ``TILE_ROWS``
tokens (a decode step), every held expert runs on every token and each
result is weighted by what the token gave that expert (0 if it did not
choose it): one batched matmul that reads each held expert's weights once.
Past that (a prefill), the (token, choice) pairs are sorted by held expert
and walked in tiles of ``TILE_ROWS`` rows, each tile inside one expert's
segment: a tile gathers its tokens, runs that expert's SwiGLU and
scatter-adds the gated result.  That loop has a static worst-case count of
tiles (every token on every held expert) and a ``lax.cond`` skips the tiles
past those routing filled, so the work follows the tokens routed here and
reverse-mode differentiation still works.  (Inside a loop the expert weights
cannot be read in place: each layer's are copied out of the stacked
parameters, which a decode step would pay for every layer.)  Either way an
expert's SwiGLU is int8 when its weights are quantized, like every other
linear.

Routing runs per routing group (one per data shard by default): groups
change where dispatch happens, not the result.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params, dense_init, swiglu_ffn, swiglu_init

TILE_ROWS = 256


def moe_init(key, cfg, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held_experts
    ks = jax.random.split(key, 5)

    def experts(k, d_in, d_out):
        w = jax.random.normal(k, (e, d_in, d_out), jnp.float32) / math.sqrt(d_in)
        return {"w": w.astype(dtype)}

    router: Params = {"w": dense_init(ks[0], d, cfg.n_experts, jnp.float32)}
    if cfg.router == "sigmoid":
        router["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    p = {"router": router, "w_gate": experts(ks[1], d, f), "w_up": experts(ks[2], d, f),
         "w_down": experts(ks[3], f, d)}
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], d, f * cfg.n_shared_experts, dtype)
    return p


def route(p: Params, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (T, D).  Returns each token's ``experts_per_token`` expert ids
    (T, k), their weights (T, k) float32, and the load-balance loss."""
    logits = x.astype(jnp.float32) @ p["w"]  # (T, E)
    k = cfg.experts_per_token
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["bias"], k)  # the bias picks, it does not weigh
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling
        return idx, w, jnp.float32(0)
    top, idx = jax.lax.top_k(logits, k)
    # Switch-style load-balance loss: router probability mass x top-1 dispatch mass
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    ce = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, axis=-1), cfg.n_experts, dtype=jnp.float32), axis=0)
    return idx, jax.nn.softmax(top, axis=-1), cfg.n_experts * jnp.sum(me * ce)


def held_experts_part(experts: Params, x: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray,
                      first: int, counted: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the routed result, float32 (T, D), and how
    many held experts at least one counted token chose (``counted``: (T,)
    bool, every token if not given; it changes no result).  ``experts``
    holds ``(held, d_in, d_out)`` SwiGLU weights for experts ``first ...``."""
    t, d = x.shape
    k = idx.shape[1]
    held = jax.tree_util.tree_leaves(experts["w_down"])[0].shape[0]
    local = idx - first
    flat = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    counts = jnp.bincount(flat, length=held + 1)[:held]
    if counted is None:
        used = jnp.sum(counts > 0)
    else:
        mine = jnp.where(jnp.repeat(counted, k), flat, held)
        used = jnp.sum(jnp.bincount(mine, length=held + 1)[:held] > 0)
    if t <= TILE_ROWS:
        gate = jnp.einsum("tk,tke->et", w, jax.nn.one_hot(local, held, dtype=w.dtype))
        y = jax.vmap(swiglu_ffn, in_axes=(0, None))(experts, x)  # (held, t, d)
        return jnp.einsum("et,etd->td", gate, y.astype(jnp.float32)), used
    tile = TILE_ROWS
    order = jnp.argsort(flat, stable=True)  # held experts' pairs first, by expert
    tiles = -(-counts // tile)
    tiles_end = jnp.cumsum(tiles)
    seg_start = jnp.cumsum(counts) - counts
    flat_w = w.reshape(-1)
    rows = jnp.arange(tile)

    def one_tile(i, out):
        e = jnp.searchsorted(tiles_end, i, side="right")
        r = (i - tiles_end[e] + tiles[e]) * tile + rows  # ranks inside expert e's segment
        live = r < counts[e]
        pair = order[jnp.minimum(seg_start[e] + r, t * k - 1)]
        tok = jnp.where(live, pair // k, t)  # t: no token
        pe = jax.tree_util.tree_map(lambda a: a[e], experts)
        y = swiglu_ffn(pe, x.at[tok].get(mode="fill", fill_value=0))
        gate = jnp.where(live, flat_w[pair], 0.0)
        return out.at[tok].add(y.astype(jnp.float32) * gate[:, None], mode="drop")

    def step(i, out):
        return jax.lax.cond(i < tiles_end[-1], one_tile, lambda i, o: o, i, out)

    most = -(-t * min(k, held) // tile) + held
    out = jax.lax.fori_loop(0, most, step, jnp.zeros((t, d), jnp.float32))
    return out, used


def moe_ffn(p: Params, x: jnp.ndarray, cfg, n_groups: int,
            counted: Optional[jnp.ndarray] = None):
    """x: (B, S, D) -> (out, aux_loss, held experts chosen by a counted
    token; ``counted`` (B, S) bool, every token if not given).  Routed per
    group of B*S/n_groups tokens; ``route``, ``experts`` and ``shared``
    name-scope the three parts."""
    b, s, d = x.shape
    tokens = b * s
    assert tokens % n_groups == 0, (tokens, n_groups)
    xg = x.reshape(n_groups, tokens // n_groups, d)
    cg = None if counted is None else counted.reshape(n_groups, tokens // n_groups)
    experts = {n: p[n] for n in ("w_gate", "w_up", "w_down")}

    def group(xc):
        xi, ci = xc
        with jax.named_scope("route"):
            idx, w, aux = route(p["router"], xi, cfg)
        with jax.named_scope("experts"):
            y, used = held_experts_part(experts, xi, idx, w, cfg.first_held_expert, ci)
        return y, aux, used

    if n_groups == 1:  # a vmap would turn each tile's cond into a select of both branches
        y, aux, used = group((xg[0], None if cg is None else cg[0]))
    else:
        y, aux, used = jax.lax.map(group, (xg, cg))
        aux, used = jnp.mean(aux), jnp.sum(used)
    y = y.reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("shared"):
            y = y + swiglu_ffn(p["shared"], x).astype(jnp.float32)
    return y.astype(x.dtype), aux, used
