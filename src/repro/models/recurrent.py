"""Recurrent temporal-mixing blocks: RG-LRU (RecurrentGemma/Griffin), and the
xLSTM pair (chunkwise-parallel mLSTM, sequential sLSTM).

All recurrences run in fp32 with log-space gate stabilization.  Each block has
two execution forms:

* sequence form (train/prefill): RG-LRU via ``jax.lax.associative_scan``;
  mLSTM via a chunkwise-parallel algorithm (intra-chunk quadratic + inter-chunk
  state recurrence) — both sub-quadratic in S and never materialize O(S^2);
  sLSTM is inherently sequential (recurrent weight matrices) and uses
  ``lax.scan`` over time.
* single-step form (decode): carries a fixed-size state — this is what makes
  the ``long_500k`` cell tractable for these families.

``BLOCK_KINDS`` holds the three block kinds: each ``*_block_apply`` serves
both forms, and the state it returns is the block's cache entry.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import BlockKind, Params, dense_init, linear, linear_init

# ---------------------------------------------------------------------------
# causal conv1d (width-K depthwise), used by RG-LRU and mLSTM blocks
# ---------------------------------------------------------------------------

CONV_K = 4


def causal_conv1d(u: jnp.ndarray, kernel: jnp.ndarray, state: Optional[jnp.ndarray] = None):
    """u: (B,S,W); kernel: (K,W) depthwise.  state: (B,K-1,W) trailing inputs
    of the previous segment.  Returns (y, new_state)."""
    b, s, w = u.shape
    k = kernel.shape[0]
    if state is None:
        state = jnp.zeros((b, k - 1, w), u.dtype)
    ext = jnp.concatenate([state, u], axis=1)  # (B, S+K-1, W)
    y = jnp.zeros_like(u)
    for j in range(k):
        y = y + ext[:, j : j + s] * kernel[j]
    return y, ext[:, -(k - 1) :]


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def rglru_block_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    w = d  # lru_width == d_model in RecurrentGemma
    ks = jax.random.split(key, 7)
    return {
        "w_gate_branch": linear_init(ks[0], d, w, dtype),
        "w_rec_branch": linear_init(ks[1], d, w, dtype),
        "conv": {"kernel": (jax.random.normal(ks[2], (CONV_K, w)) * 0.1).astype(dtype)},
        "w_a": linear_init(ks[3], w, w, dtype),  # recurrence gate
        "w_i": linear_init(ks[4], w, w, dtype),  # input gate
        "lambda": jnp.full((w,), 2.0, jnp.float32),  # softplus(2)≈2.1 → slow decay
        "w_out": linear_init(ks[5], w, d, dtype),
    }


def _rglru_coeffs(p: Params, u: jnp.ndarray):
    """u: (..., W) conv output -> (log_a, x_in) in fp32."""
    r = jax.nn.sigmoid(linear(p["w_a"], u).astype(jnp.float32))
    i = jax.nn.sigmoid(linear(p["w_i"], u).astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["lambda"]) * r
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))  # sqrt(1 - a^2), stable
    x_in = beta * (i * u.astype(jnp.float32))
    return log_a, x_in


def rglru_block_apply(p: Params, x: jnp.ndarray, cfg, state: Optional[Dict] = None):
    """x: (B,S,d).  Returns (y, new_state) with state {"h": (B,W), "conv": (B,K-1,W)}."""
    gate = jax.nn.gelu(linear(p["w_gate_branch"], x).astype(jnp.float32)).astype(x.dtype)
    u0 = linear(p["w_rec_branch"], x)
    conv_state = state["conv"] if state else None
    u, conv_state = causal_conv1d(u0, p["conv"]["kernel"], conv_state)
    log_a, x_in = _rglru_coeffs(p, u)
    if x.shape[1] == 1 and state is not None:  # decode step
        h = state["h"] * jnp.exp(log_a[:, 0]) + x_in[:, 0]
        hs = h[:, None]
    else:
        a = jnp.exp(log_a)
        if state is not None:  # chain from carried state
            x_in = x_in.at[:, 0].add(a[:, 0] * state["h"])

        def comb(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2

        _, hs = jax.lax.associative_scan(comb, (a, x_in), axis=1)
        h = hs[:, -1]
    y = linear(p["w_out"], (gate.astype(jnp.float32) * hs).astype(x.dtype))
    return y, {"h": h, "conv": conv_state}


def rglru_state_init(cfg, batch: int, dtype=jnp.float32) -> Dict:
    w = cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, CONV_K - 1, w), jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix memory) — chunkwise parallel
# ---------------------------------------------------------------------------


def _mlstm_dims(cfg) -> Tuple[int, int, int]:
    pf = 2 * cfg.d_model  # projection factor 2
    h = cfg.n_heads
    return pf, h, pf // h


def mlstm_block_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    pf, h, dh = _mlstm_dims(cfg)
    ks = jax.random.split(key, 9)
    return {
        "w_up": linear_init(ks[0], d, 2 * pf, dtype),  # [x_m | z-gate]
        "conv": {"kernel": (jax.random.normal(ks[1], (CONV_K, pf)) * 0.1).astype(dtype)},
        "w_q": linear_init(ks[2], pf, pf, dtype),
        "w_k": linear_init(ks[3], pf, pf, dtype),
        "w_v": linear_init(ks[4], pf, pf, dtype),
        "w_if": linear_init(ks[5], pf, 2 * h, dtype),  # per-head scalar gates
        "gn_scale": jnp.ones((pf,), dtype),
        "w_down": linear_init(ks[6], pf, d, dtype),
    }


def _heads(x, h):  # (B,S,pf) -> (B,S,H,dh)
    b, s, _ = x.shape
    return x.reshape(b, s, h, -1)


def _mlstm_chunk_scan(q, k, v, ig, lf, state, chunk: int):
    """Chunkwise stabilized mLSTM.

    q,k,v: (B,S,H,dh) — q pre-scaled by 1/sqrt(dh).
    ig, lf: (B,S,H) log input gate (ĩ) and log forget gate (logsigmoid f̃).
    state: dict C (B,H,dh,dh), n (B,H,dh), m (B,H).
    Returns (y (B,S,H,dh), new_state).
    """
    b, s, h, dh = q.shape
    l = min(chunk, s)
    pad = (-s) % l
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
        ig = jnp.pad(ig, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        lf = jnp.pad(lf, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // l
    # (nc, B, H, L, ...) layout for scan
    qc = q.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)
    kc = k.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nc, l, h, dh).transpose(1, 0, 3, 2, 4)
    igc = ig.reshape(b, nc, l, h).transpose(1, 0, 3, 2)  # (nc,B,H,L)
    lfc = lf.reshape(b, nc, l, h).transpose(1, 0, 3, 2)
    tri = jnp.tril(jnp.ones((l, l), bool))

    def step(carry, xs):
        C, n, m = carry  # (B,H,dh,dh), (B,H,dh), (B,H)
        qi, ki, vi, ii, fi = xs
        bcum = jnp.cumsum(fi, axis=-1)  # (B,H,L) inclusive log-decay F_t
        g = ii - bcum  # g_s = ĩ_s - F_s
        gmax = jax.lax.cummax(g, axis=g.ndim - 1)
        m_t = jnp.maximum(m[..., None] + bcum, bcum + gmax)  # (B,H,L)
        # inter-chunk: queries read incoming state
        dec_in = jnp.exp(m[..., None] + bcum - m_t)  # (B,H,L)
        y_inter = jnp.einsum("bhld,bhde->bhle", qi, C) * dec_in[..., None]
        n_inter = jnp.einsum("bhld,bhd->bhl", qi, n) * dec_in
        # intra-chunk: D_ts = exp(F_t - F_s + ĩ_s - m_t), s<=t
        logd = bcum[..., :, None] - bcum[..., None, :] + ii[..., None, :] - m_t[..., None]
        logd = jnp.where(tri, logd, -1e30)
        d_mat = jnp.exp(logd)  # (B,H,L,L)
        s_mat = jnp.einsum("bhld,bhsd->bhls", qi, ki) * d_mat
        y_intra = jnp.einsum("bhls,bhsd->bhld", s_mat, vi)
        n_intra = jnp.sum(s_mat, axis=-1)
        denom = jnp.maximum(jnp.abs(n_inter + n_intra), jnp.exp(-m_t))[..., None]
        y = (y_inter + y_intra) / denom  # (B,H,L,dh)
        # state update to end of chunk
        btot = bcum[..., -1]  # (B,H)
        m_new = jnp.maximum(m + btot, btot + gmax[..., -1])
        w_state = jnp.exp(m + btot - m_new)  # old-state decay
        w_in = jnp.exp(btot[..., None] - bcum + ii - m_new[..., None])  # (B,H,L)
        C_new = C * w_state[..., None, None] + jnp.einsum(
            "bhl,bhld,bhle->bhde", w_in, ki, vi
        )
        n_new = n * w_state[..., None] + jnp.einsum("bhl,bhld->bhd", w_in, ki)
        return (C_new, n_new, m_new), y

    (C, n, m), ys = jax.lax.scan(step, (state["C"], state["n"], state["m"]), (qc, kc, vc, igc, lfc))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, sp, h, dh)[:, :s]
    return y, {"C": C, "n": n, "m": m}


def _mlstm_decode_step(q, k, v, ig, lf, state):
    """Single step.  q,k,v: (B,H,dh); ig,lf: (B,H)."""
    C, n, m = state["C"], state["n"], state["m"]
    m_new = jnp.maximum(lf + m, ig)
    f_w = jnp.exp(lf + m - m_new)
    i_w = jnp.exp(ig - m_new)
    C = C * f_w[..., None, None] + i_w[..., None, None] * (k[..., :, None] * v[..., None, :])
    n = n * f_w[..., None] + i_w[..., None] * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)), jnp.exp(-m_new))
    y = num / denom[..., None]
    return y, {"C": C, "n": n, "m": m_new}


def _groupnorm_heads(x, scale, eps=1e-5):
    """Per-head layernorm (no mean-center: RMS) over dh.  x: (B,S,H,dh)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps)


def mlstm_block_apply(p: Params, x: jnp.ndarray, cfg, state: Optional[Dict] = None, chunk: int = 256):
    b, s, d = x.shape
    pf, h, dh = _mlstm_dims(cfg)
    up = linear(p["w_up"], x)
    xm, z = jnp.split(up, 2, axis=-1)
    conv_state = state["conv"] if state else None
    xc, conv_state = causal_conv1d(xm, p["conv"]["kernel"], conv_state)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    q = _heads(linear(p["w_q"], xc), h).astype(jnp.float32) / math.sqrt(dh)
    k = _heads(linear(p["w_k"], xc), h).astype(jnp.float32)
    v = _heads(linear(p["w_v"], xm), h).astype(jnp.float32)
    gates = linear(p["w_if"], xc).astype(jnp.float32)  # (B,S,2H)
    ig, fg = jnp.split(gates, 2, axis=-1)
    lf = jax.nn.log_sigmoid(fg)
    if state is None:
        cell = mlstm_state_init(cfg, b)
    else:
        cell = {k2: state[k2] for k2 in ("C", "n", "m")}
    if s == 1 and state is not None:  # decode
        y, cell = _mlstm_decode_step(q[:, 0], k[:, 0], v[:, 0], ig[:, 0], lf[:, 0], cell)
        y = y[:, None]
    else:
        y, cell = _mlstm_chunk_scan(q, k, v, ig, lf, cell, chunk)
    y = _groupnorm_heads(y, None).reshape(b, s, pf).astype(x.dtype) * p["gn_scale"]
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = linear(p["w_down"], y)
    return out, {"C": cell["C"], "n": cell["n"], "m": cell["m"], "conv": conv_state}


def mlstm_state_init(cfg, batch: int) -> Dict:
    pf, h, dh = _mlstm_dims(cfg)
    return {
        "C": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, h, dh), jnp.float32),
        "m": jnp.zeros((batch, h), jnp.float32),
    }


def mlstm_full_state_init(cfg, batch: int) -> Dict:
    st = mlstm_state_init(cfg, batch)
    pf, _, _ = _mlstm_dims(cfg)
    st["conv"] = jnp.zeros((batch, CONV_K - 1, pf), jnp.dtype(cfg.dtype))
    return st


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, true recurrence) — sequential scan
# ---------------------------------------------------------------------------


def slstm_block_init(key, cfg, dtype) -> Params:
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 7)
    ffd = ((4 * d // 3) + 63) // 64 * 64
    return {
        "w_in": linear_init(ks[0], d, 4 * d, dtype),  # z,i,f,o input projections
        "r": (jax.random.normal(ks[1], (4, h, dh, dh)) * (1.0 / math.sqrt(dh))).astype(dtype),
        "gn_scale": jnp.ones((d,), dtype),
        "w_up": linear_init(ks[2], d, 2 * ffd, dtype),  # GeGLU post-up FFN
        "w_down": linear_init(ks[3], ffd, d, dtype),
    }


def _slstm_cell(p, xz, xi, xf, xo, state, h_heads):
    """One timestep.  x*: (B,H,dh) pre-activations from the input projection."""
    c, n, hprev, m = state  # each (B,H,dh)
    rz, ri, rf, ro = (p["r"][j] for j in range(4))
    z = jnp.tanh(xz + jnp.einsum("bhd,hde->bhe", hprev, rz))
    it = xi + jnp.einsum("bhd,hde->bhe", hprev, ri)
    ft = xf + jnp.einsum("bhd,hde->bhe", hprev, rf)
    ot = jax.nn.sigmoid(xo + jnp.einsum("bhd,hde->bhe", hprev, ro))
    lf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(lf + m, it)
    i_w = jnp.exp(it - m_new)
    f_w = jnp.exp(lf + m - m_new)
    c_new = f_w * c + i_w * z
    n_new = jnp.maximum(f_w * n + i_w, jnp.exp(-m_new))
    h_new = ot * c_new / n_new
    return (c_new, n_new, h_new, m_new), h_new


def slstm_block_apply(p: Params, x: jnp.ndarray, cfg, state: Optional[Dict] = None):
    b, s, d = x.shape
    h = cfg.n_heads
    dh = d // h
    pre = linear(p["w_in"], x).astype(jnp.float32)  # (B,S,4d)
    pre = pre.reshape(b, s, 4, h, dh)
    if state is None:
        st = slstm_state_init(cfg, b)
    else:
        st = state
    cell = (st["c"], st["n"], st["h"], st["m"])

    def step(carry, xs):
        return _slstm_cell(p, xs[:, 0], xs[:, 1], xs[:, 2], xs[:, 3], carry, h)

    cell, hs = jax.lax.scan(step, cell, pre.transpose(1, 0, 2, 3, 4))  # scan over S
    hs = hs.transpose(1, 0, 2, 3)  # (B,S,H,dh)
    hs = _groupnorm_heads(hs, None).reshape(b, s, d).astype(x.dtype) * p["gn_scale"]
    # post-up GeGLU FFN
    up = linear(p["w_up"], hs)
    g, u = jnp.split(up, 2, axis=-1)
    y = linear(p["w_down"], jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * u)
    c, n, hh, m = cell
    return y, {"c": c, "n": n, "h": hh, "m": m}


def slstm_state_init(cfg, batch: int) -> Dict:
    h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    z = jnp.zeros((batch, h, dh), jnp.float32)
    return {"c": z, "n": z + 1.0, "h": z, "m": z}


# ---------------------------------------------------------------------------
# block kinds
# ---------------------------------------------------------------------------


def _recurrent_kind(init, apply, state_init, ffn: bool, seq_kwargs=lambda flags: {}) -> BlockKind:
    return BlockKind(
        param="mixer", scope=None, ffn=ffn, init=init,
        seq=lambda p, h, cfg, flags, positions: apply(p, h, cfg, **seq_kwargs(flags)),
        cache=lambda cfg, batch, max_len, flags: state_init(cfg, batch),
        to_decode=lambda entries, cfg, s, max_len, flags: entries,
        decode=lambda p, h, cfg, entry, pos: apply(p, h, cfg, entry),
    )


BLOCK_KINDS = {
    "rglru": _recurrent_kind(rglru_block_init, rglru_block_apply, rglru_state_init, ffn=True),
    # the chunkwise form's chunk: the attention chunk, at most 256 positions
    "mlstm": _recurrent_kind(mlstm_block_init, mlstm_block_apply, mlstm_full_state_init,
                             ffn=False, seq_kwargs=lambda flags: {"chunk": min(flags.attn_chunk, 256)}),
    "slstm": _recurrent_kind(slstm_block_init, slstm_block_apply, slstm_state_init, ffn=False),
}
