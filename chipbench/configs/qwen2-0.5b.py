"""qwen2-0.5b on the program's serving path, and the work it requires.

The engine is built as ``launch/serve.build_engine`` builds it (same
``RunFlags``, int8 serving weights, the ``pallas`` backend), with two
differences the configuration file names: the weights come from the seed,
made on the device already in their served form (int8 and a float32 scale
per output channel) in one jitted call, and ``rms_norm_eps`` is the
published one.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.traffic import seed32
from chipbench.work import Work

Config = Dict[str, Any]
PAD_MULTIPLE = 2048  # the program pads the embedding to a multiple of this


def program_config(cfg: Config):
    from repro.configs.base import ModelConfig, QuantConfig

    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], qkv_bias=cfg["qkv_bias"], block_pattern=("attn",),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        quant=QuantConfig(enabled=True, act_bits=cfg["act_bits"], weight_bits=cfg["weight_bits"]),
        source=cfg["paper"])


def dims(cfg: Config) -> Dict[str, int]:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    vp = -(-cfg["vocab_size"] // PAD_MULTIPLE) * PAD_MULTIPLE
    return {"d": d, "hd": hd, "q": h * hd, "kv": kv * hd, "f": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"], "vp": vp}


LINEARS = (("wq", "d", "q", True), ("wk", "d", "kv", True), ("wv", "d", "kv", True),
           ("wo", "q", "d", False), ("w_gate", "d", "f", False), ("w_up", "d", "f", False),
           ("w_down", "f", "d", False))


def make_params(cfg: Config, seed: int):
    """The serving parameter tree ``ServeEngine`` takes, made on the device:
    each linear drawn N(0, 1/d_in) in float32 and stored as int8 with a
    float32 scale per output channel (symmetric, max-abs), layers stacked on
    a leading axis as the program's ``lax.scan`` reads them."""
    n = dims(cfg)
    g, qmax = n["layers"], 2 ** (cfg["weight_bits"] - 1) - 1
    bf16 = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 4 + 2 * len(LINEARS)))

        def linear(din, dout, bias):
            w = jax.random.normal(next(ks), (g, din, dout), jnp.float32) / math.sqrt(din)
            scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax, 1e-8)
            p = {"w_q": jnp.clip(jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int8),
                 "w_scale": scale}
            b = 0.1 * jax.random.normal(next(ks), (g, dout), jnp.float32)
            return dict(p, b=b.astype(bf16)) if bias else p

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)).astype(bf16)

        lin = {name: linear(n[a], n[b], bias) for name, a, b, bias in LINEARS}
        block = {"ln1": {"scale": norm((g, n["d"]))},
                 "attn": {k: lin[k] for k in ("wq", "wk", "wv", "wo")},
                 "ln2": {"scale": norm((g, n["d"]))},
                 "ffn": {k: lin[k] for k in ("w_gate", "w_up", "w_down")}}
        embed = 0.02 * jax.random.normal(next(ks), (n["vp"], n["d"]), jnp.float32)
        return {"embed": {"w": embed.astype(bf16)}, "blocks": {"00_attn": block},
                "final_norm": {"scale": norm((n["d"],))}}

    return make(jax.random.key(seed32(seed, "weights")))


def make_engine(cfg: Config, params, max_len: int, backend: str):
    from repro.models.runtime import RunFlags
    from repro.serve.engine import ServeEngine

    return ServeEngine(program_config(cfg), params, RunFlags(**cfg["run_flags"]),
                       max_len=max_len, backend=backend)


# -- work -------------------------------------------------------------------


def param_counts(cfg: Config) -> Dict[str, int]:
    """Parameters by kind (the embedding at the published vocabulary)."""
    n = dims(cfg)
    matmul = n["layers"] * sum(n[a] * n[b] for _, a, b, _ in LINEARS)
    bias = n["layers"] * sum(n[b] for _, _, b, has in LINEARS if has)
    norms = (2 * n["layers"] + 1) * n["d"]
    embed = n["vocab"] * n["d"]
    return {"matmul": matmul, "bias": bias, "norm": norms, "embed": embed,
            "total": matmul + bias + norms + embed}


def token_work(cfg: Config, pos: int, logits: bool) -> Work:
    """One token's operations at position ``pos`` (attending ``pos + 1``
    keys): 2 x multiply-adds of the int8 linears, of attention's scores and
    readout (bf16), and of the tied-embedding logits (bf16) when the step
    computes them.  Bytes are counted per step, not per token."""
    n = dims(cfg)
    linear = 2 * param_counts(cfg)["matmul"]
    attn = 2 * 2 * n["layers"] * n["q"] * (pos + 1)
    head = 2 * n["d"] * n["vocab"] if logits else 0
    return Work("token", linear, attn + head, 0)


def weight_bytes(cfg: Config) -> int:
    """Bytes a step reads once for its weights: int8 linears with their
    float32 scales, bf16 biases and norms, the bf16 embedding read for the
    logits."""
    n, c = dims(cfg), param_counts(cfg)
    scales = n["layers"] * sum(n[b] for _, _, b, _ in LINEARS) * 4
    return c["matmul"] * cfg["weight_bits"] // 8 + scales + 2 * (c["bias"] + c["norm"] + c["embed"])


def kv_bytes_per_token(cfg: Config) -> int:
    n = dims(cfg)
    return 2 * n["layers"] * n["kv"] * 2  # keys and values, bf16, every layer


def prefill_work(cfg: Config, batch: int, prompt_len: int) -> Work:
    """A prefill of ``batch`` prompts of ``prompt_len`` tokens: causal
    attention (position p attends p + 1 keys), logits of the last token only;
    weights read once, the cache written once."""
    n, s = dims(cfg), prompt_len
    linear = token_work(cfg, 0, logits=False).int8_ops
    attn = 2 * 2 * n["layers"] * n["q"] * s * (s + 1) // 2
    head = 2 * n["d"] * n["vocab"]
    by = weight_bytes(cfg) + batch * s * kv_bytes_per_token(cfg) + batch * n["vocab"] * 2
    return Work("prefill", batch * s * linear, batch * (attn + head), by)


def decode_work(cfg: Config, batch: int, pos: int) -> Work:
    """One decode step of ``batch`` lanes at position ``pos``: weights once,
    the cache's ``pos`` rows read and one row written per lane, logits."""
    n = dims(cfg)
    w = token_work(cfg, pos, logits=True)
    by = (weight_bytes(cfg) + batch * (pos + 1) * kv_bytes_per_token(cfg)
          + batch * n["vocab"] * 2)
    return Work("decode", batch * w.int8_ops, batch * w.bf16_ops, by)
