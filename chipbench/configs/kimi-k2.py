"""kimi-k2 on the program's serving path, and the work it requires.

One chip's share of Kimi-K2-Instruct at its published widths (the
configuration file states the cut and the 48-chip deployment it stands
for): the leading dense layer and 4 expert layers, experts 0-7 of 384 with
the router's full 384 outputs, the shared expert, and a slice of the
vocabulary.  The engine is ``ServeEngine`` on the ``pallas`` backend with
int8 serving weights, as for qwen2-0.5b; the weights come from the seed,
made on the device already in their served form (int8 and a float32 scale
per output channel), one jitted call per weight shape.

The work functions count MLA at its own shapes (prefill expands the latent
into per-head keys and values; decode absorbs ``kv_b`` and attends over the
576-wide latent and rotary key) and the held experts' tokens at top-8 over
384.  A decode step's least time reads a held expert's weights only when a
live lane's token chose it: ``decode_work`` takes the held experts used per expert
layer from the engine's counter, and otherwise their expectation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.traffic import seed32
from chipbench.work import Work

Config = Dict[str, Any]
PAD_MULTIPLE = 2048  # the program pads the vocabulary to a multiple of this


def program_config(cfg: Config):
    from repro.configs.base import MLAConfig, ModelConfig, QuantConfig, YarnConfig

    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"], cfg["norm_topk_prob"]) != (
            "sigmoid", "noaux_tc", 1, True):
        raise ValueError("the program routes by noaux_tc over sigmoid scores in one group")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"], block_pattern=("mla",),
        n_experts=cfg["published"]["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"], router="sigmoid",
        routed_scaling=cfg["routed_scaling_factor"], n_shared_experts=cfg["n_shared_experts"],
        held_experts=cfg["n_routed_experts"],
        first_held_expert=cfg["deployment"]["first_held_expert"],
        first_dense_layers=cfg["first_k_dense_replace"], dense_d_ff=cfg["intermediate_size"],
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"]),
        yarn=YarnConfig(factor=float(rs["factor"]),
                        original_max_position=rs["original_max_position_embeddings"],
                        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], embed_scale=False, dtype=cfg["torch_dtype"],
        quant=QuantConfig(enabled=True, act_bits=cfg["act_bits"], weight_bits=cfg["weight_bits"]),
        source=cfg["source"])


def dims(cfg: Config) -> Dict[str, int]:
    h = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "h": h, "qlr": cfg["q_lora_rank"], "c": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "f": cfg["moe_intermediate_size"], "fd": cfg["intermediate_size"],
            "E": cfg["published"]["n_routed_experts"], "held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
            "layers": cfg["num_hidden_layers"], "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"],
            "vp": -(-cfg["vocab_size"] // PAD_MULTIPLE) * PAD_MULTIPLE}


def _attn_linears(n):
    """(name, d_in, d_out) of latent attention's linears."""
    return (("wq_a", n["d"], n["qlr"]), ("wq_b", n["qlr"], n["h"] * n["qk"]),
            ("wkv_a", n["d"], n["c"] + n["rope"]), ("wkv_b", n["c"], n["h"] * (n["nope"] + n["v"])),
            ("wo", n["h"] * n["v"], n["d"]))


@partial(jax.jit, static_argnums=(1, 2))
def _int8_linear(key, shape, qmax):
    """A linear drawn N(0, 1/d_in) in float32, stored int8 with a float32
    max-abs scale per output channel (``shape`` ends in (d_in, d_out))."""
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True) / qmax, 1e-8)
    return {"w_q": jnp.clip(jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int8),
            "w_scale": scale}


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, shape, mean, std, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(cfg: Config, seed: int):
    """The serving parameter tree ``ServeEngine`` takes, made on the device:
    the dense layer in ``dense_blocks``, the expert layers stacked on a
    leading axis in ``blocks`` as the program's ``lax.scan`` reads them."""
    n = dims(cfg)
    qmax = 2 ** (cfg["weight_bits"] - 1) - 1
    bf16 = jnp.dtype(cfg["torch_dtype"])
    keys = iter(jax.random.split(jax.random.key(seed32(seed, "weights")), 64))

    def linear(*shape):
        return _int8_linear(next(keys), shape, qmax)

    def norm(*shape):
        return {"scale": _normal(next(keys), shape, 1.0, 0.1, bf16)}

    def swiglu(g, *lead, f):
        return {"w_gate": linear(g, *lead, n["d"], f), "w_up": linear(g, *lead, n["d"], f),
                "w_down": linear(g, *lead, f, n["d"])}

    def block(g, dense):
        attn = {name: linear(g, a, b) for name, a, b in _attn_linears(n)}
        attn.update(q_norm=norm(g, n["qlr"]), kv_norm=norm(g, n["c"]))
        if dense:
            ffn = swiglu(g, f=n["fd"])
        else:
            ffn = dict(swiglu(g, n["held"], f=n["f"]),
                       shared=swiglu(g, f=n["f"] * n["shared"]),
                       router={"w": _normal(next(keys), (g, n["d"], n["E"]), 0.0,
                                            1 / math.sqrt(n["d"]), jnp.float32),
                               "bias": _normal(next(keys), (g, n["E"]), 0.0,
                                               cfg["router_bias_std"], jnp.float32)})
        return {"ln1": norm(g, n["d"]), "attn": attn, "ln2": norm(g, n["d"]), "ffn": ffn}

    return {"embed": {"w": _normal(next(keys), (n["vp"], n["d"]), 0.0, 0.02, bf16)},
            "dense_blocks": {"00_mla": block(n["dense"], True)},
            "blocks": {"00_mla": block(n["moe"], False)},
            "final_norm": norm(n["d"]),
            "lm_head": linear(n["d"], n["vp"])}


def make_engine(cfg: Config, params, max_len: int, backend: str):
    from repro.models.runtime import RunFlags
    from repro.serve.engine import ServeEngine

    return ServeEngine(program_config(cfg), params, RunFlags(**cfg["run_flags"]),
                       max_len=max_len, backend=backend)


# -- work -------------------------------------------------------------------


def _expert(n) -> int:
    return 3 * n["d"] * n["f"]


def param_counts(cfg: Config) -> Dict[str, int]:
    """Parameters held here, by kind (the head and embedding at the slice)."""
    n = dims(cfg)
    attn = n["layers"] * sum(a * b for _, a, b in _attn_linears(n))
    dense = n["dense"] * 3 * n["d"] * n["fd"]
    experts = n["moe"] * n["held"] * _expert(n)
    shared = n["moe"] * n["shared"] * _expert(n)
    head = n["d"] * n["vocab"]
    return {"attn": attn, "dense": dense, "experts": experts, "shared": shared, "head": head,
            "router": n["moe"] * n["E"] * (n["d"] + 1), "embed": n["vocab"] * n["d"],
            "norm": n["layers"] * (2 * n["d"] + n["qlr"] + n["c"]) + n["d"]}


def expected_experts_used(cfg: Config, batch: int) -> float:
    """Held experts of one layer that at least one of ``batch`` tokens
    chooses, in expectation (each token picks ``k`` of ``E``)."""
    n = dims(cfg)
    return n["held"] * (1 - (1 - n["k"] / n["E"]) ** batch)


def _linear_macs(n, held_per_token: float) -> float:
    """Int8 multiply-adds of one token: every linear (``kv_b`` multiplies the
    unquantized latent and is counted apart), ``held_per_token`` held
    experts, the shared expert."""
    attn = sum(a * b for name, a, b in _attn_linears(n) if name != "wkv_b")
    return (n["layers"] * attn + n["dense"] * 3 * n["d"] * n["fd"]
            + n["moe"] * (held_per_token + n["shared"]) * _expert(n))


def _held_per_token(n) -> float:
    """Held experts a token uses on average: top-k over E, ``held`` of them here."""
    return n["k"] * n["held"] / n["E"]


def token_work(cfg: Config, pos: int, logits: bool) -> Work:
    """One decode token at position ``pos`` (attending ``pos + 1`` latents):
    2 x the int8 multiply-adds of the linears and of its share of the held
    experts, and at bf16 the router, the absorbed ``kv_b`` (query in, output
    out), the scores over the latent and the rotary key, the readout of the
    latent, and the head's logits when the step computes them.  Bytes are
    counted per step, not per token."""
    n = dims(cfg)
    linear = _linear_macs(n, _held_per_token(n)) + (n["d"] * n["vocab"] if logits else 0)
    absorb = n["h"] * n["c"] * (n["nope"] + n["v"])
    attend = n["h"] * (2 * n["c"] + n["rope"]) * (pos + 1)
    router = n["moe"] * n["d"] * n["E"]
    return Work("token", 2 * linear, 2 * (n["layers"] * (absorb + attend) + router), 0)


def weight_bytes(cfg: Config, experts_used: Optional[float] = None) -> float:
    """Bytes a step reads once for its weights: int8 linears with their
    float32 scales, the float32 router, bf16 norms; of the held experts
    ``experts_used`` per expert layer (all of them if not given)."""
    n, c = dims(cfg), param_counts(cfg)
    used = n["held"] if experts_used is None else experts_used
    experts = n["moe"] * used * (_expert(n) + 4 * (2 * n["f"] + n["d"]))
    linears = c["attn"] + c["dense"] + c["shared"] + c["head"]
    scales = 4 * (n["layers"] * sum(b for _, _, b in _attn_linears(n))
                  + n["dense"] * (2 * n["fd"] + n["d"])
                  + n["moe"] * n["shared"] * (2 * n["f"] + n["d"]) + n["vp"])
    return linears + scales + experts + 4 * c["router"] + 2 * c["norm"]


def kv_bytes_per_token(cfg: Config) -> int:
    n = dims(cfg)
    return n["layers"] * (n["c"] + n["rope"]) * 2  # latent and rotary key, bf16, every layer


def prefill_work(cfg: Config, batch: int, prompt_len: int) -> Work:
    """A prefill of ``batch`` prompts of ``prompt_len`` tokens in the
    expanded form: ``kv_b`` expands each latent into 64 heads' keys and
    values, causal attention (position p attends p + 1 keys) over 192-wide
    keys and 128-wide values, the held experts' share of the tokens, the
    logits of the last token only; weights read once, the cache written
    once."""
    n, s = dims(cfg), prompt_len
    tokens = batch * s
    linear = tokens * _linear_macs(n, _held_per_token(n)) + batch * n["d"] * n["vocab"]
    expand = tokens * n["c"] * n["h"] * (n["nope"] + n["v"])
    attend = batch * n["h"] * (n["qk"] + n["v"]) * s * (s + 1) // 2
    router = tokens * n["moe"] * n["d"] * n["E"]
    by = weight_bytes(cfg) + tokens * kv_bytes_per_token(cfg) + batch * n["vocab"] * 2
    return Work("prefill", 2 * linear, 2 * (n["layers"] * (expand + attend) + router), by)


def decode_work(cfg: Config, batch: int, pos: int,
                experts_used: Optional[float] = None) -> Work:
    """One decode step of ``batch`` lanes at position ``pos``: the weights
    once, of the held experts those a live lane's token chose
    (``experts_used`` per expert layer, else the expectation over every
    lane), the cache's ``pos + 1`` rows read and one written per lane, the
    logits."""
    n = dims(cfg)
    w = token_work(cfg, pos, logits=True)
    used = expected_experts_used(cfg, batch) if experts_used is None else experts_used
    by = (weight_bytes(cfg, used) + batch * (pos + 1) * kv_bytes_per_token(cfg)
          + batch * n["vocab"] * 2)
    return Work("decode", batch * w.int8_ops, batch * w.bf16_ops, by)
