"""Kimi-K2 (DeepSeek-V3's layer) on the serving path: multi-head latent
attention, YaRN, a leading dense layer, and the parameter counts of the
published entry.  The MoE layer's own tests are in ``test_moe.py``.

Everything runs on the CPU at a reduced size on seeded random weights; the
serving comparison uses the benchmark's configuration side and its plain
reference (``chipbench/configs/kimi-k2.py`` and ``kimi-k2.reference.py``).
"""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import BENCH, load_module
from repro.configs import get_config, reduced_config
from repro.models import mla
from repro.models.common import yarn_freqs
from repro.models.runtime import RunFlags
from repro.models.transformer import init_params, prefill
from repro.serve.engine import Request, ServeEngine

KIMI = load_module(BENCH / "configs" / "kimi-k2.py")
REFERENCE = load_module(BENCH / "configs" / "kimi-k2.reference.py")
FLAGS = RunFlags(attn_chunk=8, flash_threshold=8)


def tiny_cfg(dtype="float32"):
    """The benchmark's configuration file at a CPU size: every width cut,
    16 routed experts of which 4 held, top-8 as published."""
    cfg = json.loads((BENCH / "configs" / "kimi-k2.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3,
               n_routed_experts=4, vocab_size=512, torch_dtype=dtype,
               run_flags=dict(cfg["run_flags"], attn_chunk=8, flash_threshold=8))
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    return cfg


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_then_latent_decode_match_the_reference_logits(seed):
    """Prefill of 16 tokens, then 8 decode steps through the latent cache on
    ``ServeEngine`` (int8 weights and activations, ``xla`` backend), against
    the reference's full forward over the same 24 tokens.

    In float32 the two compute the same integers and agree to float32
    rounding: most logits within 1e-4.  A logit may move by up to about
    0.05 where float32 rounding flips one activation's int8 rounding, or a
    near-tie between two experts' scores, somewhere earlier in its sequence,
    so the widest gap is held to 0.1.  A lost cache write or a wrong
    absorption moves logits by O(1).  (In bfloat16 such flips are common
    at this size, and the chip's check, with its own limits, covers it.)"""
    cfg = tiny_cfg()
    params = KIMI.make_params(cfg, seed)
    engine = KIMI.make_engine(cfg, params, 32, "xla")
    toks = np.random.default_rng(seed).integers(2, cfg["vocab_size"], (2, 24)).astype(np.int32)
    cache, lg = engine.prefill_step(engine.params, {"tokens": jnp.asarray(toks[:, :16])})
    served = [lg]
    for i in range(16, 23):
        cache, lg = engine.decode_step(engine.params, cache, jnp.asarray(toks[:, i:i + 1]))
        served.append(lg)
    served = np.stack([np.asarray(s)[:, : cfg["vocab_size"]] for s in served], axis=1)
    with jax.default_matmul_precision("highest"):
        ref = np.stack([np.asarray(REFERENCE.logits(cfg, params, jnp.asarray(t), 8))
                        for t in toks])[:, 15:23]
    gap = np.abs(served - ref)
    assert np.median(gap) < 1e-4
    assert gap.max() < 0.1
    assert int(cache["pos"]) == 23


def _f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


def test_absorbed_decode_equals_expanded_attention_in_float32():
    """The decode form (``kv_b`` absorbed, attending over the latent cache)
    gives the sequence form's outputs at every position, in float32."""
    cfg = _f32(reduced_config(get_config("kimi-k2-1t-a32b")))
    p = init_params(jax.random.key(0), cfg)["blocks"]["00_mla"]["attn"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    b, s = 2, 16
    x = jax.random.normal(jax.random.key(1), (b, s, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, entries = mla.mla_attention(p, x, cfg, FLAGS, jnp.arange(s)[None])
        entry = mla.cache_entry(cfg, b, s, jnp.float32)
        got = []
        for t in range(s):
            y, entry = mla.mla_decode(p, x[:, t:t + 1], cfg, entry, t)
            got.append(y)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(entry["c_kv"], entries["c_kv"], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(entry["k_pe"], entries["k_pe"], rtol=1e-6, atol=1e-6)


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` inverse frequencies,
    written out from the published modelling code in numpy."""
    def correction_dim(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    return freq_inter * (1 - mask) + freq_extra * mask


@pytest.mark.parametrize("beta_fast", [1.0, 32.0])  # Kimi-K2's, DeepSeek-V3's
def test_yarn_frequencies_match_the_published_formula(beta_fast):
    y = dataclasses.replace(get_config("kimi-k2-1t-a32b").yarn, beta_fast=beta_fast)
    got = yarn_freqs(64, 50_000.0, y)
    want = _published_yarn(64, 50_000.0, y.factor, y.original_max_position, y.beta_fast, y.beta_slow)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    # the low dims keep their frequency, the high ones are interpolated by the factor
    extra = 1.0 / 50_000.0 ** (np.arange(0, 64, 2) / 64)
    assert got[0] == pytest.approx(extra[0]) and got[-1] == pytest.approx(extra[-1] / y.factor)


def test_softmax_scale_carries_yarn_mscale_squared():
    cfg = get_config("kimi-k2-1t-a32b")
    mscale = 0.1 * math.log(32) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)


def test_published_entry_parameter_counts():
    """1.03T parameters, 32.9B active, from the published config."""
    cfg = get_config("kimi-k2-1t-a32b")
    assert cfg.param_count() == pytest.approx(1.0264e12, rel=1e-3)
    assert cfg.active_param_count() == pytest.approx(3.286e10, rel=1e-3)


def test_blocked_prefill_writes_the_same_cache():
    cfg = _f32(reduced_config(get_config("kimi-k2-1t-a32b")))
    params = init_params(jax.random.key(2), cfg)
    toks = jax.random.randint(jax.random.key(3), (4, 16), 2, cfg.vocab_size)
    whole = prefill(params, cfg, {"tokens": toks}, FLAGS, max_len=20)
    blocked = prefill(params, cfg, {"tokens": toks}, dataclasses.replace(FLAGS, prefill_block=2),
                      max_len=20)
    for a, b in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(blocked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_engine_counts_the_held_experts_a_token_chose():
    cfg = reduced_config(get_config("kimi-k2-1t-a32b"))
    engine = ServeEngine(cfg, init_params(jax.random.key(0), cfg), FLAGS, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(2, 200, 6).astype(np.int32), max_new_tokens=n)
            for i, n in enumerate([3, 5])]
    engine.run(reqs)
    c = engine.counters
    assert c.decode_steps == 4
    assert c.expert_slots == cfg.n_held_experts * cfg.moe_layers * 4
    assert 0 < c.expert_slots_used <= c.expert_slots
    assert c.expert_slots_by_run == [(c.expert_slots, c.expert_slots_used)]


def test_a_retired_lanes_pad_tokens_count_no_held_expert():
    """Lane 0 retires after its first token and decodes the pad token from
    then on: the batch counts the held experts that lane 1's tokens alone
    chose, as lane 1 served alone does."""
    cfg = _f32(reduced_config(get_config("kimi-k2-1t-a32b")))
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 200, 6).astype(np.int32) for _ in range(2)]

    def served(lanes):
        engine = ServeEngine(cfg, params, FLAGS, max_len=32)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=n) for i, n in lanes]
        engine.run(reqs)
        return engine.counters, reqs

    both, reqs = served([(0, 1), (1, 6)])
    alone, solo = served([(1, 6)])
    assert reqs[1].generated == solo[0].generated
    assert both.decode_steps == alone.decode_steps == 5
    assert both.expert_slots == alone.expert_slots
    assert both.expert_slots_used == alone.expert_slots_used
