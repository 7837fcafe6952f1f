"""MoE layer: dropless dispatch over the held experts equals a dense
per-token reference; routing groups, skewed routing and the sigmoid router's
correction bias; and the experts' shares add up to the whole layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.common import swiglu
from repro.models.moe import moe_ffn, moe_init, route


def _routing(router, xf, cfg):
    """Each token's top-k experts and their weights, written out from the
    published recipes: softmax over the top-k logits; or sigmoid scores,
    top-k of scores plus correction bias, the chosen experts' unbiased
    scores normalised to 1 and scaled."""
    logits = xf.astype(jnp.float32) @ router["w"]
    k = cfg.experts_per_token
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + router["bias"], k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.routed_scaling
    top, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(top, axis=-1)


def _dense_reference(p, x, cfg):
    """Every token through each of its top-k experts, no capacity."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    idx, gates = _routing(p["router"], xf, cfg)
    out = jnp.zeros_like(xf)
    held = p["w_down"]["w"].shape[0]
    for e in range(held):
        w = {n: p[n]["w"][e] for n in ("w_gate", "w_up", "w_down")}
        ye = swiglu(xf @ w["w_gate"], xf @ w["w_up"]) @ w["w_down"]
        weight = jnp.sum(jnp.where(idx == cfg.first_held_expert + e, gates, 0.0), axis=-1)
        out = out + ye * weight[:, None].astype(ye.dtype)
    if "shared" in p:
        sp = p["shared"]
        out = out + swiglu(xf @ sp["w_gate"]["w"], xf @ sp["w_up"]["w"]) @ sp["w_down"]["w"]
    return out.reshape(b, s, d)


def _kimi(n_experts=4):
    cfg = reduced_config(get_config("kimi-k2-1t-a32b"))
    return dataclasses.replace(cfg, n_experts=n_experts)


@pytest.mark.slow
def test_moe_matches_dense_reference_with_ample_capacity():
    """Softmax routing (dbrx): the dropless layer is the dense reference."""
    cfg = reduced_config(get_config("dbrx-132b"))
    p = moe_init(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    got, aux, _ = moe_ffn(p, x, cfg, n_groups=1)
    want = _dense_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-3)
    assert float(aux) > 0


def test_moe_group_count_invariance():
    """Routing groups change dispatch locality, not the math (same tokens)."""
    cfg = _kimi()
    p = moe_init(jax.random.key(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (4, 8, cfg.d_model), jnp.float32)
    y1, _, used1 = moe_ffn(p, x, cfg, n_groups=1)
    y2, _, _ = moe_ffn(p, x, cfg, n_groups=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-3)
    assert int(used1) == cfg.n_experts  # 32 tokens, top-2 of 4: every expert chosen


@pytest.mark.parametrize("tokens", [8, 300])  # every held expert; tiles, the last part-full
def test_skewed_routing_drops_no_token(tokens):
    """Every token picks the same two experts: each gets every token, far
    past any capacity factor, and the result is still the dense one."""
    cfg = _kimi()
    p = moe_init(jax.random.key(5), cfg, jnp.float32)
    p["router"]["bias"] = jnp.array([10.0, 10.0, 0.0, 0.0])  # experts 0 and 1, always
    x = jax.random.normal(jax.random.key(6), (1, tokens, cfg.d_model), jnp.float32)
    idx, _, _ = route(p["router"], x[0], cfg)
    assert set(np.unique(np.asarray(idx)).tolist()) == {0, 1}
    got, _, used = moe_ffn(p, x, cfg, n_groups=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_dense_reference(p, x, cfg)),
                               atol=1e-4, rtol=1e-3)
    assert int(used) == 2


def test_correction_bias_picks_experts_but_does_not_weigh_them():
    cfg = _kimi(n_experts=8)
    p = moe_init(jax.random.key(7), cfg, jnp.float32)["router"]
    x = jax.random.normal(jax.random.key(8), (64, cfg.d_model), jnp.float32)
    scores = jax.nn.sigmoid(x @ p["w"])
    idx0, w0, _ = route(p, x, cfg)
    biased = dict(p, bias=jnp.linspace(0.0, 0.5, 8))  # favours the later experts
    idx1, w1, _ = route(biased, x, cfg)
    assert np.any(np.asarray(idx0) != np.asarray(idx1))  # selection moved
    assert np.mean(np.asarray(idx1)) > np.mean(np.asarray(idx0))
    for router, idx, w in ((p, idx0, w0), (biased, idx1, w1)):
        chosen = jnp.take_along_axis(scores, idx, axis=-1)  # unbiased scores of the chosen
        want = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.routed_scaling
        np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
        ref_idx, ref_w = _routing(router, x, cfg)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-6)


@pytest.mark.parametrize("tokens", [6, 300])  # both dispatches
def test_uncounted_tokens_change_nothing_and_count_for_nothing(tokens):
    """A ``counted`` mask (a server's live lanes) leaves the result as it
    is; the held experts counted are those the counted tokens alone chose."""
    cfg = _kimi(n_experts=8)
    p = moe_init(jax.random.key(11), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(12), (1, tokens, cfg.d_model), jnp.float32)
    counted = jnp.arange(tokens)[None] < 2
    y_all, _, used_all = moe_ffn(p, x, cfg, n_groups=1)
    y, _, used = moe_ffn(p, x, cfg, n_groups=1, counted=counted)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))
    _, _, used_first = moe_ffn(p, x[:, :2], cfg, n_groups=1)
    assert int(used) == int(used_first) < int(used_all)


@pytest.mark.parametrize("tokens", [12, 150])  # both dispatches: all held experts; tiles
def test_expert_shares_add_up_to_the_whole_layer(tokens):
    """Eight experts held two at a time by four chips: the four shares'
    routed parts, plus the shared expert counted once, are the uncut
    layer's output."""
    cfg = _kimi(n_experts=8)
    p = moe_init(jax.random.key(9), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(10), (2, tokens, cfg.d_model), jnp.float32)
    whole, _, _ = moe_ffn(p, x, cfg, n_groups=1)
    shared = _dense_reference(dict(p, w_gate={"w": p["w_gate"]["w"][:0]},
                                   w_up={"w": p["w_up"]["w"][:0]},
                                   w_down={"w": p["w_down"]["w"][:0]}), x, cfg)
    total = -3 * shared
    for first in range(0, 8, 2):
        share_cfg = dataclasses.replace(cfg, held_experts=2, first_held_expert=first)
        share = dict(p, **{n: {"w": p[n]["w"][first: first + 2]}
                           for n in ("w_gate", "w_up", "w_down")})
        y, _, _ = moe_ffn(share, x, share_cfg, n_groups=1)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-4, rtol=1e-3)
