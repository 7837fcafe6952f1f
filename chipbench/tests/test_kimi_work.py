"""Work counts of the kimi-k2 configuration against hand sums of its shapes."""
import json

import pytest

from chipbench.harness import BENCH, load_module

KIMI = load_module(BENCH / "configs" / "kimi-k2.py")


def test_kimi_share_of_a_chip():
    """One chip's share of Kimi-K2: 497.5M int8 parameters in each of its 5
    layers (attention with its latent projections, and either the dense MLP
    or 8 held experts and the shared one), 1,152 cache bytes a layer a
    position."""
    c = json.loads((BENCH / "configs" / "kimi-k2.json").read_text())
    p = KIMI.param_counts(c)
    attn = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    assert p["attn"] == 5 * attn
    assert p["dense"] == 3 * 7168 * 18432
    assert p["experts"] + p["shared"] == 4 * 9 * 3 * 7168 * 2048
    per_layer = (p["attn"] + p["dense"] + p["experts"] + p["shared"]) / 5
    assert per_layer == pytest.approx(497.5e6, rel=1e-3)
    assert KIMI.kv_bytes_per_token(c) == 5 * 1152
    # a token uses 8 x 8/384 held experts on average; at batch 32 a layer's
    # held experts are chosen by at least one token with 1-(1-8/384)^32
    assert KIMI.expected_experts_used(c, 32) == pytest.approx(8 * (1 - (1 - 8 / 384) ** 32))
    full = KIMI.decode_work(c, 32, 8191)
    fewer = KIMI.decode_work(c, 32, 8191, experts_used=1.0)
    assert full.bytes - fewer.bytes == pytest.approx(
        4 * (KIMI.expected_experts_used(c, 32) - 1) * (3 * 7168 * 2048 + 4 * (2 * 2048 + 7168)))
