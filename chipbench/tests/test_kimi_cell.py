"""The kimi-k2 cell end to end on the CPU at a tiny size, through the same
``run_cell`` the command runs: a sound run is correct and its per-layer
readers read it; a decode step that loses its latent-cache write, a program
that ignores the router's correction bias, and the int4 control are not
correct.

``tiny.spec`` cuts only the configurations it knows, so ``spec`` here cuts
this one: tiny widths, 3 layers, 16 experts of which 4 are held, a
vocabulary of 1000, and float32 (in bfloat16 at these widths rounding flips
int8 roundings and near-tied expert choices so often that a sound run's
logits stray by up to 0.7 from the reference's).  There a sound run reads a
widest logit gap of 0.000 (seeds 0-5), so the tests hold the gap to
``TINY_GAP_LIMIT``; there a program that ignores the bias reads 0.17-1.44,
one that loses its latent writes 1.7-3.9 and the int4 control 2.5-4.2.  The
share of tokens more than the configuration's tolerance below the best keeps
the chip's limit (lost latent writes read 36-52%).  The chip's limits are
set from full-size readings (PERF.md)."""
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests import tiny

CELL = "kimi-k2.agent-8k"
TINY_GAP_LIMIT = 0.01


def spec():
    """The cell cut to a CPU's size, with outputs of 8-16 tokens, so that a
    decode fault has tokens to show in."""
    s = harness.CellSpec(CELL)
    s.cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                 num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 num_hidden_layers=3, n_routed_experts=4, vocab_size=1000,
                 torch_dtype="float32",
                 run_flags=dict(s.cfg["run_flags"], attn_chunk=8, flash_threshold=8))
    s.cfg["published"] = dict(s.cfg["published"], n_routed_experts=16)
    s.cfg["limits"] = dict(s.cfg["limits"], max_logit_gap=TINY_GAP_LIMIT)
    s.mix.update(batch=2, max_len=48, check_requests=2, trace_seconds=tiny.WINDOW_S,
                 prompt=dict(median=16, sigma=0.5, min=8, max=32, round="pow2", cycle=4),
                 new_tokens=dict(median=12, sigma=0.5, min=8, max=16))
    return s


def run(seed, **kw):
    return tiny.run(CELL, seed=seed, spec_=spec(), backend="xla", **kw)


def _latent_write_lost(cell):
    """The decode step advances its position but keeps the latent cache it
    was given: no latent or rotary key is ever written after the prefill."""
    eng, decode = cell.engine, cell.engine.decode_step

    def step(p, cache, tok):
        new, logits = decode(p, cache, tok)
        return dict(new, blocks=cache["blocks"], dense_blocks=cache["dense_blocks"]), logits

    eng.decode_step = step


def _bias_ignored(cell):
    """The router's correction bias left out of the served parameters."""
    ffn = cell.engine.params["blocks"]["00_mla"]["ffn"]
    ffn["router"] = dict(ffn["router"], bias=jnp.zeros_like(ffn["router"]["bias"]))


def test_sound_run_is_correct_and_its_readers_read_it():
    out = run(seed=2**40 + 7, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert 0 < m["held_expert_use.kimi"]["value"] <= 100
    assert 0 < m["lane_use.kimi"]["value"] <= 100
    assert m["decode_gap_p95_ms.kimi"]["value"] > 0
    assert m["mfu.kimi"]["value"] > 0
    # no device plane on the CPU: the readers of device time read nothing
    assert "decode_step_roofline.kimi" not in m and "prefill_ms_per_ktok.kimi" not in m


@pytest.mark.parametrize("fault", [_latent_write_lost, _bias_ignored])
def test_fault_is_not_correct(fault):
    assert run(seed=4)["correct"]
    out = run(seed=4, patch=fault)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT


def test_lost_latent_writes_fail_the_token_share():
    out = run(seed=5, patch=_latent_write_lost)
    c = out["checks"]["mismatched_tokens_pct"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [0, 1])
def test_control_is_not_correct(seed):
    out = run(seed=seed, control_bits=4)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT
