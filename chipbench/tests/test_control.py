"""The check's control and the faults it must catch, at tiny sizes on the
CPU: each drives a whole run, with the timed path broken underneath or the
control judged in the program's place, and sees ``correct`` come out false.

At these sizes a sound serving run reads a widest logit gap of at most
0.003, the int4 control 0.18-0.26 and a decode step that leaves its cache
unchanged 0.11-0.32 (seeds 0-5), so the serving tests hold the gap to
``TINY_GAP_LIMIT``; the chip's limit is set from full-size readings
(PERF.md).  Sound runs read no token more than 0.05 below the
reference's best here, so the share of such tokens keeps the chip's limit.
"""
import pytest

from chipbench.faults import FAULTS
from chipbench.tests import tiny

TINY_GAP_LIMIT = 0.05
CONTROL_BITS = 4   # int4: the precision below the stated int8


def serve_spec(gap_limit: float = TINY_GAP_LIMIT):
    """Outputs of 8-16 tokens, so that a decode fault has tokens to show in."""
    s = tiny.spec("qwen2-0.5b.decode")
    s.cfg["limits"] = dict(s.cfg["limits"], max_logit_gap=gap_limit)
    s.mix.update(new_tokens=dict(median=12, sigma=0.5, min=8, max=16))
    return s


@pytest.mark.parametrize("fault", sorted(FAULTS["images"]))
def test_resnet_fault_is_not_correct(fault):
    sound = tiny.run("resnet18.b64", seed=3)
    assert sound["correct"]
    out = tiny.run("resnet18.b64", seed=3, patch=FAULTS["images"][fault])
    assert not out["correct"]
    assert out["checks"]["mismatched_logits"]["value"] > 0


@pytest.mark.parametrize("seed", [3, 2**40 + 1])
def test_resnet_control_is_not_correct(seed):
    out = tiny.run("resnet18.b64", seed=seed, control_bits=CONTROL_BITS)
    assert not out["correct"]
    assert out["checks"]["mismatched_logits"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS["serve"]))
def test_serving_fault_is_not_correct(fault):
    assert tiny.run("qwen2-0.5b.decode", seed=4, spec_=serve_spec(), backend="xla")["correct"]
    out = tiny.run("qwen2-0.5b.decode", seed=4, spec_=serve_spec(), backend="xla",
                   patch=FAULTS["serve"][fault])
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT
    assert _share_over_limit(out)


def _share_over_limit(out) -> bool:
    c = out["checks"]["mismatched_tokens_pct"]
    return c["value"] > c["limit"]


def test_lost_cache_write_fails_the_token_share_alone():
    """At full size a decode step that loses its cache write reads a widest
    gap under the chip's limit; the share of mismatched tokens catches it.
    Here the gap's limit is the chip's, above what the fault reads."""
    s = serve_spec(gap_limit=1.0)
    assert tiny.run("qwen2-0.5b.decode", seed=4, spec_=s, backend="xla")["correct"]
    out = tiny.run("qwen2-0.5b.decode", seed=4, spec_=s, backend="xla",
                   patch=FAULTS["serve"]["decode_state_unchanged"])
    assert out["checks"]["max_logit_gap"]["value"] <= out["checks"]["max_logit_gap"]["limit"]
    assert not out["correct"]
    assert _share_over_limit(out)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_serving_control_is_not_correct(seed):
    out = tiny.run("qwen2-0.5b.decode", seed=seed, spec_=serve_spec(), backend="xla",
                   control_bits=CONTROL_BITS)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT
    assert _share_over_limit(out)
