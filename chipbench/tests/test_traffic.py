"""The traffic generator: fixed sizes for every seed, contents from the seed."""
import numpy as np
import pytest

from chipbench import traffic


def batches(mix, seed, n):
    src = traffic.serve_batches(mix, seed, vocab=1000)
    return [next(src) for _ in range(n)]


CYCLING = {"batch": 4, "max_len": 640,
           "prompt": {"median": 256, "sigma": 0.5, "min": 128, "max": 512, "round": "pow2",
                      "cycle": 8},
           "new_tokens": {"median": 32, "sigma": 0.8, "min": 8, "max": 128}}


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = CYCLING
    cycle = mix["prompt"]["cycle"]
    a, b = batches(mix, 1, cycle), batches(mix, 2**40 + 3, cycle)
    assert sorted(x.prompt_len for x in a) == sorted(x.prompt_len for x in b)
    assert [x.prompt_len for x in a] != [x.prompt_len for x in b]
    for x, y in zip(a, b):
        assert sorted(x.new_tokens) == sorted(y.new_tokens)
        assert x.prompts.shape == (mix["batch"], x.prompt_len)
    assert not np.array_equal(a[0].prompts[:, :8], b[0].prompts[:, :8])


@pytest.mark.parametrize("mix", ["decode", "cycling"])
def test_same_seed_same_inputs_and_sizes_within_the_mix(mix):
    mix = CYCLING if mix == "cycling" else traffic.load_mix(mix)
    a, b = batches(mix, 7, 5), batches(mix, 7, 5)
    for x, y in zip(a, b):
        assert x.prompt_len == y.prompt_len and np.array_equal(x.prompts, y.prompts)
    lens = traffic.prompt_lengths(mix)
    assert all(x.prompt_len in lens for x in a)
    assert max(lens) + max(traffic.lognormal_quantiles(mix["new_tokens"], mix["batch"])) \
        <= mix["max_len"]
    assert all(((x.prompts >= 2) & (x.prompts < 1000)).all() for x in a)


def test_decode_mix_sizes():
    """The decode cell's sizes: every prompt 1024 tokens, outputs the 64
    quantiles of lognormal(129, 0.8) clipped to 16-512."""
    mix = traffic.load_mix("decode")
    assert traffic.prompt_lengths(mix) == [1024]
    outs = traffic.lognormal_quantiles(mix["new_tokens"], mix["batch"])
    assert (len(outs), outs[0], outs[-1], sum(outs)) == (64, 19, 512, 10739)


def test_quantiles_clip_and_round():
    d = {"median": 100, "sigma": 2.0, "min": 16, "max": 512, "round": "pow2"}
    q = traffic.lognormal_quantiles(d, 50)
    assert q == sorted(q) and q[0] == 16 and q[-1] == 512
    assert all(v & (v - 1) == 0 for v in q)


def test_round_to_a_multiple():
    d = {"median": 1500, "sigma": 0.0, "min": 1, "max": 4096, "round": 128}
    assert traffic.lognormal_quantiles(d, 3) == [1536] * 3


def test_seeds_beyond_32_bits():
    assert traffic.seed32(2**40 + 5, "w") != traffic.seed32(5, "w")
    assert 0 <= traffic.seed32(-3, "w") < 2**32
