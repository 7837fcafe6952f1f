"""Every block kind and model form through the three entry points, at tiny
widths in float32: a decode step on token ``s`` after ``prefill`` of ``s``
tokens gives the last-position logits of ``prefill`` of ``s + 1`` tokens, and
``forward``'s last logits are ``prefill``'s.

The prompt (6 tokens, 7 with the decoded one) stays inside recurrentgemma's
reduced window of 8, so the windowed cache is padded, not cropped.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models.runtime import RunFlags
from repro.models.transformer import decode_step, forward, init_params, prefill

FLAGS = RunFlags(attn_chunk=8, flash_threshold=64)
S = 6

# case: (arch, flags, largest |error| over largest |logit| a decode step may read)
CASES = {
    "attn": ("qwen2-0.5b", FLAGS, 1e-5),
    "local_attn+rglru": ("recurrentgemma-2b", FLAGS, 1e-5),
    "mlstm+slstm": ("xlstm-1.3b", FLAGS, 1e-5),
    "mla+moe+dense_lead": ("kimi-k2-1t-a32b", FLAGS, 1e-5),
    "encoder_decoder": ("whisper-medium", FLAGS, 1e-5),
    "vision_prefix": ("phi-3-vision-4.2b", FLAGS, 1e-5),
    # the decode step reads keys and values rounded to int8
    "quant_kv": ("qwen2-0.5b", dataclasses.replace(FLAGS, quant_kv=True), 0.05),
}


def _batch(cfg, tokens):
    b = tokens.shape[0]
    out = {"tokens": tokens}
    key = jax.random.key(3)
    if cfg.is_encdec:
        out["enc_embeds"] = jax.random.normal(key, (b, cfg.enc_seq_len, cfg.d_model))
    if cfg.frontend == "vision":
        out["patch_embeds"] = jax.random.normal(key, (b, cfg.n_patches, cfg.d_model))
    return out


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_continues_prefill_and_forward_matches_prefill(case):
    arch, flags, tol = CASES[case]
    cfg = dataclasses.replace(reduced_config(get_config(arch)), dtype="float32")
    params = init_params(jax.random.key(1), cfg)
    toks = jax.random.randint(jax.random.key(2), (2, S + 1), 2, cfg.vocab_size)

    @jax.jit
    def run(params, toks):
        cache, last = prefill(params, cfg, _batch(cfg, toks[:, :S]), flags, max_len=S + 2)
        cache, stepped = decode_step(params, cfg, cache, toks[:, S:], flags)
        _, want = prefill(params, cfg, _batch(cfg, toks), flags, max_len=S + 2)
        logits, _ = forward(params, cfg, _batch(cfg, toks[:, :S]), flags)
        return cache["pos"], stepped, want, logits[:, -1], last

    pos, stepped, want, forward_last, last = run(params, toks)
    assert int(pos) == S + 1
    assert _rel(stepped, want) < tol, _rel(stepped, want)
    assert _rel(forward_last, last) < 1e-5, _rel(forward_last, last)
