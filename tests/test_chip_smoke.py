"""The serving check of ``chip_smoke.py`` and the readings behind its bound,
at reduced qwen2-0.5b width on the CPU (``xla`` on both sides)."""
import jax
import pytest

import chip_smoke
from repro.configs import get_config, reduced_config
from scripts import serve_logit_gap as gap


@pytest.fixture(scope="module")
def small():
    from repro.launch.serve import build_engine, synthetic_requests

    cfg = reduced_config(get_config(chip_smoke.SERVE_ARCH))
    engine = build_engine(cfg, backend="xla",
                          max_len=chip_smoke.PROMPT_LEN + chip_smoke.NEW_TOKENS)
    batch = engine.pack(synthetic_requests(cfg, chip_smoke.SERVE_REQUESTS,
                                           chip_smoke.PROMPT_LEN, chip_smoke.NEW_TOKENS))
    return cfg, engine, batch


def test_check_serving_passes_on_matching_steps(capsys):
    cpu = jax.devices("cpu")[0]
    chip_smoke.check_serving("xla", cpu, cpu, cfg=reduced_config(get_config(chip_smoke.SERVE_ARCH)))
    assert "prefill 0, decode 0, prefill cache 0 " in capsys.readouterr().out


# (variant, readings above the bound, KV-cache entries written wrongly).  A
# mask fault in the last layer (layer 1 here) changes nothing that is served:
# the logits read only the last position, which the causal mask does not
# restrict, and the layer's cached keys and values are computed before its
# attention.
@pytest.mark.parametrize("name, above, kv_faults", [
    ("sound, layers unrolled", set(), 0),
    ("float32 activations", set(), 0),
    ("causal mask off, layer 0", {"prefill", "decode", "prefill cache"}, 0),
    ("causal mask off, layer 1", set(), 0),
    ("causal mask off, every layer", {"prefill", "decode", "prefill cache"}, 0),
    ("kv write skipped, layer 1", set(), 2),
    ("kv write skipped, every layer", set(), 4),
])
def test_serving_check_reads_planted_faults(small, name, above, kv_faults):
    cfg, engine, batch = small
    cpu = jax.devices("cpu")[0]
    (v,) = [v for v in gap.variants(cfg.n_layers) if v.name == name]
    ((_, *errs, faults),) = gap.readings(cfg, engine, batch, cpu, cpu, "xla", [v])
    read = dict(zip(("prefill", "decode", "prefill cache"), errs))
    assert {k for k, e in read.items() if e > chip_smoke.SERVE_REL_TOL} == above, read
    assert faults == kv_faults
