"""The program's own spans, name scopes and counters.

``ServeEngine.run`` and ``Executor.__call__`` mark their phases with
``jax.profiler.TraceAnnotation`` host spans; the Program replay, the kernel
dispatch and the transformer's steps name their device ops with
``jax.named_scope``; ``ServeEngine.counters`` counts decode lane-steps.
Everything here runs on the CPU at tiny sizes.
"""
import glob
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels import api
from repro.models import resnet
from repro.models.runtime import RunFlags
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServeEngine, make_decode_step

FLAGS = RunFlags(attn_chunk=8, flash_threshold=64)
MICRO = resnet.ResNetConfig(
    in_channels=2, input_hw=8, stem_channels=4, stem_pool="max",
    stage_channels=(4,), blocks_per_stage=(1,), num_classes=5,
)


@pytest.fixture(scope="module")
def engine():
    cfg = reduced_config(get_config("qwen2-0.5b"))
    return ServeEngine(cfg, init_params(jax.random.key(0), cfg), FLAGS, max_len=32)


def requests(new_tokens, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(2, 200, size=5).astype(np.int32), max_new_tokens=n)
            for i, n in enumerate(new_tokens)]


@pytest.fixture(scope="module")
def program():
    params = resnet.init_params(MICRO, seed=0)
    x = resnet.make_input(MICRO, batch=1, seed=1)
    traced = api.trace(lambda p, v: resnet.forward(MICRO, p, v), name="rn-micro")
    with api.use_backend("xla"):
        ex = api.compile(traced.trace(params, x))
    return ex, params, x


def test_serve_counters_follow_the_requests_lengths(engine):
    before = engine.counters.decode_steps, engine.counters.lane_steps, engine.counters.useful_lane_steps
    served = []
    for lengths in ([2, 5, 3], [4, 1, 4]):
        reqs = engine.run(requests(lengths))
        assert [len(r.generated) for r in reqs] == lengths
        served.append(reqs)
    c = engine.counters
    steps = sum(max(len(r.generated) for r in reqs) - 1 for reqs in served)
    assert c.decode_steps - before[0] == steps == 4 + 3
    assert c.lane_steps - before[1] == 3 * steps
    assert c.useful_lane_steps - before[2] == sum(len(r.generated) - 1 for reqs in served for r in reqs)


def test_counters_skip_requests_already_done(engine):
    c0 = engine.counters.useful_lane_steps
    reqs = requests([3, 3])
    reqs[1].done = True
    engine.run(reqs)
    assert len(reqs[0].generated) == 3 and reqs[1].generated == []
    assert engine.counters.useful_lane_steps - c0 == 2


def test_program_replay_names_each_node_and_its_executable(program):
    ex, params, x = program
    text = jax.jit(lambda p, v: ex(p, v)).lower(params, x).compile().as_text()
    assert "jit(rn_micro)" in text
    for idx, kernel in enumerate(ex.program.kernels):
        assert f"/n{idx}/{kernel}/" in text, (idx, kernel)


@pytest.mark.parametrize("name, ident", [("resnet18", "resnet18"), ("rn-micro", "rn_micro"),
                                         ("<lambda>", "_lambda_"), ("8x8", "_8x8"), ("", "program")])
def test_program_name_becomes_an_identifier(name, ident):
    from repro.kernels.program import _identifier

    assert _identifier(name) == ident


def test_decode_step_scopes_its_parts(engine):
    cfg = engine.cfg
    cache, _ = engine.prefill_step(engine.params, engine.pack(requests([2, 2])))
    step = jax.jit(make_decode_step(cfg, FLAGS))
    text = step.lower(engine.params, cache, np.zeros((2, 1), np.int32)).compile().as_text()
    assert "jit(decode_step)" in text
    for scope in ("/attn/kv_write/", "/attn/", "/ffn/", "/lm_head/"):
        assert scope in text, scope


def test_latent_attention_and_expert_steps_scope_their_parts():
    """Kimi's steps: the expanded and absorbed forms of latent attention,
    the cache write, the three parts of an expert layer and the dense
    layer's ``ffn``."""
    from repro.serve.engine import make_prefill_step

    cfg = reduced_config(get_config("kimi-k2-1t-a32b"))
    eng = ServeEngine(cfg, init_params(jax.random.key(0), cfg), FLAGS, max_len=32)
    batch = eng.pack(requests([2, 2]))
    text = jax.jit(make_prefill_step(cfg, FLAGS, max_len=32)).lower(eng.params, batch).compile()
    assert "/attn/mla_expand/" in text.as_text()
    cache, _ = eng.prefill_step(eng.params, batch)
    step = jax.jit(make_decode_step(cfg, FLAGS))
    text = step.lower(eng.params, cache, np.zeros((2, 1), np.int32)).compile().as_text()
    for scope in ("/attn/mla_absorb/", "/attn/kv_write/", "/moe/route/", "/moe/experts/",
                  "/moe/shared/", "/ffn/", "/lm_head/"):
        assert scope in text, scope


def _spans(log_dir):
    """Host spans ``(name, start, end)`` and the executables the device ran,
    from the one xplane file under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans, modules = [], Counter()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "program.")):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                stats = dict(e.stats)
                if "hlo_module" in stats and not e.name.startswith("end:"):
                    modules[stats["hlo_module"]] += 1
    return spans, modules


def test_profiler_trace_holds_the_program_spans(engine, program, tmp_path):
    ex, params, x = program
    jax.block_until_ready(ex(params, x))
    engine.run(requests([2, 2]))
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = engine.run(requests([3, 1, 2]))
        for _ in range(2):
            jax.block_until_ready(ex(params, x))
    finally:
        jax.profiler.stop_trace()
    spans, modules = _spans(tmp_path)
    names = Counter(n for n, _, _ in spans)
    steps = max(len(r.generated) for r in reqs) - 1
    assert names["serve.run"] == 1 and names["serve.pack"] == 1 and names["serve.prefill"] == 1
    assert names["serve.decode"] == steps == 2
    assert names["serve.sample"] == steps + 1          # after the prefill and each decode step
    assert names["serve.retire"] == steps + 1          # the last one finds every lane done
    assert names["program.call"] == 2
    (run,) = [(s, e) for n, s, e in spans if n == "serve.run"]
    for n, s, e in spans:
        if n.startswith("serve.") and n != "serve.run":
            assert run[0] <= s <= e <= run[1], n
    # the executables carry the step's or the program's name
    assert {"jit_prefill_step", "jit_decode_step", "jit_rn_micro"} <= set(modules)
