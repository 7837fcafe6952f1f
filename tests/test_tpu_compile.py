"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler compiles for a described (not attached) ``v5e:2x2``
topology; each program here is compiled for its first chip, so Mosaic's
block-shape, layout and lowering rules are checked on every test run — the
rules interpret mode does not apply.  Shapes are those ``chip_smoke.py``
runs on the chip.  Nothing executes: a compile that passes is not a chip
run.

The topology is described inside a module fixture (never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file), and the persistent compilation cache is off around the
compiles (an entry written without a chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.kernels import api


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cases():
    return {c.name: c for c in chip_smoke.kernel_cases()}


def _struct(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compile_pallas(fn, *args):
    with api.use_backend("pallas"):
        return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", sorted(api.registered_kernels()))
def test_registry_kernel_compiles_for_v5e(name, cases, one_chip):
    case = cases[name]
    compiled = _compile_pallas(
        lambda *a: api.dispatch(name, *a, **case.kwargs),
        *(_struct(a, one_chip) for a in case.args),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet18_program_compiles_for_v5e(one_chip):
    from repro.models import resnet

    cfg = resnet.RESNET18
    params, x = jax.tree_util.tree_map(
        lambda a: _struct(a, one_chip),
        jax.eval_shape(lambda: (resnet.init_params(cfg),
                                resnet.make_input(cfg, batch=chip_smoke.RESNET_BATCH))),
    )
    traced = api.trace(lambda p, x: resnet.forward(cfg, p, x), name="resnet18")
    with api.use_backend("pallas"):
        ex = api.compile(traced.trace(params, x))
    text = jax.jit(lambda p, x: ex(p, x)).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") >= len(resnet.layer_names(cfg))


def test_qwen2_decode_step_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.models.runtime import RunFlags
    from repro.models.transformer import cache_shape
    from repro.serve.engine import make_decode_step, serve_params_shape

    cfg = get_config(chip_smoke.SERVE_ARCH)
    flags = RunFlags(attn_chunk=64, flash_threshold=256, quant_serve=True)
    batch, max_len = chip_smoke.SERVE_REQUESTS, chip_smoke.PROMPT_LEN + chip_smoke.NEW_TOKENS
    params, cache = jax.tree_util.tree_map(
        lambda a: _struct(a, one_chip),
        (serve_params_shape(cfg, flags), cache_shape(cfg, batch, max_len, flags)),
    )
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    step = make_decode_step(cfg, flags, backend="pallas")
    compiled = jax.jit(step).lower(params, cache, tokens).compile()
    assert compiled.memory_analysis() is not None


def test_kimi_decode_step_compiles_for_v5e(one_chip):
    """One chip's share of Kimi-K2 at published widths (the benchmark's
    configuration): the absorbed-MLA decode step over a batch of 32 with an
    8,704-position latent cache fits the chip."""
    import json

    from chipbench.harness import BENCH, load_module
    from repro.models.runtime import RunFlags
    from repro.models.transformer import cache_shape
    from repro.serve.engine import make_decode_step

    kimi = load_module(BENCH / "configs" / "kimi-k2.py")
    cfgj = json.loads((BENCH / "configs" / "kimi-k2.json").read_text())
    mix = json.loads((BENCH / "traffic" / "agent-8k.json").read_text())
    cfg, flags = kimi.program_config(cfgj), RunFlags(**cfgj["run_flags"])
    params, cache = jax.tree_util.tree_map(
        lambda a: _struct(a, one_chip),
        (jax.eval_shape(lambda: kimi.make_params(cfgj, 0)),
         cache_shape(cfg, mix["batch"], mix["max_len"], flags)),
    )
    tokens = jax.ShapeDtypeStruct((mix["batch"], 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_decode_step(cfg, flags, backend="pallas")).lower(
        params, cache, tokens).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes < 16e9


def test_resnet18_activations_stay_channels_last_on_v5e(one_chip):
    """The benchmark's ResNet-18 forward (112x112 images, a 2x2 stem max
    pool) compiled for v5e: outside the Pallas calls no instruction makes an
    activation in its (N, C, H, W) shape, the form whose (8, 128) tiles pad W
    up to 128 lanes (the program's input is the only one), none lays an
    (N, H, W, C) activation out with C off the minor dimension (XLA's
    channels-major relayout around a conv whose row width is no whole tile),
    and nothing loops (the window matrix of the pool's old path filled a
    ``while``).  The wrappers' transposes to and from channels-last rows
    cancel."""
    import json
    import re

    from chipbench.harness import BENCH, load_module
    from repro.models import resnet

    rn = load_module(BENCH / "configs" / "resnet18.py")
    cfg = rn.program_config(json.loads((BENCH / "configs" / "resnet18.json").read_text()))
    n = chip_smoke.RESNET_BATCH
    params, x = jax.tree_util.tree_map(
        lambda a: _struct(a, one_chip),
        jax.eval_shape(lambda: (resnet.init_params(cfg), resnet.make_input(cfg, batch=n))),
    )
    traced = api.trace(lambda p, x: resnet.forward(cfg, p, x), name="resnet18")
    with api.use_backend("pallas"):
        ex = api.compile(traced.trace(params, x))
    text = jax.jit(lambda p, x: ex(p, x)).lower(params, x).compile().as_text()
    hw = cfg.input_hw
    nchw = {(n, cfg.stem_channels, hw, hw)}
    hw //= 2
    for i, c in enumerate(cfg.stage_channels):
        hw //= 2 if i else 1
        nchw.add((n, c, hw, hw))
    nhwc = {(n, h, w, c) for n, c, h, w in nchw}
    made, channels_major = [], []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\{([\d,]+)", line)
        if m and "tpu_custom_call" not in line:
            dims = tuple(int(d) for d in m.group(2).split(","))
            if dims in nchw:
                made.append(m.group(1))
            if dims in nhwc and not m.group(3).startswith("3,"):
                channels_major.append(m.group(1))
    assert not made, made
    assert not channels_major, channels_major
    assert text.count("tpu_custom_call") >= len(resnet.layer_names(cfg))
    assert " while(" not in text
