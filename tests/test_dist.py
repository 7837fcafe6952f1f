"""Distribution layer: sharding rules (divisibility fallbacks), collective
schedules on a multi-device subprocess, HLO collective parsing, dry-run cell
on a small forced-device mesh."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import MeshRules, param_specs
from repro.launch.hlo_analysis import parse_collectives, roofline_terms
from repro.models.transformer import params_shape


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


def _rules():
    return MeshRules(mesh=_FakeMesh({"data": 16, "model": 16}), dp_axes=("data",))


def test_param_specs_divisibility_fallbacks():
    rules = _rules()
    cfg = get_config("qwen2-0.5b")  # 14 heads, kv=2: both !% 16
    shapes = params_shape(cfg)
    specs = param_specs(shapes, cfg, rules)
    blk = specs["blocks"]["00_attn"]
    # stacked leaves are (G, d_in, d_out): group axis never sharded
    assert blk["attn"]["wq"]["w"] == P(None, None, None), "14 q-heads must replicate"
    assert blk["attn"]["wk"]["w"] == P(None, None, None), "2 kv-heads must replicate"
    assert blk["ffn"]["w_gate"]["w"] == P(None, None, "model")
    assert blk["ffn"]["w_down"]["w"] == P(None, "model", None)
    assert any("replicated" in d for d in rules.decisions)


def test_param_specs_moe_and_dense():
    rules = _rules()
    cfg = get_config("kimi-k2-1t-a32b")  # 64 heads, 384 experts: divisible
    shapes = params_shape(cfg)
    specs = param_specs(shapes, cfg, rules)
    blk = specs["blocks"]["00_mla"]
    # latent attention: heads column-parallel out of the latents, which replicate
    assert blk["attn"]["wq_b"]["w"] == P(None, None, "model")
    assert blk["attn"]["wkv_b"]["w"] == P(None, None, "model")
    assert blk["attn"]["wq_a"]["w"] == P(None, None, None)
    assert blk["attn"]["wo"]["w"] == P(None, "model", None)
    assert blk["ffn"]["w_gate"]["w"] == P(None, "model", None, None)  # (G, E, d, f)
    assert blk["ffn"]["shared"]["w_down"]["w"] == P(None, "model", None)
    assert blk["ffn"]["router"]["w"] == P(None, None, None)
    # the leading dense layer: a plain MLP, d_ff column-parallel
    dense = specs["dense_blocks"]["00_mla"]
    assert dense["ffn"]["w_gate"]["w"] == P(None, None, "model")
    assert specs["embed"]["w"] == P("model", None)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "kimi-k2-1t-a32b"])
def test_cache_specs_cover_every_cache_leaf(arch):
    """A spec for every leaf of the decode cache: Kimi's dense-layer stack,
    its expert counter and live-lane mask too."""
    from repro.configs import reduced_config
    from repro.models.transformer import cache_shape
    from repro.serve.engine import cache_specs

    cfg = reduced_config(get_config(arch))
    shapes = cache_shape(cfg, 32, 64)
    specs = cache_specs(cfg, 32, 64, _rules())
    paired = jax.tree_util.tree_map(lambda sh, sp: (sh.ndim, sp), shapes, specs)
    assert paired["pos"] == (0, P())
    if cfg.is_moe:
        assert set(specs) == {"pos", "blocks", "dense_blocks", "expert_slots_used", "live_lanes"}


def test_batch_axis_fallbacks():
    rules = _rules()
    assert rules.batch_axes(256) == ("data",)
    assert rules.batch_axes(1) is None  # long_500k: replicate batch


# ---------------------------------------------------------------------------
# collective property tests (hypothesis-stub) against the inter-chip link
# cost model — the same ChipCluster closed forms the multi-chip plan chooser
# scores before committing to a sharding
# ---------------------------------------------------------------------------

from repro.core import isa  # noqa: E402
from repro.core.machine import PIMSAB  # noqa: E402
from repro.core.noc import ChipCluster  # noqa: E402
from repro.core.simulator import Simulator  # noqa: E402
from repro.kernels.multichip import _wrap_int32, resolve_cluster  # noqa: E402
from tests._hypothesis_stub import given, settings, st  # noqa: E402


@settings(max_examples=30)
@given(st.sampled_from((2, 3, 4, 6, 8)), st.integers(32, 2**20))
def test_link_cost_model_properties(chips: int, bits: int):
    cluster = resolve_cluster(chips, None)
    assert cluster.chips == chips
    port = cluster.allreduce_port_bits(bits)
    # each port moves the classic (N-1)/N of the payload, twice (RS + AG)
    assert 0 < port < bits
    assert port >= bits // chips
    ar = cluster.allreduce_cycles(bits)
    assert ar >= 2 * cluster.link.stream_cycles(port)
    # monotone in payload: the plan chooser may safely binary-search sizes
    assert cluster.allreduce_cycles(2 * bits) >= ar
    # latency pipelines but never disappears
    assert cluster.allreduce_rounds() >= 1
    assert ar >= cluster.link.latency_cycles * (cluster.allreduce_rounds() + 1)
    # p2p monotone in both distance and payload
    far = cluster.chips - 1
    assert cluster.p2p_cycles(0, far, bits) >= cluster.p2p_cycles(0, 0, bits)
    assert cluster.p2p_cycles(0, far, 2 * bits) >= cluster.p2p_cycles(0, far, bits)


@settings(max_examples=20)
@given(st.sampled_from((2, 4, 8)), st.integers(0, 2**31 - 1))
def test_host_wrap_allreduce_matches_int32_oracle(chips: int, seed: int):
    """The cluster executor's host allreduce (int64 partial sum + mod-2^32
    wrap) must equal both the sequential int32 wrap accumulation a single
    chip performs and the jnp int32 oracle — addition mod 2^32 is
    associative, which is the whole bit-exactness argument for K-sharding."""
    rng = np.random.default_rng(seed)
    parts = rng.integers(-2**31, 2**31, (chips, 6, 5), dtype=np.int64)
    host = _wrap_int32(parts.sum(axis=0))
    acc = np.zeros((6, 5), np.int32)
    for p in parts:
        acc = _wrap_int32(acc.astype(np.int64) + p)
    assert np.array_equal(host, acc)
    oracle = np.asarray(
        jax.numpy.sum(jax.numpy.asarray(parts.astype(np.int32)), axis=0))
    assert np.array_equal(host, oracle)


def test_allreduce_closed_form_matches_scheduled_timeline():
    """The plan chooser's closed-form allreduce cost is exactly what the
    simulator schedules when the same rounds run as ChipSend/ChipRecv."""
    for chips, bits in ((2, 4096), (4, 65536), (8, 1 << 18)):
        cluster = resolve_cluster(chips, None)
        cfg = cluster.timing_cfg(PIMSAB)
        port = cluster.allreduce_port_bits(bits)
        sim = Simulator(cfg)
        sim.step(isa.ChipSend(chip=0, peer=-1, bits=port, rounds=1,
                              phase="x:ar:c0", tag="ar"))
        sim.step(isa.ChipRecv(chip=0, peer=-1, bits=port,
                              rounds=cluster.allreduce_rounds(), sync=True,
                              phase="ar.done", after=("x:ar:c0",), tag="ar"))
        assert sim.res.makespan == pytest.approx(cluster.allreduce_cycles(bits))


MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.dist.collectives import (
        htree_allreduce, ring_allgather_matmul, compressed_psum_with_feedback, shuffle,
    )
    mesh = jax.make_mesh((8,), ("model",))
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = htree_allreduce(x, mesh, "model")
    want = jnp.tile(x.reshape(8, 1, 4).sum(0), (8, 1)).reshape(8, 4)
    assert np.allclose(np.asarray(out), np.asarray(want)), "htree"

    k = jax.random.key(0)
    a = jax.random.normal(k, (16, 32))
    w = jax.random.normal(jax.random.key(1), (32, 24))
    y = ring_allgather_matmul(a, w, mesh, "model")
    assert np.allclose(np.asarray(y), np.asarray(a @ w), atol=1e-3), "ring matmul"

    g = jax.random.normal(jax.random.key(2), (64,))
    err = jnp.zeros((64,))
    red, new_err = compressed_psum_with_feedback(g, err, mesh, ("model",))
    # replicated input: mean-reduce returns ~the same vector, error bounded
    assert np.allclose(np.asarray(red), np.asarray(g), atol=0.05), "compressed psum"
    assert float(jnp.abs(new_err).max()) <= float(jnp.abs(g).max()) / 127 + 1e-6

    # shuffle (all-to-all) vs the single-device block-transpose oracle
    z = jnp.arange(8 * 8 * 3, dtype=jnp.int32).reshape(8 * 8, 3)
    sh = shuffle(z, mesh, "model", split_dim=0)
    want_sh = np.asarray(z).reshape(8, 8, 1, 3).transpose(1, 0, 2, 3).reshape(8 * 8, 3)
    assert np.array_equal(np.asarray(sh), want_sh), "shuffle"

    # int32 htree allreduce wraps exactly like the single-device wrap-sum
    rng = np.random.default_rng(3)
    xi = jnp.asarray(rng.integers(-2**31, 2**31, (8, 4), dtype=np.int64).astype(np.int32))
    oi = htree_allreduce(xi, mesh, "model")
    want_i = ((np.asarray(xi).astype(np.int64).sum(0) + 2**31) % 2**32 - 2**31).astype(np.int32)
    assert np.array_equal(np.asarray(oi), np.tile(want_i, (8, 1))), "int32 htree"
    print("MULTIDEV_OK")
    """
)


@pytest.mark.slow
def test_collectives_multidevice_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr


DRYRUN_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json, dataclasses
    import jax
    from repro.configs import reduced_config, get_config
    from repro.configs.base import ShapeCell
    from repro.dist.sharding import MeshRules
    from repro.launch.specs import input_specs
    from repro.launch.hlo_analysis import parse_collectives
    from repro.models.runtime import RunFlags
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(reduced_config(get_config("internlm2-20b")), n_heads=4, n_kv_heads=4)
    cell = ShapeCell("tiny_train", "train", 32, 8)
    mesh = jax.make_mesh((4, 4), ("data", "model"))
    rules = MeshRules.from_mesh(mesh)
    flags = RunFlags(attn_chunk=16, flash_threshold=64)
    specs = input_specs(cfg, cell, rules, flags)
    step = make_train_step(cfg, flags, rules)
    with mesh:
        compiled = jax.jit(step).lower(specs["state"], specs["batch"]).compile()
        stats = parse_collectives(compiled.as_text())
        mem = compiled.memory_analysis()
    assert stats.total_operand_bytes > 0, "TP training must emit collectives"
    assert mem.argument_size_in_bytes > 0
    print("DRYRUN_OK", stats.total_operand_bytes)
    """
)


@pytest.mark.slow
def test_tiny_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", DRYRUN_SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=900,
    )
    assert "DRYRUN_OK" in r.stdout, r.stdout + r.stderr


def test_hlo_collective_parser():
    hlo = """
  %all-reduce.1 = f32[1024,512]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[4096]{0} all-gather(%y), replica_groups=[32,8]<=[256], dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups=[8,4]<=[32], dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    stats = parse_collectives(hlo)
    assert stats.counts == {"all-reduce": 1, "all-gather": 1, "reduce-scatter": 1, "collective-permute": 1}
    assert stats.operand_bytes["all-reduce"] == 1024 * 512 * 4
    assert stats.operand_bytes["all-gather"] == 4096 * 2 // 8
    assert stats.operand_bytes["reduce-scatter"] == 128 * 4 * 4
    assert stats.operand_bytes["collective-permute"] == 64 * 64 * 2
    rl = roofline_terms(1e12, 1e9, stats, model_flops_per_device=5e11)
    assert rl.dominant in ("compute", "memory", "collective")
    assert 0 < rl.useful_ratio <= 1
