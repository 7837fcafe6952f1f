"""ResNet-18 on the program's path, and the work it requires.

Weights and images are made on the device from the seed, one jitted call
each, as int32 arrays that hold the int8 range: the form
``models/resnet.forward`` takes.  The forward goes through the Program API
(``api.trace`` then ``api.compile`` under the kernel backend) and the window
calls the ``Executor`` that returns, as ``chip_smoke.py`` does.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.traffic import seed32
from chipbench.work import Work, total

Config = Dict[str, Any]
ACT_BYTES = 4  # activations are int32 from the stem conv on


def program_config(cfg: Config):
    from repro.models import resnet

    stem = cfg["stem"]
    if (stem["conv_kernel"], stem["conv_stride"], stem["pool"], stem["pool_window"]) != (3, 1, "max", 2):
        raise ValueError(f"models/resnet.forward runs a 3x3/1 stem and a 2x2 max pool, not {stem}")
    return resnet.ResNetConfig(
        in_channels=cfg["in_channels"], input_hw=stem["input_hw"],
        stem_channels=stem["channels"], stem_pool="max",
        stage_channels=tuple(cfg["stage_channels"]),
        blocks_per_stage=tuple(cfg["blocks_per_stage"]),
        num_classes=cfg["num_classes"], input_bits=cfg["input_bits"],
        weight_bits=cfg["weight_bits"])


def blocks(cfg: Config) -> List[Tuple[int, int, int, int, bool]]:
    """(stage, c_in, c_out, stride, projection) of each BasicBlock, in order."""
    out, c_in = [], cfg["stem"]["channels"]
    for si, (c_out, n) in enumerate(zip(cfg["stage_channels"], cfg["blocks_per_stage"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            out.append((si, c_in, c_out, stride, stride != 1 or c_in != c_out))
            c_in = c_out
    return out


def weight_shapes(cfg: Config) -> Dict[str, Any]:
    """The parameter tree ``models/resnet.forward`` reads, as shapes."""
    stages: List[List[Dict[str, Tuple[int, ...]]]] = [[] for _ in cfg["stage_channels"]]
    for si, c_in, c_out, _, proj in blocks(cfg):
        b = {"conv1": (c_out, c_in, 3, 3), "conv2": (c_out, c_out, 3, 3)}
        if proj:
            b["proj"] = (c_out, c_in, 1, 1)
        stages[si].append(b)
    return {"stem": (cfg["stem"]["channels"], cfg["in_channels"], 3, 3), "stages": stages,
            "head": (cfg["stage_channels"][-1], cfg["num_classes"])}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _randint_tree(shapes, key, bits: int):
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    lim = 2 ** (bits - 1)

    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(leaves))
        return [jax.random.randint(kk, s, -lim + 1, lim, jnp.int32) for kk, s in zip(ks, leaves)]

    return jax.tree_util.tree_unflatten(treedef, make(key))


def make_params(cfg: Config, seed: int):
    return _randint_tree(weight_shapes(cfg), jax.random.key(seed32(seed, "weights")),
                         cfg["weight_bits"])


def make_inputs(cfg: Config, batch: int, n: int, seed: int) -> List[jax.Array]:
    hw = cfg["stem"]["input_hw"]
    shape = (batch, cfg["in_channels"], hw, hw)
    return _randint_tree([shape] * n, jax.random.key(seed32(seed, "images")), cfg["input_bits"])


def compile_forward(cfg: Config, params, x, backend: str):
    """The program's compiled forward: ``(params, x) -> logits``."""
    from repro.kernels import api
    from repro.models import resnet

    rcfg = program_config(cfg)
    traced = api.trace(lambda p, xx: resnet.forward(rcfg, p, xx), name="resnet18")
    with api.use_backend(backend):
        return api.compile(traced.trace(params, x))


def kernel_calls(cfg: Config, batch: int) -> List[Work]:
    """The registry kernel calls of one forward, in order, each with the
    model's work: 2 x multiply-adds on the int8 path (int8 weights; the
    activations reach 32 bits, which the chip's int8 peak does not credit),
    and bytes of its input, weights and output read or written once (images
    and weights 1 byte, activations int32)."""
    wb = -(-cfg["weight_bits"] // 8)
    calls: List[Work] = []

    def conv(c_in, c_out, hw, k, stride, in_bytes=ACT_BYTES):
        out = (hw + 2 * (k // 2) - k) // stride + 1
        macs = batch * out * out * c_out * c_in * k * k
        calls.append(Work("conv2d", 2 * macs, 0,
                          batch * c_in * hw * hw * in_bytes + c_out * c_in * k * k * wb
                          + batch * c_out * out * out * ACT_BYTES))
        return out

    def ewise(name, n_in, elems, out_elems=None):
        out_elems = elems if out_elems is None else out_elems
        calls.append(Work(name, 0, 0, (n_in * elems + out_elems) * ACT_BYTES))

    c, hw = cfg["stem"]["channels"], cfg["stem"]["input_hw"]
    hw = conv(cfg["in_channels"], c, hw, 3, 1, in_bytes=-(-cfg["input_bits"] // 8))
    ewise("relu", 1, batch * c * hw * hw)
    ewise("maxpool2d", 1, batch * c * hw * hw, batch * c * (hw // 2) ** 2)
    hw //= 2
    for _, c_in, c_out, stride, proj in blocks(cfg):
        h1 = conv(c_in, c_out, hw, 3, stride)
        ewise("relu", 1, batch * c_out * h1 * h1)
        conv(c_out, c_out, h1, 3, 1)
        if proj:
            conv(c_in, c_out, hw, 1, stride)
        ewise("ewise_add", 2, batch * c_out * h1 * h1)
        ewise("relu", 1, batch * c_out * h1 * h1)
        hw = h1
    c = cfg["stage_channels"][-1]
    ewise("global_avgpool", 1, batch * c * hw * hw, batch * c)
    n = cfg["num_classes"]
    calls.append(Work("int_matmul", 2 * batch * c * n, 0,
                      batch * c * ACT_BYTES + c * n * wb + batch * n * ACT_BYTES))
    return calls


def forward_work(cfg: Config, batch: int) -> Work:
    return total(kernel_calls(cfg, batch), "forward")
