"""On-chip smoke check: the TPU-native path through its normal entry points.

    python chip_smoke.py

Runs in one process on one TPU chip and exits non-zero at the first failure:

1. device   -- refuses to run unless JAX's first device is a TPU;
2. kernels  -- every registry kernel under the ``pallas`` backend at
   ResNet18 / qwen2-0.5b widths, checked against its oracle on the host CPU
   (integer kernels bit-exact, float kernels allclose); each jitted call
   must contain a ``tpu_custom_call``;
3. resnet18 -- ``resnet.RESNET18`` at batch 8 through ``api.trace`` +
   ``api.compile`` under ``pallas``, bit-exact against the ``xla`` forward;
4. serving  -- ``ServeEngine`` for qwen2-0.5b at full width with int8
   serving weights answers 4 requests (128-token prompts, 16 new tokens);
   its prefill logits are checked against the same step on the host CPU.

The last line of stdout is one JSON object naming the device.  Weights and
data are random, made from fixed seeds; nothing is downloaded.  The timings
printed are one-off smoke timings, not metrics.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

I32 = (np.iinfo(np.int32).min, np.iinfo(np.int32).max)


class KernelCase(NamedTuple):
    """One registry kernel call: operands (host arrays), static kwargs, and
    the tolerance it is held to (``None``: bit-exact)."""

    name: str
    args: Tuple[np.ndarray, ...]
    kwargs: Dict[str, Any]
    tol: Optional[float]


def kernel_cases(seed: int = 0) -> List[KernelCase]:
    """One case per registry kernel at ResNet18 (batch 8, CIFAR scale,
    1000-class head) and qwen2-0.5b (d_model 896, d_ff 4864, head_dim 64)
    widths.  Integer operands without a precision hint span the whole int32
    range, so the bit-exact check covers int32 wraparound."""
    rng = np.random.default_rng(seed)

    def ints(shape, lo, hi, dtype=np.int32):
        return rng.integers(lo, hi, shape, dtype=np.int64).astype(dtype)

    def normal(shape):
        return rng.standard_normal(shape, dtype=np.float32)

    onehot = np.zeros(1024, np.int32)
    onehot[517] = 1
    act = (8, 64, 32, 32)  # ResNet18 stage-1 activation at batch 8
    return [
        KernelCase("bitslice_matmul",
                   (ints((2, 256, 896), -128, 128, np.int8),
                    ints((2, 896, 896), -128, 128, np.int8)), {}, None),
        KernelCase("htree_reduce", (normal((128, 896)),), {}, 1e-5),
        KernelCase("rglru_scan",
                   (1.0 / (1.0 + np.exp(-normal((2, 1024, 2560)))),
                    normal((2, 1024, 2560)), normal((2, 2560))), {}, 1e-4),
        KernelCase("ewise_add", (ints(act, *I32), ints(act, *I32)), {}, None),
        KernelCase("relu", (ints(act, *I32),), {}, None),
        KernelCase("conv2d", (ints(act, *I32), ints((64, 64, 3, 3), *I32)),
                   {"stride": 1, "padding": 1}, None),
        KernelCase("int_matmul",
                   (ints((256, 512), -(1 << 11), 1 << 11), ints((512, 1000), -3, 4)),
                   {"x_bits": 12, "w_bits": 3}, None),
        KernelCase("maxpool2d", (ints(act, *I32),), {"window": 2}, None),
        KernelCase("avgpool2d", (ints(act, -(1 << 24), 1 << 24),), {"window": 2}, None),
        KernelCase("global_avgpool", (ints((8, 512, 4, 4), -(1 << 24), 1 << 24),),
                   {}, None),
        KernelCase("attention_qk",
                   (ints((128, 64), -128, 128), ints((1024, 64), -128, 128)),
                   {"q_bits": 8, "k_bits": 8}, None),
        KernelCase("softmax_fixedpoint", (ints((128, 1024), -4000, 4000),),
                   {"in_frac": 7}, None),
        KernelCase("attention_pv",
                   (ints((128, 1024), 0, 65), ints((1024, 64), *I32)), {}, None),
        KernelCase("decode_gemv", (ints((4864, 896), *I32), ints((896,), *I32)),
                   {}, None),
        KernelCase("kv_append",
                   (ints((1024, 64), *I32), ints((64,), *I32), onehot), {}, None),
    ]


# The serving steps on the chip against the same steps on the host CPU, each
# read as max|error| over the largest CPU value: the prefill and decode
# logits, and the prefill KV cache layer by layer.  Both sides run bfloat16
# activations quantized to int8 per row, so one rounding difference flips a
# quantization step and 24 layers amplify it.  scripts/serve_logit_gap.py
# prints the readings; on a v5e chip (PERF.md, PR 11) sound steps read at
# most 0.0631 (logits) and 0.0782 (cache), float32 activations 0.0695 and
# 0.0854, while the causal mask dropped in layer 12 reads 0.297 on the cache
# (0.0955 on the logits) and in layer 0 above 1.  The bound sits between.
SERVE_REL_TOL = 0.15
RESNET_BATCH = 8
SERVE_ARCH, SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS = "qwen2-0.5b", 4, 128, 16


def _compare(got: np.ndarray, want: np.ndarray, tol: Optional[float]) -> str:
    """Raise unless ``got`` matches ``want`` (bit-exact when ``tol`` is None)."""
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"got {got.dtype}{got.shape}, want {want.dtype}{want.shape}")
    if tol is None:
        bad = int(np.count_nonzero(got != want))
        if bad:
            raise AssertionError(f"{bad} of {want.size} elements differ")
        return "bit-exact"
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    return f"max|err| {float(np.max(np.abs(got - want))):.3g} (tol {tol})"


def _jitted(name: str, kwargs: Dict[str, Any], backend: str) -> Callable:
    from repro.kernels import api

    def call(*args):
        with api.use_backend(backend):
            return api.dispatch(name, *args, **kwargs)

    return jax.jit(call)


def check_kernels(backend: str, device, ref_device, cases: List[KernelCase]) -> None:
    """Each case under ``backend`` on ``device`` against its oracle (the
    ``xla`` backend) on ``ref_device``."""
    for c in cases:
        args = [jax.device_put(a, device) for a in c.args]
        lowered = _jitted(c.name, c.kwargs, backend).lower(*args)
        if backend == "pallas" and "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{c.name}: no tpu_custom_call in the jitted call")
        got = np.asarray(lowered.compile()(*args))
        want = np.asarray(_jitted(c.name, c.kwargs, "xla")(
            *(jax.device_put(a, ref_device) for a in c.args)))
        shapes = " ".join("x".join(map(str, a.shape)) for a in c.args)
        print(f"kernel {c.name:18s} ok  {shapes:28s} {_compare(got, want, c.tol)}",
              flush=True)


def check_resnet(backend: str, device, ref_device, cfg=None, batch: int = RESNET_BATCH) -> None:
    """ResNet18 through ``api.trace`` + ``api.compile`` under ``backend`` on
    ``device``, bit-exact against the same traced forward under ``xla`` on
    ``ref_device``."""
    from repro.kernels import api
    from repro.models import resnet

    cfg = cfg or resnet.RESNET18
    params = resnet.init_params(cfg, seed=0)
    x = resnet.make_input(cfg, batch=batch, seed=1)
    traced = api.trace(lambda p, x: resnet.forward(cfg, p, x), name="resnet18")

    def run(backend_, dev):
        p, xd = jax.device_put((params, x), dev)
        with api.use_backend(backend_):
            ex = api.compile(traced.trace(p, xd))
        return ex, p, xd

    ex, p, xd = run(backend, device)
    lowered = jax.jit(lambda p, x: ex(p, x)).lower(p, xd)
    if backend == "pallas" and "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("resnet18: no tpu_custom_call in the compiled Program")
    t0 = time.perf_counter()
    got = jax.block_until_ready(ex(p, xd))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(ex(p, xd)))
    t_call = time.perf_counter() - t0
    ref_ex, p_ref, x_ref = run("xla", ref_device)
    want = np.asarray(ref_ex(p_ref, x_ref))
    n_kernels = len(ex.program.ops)
    print(f"resnet18 ok  batch {batch}, {n_kernels} kernels, logits "
          f"{got.dtype}{got.shape} {_compare(got, want, None)} vs xla; "
          f"one-off smoke timing, not a metric: compile+first call {t_first:.2f}s, "
          f"one call {t_call * 1e3:.2f}ms", flush=True)


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """The serving check's reading: max|got - want| over max|want|."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class ServeOut(NamedTuple):
    """One prefill step and one decode step after it, fed ``token``."""

    logits: np.ndarray       # prefill logits (batch, padded vocabulary)
    token: np.ndarray        # (batch, 1)
    step_logits: np.ndarray  # decode step logits
    cache: Any               # the prefill's KV cache
    step_cache: Any          # the decode step's KV cache


def serve_steps(prefill: Callable, decode: Callable, params, batch, device, vocab: int,
                 token: Optional[np.ndarray] = None) -> ServeOut:
    """Run the steps on ``device``; ``token`` is by default the prefill's
    greedy pick over the vocabulary."""
    params, batch = jax.device_put((params, batch), device)
    cache, logits = prefill(params, batch)
    logits = np.asarray(logits, np.float32)
    if token is None:
        token = np.argmax(logits[:, :vocab], axis=-1).astype(np.int32)[:, None]
    step_cache, step = decode(params, cache, jax.device_put(token, device))
    return ServeOut(logits, token, np.asarray(step, np.float32),
                    jax.device_get(cache), jax.device_get(step_cache))


def cache_rel_err(got: Any, want: Any) -> float:
    """The prefill KV cache's reading: the largest ``rel_err`` of one
    layer's keys or values.  A fault in layer l moves layer l+1's cache at
    every position, where the last-token logits see it only through the
    last position."""
    return max(rel_err(np.asarray(g[layer], np.float32), np.asarray(w[layer], np.float32))
               for key, entry in want["blocks"].items() for n in ("k", "v")
               for g, w in [(got["blocks"][key][n], entry[n])]
               for layer in range(w.shape[0]))


def kv_write_faults(out: ServeOut) -> List[str]:
    """Where the decode step wrote the attention KV cache wrongly.  In every
    layer it must keep the prefill's rows bit for bit, write row ``pos``
    (the new token's) and leave the later rows empty.  Exact, unlike the
    logits: no rounding enters."""
    pos = int(out.cache["pos"])
    faults = []
    for key, entry in out.step_cache["blocks"].items():
        for n in ("k", "v"):
            old, new = out.cache["blocks"][key][n], entry[n]  # (layers, B, T, ...)
            for layer in range(new.shape[0]):
                if not (np.array_equal(old[layer, :, :pos], new[layer, :, :pos])
                        and new[layer, :, pos].any() and not new[layer, :, pos + 1:].any()):
                    faults.append(f"{key}/{n} layer {layer}")
    return faults


def check_serving(backend: str, device, ref_device, cfg=None) -> None:
    """``ServeEngine`` the way ``launch/serve.py`` builds it answers the
    requests.  Its prefill logits and KV cache, and the logits of one decode
    step after it, are checked against the same steps on the host CPU, and
    the decode step's KV-cache write exactly."""
    from repro.configs import get_config
    from repro.launch.serve import build_engine, synthetic_requests
    from repro.serve.engine import make_decode_step, make_prefill_step

    cfg = cfg or get_config(SERVE_ARCH)
    t0 = time.perf_counter()
    with jax.default_device(device):
        engine = build_engine(cfg, backend=backend, max_len=PROMPT_LEN + NEW_TOKENS)
        jax.block_until_ready(engine.params)
    t_init = time.perf_counter() - t0
    reqs = synthetic_requests(cfg, SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS)
    batch = engine.pack(reqs)

    ref_prefill = jax.jit(make_prefill_step(cfg, engine.flags, max_len=engine.max_len,
                                            backend="xla"))
    ref_decode = jax.jit(make_decode_step(cfg, engine.flags, backend="xla"))
    want = serve_steps(ref_prefill, ref_decode, engine.params, batch, ref_device,
                        cfg.vocab_size)
    got = serve_steps(engine.prefill_step, engine.decode_step, engine.params, batch,
                       device, cfg.vocab_size, token=want.token)
    errs = {"prefill": rel_err(got.logits, want.logits),
            "decode": rel_err(got.step_logits, want.step_logits),
            "prefill cache": cache_rel_err(got.cache, want.cache)}
    if not (np.isfinite(got.logits).all() and np.isfinite(got.step_logits).all()):
        raise AssertionError("serving: non-finite logits")
    for what, err in errs.items():
        if not err <= SERVE_REL_TOL:
            raise AssertionError(f"serving: {what} off the CPU's by {err:.3g} of its "
                                 f"max|value| (tol {SERVE_REL_TOL})")
    faults = kv_write_faults(got)
    if faults:
        raise AssertionError(f"serving: decode step's KV-cache write wrong in {faults}")

    t0 = time.perf_counter()
    done = engine.run(reqs)
    t_run = time.perf_counter() - t0
    for r in done:
        toks = np.asarray(r.generated)
        if len(toks) != NEW_TOKENS or not ((toks >= 0) & (toks < cfg.vocab_size)).all():
            raise AssertionError(f"serving: request {r.rid} got {r.generated}")
    readings = ", ".join(f"{what} {err:.3g}" for what, err in errs.items())
    print(f"serving {cfg.name} ok  {len(done)} requests x {PROMPT_LEN}-token prompts, "
          f"{NEW_TOKENS} in-vocabulary tokens each; vs CPU, as max|err| over "
          f"max|value|: {readings} (tol {SERVE_REL_TOL}); "
          f"KV-cache write exact in {cfg.n_layers} layers; one-off smoke timing, not a "
          f"metric: init {t_init:.2f}s, run incl. compile {t_run:.2f}s", flush=True)


def main() -> int:
    # the references run on the host CPU in this process, so a platform list
    # that names only the accelerator gets the CPU appended (after it)
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache

    print(f"device {dev.device_kind} x{len(jax.devices())} ({dev.platform}); "
          f"compile cache {setup_compile_cache()}", flush=True)
    cpu = jax.devices("cpu")[0]
    check_kernels("pallas", dev, cpu, kernel_cases())
    check_resnet("pallas", dev, cpu)
    check_serving("pallas", dev, cpu)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
