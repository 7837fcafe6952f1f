#!/usr/bin/env python
"""What the serving check of ``chip_smoke.py`` reads under rounding noise and
under planted faults.

    python scripts/serve_logit_gap.py          # on a TPU host, from the repo root

The check runs qwen2-0.5b's int8 serving steps (prefill, then one decode step)
on the chip and on the host CPU.  It reads max|error| over max|value| of the
prefill logits, the decode logits and, layer by layer, the prefill KV cache,
against ``chip_smoke.SERVE_REL_TOL``.  This script prints those readings for
variants of the chip's steps, against the same CPU reference:

* sound steps (as the check runs them, and with the layers unrolled, which the
  one-layer faults need);
* float32 instead of bfloat16 activations, on the chip and on the CPU: how
  far a change of precision alone moves the readings;
* planted faults, one at a time: the causal mask dropped in the prefill, and
  the new token's KV-cache write skipped in the decode step, in layer 0,
  layer 12 or every layer.

A sound chip must read below the bound and a faulty one above it; the
last column counts the cache entries ``chip_smoke.kv_write_faults``
flags, which it checks exactly.  A fault is planted by wrapping one model
function while the step traces; no source file changes.  Weights and prompts
are those of ``chip_smoke.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import sys
from pathlib import Path
from typing import Callable, Iterator, List, NamedTuple, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import chip_smoke  # noqa: E402


class Variant(NamedTuple):
    name: str
    on_ref: bool = False             # run on the reference device instead
    f32: bool = False                # float32 activations
    unrolled: bool = False           # layers unrolled, not scanned
    prefill_fault: Optional[Callable[[], contextlib.AbstractContextManager]] = None
    decode_fault: Optional[Callable[[], contextlib.AbstractContextManager]] = None


@contextlib.contextmanager
def _wrapped(module, name: str, layers: Optional[set], fault: Callable) -> Iterator[None]:
    """Replace ``module.name`` by ``fault(orig, *args, **kwargs)`` on the calls
    whose index is in ``layers`` (every call when None).  One call per layer
    when the layers are unrolled; one call for all of them under a scan."""
    orig = getattr(module, name)
    calls = itertools.count()

    def call(*args, **kwargs):
        i = next(calls)
        if layers is None or i in layers:
            return fault(orig, *args, **kwargs)
        return orig(*args, **kwargs)

    setattr(module, name, call)
    try:
        yield
    finally:
        setattr(module, name, orig)


def mask_off(layers: Optional[set] = None):
    """Prefill attention without its causal mask."""
    from repro.models import attention

    return lambda: _wrapped(attention, "full_attention", layers,
                            lambda orig, *a, **k: orig(*a, **{**k, "causal": False}))


def kv_write_skipped(layers: Optional[set] = None):
    """A decode step that never writes the new token's row into the KV cache:
    its attention and the cache it returns both lack it."""
    from repro.models import attention

    return lambda: _wrapped(attention, "kv_write", layers, lambda orig, entry, *a: entry)


def variants(n_layers: int) -> List[Variant]:
    mid = {n_layers // 2}
    return [
        Variant("sound"),
        Variant("sound, layers unrolled", unrolled=True),
        Variant("float32 activations, on the CPU", on_ref=True, f32=True),
        Variant("float32 activations", f32=True),
        Variant("causal mask off, layer 0", unrolled=True, prefill_fault=mask_off({0})),
        Variant(f"causal mask off, layer {min(mid)}", unrolled=True,
                prefill_fault=mask_off(mid)),
        Variant("causal mask off, every layer", prefill_fault=mask_off()),
        Variant("kv write skipped, layer 0", unrolled=True,
                decode_fault=kv_write_skipped({0})),
        Variant(f"kv write skipped, layer {min(mid)}", unrolled=True,
                decode_fault=kv_write_skipped(mid)),
        Variant("kv write skipped, every layer", decode_fault=kv_write_skipped()),
    ]


def _to_f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def readings(cfg, engine, batch, device, ref_device, backend: str,
             vs: List[Variant]) -> List[tuple]:
    """``(name, prefill logits, decode logits, prefill cache, KV-cache write
    faults)`` readings for each variant against the sound steps on
    ``ref_device`` under ``xla``."""
    from repro.serve.engine import make_decode_step, make_prefill_step

    def steps(cfg_, flags_, backend_, v: Variant):
        prefill = jax.jit(make_prefill_step(cfg_, flags_, max_len=engine.max_len,
                                            backend=backend_))
        decode = jax.jit(make_decode_step(cfg_, flags_, backend=backend_))
        # the faults are planted while each step traces (its first call)

        def planted(step, fault):
            def run(*args):
                with fault() if fault else contextlib.nullcontext():
                    return step(*args)
            return run

        return planted(prefill, v.prefill_fault), planted(decode, v.decode_fault)

    sound = Variant("reference")
    want = chip_smoke.serve_steps(*steps(cfg, engine.flags, "xla", sound), engine.params,
                                   batch, ref_device, cfg.vocab_size)
    out = []
    for v in vs:
        cfg_v = dataclasses.replace(cfg, dtype="float32") if v.f32 else cfg
        params = _to_f32(engine.params) if v.f32 else engine.params
        flags_v = dataclasses.replace(engine.flags, scan_layers=not v.unrolled)
        dev, be = (ref_device, "xla") if v.on_ref else (device, backend)
        got = chip_smoke.serve_steps(*steps(cfg_v, flags_v, be, v), params, batch, dev,
                                      cfg.vocab_size, token=want.token)
        out.append((v.name, chip_smoke.rel_err(got.logits, want.logits),
                    chip_smoke.rel_err(got.step_logits, want.step_logits),
                    chip_smoke.cache_rel_err(got.cache, want.cache),
                    len(chip_smoke.kv_write_faults(got))))
        print(f"{v.name:36s} prefill {out[-1][1]:.4f}  decode {out[-1][2]:.4f}  "
              f"prefill cache {out[-1][3]:.4f}  kv-write faults {out[-1][4]}", flush=True)
    return out


def main() -> int:
    from repro.configs import get_config
    from repro.launch.serve import build_engine, synthetic_requests

    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"serve_logit_gap: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    cfg = get_config(chip_smoke.SERVE_ARCH)
    with jax.default_device(dev):
        engine = build_engine(cfg, backend="pallas",
                              max_len=chip_smoke.PROMPT_LEN + chip_smoke.NEW_TOKENS)
    batch = engine.pack(synthetic_requests(cfg, chip_smoke.SERVE_REQUESTS,
                                           chip_smoke.PROMPT_LEN, chip_smoke.NEW_TOKENS))
    print(f"{cfg.name} on {dev.device_kind} against the host CPU, as max|err| over "
          f"max|value| (chip_smoke bound {chip_smoke.SERVE_REL_TOL})", flush=True)
    readings(cfg, engine, batch, dev, jax.devices("cpu")[0], "pallas", variants(cfg.n_layers))
    return 0


if __name__ == "__main__":
    sys.exit(main())
