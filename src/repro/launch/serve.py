"""Serving launcher CLI: random-inits a model (seeded), runs the batched
engine over synthetic requests with int8 bit-sliced weights.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b [--backend pallas]

``--backend`` picks the kernel backend the jitted steps trace under:
``pallas`` (the default: the compiled TPU kernels, which raise on a host
without a TPU), ``interpret`` (the same kernel bodies in the Pallas
interpreter, for CPU checks) or ``xla`` (the pure-jnp oracles).  With int8
serving weights the transformer step calls no registry kernel (each linear is
one XLA integer dot), so there the choice changes nothing.
"""
from __future__ import annotations

import argparse
import time
from typing import List

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig
from repro.kernels import api
from repro.launch.compile_cache import setup_compile_cache
from repro.models.runtime import RunFlags
from repro.models.transformer import init_params
from repro.serve.engine import Request, ServeEngine

# the jax-side backends (pimsab executes on the host simulator, not under jit)
BACKENDS = tuple(b for b in api.BACKENDS if b != "pimsab")


def build_engine(cfg: ModelConfig, *, backend: str = "pallas", quant: bool = True,
                 max_len: int = 128) -> ServeEngine:
    """The launcher's engine: seeded random weights, int8 serving weights
    unless ``quant`` is off, kernels on ``backend``."""
    flags = RunFlags(attn_chunk=64, flash_threshold=256, quant_serve=quant)
    params = init_params(jax.random.key(0), cfg)
    return ServeEngine(cfg, params, flags, max_len=max_len, backend=backend)


def synthetic_requests(cfg: ModelConfig, n: int, prompt_len: int,
                       new_tokens: int) -> List[Request]:
    """``n`` requests with seeded uniform random in-vocabulary prompts."""
    rng = np.random.default_rng(0)
    return [
        Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, size=prompt_len).astype(np.int32),
                max_new_tokens=new_tokens)
        for i in range(n)
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--backend", choices=BACKENDS, default="pallas")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    engine = build_engine(cfg, backend=args.backend, quant=not args.no_quant)
    reqs = synthetic_requests(cfg, args.requests, 8, args.new_tokens)
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, quant_serve={engine.flags.quant_serve}, "
          f"backend={args.backend})")
    for r in done[:2]:
        print(f"  req {r.rid}: {r.generated[:8]}...")


if __name__ == "__main__":
    main()
