"""The one traffic generator: reads a mix file ``chipbench/traffic/<mix>.json``.

A mix is data only (see the files beside this one).  Every size a run uses
comes from fixed quantiles of the mix's distributions, so every seed gets the
same multiset of sizes; the seed only orders them and draws the contents
(token ids, pixels).  That keeps the work of a window the same from seed to
seed, while no two seeds send the same inputs.

Length distributions are lognormal, given by ``median`` and ``sigma`` and
clipped to ``[min, max]``; ``"round": "pow2"`` rounds a length up to a power
of two, and ``"round": n`` up to a multiple of ``n``.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, NamedTuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> Dict[str, Any]:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _entropy(seed: int, stream: str) -> List[int]:
    return [abs(int(seed)), int(seed < 0), *stream.encode()]


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(_entropy(seed, stream))


def seed32(seed: int, stream: str) -> int:
    """A 32-bit seed per (seed, stream), for ``jax.random.key`` (which keeps
    only the low 32 bits of a larger one)."""
    return int(np.random.SeedSequence(_entropy(seed, stream)).generate_state(1)[0])


def lognormal_quantiles(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified quantiles ((i + 0.5) / n) of the clipped, rounded
    lognormal ``dist``, ascending."""
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        v = dist["median"] * math.exp(dist["sigma"] * z)
        v = min(max(v, dist["min"]), dist["max"])
        r = dist.get("round")
        if r == "pow2":
            v = 2 ** math.ceil(math.log2(v))
        elif r:
            v = r * math.ceil(v / r)
        out.append(int(round(v)))
    return out


class ServeBatch(NamedTuple):
    """One closed-loop batch: every prompt has ``prompt_len`` tokens (the
    engine left-pads a batch to its longest prompt without masking the pad,
    so a batch is formed from prompts of one length)."""

    prompt_len: int
    prompts: np.ndarray          # (batch, prompt_len) int32
    new_tokens: List[int]        # per request


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    """The distinct prompt lengths of a serving mix (the prefill shapes)."""
    return sorted(set(lognormal_quantiles(mix["prompt"], mix["prompt"]["cycle"])))


def serve_batches(mix: Dict[str, Any], seed: int, vocab: int) -> Iterator[ServeBatch]:
    """Endless closed-loop batches.  Prompt lengths go round a cycle of
    ``prompt.cycle`` quantiles, reshuffled each lap; each batch's output
    lengths are the ``batch`` quantiles of ``new_tokens`` in a seeded order."""
    rng = rng_for(seed, "serve")
    cycle = lognormal_quantiles(mix["prompt"], mix["prompt"]["cycle"])
    outs = lognormal_quantiles(mix["new_tokens"], mix["batch"])
    while True:
        for s in rng.permutation(cycle):
            prompts = rng.integers(2, vocab, (mix["batch"], int(s)), dtype=np.int32)
            yield ServeBatch(int(s), prompts, [int(n) for n in rng.permutation(outs)])
