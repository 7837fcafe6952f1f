"""Closed loop of image batches around a compiled forward (one client).

Set-up makes the weights and ``input_batches`` batches of ``batch`` images
on the device from the seed, compiles the configuration's forward and runs
it once on every batch.  The window calls the forward back to back on the
batches in turn, each call waited for with ``block_until_ready``.

``images_per_s`` is the images of every call in the window over the
window's length, from its start to the end of its last call.  The check
compares, exactly, the logits of ``check_images`` images from each of
``check_calls`` calls drawn from the seed among all calls of the window
with the plain reference's.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from chipbench.trace import span
from chipbench.traffic import rng_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


class Record:
    """What the window did, on the host clock (``time.perf_counter``)."""

    def __init__(self):
        self.t0 = self.t_end = 0.0
        self.calls = 0
        self.kept: List[Tuple[int, Any]] = []   # (input batch index, logits on the device)


class Cell:
    def __init__(self, config: str, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 backend: str = "pallas"):
        from chipbench.harness import load_module

        self.model = load_module(CONFIGS / f"{config}.py")
        self.reference = load_module(CONFIGS / f"{config}.reference.py")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.params = self.model.make_params(cfg, seed)
        self.batches = self.model.make_inputs(cfg, mix["batch"], mix["input_batches"], seed)
        self.forward = self.model.compile_forward(cfg, self.params, self.batches[0], backend)
        for x in self.batches:
            jax.block_until_ready(self.forward(self.params, x))
        self.record = Record()

    def window(self, seconds: float, traced: bool) -> Record:
        rec, rng = Record(), rng_for(self.seed, "check")
        k, n_in = self.mix["check_calls"], len(self.batches)
        with span("bench.window", traced):
            rec.t0 = time.perf_counter()
            stop = rec.t0 + seconds
            t = rec.t0
            while t < stop:
                i = rec.calls % n_in
                with span("bench.forward", traced):
                    with span("bench.dispatch", traced):
                        out = self.forward(self.params, self.batches[i])
                    with span("bench.wait", traced):
                        jax.block_until_ready(out)
                t = time.perf_counter()
                # reservoir sample of k calls, so every call is equally likely
                if rec.calls < k:
                    rec.kept.append((i, out))
                else:
                    j = int(rng.integers(0, rec.calls + 1))
                    if j < k:
                        rec.kept[j] = (i, out)
                rec.calls += 1
            rec.t_end = t
        self.record = rec
        return rec

    def images(self) -> int:
        return self.record.calls * self.mix["batch"]

    def end_to_end(self) -> Dict[str, float]:
        rec = self.record
        return {"images_per_s": self.images() / (rec.t_end - rec.t0)}

    def attempted_failed(self) -> Tuple[int, int]:
        return self.images(), 0

    def release(self) -> None:
        """Copy what the check needs to the host and free the device state."""
        rng = rng_for(self.seed, "check_rows")
        b = self.mix["batch"]
        self._to_check = []
        for i, out in self.record.kept:
            rows = np.sort(rng.choice(b, size=min(self.mix["check_images"], b), replace=False))
            self._to_check.append((np.asarray(self.batches[i])[rows], np.asarray(out)[rows]))
        self._params = jax.device_get(self.params)
        self.record.kept = []
        del self.params, self.batches, self.forward

    def check(self, control_bits: int = 0) -> Dict[str, Dict[str, float]]:
        """The numbers compared, each with its limit.  With ``control_bits``
        the control stands in the program's place: the reference at that
        precision on the same images."""
        if not self._to_check:
            return {}
        images = np.concatenate([x for x, _ in self._to_check])
        want = self.reference.logits(self.cfg, self._params, images)
        got = (self.reference.logits(self.cfg, self._params, images, bits=control_bits)
               if control_bits else np.concatenate([y for _, y in self._to_check]))
        bad = int(np.count_nonzero(got != want)) if got.shape == want.shape else int(want.size)
        return {"mismatched_logits": {"value": bad, "limit": self.cfg["limits"]["mismatched_logits"],
                                      "of": int(want.size)}}
