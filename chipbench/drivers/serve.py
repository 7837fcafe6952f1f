"""Closed loop of request batches around ``ServeEngine.run`` (one client).

Set-up makes the weights on the device from the seed, builds the engine and
serves one batch of each prompt length the mix uses (two tokens each), so
that every prefill shape and the decode step are compiled.  The window hands
the engine the mix's batches back to back; a batch is submitted when the
previous one has returned.

Each request's ``generated`` is a list that stamps every token as the engine
appends it: the point where ``ServeEngine.run`` hands a token to its caller,
after the host has synced on it.  The window runs whole batches: it submits
a batch while its time is not up, and ends when the last one returns.
``tokens_per_s`` is every token of those batches over the window from its
start to that end, so that where the time runs out inside a batch, which
hands out most of its tokens early, does not move it.

The check draws from the seed ``check_requests`` of the requests served,
each from another lane of the batch (with ``check_requests`` equal to the
batch, every lane), the one with the most tokens among them, and runs the
plain reference once over each prompt and its served tokens:
``max_logit_gap`` is the widest gap by which a served token's logit lies
below the reference's best at its position, and ``mismatched_tokens_pct``
the share of the checked tokens that lie below it by more than the
configuration's ``mismatch_logit_tolerance``, which passes the near-ties
that a sound bfloat16 program flips.  The widest gap swings with those
near-ties; the share is steady from seed to seed and catches a decode step
that loses its cache write, which shifts many logits by less than the
widest gap's limit.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Tuple

import numpy as np

from chipbench import traffic
from chipbench.trace import span

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


class StampedTokens(list):
    """A request's ``generated``: stamps each token as it is appended."""

    def __init__(self):
        super().__init__()
        self.stamps: List[float] = []

    def append(self, token) -> None:
        self.stamps.append(time.perf_counter())
        super().append(token)


class Step(NamedTuple):
    """One call of the engine's jitted steps, as the traced run saw it."""

    kind: str      # "prefill" | "decode"
    batch: int
    pos: int       # prompt length (prefill) or the new token's position (decode)


class Batch(NamedTuple):
    prompt_len: int
    requests: List[Any]


class Record:
    def __init__(self):
        self.t0 = self.t_end = 0.0
        self.batches: List[Batch] = []
        self.steps: List[Step] = []

    def stamps(self) -> List[float]:
        return [t for b in self.batches for r in b.requests for t in r.generated.stamps]

    def requests(self) -> List[Any]:
        return [r for b in self.batches for r in b.requests]


class Cell:
    def __init__(self, config: str, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 backend: str = "pallas"):
        from chipbench.harness import load_module

        self.model = load_module(CONFIGS / f"{config}.py")
        self.reference = load_module(CONFIGS / f"{config}.reference.py")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.params = self.model.make_params(cfg, seed)
        self.engine = self.model.make_engine(cfg, self.params, mix["max_len"], backend)
        for s in traffic.prompt_lengths(mix):
            self._serve([np.full(s, 2, np.int32)] * mix["batch"], [2] * mix["batch"])
        self.source = traffic.serve_batches(mix, seed, cfg["vocab_size"])
        self.record = Record()

    def _serve(self, prompts, new_tokens) -> List[Any]:
        from repro.serve.engine import Request

        reqs = [Request(rid=i, prompt=p, max_new_tokens=n, generated=StampedTokens())
                for i, (p, n) in enumerate(zip(prompts, new_tokens))]
        self.engine.run(reqs)
        for r in reqs:
            if len(r.generated.stamps) != len(r.generated):
                raise RuntimeError("the engine no longer hands tokens over through "
                                   "Request.generated.append; the driver cannot time them")
        return reqs

    def _wrap_steps(self, rec: Record):
        """Spans around the engine's jitted steps (traced runs only), so that
        the trace can tell prefill's device time from decode's."""
        eng, state = self.engine, {}
        prefill, decode = eng.prefill_step, eng.decode_step

        def prefill_step(params, batch):
            b, s = batch["tokens"].shape
            state["pos"] = s
            rec.steps.append(Step("prefill", b, s))
            with span("bench.prefill", True):
                return prefill(params, batch)

        def decode_step(params, cache, tokens):
            rec.steps.append(Step("decode", tokens.shape[0], state["pos"]))
            state["pos"] += 1
            with span("bench.decode", True):
                return decode(params, cache, tokens)

        eng.prefill_step, eng.decode_step = prefill_step, decode_step
        return prefill, decode

    def step_device_time(self, trace) -> List[Tuple[Step, float]]:
        """Each step of the traced window with the device busy seconds it set
        off (from its call to the next step's call)."""
        marks = trace.spans_named("bench.prefill", "bench.decode")
        steps = self.record.steps
        if [m.name[len("bench."):] for m in marks] != [s.kind for s in steps]:
            raise RuntimeError(f"{len(marks)} step spans in the trace, {len(steps)} steps recorded")
        return list(zip(steps, trace.busy_after(marks)))

    def window(self, seconds: float, traced: bool) -> Record:
        rec = Record()
        if traced:
            originals = self._wrap_steps(rec)
        try:
            with span("bench.window", traced):
                rec.t0 = t = time.perf_counter()
                stop = rec.t0 + seconds
                while t < stop:
                    b = next(self.source)
                    with span("bench.batch", traced):
                        reqs = self._serve(list(b.prompts), b.new_tokens)
                    rec.batches.append(Batch(b.prompt_len, reqs))
                    t = time.perf_counter()
                rec.t_end = t
        finally:
            if traced:
                self.engine.prefill_step, self.engine.decode_step = originals
        self.record = rec
        return rec

    def end_to_end(self) -> Dict[str, float]:
        rec = self.record
        return {"tokens_per_s": len(rec.stamps()) / (rec.t_end - rec.t0)}

    def _failed(self, reqs) -> List[Any]:
        v = self.cfg["vocab_size"]
        return [r for r in reqs if len(r.generated) != r.max_new_tokens
                or not all(0 <= t < v for t in r.generated)]

    def attempted_failed(self) -> Tuple[int, int]:
        reqs = self.record.requests()
        return len(reqs), len(self._failed(reqs))

    def release(self) -> None:
        """Pick the requests to check and free the engine: one with the most
        tokens, then one from each of ``check_requests - 1`` other lanes, each
        in a batch drawn from the seed."""
        batches = [b.requests for b in self.record.batches]
        rng = traffic.rng_for(self.seed, "check")
        picks = []
        if batches:
            longest = max(len(r.generated) for b in batches for r in b)
            first = [(i, j) for i, b in enumerate(batches) for j, r in enumerate(b)
                     if len(r.generated) == longest]
            bi, lane = first[rng.integers(len(first))]
            lanes = [j for j in range(len(batches[bi])) if j != lane]
            k = min(self.mix["check_requests"], len(batches[bi])) - 1
            picks = [batches[bi][lane]] + [
                batches[rng.integers(len(batches))][j]
                for j in rng.choice(lanes, size=k, replace=False)]
        self._to_check = [(np.asarray(r.prompt), np.asarray(r.generated, np.int32))
                          for r in picks]
        self._n_failed = len(self._failed(self.record.requests()))
        del self.engine
        self.record.batches = []

    def check(self, control_bits: int = 0) -> Dict[str, Dict[str, float]]:
        """The numbers compared, each with its limit.  With ``control_bits``
        the control stands in the program's place: at each position of the
        same prompts and served tokens, the token that the reference at that
        precision ranks first."""
        if not self._to_check:
            return {}
        gaps = [self.reference.served_gaps(self.cfg, self.params, p, g, self.mix["max_len"],
                                           control_bits)[1 if control_bits else 0]
                for p, g in self._to_check]
        gaps = np.concatenate(gaps)
        lim = self.cfg["limits"]
        return {"failed_requests": {"value": self._n_failed, "limit": lim["failed_requests"]},
                "max_logit_gap": {"value": float(np.max(gaps)), "limit": lim["max_logit_gap"],
                                  "tokens": int(gaps.size)},
                "mismatched_tokens_pct": {
                    "value": 100.0 * float(np.mean(gaps > self.cfg["mismatch_logit_tolerance"])),
                    "limit": lim["mismatched_tokens_pct"]}}
