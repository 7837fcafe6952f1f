"""Serving engine: prefill + decode step builders, batched request loop.

The serve path uses bit-sliced int8 weights (``maybe_quantize_tree``) — the
paper's adaptive-precision inference — halving the weight-memory roofline
term vs. bf16.  Kernel dispatch goes through the backend registry: pass
``backend=`` ("xla" on CPU, "pallas" on TPU) to the step builders or
:class:`ServeEngine` and every registry kernel traced under that step runs
there (the ``use_backend`` scope is active during tracing).

Prefill/decode steps are compiled **once per signature** through the kernel
API's global compile cache (``repro.kernels.program.cached_executable``, the
same cache backing ``api.compile``): constructing a second ServeEngine with
the same (config, flags, backend, max_len) reuses the jitted steps instead
of re-tracing/re-lowering them — visible in ``api.compile_cache_info()``.
Their executables are named ``jit_prefill_step`` and ``jit_decode_step``.

:meth:`ServeEngine.run` marks its phases with ``jax.profiler``
``TraceAnnotation`` spans (``serve.*``, see there) and keeps
:class:`ServeCounters`; with no profiler running a span costs under a
microsecond and records nothing.
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import MeshRules, cache_entry_spec, param_specs
from repro.kernels.api import use_backend
from repro.kernels.program import cached_executable
from repro.models.common import maybe_quantize_tree
from repro.models.runtime import DEFAULT_FLAGS, RunFlags
from repro.models import transformer
from repro.models.transformer import cache_shape, init_cache, prefill


def _backend_scope(backend: Optional[str]):
    return use_backend(backend) if backend else contextlib.nullcontext()


def serve_params_shape(cfg: ModelConfig, flags: RunFlags = DEFAULT_FLAGS):
    """ShapeDtypeStruct tree of the (possibly quantized) serving params."""
    from repro.models.transformer import init_params

    def build():
        p = init_params(jax.random.key(0), cfg)
        return maybe_quantize_tree(p, cfg) if flags.quant_serve else p

    return jax.eval_shape(build)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, rules: MeshRules, flags: RunFlags = DEFAULT_FLAGS):
    shapes = cache_shape(cfg, batch, max_len, flags)

    def visit(path, leaf):
        if leaf.ndim == 0:
            return P()
        # leading dim is the scan-group axis; entry rules apply to the rest
        inner = cache_entry_spec(leaf.shape[1:], cfg, rules, seq_shard_kv=flags.seq_shard_kv)
        return P(None, *inner)

    # every stack (``dense_blocks`` too); the position and a MoE model's
    # counter and lane mask are replicated
    return {k: jax.tree_util.tree_map_with_path(visit, v) if isinstance(v, dict) else P()
            for k, v in shapes.items()}


def make_prefill_step(cfg, flags=DEFAULT_FLAGS, rules=None, max_len=None, backend=None) -> Callable:
    def prefill_step(params, batch):
        with _backend_scope(backend):
            return prefill(params, cfg, batch, flags, rules, max_len=max_len)

    return prefill_step


def make_decode_step(cfg, flags=DEFAULT_FLAGS, rules=None, backend=None) -> Callable:
    def decode_step(params, cache, tokens):
        with _backend_scope(backend):
            return transformer.decode_step(params, cfg, cache, tokens, flags, rules)

    return decode_step


# ---------------------------------------------------------------------------
# A small batched-request engine (used by examples/serve_lm.py)
# ---------------------------------------------------------------------------


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class ServeCounters:
    """What an engine's decode loops did since it was built.

    ``decode_steps`` counts jitted decode steps, ``lane_steps`` the batch
    lanes they ran (batch x steps of each run), ``useful_lane_steps`` those
    lanes whose request still wanted a token.  ``useful_lane_steps /
    lane_steps`` is the share of decode work that served a request: a
    lock-step batch keeps decoding its retired lanes until its longest
    request ends.  Take differences of two readings to count an interval.

    For a model with experts, ``expert_slots`` counts the held experts of
    every expert layer in each decode step (held experts x expert layers x
    decode steps), ``expert_slots_used`` those of them that the token of at
    least one lane whose request still wanted a token chose (counted on the
    device, read once per ``run``; a retired lane's pad token counts for
    nothing); their ratio is the share of held-expert weight reads that
    served a request.  ``expert_slots_by_run`` keeps ``(expert_slots,
    expert_slots_used)`` of each run, in order."""

    decode_steps: int = 0
    lane_steps: int = 0
    useful_lane_steps: int = 0
    expert_slots: int = 0
    expert_slots_used: int = 0
    expert_slots_by_run: List[Tuple[int, int]] = field(default_factory=list)


class ServeEngine:
    """Static-batch engine: pads prompts to a bucket, prefills, then decodes
    all requests in lock-step, retiring finished ones (continuous batching at
    iteration granularity)."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        flags: RunFlags = DEFAULT_FLAGS,
        max_len: int = 512,
        eos: int = -1,
        backend: Optional[str] = None,
    ):
        """``eos`` is the token id that retires a request the moment it is
        generated; the default ``-1`` is an explicit "never" sentinel (no
        vocabulary id is negative, so decode only stops at
        ``max_new_tokens``).  Retired lanes keep their batch slot — the
        static shapes require it — but their token feed is masked to the pad
        id so the cache never ingests post-eos garbage; for slot reclamation
        see ``repro.serve.scheduler.ContinuousBatcher``."""
        self.cfg, self.flags, self.max_len, self.eos = cfg, flags, max_len, eos
        self.backend = backend
        self.params = maybe_quantize_tree(params, cfg) if flags.quant_serve else params
        # compile-once: identical engine signatures share the jitted steps
        # (jax re-traces a fresh lambda per jit object — caching the jitted
        # callable, not just the XLA executable, avoids that too).
        # ``prefill_step(params, batch)`` and ``decode_step(params, cache,
        # tokens)`` return ``(cache, logits)``; the logits span the padded
        # vocabulary (``cfg.padded_vocab()``)
        self.prefill_step = cached_executable(
            ("serve_step", "prefill", repr(cfg), repr(flags), backend, max_len),
            lambda: jax.jit(make_prefill_step(cfg, flags, max_len=max_len, backend=backend)),
        )
        self.decode_step = cached_executable(
            ("serve_step", "decode", repr(cfg), repr(flags), backend),
            lambda: jax.jit(make_decode_step(cfg, flags, backend=backend)),
        )
        self.counters = ServeCounters()

    def pack(self, requests: List[Request]) -> Dict[str, jnp.ndarray]:
        """The prefill batch: prompts left-padded to one length (at least 8)."""
        b = len(requests)
        s = max(len(r.prompt) for r in requests)
        s = max(s, 8)
        toks = np.zeros((b, s), np.int32)
        for i, r in enumerate(requests):
            toks[i, s - len(r.prompt) :] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.frontend == "vision":
            batch["patch_embeds"] = jnp.zeros((b, self.cfg.n_patches, self.cfg.d_model), jnp.dtype(self.cfg.dtype))
        if self.cfg.is_encdec:
            batch["enc_embeds"] = jnp.zeros((b, self.cfg.enc_seq_len, self.cfg.d_model), jnp.dtype(self.cfg.dtype))
        return batch

    def _greedy(self, logits) -> np.ndarray:
        # the embedding is padded past the vocabulary; those ids are no tokens
        return np.array(jnp.argmax(logits[:, : self.cfg.vocab_size], axis=-1), np.int32)

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve one batch to the end of its longest request.

        Host spans, nested under ``serve.run`` (the whole call):
        ``serve.pack`` builds the prefill batch; ``serve.prefill`` dispatches
        the prefill step; per decode step, ``serve.retire`` hands each lane's
        token to its request and retires finished lanes, ``serve.decode``
        uploads the tokens (for a model with experts, after a lane retires,
        the live lanes too) and dispatches the decode step, and
        ``serve.sample`` picks the next tokens and waits for them on the
        host (after the prefill too).  Device time that no op fills while
        the host is inside one of them is that phase's cost to the chip."""
        with TraceAnnotation("serve.run"):
            with TraceAnnotation("serve.pack"):
                batch = self.pack(requests)
            with TraceAnnotation("serve.prefill"):
                cache, logits = self.prefill_step(self.params, batch)
            with TraceAnnotation("serve.sample"):
                next_tok = self._greedy(logits)
            steps = max(r.max_new_tokens for r in requests)
            before = [len(r.generated) for r in requests]
            live = np.ones(len(requests), bool)  # as the prefill's cache has it
            decode_steps = 0
            for _ in range(steps):
                with TraceAnnotation("serve.retire"):
                    for i, r in enumerate(requests):
                        if not r.done:
                            t = int(next_tok[i])
                            r.generated.append(t)
                            if t == self.eos or len(r.generated) >= r.max_new_tokens:
                                r.done = True
                        if r.done:
                            # retired lane: its stale argmax must not keep decoding —
                            # feed the pad id so the lock-step cache stays clean
                            next_tok[i] = 0
                    if all(r.done for r in requests):
                        break
                with TraceAnnotation("serve.decode"):
                    if self.cfg.is_moe:  # the expert counter counts the live lanes' tokens
                        now = np.array([not r.done for r in requests])
                        if (now != live).any():
                            live = now
                            cache = dict(cache, live_lanes=jnp.asarray(live))
                    cache, logits = self.decode_step(self.params, cache, jnp.asarray(next_tok)[:, None])
                with TraceAnnotation("serve.sample"):
                    next_tok = self._greedy(logits)
                decode_steps += 1
        # every token after a request's first came out of a decode step
        c = self.counters
        c.decode_steps += decode_steps
        c.lane_steps += len(requests) * decode_steps
        c.useful_lane_steps += sum(max(len(r.generated) - n - 1, 0)
                                   for r, n in zip(requests, before))
        if self.cfg.is_moe:
            slots = self.cfg.n_held_experts * self.cfg.moe_layers * decode_steps
            used = int(cache["expert_slots_used"])
            c.expert_slots += slots
            c.expert_slots_used += used
            c.expert_slots_by_run.append((slots, used))
        return requests
