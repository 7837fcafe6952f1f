"""Unified kernel-execution API: typed tensors, precision specs, backends.

PIMSAB's bit-serial compute is *divisible*: adaptive precision, bit-slicing
and constant handling are all choices about how one logical tensor is
decomposed.  This module makes that decomposition first-class instead of
threading ``(x_slices, slice_bits, act_bits, weight_bits, skip, impl, block)``
kwargs through every layer:

* :class:`SlicedTensor` — a JAX pytree carrying the slice stack, the
  dequantization scale, and *static* zero-slice metadata, so the paper's
  ``mul_const`` zero-bit skipping flows to the kernel by construction.
* :class:`PrecisionSpec` — one object for ``act_bits/weight_bits/slice_bits/
  accum_bits`` with the adaptive-precision presets of §IV-C.
* A **backend registry**: each Pallas kernel registers itself (paired with
  its pure-jnp oracle) via :func:`register_kernel`; execution backend is
  chosen by the :func:`use_backend` context manager —

  - ``"xla"``       — the oracle (what the CPU dry-run lowers),
  - ``"interpret"`` — the Pallas kernel body run in interpreter mode
    (CPU validation of the real kernel),
  - ``"pallas"``    — the compiled TPU kernel,
  - ``"pimsab"``    — the paper's architecture model: the call is lowered
    through the tensor DSL → §V compiler → ISA and executed bit-serially on
    the functional simulator (``repro.kernels.pimsab_backend``); modeled
    cycles/energy are retrievable via :func:`last_sim_report`.

Validation tests and benchmark enumeration are generated from the registry
(:func:`registered_kernels`) instead of hand-maintained lists.

On top of per-call dispatch sits the **Program API** (:mod:`repro.kernels.
program`): :func:`trace` captures a chain of registry kernel calls into a
:class:`~repro.kernels.program.Program`, :func:`compile` lowers it once for
the active backend and returns a cached
:class:`~repro.kernels.program.Executor` — on the pimsab backend the whole
chain compiles to one fused ISA stream with integer intermediates kept
CRAM-resident (the producer's DRAM store and consumer's DRAM load are
elided).  Eager dispatch stays the default; programs are the opt-in fast
path and are bit-exact against it.

(The ``repro.kernels.ops`` ``impl=`` compatibility shims from the first API
release have been removed; ``scripts/check_api.py`` rejects imports of that
module.)
"""
from __future__ import annotations

import contextlib
import contextvars
import math
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PrecisionSpec",
    "SlicedTensor",
    "BACKENDS",
    "use_backend",
    "current_backend",
    "set_default_backend",
    "register_kernel",
    "register_pimsab_impl",
    "get_kernel",
    "registered_kernels",
    "dispatch",
    "active_pairs",
    "skip_pairs",
    "zero_slice_pairs",
    "bitslice_matmul_oracle",
    "matmul",
    "quantized_matmul",
    "htree_reduce",
    "rglru_scan",
    "ewise_add",
    "relu",
    "conv2d",
    "maxpool2d",
    "avgpool2d",
    "global_avgpool",
    "int_matmul",
    "attention_qk",
    "softmax_fixedpoint",
    "attention_pv",
    "decode_gemv",
    "kv_append",
    "static_value",
    "last_executed_pairs",
    "last_sim_report",
    "sim_report_log",
    "clear_sim_report_log",
    "last_verify_report",
    "profile_timelines",
    # Program API (re-exported from repro.kernels.program)
    "trace",
    "compile",
    "Program",
    "ResidentState",
    "Executor",
    "TracedFunction",
    "TraceError",
    "compile_cache_info",
    "clear_compile_cache",
    "PimsabTracerError",
    # Mapping autotuner (re-exported from repro.core.compiler.autotune)
    "TuneConfig",
    "tuning",
    "tune_cache_info",
    "clear_tune_cache",
    # Static verifier (re-exported from repro.core.compiler.verify)
    "VerifierError",
    "VerifierWarning",
    "VerifyReport",
    "Diagnostic",
    # Multi-chip scale-out (re-exported from repro.kernels.multichip)
    "ChipCluster",
    "ChipLink",
    "ClusterExecutor",
    "ClusterReport",
    "compile_cluster",
    "cluster_timing_report",
    "weak_scaling_report",
]


# ---------------------------------------------------------------------------
# version-safe staticness probe
# ---------------------------------------------------------------------------


def static_value(arr: Any) -> Optional[np.ndarray]:
    """Concrete ndarray if ``arr`` is static at trace time, else ``None``.

    Deliberately does NOT touch ``jax.core.Tracer`` (its home has moved
    across JAX releases); a tracer is exactly the thing that refuses to
    materialize as a numpy array, so we ask it to and catch the refusal.
    """
    if arr is None:
        return None
    if isinstance(arr, (np.ndarray, np.generic, int, float, bool)):
        return np.asarray(arr)
    try:
        return np.asarray(arr)
    except Exception:  # tracer (ConcretizationTypeError et al.) → dynamic
        return None


# ---------------------------------------------------------------------------
# PrecisionSpec
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PrecisionSpec:
    """Bit widths of one logical matmul, PIMSAB adaptive-precision style.

    ``slice_bits`` is the hardware-native slice width (8 on the MXU int8
    path — the radix-256 analogue of the paper's 1-bit planes); operands
    wider than a slice are decomposed into ``ceil(bits / slice_bits)``
    slices and recombined with shifts.
    """

    act_bits: int = 8
    weight_bits: int = 8
    slice_bits: int = 8
    accum_bits: int = 32

    def __post_init__(self) -> None:
        if not (1 <= self.slice_bits <= 8):
            raise ValueError(f"slice_bits must be in [1, 8], got {self.slice_bits}")
        if self.act_bits < 1 or self.weight_bits < 1:
            raise ValueError(f"bits must be >= 1: {self}")
        if self.accum_bits < self.act_bits + self.weight_bits:
            raise ValueError(
                f"accum_bits={self.accum_bits} cannot hold a "
                f"{self.act_bits}x{self.weight_bits}-bit product"
            )

    @property
    def act_slices(self) -> int:
        return max(1, math.ceil(self.act_bits / self.slice_bits))

    @property
    def weight_slices(self) -> int:
        return max(1, math.ceil(self.weight_bits / self.slice_bits))

    @property
    def single_pass(self) -> bool:
        """True if the matmul is one MXU pass (no slice recombination)."""
        return self.act_slices == 1 and self.weight_slices == 1

    @classmethod
    def from_quant_config(cls, q) -> "PrecisionSpec":
        """Lift a :class:`repro.configs.base.QuantConfig` into a spec."""
        return cls(act_bits=q.act_bits, weight_bits=q.weight_bits, slice_bits=q.slice_bits)


def _install_presets() -> None:
    # Adaptive-precision presets (§IV-C): precision tracks the value range,
    # slices track the precision.  Defined here (not as class attrs inside
    # the body) because dataclass fields would swallow them.
    presets = {
        "int4": PrecisionSpec(act_bits=4, weight_bits=4),
        "int8": PrecisionSpec(act_bits=8, weight_bits=8),
        "int12": PrecisionSpec(act_bits=12, weight_bits=12, accum_bits=32),
        "int16": PrecisionSpec(act_bits=16, weight_bits=16, accum_bits=32),
        "w4a8": PrecisionSpec(act_bits=8, weight_bits=4),
        "w8a16": PrecisionSpec(act_bits=16, weight_bits=8),
    }
    for name, spec in presets.items():
        setattr(PrecisionSpec, name, spec)


_install_presets()


# ---------------------------------------------------------------------------
# SlicedTensor
# ---------------------------------------------------------------------------


def _zero_slice_ids(slices: Any) -> Tuple[int, ...]:
    """Indices of statically-all-zero slices (``()`` when dynamic).

    For on-device arrays the emptiness reduction runs on device and only
    ``n_slices`` booleans cross to the host — probing a big activation
    stack must not cost a full device→host copy.  Tracers refuse the
    transfer and fall through to the conservative dense answer.
    """
    if slices is None:
        return ()
    if isinstance(slices, (np.ndarray, np.generic)):
        return tuple(s for s in range(slices.shape[0]) if not slices[s].any())
    try:
        # np.asarray forces materialization: device_get on a tracer returns
        # the tracer unchanged, so the conversion is where tracers refuse
        flags = np.asarray(
            jax.device_get(jnp.any(slices, axis=tuple(range(1, slices.ndim))))
        )
    except Exception:  # tracer → dynamic
        return ()
    return tuple(i for i, f in enumerate(flags) if not f)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True, eq=False)
class SlicedTensor:
    """A logical integer tensor stored as a stack of signed-digit slices.

    ``slices`` is ``(n_slices, *shape)`` int8 in the balanced signed-digit
    radix-2**slice_bits decomposition (low-to-high):

        value == Σ_s slices[s] · 2**(slice_bits·s)

    ``scale`` (optional) dequantizes the logical value back to float.
    ``zero_slices`` caches which slices were statically all-zero at
    construction time — PIMSAB ``mul_const`` zero-bit skipping — and rides
    through ``jax.jit`` as pytree aux data, so kernels skip dead MXU passes
    even when the slice data itself has become a tracer.
    """

    slices: jnp.ndarray
    scale: Optional[jnp.ndarray] = None
    slice_bits: int = 8
    orig_bits: int = 8
    zero_slices: Tuple[int, ...] = ()

    # -- pytree protocol (aux = everything static) --
    def tree_flatten(self):
        return (self.slices, self.scale), (self.slice_bits, self.orig_bits, self.zero_slices)

    @classmethod
    def tree_unflatten(cls, aux, children):
        slices, scale = children
        slice_bits, orig_bits, zero_slices = aux
        return cls(slices=slices, scale=scale, slice_bits=slice_bits,
                   orig_bits=orig_bits, zero_slices=zero_slices)

    # -- constructors --
    @classmethod
    def from_int(
        cls,
        x: jnp.ndarray,
        bits: int,
        *,
        slice_bits: int = 8,
        scale: Optional[jnp.ndarray] = None,
    ) -> "SlicedTensor":
        """Decompose an integer tensor into slices, caching zero-slice ids."""
        from repro.kernels import ref

        slices = ref.to_slices(x, bits, slice_bits)
        return cls(
            slices=slices,
            scale=scale,
            slice_bits=slice_bits,
            orig_bits=bits,
            zero_slices=_zero_slice_ids(slices),
        )

    @classmethod
    def quantize(
        cls, x: jnp.ndarray, spec: PrecisionSpec = PrecisionSpec.int8, *, weight: bool = False
    ) -> "SlicedTensor":
        """Dynamic symmetric per-row (act) / per-column (weight) quantization.

        Activations quantize along the last axis (the contraction axis of
        ``x @ w``); weights along the second-to-last.
        """
        bits = spec.weight_bits if weight else spec.act_bits
        axis = -2 if weight else -1
        qmax = 2 ** (bits - 1) - 1
        xf = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / qmax, 1e-8)
        x_q = jnp.clip(jnp.round(xf / scale), -qmax - 1, qmax).astype(jnp.int32)
        return cls.from_int(x_q, bits, slice_bits=spec.slice_bits, scale=scale)

    # -- views --
    @property
    def n_slices(self) -> int:
        return self.slices.shape[0]

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.slices.shape[1:])

    def to_int(self) -> jnp.ndarray:
        from repro.kernels import ref

        return ref.from_slices(self.slices, self.slice_bits)

    def dequantize(self) -> jnp.ndarray:
        v = self.to_int().astype(jnp.float32)
        return v * self.scale if self.scale is not None else v


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

BACKENDS = ("pallas", "interpret", "xla", "pimsab")

# With no scope active, registry calls run the oracles.  Nothing switches
# backends by itself: an entry point that wants the kernels names "pallas"
# (``launch/serve.py --backend``, ``chip_smoke.py``), and a kernel under
# "pallas" on a host without a TPU raises rather than falling back.
# Overridable per process via set_default_backend and per scope via
# use_backend.
_default_backend = "xla"
_backend_stack: contextvars.ContextVar[Tuple[str, ...]] = contextvars.ContextVar(
    "repro_kernel_backend_stack", default=()
)


def _check_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    return name


def current_backend() -> str:
    """The innermost active backend (thread/context-local), else the default."""
    stack = _backend_stack.get()
    return stack[-1] if stack else _default_backend


def set_default_backend(name: str) -> None:
    """Set the process-wide default backend (used when no context is active)."""
    global _default_backend
    _default_backend = _check_backend(name)


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[str]:
    """Scope all registry-dispatched kernels to ``name``.

    Nests (innermost wins) and is context-local: a ``use_backend`` entered
    on one thread / async task does not leak into another.
    """
    _check_backend(name)
    token = _backend_stack.set(_backend_stack.get() + (name,))
    try:
        yield name
    finally:
        _backend_stack.reset(token)


@dataclass(frozen=True)
class KernelDef:
    """One registered kernel: the Pallas implementation + its oracle (+ the
    optional architecture-simulator lowering, attached separately by
    :func:`register_pimsab_impl`)."""

    name: str
    pallas: Callable[..., Any]
    oracle: Callable[..., Any]
    pimsab: Optional[Callable[..., Any]] = None


_REGISTRY: Dict[str, KernelDef] = {}
_registry_lock = threading.Lock()


def register_kernel(name: str, *, oracle: Callable[..., Any]):
    """Decorator: pair a Pallas kernel with its pure-jnp oracle.

    The Pallas callable must accept ``interpret: bool`` (both non-pallas
    backends reach it that way); the oracle must accept the same positional
    operands.  Registration is idempotent per name (last wins) so module
    reloads in tests don't error.
    """

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        with _registry_lock:
            prev = _REGISTRY.get(name)
            _REGISTRY[name] = KernelDef(
                name=name, pallas=fn, oracle=oracle,
                pimsab=prev.pimsab if prev else None,
            )
        return fn

    return deco


def register_pimsab_impl(name: str):
    """Decorator: attach the architecture-simulator lowering to kernel
    ``name`` (which must already be registered).  Kept separate from
    :func:`register_kernel` so the DSL→ISA→simulator bridge stays an optional
    layer the TPU path never imports."""

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        import dataclasses

        with _registry_lock:
            try:
                kd = _REGISTRY[name]
            except KeyError:
                raise KeyError(
                    f"cannot attach pimsab impl: kernel {name!r} not registered"
                ) from None
            _REGISTRY[name] = dataclasses.replace(kd, pimsab=fn)
        return fn

    return deco


_bootstrapped = False


def _ensure_registered() -> None:
    # Kernel modules self-register on import; importing them lazily here
    # avoids an import cycle (kernel modules import this module for the
    # decorator and active_pairs).  Guarded by a flag, not registry
    # non-emptiness: a direct import of one kernel module must not mask
    # the others.
    global _bootstrapped
    if _bootstrapped:
        return
    import repro.kernels.attention  # noqa: F401
    import repro.kernels.bitslice_matmul  # noqa: F401
    import repro.kernels.conv  # noqa: F401
    import repro.kernels.ewise  # noqa: F401
    import repro.kernels.htree_reduce  # noqa: F401
    import repro.kernels.rglru_scan  # noqa: F401
    # last: attaches the simulator lowering to the kernels registered above
    import repro.kernels.pimsab_backend  # noqa: F401

    _bootstrapped = True


def get_kernel(name: str) -> KernelDef:
    """The :class:`KernelDef` registered under ``name`` (KeyError with the
    registered-name list when absent)."""
    _ensure_registered()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel {name!r} registered; have {sorted(_REGISTRY)}") from None


def registered_kernels() -> Mapping[str, KernelDef]:
    """Immutable view of the registry (tests/benchmarks enumerate this)."""
    _ensure_registered()
    return dict(_REGISTRY)


class PimsabTracerError(ValueError):
    """A pimsab-backend kernel was reached with jax tracers (e.g. under
    ``jax.jit``).  Raised *before* lowering starts, naming the kernel."""


def _require_concrete_operands(name: str, args: Tuple[Any, ...]) -> None:
    for i, a in enumerate(args):
        if hasattr(a, "shape") and hasattr(a, "dtype") and static_value(a) is None:
            raise PimsabTracerError(
                f"kernel {name!r} on the 'pimsab' backend needs concrete "
                f"operands, but operand {i} is a jax tracer (the call sits "
                "under jax.jit/vmap/grad). Either run the kernel eagerly "
                "outside the transform, or capture the kernel chain with "
                "api.trace(fn) and execute the compiled Program instead — "
                "programs lower once and replay without jax tracing."
            )


def dispatch(name: str, *args, pallas_kwargs: Optional[Dict[str, Any]] = None, **kwargs):
    """Run kernel ``name`` on the currently-active backend.

    ``kwargs`` reach both implementations; ``pallas_kwargs`` (block sizes
    and other tiling knobs the oracle has no business seeing) only the
    Pallas call.  This is the single backend branch — the public wrappers
    below all go through it.  Inside :func:`trace` the call is recorded into
    the Program under construction instead of executing.  The call runs
    under ``jax.named_scope(name)``, so the device ops it emits carry the
    kernel's name in a profiler trace.
    """
    from repro.kernels import program as _program

    ctx = _program.active_trace()
    if ctx is not None:
        return ctx.record(name, args, kwargs, pallas_kwargs)
    k = get_kernel(name)
    backend = current_backend()
    with jax.named_scope(name):
        if backend == "xla":
            return k.oracle(*args, **kwargs)
        if backend == "pimsab":
            if k.pimsab is None:
                raise NotImplementedError(
                    f"kernel {name!r} has no pimsab lowering "
                    "(register one with api.register_pimsab_impl)"
                )
            _require_concrete_operands(name, args)
            # tiling knobs in pallas_kwargs are TPU-specific; the DSL compiler
            # chooses its own distribution (§V-B)
            return k.pimsab(*args, **kwargs)
        kw = dict(kwargs, **(pallas_kwargs or {}))
        return k.pallas(*args, interpret=(backend == "interpret"), **kw)


# ---------------------------------------------------------------------------
# bit-sliced matmul on the new surface
# ---------------------------------------------------------------------------


def active_pairs(
    n_x: int, n_w: int, skip: Tuple[Tuple[int, int], ...] = ()
) -> Tuple[Tuple[int, int], ...]:
    """The (s, t) slice pairs a bit-sliced matmul actually executes.

    Single source of truth for zero-slice skipping: both the Pallas kernel's
    unrolled shift list and the XLA oracle loop iterate exactly this tuple,
    so a skipped pair is *provably* never issued.
    """
    dead = set(skip)
    return tuple((s, t) for s in range(n_x) for t in range(n_w) if (s, t) not in dead)


def skip_pairs(x: SlicedTensor, w: SlicedTensor) -> Tuple[Tuple[int, int], ...]:
    """(s, t) pairs statically known to contribute zero, from cached metadata."""
    return tuple(
        (s, t)
        for s in range(x.n_slices)
        for t in range(w.n_slices)
        if s in x.zero_slices or t in w.zero_slices
    )


def zero_slice_pairs(
    x_slices: Optional[np.ndarray], w_slices: Optional[np.ndarray]
) -> Tuple[Tuple[int, int], ...]:
    """Statically-zero (s, t) pairs of raw slice stacks — PIMSAB ``mul_const``
    zero-bit skipping for callers that haven't built :class:`SlicedTensor`s.

    Only possible when operands are concrete (inference-time constants);
    tracers are conservatively assumed dense.  Staticness is probed with
    :func:`static_value` (version-safe — no ``jax.core.Tracer`` isinstance
    checks, which break across JAX relocations).
    """

    def dead(arr):
        a = static_value(arr)
        if a is None:
            return None
        return [s for s in range(a.shape[0]) if not a[s].any()]

    xs, ws = dead(x_slices), dead(w_slices)
    if not xs and not ws:
        return ()
    nx = x_slices.shape[0] if x_slices is not None else 1
    nw = w_slices.shape[0] if w_slices is not None else 1
    skip = []
    for s in range(nx):
        for t in range(nw):
            if (xs and s in xs) or (ws and t in ws):
                skip.append((s, t))
    return tuple(skip)


# Debug/observability: the pair list handed to the most recent bit-sliced
# matmul dispatch on this thread (the list the kernel unrolls / the oracle
# loops over).  Regression tests assert skipped pairs never appear here.
_last_pairs = threading.local()


def last_executed_pairs() -> Tuple[Tuple[int, int], ...]:
    """The (s, t) slice-pair list the most recent bit-sliced matmul dispatch
    on this thread actually executed — regression tests assert statically
    skipped pairs never appear here."""
    return getattr(_last_pairs, "pairs", ())


def bitslice_matmul_oracle(x_slices, w_slices, *, slice_bits=8, skip=()):
    """Skip-aware pure-jnp oracle: loops exactly ``active_pairs(...)`` —
    with an empty skip list this is ``ref.bitslice_matmul_ref``."""
    acc = jnp.zeros((x_slices.shape[1], w_slices.shape[2]), jnp.int32)
    for s, t in active_pairs(x_slices.shape[0], w_slices.shape[0], skip):
        prod = jax.lax.dot_general(
            x_slices[s], w_slices[t], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + (prod << (slice_bits * (s + t)))
    return acc


def matmul(
    x: SlicedTensor,
    w: SlicedTensor,
    *,
    skip: Tuple[Tuple[int, int], ...] = (),
    block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """``x (M, K) @ w (K, N)`` over slice stacks, zero slices skipped.

    The skipped pairs are the union of the operands' cached zero-slice
    metadata and the explicit ``skip`` argument.  Returns float32 (scales
    applied) when either operand carries a scale, else the raw int32
    accumulator.
    """
    if x.slice_bits != w.slice_bits:
        raise ValueError(f"slice_bits mismatch: {x.slice_bits} vs {w.slice_bits}")
    all_skip = tuple(sorted(set(skip_pairs(x, w)) | set(skip)))
    _last_pairs.pairs = active_pairs(x.n_slices, w.n_slices, all_skip)
    acc = dispatch(
        "bitslice_matmul", x.slices, w.slices,
        slice_bits=x.slice_bits, skip=all_skip,
        pallas_kwargs=None if block is None else {"block": block},
    )
    if x.scale is None and w.scale is None:
        return acc
    out = acc.astype(jnp.float32)
    if x.scale is not None:
        out = out * x.scale.reshape(-1, 1)
    if w.scale is not None:
        out = out * w.scale.reshape(1, -1)
    return out


def quantized_matmul(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    w_scale: jnp.ndarray,
    spec: PrecisionSpec = PrecisionSpec.int8,
) -> jnp.ndarray:
    """End-to-end PIMSAB path: dynamic act quant → slice decomposition →
    zero-slice skip (by SlicedTensor construction) → integer matmul →
    dequantize.  ``x (..., K)`` float; ``w_q (K, N)`` int; out ``(..., N)``.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    x_st = SlicedTensor.quantize(x.reshape(-1, k), spec)
    w_st = SlicedTensor.from_int(
        w_q, spec.weight_bits, slice_bits=spec.slice_bits, scale=w_scale.reshape(-1)
    )
    out = matmul(x_st, w_st)
    return out.reshape(*lead, -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# other registered kernels on the new surface
# ---------------------------------------------------------------------------


def _tiling(**knobs) -> Optional[Dict[str, Any]]:
    """``pallas_kwargs`` holding the tiling knobs a caller set; the kernels
    own the defaults (``None``: all of them)."""
    return {k: v for k, v in knobs.items() if v is not None} or None


def htree_reduce(x: jnp.ndarray) -> jnp.ndarray:
    """(N, D) → (D,) log-depth H-tree reduction on the active backend."""
    return dispatch("htree_reduce", x)


def rglru_scan(
    a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray, *,
    block_t: Optional[int] = None, block_w: Optional[int] = None,
) -> jnp.ndarray:
    """RG-LRU linear recurrence h_t = a_t·h_{t-1} + b_t on the active backend."""
    return dispatch(
        "rglru_scan", a, b, h0,
        pallas_kwargs=_tiling(block_t=block_t, block_w=block_w),
    )


def ewise_add(x: jnp.ndarray, y: jnp.ndarray, *, block: Optional[int] = None) -> jnp.ndarray:
    """Elementwise x + y (any matching shapes) on the active backend."""
    return dispatch("ewise_add", x, y, pallas_kwargs=_tiling(block=block))


def relu(x: jnp.ndarray, *, block: Optional[int] = None) -> jnp.ndarray:
    """Elementwise max(x, 0) on the active backend (PIMSAB: CmpGE + predicated
    copy through the PE mask latch)."""
    return dispatch("relu", x, pallas_kwargs=_tiling(block=block))


def conv2d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    stride: int = 1,
    padding: int = 0,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """2-D convolution ``(N, C, H, W) × (OC, C, KH, KW) → (N, OC, OH, OW)``
    on the active backend.

    Integer inputs accumulate in int32 (wrapping — bit-exact across
    backends); the pimsab backend lowers via im2col onto the ``mac`` gemm
    pipeline.  ``x_bits``/``w_bits`` are static precision hints: the pimsab
    lowering sizes its fields from them (program mode cannot calibrate from
    values) and the Pallas kernel its int8 slice counts; when they bound
    the operand magnitudes — or saturate at 32, where wraparound matches
    int32 — results stay bit-exact.
    """
    return dispatch(
        "conv2d", x, w, stride=stride, padding=padding,
        x_bits=x_bits, w_bits=w_bits, pallas_kwargs=_tiling(block=block),
    )


def maxpool2d(
    x: jnp.ndarray, *, window: int = 2, stride: Optional[int] = None,
    block: Optional[int] = None,
) -> jnp.ndarray:
    """Window max pooling ``(N, C, H, W) → (N, C, OH, OW)`` (no padding;
    ``stride`` defaults to ``window``).  PIMSAB folds the window with CmpGE +
    masked copies — the same predicated-execution idiom relu uses."""
    return dispatch(
        "maxpool2d", x, window=window, stride=stride,
        pallas_kwargs=_tiling(block=block),
    )


def avgpool2d(
    x: jnp.ndarray, *, window: int = 2, block: Optional[int] = None
) -> jnp.ndarray:
    """Window average pooling, stride == window.  Integer inputs floor-divide
    by the window count — on PIMSAB the divide is free: the store reads the
    sum accumulator at a wordline offset (arithmetic right shift), so the
    window count must be a power of two there."""
    return dispatch("avgpool2d", x, window=window, pallas_kwargs=_tiling(block=block))


def global_avgpool(x: jnp.ndarray, *, block: Optional[int] = None) -> jnp.ndarray:
    """Global spatial average ``(N, C, H, W) → (N, C)`` (integer inputs
    floor-divide by H·W; a power of two on the pimsab backend)."""
    return dispatch("global_avgpool", x, pallas_kwargs=_tiling(block=block))


def int_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """Raw-integer ``(M, K) @ (K, N)`` with int32 accumulation — the
    network-head matmul for activations that arrive as another kernel's
    integer output (no :class:`SlicedTensor` slice stacks involved)."""
    return dispatch(
        "int_matmul", x, w, x_bits=x_bits, w_bits=w_bits,
        pallas_kwargs=_tiling(block=block),
    )


def attention_qk(
    q: jnp.ndarray, k: jnp.ndarray, *,
    q_bits: Optional[int] = None, k_bits: Optional[int] = None,
    out_bits: Optional[int] = None, block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """Attention scores ``(M, D) q × (T, D) k → (M, T) int32`` (q·Kᵀ) on the
    active backend.

    ``q_bits``/``k_bits`` are static precision hints (pimsab field widths,
    Pallas int8 slice counts).
    ``out_bits`` is the caller's promise that every score fits that many
    signed bits: in program mode it clamps the score field width so the
    downstream fixed-point softmax scratch stays small (scores that overflow
    it wrap on the machine).  In a decode program whose K operand is a
    :class:`ResidentState` KV cache, the key cache chains CRAM-resident from
    the ``kv_append`` updater straight into this reduction.
    """
    return dispatch(
        "attention_qk", q, k, q_bits=q_bits, k_bits=k_bits, out_bits=out_bits,
        pallas_kwargs=_tiling(block=block),
    )


def softmax_fixedpoint(
    x: jnp.ndarray, *, in_frac: int, in_bits: Optional[int] = None,
    block_r: Optional[int] = None,
) -> jnp.ndarray:
    """Bit-exact fixed-point row softmax of ``(R, T)`` integers on the active
    backend.

    Inputs carry ``in_frac`` fraction bits (must be ≥ ``SOFTMAX_F −
    SOFTMAX_K`` = 3); outputs are int32 probabilities with ``SOFTMAX_F`` = 6
    fraction bits, rows summing to ≈ ``2**6``.  All three backends run the
    identical integer recipe (max-subtract, squared-polynomial exp,
    restoring-division normalizer), so results match bit for bit; ``in_bits``
    is a static width hint for the pimsab lowering.
    """
    return dispatch(
        "softmax_fixedpoint", x, in_frac=in_frac, in_bits=in_bits,
        pallas_kwargs=_tiling(block_r=block_r),
    )


def attention_pv(
    p: jnp.ndarray, v: jnp.ndarray, *, shift: Optional[int] = None,
    p_bits: Optional[int] = None, v_bits: Optional[int] = None,
    block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """Probability-weighted value mix ``(M, T) p × (T, Dv) v → (M, Dv)
    int32`` with the accumulator arithmetically shifted right by ``shift``
    (default ``SOFTMAX_F``) on the active backend — on pimsab a free
    shifted-window read of the MAC accumulator.  The V cache is re-streamed
    (never chained CRAM-resident: the updater leaves it laid out per cache
    row, but this reduction wants it per output column)."""
    kwargs = dict(p_bits=p_bits, v_bits=v_bits)
    if shift is not None:
        kwargs["shift"] = shift
    return dispatch(
        "attention_pv", p, v, pallas_kwargs=_tiling(block=block), **kwargs
    )


def decode_gemv(
    w: jnp.ndarray, x: jnp.ndarray, *,
    w_bits: Optional[int] = None, x_bits: Optional[int] = None,
    block: Optional[Tuple[int, int, int]] = None,
) -> jnp.ndarray:
    """Single-token decode projection ``(M, K) w × (K,) x → (M,) int32`` on
    the active backend.  The pimsab lowering sends the shared activation
    down the RF constant path (one RfLoad + MacConst per reduction index)
    instead of broadcasting it through the NoC."""
    return dispatch(
        "decode_gemv", w, x, w_bits=w_bits, x_bits=x_bits,
        pallas_kwargs=_tiling(block=block),
    )


def kv_append(
    cache: jnp.ndarray, new: jnp.ndarray, onehot: jnp.ndarray
) -> jnp.ndarray:
    """``(T, D)`` cache with the row selected by the one-hot ``(T,)``
    ``onehot`` replaced by the ``(D,)`` ``new`` row (all-zero selector → no
    op) on the active backend.  Bind the cache operand to a
    :class:`ResidentState` when compiling a decode program and the append
    updates reserved CRAM wordlines in place — zero DRAM traffic per step."""
    return dispatch("kv_append", cache, new, onehot)


def last_sim_report():
    """The :class:`~repro.kernels.pimsab_backend.SimReport` of the most recent
    pimsab-backend kernel call *or Program execution* on this thread
    (``None`` before any).  Reports carry the phase-timeline views: modeled
    ``total_cycles`` is the overlapped makespan, ``serialized_cycles`` the
    no-overlap clock, ``overlapped_cycles`` their difference, plus
    ``critical_path`` / per-resource ``utilization``."""
    from repro.kernels import pimsab_backend

    return pimsab_backend.last_sim_report()


def sim_report_log():
    """Bounded ring of recent pimsab :class:`SimReport`s on this thread,
    oldest first (the last entry is :func:`last_sim_report`).  Holds the most
    recent ``pimsab_backend.SIM_REPORT_LOG_SIZE`` reports — enough for a
    serving scheduler to aggregate per-decode-step energy/cycles across a
    whole batch window without interposing on every call."""
    from repro.kernels import pimsab_backend

    return pimsab_backend.sim_report_log()


def clear_sim_report_log():
    """Empty this thread's :func:`sim_report_log` ring (benchmarks call this
    at window boundaries so aggregation never double-counts a step)."""
    from repro.kernels import pimsab_backend

    return pimsab_backend.clear_sim_report_log()


def last_verify_report():
    """Static-verifier :class:`~repro.core.compiler.verify.VerifyReport`
    tuple of the most recent pimsab compile on this thread — one report per
    verified ISA stream (the functional + timing pair for a compiled traced
    program).  Empty before any pimsab compile, or after ``verify=False``."""
    from repro.kernels import pimsab_backend

    return pimsab_backend.last_verify_report()


def profile_timelines(enable: bool = True):
    """Context manager: pimsab timing runs inside it record per-instruction
    scheduling intervals on their :class:`SimReport` (``report.timeline``) —
    what ``kernels_bench --profile`` dumps as the per-phase artifact."""
    from repro.kernels import pimsab_backend

    return pimsab_backend.profile_timelines(enable)


# ---------------------------------------------------------------------------
# Program API: trace → compile-once → execute (repro.kernels.program)
# ---------------------------------------------------------------------------

from repro.kernels.program import (  # noqa: E402  (after dispatch: program.py
    Executor,                        # lazily imports this module back)
    Program,
    ResidentState,
    TraceError,
    TracedFunction,
    clear_compile_cache,
    compile_cache_info,
    compile_program,
    trace,
)

# ``api.compile(program)`` — the documented spelling; the module-level name
# deliberately shadows the (unused here) builtin.
compile = compile_program

# Structured diagnostics of the compile-time static verifier
# (``api.compile(..., verify=True)``, on by default for pimsab).
from repro.core.compiler.verify import (  # noqa: E402
    Diagnostic,
    VerifierError,
    VerifierWarning,
    VerifyReport,
)

# Mapping autotuner (``api.compile(..., tune=True | TuneConfig(...))``, or
# scope-wide via ``with api.tuning(...):``).  Tuned winners are cached like
# compiled executables; inspect hits/misses/provenance via
# ``api.tune_cache_info()``.
from repro.core.compiler.autotune import (  # noqa: E402
    TuneConfig,
    clear_tune_cache,
    tune_cache_info,
    tuning,
)

# Multi-chip scale-out (``api.compile(program, chips=N)`` or the explicit
# cluster/report entry points) — sharded bit-exact execution over an
# inter-chip link model; see repro.kernels.multichip and docs/architecture.md.
from repro.core.noc import ChipCluster, ChipLink  # noqa: E402
from repro.kernels.multichip import (  # noqa: E402
    ClusterExecutor,
    ClusterReport,
    cluster_timing_report,
    compile_cluster,
    weak_scaling_report,
)
