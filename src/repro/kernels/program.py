"""Graph-level Program API: trace → compile-once → execute.

Eager registry dispatch (``api.use_backend`` + one kernel per call) lowers
every call in isolation and, on the ``pimsab`` backend, round-trips every
intermediate through DRAM.  This module adds the opt-in fast path:

* :func:`trace` wraps a function of registry-kernel calls; calling the traced
  function captures the kernel calls into a :class:`Program` — a small
  dataflow **DAG** over slots / captured constants / node outputs.  Values
  may fan out to any number of consumers (a residual-block input feeds both
  the conv path and the shortcut), kernels may fan in node-valued operands
  (residual adds), and any subset of values can be returned as program
  outputs; node order is trace order, which is topological by construction.
* :func:`compile_program` (exported as ``api.compile``) lowers a Program for
  the active backend **once** and returns a cached :class:`Executor`:

  - ``xla``/``interpret``/``pallas`` — the whole chain replays inside a
    single ``jax.jit``, so repeated calls never re-trace.  The executable
    carries the program's name (``jit_<name>``) and each node's device ops
    the name scope ``n<idx>/<kernel>``; ``Executor.__call__`` is a
    ``program.call`` profiler span on the host;
  - ``pimsab`` — the chain becomes one ``tensor_dsl.WorkloadGraph`` and is
    distributed/allocated/codegen'd jointly (``pimsab_backend``): integer
    producer→consumer intermediates stay CRAM-resident and the DRAM
    store/load pair at the kernel boundary is elided.

* The compile cache is keyed on the program signature (kernel names, operand
  shapes/dtypes, kwargs such as ``slice_bits``/``skip``, captured-constant
  fingerprints) plus the backend and — for pimsab — the functional machine
  config.  :func:`compile_cache_info` exposes hit/miss/size counters so
  "second compile was a cache hit" is assertable; :func:`cached_executable`
  shares the same cache with coarser consumers (the serve engine's
  prefill/decode steps).

Precision note: eager pimsab lowering sizes integer operands from their
*values* (per-call calibration); program mode must replay with fresh values,
so it sizes them from the *dtype* — results stay bit-exact, modeled cycles
differ slightly.
"""
from __future__ import annotations

import contextvars
import hashlib
import re
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

__all__ = [
    "TraceError",
    "ProgramValue",
    "OpCall",
    "Program",
    "TracedFunction",
    "trace",
    "ResidentState",
    "Executor",
    "compile_program",
    "compile_cache_info",
    "clear_compile_cache",
    "cached_executable",
    "CacheInfo",
]


class TraceError(TypeError):
    """A traced function did something the Program IR cannot capture."""


# ---------------------------------------------------------------------------
# IR
# ---------------------------------------------------------------------------

# input references: ("slot", i) — i-th leaf of the call arguments;
# ("node", i) — output of the i-th captured kernel call;
# ("const", i) — array captured from the traced function's closure.
InRef = Tuple[str, int]


@dataclass(frozen=True)
class OpCall:
    """One captured registry-kernel call."""

    kernel: str
    inputs: Tuple[InRef, ...]
    kwargs: Tuple[Tuple[str, Any], ...]
    pallas_kwargs: Tuple[Tuple[str, Any], ...]
    out_aval: Tuple[Tuple[int, ...], str]  # (shape, dtype)


@dataclass(frozen=True)
class Program:
    """A traced sequence of registry kernel calls (the compile unit)."""

    name: str
    ops: Tuple[OpCall, ...]
    n_slots: int
    slot_avals: Tuple[Tuple[Tuple[int, ...], str], ...]
    consts: Tuple[np.ndarray, ...]
    in_tree: Any  # jax PyTreeDef of (args, kwargs)
    out_tree: Any
    out_refs: Tuple[InRef, ...]

    @property
    def kernels(self) -> Tuple[str, ...]:
        return tuple(op.kernel for op in self.ops)

    def signature(self) -> Tuple:
        """Hashable compile key: everything lowering depends on except the
        slot *values* — ops, slot avals, both pytree structures, the output
        refs (programs differing only in what they return must not share an
        Executor), and a content fingerprint per captured constant (their
        values are baked into the executor).  Memoized: constant hashing is
        paid once per Program, not per compile lookup."""
        sig = getattr(self, "_signature_cache", None)
        if sig is None:
            const_fp = tuple(
                (c.shape, str(c.dtype), hashlib.sha1(np.ascontiguousarray(c)).hexdigest())
                for c in self.consts
            )
            sig = (self.name, self.ops, self.slot_avals, self.in_tree,
                   self.out_tree, self.out_refs, const_fp)
            object.__setattr__(self, "_signature_cache", sig)
        return sig


class ProgramValue:
    """Placeholder for a kernel output inside :func:`trace`.

    It can only be passed to another registry kernel; any other use (jnp
    arithmetic, ``astype``, materialization) raises :class:`TraceError` with
    the capture position, so failures are early and named.
    """

    def __init__(self, node: int, aval: Tuple[Tuple[int, ...], str], kernel: str):
        self._node = node
        self._aval = aval
        self._kernel = kernel

    @property
    def shape(self) -> Tuple[int, ...]:
        return self._aval[0]

    @property
    def dtype(self):
        return np.dtype(self._aval[1])

    @property
    def ndim(self) -> int:
        return len(self._aval[0])

    def _refuse(self, what: str):
        raise TraceError(
            f"the output of kernel {self._kernel!r} (node {self._node}) is a "
            f"program-trace placeholder and does not support {what}; inside "
            "api.trace(...) kernel outputs can only feed other registry "
            "kernels (or be returned). Compute everything else outside the "
            "traced function."
        )

    def __array__(self, *a, **k):
        self._refuse("materialization")

    def __getattr__(self, name):
        raise TraceError(
            f"the output of kernel {self._kernel!r} (node {self._node}) is a "
            f"program-trace placeholder (no attribute {name!r}); inside "
            "api.trace(...) kernel outputs can only feed other registry "
            "kernels or be returned."
        )

    for _op in ("add", "radd", "sub", "rsub", "mul", "rmul", "truediv",
                "rtruediv", "matmul", "neg", "lt", "le", "gt", "ge"):
        exec(  # noqa: S102 - tiny metaprogram, keeps the refusal list in one place
            f"def __{_op}__(self, *a): self._refuse('arithmetic (__{_op}__)')"
        )
    del _op


def _aval_of(x: Any) -> Tuple[Tuple[int, ...], str]:
    if isinstance(x, ProgramValue):
        return x._aval
    a = np.asarray(x) if not hasattr(x, "dtype") else x
    return (tuple(a.shape), str(a.dtype))


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class _TraceCtx:
    def __init__(self, name: str, leaves: List[Any]):
        self.name = name
        self.slots_by_id = {id(l): i for i, l in enumerate(leaves)}
        self.slot_avals = tuple(_aval_of(l) for l in leaves)
        self.ops: List[OpCall] = []
        self.consts: List[Any] = []  # original objects (keeps ids alive)
        self.consts_by_id: Dict[int, int] = {}

    def _ref(self, a: Any) -> InRef:
        if isinstance(a, ProgramValue):
            return ("node", a._node)
        aid = id(a)
        if aid in self.slots_by_id:
            return ("slot", self.slots_by_id[aid])
        if aid not in self.consts_by_id:
            self.consts_by_id[aid] = len(self.consts)
            self.consts.append(a)
        return ("const", self.consts_by_id[aid])

    @staticmethod
    def _freeze_kwargs(kw: Optional[Dict[str, Any]]) -> Tuple[Tuple[str, Any], ...]:
        items = tuple(sorted((kw or {}).items()))
        try:
            hash(items)
        except TypeError:
            raise TraceError(
                f"kernel kwargs {kw!r} are not hashable — program signatures "
                "require static (hashable) kwargs"
            ) from None
        return items

    def record(self, kernel: str, args: Tuple[Any, ...], kwargs: Dict[str, Any],
               pallas_kwargs: Optional[Dict[str, Any]]) -> ProgramValue:
        from repro.kernels import api

        refs = tuple(self._ref(a) for a in args)
        # stand-ins for shape inference (node refs use the recorded aval)
        structs = []
        for (kind, i), a in zip(refs, args):
            shp, dt = self.ops[i].out_aval if kind == "node" else _aval_of(a)
            structs.append(jax.ShapeDtypeStruct(shp, np.dtype(dt)))
        oracle = api.get_kernel(kernel).oracle
        out = jax.eval_shape(lambda *xs: oracle(*xs, **(kwargs or {})), *structs)
        self.ops.append(OpCall(
            kernel=kernel,
            inputs=refs,
            kwargs=self._freeze_kwargs(kwargs),
            pallas_kwargs=self._freeze_kwargs(pallas_kwargs),
            out_aval=(tuple(out.shape), str(out.dtype)),
        ))
        return ProgramValue(len(self.ops) - 1, (tuple(out.shape), str(out.dtype)), kernel)


_trace_ctx: contextvars.ContextVar[Optional[_TraceCtx]] = contextvars.ContextVar(
    "repro_program_trace_ctx", default=None
)


def active_trace() -> Optional[_TraceCtx]:
    """The trace context ``api.dispatch`` must record into (None = eager)."""
    return _trace_ctx.get()


class TracedFunction:
    """``trace(fn)`` wrapper: call it like ``fn`` — each distinct input
    signature is traced once, compiled once (per backend), then replayed."""

    def __init__(self, fn: Callable[..., Any], name: Optional[str] = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "program")
        self._programs: Dict[Tuple, Program] = {}
        self._lock = threading.Lock()

    def trace(self, *args, **kwargs) -> Program:
        """Capture a fresh Program for these arguments (no caching)."""
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        return self._trace(leaves, in_tree, args, kwargs)

    def _trace(self, leaves, in_tree, args, kwargs) -> Program:
        ctx = _TraceCtx(self.name, leaves)
        token = _trace_ctx.set(ctx)
        try:
            result = self.fn(*args, **kwargs)
        finally:
            _trace_ctx.reset(token)
        if not ctx.ops:
            raise TraceError(
                f"trace({self.name}) captured no registry kernel calls — "
                "nothing to compile; call kernels via repro.kernels.api"
            )
        out_leaves, out_tree = jax.tree_util.tree_flatten(result)
        out_refs = tuple(ctx._ref(l) for l in out_leaves)
        return Program(
            name=self.name,
            ops=tuple(ctx.ops),
            n_slots=len(leaves),
            slot_avals=ctx.slot_avals,
            consts=tuple(np.asarray(c) for c in ctx.consts),
            in_tree=in_tree,
            out_tree=out_tree,
            out_refs=out_refs,
        )

    def program_for(self, *args, **kwargs) -> Program:
        """The (cached) Program this call signature maps to.

        The per-signature trace cache assumes captured constants (closure
        arrays) are stable; use this for introspection or when you own that
        guarantee — ``__call__`` re-traces instead, so it never replays stale
        constants.
        """
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        key = (in_tree, tuple(_aval_of(l) for l in leaves))
        with self._lock:
            prog = self._programs.get(key)
        if prog is None:
            prog = self._trace(leaves, in_tree, args, kwargs)
            with self._lock:
                prog = self._programs.setdefault(key, prog)
        return prog

    def __call__(self, *args, **kwargs):
        # Re-trace on every call: capture is cheap (one eval_shape per
        # kernel) and it keeps captured constants honest — an array computed
        # *from the arguments* inside fn is frozen into the program as a
        # constant, so replaying a cached trace would silently reuse the old
        # value.  Fresh constants change the signature's content fingerprint,
        # which routes to a correct (re)compile instead; only the expensive
        # lowering is cached.
        prog = self.trace(*args, **kwargs)
        ex = compile_program(prog)
        leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
        return ex._execute_leaves(leaves)


def trace(fn: Callable[..., Any], *, name: Optional[str] = None) -> TracedFunction:
    """Wrap ``fn`` (a chain of ``repro.kernels.api`` kernel calls) so each
    call signature is captured once and executed through a cached, compiled
    :class:`Executor` on the backend active at call time."""
    return TracedFunction(fn, name=name)


# ---------------------------------------------------------------------------
# executors + compile cache
# ---------------------------------------------------------------------------


class ResidentState:
    """A persistent integer tensor the pimsab backend keeps CRAM-resident
    across program executions — the serve engine's KV cache.

    The handle names a ``(rows, fields)`` array stored at ``prec`` bits per
    field.  Bind it to a traced program's slot via
    ``compile_program(prog, states={slot_index: handle})``: the compiler
    reserves a wordline region for it, pins the slot's ``kv_append`` updater
    to that region (in_a and out alias — the append updates CRAM in place,
    zero DRAM traffic for the cache), and the executor seeds/harvests the
    region around each run.  ``.value`` always mirrors the logical cache
    after the most recent execution, so host-side swapping (the continuous-
    batching scheduler parking an evicted request's cache) is just reading
    and reassigning ``.value``.

    The slot still takes an aval-matching argument at call time — pass
    :meth:`placeholder`; its contents are ignored for state-bound slots.
    When the mapping layer *declines* residency (capacity or cost-model
    gated, see the compile's N-PLAN notes), execution transparently falls
    back to streaming ``.value`` through DRAM — same results, no silent
    wrong answers."""

    def __init__(self, name: str, shape: Tuple[int, int], prec: int,
                 dtype: str = "int8", init: Optional[np.ndarray] = None):
        if len(shape) != 2:
            raise ValueError(f"ResidentState {name!r} must be 2-D (rows, fields)")
        self.name = str(name)
        self.shape = (int(shape[0]), int(shape[1]))
        self.prec = int(prec)
        self.dtype = np.dtype(dtype)
        self.value = (
            np.zeros(self.shape, np.int64) if init is None
            else np.asarray(init, np.int64).copy()
        )
        if self.value.shape != self.shape:
            raise ValueError(
                f"ResidentState {name!r} init shape {self.value.shape} != {self.shape}"
            )

    def spec(self) -> Tuple[str, Tuple[int, int], int]:
        """The hashable compile-key identity: (name, shape, prec)."""
        return (self.name, self.shape, self.prec)

    def placeholder(self) -> np.ndarray:
        """An aval-matching argument for the state's slot — the compiled
        program reads the CRAM-resident value, never this array."""
        return np.zeros(self.shape, self.dtype)

    def to_array(self) -> np.ndarray:
        """The logical cache at its declared dtype (a copy)."""
        return self.value.astype(self.dtype)

    def __repr__(self) -> str:
        return (f"ResidentState({self.name!r}, shape={self.shape}, "
                f"prec={self.prec})")


@dataclass(frozen=True)
class CacheInfo:
    """Compile-cache counters plus one metadata record per cached Executor.

    Each entry is ``{"name", "backend", "kernels", "verify"}`` where
    ``verify`` summarizes the static-verifier outcome of that compile —
    error/warning counts and the ``N-PLAN`` notes explaining why
    ``distribute_graph`` declined residency or double buffering for the
    cached plan (``None`` when the compile skipped verification)."""

    hits: int
    misses: int
    size: int
    entries: Tuple[Dict[str, Any], ...] = ()


class Executor:
    """A compiled Program bound to one backend.  Call it with the same
    argument structure the traced function took; re-lowering never happens
    (``jax.jit`` replay for the TPU-side backends, a fused
    ``WorkloadGraph`` program for pimsab)."""

    def __init__(self, program: Program, backend: str,
                 run: Callable[[List[Any]], Any],
                 report: Optional[Any] = None,
                 verify_reports: Tuple[Any, ...] = ()):
        self.program = program
        self.backend = backend
        self._run = run
        self.report = report  # aggregated SimReport (pimsab), else None
        self.verify_reports = verify_reports  # VerifyReports (pimsab verify=True)
        self.states: Optional[Dict[int, "ResidentState"]] = None

    def bind_states(self, states: Dict[int, "ResidentState"]) -> None:
        """Swap in the ResidentState handles the next calls seed/harvest.

        The compiled artifact is keyed on state *specs*, not handles, so one
        executor serves many requests: the continuous-batching scheduler
        rebinds each request's caches before its decode step (spec-
        compatible handles only — the executor validates at run time)."""
        self.states = dict(states)

    # a host span over the flattening, the argument checks and the dispatch;
    # the device runs the executable after (and partly during) it
    @partial(jax.profiler.annotate_function, name="program.call")
    def __call__(self, *args, **kwargs):
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        if in_tree != self.program.in_tree:
            raise TypeError(
                f"Executor({self.program.name!r}) called with a different "
                f"argument structure than it was traced with:\n"
                f"  traced: {self.program.in_tree}\n  got:    {in_tree}"
            )
        avals = tuple(_aval_of(l) for l in leaves)
        if avals != self.program.slot_avals:
            diffs = [
                f"  leaf {i}: traced {t}, got {g}"
                for i, (t, g) in enumerate(zip(self.program.slot_avals, avals))
                if t != g
            ]
            raise TypeError(
                f"Executor({self.program.name!r}) called with different leaf "
                "shapes/dtypes than it was compiled for (compile a new "
                "program for this signature):\n" + "\n".join(diffs)
            )
        return self._execute_leaves(leaves)

    def _execute_leaves(self, leaves: List[Any]):
        out_leaves = self._run(leaves)
        return jax.tree_util.tree_unflatten(self.program.out_tree, out_leaves)


_cache_lock = threading.Lock()
_cache: Dict[Any, Any] = {}
_cache_meta: Dict[Any, Dict[str, Any]] = {}
_hits = 0
_misses = 0


def compile_cache_info() -> CacheInfo:
    """Hit/miss/size counters of the global compile cache (Executors + other
    cached executables such as serve steps), plus per-entry metadata — the
    structured verifier summary recorded at compile time, including the
    plan-decline notes (see :class:`CacheInfo`)."""
    with _cache_lock:
        return CacheInfo(
            hits=_hits, misses=_misses, size=len(_cache),
            entries=tuple(dict(m) for m in _cache_meta.values()),
        )


def clear_compile_cache() -> None:
    """Empty the global compile cache and reset its hit/miss counters (test
    isolation; compiled Executors are rebuilt on next use)."""
    global _hits, _misses
    with _cache_lock:
        _cache.clear()
        _cache_meta.clear()
        _hits = 0
        _misses = 0


def cached_executable(key: Any, build: Callable[[], Any],
                      meta: Optional[Callable[[Any], Dict[str, Any]]] = None) -> Any:
    """Generic compile-once: return the cached artifact for ``key`` or build
    it (outside the lock — builds can be slow and re-entrant).  ``meta``, if
    given, maps the freshly built artifact to the :class:`CacheInfo` entry
    recorded for it."""
    global _hits, _misses
    with _cache_lock:
        if key in _cache:
            _hits += 1
            return _cache[key]
    artifact = build()
    with _cache_lock:
        if key in _cache:  # lost a race: keep the first, still a miss for us
            _misses += 1
            return _cache[key]
        _misses += 1
        _cache[key] = artifact
        if meta is not None:
            _cache_meta[key] = meta(artifact)
    return artifact


def _identifier(name: str) -> str:
    """``name`` as a Python identifier: other characters become ``_``."""
    ident = re.sub(r"[^0-9A-Za-z_]", "_", name) or "program"
    return ident if not ident[0].isdigit() else "_" + ident


def _jax_run(program: Program, backend: str) -> Callable[[List[Any]], Any]:
    """Replay the whole program inside one jitted function (compile-once for
    the jax-side backends)."""
    from repro.kernels import api

    def replay(leaves, consts):
        env: Dict[int, Any] = {}

        def resolve(ref):
            kind, i = ref
            if kind == "slot":
                return leaves[i]
            if kind == "const":
                return consts[i]
            return env[i]

        with api.use_backend(backend):
            for idx, op in enumerate(program.ops):
                vals = [resolve(r) for r in op.inputs]
                with jax.named_scope(f"n{idx}"):
                    env[idx] = api.dispatch(
                        op.kernel, *vals,
                        pallas_kwargs=dict(op.pallas_kwargs) or None,
                        **dict(op.kwargs),
                    )
        return [resolve(r) for r in program.out_refs]

    # the executable is named after the program: jit_<name>
    replay.__name__ = replay.__qualname__ = _identifier(program.name)
    jitted = jax.jit(replay)
    consts = [np.asarray(c) for c in program.consts]
    return lambda leaves: jitted(leaves, consts)


def _executor_meta(ex: "Executor") -> Dict[str, Any]:
    """The :class:`CacheInfo` entry for a freshly compiled Executor: identity
    plus the static-verifier summary (error/warning counts and the N-PLAN
    notes recording why residency/double-buffering was declined)."""
    entry: Dict[str, Any] = {
        "name": ex.program.name,
        "backend": ex.backend,
        "kernels": list(ex.program.kernels),
        "verify": None,
    }
    if ex.verify_reports:
        entry["verify"] = {
            "ok": all(r.ok for r in ex.verify_reports),
            "errors": sum(len(r.errors) for r in ex.verify_reports),
            "warnings": sum(len(r.warnings) for r in ex.verify_reports),
            "notes": sorted({
                (d.node, d.message)
                for r in ex.verify_reports for d in r.notes
            }),
        }
    if ex.report is not None and getattr(ex.report, "autotune", None):
        entry["autotune"] = dict(ex.report.autotune)
    return entry


def compile_program(program: Program, backend: Optional[str] = None, *,
                    verify: bool = True,
                    states: Optional[Dict[int, ResidentState]] = None,
                    tune: Any = None,
                    chips: Optional[int] = None,
                    cluster: Any = None,
                    plan: str = "auto") -> Executor:
    """Lower ``program`` for ``backend`` (default: the active backend) and
    return the Executor — cached on (signature, backend[, machine config,
    verify]), so an identical second compile is a pure cache hit.

    ``verify=True`` (the default) runs the compile-time static verifier on
    the pimsab backend — liveness/def-use, schedule-hazard race detection
    and precision-overflow lint over both fused ISA streams — raising
    :class:`repro.core.compiler.verify.VerifierError` on any error; the
    verifier summary (including plan-decline notes) is recorded on the cache
    entry, visible via :func:`compile_cache_info`.  The flag is a no-op on
    the jax-side backends.

    ``states`` (pimsab only) maps slot index → :class:`ResidentState`: the
    slot's KV cache stays CRAM-resident across calls.  The cache key carries
    the state *specs*, so spec-identical handles share one executor — use
    :meth:`Executor.bind_states` (done here automatically) to swap handles
    between calls.

    ``tune`` (pimsab only) opts the timing-side lowering into the mapping
    autotuner: ``True`` uses the default :class:`~repro.core.compiler.
    autotune.TuneConfig`, an explicit ``TuneConfig`` pins the search budget
    and seed, ``False`` forces it off, and ``None`` (the default) inherits
    an enclosing :func:`repro.kernels.api.tuning` scope.  The effective
    config joins the cache key, so tuned and untuned executors for the same
    program coexist, and the winning search provenance is recorded on the
    cache entry (``compile_cache_info().entries[...]["autotune"]``).

    ``chips``/``cluster`` (pimsab only) compile the program for a multi-chip
    :class:`~repro.core.noc.ChipCluster` instead of one chip: the returned
    :class:`~repro.kernels.multichip.ClusterExecutor` runs the sharded plan
    bit-exactly against the 1-chip result.  ``plan`` forces ``"tp"``/``"pp"``
    or leaves the cost model to choose (``"auto"``, the default)."""
    from repro.kernels import api

    backend = api._check_backend(backend or api.current_backend())
    if cluster is not None or (chips is not None and int(chips) != 1):
        # Multi-chip scale-out: shard the program across a ChipCluster and
        # return the bit-exact ClusterExecutor (repro.kernels.multichip).
        if backend != "pimsab":
            raise NotImplementedError(
                "chips/cluster sharding is a pimsab-backend concept; the "
                "jax-side backends replay the whole program on one device"
            )
        if states:
            raise NotImplementedError(
                "ResidentState stays CRAM-resident on one chip and does not "
                "shard across a ChipCluster; serve on chips=1"
            )
        from repro.kernels import multichip

        return multichip.compile_cluster(
            program, chips=chips, cluster=cluster,
            plan=plan, verify=verify, tune=tune,
        )
    key: Tuple = ("program", program.signature(), backend)
    if backend == "pimsab":
        from repro.core.compiler import autotune
        from repro.kernels import pimsab_backend as pb

        tc = autotune.resolve(tune) if tune is not None else autotune.active()
        state_specs = tuple(sorted(
            (slot, st.spec()) for slot, st in (states or {}).items()
        ))
        key = key + (pb._functional_cfg(), bool(verify), state_specs, tc)

        def build() -> Executor:
            compiled = pb.compile_traced_program(
                program, verify=verify,
                state_slots={slot: st.spec() for slot, st in states.items()}
                if states else None,
                tune=tc if tc is not None else False,
            )
            ex = Executor(
                program, backend,
                run=None,  # set below: the closure reads ex.states per call
                report=compiled.report,
                verify_reports=compiled.verify_reports,
            )
            ex._run = lambda leaves: pb.execute_traced_program(
                compiled, leaves, states=ex.states
            )
            return ex
    else:
        if states:
            raise NotImplementedError(
                "ResidentState is a pimsab-backend concept; the jax-side "
                "backends replay the whole chain functionally"
            )

        def build() -> Executor:
            return Executor(program, backend, run=_jax_run(program, backend))

    ex = cached_executable(key, build, meta=_executor_meta)
    if states is not None:
        ex.bind_states(states)
    return ex
