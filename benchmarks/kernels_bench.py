"""Registry-driven kernel micro-benchmarks (the perf-trajectory baseline).

The kernel list is enumerated from the backend registry
(``repro.kernels.api.registered_kernels``) — not hand-maintained — so a new
``@register_kernel`` automatically joins the bench.  Each kernel's Pallas
body is cross-checked once, in interpret mode, against its oracle on a
reduced shape (the kernels' speed on the chip is measured by ``chipbench/``).

Every kernel also runs once under ``use_backend("pimsab")`` on a reduced
shape: the call lowers through the tensor DSL → §V compiler → ISA, executes
bit-exactly on the functional simulator, and attaches *modeled* full-chip
cycles/energy via ``api.last_sim_report()`` — so ``BENCH_kernels.json``
tracks the architecture model's trajectory.

A **program-mode** section runs the `matmul → ewise_add → relu` chain through
``api.trace``/``api.compile`` on the pimsab backend and records the
fused-vs-eager DRAM-cycle win (the elided store/load pairs) plus the compile
cache behaviour — pinning the Program API's headline number as an artifact.
An **e2e** section (``benchmarks/e2e_resnet.py``) does the same at network
scale: the ResNet18-style DAG program executed bit-exactly on the functional
simulator plus the paper-shaped config modeled timing-only, with per-layer
cycles gated individually (schema: ``docs/benchmarks.md``).

Since the phase-timeline refactor, every pimsab entry carries both clocks:
``modeled_cycles`` is the overlapped makespan (double-buffered / staggered
schedules hide DRAM streaming behind compute), ``serialized_cycles`` the
fully-dependent sum, ``overlapped_cycles`` the win, plus the critical-path
breakdown and per-resource utilization.  A **large_shapes** section models
real layer shapes (256×1024×1024 matmul, 64k-element elementwise) timing-only
at full chip scale — the shapes that actually exercise multi-phase
pipelining, far beyond what bit-serial functional simulation can chew.

``run()`` returns the row list for benchmarks/run.py; ``main()`` also writes
``BENCH_kernels.json`` at the repo root so future PRs have a baseline to
compare against.  ``main(check=True)`` (CLI: ``--check``) first diffs the
fresh *modeled* cycles (per-kernel, large-shape, and program-mode) against
the committed baseline and fails on a >5% regression — wall-clock numbers
are machine-dependent and are not gated.  ``main(profile=True)`` (CLI:
``--profile``) additionally records per-instruction scheduling intervals and
writes them to ``BENCH_kernels_timeline.json`` (uploaded by CI) — the
per-phase timeline artifact.

Every pinned modeled row is produced with the **mapping autotuner** on at a
small fixed budget (``BENCH_TUNE``; per-section overrides in
``e2e_resnet.DEFAULT_TUNE`` / ``serve_bench.DEFAULT_TUNE``): the timing
stream takes the searched mapping, functional execution keeps the heuristic
plan, so every bit-exactness sentinel is unaffected by construction.  Each
row carries its search provenance under ``autotune`` (schema:
``docs/benchmarks.md``).  ``main(autotune=True)`` (CLI: ``--autotune``)
additionally writes ``BENCH_autotune.json`` — per-row candidate counts and
provenance — and, combined with ``--check``, asserts tuned modeled cycles
never regress the pinned baselines (``<=`` per row, not just within 5%).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import re
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import api, ref

REPO_ROOT = Path(__file__).resolve().parent.parent
OUT_PATH = REPO_ROOT / "BENCH_kernels.json"
TIMELINE_PATH = REPO_ROOT / "BENCH_kernels_timeline.json"
AUTOTUNE_PATH = REPO_ROOT / "BENCH_autotune.json"

# The small fixed search budget every pinned kernel/large-shape/program row
# is produced with (deterministic: enumeration order is seed-rotated, no
# wall-clock anywhere in the loop).  The e2e and serve sections carry their
# own budgets — see e2e_resnet.DEFAULT_TUNE / serve_bench.DEFAULT_TUNE.
BENCH_TUNE = api.TuneConfig(budget=96, beam=4, seed=0)


def _tuning_ctx(tune: Optional[api.TuneConfig]):
    return api.tuning(tune) if tune is not None else contextlib.nullcontext()

# Validation operand builders per registered kernel (reduced shapes).  A
# kernel registered without an entry in _cases() still fails loudly in run()
# — coverage is enforced by the registry, not this dict.
_SEED = 0


def _img(shape, lo=-100, hi=100, seed=0):
    """Random int32 tensor for the conv/pool/int-matmul cases."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(lo, hi, shape), jnp.int32)


def _wconv(shape, seed=0):
    """Random int8-range conv weight (int32 storage)."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-127, 128, shape), jnp.int32)


def _validate_binary(fn, oracle, x, w) -> bool:
    with api.use_backend("interpret"):
        got = fn(x, w)
    return bool(jnp.allclose(oracle(x, w), got))


def _bitslice_args(m, n, k, xb, wb):
    rng = np.random.default_rng(_SEED)
    xlo, xhi = ref.slice_range(xb)
    wlo, whi = ref.slice_range(wb)
    x = jnp.asarray(rng.integers(xlo, xhi + 1, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(wlo, whi + 1, (k, n)), jnp.int32)
    return (
        api.SlicedTensor.from_int(x, xb),
        api.SlicedTensor.from_int(w, wb, scale=jnp.ones((n,), jnp.float32)),
    )


def _cases() -> Dict[str, Callable[[], bool]]:
    """Per registered kernel: a check that interpret mode matches the oracle
    on a reduced shape."""
    return {
        "bitslice_matmul": lambda: _validate_matmul(128, 128, 128, 8, 16),
        "htree_reduce": lambda: _validate_unary(
            api.htree_reduce, ref.htree_reduce_ref,
            jax.random.normal(jax.random.key(_SEED), (16, 512), jnp.float32),
        ),
        "rglru_scan": lambda: _validate_rglru(),
        "ewise_add": lambda: _validate_unary(
            lambda x: api.ewise_add(x, x), lambda x: x + x,
            jax.random.normal(jax.random.key(6), (64, 128), jnp.float32),
        ),
        "relu": lambda: _validate_unary(
            api.relu, ref.relu_ref,
            jax.random.normal(jax.random.key(8), (64, 128), jnp.float32),
        ),
        "conv2d": lambda: _validate_binary(
            lambda x, w: api.conv2d(x, w, stride=1, padding=1),
            lambda x, w: ref.conv2d_ref(x, w, stride=1, padding=1),
            _img((1, 4, 8, 8), seed=11), _wconv((4, 4, 3, 3), seed=12),
        ),
        "int_matmul": lambda: _validate_binary(
            api.int_matmul, ref.int_matmul_ref,
            _img((32, 64), seed=15), _img((64, 16), seed=16),
        ),
        "maxpool2d": lambda: _validate_unary(
            lambda x: api.maxpool2d(x, window=2),
            lambda x: ref.maxpool2d_ref(x, window=2),
            _img((2, 4, 16, 16), seed=18),
        ),
        "avgpool2d": lambda: _validate_unary(
            lambda x: api.avgpool2d(x, window=2),
            lambda x: ref.avgpool2d_ref(x, window=2),
            _img((2, 4, 16, 16), seed=20),
        ),
        "global_avgpool": lambda: _validate_unary(
            api.global_avgpool, ref.global_avgpool_ref,
            _img((2, 8, 16, 16), seed=22),
        ),
        # serving kernels — quantized single-head attention decode (see
        # docs/serving.md for the precision envelopes the shapes respect)
        "attention_qk": lambda: _validate_binary(
            api.attention_qk, ref.attention_qk_ref,
            _img((4, 16), -7, 8, seed=42), _img((8, 16), -15, 16, seed=43),
        ),
        "softmax_fixedpoint": lambda: _validate_unary(
            lambda x: api.softmax_fixedpoint(x, in_frac=7),
            lambda x: ref.softmax_fixedpoint_ref(x, in_frac=7),
            _img((8, 16), -400, 400, seed=45),
        ),
        "attention_pv": lambda: _validate_binary(
            api.attention_pv, ref.attention_pv_ref,
            _img((4, 8), 0, 65, seed=48), _img((8, 16), seed=49),
        ),
        "decode_gemv": lambda: _validate_binary(
            api.decode_gemv, ref.decode_gemv_ref,
            _img((16, 32), -50, 50, seed=52), _img((32,), -50, 50, seed=53),
        ),
        "kv_append": lambda: _validate_kv_append(),
    }


def _pimsab_cases() -> Dict[str, Callable]:
    """Reduced-shape calls for the architecture-model run (functional
    simulation is bit-serial — registry-bench shapes would take minutes)."""
    rng = np.random.default_rng(_SEED)

    def _matmul():
        x, w = _bitslice_args(32, 32, 64, 8, 8)
        want = api.matmul(x, w)  # xla oracle (active backend is set by caller)
        with api.use_backend("pimsab"):
            got = api.matmul(x, w)
        return bool(jnp.allclose(want, got))

    def _htree():
        x = jax.random.normal(jax.random.key(_SEED), (16, 64), jnp.float32)
        with api.use_backend("pimsab"):
            got = api.htree_reduce(x)
        return bool(jnp.allclose(ref.htree_reduce_ref(x), got, atol=5e-3))

    def _rglru():
        a = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (1, 8, 64)))
        b = jax.random.normal(jax.random.key(2), (1, 8, 64))
        h0 = jax.random.normal(jax.random.key(3), (1, 64))
        with api.use_backend("pimsab"):
            got = api.rglru_scan(a, b, h0)
        return bool(jnp.allclose(ref.rglru_scan_ref(a, b, h0), got, atol=5e-2))

    def _ewise():
        x = jnp.asarray(rng.integers(-100, 100, (16, 64)), jnp.int32)
        with api.use_backend("pimsab"):
            got = api.ewise_add(x, x)
        return bool((np.asarray(got) == np.asarray(x + x)).all())

    def _relu():
        x = jnp.asarray(rng.integers(-100, 100, (16, 64)), jnp.int32)
        with api.use_backend("pimsab"):
            got = api.relu(x)
        return bool((np.asarray(got) == np.asarray(jnp.maximum(x, 0))).all())

    def _conv():
        x = _img((1, 3, 8, 8), -8, 8, seed=30)
        w = _wconv((4, 3, 3, 3), seed=31)
        want = ref.conv2d_ref(x, w, stride=1, padding=1)
        with api.use_backend("pimsab"):
            got = api.conv2d(x, w, stride=1, padding=1)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _intmm():
        x = _img((16, 32), seed=32)
        w = _img((32, 8), seed=33)
        want = ref.int_matmul_ref(x, w)
        with api.use_backend("pimsab"):
            got = api.int_matmul(x, w)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _maxpool():
        x = _img((1, 4, 8, 8), seed=34)
        want = ref.maxpool2d_ref(x, window=2)
        with api.use_backend("pimsab"):
            got = api.maxpool2d(x, window=2)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _avgpool():
        x = _img((1, 4, 8, 8), seed=35)
        want = ref.avgpool2d_ref(x, window=2)
        with api.use_backend("pimsab"):
            got = api.avgpool2d(x, window=2)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _gap():
        x = _img((2, 8, 4, 4), seed=36)
        want = ref.global_avgpool_ref(x)
        with api.use_backend("pimsab"):
            got = api.global_avgpool(x)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _qk():
        q = _img((2, 8), -7, 8, seed=40)
        k = _img((4, 8), -15, 16, seed=41)
        want = ref.attention_qk_ref(q, k)
        with api.use_backend("pimsab"):
            got = api.attention_qk(q, k)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _softmax():
        x = _img((4, 8), -400, 400, seed=44)
        want = ref.softmax_fixedpoint_ref(x, in_frac=7)
        with api.use_backend("pimsab"):
            got = api.softmax_fixedpoint(x, in_frac=7)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _pv():
        p = _img((2, 8), 0, 65, seed=46)
        v = _img((8, 4), seed=47)
        want = ref.attention_pv_ref(p, v)
        with api.use_backend("pimsab"):
            got = api.attention_pv(p, v)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _gemv():
        w = _img((8, 16), -50, 50, seed=50)
        x = _img((16,), -50, 50, seed=51)
        want = ref.decode_gemv_ref(w, x)
        with api.use_backend("pimsab"):
            got = api.decode_gemv(w, x)
        return bool((np.asarray(want) == np.asarray(got)).all())

    def _kvapp():
        cache = _img((8, 4), seed=54)
        new = _img((4,), seed=55)
        onehot = jnp.zeros(8, jnp.int8).at[5].set(1)
        want = ref.kv_append_ref(cache, new, onehot)
        with api.use_backend("pimsab"):
            got = api.kv_append(cache, new, onehot)
        return bool((np.asarray(want) == np.asarray(got)).all())

    return {
        "bitslice_matmul": _matmul,
        "htree_reduce": _htree,
        "rglru_scan": _rglru,
        "ewise_add": _ewise,
        "relu": _relu,
        "conv2d": _conv,
        "int_matmul": _intmm,
        "maxpool2d": _maxpool,
        "avgpool2d": _avgpool,
        "global_avgpool": _gap,
        "attention_qk": _qk,
        "softmax_fixedpoint": _softmax,
        "attention_pv": _pv,
        "decode_gemv": _gemv,
        "kv_append": _kvapp,
    }


def _validate_matmul(m, n, k, xb, wb) -> bool:
    x, w = _bitslice_args(m, n, k, xb, wb)
    with api.use_backend("xla"):
        want = api.matmul(x, w)
    with api.use_backend("interpret"):
        got = api.matmul(x, w, block=(128, 128, 128))
    return bool(jnp.allclose(want, got))


def _validate_unary(fn, oracle, x) -> bool:
    with api.use_backend("interpret"):
        got = fn(x)
    return bool(jnp.allclose(oracle(x), got))


def _validate_kv_append() -> bool:
    cache = _img((8, 16), seed=56)
    new = _img((16,), seed=57)
    onehot = jnp.zeros(8, jnp.int8).at[3].set(1)
    with api.use_backend("interpret"):
        got = api.kv_append(cache, new, onehot)
    return bool((np.asarray(ref.kv_append_ref(cache, new, onehot)) == np.asarray(got)).all())


def _validate_rglru() -> bool:
    a = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (1, 256, 512)))
    b = jax.random.normal(jax.random.key(2), (1, 256, 512))
    h0 = jax.random.normal(jax.random.key(3), (1, 512))
    with api.use_backend("interpret"):
        got = api.rglru_scan(a, b, h0)
    return bool(jnp.allclose(ref.rglru_scan_ref(a, b, h0), got, atol=1e-4))


def run(tune: Optional[api.TuneConfig] = BENCH_TUNE) -> List[Dict]:
    cases = _cases()
    sim_cases = _pimsab_cases()
    rows = []
    for name in sorted(api.registered_kernels()):
        case = cases.get(name)
        if case is None:
            raise KeyError(
                f"kernel {name!r} is registered but has no validation case — "
                "add one to benchmarks/kernels_bench.py"
            )
        row = {"kernel": name, "interpret_matches_oracle": case()}
        sim_case = sim_cases.get(name)
        if sim_case is None:
            raise KeyError(
                f"kernel {name!r} has no pimsab bench case — "
                "add one to benchmarks/kernels_bench.py"
            )
        with _tuning_ctx(tune):
            matches = sim_case()
        rep = api.last_sim_report()
        row["pimsab"] = {
            "matches_oracle": matches,
            "workload": rep.workload,
            "modeled_cycles": rep.total_cycles,
            "serialized_cycles": rep.serialized_cycles,
            "overlapped_cycles": rep.overlapped_cycles,
            "modeled_seconds": rep.modeled_seconds,
            "cycle_breakdown": {k: round(v, 4) for k, v in rep.cycle_breakdown.items()},
            "critical_path": {k: round(v, 1) for k, v in rep.critical_path.items()},
            "utilization": {k: round(v, 4) for k, v in rep.utilization.items()},
            "energy_j": rep.energy_j,
            "instrs": rep.instrs,
            "functional_instrs": rep.functional_instrs,
            "autotune": dict(rep.autotune),
        }
        rows.append(row)
    return rows


# real layer shapes (timing-only — the functional bit-serial machine cannot
# chew them, but the full-scale analytic model can): these are the shapes
# where multi-phase pipelining actually matters
def _large_shape_workloads():
    from repro.core.compiler.tensor_dsl import Loop, Ref, Workload

    gemm = Workload(
        name="matmul_256x1024x1024_i8",
        loops=(Loop("x", 256, "data"), Loop("y", 1024, "data"),
               Loop("k", 1024, "reduce")),
        out=Ref("c", ("x", "y"), prec=32),
        ins=(Ref("a", ("x", "k"), prec=9), Ref("b", ("k", "y"), prec=9)),
        op="mac",
        acc_prec=32,
    )
    ewise = Workload(
        name="ewise_add_65536_i16",
        loops=(Loop("i", 65536, "data"),),
        out=Ref("y", ("i",), prec=17),
        ins=(Ref("xa", ("i",), prec=16), Ref("xb", ("i",), prec=16)),
        op="map_add",
        acc_prec=17,
    )
    relu = Workload(
        name="relu_65536_i16",
        loops=(Loop("i", 65536, "data"),),
        out=Ref("y", ("i",), prec=16),
        ins=(Ref("xa", ("i",), prec=16),
             Ref("z", ("i",), prec=16, is_const=True, const_value=0)),
        op="relu",
        acc_prec=16,
    )
    return [gemm, ewise, relu]


def large_shapes(timelines: Optional[Dict] = None,
                 tune: Optional[api.TuneConfig] = BENCH_TUNE) -> List[Dict]:
    """Model the large shapes; when a ``timelines`` dict is passed (and
    profiling is active, see main), harvest each report's per-instruction
    scheduling intervals into it — same pass, no re-modeling."""
    from repro.kernels import pimsab_backend as pb

    rows = []
    for w in _large_shape_workloads():
        rep = pb.timing_report(w, kernel=w.name,
                               tune=tune if tune is not None else False)
        rows.append({
            "workload": w.name,
            "modeled_cycles": rep.total_cycles,
            "serialized_cycles": rep.serialized_cycles,
            "overlapped_cycles": rep.overlapped_cycles,
            "modeled_seconds": rep.modeled_seconds,
            "cycle_breakdown": {k: round(v, 4) for k, v in rep.cycle_breakdown.items()},
            "critical_path": {k: round(v, 1) for k, v in rep.critical_path.items()},
            "utilization": {k: round(v, 4) for k, v in rep.utilization.items()},
            "double_buffered": rep.mapping["double_buffered"],
            "serial_iters": rep.mapping["serial_iters"],
            "instrs": rep.instrs,
            "autotune": dict(rep.autotune),
        })
        if timelines is not None and rep.timeline:
            timelines[w.name] = {
                "modeled_cycles": rep.total_cycles,
                "overlapped_cycles": rep.overlapped_cycles,
                "utilization": {k: round(v, 4) for k, v in rep.utilization.items()},
                "timeline": [dict(t) for t in rep.timeline],
            }
    return rows


def program_mode(timelines: Optional[Dict] = None,
                 tune: Optional[api.TuneConfig] = BENCH_TUNE) -> Dict:
    """The traced `matmul → ewise_add → relu` chain on the pimsab backend:
    fused DRAM cycles vs the eager per-kernel sum, bit-exactness, and the
    compile-cache hit on the second identical compile.  ``timelines`` as in
    :func:`large_shapes` — the fused chain's schedule joins the artifact."""
    rng = np.random.default_rng(_SEED)
    # K small enough that the lane-contiguous (reduce_split=1) producer
    # layout still fits one k-chunk — the regime where residency wins; the
    # planner's cost model declines the fusion at shapes where it would not
    x = jnp.asarray(rng.integers(-100, 100, (16, 8)), jnp.int32)
    w = jnp.asarray(rng.integers(-100, 100, (8, 16)), jnp.int32)
    y = jnp.asarray(rng.integers(-100, 100, (16, 16)), jnp.int32)
    xs = api.SlicedTensor.from_int(x, 8)
    ws = api.SlicedTensor.from_int(w, 8)

    def chain(xs, ws, y):
        return api.relu(api.ewise_add(api.matmul(xs, ws), y))

    eager_reports = []
    with _tuning_ctx(tune), api.use_backend("pimsab"):
        acc = api.matmul(xs, ws)
        eager_reports.append(api.last_sim_report())
        s = api.ewise_add(acc, y)
        eager_reports.append(api.last_sim_report())
        eager = api.relu(s)
        eager_reports.append(api.last_sim_report())
    eager_dram = sum(r.cycles["dram"] for r in eager_reports)
    eager_total = sum(r.total_cycles for r in eager_reports)

    traced = api.trace(chain, name="bench_matmul_add_relu")
    before = api.compile_cache_info()
    with _tuning_ctx(tune), api.use_backend("pimsab"):
        got = traced(xs, ws, y)
        rep = api.last_sim_report()
        api.compile(traced.program_for(xs, ws, y))  # identical signature
    after = api.compile_cache_info()
    if timelines is not None and rep.timeline:
        timelines["program:" + "->".join(rep.kernels)] = {
            "modeled_cycles": rep.total_cycles,
            "overlapped_cycles": rep.overlapped_cycles,
            "utilization": {k: round(v, 4) for k, v in rep.utilization.items()},
            "timeline": [dict(t) for t in rep.timeline],
        }
    return {
        "chain": list(rep.kernels),
        "bit_exact_vs_eager": bool((np.asarray(got) == np.asarray(eager)).all()),
        "modeled_cycles": rep.total_cycles,
        "serialized_cycles": rep.serialized_cycles,
        "overlapped_cycles": rep.overlapped_cycles,
        "critical_path": {k: round(v, 1) for k, v in rep.critical_path.items()},
        "utilization": {k: round(v, 4) for k, v in rep.utilization.items()},
        "dram_cycles": rep.cycles["dram"],
        "eager_dram_cycles_sum": eager_dram,
        "eager_modeled_cycles_sum": eager_total,
        "dram_cycle_win": eager_dram - rep.cycles["dram"],
        "elided_dram_bits": rep.elided_dram_bits,
        "resident_edges": list(rep.resident_edges),
        "per_kernel_cycles": {
            p["kernel"]: p["total_cycles"] for p in rep.per_kernel
        },
        "autotune": dict(rep.autotune),
        "compile_cache": {
            "second_compile_was_hit": after.hits > before.hits,
            "misses_added": after.misses - before.misses,
        },
    }


def simwall() -> Dict:
    """Functional-simulator wall-clock throughput on a pinned workload.

    Two measurements on the same compiled GEMM stream (no DRAM content, so
    this times the compute data plane, not host I/O):

    * the tile-batched ``CramBank`` path (the default), and
    * the per-bit ``exact_bits`` reference it must stay bit-identical to —
      their ratio is the locked-in batching speedup.

    ``lane_ops_per_sec`` counts every (instruction × bitline lane × CRAM)
    the broadcast SIMD stream drives per wall-second — the honest
    "simulated machine throughput" number quoted in docs/benchmarks.md.
    Wall numbers are machine noise and are never gated numerically; the
    ``--check`` gate pins that the section exists and that a pinned
    ``int_matmul`` stays bit-exact against the numpy oracle when executed
    through the batched path end to end.
    """
    try:
        from benchmarks import workloads
    except ImportError:  # run as `python benchmarks/kernels_bench.py`
        import workloads
    from repro.core.compiler.codegen import compile_workload
    from repro.core.machine import PimsabConfig
    from repro.core.simulator import Simulator

    cfg = PimsabConfig(mesh_cols=2, mesh_rows=2, crams_per_tile=1)
    cp = compile_workload(workloads.gemm(m=1024, n=32, k=256, prec=8, acc=32), cfg)
    walls = {}
    for exact in (False, True):
        sim = Simulator(cfg, functional=True, exact_bits=exact)
        t0 = time.perf_counter()
        sim.run(cp.program)
        walls[exact] = time.perf_counter() - t0
    lanes = cfg.mesh_rows * cfg.mesh_cols * cfg.crams_per_tile * cfg.cram_cols

    # end-to-end bit-exactness through the api on the same machine config
    rng = np.random.default_rng(_SEED)
    x = jnp.asarray(rng.integers(-128, 128, (64, 256)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (256, 64)), jnp.int32)
    t0 = time.perf_counter()
    with api.use_backend("pimsab"):
        got = api.int_matmul(x, w, x_bits=8, w_bits=8)
    e2e_wall = time.perf_counter() - t0
    bit_exact = bool((np.asarray(got) == np.asarray(x) @ np.asarray(w)).all())

    return {
        "workload": "gemm_m1024_n32_k256_p8",
        "instrs": len(cp.program),
        "wall_seconds": round(walls[False], 3),
        "exact_bits_wall_seconds": round(walls[True], 3),
        "batched_speedup": round(walls[True] / walls[False], 2),
        "instrs_per_sec": int(len(cp.program) / walls[False]),
        "lane_ops_per_sec": int(len(cp.program) * lanes / walls[False]),
        "e2e": {
            "workload": "int_matmul_64x256x64_i8",
            "wall_seconds": round(e2e_wall, 3),
            "bit_exact": bit_exact,
        },
    }


SCALING_CHIPS = (1, 2, 4, 8)


def _scaling_rows(prog, workload: str) -> Dict:
    """Strong- and weak-scaling curves for one traced program, untuned (the
    plan search already compiles dozens of candidate segments; the pinned
    numbers stay deterministic without an autotune budget riding along)."""
    from repro.kernels import multichip as mc

    strong, weak = [], []
    base = None
    for chips in SCALING_CHIPS:
        rep = mc.cluster_timing_report(prog, chips=chips)
        if base is None:
            base = rep.total_cycles
        strong.append({
            "chips": chips,
            "mesh": list(rep.mesh),
            "plan": rep.plan,
            "total_cycles": rep.total_cycles,
            "serial_cycles": rep.serial_cycles,
            "serialized_cycles": rep.serialized_cycles,
            "overlapped_cycles": rep.overlapped_cycles,
            "link_bits": rep.link_bits,
            "speedup": round(base / rep.total_cycles, 3),
            "notes": sorted({n.split(":", 1)[0] for n in rep.notes}),
        })
        if chips > 1:
            wrep = mc.weak_scaling_report(prog, chips=chips)
            weak.append({
                "chips": chips,
                "total_cycles": wrep.total_cycles,
                "throughput_x": round(
                    chips * base / wrep.total_cycles, 3),
            })
    return {"workload": workload, "strong": strong, "weak": weak}


def scaling() -> Dict:
    """Multi-chip scale-out curves (docs/benchmarks.md "scaling" schema).

    The paper-shaped RESNET18 and one transformer decode layer, each planned
    on 1/2/4/8-chip clusters by the simulator-backed cost model
    (``repro.kernels.multichip``).  The ``--check`` gate pins three
    invariants on top of the 5% cycle gate: strong scaling is monotone
    (N-chip never loses to 1-chip — the replicated candidate guarantees it),
    the overlapped makespan never exceeds the serialized schedule, and on
    each workload at least one multi-chip point hides link traffic behind
    compute strictly (``total_cycles < serial_cycles``)."""
    from repro.models import resnet
    from repro.serve.pimsab_step import decode_layer_program

    cfg = resnet.RESNET18
    params = resnet.init_params(cfg, seed=0)
    x = resnet.make_input(cfg, batch=1, seed=1)
    traced = api.trace(lambda p, v: resnet.forward(cfg, p, v),
                       name="resnet18_scaling")
    rows = [
        _scaling_rows(traced.trace(params, x), "resnet18"),
        _scaling_rows(decode_layer_program(), "decode_layer"),
    ]
    return {"chips": list(SCALING_CHIPS), "workloads": rows}


def check_scaling(section: Optional[Dict], baseline: Dict,
                  tol: float = 0.05) -> List[str]:
    """The scaling-section gates (see :func:`scaling`)."""
    failures: List[str] = []
    if section is None:
        failures.append("scaling: multi-chip section missing from run")
        return failures
    base_wl = {w["workload"]: w
               for w in baseline.get("scaling", {}).get("workloads", [])}
    for wl in section["workloads"]:
        name = wl["workload"]
        strong = wl["strong"]
        one_chip = strong[0]["total_cycles"]
        if strong[0]["chips"] != 1:
            failures.append(f"scaling:{name}: strong curve must start at 1 chip")
            continue
        overlapped_somewhere = False
        for row in strong:
            label = f"scaling:{name}@{row['chips']}"
            if row["total_cycles"] > one_chip * (1 + 1e-9):
                failures.append(
                    f"{label}: strong scaling not monotone "
                    f"({row['total_cycles']} > 1-chip {one_chip})")
            if row["total_cycles"] > row["serial_cycles"] * (1 + 1e-9):
                failures.append(
                    f"{label}: overlapped makespan {row['total_cycles']} "
                    f"exceeds serialized {row['serial_cycles']}")
            if row["chips"] > 1 and row["total_cycles"] < row["serial_cycles"]:
                overlapped_somewhere = True
            old_rows = {r["chips"]: r
                        for r in base_wl.get(name, {}).get("strong", [])}
            old = old_rows.get(row["chips"], {}).get("total_cycles")
            if old and (row["total_cycles"] - old) / old > tol:
                failures.append(
                    f"{label}: modeled cycles {old} -> {row['total_cycles']} "
                    f"(+{(row['total_cycles'] - old) / old:.1%} > {tol:.0%})")
        if not overlapped_somewhere:
            failures.append(
                f"scaling:{name}: no multi-chip point overlaps communication "
                "with compute (total_cycles == serial_cycles everywhere)")
        for row in wl["weak"]:
            if abs(row["total_cycles"] - one_chip) > 1e-6 * max(one_chip, 1):
                failures.append(
                    f"scaling:{name}@{row['chips']}(weak): per-chip makespan "
                    f"{row['total_cycles']} drifted from 1-chip {one_chip}")
    return failures


def check_against_baseline(result: Dict, baseline: Dict, tol: float = 0.05) -> List[str]:
    """Correctness flags must hold and modeled cycles must not regress by
    more than ``tol`` vs the committed baseline (wall-clock fields are
    ignored — they are machine noise)."""
    failures: List[str] = []
    for row in result["kernels"]:
        if not row["interpret_matches_oracle"]:
            failures.append(f"{row['kernel']}: interpret mode no longer matches oracle")
        if not row["pimsab"]["matches_oracle"]:
            failures.append(f"{row['kernel']}: pimsab backend no longer matches oracle")
    if not result["program"]["bit_exact_vs_eager"]:
        failures.append("program: traced chain no longer bit-exact vs eager pimsab")
    if not result["program"]["compile_cache"]["second_compile_was_hit"]:
        failures.append("program: second identical compile was not a cache hit")
    sw = result.get("simwall")
    if sw is None:
        failures.append("simwall: functional-throughput section missing from run")
    elif not sw["e2e"]["bit_exact"]:
        failures.append("simwall: pinned int_matmul no longer bit-exact on the batched path")
    tiny = result["e2e"]["tiny"]
    if not tiny["bit_exact_vs_oracle"]:
        failures.append("e2e: traced ResNet no longer bit-exact vs the JAX oracle")
    if not tiny["compile_cache"]["second_compile_was_hit"]:
        failures.append("e2e: second identical network compile was not a cache hit")

    def gate(label: str, new: Optional[float], old: Optional[float]) -> None:
        if not old or new is None:
            return
        rel = (new - old) / old
        if rel > tol:
            failures.append(f"{label}: modeled cycles {old} -> {new} (+{rel:.1%} > {tol:.0%})")
        elif abs(rel) > 1e-12:
            print(f"  note: {label} modeled cycles {old} -> {new} ({rel:+.1%})")

    base_rows = {r["kernel"]: r for r in baseline.get("kernels", [])}
    for row in result["kernels"]:
        old = base_rows.get(row["kernel"], {}).get("pimsab", {}).get("modeled_cycles")
        gate(row["kernel"], row["pimsab"]["modeled_cycles"], old)
    base_large = {r["workload"]: r for r in baseline.get("large_shapes", [])}
    for row in result["large_shapes"]:
        old = base_large.get(row["workload"], {}).get("modeled_cycles")
        gate(f"large:{row['workload']}", row["modeled_cycles"], old)
    gate(
        "program:modeled",
        result["program"]["modeled_cycles"],
        baseline.get("program", {}).get("modeled_cycles"),
    )
    gate(
        "program:dram",
        result["program"]["dram_cycles"],
        baseline.get("program", {}).get("dram_cycles"),
    )
    # end-to-end network gates: total + per-layer modeled cycles, both configs
    for net in ("tiny", "resnet18"):
        new_sec = result["e2e"][net]
        old_sec = baseline.get("e2e", {}).get(net, {})
        gate(f"e2e:{net}", new_sec["modeled_cycles"], old_sec.get("modeled_cycles"))
        gate(f"e2e:{net}:dram", new_sec["dram_cycles"], old_sec.get("dram_cycles"))
        old_layers = {p["node"]: p for p in old_sec.get("per_layer", [])}
        for p in new_sec["per_layer"]:
            gate(
                f"e2e:{net}:{p['node']}",
                p["total_cycles"],
                old_layers.get(p["node"], {}).get("total_cycles"),
            )
    # serving gates: KV residency + program reuse sentinels, pinned token
    # counts, modeled cycles per batch point (benchmarks/serve_bench.py)
    try:
        from benchmarks import serve_bench
    except ImportError:
        import serve_bench
    serve = result.get("serve")
    if serve is None:
        failures.append("serve: serving section missing from run")
    else:
        failures.extend(serve_bench.check_serve(serve, baseline, tol=tol))
    # multi-chip scaling gates: 5% cycles + monotonicity + overlap sentinels
    failures.extend(check_scaling(result.get("scaling"), baseline, tol=tol))
    return failures


_SECTION_PREFIXES = {
    "large": "large_shapes", "program": "program", "e2e": "e2e",
    "serve": "serve", "simwall": "simwall", "scaling": "scaling",
}


def _failure_delta(f: str) -> Optional[float]:
    m = re.search(r"\(([-+]\d+(?:\.\d+)?)%", f)
    return float(m.group(1)) if m else None


def failure_summary(failures: List[str]) -> List[str]:
    """One line per failing section: how many rows failed, which row is
    worst, and by what percent — so a red ``--check`` names the culprit
    up front instead of burying it in the full diff dump."""
    by_section: Dict[str, List[str]] = {}
    for f in failures:
        sec = _SECTION_PREFIXES.get(f.split(":", 1)[0], "kernels")
        by_section.setdefault(sec, []).append(f)
    lines = []
    for sec in sorted(by_section):
        fs = by_section[sec]
        worst = max(fs, key=lambda f: _failure_delta(f) or float("-inf"))
        row = worst.split(": ", 1)[0]
        d = _failure_delta(worst)
        delta = f"{d:+.1f}%" if d is not None else "correctness"
        lines.append(
            f"{sec}: {len(fs)} failing row(s); worst {row} ({delta})"
        )
    return lines


def autotune_rows(result: Dict) -> List[Dict]:
    """Flatten every pinned modeled row into the ``BENCH_autotune.json``
    shape: section, row name, tuned modeled cycles, candidate counts
    (``scored`` / ``verifier_rejected``) and the full search provenance."""
    rows: List[Dict] = []

    def add(section: str, name: str, cycles, prov) -> None:
        prov = prov or {}
        rows.append({
            "section": section,
            "row": name,
            "modeled_cycles": cycles,
            "candidates_scored": prov.get("scored", 0),
            "verifier_rejected": prov.get("verifier_rejected", 0),
            "improvement_pct": prov.get("improvement_pct", 0.0),
            "provenance": dict(prov),
        })

    for r in result["kernels"]:
        add("kernels", r["kernel"], r["pimsab"]["modeled_cycles"],
            r["pimsab"].get("autotune"))
    for r in result["large_shapes"]:
        add("large_shapes", r["workload"], r["modeled_cycles"],
            r.get("autotune"))
    prog = result["program"]
    add("program", "->".join(prog["chain"]), prog["modeled_cycles"],
        prog.get("autotune"))
    for net, sec in result["e2e"].items():
        add("e2e", net, sec["modeled_cycles"], sec.get("autotune"))
    for r in result["serve"]["batches"]:
        add("serve", f"batch{r['batch']}", r["total_cycles"],
            r.get("autotune"))
    return rows


def check_autotune(result: Dict, baseline: Dict) -> List[str]:
    """The ``--autotune --check`` gate: tuned modeled cycles must never
    exceed the pinned baselines — ``<=`` per row (tiny float slack), not the
    5% regression band the plain gate allows."""
    failures: List[str] = []

    def gate(label: str, new, old) -> None:
        if not old or new is None:
            return
        if new > old * (1 + 1e-9):
            rel = (new - old) / old
            failures.append(
                f"{label}: tuned modeled cycles {old} -> {new} "
                f"(+{rel:.2%} — autotune must never regress the baseline)"
            )

    base_rows = {r["kernel"]: r for r in baseline.get("kernels", [])}
    for row in result["kernels"]:
        gate(row["kernel"], row["pimsab"]["modeled_cycles"],
             base_rows.get(row["kernel"], {}).get("pimsab", {}).get("modeled_cycles"))
    base_large = {r["workload"]: r for r in baseline.get("large_shapes", [])}
    for row in result["large_shapes"]:
        gate(f"large:{row['workload']}", row["modeled_cycles"],
             base_large.get(row["workload"], {}).get("modeled_cycles"))
    gate("program:modeled", result["program"]["modeled_cycles"],
         baseline.get("program", {}).get("modeled_cycles"))
    for net in ("tiny", "resnet18"):
        gate(f"e2e:{net}", result["e2e"][net]["modeled_cycles"],
             baseline.get("e2e", {}).get(net, {}).get("modeled_cycles"))
    base_serve = {r["batch"]: r for r in
                  baseline.get("serve", {}).get("batches", [])}
    for row in result["serve"]["batches"]:
        gate(f"serve:batch{row['batch']}", row["total_cycles"],
             base_serve.get(row["batch"], {}).get("total_cycles"))
    return failures


def main(check: bool = False, profile: bool = False,
         autotune: bool = False) -> Dict:
    # per-phase timeline artifact: collected from the SAME modeling pass the
    # bench rows come from (no double compile) — the large shapes plus the
    # fused program chain
    try:
        from benchmarks import e2e_resnet, serve_bench
    except ImportError:  # run as `python benchmarks/kernels_bench.py`
        import e2e_resnet
        import serve_bench

    timelines: Optional[Dict] = {} if profile else None
    profile_ctx = api.profile_timelines() if profile else contextlib.nullcontext()
    with profile_ctx:
        result = {
            "kernels": run(),
            "large_shapes": large_shapes(timelines),
            "program": program_mode(timelines),
            "e2e": e2e_resnet.collect(),
            "simwall": simwall(),
            "serve": serve_bench.collect(),
            "scaling": scaling(),
        }
    if check:
        if not OUT_PATH.exists():
            raise SystemExit(f"--check: no committed baseline at {OUT_PATH}")
        baseline = json.loads(OUT_PATH.read_text())
        failures = check_against_baseline(result, baseline)
        if autotune:
            failures.extend(check_autotune(result, baseline))
        if failures:
            print("kernels_bench --check: FAIL (modeled-cycle regression >5%)")
            for line in failure_summary(failures):
                print(" !", line)
            for f in failures:
                print(" -", f)
            raise SystemExit(1)
        print("kernels_bench --check: OK (modeled cycles within 5% of baseline)")
    if autotune:
        artifact = {
            "tune": {
                "kernels": BENCH_TUNE.to_json(),
                "e2e": e2e_resnet.DEFAULT_TUNE.to_json(),
                "serve": serve_bench.DEFAULT_TUNE.to_json(),
            },
            "tune_cache": {
                "hits": api.tune_cache_info().hits,
                "misses": api.tune_cache_info().misses,
            },
            "rows": autotune_rows(result),
        }
        AUTOTUNE_PATH.write_text(json.dumps(artifact, indent=2) + "\n")
        print(f"wrote {AUTOTUNE_PATH}")
    OUT_PATH.write_text(json.dumps(result, indent=2) + "\n")
    if profile:
        TIMELINE_PATH.write_text(json.dumps(timelines, indent=2) + "\n")
        print(f"wrote {TIMELINE_PATH}")
    for r in result["kernels"]:
        print(r)
    for r in result["large_shapes"]:
        print(r)
    print("program:", result["program"])
    for net, sec in result["e2e"].items():
        print(f"e2e:{net}:", {k: v for k, v in sec.items()
                              if k not in ("per_layer", "kernels")})
    print("simwall:", result["simwall"])
    for row in result["serve"]["batches"]:
        print("serve:", row)
    for wl in result["scaling"]["workloads"]:
        for row in wl["strong"]:
            print(f"scaling:{wl['workload']}:", row)
    print(f"wrote {OUT_PATH}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--check", action="store_true",
        help="diff modeled cycles against the committed BENCH_kernels.json "
        "baseline and exit 1 on a >5%% regression before overwriting it",
    )
    ap.add_argument(
        "--profile", action="store_true",
        help="also write BENCH_kernels_timeline.json: per-instruction "
        "scheduling intervals (the per-phase timeline artifact CI uploads)",
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="also write BENCH_autotune.json (per-row candidate counts and "
        "search provenance); with --check, additionally assert tuned "
        "modeled cycles never exceed the pinned baselines",
    )
    args = ap.parse_args()
    main(check=args.check, profile=args.profile, autotune=args.autotune)
