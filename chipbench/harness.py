"""One run of one benchmark cell: set-up, the measured window, the check.

Everything specific to a cell is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``, with the
program side in ``configs/<config>.py`` and the plain reference in
``configs/<config>.reference.py``) and a traffic mix (``traffic/<mix>.json``,
whose ``driver`` key names ``drivers/<driver>.py``).  Each per-layer metric
is read by ``metrics/<metric>.py``.  Nothing here names a cell.

A driver module defines ``Cell(config_name, cfg, mix, seed, backend)``, whose
constructor is the set-up (weights and inputs from the seed, every shape the
window uses warmed), with ``window(seconds, traced)``, ``end_to_end()``,
``release()`` and ``check(control_bits)``; see ``drivers/images.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, a malformed cell, a compile
    inside the window)."""


def load_module(path: Path) -> ModuleType:
    """Import a benchmark file by path (its name may hold dots and dashes)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "chipbench_" + "".join(c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _one(items: List[Dict[str, Any]], what: str) -> Dict[str, Any]:
    if len(items) != 1:
        raise BenchError(f"{what}: {len(items)} entries in BENCHMARK.json")
    return items[0]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CellSpec:
    """A cell as ``BENCHMARK.json`` and its data files describe it."""

    def __init__(self, workload: str, bench: Optional[Dict[str, Any]] = None):
        from chipbench import traffic

        self.bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
        self.cell = _one([w for w in self.bench["workloads"] if w["name"] == workload],
                         f"workload {workload!r}")
        self.config = _one([c for c in self.bench["configs"]
                            if c["name"] == self.cell["config"]], f"config {self.cell['config']!r}")
        self.cfg = json.loads((ROOT / self.config["file"]).read_text())
        self.mix = traffic.load_mix(self.cell["traffic"])
        self.driver = load_module(BENCH / "drivers" / f"{self.mix['driver']}.py")

    def metrics(self, kind: str) -> List[Dict[str, Any]]:
        return [m for m in self.bench[kind] if applies(m, self.cell["name"])]


def tpu_device(chips: int):
    """The first TPU device, when JAX sees at least ``chips`` of them; the
    host CPU is kept beside it for the references."""
    import jax

    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        raise BenchError(f"no TPU: {e}") from None
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees {len(devices)}")
    return devices[0]


def chip_peaks(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts XLA backend compilations while ``active``."""

    def __init__(self):
        import jax

        self.active, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active and event == COMPILE_EVENT:
            self.n += 1

    @contextmanager
    def counting(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def setup_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), holding
    every program, however fast it compiled, so that only a checkout's
    first run compiles."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache

    path = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Reading:
    """What a per-layer metric reader sees: the reduced trace, the driver's
    cell and its record of the traced window, and the chip's peaks."""

    def __init__(self, trace, cell, peaks: Dict[str, float]):
        self.trace, self.cell, self.peaks = trace, cell, peaks
        self.record = cell.record


def traced_window(cell, seconds: float):
    """The window under the profiler (host Python tracing off); returns the
    reduced trace."""
    import jax
    from chipbench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            cell.window(seconds, traced=True)
        finally:
            jax.profiler.stop_trace()
        return tr.Trace(tr.rows_from_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def verdict(checks: Dict[str, Dict[str, Any]]) -> bool:
    """``correct``: every number compared within its limit."""
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, backend: str = "pallas",
             spec: Optional[CellSpec] = None, patch=None,
             control_bits: int = 0) -> Dict[str, Any]:
    """One run; returns the result object (``checks`` last).  ``require_tpu``
    off, a ``spec`` with small sizes and a CPU ``backend`` let the tests
    drive the same path without the chip; ``patch(cell)`` lets them break
    the timed path underneath, and ``control_bits`` judges the control in
    the program's place."""
    import jax

    spec = spec or CellSpec(workload)
    if require_tpu:
        device = tpu_device(spec.cell["chips"])
        peaks = chip_peaks(device.device_kind)
        setup_cache()
    else:
        device, peaks = jax.devices()[0], chip_peaks("TPU v5 lite")
    compiles = CompileCounter()
    cell = spec.driver.Cell(spec.cell["config"], spec.cfg, spec.mix, seed, backend=backend)
    if patch is not None:
        patch(cell)
    setup_s = time.perf_counter() - t_start
    with compiles.counting():
        if trace:
            tr = traced_window(cell, min(seconds, spec.mix["trace_seconds"]))
        else:
            cell.window(seconds, traced=False)
    if compiles.n:
        raise BenchError(f"{compiles.n} compilation(s) inside the measured window")
    stats = device.memory_stats() or {}
    dev_info = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices(device.platform)),
                "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    metrics: Dict[str, Dict[str, Any]] = {}
    result: Dict[str, Any] = {}
    if trace:
        reading = Reading(tr, cell, peaks)
        for m in spec.metrics("per_layer"):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        dev_info.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_by_span(10)}
    else:
        values = dict(cell.end_to_end(), setup_s=setup_s)
        for m in spec.metrics("end_to_end"):
            if m["name"] not in values:
                raise BenchError(f"driver {spec.mix['driver']!r} gives no {m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    attempted, failed = cell.attempted_failed()
    t_window = time.perf_counter()
    cell.release()
    gc.collect()
    checks = cell.check(control_bits)
    print(f"chipbench: set-up {setup_s:.2f} s, window and readings "
          f"{t_window - t_start - setup_s:.2f} s, check {time.perf_counter() - t_window:.2f} s",
          file=sys.stderr)
    return {"correct": verdict(checks), "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": dev_info, **result, "checks": checks}


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out))
    return 0
