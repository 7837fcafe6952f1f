"""From a profiler trace to what the per-layer metrics read.

A trace is reduced to rows ``(plane, line, name, start_ns, duration_ns)``:

* device operations: plane ``/device:TPU:<n>``, line ``XLA Ops``.  The line
  nests a ``while`` op around its body's ops, so every time taken from it is
  a union of intervals, never a sum;
* executable runs: the same plane, line ``XLA Modules``;
* the benchmark's own host spans: any host line, names starting ``bench.``
  (``jax.profiler.TraceAnnotation`` in the drivers).

A Pallas kernel is a device op whose HLO text names
``custom_call_target="tpu_custom_call"``; every other device op is XLA glue.
The host and device clocks of one trace agree to about a millisecond.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import json
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'

Row = Tuple[str, str, str, float, float]   # plane, line, name, start_ns, duration_ns
Interval = Tuple[float, float]


def span(name: str, traced: bool):
    """A benchmark host span in the profiler's trace (traced runs only)."""
    import jax

    return jax.profiler.TraceAnnotation(name) if traced else contextlib.nullcontext()


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float    # ns

    @property
    def duration(self) -> float:
        return self.end - self.start


def rows_from_xplane(log_dir: str) -> List[Row]:
    """The rows of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, found {files}")
    rows: List[Row] = []
    for plane in ProfileData.from_file(files[0]).planes:
        device = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                rows.extend((plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
            elif not device and plane.name.startswith("/host"):
                rows.extend((plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name.startswith(SPAN_PREFIX))
    return rows


def rows_from_json(path: str) -> List[Row]:
    return [tuple(r) for r in json.loads(open(path).read())]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same time as ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Coverage:
    """Merged intervals with prefix sums: covered time in any ``[lo, hi]``
    in O(log n)."""

    def __init__(self, intervals: Iterable[Interval]):
        self.iv = merge(intervals)
        self.starts = [s for s, _ in self.iv]
        self.cum = [0.0]
        for s, e in self.iv:
            self.cum.append(self.cum[-1] + (e - s))

    def covered(self, lo: float, hi: float) -> float:
        if hi <= lo or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        j = bisect.bisect_left(self.starts, hi)
        if j <= i:
            return 0.0
        total = self.cum[j] - self.cum[i]
        s0, e0 = self.iv[i]
        total -= min(max(lo - s0, 0.0), e0 - s0)       # cut before lo
        s1, e1 = self.iv[j - 1]
        total -= min(max(e1 - hi, 0.0), e1 - s1)       # cut after hi
        return max(total, 0.0)


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


_HLO = re.compile(r"^%(\S+) = ")
_OPCODE = re.compile(r"\s*([\w-]+)\(")


def op_label(hlo_text: str) -> str:
    """A short name for a device op from its HLO text: opcode, op name and
    result type (``tuple`` for a tuple), e.g. ``copy copy.317
    s32[64,56,56,64,3,3]``."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:80]
    rest = hlo_text[m.end():]
    if rest.startswith("("):       # a tuple type: skip to its closing parenthesis
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        typ, rest = "tuple", rest[i + 1:]
    else:
        typ, _, rest = rest.partition(" ")
        typ = typ.split("{")[0]
    op = _OPCODE.match(rest)
    opcode = "tpu_custom_call" if PALLAS_MARK in hlo_text else (op.group(1) if op else "?")
    return f"{opcode} {m.group(1)} {typ}"[:120]


class Trace:
    """One traced window, on the trace's clock (ns)."""

    def __init__(self, rows: Sequence[Row], window_span: str = "bench.window"):
        planes = sorted({r[0] for r in rows if r[0].startswith("/device:TPU")})
        self.n_devices = len(planes) or 1
        self.ops = [Event(r[2], r[3], r[3] + r[4]) for r in rows if r[1] == OPS_LINE]
        self.modules = [Event(r[2], r[3], r[3] + r[4]) for r in rows if r[1] == MODULES_LINE]
        self.spans = sorted((Event(r[2], r[3], r[3] + r[4]) for r in rows
                             if r[2].startswith(SPAN_PREFIX)), key=lambda e: e.start)
        windows = [s for s in self.spans if s.name == window_span]
        if len(windows) != 1:
            raise RuntimeError(f"expected one {window_span!r} span, found {len(windows)}")
        self.lo, self.hi = windows[0].start, windows[0].end
        self.window_name = window_span
        self._busy = Coverage((o.start, o.end) for o in self.ops)
        self._pallas = Coverage((o.start, o.end) for o in self.ops if PALLAS_MARK in o.name)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, lo: Optional[float] = None, hi: Optional[float] = None,
               pallas: bool = False) -> float:
        """Device busy seconds in ``[lo, hi]`` (default: the window),
        averaged over the devices; with ``pallas`` only inside Pallas
        kernels."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        cov = self._pallas if pallas else self._busy
        return cov.covered(lo, hi) * 1e-9 / self.n_devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device ops (by label) that took the most time in the
        window, with their summed durations in seconds."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            d = min(o.end, self.hi) - max(o.start, self.lo)
            if d > 0:
                lab = op_label(o.name)
                tot[lab] = tot.get(lab, 0.0) + d
        return [[n, t * 1e-9] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_by_span(self, k: int = 10) -> List[List]:
        """Idle device time in the window, summed by the innermost benchmark
        span the host was in at each gap's midpoint (the window's own name
        where it was in no other), largest first."""
        inner = [s for s in self.spans if s.name != self.window_name]
        starts = [s.start for s in inner]
        longest = max((s.duration for s in inner), default=0.0)
        tot: Dict[str, float] = {}
        for s, e in gaps(self._busy.iv, self.lo, self.hi):
            mid = 0.5 * (s + e)
            j = bisect.bisect_right(starts, mid)
            i = bisect.bisect_left(starts, mid - longest)
            holders = [sp for sp in inner[i:j] if mid < sp.end]
            name = min(holders, key=lambda sp: sp.duration).name if holders else self.window_name
            tot[name] = tot.get(name, 0.0) + (e - s)
        return [[n, t * 1e-9] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def spans_named(self, *names: str) -> List[Event]:
        """Spans with one of ``names`` that start inside the window."""
        return [s for s in self.spans if s.name in names and self.lo <= s.start < self.hi]

    def busy_after(self, marks: Sequence[Event]) -> List[float]:
        """For each span of ``marks`` (in time order), the device busy
        seconds from its start to the next one's (the last: to the window's
        end): the device work that call set off, where the host waits for
        each call's result before it makes the next."""
        ends = [m.start for m in marks[1:]] + [self.hi]
        return [self.busy_s(m.start, e) for m, e in zip(marks, ends)]
