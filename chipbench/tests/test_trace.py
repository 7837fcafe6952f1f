"""The reduction from trace rows to busy time, idle gaps and the Pallas /
glue split, on hand-made rows and on a small recorded trace.

``data/resnet18_b1_two_forwards.json`` holds the device rows (op text cut to
100 characters) and the ``bench.forward`` spans of two single-image ResNet-18
forwards, as traced on one TPU v5 lite chip, with a ``bench.window`` span
added around them; times shifted to start near zero.
"""
from pathlib import Path

import pytest

from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "resnet18_b1_two_forwards.json"
TPU = "/device:TPU:0"
MARK = tr.PALLAS_MARK


def row(line, name, start, dur, plane=TPU):
    return (plane, line, name, float(start), float(dur))


def brute_union(intervals, lo, hi):
    """Covered length by unit steps (integer nanoseconds)."""
    return sum(1 for t in range(int(lo), int(hi))
               if any(s <= t < e for s, e in intervals))


def test_coverage_matches_brute_force():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25), (40, 41), (50, 50)]
    cov = tr.Coverage(iv)
    for lo, hi in [(0, 60), (7, 25), (16, 19), (12, 21), (25, 40), (29, 45), (60, 70)]:
        assert cov.covered(lo, hi) == brute_union(iv, lo, hi), (lo, hi)


def test_gaps_cover_the_rest_of_the_window():
    iv = [(0, 10), (5, 15), (20, 30)]
    g = tr.gaps(iv, -5, 35)
    assert g == [(-5, 0), (15, 20), (30, 35)]
    assert sum(e - s for s, e in g) + tr.Coverage(iv).covered(-5, 35) == 40


def test_nested_ops_count_once_and_split_pallas_from_glue():
    rows = [
        row("XLA Ops", "%while.1 = s32[8] while(...)", 100, 100),          # wraps the next two
        row("XLA Ops", "%fusion.2 = s32[8] fusion(...)", 110, 30),
        row("XLA Ops", f"%replay.3 = s32[8] custom-call(...), {MARK}", 150, 40),
        row("XLA Ops", f"%replay.4 = s32[8] custom-call(...), {MARK}", 300, 50),
        row("python", "bench.window", 0, 400, plane="/host:CPU"),
        row("python", "bench.dispatch", 200, 90, plane="/host:CPU"),
    ]
    t = tr.Trace(rows)
    assert t.window_s == pytest.approx(400e-9)
    assert t.busy_s() == pytest.approx(150e-9)              # 100..200 and 300..350
    assert t.busy_s(pallas=True) == pytest.approx(90e-9)
    assert t.idle_share() == pytest.approx(1 - 150 / 400)
    # gaps: 0..100 and 350..400 outside every span, 200..300 in the dispatch
    assert dict(t.idle_by_span()) == pytest.approx(
        {"bench.window": 150e-9, "bench.dispatch": 100e-9})
    top = dict(t.top_ops())
    assert top["tpu_custom_call replay.4 s32[8]"] == pytest.approx(50e-9)
    assert top["while while.1 s32[8]"] == pytest.approx(100e-9)


def test_recorded_trace():
    t = tr.Trace(tr.rows_from_json(str(DATA)))
    modules = [m for m in t.modules if m.name.startswith("jit_replay")]
    assert len(modules) == 2 and len(t.spans_named("bench.forward")) == 2
    ops = [(o.start, o.end) for o in t.ops]
    # the union, against a plain sort-and-sweep over the same rows
    swept, end = 0.0, float("-inf")
    for s, e in sorted(ops):
        s, e = max(s, t.lo), min(e, t.hi)
        if e > max(s, end):
            swept += e - max(s, end)
            end = e
    assert t.busy_s() == pytest.approx(swept * 1e-9, rel=1e-12)
    # every op of a forward lies inside its executable's run, which it fills
    for m in modules:
        inside = tr.Coverage(ops).covered(m.start, m.end)
        assert 0.9 * m.duration <= inside <= m.duration + 1
    pallas = t.busy_s(pallas=True)
    assert 0 < pallas < t.busy_s()
    n_pallas = sum(1 for o in t.ops if MARK in o.name)
    assert n_pallas == 2 * 48      # all 48 registry kernels of a forward are Pallas calls
    # idle time is the window less the busy time, all of it attributed
    idle = sum(s for _, s in t.idle_by_span(k=100))
    assert idle == pytest.approx(t.window_s - t.busy_s(), rel=1e-9)
