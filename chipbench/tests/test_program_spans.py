"""The readings next to the program's own spans and counters.

The program marks its phases with host spans named ``serve.*`` and
``program.*`` and counts decode lane-steps in ``ServeEngine.counters``.
The existing readers read ``bench.*`` spans and device ops by name, so the
program's spans must leave every one of them as it was; ``lane_use.serve``
reads the counters.
"""
import json
from types import SimpleNamespace

import pytest

from chipbench import harness, traffic
from chipbench import trace as tr
from chipbench.tests import tiny
from chipbench.tests.test_trace import DATA

HOST = "/host:CPU"
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def with_program_spans(rows):
    """``rows`` with a ``program.call`` span at the start of each
    ``bench.dispatch`` or ``bench.forward``, a ``serve.run`` over the window and
    ``serve.*`` spans in each idle gap, as the program's spans would lie."""
    out = list(rows)
    for _, _, name, s, d in rows:
        if name in ("bench.forward", "bench.dispatch"):
            out.append((HOST, "python", "program.call", s + 10.0, min(d, 600e3)))
    t = tr.Trace(rows)
    out.append((HOST, "python", "serve.run", t.lo + 1.0, t.hi - t.lo - 2.0))
    for i, (s, e) in enumerate(tr.gaps(t._busy.iv, t.lo, t.hi)):
        out.append((HOST, "python", ("serve.retire", "serve.sample")[i % 2], s, e - s))
    return out


def resnet_reading(rows):
    cfg = json.loads((harness.ROOT / "chipbench/configs/resnet18.json").read_text())
    cell = SimpleNamespace(
        cfg=cfg, mix=traffic.load_mix("b1"),
        model=harness.load_module(harness.BENCH / "configs" / "resnet18.py"),
        record=SimpleNamespace(calls=2))
    return harness.Reading(tr.Trace(rows), cell, harness.chip_peaks("TPU v5 lite"))


RECORDED_READERS = [m["name"] for m in BENCH["per_layer"]
                    if m["name"].endswith(".resnet") or m["name"] in ("resnet_kernels_roofline",
                                                                       "idle_share.serve")]


@pytest.mark.parametrize("name", RECORDED_READERS)
def test_program_spans_leave_the_recorded_readings_as_they_were(name):
    rows = tr.rows_from_json(str(DATA))
    plain, spanned = resnet_reading(rows), resnet_reading(with_program_spans(rows))
    assert len(spanned.trace.spans) == len(plain.trace.spans)
    value = reader(name)(plain)
    assert value is not None and value > 0
    assert reader(name)(spanned) == value


def test_program_spans_leave_the_breakdown_as_it_was():
    rows = tr.rows_from_json(str(DATA))
    plain, spanned = tr.Trace(rows), tr.Trace(with_program_spans(rows))
    assert spanned.top_ops(10) == plain.top_ops(10)
    assert spanned.idle_by_span(10) == plain.idle_by_span(10)
    assert (spanned.busy_s(), spanned.window_s) == (plain.busy_s(), plain.window_s)


def serve_reading(counters, mix):
    engine = SimpleNamespace(counters=counters) if counters else SimpleNamespace()
    return SimpleNamespace(cell=SimpleNamespace(engine=engine, mix=mix))


def test_lane_use_takes_the_warm_up_off_the_counters():
    mix = traffic.load_mix("decode")
    assert len(traffic.prompt_lengths(mix)) == 1 and mix["batch"] == 64
    # warm-up: one decode step of 64 useful lanes; then one batch of 511
    # steps serving 10,675 lane-steps
    counters = SimpleNamespace(decode_steps=1 + 511, lane_steps=64 + 64 * 511,
                               useful_lane_steps=64 + 10675)
    assert reader("lane_use.serve")(serve_reading(counters, mix)) == pytest.approx(
        100 * 10675 / (64 * 511))
    # nothing decoded in the window, or no counters at all: no reading
    warm_only = SimpleNamespace(decode_steps=1, lane_steps=64, useful_lane_steps=64)
    assert reader("lane_use.serve")(serve_reading(warm_only, mix)) is None
    assert reader("lane_use.serve")(serve_reading(None, mix)) is None


def test_traced_decode_run_reads_lane_use_from_the_engine():
    served = []

    def patch(cell):
        release = cell.release

        def keep_then_release():   # the release drops the window's batches
            served.extend(cell.record.batches)
            release()

        cell.release = keep_then_release

    out = tiny.run("qwen2-0.5b.decode", seed=2**33 + 7, trace=True, patch=patch)
    assert out["correct"] and served
    steps = [max(len(r.generated) for r in b.requests) - 1 for b in served]
    useful = sum(len(r.generated) - 1 for b in served for r in b.requests)
    lanes = sum(len(b.requests) * n for b, n in zip(served, steps))
    assert 0 < useful < lanes
    assert out["metrics"]["lane_use.serve"]["value"] == pytest.approx(100 * useful / lanes)
