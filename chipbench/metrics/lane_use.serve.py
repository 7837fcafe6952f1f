"""Share of the traced window's decode lane-steps whose request still
wanted a token (%), as ``ServeEngine.counters`` counts them:
``useful_lane_steps`` over ``lane_steps``.

The counters run from the engine's construction, so the set-up's warm-up
batches are taken off: one batch per prompt length of the mix, each of two
tokens a request, so one decode step in which every lane is useful.  An
engine without counters reads nothing."""
from chipbench import traffic


def read(r):
    c = getattr(r.cell.engine, "counters", None)
    if c is None:
        return None
    warm = len(traffic.prompt_lengths(r.cell.mix)) * r.cell.mix["batch"]
    lanes = c.lane_steps - warm
    return 100.0 * (c.useful_lane_steps - warm) / lanes if lanes > 0 else None
