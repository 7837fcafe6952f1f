"""Decode steps' roofline share of their device time (%), as
``decode_step_roofline`` reads it, but with the held experts' weights read
only for the experts a live lane's token chose: the engine's counter gives
the held experts used per expert layer and step over the window (see
``held_expert_use.kimi``); without it, the configuration's expectation for
the batch.  Each step's least time is the larger of its bytes (the weights
so counted, the latent cache up to its position, one row written, the
logits) over HBM bandwidth and its operations at the peaks."""
from chipbench.harness import BENCH, load_module
from chipbench.work import least_time

window_slots = load_module(BENCH / "metrics" / "held_expert_use.kimi.py").window_slots


def read(r):
    w = window_slots(r)
    used = r.cell.cfg["n_routed_experts"] * w[1] / w[0] if w else None
    least = busy = 0.0
    for s, b in r.cell.step_device_time(r.trace):
        if s.kind == "decode":
            work = r.cell.model.decode_work(r.cell.cfg, s.batch, s.pos, experts_used=used)
            least += least_time(work, r.peaks)
            busy += b
    return 100.0 * least / busy if busy > 0 else None
