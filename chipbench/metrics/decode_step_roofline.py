"""Decode steps' roofline share of their device time (%).

For each decode step in the traced window, the least time the chip needs
for it (int8 weights and the bf16 embedding read once, the cache rows up to
its position read, one row written, the logits written; or its operations at
the peaks, if larger), summed, over the device busy time from each step's
call to the next call of either step."""
from chipbench.work import least_time


def read(r):
    least = busy = 0.0
    for s, b in r.cell.step_device_time(r.trace):
        if s.kind == "decode":
            least += least_time(r.cell.model.decode_work(r.cell.cfg, s.batch, s.pos), r.peaks)
            busy += b
    return 100.0 * least / busy if busy > 0 else None
