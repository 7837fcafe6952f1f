"""The forwards' share of the chip's peak over the traced window (%): the
model's operations of every forward in the window (2 x multiply-adds at the
model's shapes, at the int8 peak) over the window's length."""
from chipbench.work import total


def read(r):
    rec, cell = r.record, r.cell
    if not rec.calls:
        return None
    w = total([cell.model.forward_work(cell.cfg, cell.mix["batch"])] * rec.calls)
    return 100.0 * w.int8_ops / r.peaks["int8_ops"] / r.trace.window_s
