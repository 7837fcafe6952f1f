"""The registry kernels' roofline share of the forward's device time (%).

For each of the forward's registry kernel calls, the least time the chip
needs for the model's work at the call's shapes (the larger of operations
over the int8 peak and bytes over HBM bandwidth); their sum over the
forward's device busy time in the traced window, Pallas kernels and XLA glue
alike.  The same work reads the same share whatever implements it."""
from chipbench.work import least_time


def read(r):
    rec, cell = r.record, r.cell
    busy = r.trace.busy_s()
    if not rec.calls or busy <= 0:
        return None
    per_forward = sum(least_time(w, r.peaks)
                      for w in cell.model.kernel_calls(cell.cfg, cell.mix["batch"]))
    return 100.0 * per_forward * rec.calls / busy
