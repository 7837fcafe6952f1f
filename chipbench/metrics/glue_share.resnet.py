"""Share of the device's busy time outside Pallas kernels (%): the XLA
glue the Program replay puts around them (im2col, int8 slicing, padding,
layout copies)."""


def read(r):
    busy = r.trace.busy_s()
    if busy <= 0:
        return None
    return 100.0 * (busy - r.trace.busy_s(pallas=True)) / busy
