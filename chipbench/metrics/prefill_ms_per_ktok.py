"""Device time of prefill per thousand prompt tokens (ms): the device busy
time from each prefill call to the next step call, over the prompt tokens
those prefills took."""


def read(r):
    busy = tokens = 0
    for s, b in r.cell.step_device_time(r.trace):
        if s.kind == "prefill":
            busy += b
            tokens += s.batch * s.pos
    return 1e6 * busy / tokens if tokens and busy > 0 else None
