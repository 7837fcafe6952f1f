"""Share of the held experts' decode slots that served a request (%): of
the held experts of every expert layer in every decode step of the traced
window, those that the token of at least one lane whose request still
wanted a token chose, as ``ServeEngine.counters`` counts them on the device
(``expert_slots_used`` over ``expert_slots``; a retired lane's pad token
counts for nothing).  Each is a held expert's weight read that a request
needed.

The counters run from the engine's construction; the set-up's warm-up
serves one batch per prompt length of the mix, so those first runs of
``expert_slots_by_run`` are taken off.  An engine without the counters
reads nothing."""
from chipbench import traffic


def window_slots(r):
    """(slots, slots used) of the traced window's runs, or None."""
    runs = getattr(getattr(r.cell.engine, "counters", None), "expert_slots_by_run", None)
    window = (runs or [])[len(traffic.prompt_lengths(r.cell.mix)):]
    slots = sum(s for s, _ in window)
    return (slots, sum(u for _, u in window)) if slots > 0 else None


def read(r):
    w = window_slots(r)
    return 100.0 * w[1] / w[0] if w else None
