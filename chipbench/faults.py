"""Faults planted under a cell's timed path, each of which the check must
catch.  A fault is ``patch(cell)``, applied once the cell is set up; it
compiles what it needs there, so the window compiles nothing.

``chipbench/tests/test_control.py`` drives a whole run with each fault at
tiny sizes on the CPU; ``chipbench/control.py --fault <name>`` reads one on
the chip at the cell's own size.
"""
from __future__ import annotations

import jax


def _warmed(fn, like):
    """``fn`` jitted and compiled now, so that the window compiles nothing."""
    f = jax.jit(fn)
    jax.block_until_ready(f(like))
    return f


def _break_forward(alter):
    def patch(cell):
        fwd = cell.forward
        bad = _warmed(alter, fwd(cell.params, cell.batches[0]))
        cell.forward = lambda p, x: bad(fwd(p, x))
    return patch


def _alter_token(cell):
    """Every lane's greedy pick altered on each third step, where the engine
    produces it."""
    eng, greedy, calls = cell.engine, cell.engine._greedy, [0]

    def altered(logits):
        t, calls[0] = greedy(logits), calls[0] + 1
        return (t + 1) % cell.cfg["vocab_size"] if calls[0] % 3 == 1 else t

    eng._greedy = altered


def _state_unchanged(cell):
    """The decode step returns the cache it was given: its key and value
    rows are never written."""
    eng, decode = cell.engine, cell.engine.decode_step
    eng.decode_step = lambda p, cache, tok: (cache, decode(p, cache, tok)[1])


# by driver: the faults that cell kind can have
FAULTS = {
    "images": {
        "answer_altered": _break_forward(lambda y: y.at[:, 0].add(1)),
        "half_batch_left_out": _break_forward(lambda y: y.at[y.shape[0] // 2:].set(0)),
    },
    "serve": {
        "token_altered": _alter_token,
        "decode_state_unchanged": _state_unchanged,
    },
}
