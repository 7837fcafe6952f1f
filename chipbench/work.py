"""The work a model requires, counted from its shapes, and the least time
the chip could do it in.

Every count is of the model's own work at the model's shapes: the
multiply-adds its layers define and the bytes its tensors hold at the
precision the configuration states, read and written once.  Padding, bit
slices, patch matrices and other work an implementation adds is never
counted, so a faster implementation of the same work reads a higher share,
and no share can pass 100%.
"""
from __future__ import annotations

from typing import Dict, Iterable, NamedTuple


class Work(NamedTuple):
    """One call's work: operations on the int8 path (integer matmuls), on
    the bf16 path (floating-point matmuls and attention), and HBM bytes."""

    name: str
    int8_ops: float
    bf16_ops: float
    bytes: float


def least_time(work: Work, peaks: Dict[str, float]) -> float:
    """Seconds the chip needs at least: the compute bound (each path at its
    own peak) or the HBM bound, whichever is larger."""
    compute = work.int8_ops / peaks["int8_ops"] + work.bf16_ops / peaks["bf16_flops"]
    return max(compute, work.bytes / peaks["hbm_bytes_per_s"])


def total(works: Iterable[Work], name: str = "total") -> Work:
    ws = list(works)
    return Work(name, sum(w.int8_ops for w in ws), sum(w.bf16_ops for w in ws),
                sum(w.bytes for w in ws))
