"""The served tokens' share of the chip's peak over the traced window (%).

Counts the model's operations for the work that served a request: every
prompt token of every prefill, and each decode step's work for the lanes
whose request still wanted a token (2 x multiply-adds of the int8 linears at
the int8 peak; attention at each token's position and the logits at the
bf16 peak).  A lane the lock-step engine decodes after its request is done
does no useful work and is not counted."""
from chipbench.work import total


def read(r):
    cell, rec = r.cell, r.record
    works = []
    for b in rec.batches:
        works.append(cell.model.prefill_work(cell.cfg, len(b.requests), b.prompt_len))
        for req in b.requests:
            works += [cell.model.token_work(cell.cfg, b.prompt_len + j - 1, logits=True)
                      for j in range(1, len(req.generated))]
    if not works:
        return None
    w = total(works)
    compute = w.int8_ops / r.peaks["int8_ops"] + w.bf16_ops / r.peaks["bf16_flops"]
    return 100.0 * compute / r.trace.window_s
