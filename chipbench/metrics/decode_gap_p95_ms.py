"""95th percentile of the gaps between a request's successive tokens, as
the engine hands them over (host clock, ms), over every request of the
traced window."""
import numpy as np


def read(r):
    gaps = [b - a for batch in r.record.batches for req in batch.requests
            for a, b in zip(req.generated.stamps, req.generated.stamps[1:])]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
