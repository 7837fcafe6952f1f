"""95th percentile of the gaps between a request's successive tokens, as
the engine hands them over (host clock, ms), read as ``decode_gap_p95_ms``
reads it."""
from chipbench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "decode_gap_p95_ms.py").read
