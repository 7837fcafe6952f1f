"""The served tokens' share of the chip's peak over the traced window (%),
read as ``mfu.serve`` reads it, from the configuration's own work
functions: MLA at its shapes (expanded in prefill, absorbed in decode) and
each token's share of the held experts at top-8 over 384."""
from chipbench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "mfu.serve.py").read
