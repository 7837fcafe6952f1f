"""Share of the traced window in which no operation ran on the device (%),
read as ``idle_share.serve`` reads it."""
from chipbench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "idle_share.serve.py").read
