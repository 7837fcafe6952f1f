"""Share of the traced window's decode lane-steps whose request still
wanted a token (%), read as ``lane_use.serve`` reads it."""
from chipbench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "lane_use.serve.py").read
