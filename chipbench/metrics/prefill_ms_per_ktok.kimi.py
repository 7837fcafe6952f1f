"""Device time of prefill per thousand prompt tokens (ms), read as
``prefill_ms_per_ktok`` reads it: the blocked prefill of a whole batch,
every block through every layer."""
from chipbench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "prefill_ms_per_ktok.py").read
