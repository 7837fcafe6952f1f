"""Readings that a cell's ``correct`` limits are set from, many seeds in one
process (the benchmark's own runs do not run this):

    python chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...
    python chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 --fault <name>

For each seed: the cell's set-up and a short window at its own load, then
the cell's own check twice: of what the program served (the sound reading),
and of the control in the program's place, the plain reference one
precision below the configuration's (int4 for its int8).  Both go through
the check's comparison with its limits, as a run's result does.  With
``--fault`` the program runs with that fault of ``chipbench/faults.py``
planted under its timed path, and only its reading is taken.  One line per
seed.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402

CONTROL_BITS = 4


def reading(checks):
    return {"values": {k: v["value"] for k, v in checks.items()},
            "correct": harness.verdict(checks)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    spec = harness.CellSpec(args.workload)
    patch = FAULTS[spec.mix["driver"]][args.fault] if args.fault else None
    harness.tpu_device(spec.cell["chips"])
    harness.setup_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = spec.driver.Cell(spec.cell["config"], spec.cfg, spec.mix, seed)
        if patch is not None:
            patch(cell)
        cell.window(args.seconds, traced=False)
        attempted, failed = cell.attempted_failed()
        cell.release()
        gc.collect()
        line = {"seed": seed, "attempted": attempted, "failed": failed}
        if args.fault:
            line["fault"] = dict(reading(cell.check()), name=args.fault)
        else:
            line["sound"] = reading(cell.check())
            line["control"] = reading(cell.check(CONTROL_BITS))
        line["seconds"] = round(time.perf_counter() - t0, 2)
        print(json.dumps(line), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
