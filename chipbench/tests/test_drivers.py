"""Both drivers end to end on the CPU at tiny sizes, kernels in interpret
mode, through the same ``run_cell`` the command runs (which itself refuses
a device that is not a TPU)."""
import json
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests import tiny

CELLS = [w["name"] for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_run_is_correct_and_reports_its_end_to_end_metrics(workload):
    out = tiny.run(workload, seed=2**40 + 11)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.CellSpec(workload).metrics("end_to_end")}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_the_host_side_metrics(workload):
    out = tiny.run(workload, seed=5, trace=True)
    assert out["correct"]
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out
    # no device plane on the CPU: the readers that need device time read nothing
    assert "resnet_kernels_roofline" not in out["metrics"]
    if workload.startswith("qwen"):
        assert out["metrics"]["decode_gap_p95_ms"]["value"] > 0


def test_command_refuses_a_host_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", "resnet18.b64",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_every_declared_metric_has_its_reader():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert hasattr(harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py"), "read")
    for w in bench["workloads"]:
        spec = harness.CellSpec(w["name"], bench)
        assert spec.metrics("end_to_end") and spec.metrics("per_layer")
