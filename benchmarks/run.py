"""Benchmark driver — one function per paper table/figure.

Prints ``name,wall_us,derived`` CSV rows, ``wall_us`` being the host time the
section took (plus the full per-figure detail blocks after the CSV for
auditability).
"""
from __future__ import annotations

import sys
import time
import traceback


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.3f},{derived}")


def main() -> None:
    import benchmarks.fig09_gpu as fig09
    import benchmarks.fig10_pim as fig10
    import benchmarks.fig11_breakdown as fig11
    import benchmarks.fig12_hw_sensitivity as fig12
    import benchmarks.fig13_workload_sensitivity as fig13
    import benchmarks.fig14_compiler as fig14
    import benchmarks.fig15_area as fig15
    from benchmarks import kernels_bench, roofline

    details = []
    failures = 0

    def section(name, fn, derive):
        nonlocal failures
        t0 = time.time()
        try:
            rows = fn()
            _csv(name, (time.time() - t0) * 1e6, derive(rows))
            details.append((name, rows))
        except Exception as e:  # noqa: BLE001
            failures += 1
            _csv(name, (time.time() - t0) * 1e6, f"ERROR:{type(e).__name__}")
            traceback.print_exc()

    section(
        "fig09_vs_a100", fig09.run,
        lambda rows: f"geomean_speedup={rows[-1]['speedup']:.2f}(paper3.0)_energy={rows[-1]['energy_ratio']:.2f}(paper4.2)",
    )
    section(
        "fig10_vs_pim", fig10.run,
        lambda rows: "_".join(
            f"{r['cmp']}={r['speedup']:.2f}(paper{r['paper']})" for r in rows if r.get("bench") == "geomean"
        ),
    )
    section(
        "fig11_breakdown", fig11.run,
        lambda rows: "vecadd_dram=" + str(rows[0]["time_breakdown"].get("dram", 0)),
    )
    section(
        "fig12_hw_sensitivity", fig12.run,
        lambda rows: "_".join(f"{r['config']}={r['geomean']:.3f}" for r in rows[:2]),
    )
    section(
        "fig13_workload_sensitivity", fig13.run,
        lambda rows: f"rows={len(rows)}",
    )
    section(
        "fig14_compiler_vs_hand", fig14.run,
        lambda rows: f"geomean_ratio={rows[-1]['compiled_over_hand']:.3f}(paper~1.0)",
    )
    section(
        "fig15_area", fig15.run,
        lambda rows: f"cram_frac={rows[0]['fraction']}",
    )
    section(
        "roofline_dryrun", roofline.run,
        lambda rows: f"cells={len(rows)}_ok={sum(1 for r in rows if r['status']=='ok')}",
    )
    # registry-driven kernel micro-bench (also refreshes BENCH_kernels.json,
    # the perf-trajectory baseline future PRs compare against; the "program"
    # key pins the traced-chain fused-vs-eager DRAM-cycle win)
    section(
        "kernels_api", kernels_bench.main,
        lambda res: f"kernels={len(res['kernels'])}_interpret_ok="
        f"{sum(r['interpret_matches_oracle'] for r in res['kernels'])}"
        f"_program_dram_win={res['program']['dram_cycle_win']:.0f}cyc",
    )

    print("\n=== details ===")
    for name, rows in details:
        print(f"\n--- {name} ---")
        for r in (rows["kernels"] + [rows["program"]] if isinstance(rows, dict) else rows):
            print(r)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
