import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any jax import: jax locks the device
# count at first init, and the production meshes need 512 placeholder devices.

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    SHAPES,
    SHAPES_BY_NAME,
    cell_supported,
    get_config,
    list_archs,
)
from repro.dist.sharding import MeshRules  # noqa: E402
from repro.launch.hlo_analysis import parse_collectives, roofline_terms  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models.runtime import DEFAULT_FLAGS, RunFlags  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _model_flops_per_device(cfg, cell, n_devices: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode D=batch tokens."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        d = cell.tokens
        return 6.0 * n * d / n_devices
    if cell.kind == "prefill":
        d = cell.tokens
        return 2.0 * n * d / n_devices
    return 2.0 * n * cell.global_batch / n_devices  # decode: one token per seq


def _build_step_args(cfg, cell, rules, flags):
    specs = input_specs(cfg, cell, rules, flags)
    if cell.kind == "train":
        from repro.train.steps import make_train_step

        return make_train_step(cfg, flags, rules), (specs["state"], specs["batch"]), specs
    if cell.kind == "prefill":
        from repro.serve.engine import make_prefill_step

        return (
            make_prefill_step(cfg, flags, rules, max_len=cell.seq_len),
            (specs["params"], specs["batch"]),
            specs,
        )
    from repro.serve.engine import make_decode_step

    return (
        make_decode_step(cfg, flags, rules),
        (specs["params"], specs["cache"], specs["tokens"]),
        specs,
    )


def _lower_costs(cfg, cell, mesh, rules, flags):
    """(flops, hbm_bytes, CollectiveStats) for one lowering."""
    step, args, _ = _build_step_args(cfg, cell, rules, flags)
    with mesh:
        compiled = jax.jit(step).lower(*args).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        coll = parse_collectives(compiled.as_text())
    return float(cost.get("flops", 0.0)), float(cost.get("bytes accessed", 0.0)), coll


def _scan_corrected_costs(cfg, cell, mesh, rules, flags, measured):
    """XLA's cost_analysis counts a while-loop (scan) body ONCE regardless of
    trip count (verified empirically).  Recover the true per-step cost by
    lowering *unrolled* 1-group and 2-group variants:

        body   = u(2) - u(1);  outside = u(1) - body
        total  = outside + G · body

    applied to FLOPs, HBM bytes, and collective wire/operand bytes.
    """
    import dataclasses as dc

    g = cfg.pattern_groups()
    plen = len(cfg.block_pattern)
    u = []
    for k in (1, 2):
        small = dc.replace(
            cfg, n_layers=cfg.first_dense_layers + plen * k, n_enc_layers=(k if cfg.n_enc_layers else 0)
        )
        fl = dc.replace(flags, scan_layers=False)
        u.append(_lower_costs(small, cell, mesh, rules, fl))
    f1, b1, c1 = u[0]
    f2, b2, c2 = u[1]

    def corr(v1, v2, meas):
        body = max(v2 - v1, 0.0)
        outside = max(v1 - body, 0.0)
        return outside + g * body, body, outside

    flops, fbody, foutside = corr(f1, f2, measured[0])
    hbm, _, _ = corr(b1, b2, measured[1])
    wire, _, _ = corr(c1.total_wire_bytes, c2.total_wire_bytes, None)
    operand, _, _ = corr(float(c1.total_operand_bytes), float(c2.total_operand_bytes), None)
    return {
        "flops": flops,
        "hbm_bytes": hbm,
        "collective_wire_bytes": wire,
        "collective_operand_bytes": operand,
        "per_group_flops": fbody,
        "outside_flops": foutside,
        "groups": g,
    }


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    flags: RunFlags = DEFAULT_FLAGS,
    save: bool = True,
    verbose: bool = True,
    variant: str = "baseline",
    correction: bool = True,
) -> dict:
    cfg = get_config(arch)
    cell = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "flags": dataclasses.asdict(flags),
    }
    ok, why = cell_supported(cfg, cell)
    if not ok:
        record.update(status="skipped", reason=why)
        return _finish(record, save, verbose)

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        rules = MeshRules.from_mesh(mesh)
        n_dev = mesh.size
        step, args, specs = _build_step_args(cfg, cell, rules, flags)

        with mesh:
            lowered = jax.jit(step).lower(*args)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            hlo = compiled.as_text()

        from repro.launch.memory_model import analytic_memory

        coll = parse_collectives(hlo)
        flops_raw = float(cost.get("flops", 0.0))
        hbm_raw = float(cost.get("bytes accessed", 0.0))
        if correction:
            corrected = _scan_corrected_costs(cfg, cell, mesh, rules, flags, (flops_raw, hbm_raw))
        else:  # multi-pod pass proves sharding/lowering; roofline is single-pod
            corrected = {
                "flops": flops_raw,
                "hbm_bytes": hbm_raw,
                "collective_wire_bytes": coll.total_wire_bytes,
                "collective_operand_bytes": float(coll.total_operand_bytes),
                "per_group_flops": 0.0,
                "outside_flops": 0.0,
                "groups": cfg.pattern_groups(),
                "corrected": False,
            }
        mf = _model_flops_per_device(cfg, cell, n_dev)
        from repro.launch.hlo_analysis import CollectiveStats

        coll_for_terms = CollectiveStats(
            counts=coll.counts,
            operand_bytes={"total": int(corrected["collective_operand_bytes"])},
            wire_bytes={"total": corrected["collective_wire_bytes"]},
        )
        rl = roofline_terms(corrected["flops"], corrected["hbm_bytes"], coll_for_terms, mf)
        record.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            n_devices=n_dev,
            sharding_decisions=rules.decisions,
            memory={
                "argument_bytes_per_device": mem.argument_size_in_bytes,
                "output_bytes_per_device": mem.output_size_in_bytes,
                # NOTE: XLA-CPU buffer assignment does not reuse remat-region
                # buffers; this is a pessimistic bound (see memory_model.py).
                "temp_bytes_per_device_cpu_bound": mem.temp_size_in_bytes,
                "alias_bytes_per_device": mem.alias_size_in_bytes,
                "analytic": analytic_memory(cfg, cell, rules, flags, specs),
            },
            cost={
                "flops_raw_scanbody_once": flops_raw,
                "bytes_accessed_raw": hbm_raw,
                "scan_correction": corrected,
            },
            collectives=coll.to_json(),
            roofline=rl.to_json(),
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        record.update(
            status="error",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-4000:],
            compile_s=round(time.time() - t0, 1),
        )
    return _finish(record, save, verbose)


def _finish(record: dict, save: bool, verbose: bool) -> dict:
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "" if record.get("variant", "baseline") == "baseline" else f"__{record['variant']}"
        name = f"{record['arch']}__{record['shape']}__{record['mesh']}{suffix}.json"
        (RESULTS_DIR / name).write_text(json.dumps(record, indent=2))
    if verbose:
        status = record["status"]
        line = f"[{record['mesh']}] {record['arch']:22s} {record['shape']:12s} {status}"
        if status == "ok":
            rl = record["roofline"]
            mem = record["memory"]
            line += (
                f"  compile={record['compile_s']}s"
                f"  mem={mem['analytic']['analytic_peak_per_device']/2**30:.2f}GiB/dev"
                f"(cpu-bound {mem['temp_bytes_per_device_cpu_bound']/2**30:.1f})"
                f"  dom={rl['dominant']}"
                f"  (c={rl['compute_s']:.2e}s m={rl['memory_s']:.2e}s n={rl['collective_s']:.2e}s)"
            )
        elif status == "error":
            line += f"  {record['error'][:160]}"
        else:
            line += f"  {record['reason'][:80]}"
        print(line, flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description="PIMSAB-framework multi-pod dry-run")
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape cell or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--no-correction", action="store_true",
                    help="skip the scan-cost correction lowerings (faster)")
    ap.add_argument("--skip-fresh", action="store_true",
                    help="skip cells whose saved record already has corrected costs")
    # RunFlags overrides (perf hillclimb levers)
    ap.add_argument("--attn-chunk", type=int, default=DEFAULT_FLAGS.attn_chunk)
    ap.add_argument("--flash-threshold", type=int, default=DEFAULT_FLAGS.flash_threshold)
    ap.add_argument("--no-triangular", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-quant-serve", action="store_true")
    ap.add_argument("--quant-kv", action="store_true")
    ap.add_argument("--seq-shard-kv", action="store_true")
    ap.add_argument("--no-scan-layers", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--routing-groups", type=int, default=0)
    args = ap.parse_args()

    flags = RunFlags(
        attn_chunk=args.attn_chunk,
        flash_threshold=args.flash_threshold,
        triangular_attn=not args.no_triangular,
        remat=not args.no_remat,
        quant_serve=not args.no_quant_serve,
        quant_kv=args.quant_kv,
        seq_shard_kv=args.seq_shard_kv,
        scan_layers=not args.no_scan_layers,
        zero1=args.zero1,
        grad_accum=args.grad_accum,
        routing_groups=args.routing_groups,
    )
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                if args.skip_fresh:
                    mesh_name = "pod2x16x16" if mp else "pod16x16"
                    f = RESULTS_DIR / f"{arch}__{shape}__{mesh_name}.json"
                    if f.exists():
                        rec = json.loads(f.read_text())
                        if rec.get("status") in ("ok", "skipped") and (
                            rec.get("status") == "skipped"
                            or "scan_correction" in rec.get("cost", {})
                        ):
                            continue
                rec = lower_cell(
                    arch, shape, mp, flags,
                    save=not args.no_save, variant=args.variant,
                    correction=not args.no_correction,
                )
                failures += rec["status"] == "error"
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
