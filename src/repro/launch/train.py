"""Training launcher CLI.

Tiny/smoke configs run real steps on this host; full configs on the
production mesh are launched the same way on a pod (the dry-run proves the
lowering).  ``--simulate-failure`` exercises the restart path end-to-end:
train, kill mid-run, relaunch, verify bit-exact continuation.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, reduced_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.models.runtime import RunFlags
from repro.train.trainer import TrainLoopConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-size config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch)
    loop = TrainLoopConfig(
        steps=args.steps, ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir, base_lr=args.lr
    )
    out = train(cfg, data_cfg, loop, RunFlags(attn_chunk=64, flash_threshold=256), resume=not args.no_resume)
    for h in out["history"]:
        print(h)
    if out["resumed_from"] is not None:
        print(f"(resumed from step {out['resumed_from']})")


if __name__ == "__main__":
    main()
