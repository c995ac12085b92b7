"""End-to-end training driver: ~100M-param model, few hundred steps, CPU.

Exercises the full substrate: HR-routed data pipeline (curriculum queries
scheduled to the cheapest replica), AdamW + cosine schedule, async
checkpointing with HR-layout replica manifests, and an injected node
failure at step 120 (data replica rebuilt through HR Recovery; model
state restarted from the last checkpoint). Run:

    PYTHONPATH=src python examples/train_tiny.py [--steps 300]
"""

import argparse
import dataclasses

from repro.ft.failures import FailurePlan
from repro.launch.train import TrainLoopConfig, run_training
from repro.models.config import ArchConfig
from repro.training.optimizer import OptConfig


def tiny_100m() -> ArchConfig:
    """~100M params: 12L × 768 (GPT-2-small-class, llama-style blocks)."""
    return ArchConfig(
        name="tiny-100m",
        family="dense",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=4,
        d_ff=2048,
        vocab_size=32_000,
        attention="gqa",
        act="silu",
        gated_mlp=True,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="artifacts/train_tiny_ckpt")
    ap.add_argument("--fail-at", type=int, default=120)
    args = ap.parse_args()

    cfg = tiny_100m()
    print(f"model: {cfg.name} {cfg.param_count()/1e6:.0f}M params")
    loop = TrainLoopConfig(
        steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
        log_every=20,
        opt=OptConfig(lr=3e-4, warmup_steps=30, total_steps=args.steps),
        failure_plan=FailurePlan(fail_at_steps=(args.fail_at,), nodes=(0,))
        if args.fail_at
        else FailurePlan(),
    )
    summary = run_training(cfg, loop)
    print(f"\nfinal loss {summary['final_loss']:.4f} "
          f"(start {summary['losses'][0]:.4f})")
    print(f"data replica layouts: {summary['data_layouts']}")
    print(f"avg rows scanned per curriculum query: {summary['avg_rows_scanned']:.0f}")
    print(f"recoveries survived: {len(summary['recoveries'])}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
