"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 50 --batch 8 --seq 128 --policy interrupt

Runs the real Trainer (fault-tolerant loop, policy-driven data staging,
async checkpoints) on this machine's devices. --smoke selects the reduced
same-family config (the full configs need a pod; use launch.dryrun for
those). The transfer policy chooses the paper's driver mode for host->device
batch staging — the measured difference is printed at the end.
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.core.transfer import Buffering, Management, Partitioning, TransferPolicy
from repro.data.pipeline import DataConfig, StagedPipeline, SyntheticLMSource
from repro.models.api import build_model
from repro.optim import AdamWConfig
from repro.train.loop import TrainConfig, Trainer
from repro.utils.compile_cache import enable_compile_cache

POLICIES = {
    "polling": TransferPolicy.user_level_polling,
    "scheduled": TransferPolicy.user_level_scheduled,
    "interrupt": TransferPolicy.kernel_level,
    "interrupt-double-blocks": lambda: TransferPolicy(
        Management.INTERRUPT, Buffering.DOUBLE, Partitioning.BLOCKS),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", choices=sorted(POLICIES), default="interrupt")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    tcfg = TrainConfig(
        steps=args.steps, n_microbatches=args.microbatches,
        warmup=max(args.steps // 10, 1),
        opt=AdamWConfig(lr=args.lr),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    policy = POLICIES[args.policy]()
    source = SyntheticLMSource(
        DataConfig(global_batch=args.batch, seq_len=args.seq), cfg)
    pipe = StagedPipeline(source, policy)
    trainer = Trainer(model, tcfg)
    print(f"training {cfg.name} for {args.steps} steps "
          f"(policy={policy.tag}, devices={len(jax.devices())})")
    out = trainer.run(pipe)
    pipe.close()
    for row in trainer.history:
        print(json.dumps({k: round(v, 4) for k, v in row.items()}))
    f = out["fault"]
    print(f"done. restarts={f.restarts} stragglers={f.stragglers_detected} "
          f"skipped_nonfinite={f.steps_skipped_nonfinite}")


if __name__ == "__main__":
    main()
