"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
Proteus-backed fault-tolerant checkpointing (random failures injected).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
(The default config is xlstm-125m at reduced sequence length so it finishes
on CPU; pass --arch/--batch/--seq to scale.)
"""
import argparse
import time

from repro.configs import all_configs
from repro.core.intent.selector import select_layout
from repro.core.workloads import workload_by_name
from repro.models import build_model
from repro.train.failure import FailurePlan
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import AdamW


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--fail-rate", type=float, default=0.02)
    args = ap.parse_args()

    cfg = all_configs()[args.arch].reduced()
    model = build_model(cfg)
    decision = select_layout(workload_by_name("IOR-A"))   # checkpoint profile
    print(f"[proteus] checkpoint layout: Mode {int(decision.mode)} "
          f"(conf {decision.confidence:.2f})")

    plan = FailurePlan.random_plan(args.steps, args.fail_rate, seed=1)
    print(f"[failure-plan] {len(plan.events)} injected events: "
          f"{dict(list(plan.events.items())[:5])}…")
    t0 = time.time()
    res = run_training(
        model, cfg, args.batch, args.seq,
        LoopConfig(steps=args.steps, ckpt_every=20,
                   ckpt_dir="/tmp/repro_train_lm",
                   layout_mode=decision.mode),
        optimizer=AdamW(learning_rate=1e-3, warmup_steps=args.steps // 10,
                        total_steps=args.steps),
        failure_plan=plan)
    dt = time.time() - t0
    fl = res.failure_log
    print(f"[train] {res.final_step} steps in {dt:.0f}s; "
          f"loss {res.losses[0]:.3f} → {res.losses[-1]:.3f}")
    print(f"[train] survived: {fl.crashes} crashes, {fl.stragglers} "
          f"stragglers, {fl.corruptions} corruptions "
          f"({fl.restores} restores, {fl.fallback_restores} checksum "
          f"fallbacks)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
