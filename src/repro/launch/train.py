"""Production training launcher.

    python -m repro.launch.train --arch qwen3_8b --steps 1000 \
        --checkpoint-dir /ckpt/qwen3 [--mode zero] [--multi-pod] \
        [--pack-params [--repack-every N] [--plan plan.json]]

On a real pod this process runs per host (jax.distributed.initialize is
called when JAX_COORDINATOR is set); here it also drives single-host
runs with reduced configs (--reduced) for CI. Fault tolerance: resumes
from the newest complete checkpoint, checkpoints on SIGTERM, flags
stragglers, and replays the data stream exactly (step-keyed PRNG).
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--mode", default="tp", choices=["tp", "zero"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config for single-host runs")
    ap.add_argument("--grad-compress-bits", type=int, default=None)
    ap.add_argument("--pack-params", action="store_true",
                    help="packed-master training: params live as "
                         "PackedTensor codes at the planned width; the "
                         "optimizer owns dense masters")
    ap.add_argument("--repack-every", type=int, default=1,
                    help="re-encode changed masters to codes every N "
                         "steps (packed-master mode)")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="packed-master plan source: a calibrated "
                         "per-leaf plan JSON (core.calibrate / "
                         "repro.tuning.calibrate) instead of the uniform "
                         "config width")
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="stream train.step / train.repack / "
                         "train.metrics events to this JSONL file")
    ap.add_argument("--metrics-interval", type=int, default=1,
                    metavar="N",
                    help="emit a train.step event every N steps")
    args = ap.parse_args()

    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()       # multi-host pod entry

    from repro import compat
    from repro.configs import get_config
    from repro.train import Trainer, TrainConfig

    compat.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        args.seq_len = min(args.seq_len, 128)
        args.global_batch = min(args.global_batch, 4)

    tc = TrainConfig(
        steps=args.steps,
        seq_len=args.seq_len,
        global_batch=args.global_batch,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=max(args.steps // 10, 1),
        grad_compress_bits=args.grad_compress_bits
        or cfg.compression.grad_bits,
        pack_params=args.pack_params,
        repack_every=args.repack_every,
        plan_path=args.plan,
        metrics_out=args.metrics_out,
        metrics_interval=args.metrics_interval,
    )

    if args.reduced:
        metrics = Trainer(cfg, tc).run(install_signals=True)
    else:
        # full-scale path: production mesh + sharded step programs
        from repro.compat import mesh_context
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        with mesh_context(mesh):
            metrics = Trainer(cfg, tc).run(install_signals=True)

    print(f"final loss: {metrics['final_loss']:.4f}  "
          f"steps: {metrics['last_step'] + 1}  "
          f"stragglers: {metrics['straggler_events']}")
    if args.metrics_out:
        print(f"wrote telemetry to {args.metrics_out}")


if __name__ == "__main__":
    main()
