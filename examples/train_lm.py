"""End-to-end training driver: a ~1-4M-param reduced config of any of the
10 assigned architectures, a few hundred steps on the deterministic token
stream, with checkpointing + (optional) injected failure + auto-resume.

    PYTHONPATH=src python examples/train_lm.py --arch gemma2_9b --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch mamba2_130m \
        --steps 200 --fail-at 120      # crash, then rerun to resume
"""
import argparse
import dataclasses

from repro import arch as A
from repro.configs import reduced_arch
from repro.data import TokenStream
from repro.launch import compile_cache
from repro.optim import OptimizerConfig
from repro.train import SimulatedFailure, TrainConfig, Trainer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2_130m",
                    choices=A.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--fail-at", type=int, default=None)
    args = ap.parse_args()

    spec = reduced_arch(args.arch)
    spec = dataclasses.replace(spec, optimizer=OptimizerConfig(
        kind=spec.optimizer.kind, lr_peak=3e-3, lr_min=3e-4,
        warmup_steps=20, decay_steps=args.steps))
    shape = A.ShapeSpec("example", "train", args.seq, args.batch)
    data = TokenStream(vocab=spec.cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch, noise=0.02)
    cfg = TrainConfig(steps=args.steps, ckpt_every=50,
                      ckpt_dir=f"results/example_ckpt", log_every=20)
    tr = Trainer(spec, shape, data, cfg, failure_at=args.fail_at)
    try:
        final = tr.run()
    except SimulatedFailure as e:
        print(f"crashed as requested ({e}); rerun to resume from checkpoint")
        return
    first = tr.metrics_log[0]["loss"] if tr.metrics_log else float("nan")
    print(f"\narch={args.arch} loss {first:.3f} -> {final['loss']:.3f} "
          f"in {final['step']} steps ({final['wall_s']:.0f}s)")


if __name__ == "__main__":
    main()
