"""End-to-end training driver: package-query data selection + training with
checkpointing on a ~100M-class config.

Default runs a reduced model for a few hundred steps on this CPU container;
pass --full-135m to train the real smollm-135m config (slow on CPU, the
config a pod would run).

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--full-135m]
"""
import argparse
import sys

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full-135m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    arch = "smollm-135m" if args.full_135m else "smollm-135m-smoke"
    batch = "8" if args.full_135m else "16"
    seq = "512" if args.full_135m else "128"
    losses = train_main([
        "--arch", arch,
        "--steps", str(args.steps),
        "--batch", batch,
        "--seq", seq,
        "--lr", "3e-3",
        "--select-data",                 # package-query data selection
        "--ckpt-dir", args.ckpt_dir,
        "--ckpt-every", "50",
        "--log-every", "20",
    ])
    print(f"[example] final loss {losses[-1]:.4f} "
          f"(improved {losses[0] - losses[-1]:+.4f})")


if __name__ == "__main__":
    from repro.hostdev import use_compile_cache
    use_compile_cache()
    main()
