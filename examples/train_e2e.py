"""End-to-end training driver: a ~100M-class model for a few hundred steps.

    PYTHONPATH=src python examples/train_e2e.py            # CPU-sized default
    PYTHONPATH=src python examples/train_e2e.py --full     # xlstm-125m, 200 steps

Uses the ScaDLES-integrated trainer (per-sample rate weights + linear LR
scaling active) on the synthetic bigram LM stream; checkpoints at the end.
"""
import argparse

from repro.launch import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full xlstm-125m, 200 steps (slow on CPU)")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()
    if args.full:
        steps = args.steps or 200
        argv = ["--arch", "xlstm-125m", "--steps", str(steps),
                "--batch", "8", "--seq", "256", "--scadles",
                "--ckpt", "artifacts/ckpt"]
    else:
        steps = args.steps or 60
        argv = ["--arch", "xlstm-125m", "--reduced",
                "--steps", str(steps), "--batch", "16", "--seq", "128",
                "--scadles", "--ckpt", "artifacts/ckpt"]
    train.main(argv)


if __name__ == "__main__":
    main()
