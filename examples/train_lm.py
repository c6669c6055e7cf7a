"""Train a ~100M-param LM for a few hundred steps with the MBProx optimizer
(the paper's technique as a deep-learning training step) and compare the
loss trajectory against the baseline AdamW data-parallel step.

    PYTHONPATH=src python examples/train_lm.py --steps 200 [--reduced]

Trains the published smollm-135m config (135M params — slow on CPU);
--reduced swaps in the small variant, which exercises the identical code
path.
"""
import argparse

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    print("=== MBProx (paper technique: local prox solves, 2 syncs/step) ===")
    _, mb_losses = train("smollm-135m", args.steps, optimizer="mbprox",
                         lr=5e-2, reduced=args.reduced, log_every=25)
    print("\n=== baseline (AdamW, grad all-reduce every microbatch) ===")
    _, ad_losses = train("smollm-135m", args.steps, optimizer="baseline",
                         lr=2e-2, reduced=args.reduced, log_every=25)
    print(f"\nMBProx   final/min loss: {mb_losses[-1]:.4f} / "
          f"{min(mb_losses):.4f}")
    print(f"baseline final/min loss: {ad_losses[-1]:.4f} / "
          f"{min(ad_losses):.4f}")
    print("data-axis collectives per outer step: MBProx 2, baseline "
          "n_micro (see EXPERIMENTS.md §Dry-run for the 256-chip counts)")


if __name__ == "__main__":
    main()
