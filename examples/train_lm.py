"""End-to-end training driver example.

Default: a quick 30-step run of the reduced config with checkpointing.
``--preset 100m --steps 300`` trains a genuine ~100M-parameter model for
a few hundred steps (slow on CPU; the same driver + dryrun shardings run
the full configs on a pod).

Usage:
  PYTHONPATH=src python examples/train_lm.py
  PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_example_ckpt")
    args = ap.parse_args()

    losses = train(arch="qwen3-1.7b", steps=args.steps,
                   batch_size=4 if args.preset == "100m" else 8,
                   seq_len=128 if args.preset == "100m" else 64,
                   ckpt_dir=args.ckpt_dir,
                   scale=100.0 if args.preset == "100m" else 1.0)
    k = max(1, len(losses) // 5)
    print(f"first-{k} avg loss {sum(losses[:k]) / k:.4f} -> "
          f"last-{k} avg loss {sum(losses[-k:]) / k:.4f}")
    assert sum(losses[-k:]) <= sum(losses[:k]), "loss should decrease"
    print("train_lm OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
