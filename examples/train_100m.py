"""End-to-end driver (deliverable b): train a ~100M-parameter LM for a few
hundred steps on the synthetic Markov corpus and verify the loss drops.

The model is a glm4-9b family member scaled to ~100M params (the same
code path the production launcher uses — launch/train.py — with the full
config swapped in on real hardware).

  PYTHONPATH=src python examples/train_100m.py [--steps 300]
"""
import argparse
import dataclasses

import numpy as np

from repro.configs import get_config, reduced
from repro.launch.train import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="artifacts/train_100m.npz")
    args = ap.parse_args()

    # 12 layers x d_model 768 (glm4 family geometry) ~= 100M parameters
    res = run("glm4-9b", use_reduced=True, d_model=768, n_units=6,
              steps=args.steps, batch=args.batch, seq=args.seq, lr=3e-4,
              ckpt=args.ckpt, log_every=20)
    losses = res["losses"]
    l0, l1 = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss: {l0:.3f} -> {l1:.3f}")
    assert l1 < l0 - 0.2, "training did not make progress"
    print("OK: loss improved")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
