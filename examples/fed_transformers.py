"""Beyond-paper: FedADP over a heterogeneous TRANSFORMER cohort.

Clients hold depth/width variants of one assigned architecture family
(default: glm4-9b reduced). NetChange aligns them to the union
architecture for aggregation, exactly like the VGG cohort in the paper —
demonstrating the framework's first-class integration of the technique
with modern architectures (DESIGN.md §2).

  PYTHONPATH=src python examples/fed_transformers.py [--arch glm4-9b]
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import FedADP, TransformerFamily, tfamily
from repro.data import lm_sequences
from repro.optim import sgd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps-per-round", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    base = reduced(get_config(args.arch), n_units=2, d_model=256)
    # heterogeneous cohort: full / shallow / narrow / shallow+narrow
    variants = [
        tfamily.make_variant(base, n_units=2, ffn_scale=1.0),
        tfamily.make_variant(base, n_units=1, ffn_scale=1.0),
        tfamily.make_variant(base, n_units=2, ffn_scale=0.5),
        tfamily.make_variant(base, n_units=1, ffn_scale=0.5),
    ][: args.clients]
    family = TransformerFamily()
    algo = FedADP(family, variants, n_samples=[4, 2, 2, 1][: args.clients],
                  narrow_mode="fold")
    print(f"# global architecture: {algo.global_cfg.name} "
          f"L={algo.global_cfg.n_layers} d_ff={algo.global_cfg.d_ff}")

    opt = sgd(0.05)

    def local_train(k, params):
        cfg = variants[k]
        lg = jax.jit(family.loss_and_grad(cfg))
        state = opt.init(params)
        for s in range(args.steps_per_round):
            seqs = lm_sequences(cfg.vocab_size, 4, args.seq,
                                seed=1000 * k + s)
            batch = {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}
            (loss, _), grads = lg(params, batch)
            params, state = opt.update(grads, state, params, s)
        return params

    gp = algo.init_global(jax.random.PRNGKey(0))
    eval_seqs = lm_sequences(base.vocab_size, 8, args.seq, seed=777)
    eval_batch = {"tokens": eval_seqs[:, :-1], "labels": eval_seqs[:, 1:]}
    for r in range(args.rounds):
        gp = algo.round(gp, local_train, r)
        losses = [family.evaluate(algo.distribute(gp, r + 1, k), variants[k],
                                  eval_batch)
                  for k in range(len(variants))]
        print(f"round {r+1}: per-client eval loss = "
              + "  ".join(f"{l:.3f}" for l in losses))


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
