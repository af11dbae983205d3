"""Quickstart: FedADP through the Federation API in ~40 lines.

Three clients with DIFFERENT VGG architectures jointly train one global
model on synthetic image classification; compare against standalone local
training after a few rounds.

The three moving parts (DESIGN.md §7): a ``Strategy`` (the method's
distribute/collect/aggregate math), a backend (``LoopBackend`` = the
reference per-client execution), and the ``Federation`` orchestrator
(rounds, participation, callbacks, checkpoints).

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs.vgg_family import scaled, vgg
from repro.core import VGGFamily
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import Federation, LoopBackend, make_strategy


def main(*, rounds=6, local_epochs=2, eval_every=2, n=1200, n_test=400,
         width=64, archs=("vgg13", "vgg16-wider", "vgg19"), per_arch=2,
         methods=("fedadp", "standalone")):
    # heterogeneous cohort: every client runs a different architecture
    family = VGGFamily()
    client_cfgs = [scaled(vgg(a), 0.125, width)
                   for a in archs for _ in range(per_arch)]
    data = image_classification(EASY, n, seed=0)
    test = image_classification(EASY, n_test, seed=99)
    parts = iid_partition(n, len(client_cfgs), seed=0)

    results = {}
    for method in methods:
        samplers = [ClientSampler(data, p, round_fraction=0.5, batch_size=32,
                                  seed=i) for i, p in enumerate(parts)]
        strategy = make_strategy(method, family, client_cfgs,
                                 [s.n_samples for s in samplers])
        backend = LoopBackend(family, client_cfgs, samplers,
                              local_epochs=local_epochs, lr=0.05,
                              momentum=0.9)
        fed = Federation(strategy, backend, rounds=rounds, eval_batch=test,
                         eval_every=eval_every)
        res = fed.run(jax.random.PRNGKey(0))
        print(f"{method:11s} accuracy by round: "
              + "  ".join(f"{a:.3f}" for a in res["history"]))
        results[method] = res
    return results


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
