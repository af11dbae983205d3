"""Cohort-parallel FedADP: the unified backend vs the per-client loop.

A depth+width-heterogeneous VGG cohort (both dimensions are
loop-equivalent in the unified space — segment operators, DESIGN.md §2)
is trained twice with identical data and SGD+momentum through the same
``Federation`` + ``FedADPStrategy``, swapping only the execution
backend: once through the reference per-client ``LoopBackend``, once as
a single stacked vmapped program (``UnifiedBackend`` around
fl/engine.py), shard_map-ed over the client axis when more than one
device is available.

  PYTHONPATH=src python examples/unified_cohort.py
"""
import jax

from repro.configs.vgg_family import scaled, vgg
from repro.core import VGGFamily
from repro.data import EASY, ClientSampler, image_classification, iid_partition
from repro.fl import Federation, FedADPStrategy, LoopBackend, UnifiedBackend
from repro.sharding import cohort_mesh


def main(*, rounds=4, local_epochs=1, eval_every=2, width=64,
         archs=("vgg13", "vgg16-wider", "vgg17", "vgg19-wider"),
         per_arch=2, n_per_client=160, n_test=400):
    family = VGGFamily()
    client_cfgs = [scaled(vgg(a), 0.125, width)
                   for a in archs for _ in range(per_arch)]
    K = len(client_cfgs)
    data = image_classification(EASY, n_per_client * K, seed=0)
    test = image_classification(EASY, n_test, seed=99)
    parts = iid_partition(n_per_client * K, K, seed=0)
    mesh = cohort_mesh(K)                            # None on 1 device
    print(f"{K} clients, client mesh: {mesh}")

    results = {}
    for engine in ("loop", "unified"):
        samplers = [ClientSampler(data, p, round_fraction=0.5, batch_size=32,
                                  seed=i) for i, p in enumerate(parts)]
        strategy = FedADPStrategy(family, client_cfgs,
                                  [s.n_samples for s in samplers])
        if engine == "unified":
            backend = UnifiedBackend(family, client_cfgs, samplers,
                                     local_epochs=local_epochs, lr=0.05,
                                     momentum=0.9, mesh=mesh)
        else:
            backend = LoopBackend(family, client_cfgs, samplers,
                                  local_epochs=local_epochs, lr=0.05,
                                  momentum=0.9)
        fed = Federation(strategy, backend, rounds=rounds, eval_batch=test,
                         eval_every=eval_every)
        res = fed.run(jax.random.PRNGKey(0))
        print(f"{engine:8s} acc by round: "
              + "  ".join(f"{a:.3f}" for a in res["history"])
              + f"   wall {res['wall_s']:.1f}s")
        results[engine] = res
    return results


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
