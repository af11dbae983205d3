"""Find every part of a cell by name, under a benchmark root.

``BENCHMARK.json`` names the cells; each part sits in a file of its own:

  bench/configs/<config>.json     the cohort as it is run (``file`` in
                                  BENCHMARK.json's ``configs`` entry)
  bench/traffic/<traffic>.json    the traffic mix's parameters
  bench/limits/<workload>.json    the limits of the cell's output check
  bench/metrics/<metric>.py       one reader per per-layer metric
  bench/families/<family>.py      the cohort builder of a model family
  bench/reference/<family>.py     that family's plain reference

A later configuration, mix or metric is new files plus new entries in
BENCHMARK.json; nothing here changes. A configuration names its
``family``; the two modules of that name provide:

  families/<family>.py   ``client_dicts(config)``: each client's
                         architecture as a plain dict, in client order;
                         ``program_cohort(config)``: ``(family,
                         client_cfgs)`` in the system's own types;
                         ``train_flops_per_sample(client, mix)``: forward
                         and backward FLOPs of one row of the mix's data
  reference/<family>.py  ``union(clients)``: the union architecture;
                         ``init_params(key, union)``: its weights;
                         ``fedadp_round(g, union, clients, n_samples,
                         batches, *, round_idx, base_seed, lr, momentum,
                         dtype, store)``: one round from the global model,
                         ``batches`` each client's ``traffic.padded_round``
                         ``(inputs, targets, valid)``; ``loss(params,
                         inputs, targets, valid)``: the mean loss over the
                         rows ``valid`` marks

A mix's ``data`` (``"images"`` or ``"tokens"``, ``bench/traffic.py``)
says which arrays the data holds and which mix keys make it; a token
cohort is a family module, a reference module, a configuration and a
mix, all new files.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with every part it names loaded."""
    root: Path
    name: str
    entry: dict                  # the ``workloads`` entry
    config: dict                 # bench/configs/<config>.json
    mix: dict                    # bench/traffic/<traffic>.json
    limits: Dict[str, float]     # bench/limits/<workload>.json
    end_to_end: List[dict]       # metrics this cell reports, trace 0
    per_layer: List[dict]        # metrics this cell reports, trace 1
    _modules: Dict[str, object] = field(default_factory=dict, repr=False)

    def _module(self, *parts: str):
        """Load ``bench/<parts>.py`` once per cell."""
        key = "/".join(parts)
        if key not in self._modules:
            self._modules[key] = load_module(
                self.root.joinpath("bench", *parts[:-1], parts[-1] + ".py"),
                "bench_" + "_".join(parts).replace(".", "_")
                .replace("-", "_"))
        return self._modules[key]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def family(self):
        return self._module("families", self.config["family"])

    def reference(self):
        return self._module("reference", self.config["family"])

    def reader(self, metric: str) -> Callable:
        return self._module("metrics", metric).read


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(entries)})")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[entry["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")
    return Cell(root=root, name=workload, entry=entry, config=config,
                mix=mix, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)])
