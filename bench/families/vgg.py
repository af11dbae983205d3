"""The VGG family: a configuration file -> the system's cohort, and the
model FLOPs each client's own architecture needs.

A configuration (``bench/configs/*.json`` with ``"family": "vgg"``)
lists ``archs`` (name -> per-stage conv widths) and ``clients`` (pairs
of arch name and count, in client order), with the shared
``classifier``, ``n_classes``, ``in_channels`` and ``image_size``.
"""
from __future__ import annotations

from typing import List, Optional


def client_dicts(config: dict) -> List[dict]:
    """Each client's architecture as a plain dict, in client order."""
    shared = {k: config[k] for k in ("classifier", "n_classes",
                                     "in_channels", "image_size")}
    out = []
    for arch, count in config["clients"]:
        out += [dict(shared, name=arch,
                     stages=[list(s) for s in config["archs"][arch]])
                for _ in range(int(count))]
    return out


def program_cohort(config: dict):
    """``(family, client_cfgs)`` in the system's own types."""
    from repro.configs.vgg_family import VGGConfig
    from repro.core import VGGFamily
    cfgs = [VGGConfig(name=c["name"],
                      stages=tuple(tuple(s) for s in c["stages"]),
                      classifier=tuple(c["classifier"]),
                      n_classes=c["n_classes"],
                      in_channels=c["in_channels"],
                      image_size=c["image_size"])
            for c in client_dicts(config)]
    return VGGFamily(), cfgs


def layer_macs(c: dict) -> List[int]:
    """Multiply-accumulates of each conv / fc layer for one sample, in
    chain order (3x3 SAME convs, a 2x2 pool after every stage)."""
    macs, cin, hw = [], c["in_channels"], c["image_size"]
    for ws in c["stages"]:
        for cout in ws:
            macs.append(hw * hw * 9 * cin * cout)
            cin = cout
        hw //= 2
    din = cin * hw * hw
    for dout in list(c["classifier"]) + [c["n_classes"]]:
        macs.append(din * dout)
        din = dout
    return macs


def train_flops_per_sample(c: dict, mix: Optional[dict] = None) -> int:
    """Forward + backward FLOPs of one training sample, an image of any
    traffic ``mix``: 2 per MAC forward, 2 for the weight gradient, 2 for
    the input gradient of every layer but the first (the image needs
    none). Bias adds, ReLU and pooling are left out."""
    macs = layer_macs(c)
    return 6 * sum(macs) - 2 * macs[0]
