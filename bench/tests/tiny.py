"""A benchmark root holding tiny cells, for CPU tests of the harness.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``
and adds a four-client VGG cohort at a sixteenth of the widths (the
paper's depth and -Wider pattern kept), a two-round-step traffic mix,
limits and a BENCHMARK.json that names the tiny cells.
``add_lm_cell(root)`` adds, as new files alone, a token cohort: the
test-only family ``tinylm.py`` (three reduced glm4-9b clients that
differ in depth and ``d_ff``) and a packed-document token mix.

The tests that see a fault or a control fail the tiny cells' limits
also hold it against ``committed_limits()``, the loosest of the
committed cells' limits, so a committed limit loosened past what they
read fails a test.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
WIDTHS = (4, 8, 16, 32, 32)
DEPTHS = {"t13": (2, 2, 2, 2, 2), "t16-wider": (2, 2, 3, 3, 3),
          "t19": (2, 2, 4, 4, 4), "t19-wider": (2, 2, 4, 4, 4)}


def arch(name):
    st = [[WIDTHS[i]] * n for i, n in enumerate(DEPTHS[name])]
    if name.endswith("wider"):
        st[3][0] = 48
    return st


def plane_size(cfg) -> int:
    """Parameters of the cohort's union (what the engine's plane holds)."""
    n, cin, hw = 0, cfg["in_channels"], cfg["image_size"]
    union = {}
    for arch_name, _ in cfg["clients"]:
        for si, ws in enumerate(cfg["archs"][arch_name]):
            for li, w in enumerate(ws):
                union[(si, li)] = max(union.get((si, li), 0), w)
    stages = len(cfg["archs"][cfg["clients"][0][0]])
    for si in range(stages):
        for li in range(max(li for s, li in union if s == si) + 1):
            w = union[(si, li)]
            n += 9 * cin * w + w
            cin = w
        hw //= 2
    din = cin * hw * hw
    for d in list(cfg["classifier"]) + [cfg["n_classes"]]:
        n += din * d + d
        din = d
    return n


def tiny_config(clients):
    cfg = {"family": "vgg", "reduced": [], "image_size": 32,
           "in_channels": 3, "n_classes": 10, "classifier": [32, 32],
           "archs": {a: arch(a) for a, _ in clients},
           "clients": [list(c) for c in clients],
           "method": "fedadp", "filler": "zero", "agg_mode": "filler",
           "narrow_mode": "paper", "lr": 0.01, "momentum": 0.9,
           "k_chunk": 2}
    cfg["plane_size"] = plane_size(cfg)
    return cfg


MIX = {"n_train": 256, "round_fraction": 0.25, "batch_size": 12,
       "local_epochs": 2, "class_signal": 0.5}
LIMITS = {"d1_gap": 1e-3, "d3_gap": 1e-3, "loss_gap": 1e-3}


def committed_limits() -> dict:
    """The loosest limit of each number over the committed cells."""
    out: dict = {}
    for p in sorted((REPO / "bench" / "limits").glob("*.json")):
        for k, v in json.loads(p.read_text()).items():
            out[k] = max(out.get(k, v), v)
    return out



def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfgs = {"tiny-mixed": tiny_config([("t13", 1), ("t16-wider", 1),
                                       ("t19", 1), ("t19-wider", 1)]),
            "tiny-depth": tiny_config([("t13", 2), ("t19", 2)])}
    bench["configs"], bench["workloads"] = [], []
    for name, cfg in cfgs.items():
        path = f"bench/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": path, "reduced": [],
                                 "why": "test"})
        cell = f"{name}.tiny"
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(LIMITS))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


LM_CONFIG = {"family": "tinylm", "reduced": [], "base": "glm4-9b",
             "base_units": 2, "d_model": 32, "vocab_size": 512,
             "archs": {"u2f0.5": {"n_units": 2, "ffn_scale": 0.5},
                       "u1f1": {"n_units": 1, "ffn_scale": 1.0},
                       "u2f0.75": {"n_units": 2, "ffn_scale": 0.75}},
             "clients": [["u2f0.5", 1], ["u1f1", 1], ["u2f0.75", 1]],
             "method": "fedadp", "filler": "zero", "agg_mode": "filler",
             "narrow_mode": "paper", "lr": 0.05, "momentum": 0.9,
             "k_chunk": 2}
LM_MIX = {"data": "tokens", "n_train": 48, "seq_len": 16,
          "doc_len_median": 6, "doc_len_sigma": 0.8, "n_topics": 4,
          "topic_vocab": 8, "signal": 0.5, "round_fraction": 0.5,
          "batch_size": 3, "local_epochs": 1, "probe": 8}
LM_CELL = "tiny-lm.tiny_tokens"


def lm_plane_size(family_module, cfg) -> int:
    """Parameters of the token cohort's union, by the system's layout."""
    from repro.core import plane
    from repro.core.aggregation import global_shapes
    family, cfgs = family_module.program_cohort(cfg)
    return plane.PlaneSpec.from_tree(
        global_shapes(family, family.union(cfgs))).size


def add_lm_cell(root: Path) -> str:
    """Add the token cell to a ``make_root`` root, by new files alone;
    returns the cell's name."""
    import tinylm
    shutil.copy(tinylm.__file__, root / "bench" / "families" / "tinylm.py")
    cfg = dict(LM_CONFIG, plane_size=lm_plane_size(tinylm, LM_CONFIG))
    (root / "bench/configs/tiny-lm.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny_tokens.json").write_text(json.dumps(LM_MIX))
    (root / "bench/limits" / f"{LM_CELL}.json").write_text(
        json.dumps(LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-lm", "source": "test",
                             "file": "bench/configs/tiny-lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": LM_CELL, "config": "tiny-lm",
                               "traffic": "tiny_tokens", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return LM_CELL
