"""The harness finds every part of a cell by name: a new configuration,
traffic mix and per-layer metric are new files plus new BENCHMARK.json
entries, with no existing file edited. And a run with no TPU, or with
nothing but the benchmark's own files, exits non-zero with no result."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import registry  # noqa: E402
import tiny  # noqa: E402


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_traffic_metric_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    before = digest(root)
    cfg = json.loads((root / "bench/configs/tiny-depth.json").read_text())
    cfg["clients"] = [["t13", 3], ["t19", 1]]
    (root / "bench/configs/tiny-new.json").write_text(json.dumps(cfg))
    mix = dict(tiny.MIX, batch_size=7)
    (root / "bench/traffic/new_mix.json").write_text(json.dumps(mix))
    (root / "bench/limits/tiny-new.new_mix.json").write_text(
        json.dumps(tiny.LIMITS))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['x']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-new.new_mix",
                               "config": "tiny-new", "traffic": "new_mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "local training", "moves": "round_s",
                               "workloads": ["tiny-new.new_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = digest(root)
    assert all(after[k] == v for k, v in before.items()), \
        "an existing file changed"
    cell = registry.load_cell("tiny-new.new_mix", root)
    assert cell.config["clients"] == [["t13", 3], ["t19", 1]]
    assert cell.mix["batch_size"] == 7
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert cell.reader("new_metric")({"x": 3.0}) == 6.0
    assert len(cell.family().client_dicts(cell.config)) == 4
    # a metric scoped to the new cell is not asked of the others
    old = registry.load_cell("tiny-depth.tiny", root)
    assert "new_metric" not in [m["name"] for m in old.per_layer]


def _run(cmd, cwd, env):
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return not last.startswith("{")


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([sys.executable, "bench/run.py", "--workload",
                 "vgg-depth20.one_step", "--seed", "2147483659",
                 "--seconds", "1", "--trace", "0"], BENCH.parent, env)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no system to
    run: the run fails before it prints a result."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, 'bench'); import harness; "
            "harness.run_cell('vgg-depth20.one_step', 1, 1.0, False, "
            "require_tpu=False)")
    proc = _run([sys.executable, "-c", code], tmp_path, env)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "repro" in proc.stderr
