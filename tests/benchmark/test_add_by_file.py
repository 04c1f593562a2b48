"""A later PR adds a configuration, a traffic mix, a driver and a layer
metric as NEW files plus one appended entry each, and edits nothing that
is there: prove it on a temporary copy of the benchmark."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

from benchload import BENCH, REPO, cpu_env

NEW_METRIC = '''"""A new per-layer metric: real graphs a counted epoch."""


def read(facts):
    eps = facts["epochs"]
    return sum(e["graphs"] for e in eps) / len(eps) if eps else None
'''

NEW_DRIVER = '''"""A new driver: the stock one, announced."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "stock_train_epochs",
    os.path.join(os.path.dirname(__file__), "train_epochs.py"))
_stock = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_stock)


def run(ctx):
    ctx["say"]("driver announced_epochs")
    return _stock.run(ctx)
'''


def test_new_cell_from_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # a new configuration: GIN on the composed scatter path, tiny
    with open(os.path.join(BENCH, "configs", "schnet_qm9.json")) as f:
        config = json.load(f)
    config.pop("dry_cpu")
    config["corpus"] = {"generator": "qm9_shaped", "n": 200,
                        "params": {"atoms_lo": 5, "atoms_hi": 8,
                                   "layout_seed": 3}}
    config["expect"] = {"fused_ops": []}
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(model_type="GIN", aggregation_backend="scatter",
                hidden_dim=8, num_conv_layers=2, radius=3.0,
                max_neighbours=6)
    arch["output_heads"]["graph"].update(dim_sharedlayers=8,
                                         dim_headlayers=[8, 8])
    config["NeuralNetwork"]["Training"]["batch_size"] = 8
    (root / "benchmark" / "configs" / "gin_tiny.json").write_text(
        json.dumps(config))
    # a new traffic mix on a new driver, and a new layer metric
    (root / "benchmark" / "traffic" / "announced.json").write_text(
        json.dumps({"driver": "announced_epochs", "why": "test",
                    # one bucket: a shuffled tiny corpus would otherwise
                    # meet a new bucket (a compile) in some later epoch
                    "env": {"HYDRAGNN_AUTO_PIPELINE": "0",
                            "HYDRAGNN_NUM_BUCKETS": "1"},
                    "expect": {"pipeline": {"resident": False,
                                            "steps_per_dispatch": 1}}}))
    (root / "benchmark" / "drivers" / "announced_epochs.py").write_text(
        NEW_DRIVER)
    (root / "benchmark" / "layer_metrics" / "graphs_per_epoch.py"
     ).write_text(NEW_METRIC)
    # entries are appended; none that is there changes
    bench["configs"].append({
        "name": "gin_tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/gin_tiny.json"})
    bench["workloads"].append({
        "name": "gin_tiny-announced", "config": "gin_tiny",
        "traffic": "announced", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "graphs_per_epoch", "unit": "graphs", "better": "higher",
        "source": "program_counter", "layer": "data path", "moves":
        "train_graphs_per_s", "workloads": ["gin_tiny-announced"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    r = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "gin_tiny-announced", "--seed", "1", "--seconds", "2", "--trace",
         "1", "--dry-cpu"],
        cwd=root, env=cpu_env(), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "driver announced_epochs" in r.stdout
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # 160 train molecules, batch 8, nothing dropped
    assert line["metrics"]["graphs_per_epoch"] == {"value": 160.0,
                                                   "unit": "graphs"}
    assert "pad_edges_waste_pct" in line["metrics"]
    # a metric listed for other cells only is not this cell's
    assert "collective_exposed_pct" not in line["metrics"]

    # no file that was there was edited
    def same(a, b):
        cmp = filecmp.dircmp(a, b, ignore=[".cache", "__pycache__"])
        assert not cmp.diff_files and not cmp.left_only, (
            cmp.diff_files, cmp.left_only)
        for sub in cmp.common_dirs:
            same(os.path.join(a, sub), os.path.join(b, sub))

    same(BENCH, str(root / "benchmark"))
