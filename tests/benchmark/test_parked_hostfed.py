"""The host-fed cell left BENCHMARK.json (its runs spread too widely on a
shared host: PERF.md, Findings), but its traffic file, its layer metric's
reader and the driver's loader stopwatch stayed: one appended entry each
brings them back.  Held here so that they do not rot meanwhile."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchload import BENCH, REPO, cpu_env, load


def test_hostfed_cell_comes_back_by_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "schnet_qm9-hostfed", "config": "schnet_qm9",
        "traffic": "hostfed", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "loader_wait_pct", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "pipeline", "moves":
        "train_graphs_per_s", "workloads": ["schnet_qm9-hostfed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         "schnet_qm9-hostfed", "--seed", "2", "--seconds", "3", "--trace",
         "1", "--dry-cpu"],
        cwd=root, env=cpu_env(), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # ``correct`` holds the traffic file's ``expect``: residency off, K > 1
    assert line["correct"] is True and line["attempted"] > 0
    assert '"resident": false' in r.stdout
    # a host-clock share is no count: a CPU rehearsal does not state it
    assert "loader_wait_pct" not in line["metrics"]


def test_loader_wait_reader():
    read = load("layer_metrics", "loader_wait_pct").read
    facts = {"epochs": [{"t0": 10.0, "t1": 14.0}, {"t0": 14.0, "t1": 20.0}],
             # (end of next(), seconds in it): 1.0 + 0.5 of the 10 counted
             # seconds; the third ended after them
             "loader_waits": [(12.0, 1.0), (15.0, 0.5), (25.0, 3.0)]}
    assert read(facts) == pytest.approx(15.0)
    assert read({"epochs": [], "loader_waits": []}) is None
