"""BENCHMARK.json against the contract's shape rules, and one tiny cell end
to end on the CPU under --dry-cpu: the last stdout line, the refusal of a
wrong platform or chip count, and no device number from a CPU run."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchload import BENCH, REPO, cpu_env

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line_ok(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["configs"]) <= 24
    assert 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"])
        assert _line_ok(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line_ok(w["why"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line_ok(m["layer"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in (metrics, bench["workloads"], bench["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_cells_metrics_and_files_hang_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    for w in bench["workloads"]:
        # every cell reports setup_s, one more end-to-end metric and at
        # least one per-layer metric
        mine = [m["name"] for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", cells)
                   for m in bench["per_layer"])
        with open(os.path.join(REPO, configs[w["config"]]["file"])) as f:
            config = json.load(f)
        assert os.path.isfile(os.path.join(
            BENCH, "corpora", config["corpus"]["generator"] + ".py"))
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           traffic["driver"] + ".py"))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    # a full check must fit: 2 + 14 x cells runs at the full 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_every_file_under_paths_is_named_from_a_names_characters(bench):
    for p in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert PATH.match(rel), rel


def _run(args, cwd=REPO, env=None):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py")] + args,
        cwd=cwd, env=env or cpu_env(), capture_output=True, text=True,
        timeout=600)


def test_dry_cpu_cell_end_to_end(bench):
    r = _run(["--workload", "schnet_qm9-resident", "--seed", "5",
              "--seconds", "3", "--trace", "1", "--dry-cpu"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # the device is named as JAX reports it, and a CPU run carries counts
    # only: no rate, no time, no idle share, no memory figure
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    counts = {m["name"] for m in bench["per_layer"]
              if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counts
    assert "pad_edges_waste_pct" in line["metrics"]
    assert "hbm_peak_gb" not in line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    assert all("[cpu]" in ln for ln in r.stdout.splitlines()
               if ln.startswith("["))


@pytest.mark.parametrize("args,why", [
    (["--workload", "schnet_qm9-resident"], "a CPU where a TPU is asked"),
    (["--workload", "schnet_qm9-dp4"], "a CPU where four TPUs are asked"),
    (["--workload", "no-such-cell"], "an unknown cell"),
])
def test_refuses_without_a_result(args, why):
    r = _run(args + ["--seed", "0", "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0, why
    assert r.stdout.strip() == "", why


def test_refuses_a_wrong_chip_count_even_on_the_right_platform():
    env = cpu_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = _run(["--workload", "schnet_qm9-resident", "--seed", "0",
              "--seconds", "1", "--trace", "0", "--dry-cpu"], env=env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "refusing to run" in r.stderr


def test_refuses_where_the_system_under_test_is_missing(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths has nothing to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = cpu_env()
    env.pop("PYTHONPATH")
    r = _run(["--workload", "schnet_qm9-resident", "--seed", "0",
              "--seconds", "1", "--trace", "0", "--dry-cpu"],
             cwd=tmp_path, env=env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "not in this checkout" in r.stderr
