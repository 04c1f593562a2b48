"""chip_smoke.py (the on-chip bring-up proof) and the compile-cache helper
it shares with every entry point — what a CPU box can hold them to:

  - the explicit ``--platform cpu --tiny`` dry run drives the whole path
    (data -> run_training -> run_prediction -> InferenceServer -> kernel
    parity) and says ``cpu`` everywhere;
  - without the flag and without a TPU the script refuses in seconds,
    non-zero, with nothing on stdout and nothing trained;
  - the cache directory is placed from outside when the variable is set,
    at the fixed ``<checkout>/.jax_cache`` otherwise.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device: the local-jit path
    env.pop("PYTHONPATH", None)
    return env


def test_chip_smoke_tiny_cpu_dry_run(tmp_path):
    work = tmp_path / "work"
    r = subprocess.run(
        [sys.executable, _SMOKE, "--platform", "cpu", "--tiny",
         "--workdir", str(work)],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the LAST stdout line is the verdict, with exactly these keys
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": verdict["device"]["kind"], "count": 1}}
    assert isinstance(verdict["device"]["kind"], str)
    summary = json.loads(lines[-2])        # everything measured
    assert summary["ok"] is True
    assert summary["device"] == verdict["device"]
    assert summary["device"]["platform"] == "cpu"
    assert summary["device"]["count"] == 1
    assert summary["tiny"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(summary["seconds"]) >= {
        "data", "train", "predict", "serve", "kernels", "total"}
    # every line the script itself prints says which platform ran
    stage_lines = [ln for ln in lines if ln.startswith("[")]
    assert len(stage_lines) >= 8
    assert all(ln.startswith("[cpu]") for ln in stage_lines)
    assert all(r["interpreted"] and r["ok"] for r in summary["kernels"])
    assert {r["arch"] for r in summary["kernels"]} == {"SchNet", "PNA"}
    assert summary["train"]["aggr_dispatch"]["gather_mul:fused"] > 0
    assert summary["serve"]["cache_misses_after_warmup"] == 0


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """No flag, no TPU: non-zero in seconds, no result, nothing trained."""
    work = tmp_path / "work"
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, _SMOKE, "--workdir", str(work)],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "refusing to run" in r.stderr
    assert not work.exists()
    assert time.time() - t0 < 60
    # the CPU mode is only ever the tiny dry run, asked for by name
    r = subprocess.run(
        [sys.executable, _SMOKE, "--platform", "cpu"],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    # a directory that holds chip_smoke.py and nothing else of the repo
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(_SMOKE, alone / "chip_smoke.py")
    r = subprocess.run(
        [sys.executable, str(alone / "chip_smoke.py"), "--platform", "cpu",
         "--tiny"],
        env=_env(), cwd=str(alone), capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_setup_compile_cache(monkeypatch):
    import jax

    from hydragnn_tpu.utils import runtime

    prior = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: NO directory is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")

        def forbidden(*a, **k):
            raise AssertionError("cache directory set in code")

        with monkeypatch.context() as m:
            m.setattr(jax.config, "update", forbidden)
            assert runtime.setup_compile_cache() == "/some/where/else"
        # unset: the fixed <checkout>/.jax_cache — no temp name, pid, time
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_REPO, ".jax_cache")
        assert runtime.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert runtime.setup_compile_cache() == want    # and stable
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
