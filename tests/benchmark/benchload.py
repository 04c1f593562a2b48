"""Shared by the benchmark's tests: the benchmark's files loaded by path
(``benchmark/`` is a directory of scripts and plug-ins, not a package)."""

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def run_module():
    """benchmark/run.py as a module (its ``load_module`` finds the rest)."""
    name = "benchmark_run"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def load(kind, name):
    return run_module().load_module(kind, name)


def cpu_env():
    """Environment for a ``run.py --dry-cpu`` child: the parent's, without
    the forced 8 host devices of tests/conftest.py."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env
