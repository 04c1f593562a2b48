"""Hyperparameter optimization glue.

Parity target: the reference's HPO layer — examples/qm9_hpo/qm9_optuna.py
(Optuna TPE/random/CMA-ES :186-211), examples/multidataset_hpo (DeepHyper
async trials over srun subprocesses, val-loss scrape) and
hydragnn/utils/deephyper.py launch-command builders.

Here HPO is first-class: :func:`run_hpo` runs trials in-process against
``run_training`` (optionally via optuna when importable, else a built-in
random searcher with successive-halving pruning), and
:func:`build_launch_command` emits scheduler launch strings for
subprocess-per-trial mode (the DeepHyper pattern).
"""

from __future__ import annotations

import copy
import json
import math
import os
import subprocess
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


@dataclass
class HP:
    """One hyperparameter: categorical choices, or a (low, high) range."""

    name: str
    path: Sequence[str]          # key path into the config dict
    choices: Optional[Sequence[Any]] = None
    low: Optional[float] = None
    high: Optional[float] = None
    log: bool = False
    is_int: bool = False

    def sample(self, rng) -> Any:
        if self.choices is not None:
            return self.choices[rng.randint(len(self.choices))]
        if self.log:
            v = math.exp(rng.uniform(math.log(self.low), math.log(self.high)))
        else:
            v = rng.uniform(self.low, self.high)
        return int(round(v)) if self.is_int else v


def _set_path(config: Dict[str, Any], path: Sequence[str], value: Any) -> None:
    d = config
    for k in path[:-1]:
        d = d[k]
    d[path[-1]] = value


@dataclass
class Trial:
    number: int
    params: Dict[str, Any]
    value: Optional[float] = None
    state: str = "running"


def run_hpo(
    base_config: Dict[str, Any],
    space: Sequence[HP],
    n_trials: int = 10,
    seed: int = 0,
    sampler: str = "random",
    objective: Optional[Callable[[Dict[str, Any]], float]] = None,
    halving_epochs: Optional[Tuple[int, int]] = None,
) -> Tuple[Trial, List[Trial]]:
    """Minimize final validation loss over the search space.

    ``sampler``: "optuna-tpe" / "optuna-random" use optuna when importable;
    "random" is the built-in fallback.  ``halving_epochs`` = (low, full)
    trains every trial ``low`` epochs first and only the top half ``full``
    epochs (successive halving).  Returns (best, all trials).
    """
    if objective is None:
        objective = _default_objective(base_config)

    def make_config(params):
        cfg = copy.deepcopy(base_config)
        for hp in space:
            _set_path(cfg, hp.path, params[hp.name])
        return cfg

    if sampler.startswith("optuna"):
        try:
            return _run_optuna(make_config, space, n_trials, seed,
                               sampler.split("-", 1)[-1], objective)
        except ImportError:
            sampler = "random"

    import numpy as np

    rng = np.random.RandomState(seed)
    trials: List[Trial] = []
    for i in range(n_trials):
        params = {hp.name: hp.sample(rng) for hp in space}
        cfg = make_config(params)
        if halving_epochs:
            cfg["NeuralNetwork"]["Training"]["num_epoch"] = halving_epochs[0]
        try:
            value = objective(cfg)
            trials.append(Trial(i, params, value, "complete"))
        except Exception as e:  # failed trial
            trials.append(Trial(i, params, float("inf"), f"failed: {e}"))

    if halving_epochs:
        survivors = sorted(
            [t for t in trials if t.state == "complete"],
            key=lambda t: t.value)[: max(1, n_trials // 2)]
        for t in survivors:
            cfg = make_config(t.params)
            cfg["NeuralNetwork"]["Training"]["num_epoch"] = halving_epochs[1]
            try:
                t.value = objective(cfg)
            except Exception as e:
                t.value, t.state = float("inf"), f"failed: {e}"

    best = min(trials, key=lambda t: t.value)
    return best, trials


def _default_objective(base_config):
    def objective(cfg: Dict[str, Any]) -> float:
        import hydragnn_tpu

        _state, history, _cfg = hydragnn_tpu.run_training(cfg)
        return float(min(history["val"]))

    return objective


def _run_optuna(make_config, space, n_trials, seed, kind, objective):
    import optuna  # gated: not in the base image

    def opt_objective(trial: "optuna.Trial") -> float:
        params = {}
        for hp in space:
            if hp.choices is not None:
                params[hp.name] = trial.suggest_categorical(
                    hp.name, list(hp.choices))
            elif hp.is_int:
                params[hp.name] = trial.suggest_int(
                    hp.name, int(hp.low), int(hp.high), log=hp.log)
            else:
                params[hp.name] = trial.suggest_float(
                    hp.name, hp.low, hp.high, log=hp.log)
        return objective(make_config(params))

    samplers = {
        "tpe": lambda: optuna.samplers.TPESampler(seed=seed),
        "random": lambda: optuna.samplers.RandomSampler(seed=seed),
        "cmaes": lambda: optuna.samplers.CmaEsSampler(seed=seed),
    }
    study = optuna.create_study(
        direction="minimize", sampler=samplers.get(kind, samplers["tpe"])())
    study.optimize(opt_objective, n_trials=n_trials)
    trials = [
        Trial(t.number, t.params,
              t.value if t.value is not None else float("inf"),
              str(t.state))
        for t in study.trials
    ]
    best = min(trials, key=lambda t: t.value)
    return best, trials


# ---------------------------------------------------------------------------
# scheduler launch-command builders (reference utils/deephyper.py:94-173)
# ---------------------------------------------------------------------------

def read_node_list() -> List[str]:
    """Hosts available to this job from the scheduler env."""
    from hydragnn_tpu.utils.slurm import parse_slurm_nodelist

    nodelist = os.getenv("SLURM_NODELIST", os.getenv("SLURM_JOB_NODELIST", ""))
    if nodelist:
        return parse_slurm_nodelist(nodelist)
    lsb = os.getenv("LSB_HOSTS", "")
    if lsb:
        hosts = [h for h in lsb.split() if h != "batch"]
        return sorted(set(hosts), key=hosts.index)
    return ["localhost"]


def build_launch_command(
    trial_script: str,
    nodes: Sequence[str],
    procs_per_node: int = 1,
    system: Optional[str] = None,
    extra_args: Sequence[str] = (),
) -> List[str]:
    """Launch command for one subprocess trial on a node subset."""
    system = system or os.getenv("HYDRAGNN_SYSTEM", "")
    if os.getenv("SLURM_JOB_ID") or system in ("frontier", "perlmutter"):
        cmd = ["srun", "-n", str(len(nodes) * procs_per_node),
               "--nodelist", ",".join(nodes),
               sys.executable, trial_script]
    elif system == "summit":
        cmd = ["jsrun", "-n", str(len(nodes) * procs_per_node),
               sys.executable, trial_script]
    else:
        cmd = [sys.executable, trial_script]
    return list(cmd) + list(extra_args)


def run_hpo_async(
    trial_script: str,
    space: Sequence[HP],
    n_trials: int = 8,
    n_concurrent: int = 2,
    nodes: Optional[Sequence[str]] = None,
    nodes_per_trial: int = 1,
    procs_per_node: int = 1,
    seed: int = 0,
    timeout: float = 3600,
    loss_pattern: str = "val loss:",
    extra_args: Sequence[str] = (),
) -> Tuple[Trial, List[Trial]]:
    """Asynchronous multi-job HPO: up to ``n_concurrent`` subprocess trials
    run simultaneously, each on its own node subset (the DeepHyper pattern —
    reference examples/multidataset_hpo/gfm_deephyper_multi.py:22-41 launches
    concurrent srun trials and regex-scrapes the validation loss).

    Node subsets are managed by a queue: a finishing trial returns its nodes
    so a queued trial can start — true async scheduling, not batched waves.
    Every trial is its own process and opens the accelerator itself; a chip
    belongs to one process at a time, so on a workstation with ONE chip use
    ``n_concurrent=1`` (a second concurrent trial fails or hangs at backend
    start).
    Each trial passes its sampled params as ``--hpo key=value`` args that the
    trial script applies to its config.
    """
    import queue as _queue
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    nodes = list(nodes if nodes is not None else read_node_list())
    under_scheduler = bool(os.getenv("SLURM_JOB_ID")) or \
        os.getenv("HYDRAGNN_SYSTEM", "") in ("frontier", "perlmutter", "summit")
    if under_scheduler:
        groups: List[List[str]] = [
            nodes[i:i + nodes_per_trial]
            for i in range(0, len(nodes) - nodes_per_trial + 1,
                           nodes_per_trial)
        ] or [nodes]
    else:
        # workstation: build_launch_command ignores the node list, so don't
        # let one 'localhost' entry serialize the trials — replicate it
        groups = [list(nodes)] * max(n_concurrent, 1)
    n_workers = max(1, min(n_concurrent, len(groups)))
    free: "_queue.Queue" = _queue.Queue()
    for g in groups:
        free.put(g)

    rng = np.random.RandomState(seed)
    trials = [Trial(i, {hp.name: hp.sample(rng) for hp in space})
              for i in range(n_trials)]

    paths = {hp.name: ".".join(str(k) for k in hp.path) for hp in space}

    def run_one(trial: Trial) -> Trial:
        group = free.get()  # blocks until a node subset frees up
        try:
            hpo_args: List[str] = []
            for k, v in trial.params.items():
                hpo_args += ["--hpo", f"{paths[k]}={v}"]
            cmd = build_launch_command(
                trial_script, group, procs_per_node,
                extra_args=list(extra_args) + hpo_args)
            try:
                trial.value = launch_trial_subprocess(
                    cmd, timeout=timeout, loss_pattern=loss_pattern)
                trial.state = ("complete"
                               if math.isfinite(trial.value) else "failed")
            except Exception as e:
                trial.value, trial.state = float("inf"), f"failed: {e}"
            return trial
        finally:
            free.put(group)  # hand the nodes to the next queued trial

    with ThreadPoolExecutor(max_workers=n_workers) as pool:
        trials = list(pool.map(run_one, trials))
    best = min(trials, key=lambda t: t.value)
    return best, trials


def apply_hpo_args(config: Dict[str, Any],
                   hpo_kvs: Sequence[str]) -> Dict[str, Any]:
    """Apply ``key=value`` pairs from ``--hpo`` args to a config.  ``key`` is
    a dot-path into the nested config (e.g.
    ``NeuralNetwork.Training.Optimizer.learning_rate=0.01``)."""
    import ast

    for kv in hpo_kvs:
        key, _, raw = kv.partition("=")
        try:
            value = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            value = raw
        _set_path(config, key.split("."), value)
    return config


def launch_trial_subprocess(cmd: Sequence[str], timeout: float = 3600,
                            loss_pattern: str = "val loss:") -> float:
    """Run a trial subprocess and scrape its final validation loss (the
    DeepHyper pattern; reference examples/multidataset_hpo/
    gfm_deephyper_multi.py:35-41)."""
    r = subprocess.run(list(cmd), capture_output=True, text=True,
                       timeout=timeout)
    best = float("inf")
    for line in r.stdout.splitlines():
        if loss_pattern in line:
            try:
                v = float(line.split(loss_pattern)[1].split(",")[0])
                best = min(best, v)
            except (ValueError, IndexError):
                pass
    return best
