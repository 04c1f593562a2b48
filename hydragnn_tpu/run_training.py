"""JSON-config-driven training entry point.

Parity: reference hydragnn/run_training.py:43-133 — accepts a config file path
or dict (singledispatch), then: data loading/splitting -> config finalization
-> model -> optimizer (+ plateau LR scheduler) -> train/validate/test loop ->
rank-0 model save -> timer printout.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, Tuple

from hydragnn_tpu.config.config import get_log_name_config, save_config
from hydragnn_tpu.data.load_data import dataset_loading_and_splitting
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.trainer import (
    create_train_state,
    save_state,
    train_validate_test,
)
from hydragnn_tpu.utils.print_utils import print_distributed, setup_log
from hydragnn_tpu.utils import tracer as tr


@functools.singledispatch
def run_training(config, **kwargs):
    raise TypeError("Input must be filename string or configuration dictionary.")


@run_training.register
def _(config_file: str, **kwargs):
    with open(config_file, "r") as f:
        config = json.load(f)
    return run_training(config, **kwargs)


@run_training.register
def _(config: dict, logs_dir: str = "./logs/", seed: int = 0):
    os.environ.setdefault("SERIALIZED_DATA_PATH", os.getcwd())

    # aggregation-backend plumbing: ``Architecture.aggregation_backend``
    # pins the message-passing backend (scatter | fused) for config-driven
    # runs; a USER-set HYDRAGNN_AGGR_BACKEND env knob wins.
    # Must happen BEFORE data loading and tracing: collate attaches the
    # fused-kernel marker at batch-build time, and jitted steps pin
    # whichever backend was active when first traced
    # (ops/aggregate.py:aggr_backend).  The run's manifest records the
    # active backend and the fused-vs-fallback dispatch tally
    # (docs/TELEMETRY.md).
    backend = (config.get("NeuralNetwork", {}).get("Architecture", {})
               .get("aggregation_backend"))
    from hydragnn_tpu.ops.aggregate import KNOWN_BACKENDS, backend_scope

    if backend and str(backend) not in KNOWN_BACKENDS:
        # a typo ('fusd') would otherwise silently degrade every op to
        # the scatter path AND evade the fast-path fallback warning
        raise ValueError(
            f"Architecture.aggregation_backend {backend!r} is not one of "
            f"{KNOWN_BACKENDS}")
    # scoped to this run, so the config's choice can never masquerade as a
    # user-set knob for a later run in the same process (HPO loops,
    # notebooks)
    with backend_scope(backend, override=False):
        return _run_training_dict(config, logs_dir, seed)


def _run_training_dict(config: dict, logs_dir: str, seed: int):
    # Multi-host bootstrap happens HERE, not in user glue: under mpirun/srun
    # (OMPI_COMM_WORLD_*/SLURM_*/JAX_NUM_PROCESSES env) this initializes
    # jax.distributed; single-process runs and already-initialized runtimes
    # pass straight through (parity: reference setup_ddp is called inside
    # its run_training, hydragnn/run_training.py:77).
    from hydragnn_tpu.parallel.mesh import setup_distributed
    from hydragnn_tpu.utils.runtime import setup_compile_cache

    setup_distributed()
    setup_compile_cache()

    from hydragnn_tpu.parallel.comm import num_processes, process_index

    world_size, rank = num_processes(), process_index()

    verbosity = config.get("Verbosity", {}).get("level", 0)
    train_loader, val_loader, test_loader, config = dataset_loading_and_splitting(
        config, rank=rank, world_size=world_size, seed=seed)

    log_name = get_log_name_config(config)
    setup_log(log_name, logs_dir)
    save_config(config, log_name, logs_dir)

    cfg = ModelConfig.from_config(config["NeuralNetwork"])
    model = create_model(cfg)

    # the CONFIG-DECLARED ZeRO stage (env=False: no HYDRAGNN_ZERO overlay)
    # is resolved HERE so select_optimizer can refuse non-elementwise
    # optimizers at config time; an env-FORCED stage instead reaches the
    # trainer's warn-and-disable fallback (docs/SCALING.md LAMB caveat) —
    # a fleet-wide HYDRAGNN_ZERO=1 must not kill existing LAMB configs
    from hydragnn_tpu.parallel.zero import zero_stage_from_training

    opt_spec = select_optimizer(
        config["NeuralNetwork"]["Training"]["Optimizer"],
        zero_stage=zero_stage_from_training(
            config["NeuralNetwork"]["Training"], env=False))

    example = next(iter(train_loader))
    state = create_train_state(model, example, opt_spec, seed=seed)

    # warm start (reference load_existing_model_config, utils/model.py:81-84).
    # Restore preference order: (1) a resume bundle from a preempted /
    # walltime-stopped run — full train state PLUS epoch index,
    # step-within-epoch and scheduler/early-stop state, so the run
    # continues mid-epoch bit-identically (resilience/resume.py); (2) an
    # orbax full-state checkpoint (step counter + opt state included);
    # (3) the best-model pickle.
    training = config["NeuralNetwork"]["Training"]
    resume_meta = None
    consumed_resume_dir = None
    if training.get("continue", 0):
        from hydragnn_tpu.resilience import load_resume_bundle, resume_dir
        from hydragnn_tpu.train.trainer import load_state
        from hydragnn_tpu.utils.checkpoint import latest_step, restore_checkpoint

        start_from = training.get("startfrom", log_name)
        rdir = resume_dir(logs_dir, start_from)
        bundle = load_resume_bundle(state, rdir)
        if bundle is not None:
            state, resume_meta = bundle
            consumed_resume_dir = rdir
        else:
            orbax_dir = os.path.join(logs_dir, start_from, "orbax")
            if latest_step(orbax_dir) is not None:
                state = restore_checkpoint(state, orbax_dir)
            else:
                state = load_state(state, start_from, logs_dir)

    writer = None
    if rank == 0:
        try:
            from torch.utils.tensorboard import SummaryWriter

            writer = SummaryWriter(os.path.join(logs_dir, log_name))
        except Exception as e:  # torch optional; scalars just won't land
            print(f"TensorBoard disabled ({e!r:.120}); epoch scalars "
                  "will not be written")
            writer = None

    # unified telemetry: config's Telemetry section (finalize() wrote the
    # defaults) overlaid by env knobs (HYDRAGNN_TELEMETRY=1 enables the
    # per-step JSONL event log; see docs/TELEMETRY.md)
    from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig

    telemetry = MetricsLogger(
        TelemetryConfig.from_section(config.get("Telemetry")),
        run_name=log_name,
        out_dir=os.path.join(logs_dir, log_name, "telemetry"),
        rank=rank,
        world_size=world_size,
    )

    state, history = train_validate_test(
        model,
        cfg,
        state,
        opt_spec,
        train_loader,
        val_loader,
        test_loader,
        config["NeuralNetwork"],
        log_name,
        verbosity,
        writer=writer,
        rank=rank,
        world_size=world_size,
        logs_dir=logs_dir,
        profile_config=config.get("Profile"),
        telemetry=telemetry,
        resume_meta=resume_meta,
    )

    # the consumed bundle is cleared only after a NORMAL completion — if
    # this run was itself preempted, the trainer wrote a fresh bundle
    # (possibly into the same directory) that the next `continue` needs
    if consumed_resume_dir and not history.get("preempted"):
        from hydragnn_tpu.resilience import clear_resume_bundle

        clear_resume_bundle(consumed_resume_dir, rank=rank)

    save_state(state, log_name, logs_dir, rank=rank)
    tr.print_timers(verbosity)
    if writer is not None:
        writer.close()
    return state, history, config
