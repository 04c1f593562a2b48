"""JSON-config-driven prediction entry point.

Parity: reference hydragnn/run_prediction.py:28-83 — rebuild data + model,
load the checkpoint saved by run_training, evaluate the test split, optionally
denormalize, and return (error, per-task error, true values, predictions).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict

from hydragnn_tpu.config.config import get_log_name_config
from hydragnn_tpu.data.load_data import dataset_loading_and_splitting
from hydragnn_tpu.models.base import ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.serve.engine import load_inference_state
from hydragnn_tpu.train.trainer import make_eval_step, test


@functools.singledispatch
def run_prediction(config, **kwargs):
    raise TypeError("Input must be filename string or configuration dictionary.")


@run_prediction.register
def _(config_file: str, **kwargs):
    with open(config_file, "r") as f:
        config = json.load(f)
    return run_prediction(config, **kwargs)


@run_prediction.register
def _(config: dict, logs_dir: str = "./logs/", seed: int = 0):
    os.environ.setdefault("SERIALIZED_DATA_PATH", os.getcwd())

    # same launcher-env bootstrap as run_training (no-op when already
    # initialized or single-process)
    from hydragnn_tpu.parallel.mesh import setup_distributed
    from hydragnn_tpu.utils.runtime import setup_compile_cache

    setup_distributed()
    setup_compile_cache()

    from hydragnn_tpu.parallel.comm import num_processes, process_index
    import jax

    world_size, rank = num_processes(), process_index()

    train_loader, val_loader, test_loader, config = dataset_loading_and_splitting(
        config, rank=rank, world_size=world_size, seed=seed)

    cfg = ModelConfig.from_config(config["NeuralNetwork"])
    model = create_model(cfg)
    # inference-only restore: params + batch_stats straight from the
    # checkpoint — no optimizer init, no throwaway full train state
    # (shared with the serving engine, hydragnn_tpu/serve/engine.py)
    state = load_inference_state(config, logs_dir)

    eval_step = jax.jit(make_eval_step(model, cfg))
    error, tasks_error, true_values, predicted_values = test(
        eval_step, state, test_loader, cfg.num_heads,
        world_size=world_size, output_types=cfg.output_type,
        classify=cfg.loss_fn == "softmax_xent")

    if config["NeuralNetwork"]["Variables_of_interest"].get(
            "denormalize_output"):
        from hydragnn_tpu.postprocess.postprocess import output_denormalize

        true_values, predicted_values = output_denormalize(
            config["NeuralNetwork"]["Variables_of_interest"]["y_minmax"],
            true_values,
            predicted_values,
        )

    viz = config.get("Visualization", {})
    if viz.get("create_plots") and rank == 0:
        from hydragnn_tpu.postprocess.visualizer import Visualizer

        log_name = get_log_name_config(config)

        var = config["NeuralNetwork"]["Variables_of_interest"]
        names = var.get("output_names",
                        [f"head{i}" for i in range(cfg.num_heads)])
        v = Visualizer(log_name, num_heads=cfg.num_heads,
                       head_dims=cfg.output_dim, logs_dir=logs_dir)
        v.create_scatter_plots(true_values, predicted_values, names)
        v.create_plot_global(true_values, predicted_values, names)
        for ih in range(cfg.num_heads):
            if int(cfg.output_dim[ih]) > 1:
                v.create_parity_plot_vector(
                    names[ih], true_values[ih], predicted_values[ih],
                    int(cfg.output_dim[ih]))
            else:
                v.create_parity_plot_and_error_histogram_scalar(
                    names[ih], true_values[ih], predicted_values[ih])

    return error, tasks_error, true_values, predicted_values
