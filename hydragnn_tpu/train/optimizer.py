"""Optimizer factory (parity: reference hydragnn/utils/optimizer.py:12-113).

All seven torch optimizers plus LAMB (the reference's DeepSpeed FusedLAMB)
mapped onto optax, wrapped in ``optax.inject_hyperparams`` so the learning
rate lives in the optimizer state and host-side schedulers (ReduceLROnPlateau)
can rewrite it between steps without retracing the jit'd train step.

The reference's ZeRO-1 ``ZeroRedundancyOptimizer`` wrapping is a sharding
choice here, not a different optimizer: when ``use_zero_redundancy`` is set,
the returned spec asks the parallel layer to shard optimizer state along the
data axis (see hydragnn_tpu/parallel/mesh.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import optax


@dataclasses.dataclass(frozen=True)
class OptimizerSpec:
    tx: optax.GradientTransformation
    learning_rate: float
    use_zero_redundancy: bool = False
    # config name of the optimizer ("" for hand-built specs) — the ZeRO
    # layer needs it to refuse non-elementwise optimizers, whose per-tensor
    # statistics (LAMB's trust ratio) would silently change under slicing
    name: str = ""


_FACTORIES = {
    "SGD": lambda lr: optax.inject_hyperparams(optax.sgd)(learning_rate=lr),
    "Adam": lambda lr: optax.inject_hyperparams(optax.adam)(learning_rate=lr),
    "Adadelta": lambda lr: optax.inject_hyperparams(optax.adadelta)(
        learning_rate=lr),
    "Adagrad": lambda lr: optax.inject_hyperparams(optax.adagrad)(
        learning_rate=lr),
    "Adamax": lambda lr: optax.inject_hyperparams(optax.adamax)(
        learning_rate=lr),
    "AdamW": lambda lr: optax.inject_hyperparams(optax.adamw)(learning_rate=lr),
    "RMSprop": lambda lr: optax.inject_hyperparams(optax.rmsprop)(
        learning_rate=lr),
    # DeepSpeed FusedLAMB parity (reference optimizer.py:31-40)
    "FusedLAMB": lambda lr: optax.inject_hyperparams(optax.lamb)(
        learning_rate=lr),
    "LAMB": lambda lr: optax.inject_hyperparams(optax.lamb)(learning_rate=lr),
}


def select_optimizer(opt_config: Dict[str, Any],
                     zero_stage: int = 0) -> OptimizerSpec:
    """Build from the Training.Optimizer config section.

    ``zero_stage`` is the run's CONFIG-DECLARED ZeRO stage
    (``zero_stage_from_training(training, env=False)`` — no HYDRAGNN_ZERO
    overlay): combining it — or the legacy ``use_zero_redundancy`` flag —
    with a non-elementwise optimizer raises here, at config time, instead
    of silently training with a trust ratio computed per SLICE rather
    than per tensor.  An env-FORCED stage over a LAMB config instead hits
    the trainer's warn-and-disable fallback (docs/SCALING.md)."""
    from hydragnn_tpu.parallel.zero import NON_ELEMENTWISE_OPTIMIZERS

    opt_type = opt_config.get("type", "AdamW")
    lr = float(opt_config.get("learning_rate", 1e-3))
    if opt_type not in _FACTORIES:
        raise NameError(f"The string {opt_type} does not name a valid optimizer")
    use_zero = bool(opt_config.get("use_zero_redundancy", False))
    if (use_zero or int(zero_stage) > 0) \
            and opt_type in NON_ELEMENTWISE_OPTIMIZERS:
        raise ValueError(
            f"ZeRO sharding is incompatible with {opt_type}: its per-tensor "
            "trust ratio changes under slice partitioning (see "
            "parallel/zero.py).  Use an elementwise optimizer (Adam/AdamW/"
            "SGD/...) or set zero_stage=0 / use_zero_redundancy=false.")
    return OptimizerSpec(
        tx=_FACTORIES[opt_type](lr),
        learning_rate=lr,
        use_zero_redundancy=use_zero,
        name=str(opt_type),
    )


def set_learning_rate(opt_state, lr: float):
    """Functionally rewrite the injected learning rate in an optimizer state.

    The new leaf keeps the old one's placement: a state replicated over a
    mesh must stay so, or the jitted mesh step sees another input sharding
    and compiles a second time in the middle of the run (11 s on the
    four-chip cell the first time the plateau scheduler fired inside a
    window; PERF.md, PR 27)."""
    import jax
    import jax.numpy as jnp

    hp = dict(opt_state.hyperparams)
    old = jnp.asarray(hp["learning_rate"])
    new = jnp.asarray(lr, dtype=old.dtype)
    if isinstance(old, jax.Array) and old.committed:
        new = jax.device_put(new, old.sharding)
    hp["learning_rate"] = new
    return opt_state._replace(hyperparams=hp)


def get_learning_rate(opt_state) -> float:
    return float(opt_state.hyperparams["learning_rate"])
