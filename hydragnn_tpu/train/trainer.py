"""Training loop: jit'd step functions + host-side epoch driver.

TPU-native redesign of the reference train loop
(reference hydragnn/train/train_validate_test.py:53-664):

  - the hot path is ONE jit-compiled ``train_step`` (forward, weighted
    multi-task loss, optional energy-gradient force self-consistency term via
    ``jax.grad`` w.r.t. positions, backward, optimizer update) over padded
    static-shape batches — no per-batch head-index bookkeeping, no Python in
    the step;
  - data parallelism: batches arrive sharded along the mesh's data axis and
    gradients are averaged by XLA collectives inserted under jit (DDP parity,
    see hydragnn_tpu/parallel/mesh.py);
  - host-side control: ReduceLROnPlateau (factor 0.5 / patience 5 / min_lr
    1e-5, parity with reference run_training.py:94-96), EarlyStopping
    (utils/model.py:173-188), best-val Checkpoint with warmup
    (utils/model.py:191-224), TensorBoard scalars, SLURM time-based stop.
"""

from __future__ import annotations

import os
import pickle
import time

from hydragnn_tpu.utils.env import env_flag, env_int
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import Base, ModelConfig, multihead_loss
from hydragnn_tpu.telemetry import counters
from hydragnn_tpu.train.optimizer import (
    OptimizerSpec,
    get_learning_rate,
    select_optimizer,
    set_learning_rate,
)
from hydragnn_tpu.utils import tracer as tr
from hydragnn_tpu.utils.scope import phase  # noqa: F401 (parallel/ imports it from here)


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


@tr.profile("setup.init_state")
def create_train_state(
    model: Base,
    example_batch: GraphBatch,
    opt_spec: OptimizerSpec,
    seed: int = 0,
) -> TrainState:
    init = lambda key, drop, batch: model.init(  # noqa: E731
        {"params": key, "dropout": drop}, batch, train=False)
    if getattr(model, "jit_init", False):
        # a stack whose forward is far too large to run op by op just to
        # shape its parameters (models/laguna.py): under jit the forward
        # is dead code and only the initialisers are compiled.  The keys
        # are arguments: built inside, the seed would be a constant of the
        # program, and every seed would compile its own
        init = jax.jit(init)
    variables = init(jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 1),
                     example_batch)
    params = variables["params"]
    if getattr(model, "cfg", None) is not None and model.cfg.initial_bias is not None:
        from hydragnn_tpu.models.base import set_initial_bias

        params = set_initial_bias(params, model.cfg)
    batch_stats = variables.get("batch_stats", {})
    opt_state = opt_spec.tx.init(params)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
    )


def _force_head_indices(output_names: Optional[Sequence[str]]) -> Tuple[int, int]:
    """(energy_head, forces_head) or (-1, -1).  Parity with the reference's
    name-based detection (train_validate_test.py:433-438)."""
    if not output_names:
        return -1, -1
    e = [i for i, n in enumerate(output_names) if n == "total_energy"]
    f = [i for i, n in enumerate(output_names) if n == "atomic_forces"]
    assert len(e) <= 1, "multiple outputs are called total_energy"
    assert len(f) <= 1, "multiple outputs are called atomic_forces"
    if e and f:
        return e[0], f[0]
    return -1, -1


def _loss_and_metrics(
    model: Base,
    cfg: ModelConfig,
    params,
    batch_stats,
    g: GraphBatch,
    train: bool,
    energy_head: int = -1,
    forces_head: int = -1,
    dropout_rng: Optional[jax.Array] = None,
    dtype_policy: str = "f32",
):
    """Forward + weighted loss (+ self-consistency term); returns
    (loss, (per_head, new_batch_stats, outputs)).

    Mixed precision (``Architecture.mixed_precision`` -> cfg.compute_dtype
    "bfloat16", or the training policy ``dtype_policy="bf16"`` from
    ``Training.train_dtype_policy`` / HYDRAGNN_TRAIN_DTYPE — see
    docs/PERF.md PR-15): params and node/edge FEATURES are cast to bf16
    at THIS boundary — one choke point instead of threading dtype through
    every layer.  Deliberately kept f32: positions (bf16's 8-bit mantissa
    would quantize interatomic distances by ~0.1 A at catalyst-cell
    coordinate magnitudes, corrupting RBFs and the dE/dpos force term),
    the running batch statistics (an EMA accumulated through bf16 loses
    late-training drifts), the loss, and the gradients (transpose of the
    cast accumulates in f32).  Anything the f32 geometry touches promotes
    back to f32; the feature stack stays bf16.  Under the training policy
    the MASTER params (state.params), the optimizer state, and the loss /
    gradient accumulators all stay f32 — only this forward/backward
    computes in bf16.  ``dtype_policy`` is a Python-level branch: the
    default "f32" leaves the traced program byte-identical to a
    pre-policy build."""
    compute_dtype = (jnp.bfloat16 if (getattr(cfg, "compute_dtype", "float32")
                     == "bfloat16" or dtype_policy == "bf16") else None)
    if not getattr(model, "casts_at_boundary", True):
        # a stack that casts for itself (models/laguna.py: integer ids in
        # x, a float32 router) reads cfg.compute_dtype on its own
        compute_dtype = None
    if dtype_policy == "int8_edge":
        # int8 edge-MLP pilot: fake-quantize the edge-MLP kernels (int8
        # round-trip, straight-through grad) at this one boundary — the
        # rest of the step stays f32, master params/optimizer untouched
        from hydragnn_tpu.quant import fake_quant_edge_params

        params = fake_quant_edge_params(params)

    def _cast(tree, dtype):
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    variables = {"params": params, "batch_stats": batch_stats}
    if compute_dtype is not None:
        variables = {"params": _cast(params, compute_dtype),
                     "batch_stats": batch_stats}
    rngs = {"dropout": dropout_rng} if dropout_rng is not None else None

    def apply_fn(gg):
        if compute_dtype is not None:
            gg = gg.replace(
                x=gg.x.astype(compute_dtype),
                edge_attr=(None if gg.edge_attr is None
                           else gg.edge_attr.astype(compute_dtype)))
        if train:
            out, mutated = model.apply(
                variables, gg, train=True, mutable=["batch_stats"], rngs=rngs)
            stats = mutated.get("batch_stats", batch_stats)
        else:
            out, stats = model.apply(variables, gg, train=False), batch_stats
        if compute_dtype is not None:
            out = [o.astype(jnp.float32) for o in out]
            stats = jax.tree.map(
                lambda s, o: s.astype(o.dtype), stats, batch_stats)
        return out, stats

    if energy_head >= 0 and forces_head >= 0:
        # Energy-gradient force self-consistency (reference
        # train_validate_test.py:478-488): forces are the negative gradient,
        # so the mismatch is |dE/dpos * scale + F_label| summed over real
        # nodes.  dE/dpos comes from the SAME forward that produces the head
        # outputs (one forward + one extra backward, matching the reference's
        # create_graph autograd.grad on the live graph) — not a second apply.

        def energy_of(pos):
            out, stats = apply_fn(g.replace(pos=pos))
            e = jnp.sum(out[energy_head] * g.graph_mask[:, None])
            return e, (out, stats)

        (_, (outputs, new_stats)), grads_energy = jax.value_and_grad(
            energy_of, has_aux=True)(g.pos)  # grads: [N, 3]
        total, per_head = multihead_loss(cfg, outputs, g)
        scale = g.extras.get("grad_energy_post_scaling_factor")
        if scale is not None:
            if scale.ndim == 1:
                scale = scale[:, None]
            grads_energy = grads_energy * scale
        f_label = g.labels[forces_head]
        mism = jnp.abs(
            grads_energy.reshape(f_label.shape) + f_label
        ) * g.node_mask[:, None]
        total = total + jnp.sum(mism)
    else:
        outputs, new_stats = apply_fn(g)
        total, per_head = multihead_loss(cfg, outputs, g)

    return total, (per_head, new_stats, outputs)


def tree_l2_norm(tree) -> jax.Array:
    """Global L2 norm of a pytree's leaves, accumulated in f32 (the in-jit
    grad/param/update norm metric — a tree-wide reduction is noise next to
    the step's matmuls, and under scan-chunking it rides the same
    executable, so it's effectively free)."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def step_telemetry_metrics(g: GraphBatch, grads, new_params,
                           updates) -> Dict[str, jax.Array]:
    """The in-jit telemetry extension of the step ``metrics`` dict: global
    grad/param/update norms plus real node/edge counts (the numerators of
    the host-side padding-waste accounting; the denominators are the static
    padded shapes the host already knows)."""
    return {
        "grad_norm": tree_l2_norm(grads),
        "param_norm": tree_l2_norm(new_params),
        "update_norm": tree_l2_norm(updates),
        "nodes_real": jnp.sum(g.node_mask),
        "edges_real": jnp.sum(g.edge_mask),
    }


def model_counters(batch_stats) -> Dict[str, jax.Array]:
    """What the model counted in this step for the step records: the
    top-level scalars of ``batch_stats`` that telemetry/counters.py names
    (a stack keeps them there by its ``keep``); {} for a stack that counts
    nothing."""
    return {k: v for k, v in batch_stats.items()
            if counters.rule(k) and getattr(v, "ndim", None) == 0}


def make_train_step(
    model: Base,
    cfg: ModelConfig,
    opt_spec: OptimizerSpec,
    output_names: Optional[Sequence[str]] = None,
    telemetry_metrics: bool = False,
    nonfinite_guard: bool = False,
    dtype_policy: str = "f32",
) -> Callable[[TrainState, GraphBatch], Tuple[TrainState, Dict[str, jax.Array]]]:
    """``telemetry_metrics=True`` adds the in-jit norm/count extension; the
    trainer passes the MetricsLogger's enable state.  Default OFF so direct
    builders (bench.py, tools/) time/cost-model the exact program a
    non-telemetry production run executes.

    ``nonfinite_guard=True`` (resilience/guards.py) checks loss + gradients
    for NaN/Inf inside the jit and suppresses the whole update (old params,
    old opt state, old batch stats) on a bad step, adding a ``skipped``
    metric.  Default OFF: the guard-off program is byte-identical to a
    pre-guard build.

    ``dtype_policy="bf16"`` runs the forward/backward in bf16 with f32
    master params, optimizer state, and accumulators (see
    _loss_and_metrics); the default "f32" is byte-identical to a
    pre-policy build."""
    energy_head, forces_head = _force_head_indices(output_names)

    def train_step(state: TrainState, g: GraphBatch):
        dropout_rng = jax.random.fold_in(jax.random.PRNGKey(0xD0), state.step)

        def loss_fn(params):
            return _loss_and_metrics(
                model, cfg, params, state.batch_stats, g, True,
                energy_head, forces_head, dropout_rng,
                dtype_policy=dtype_policy)

        with phase("step.loss"):
            (loss, (per_head, new_stats, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
        with phase("step.optimizer"):
            updates, new_opt_state = opt_spec.tx.update(
                grads, state.opt_state, state.params)
            from hydragnn_tpu.models.base import encoder_freeze_mask

            updates = encoder_freeze_mask(updates, cfg.freeze_conv)
            import optax

            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
        )
        metrics = {
            "loss": loss,
            "num_graphs": g.n_real_graphs,
            **{f"task_{i}": t for i, t in enumerate(per_head)},
        }
        if telemetry_metrics:
            with phase("step.metrics"):
                metrics.update(
                    step_telemetry_metrics(g, grads, new_params, updates))
                metrics.update(model_counters(new_stats))
        if nonfinite_guard:
            from hydragnn_tpu.resilience.guards import (
                apply_step_guard,
                nonfinite_flag,
            )

            with phase("step.guard"):
                bad = nonfinite_flag(loss, grads)
                new_state, metrics = apply_step_guard(
                    bad, state, new_state, metrics)
        return new_state, metrics

    return train_step


# the trainer's own metric keys that are COUNTS over the dispatch (summed
# across the K scanned steps; "skipped" counts guard-suppressed steps
# within the dispatch).  A model counter merges by its rule in
# telemetry/counters.py; every other scalar as a graph-weighted mean
_COUNT_METRIC_KEYS = ("num_graphs", "nodes_real", "edges_real", "skipped")


def merge_scanned_metrics(ms):
    """Graph-weighted merge of per-step metric stacks [K] from a scanned
    multi-step train step — same epoch-accumulation semantics as K separate
    dispatches (one definition shared by the local and mesh scan paths).
    Counts (graphs/nodes/edges consumed) sum over the K steps; losses and
    the telemetry norms merge graph-weighted; a number of the dispatch's
    shape, the same on each of the K steps, is handed on as it is."""
    ng = ms["num_graphs"]
    total = jnp.maximum(jnp.sum(ng), 1.0)
    merged = {}
    for k, v in ms.items():
        how = counters.SUM if k in _COUNT_METRIC_KEYS else counters.rule(k)
        if how == counters.SUM:
            merged[k] = jnp.sum(v)
        elif how == counters.SAME:
            merged[k] = v[0]
        else:
            merged[k] = jnp.sum(v * ng) / total
    return merged


def _base_loader(loader, attr: str):
    """The innermost loader of a stack of wrappers that has ``attr``."""
    while loader is not None and not hasattr(loader, attr):
        loader = getattr(loader, "loader", None)
    return loader


def _align_bucket_group(loader, factor: int, fit: bool = False):
    """Raise the underlying GraphDataLoader's ``bucket_group`` to a multiple
    of ``factor`` so batches later stacked together (DeviceStackLoader over
    local devices and/or scan steps) share one bucket PadSpec — np.stack
    over mismatched bucket shapes would raise mid-epoch.

    ``fit`` says the stacked loader is about to be staged on the device
    (``ResidentDeviceLoader``: one epoch's plan, replayed for the whole
    run).  The loader is then told to fit each group's PadSpec to the
    groups of that plan (``GraphDataLoader.fit_to_groups``) instead of
    looking it up in the ladder, whose rungs were placed for single
    batches: the largest of K batches lies above the single-batch q99 with
    probability 1 - 0.99^K and falls through to the worst-case rung.  A
    loader that is not staged keeps the ladder: its shapes change every
    epoch and a fitted one would compile inside the run.  Returns the
    loader whose groups are fitted, or None."""
    if factor <= 1:
        return None
    obj = _base_loader(loader, "bucket_group")
    if obj is None:
        return None
    bg = max(1, int(obj.bucket_group))
    obj.bucket_group = factor * (-(-bg // factor))
    if fit and hasattr(obj, "fit_to_groups") and obj.fit_to_groups():
        return obj
    return None


def _auto_pipeline(train_loader, val_loader, test_loader, stack_factor=1):
    """Default-on fast-path selection for single-host runs (round-4
    VERDICT item 7): pick scan chunking K and device residency
    automatically when the explicit env knobs are unset, so the
    out-of-the-box `run_training` gets the measured-fast pipeline instead
    of requiring HYDRAGNN_STEPS_PER_DISPATCH/RESIDENT_DATASET tuning.

    Returns (auto_k, auto_resident).  Conservative by design:
    - only when every loader reports a length (peeking one batch costs one
      collate) and the run is single-process;
    - scan K only when the epoch has >= 8 dispatch units — a unit is
      ``stack_factor`` raw batches when the mesh path device-stacks them
      first — so K-stacking (drop_last) can never leave a zero-step epoch
      and trims at most a quarter of it (shuffling rotates what's dropped);
    - residency only for >= 32 batches (ResidentDeviceLoader freezes batch
      COMPOSITION after epoch 0 — harmless at scale, load-bearing for tiny
      CI runs) and when the staged train+val+test corpus fits the HBM
      budget (HYDRAGNN_RESIDENT_BUDGET_MB, default 6144).
    HYDRAGNN_AUTO_PIPELINE=0 disables both.
    """
    if os.environ.get("HYDRAGNN_AUTO_PIPELINE", "1") in ("", "0", "false",
                                                         "False"):
        return 1, False
    if jax.process_count() > 1:
        return 1, False
    try:
        n_train = len(train_loader)
        n_total = n_train + len(val_loader) + len(test_loader)
    except TypeError:
        return 1, False
    n_units = n_train // max(1, stack_factor)
    if n_units < 8:
        return 1, False
    # largest K <= 32 whose drop_last waste is <= 1/8 of the epoch
    auto_k = 1
    for k in range(min(32, n_units), 0, -1):
        if (n_units % k) * 8 <= n_units:
            auto_k = k
            break
    try:
        first = next(iter(train_loader))
    except StopIteration:
        return 1, False
    batch_bytes = sum(
        getattr(l, "nbytes", 0) for l in jax.tree_util.tree_leaves(first))
    # bucketed loaders: the peeked batch may come from the SMALLEST
    # bucket; scale to the worst-case spec so residency never turns on
    # from an underestimate and OOMs HBM during staging
    base = _base_loader(train_loader, "pad_specs")
    if base is not None and len(base.pad_specs) > 1:
        lo, hi = base.pad_specs[0], base.pad_specs[-1]
        batch_bytes *= max(
            hi.num_nodes / max(lo.num_nodes, 1),
            hi.num_edges / max(lo.num_edges, 1))
    budget = env_int("HYDRAGNN_RESIDENT_BUDGET_MB", 6144) * (1 << 20)
    auto_resident = (n_train >= 32 and batch_bytes * n_total <= budget)
    return auto_k, auto_resident


def make_scan_train_step(
    model: Base,
    cfg: ModelConfig,
    opt_spec: OptimizerSpec,
    output_names: Optional[Sequence[str]] = None,
    steps: int = 1,
    telemetry_metrics: bool = False,
    nonfinite_guard: bool = False,
    dtype_policy: str = "f32",
):
    """K sequential train steps inside one executable via ``lax.scan``.

    The input batch carries a leading [K, ...] axis of consecutive
    same-PadSpec batches (DeviceStackLoader).  Metrics come back
    graph-weighted over the K steps, so epoch accumulation in
    :func:`_run_epoch` sees the same semantics as K separate dispatches.
    Numerically identical to K sequential steps — only the host dispatch
    and argument-ingest latency are amortized (docs/PERF.md).
    """
    from jax import lax

    base = make_train_step(model, cfg, opt_spec, output_names,
                           telemetry_metrics=telemetry_metrics,
                           nonfinite_guard=nonfinite_guard,
                           dtype_policy=dtype_policy)

    def scan_step(state: TrainState, g: GraphBatch):
        state, ms = lax.scan(base, state, g, length=steps)
        return state, merge_scanned_metrics(ms)

    return scan_step


def make_eval_step(
    model: Base, cfg: ModelConfig, outputs: bool = True
) -> Callable[[TrainState, GraphBatch], Dict[str, Any]]:
    """``outputs=False``: the losses alone, for a caller that reads nothing
    else (the epoch loop's val and test passes).  A head's output can be
    large (a language model's logits: 1.5 GB a head and batch), and a
    returned array lives until its metrics dict is dropped, beside the
    next dispatch's temporaries."""
    def eval_step(state: TrainState, g: GraphBatch):
        with phase("step.eval"):
            loss, (per_head, _, outs) = _loss_and_metrics(
                model, cfg, state.params, state.batch_stats, g, False)
        metrics = {
            "loss": loss,
            "num_graphs": g.n_real_graphs,
            "per_head": per_head,
        }
        if outputs:
            metrics["outputs"] = outs
        return metrics

    return eval_step


# ---------------------------------------------------------------------------
# Host-side control objects (parity: reference hydragnn/utils/model.py)
# ---------------------------------------------------------------------------


class ReduceLROnPlateau:
    """min-mode plateau scheduler (reference run_training.py:94-96 wiring of
    torch's scheduler: factor 0.5, patience 5, min_lr 1e-5)."""

    def __init__(self, factor: float = 0.5, patience: int = 5,
                 min_lr: float = 1e-5, threshold: float = 1e-4):
        self.factor = factor
        self.patience = patience
        self.min_lr = min_lr
        self.threshold = threshold
        self.best = float("inf")
        self.bad_epochs = 0

    def step(self, metric: float, lr: float) -> float:
        if metric < self.best * (1.0 - self.threshold):
            self.best = metric
            self.bad_epochs = 0
        else:
            self.bad_epochs += 1
        if self.bad_epochs > self.patience:
            self.bad_epochs = 0
            return max(lr * self.factor, self.min_lr)
        return lr

    def state_dict(self) -> Dict[str, float]:
        return {"best": self.best, "bad_epochs": self.bad_epochs}

    def load_state_dict(self, sd: Dict[str, float]) -> None:
        self.best = float(sd["best"])
        self.bad_epochs = int(sd["bad_epochs"])


class EarlyStopping:
    """Patience on validation loss (reference utils/model.py:173-188)."""

    def __init__(self, patience: int = 10, min_delta: float = 0.0):
        self.patience = patience
        self.min_delta = min_delta
        self.count = 0
        self.min_loss = float("inf")
        self.early_stop = False

    def __call__(self, val_loss: float) -> bool:
        if val_loss < self.min_loss:
            self.min_loss = val_loss
            self.count = 0
        elif val_loss > self.min_loss + self.min_delta:
            self.count += 1
            if self.count >= self.patience:
                self.early_stop = True
        return self.early_stop

    def state_dict(self) -> Dict[str, float]:
        return {"count": self.count, "min_loss": self.min_loss,
                "early_stop": self.early_stop}

    def load_state_dict(self, sd: Dict[str, float]) -> None:
        self.count = int(sd["count"])
        self.min_loss = float(sd["min_loss"])
        self.early_stop = bool(sd["early_stop"])


class CheckpointTracker:
    """Best-metric checkpointing with warmup (reference utils/model.py:191-224).

    Runs on EVERY rank: the metric is globally reduced, so the save decision
    is identical everywhere, and the transform may be a cross-process
    collective (ZeRO consolidation all_gather) that would deadlock behind a
    rank-0 gate.  Only rank 0 actually writes the file."""

    def __init__(self, name: str, warmup: int = 0, path: str = "./logs/",
                 rank: int = 0):
        self.name = name
        self.warmup = warmup
        self.path = path
        self.rank = rank
        self.count = 0
        self.best = float("inf")
        # e.g. ZeRO opt-state consolidation before serialization (reference
        # consolidate_state_dict before save, utils/model.py:61-62)
        self.transform = lambda s: s

    def __call__(self, state: TrainState, metric: float) -> bool:
        self.count += 1
        if self.count < self.warmup or metric >= self.best:
            return False
        self.best = metric
        with tr.timer("checkpoint.save"):
            save_state(self.transform(state), self.name, self.path,
                       rank=self.rank)
        return True

    def state_dict(self) -> Dict[str, float]:
        return {"count": self.count, "best": self.best}

    def load_state_dict(self, sd: Dict[str, float]) -> None:
        self.count = int(sd["count"])
        self.best = float(sd["best"])


def save_state(state: TrainState, log_name: str, path: str = "./logs/",
               rank: int = 0) -> Optional[str]:
    """Rank-0 single-file checkpoint (reference utils/model.py:58-71 writes
    one .pk with model+optimizer state).  Written atomically (temp file +
    ``os.replace``): this is often the ONLY best-model checkpoint, and a
    crash mid-write must leave the previous good file intact."""
    if rank != 0:
        return None
    d = os.path.join(path, log_name)
    os.makedirs(d, exist_ok=True)
    fname = os.path.join(d, f"{log_name}.pk")
    payload = jax.device_get(
        {
            "step": state.step,
            "params": state.params,
            "batch_stats": state.batch_stats,
            "opt_state": state.opt_state,
        }
    )
    from hydragnn_tpu.resilience.ckpt_io import atomic_write_pickle

    atomic_write_pickle(fname, payload)
    return fname


def load_state(state: TrainState, log_name: str, path: str = "./logs/") -> TrainState:
    """Restore a saved checkpoint into an existing state skeleton."""
    fname = os.path.join(path, log_name, f"{log_name}.pk")
    with open(fname, "rb") as f:
        payload = pickle.load(f)
    return TrainState(
        step=jnp.asarray(payload["step"]),
        params=payload["params"],
        batch_stats=payload["batch_stats"],
        opt_state=payload["opt_state"],
    )


# ---------------------------------------------------------------------------
# Epoch driver
# ---------------------------------------------------------------------------


def _run_epoch(step_fn, state, loader, train: bool, profiler=None,
               steps_per_item: int = 1, telemetry=None, guard=None,
               preempt=None, chaos=None, skip_first: int = 0,
               consumed_base: int = 0):
    # ``consumed_base`` dispatch units were already skipped INSIDE the
    # loader (streaming fast-forward): the resume bundle's items_consumed
    # must still count them, but the iterator never yields them here.
    # Metrics accumulate as DEVICE scalars: no float() in the batch loop, so
    # steps dispatch back-to-back with no device->host sync (the reference
    # accumulates on device and reduces at epoch end,
    # train_validate_test.py:505-508).  No sync here either: the DEVICE
    # accumulator (total, tasks, n) — or None for an empty loader — is
    # returned for the caller to ``device_get`` together with the other
    # phases' (each sync drains the dispatch queue, so train/val/test
    # fetching separately would stall the device three times per epoch);
    # finalize the fetched value with :func:`_epoch_metrics`.
    total = None
    tasks = None
    n = None
    # host regions (utils/tracer): the loader's blocking next() and the
    # step call itself — argument ingest and enqueue, which does NOT wait
    # for the device.  None adds a sync, so the loop is the same program
    # whatever listens (docs/TELEMETRY.md "Tracing")
    wait_region = "train.data_wait" if train else "eval.data_wait"
    # HYDRAGNN_MAX_NUM_BATCH caps TRAIN STEPS per epoch (reference
    # get_nbatch, train_validate_test.py:40-50 — used for weak-scaling
    # measurement).  With scan chunking each loader item carries
    # ``steps_per_item`` steps; dispatches stop before EXCEEDING the cap
    # (floor(nbatch/K) dispatches), so a K>1 run never does more optimizer
    # steps than the K=1 run it's compared against.
    nbatch = int(os.getenv("HYDRAGNN_MAX_NUM_BATCH", "0")) or None
    batches = iter(loader)
    ibatch = -1
    while True:
        tr.start(wait_region)
        try:
            g = next(batches, None)
        finally:
            tr.stop(wait_region)
        if g is None:
            break
        ibatch += 1
        if nbatch is not None and (ibatch + 1) * steps_per_item > nbatch:
            break
        if ibatch < skip_first:
            # mid-run resume: these dispatch units were already executed by
            # the preempted run; set_epoch replayed the deterministic
            # shuffle, so skipping them continues the exact batch stream.
            # Preemption is still polled — a SIGTERM during a long replay
            # must re-save (at the SAME position: everything up to
            # skip_first was consumed by the previous run) instead of
            # burning the grace window.
            if train and preempt is not None and preempt.poll():
                preempt.consumed = consumed_base + skip_first
                break
            continue
        if train:
            if chaos is not None:
                g = chaos.on_train_dispatch(g)
            tr.start("train.dispatch")
            state, metrics = step_fn(state, g)
            tr.stop("train.dispatch")
            if telemetry is not None:
                # zero-sync: device scalars + host timestamp are buffered;
                # the one fetch happens in telemetry.flush_steps at epoch end
                telemetry.on_step(metrics, g)
            if guard is not None:
                # buffers the device `skipped` flag; one device_get every
                # poll_every dispatches — raises NonFiniteTrainingError
                # after max_consecutive bad steps
                guard.on_step(metrics, g)
            n_tasks = sum(1 for k in metrics if k.startswith("task_"))
            per_head = [metrics[f"task_{i}"] for i in range(n_tasks)]
        else:
            tr.start("eval.dispatch")
            metrics = step_fn(state, g)
            tr.stop("eval.dispatch")
            per_head = metrics["per_head"]
        ng = metrics["num_graphs"]
        loss_w = metrics["loss"] * ng
        ph = jnp.stack(per_head) * ng if per_head else jnp.zeros(0)
        if total is None:
            total, tasks, n = loss_w, ph, ng
        else:
            total, tasks, n = total + loss_w, tasks + ph, n + ng
        if profiler is not None:
            profiler.step(steps_per_item)
        if train and preempt is not None:
            if chaos is not None and chaos.preempt_now():
                preempt.request()
            if preempt.poll():
                # stop at the batch boundary: the dispatched step's state is
                # complete; record the step-within-epoch for the bundle
                preempt.consumed = consumed_base + ibatch + 1
                break
    return state, (None if total is None else (total, tasks, n))


def _epoch_metrics(acc):
    """Finalize a fetched (total, tasks, n) accumulator to (loss, tasks)."""
    if acc is None:
        return 0.0, np.zeros(0)
    total, tasks, n = acc
    n = max(float(n), 1.0)
    return float(total) / n, np.asarray(tasks) / n


# bf16-train acceptance bound: relative drift of the step-0 loss and global
# gradient norm vs the f32 step on the SAME (state, batch).  5% is loose
# against bf16's ~0.4% unit roundoff because the drift compounds through
# the conv stack and the backward chain; a model that exceeds it at step 0
# (e.g. a loss balanced on cancellation) would not train faithfully in
# bf16, so the policy falls back to f32.  Module-level so tests can
# monkeypatch the bound to force both verdicts.
_TRAIN_DTYPE_TOL = 0.05


def _train_dtype_gate(model, cfg, state, opt_spec, output_names, batch,
                      policy="bf16"):
    """Golden-replay probe for a non-f32 ``Training.train_dtype_policy``
    ("bf16" or "int8_edge"): run ONE f32 train step and ONE policy train
    step on the same (state, first batch) — un-donated local jits, so
    neither touches the run's real state — and compare loss + grad-norm
    relative drift against :data:`_TRAIN_DTYPE_TOL`.  Returns
    (ok, drift_stats).

    Mirrors serving's golden-batch replay (quant/policy.py): the operator
    asked for a numerics change, so the change must prove itself against
    the f32 reference on real data before the run commits to it.  Costs
    two extra step compilations at step 0; the f32 probe's trace is the
    same program the fallback path would jit anyway."""
    f32_step = jax.jit(make_train_step(model, cfg, opt_spec, output_names,
                                       telemetry_metrics=True))
    bf_step = jax.jit(make_train_step(model, cfg, opt_spec, output_names,
                                      telemetry_metrics=True,
                                      dtype_policy=policy))
    _, m32 = jax.device_get(f32_step(state, batch))
    _, mbf = jax.device_get(bf_step(state, batch))
    ok, stats = True, {}
    for k in ("loss", "grad_norm"):
        ref, got = float(m32[k]), float(mbf[k])
        drift = abs(got - ref) / max(abs(ref), 1e-12)
        stats[k] = drift
        # `not <=` (rather than `>`): a NaN drift must reject too
        if not drift <= _TRAIN_DTYPE_TOL:
            ok = False
    return ok, stats


def train_validate_test(
    model: Base,
    cfg: ModelConfig,
    state: TrainState,
    opt_spec: OptimizerSpec,
    train_loader,
    val_loader,
    test_loader,
    config_nn: Dict[str, Any],
    log_name: str,
    verbosity: int = 0,
    writer=None,
    rank: Optional[int] = None,
    world_size: int = 1,
    logs_dir: str = "./logs/",
    use_mesh_dp: Optional[bool] = None,
    profile_config: Optional[Dict[str, Any]] = None,
    mesh=None,
    telemetry=None,
    resume_meta: Optional[Dict[str, Any]] = None,
) -> Tuple[TrainState, Dict[str, List[float]]]:
    """Epoch loop with LR plateau scheduling, early stopping, checkpointing.

    Parity with reference train_validate_test (train_validate_test.py:53-284):
    per-epoch train/val/test losses, scheduler.step(val), checkpoint(val) with
    warmup, optional early stop, metric reduction across ranks.

    When this process drives more than one accelerator (a TPU host's local
    chips), the loop automatically switches to the data-parallel mesh path:
    device-stacked batches through the shard_map step (DDP parity; see
    hydragnn_tpu/parallel/mesh.py).  ``use_mesh_dp`` forces the choice.
    """
    training = config_nn["Training"]
    num_epoch = int(training["num_epoch"])
    output_names = config_nn["Variables_of_interest"].get("output_names")
    # fault-tolerance knobs (resilience/config.py): read BEFORE the step
    # functions are built — the non-finite guard is a trace-time flag
    from hydragnn_tpu.resilience import Chaos, ResilienceConfig

    res_cfg = ResilienceConfig.from_training(training)
    chaos = Chaos.from_env(training.get("Chaos"))
    # ZeRO sharding request (Training.zero_stage + HYDRAGNN_ZERO env, plus
    # the legacy Optimizer.use_zero_redundancy flag) — resolved before the
    # step builders because the partition is a trace-time choice
    from hydragnn_tpu.parallel.zero import (
        NON_ELEMENTWISE_OPTIMIZERS,
        zero_stage_from_training,
    )

    zero_requested = zero_stage_from_training(training, opt_spec)
    zero_stage = zero_requested
    zero_fallback = None
    # graph sharding request (Training.graph_shard + HYDRAGNN_GRAPH_SHARD*
    # env): one giant graph split across the mesh (docs/SCALING.md §6) —
    # resolved before the step builders because the partition and the halo
    # exchange are trace-time choices
    from hydragnn_tpu.graph.partition import (
        HALO_SUPPORTED_MODELS,
        GraphShardConfig,
    )

    gs_cfg = GraphShardConfig.from_training(training)
    graph_shard = gs_cfg.backend
    if zero_requested and getattr(opt_spec, "name", "") \
            in NON_ELEMENTWISE_OPTIMIZERS:
        # env-forced ZeRO on a LAMB run: warn-and-disable rather than
        # changing numerics (config-declared combinations already raised in
        # select_optimizer)
        import warnings

        warnings.warn(
            f"ZeRO stage {zero_requested} requested but optimizer "
            f"{opt_spec.name} is not elementwise — training REPLICATED "
            "(per-tensor trust ratios would change under slicing)",
            stacklevel=2)
        zero_stage, zero_fallback = 0, "non_elementwise_optimizer"
    # an explicit (ensemble-branch) mesh means other branches run disjoint
    # programs concurrently — global host collectives (telemetry cross-rank
    # reduction) would interleave with theirs and deadlock; remember before
    # ``mesh`` is reassigned below
    explicit_mesh = mesh is not None

    if rank is None:
        # who writes artifacts for this log_name: with an explicit (branch)
        # mesh, the branch's lowest process is its leader — rank 0 within the
        # branch even when global process 0 is in another branch; otherwise
        # the global process index (0 for single-process runs).
        if mesh is not None:
            leader = min(d.process_index for d in mesh.devices.flat)
            rank = 0 if jax.process_index() == leader else 1
        else:
            rank = jax.process_index()

    # unified telemetry (hydragnn_tpu/telemetry): callers (run_training)
    # pass a configured MetricsLogger; direct trainer users get the env-knob
    # construction (HYDRAGNN_TELEMETRY=1 turns on the JSONL event log with
    # no config edit).  Built BEFORE the step functions: its enable state
    # decides whether the jitted steps carry the in-jit norm metrics.
    # Epoch records flow through it unconditionally — that's how the
    # TensorBoard scalars are written (TensorBoardSink).
    from hydragnn_tpu.telemetry import MetricsLogger

    if telemetry is None:
        telemetry = MetricsLogger.from_env(
            run_name=log_name,
            out_dir=os.path.join(logs_dir, log_name, "telemetry"),
            rank=rank, world_size=world_size,
            cross_rank=(not explicit_mesh and world_size > 1))

    n_local_devices = len(jax.local_devices())
    if mesh is not None:
        # an explicit (sub-)mesh may use a SUBSET of this process's
        # devices (ensemble branch, in-process elastic harness): stack as
        # many batches per dispatch as this process contributes to THAT
        # mesh, not as many devices as the process owns — the stacked
        # batch axis must equal the mesh's split extent
        _pidx = jax.process_index()
        n_local_devices = sum(
            1 for d in mesh.devices.flat if d.process_index == _pidx)
    n_proc = jax.process_count()
    if use_mesh_dp is None:
        # multi-process runs MUST take the global-mesh path even with one
        # device per process: the local-jit path would never synchronize
        # gradients and each rank would train a divergent model.  An explicit
        # ``mesh`` (e.g. a HostGroup ensemble-branch mesh) also forces it.
        use_mesh_dp = n_local_devices > 1 or n_proc > 1 or mesh is not None
    # fast-pipeline defaults (scan chunking + device residency) when the
    # explicit knobs are unset — see _auto_pipeline.  The mesh path stacks
    # n_local_devices batches per dispatch unit before any K-stacking.
    auto_k, auto_resident = 1, False
    if ("HYDRAGNN_STEPS_PER_DISPATCH" not in os.environ
            or "HYDRAGNN_RESIDENT_DATASET" not in os.environ):
        auto_k, auto_resident = _auto_pipeline(
            train_loader, val_loader, test_loader,
            stack_factor=n_local_devices if use_mesh_dp else 1)
    resident_on = (env_flag("HYDRAGNN_RESIDENT_DATASET")
                   if "HYDRAGNN_RESIDENT_DATASET" in os.environ
                   else auto_resident)
    # -- streaming data plane (data/stream/, docs/DATA.md) ------------------
    # load_data could not emit health events (no MetricsLogger yet); a
    # recorded fallback reason surfaces here, and an active stream loader
    # forces device residency OFF — caching every collated batch on device
    # would re-materialize the epoch the stream exists to avoid holding.
    from hydragnn_tpu.data.stream.config import (
        pop_fallback,
        pop_open_retries,
    )
    from hydragnn_tpu.data.stream.loader import (
        find_stream_loader,
        try_fast_forward,
    )

    for _ev in pop_open_retries():
        # store-open attempts that failed and were retried (bounded
        # backoff, resilience/ckpt_io.with_retries) before any fallback
        telemetry.health("stream_open_retry", **_ev)
    stream_fb = pop_fallback()
    if stream_fb:
        telemetry.health("stream_fallback", reason=stream_fb)
    stream_base = find_stream_loader(train_loader)
    # the train loader whose dispatch groups are fitted to what they hold
    # (_align_bucket_group), where the run stages it resident
    fit_base = None
    if stream_base is not None:
        resident_on = False
        telemetry.health(
            "stream_open", n_samples=int(len(stream_base.indices)),
            window=int(stream_base.window), order=str(stream_base.order),
            batch_size=int(stream_base.batch_size),
            tail=bool(stream_base.tail_dir))
    # -- training dtype policy (docs/PERF.md PR-15) -------------------------
    # bf16 forward/backward with f32 master params/optimizer/accumulators.
    # Resolved BEFORE the step builders (a trace-time choice, like ZeRO and
    # graph sharding) and gated by a step-0 golden replay: an operator who
    # requested bf16 believes the numerics hold, so a drifting model must
    # fall back LOUDLY to f32 — bit-identical to an unrequested run.
    from hydragnn_tpu.quant import check_train_policy

    train_dtype = check_train_policy(
        str(training.get("train_dtype_policy", "f32") or "f32"))
    env_td = os.environ.get("HYDRAGNN_TRAIN_DTYPE", "").strip().lower()
    if env_td:
        train_dtype = check_train_policy(env_td)
    train_dtype_requested = train_dtype
    if train_dtype != "f32":
        import warnings

        req = train_dtype_requested
        resumed_td = (resume_meta or {}).get("pipeline", {}).get(
            "train_dtype")
        if resumed_td is not None:
            # crash/resume bit-parity: the preempted run's accept/reject
            # verdict is part of its traced program — reuse it verbatim
            # instead of re-probing (a probe on a different first batch
            # could flip the decision mid-run)
            train_dtype = check_train_policy(str(resumed_td))
        elif graph_shard != "off":
            warnings.warn(
                f"train_dtype_policy={req} requested with graph sharding "
                "— the halo/gspmd steps are not policy-threaded; training "
                "f32", stacklevel=2)
            telemetry.health("train_dtype_reject", requested=req,
                             reason="graph_shard")
            train_dtype = "f32"
        else:
            probe = next(iter(train_loader), None)
            if probe is None:
                warnings.warn(
                    f"train_dtype_policy={req} requested but the train "
                    "loader is empty — the acceptance probe cannot run; "
                    "training f32", stacklevel=2)
                telemetry.health("train_dtype_reject", requested=req,
                                 reason="empty_loader")
                train_dtype = "f32"
            else:
                td_ok, td_drift = _train_dtype_gate(
                    model, cfg, state, opt_spec, output_names, probe,
                    policy=req)
                if not td_ok:
                    warnings.warn(
                        f"train_dtype_policy={req} REJECTED by the step-0 "
                        f"golden replay (relative drift {td_drift} vs "
                        f"bound {_TRAIN_DTYPE_TOL}) — training f32",
                        stacklevel=2)
                    telemetry.health(
                        "train_dtype_reject", requested=req,
                        reason="golden_gate",
                        drift_loss=float(td_drift.get("loss", 0.0)),
                        drift_grad_norm=float(
                            td_drift.get("grad_norm", 0.0)),
                        tol=float(_TRAIN_DTYPE_TOL))
                    train_dtype = "f32"
    if use_mesh_dp:
        from hydragnn_tpu.parallel.mesh import (
            DeviceStackLoader,
            GlobalBatchLoader,
            make_dp_eval_step,
            make_dp_train_step,
            make_mesh,
            mesh_process_count,
        )

        if mesh is None:
            n_slices = int(os.environ.get("HYDRAGNN_NUM_SLICES", "0") or 0)
            if n_slices > 1:
                # multi-slice pod: 2-axis (dcn, ici) mesh; DP spans both
                from hydragnn_tpu.parallel.mesh import make_multislice_mesh

                mesh = make_multislice_mesh(num_slices=n_slices)
            else:
                mesh = make_mesh()  # global: every process's devices
        from hydragnn_tpu.parallel.mesh import mesh_dp_axes

        dp_axes = mesh_dp_axes(mesh)
        single_proc = mesh_process_count(mesh) == 1
        # -- graph-sharding gating (docs/SCALING.md §6): resolved BEFORE the
        # ZeRO placement because the gspmd baseline cannot compose with a
        # sharded state (its step is the local jit, no shard_map to slice
        # in), and every fallback must be LOUD — an operator who requested
        # graph sharding believes a giant graph fits
        gs_requested = graph_shard
        gs_fallback = None
        n_shards = int(mesh.devices.size)
        if graph_shard != "off":
            if not single_proc:
                gs_fallback = "multi_process"
            elif graph_shard == "halo" and len(mesh.axis_names) != 1:
                gs_fallback = "multi_axis_mesh"
            elif (graph_shard == "halo"
                    and cfg.model_type not in HALO_SUPPORTED_MODELS):
                gs_fallback = "unsupported_model"
            else:
                e_h, f_h = _force_head_indices(output_names)
                if graph_shard == "halo" and e_h >= 0 and f_h >= 0:
                    gs_fallback = "force_consistency"
            if gs_fallback is not None:
                import warnings

                warnings.warn(
                    f"graph sharding ({graph_shard}) requested but this run "
                    f"cannot use it ({gs_fallback}) — training with the "
                    "plain DP mesh path (the graph must fit one device)",
                    stacklevel=2)
                telemetry.health("graph_shard_fallback",
                                 requested=graph_shard, reason=gs_fallback)
                graph_shard = "off"
        if graph_shard == "gspmd" and zero_stage > 0:
            import warnings

            warnings.warn(
                "ZeRO cannot compose with the gspmd graph-shard baseline "
                "(its step is the local jit — no shard_map to slice the "
                "state in); training with REPLICATED state.  Use the halo "
                "backend for ZeRO + graph sharding.", stacklevel=2)
            zero_stage, zero_fallback = 0, "gspmd_graph_shard"
        # state placement through the ONE resume-composable entry point:
        # stage 0 replicates, stage >= 1 shards optimizer state — and
        # params at stage 2 — along the innermost mesh axis for the whole
        # run (reference ZeroRedundancyOptimizer, optimizer.py:43-103).
        # An elastic resume re-places a consolidated bundle with this
        # same call, so init and resume placement cannot drift apart.
        from hydragnn_tpu.parallel.zero import reshard_state

        state, zero_sh = reshard_state(state, mesh, stage=zero_stage)
        gs_stats = {}
        if graph_shard == "halo":
            # halo graph sharding: ONE graph (batch) split across the mesh —
            # loaders partition each batch into stacked HaloBatches, the
            # steps exchange halo rows (graph/partition.py, docs/SCALING.md
            # §6).  Scan chunking is not composed (the carrier is a
            # different pytree per topology bucket); K stays 1.
            from hydragnn_tpu.graph.partition import ShardedGraphLoader
            from hydragnn_tpu.parallel.mesh import (
                make_halo_eval_step,
                make_halo_train_step,
            )

            if env_int("HYDRAGNN_STEPS_PER_DISPATCH", 1) > 1:
                import warnings

                warnings.warn(
                    "HYDRAGNN_STEPS_PER_DISPATCH > 1 is not composed with "
                    "graph sharding; forcing K=1", stacklevel=2)
            steps_per_dispatch = 1
            hops = gs_cfg.hops or cfg.num_conv_layers
            if hops < cfg.num_conv_layers:
                # a halo shallower than the conv stack silently corrupts
                # boundary rows at the deeper layers — the exact
                # truncated-halo wrong answer graph_shard_halo_max refuses;
                # deeper than the stack is merely wasteful and allowed
                raise ValueError(
                    f"graph_shard_hops={hops} is shallower than the "
                    f"model's {cfg.num_conv_layers} conv layers — boundary "
                    "rows would train on silently wrong neighborhoods; "
                    "set it >= num_conv_layers or leave it 0 (auto)")
            head_types = list(cfg.output_type)
            gs_train = gs_val = gs_test = None
            if stream_base is not None:
                # disk-backed halo feed: shard gathers read straight off the
                # mmap store — the padded whole graph is never materialized
                from hydragnn_tpu.data.stream.halo import sharded_from_stream

                gs_train = sharded_from_stream(
                    train_loader, n_shards, gs_cfg, hops)
                gs_val = sharded_from_stream(
                    val_loader, n_shards, gs_cfg, hops)
                gs_test = sharded_from_stream(
                    test_loader, n_shards, gs_cfg, hops)
            if gs_train and gs_val and gs_test:
                train_loader, val_loader, test_loader = \
                    gs_train, gs_val, gs_test
            else:
                if stream_base is not None:
                    import warnings

                    warnings.warn(
                        "disk-backed halo feed needs batch_size=1 single-"
                        "host streaming loaders; composing the in-memory "
                        "partitioner over the stream instead (still "
                        "windowed, but each batch is padded host-side)",
                        stacklevel=2)
                train_loader = ShardedGraphLoader(
                    train_loader, n_shards, gs_cfg, hops, head_types)
                val_loader = ShardedGraphLoader(
                    val_loader, n_shards, gs_cfg, hops, head_types)
                test_loader = ShardedGraphLoader(
                    test_loader, n_shards, gs_cfg, hops, head_types)
            gs_stats = train_loader.peek_stats()
            train_step = make_halo_train_step(
                model, cfg, opt_spec, mesh, output_names, axis=dp_axes,
                zero_specs=zero_sh, telemetry_metrics=telemetry.enabled,
                nonfinite_guard=res_cfg.nonfinite_guard)
            eval_step = make_halo_eval_step(model, cfg, mesh, axis=dp_axes,
                                            zero=zero_sh)
        elif graph_shard == "gspmd":
            # correctness baseline: committed-sharded batches, GSPMD inserts
            # (full-array) collectives — no memory win, exact numerics
            # (parallel/graph_shard.py docstring)
            from hydragnn_tpu.parallel.graph_shard import (
                GspmdBatchLoader,
                make_gspmd_eval_step,
                make_gspmd_train_step,
            )

            steps_per_dispatch = 1
            train_loader = GspmdBatchLoader(train_loader, mesh)
            val_loader = GspmdBatchLoader(val_loader, mesh)
            test_loader = GspmdBatchLoader(test_loader, mesh)
            gs_stats = {"n_shards": n_shards}
            train_step = make_gspmd_train_step(
                model, cfg, opt_spec, mesh, output_names,
                telemetry_metrics=telemetry.enabled,
                nonfinite_guard=res_cfg.nonfinite_guard)
            eval_step = make_gspmd_eval_step(model, cfg, mesh)
        else:
            # scan chunking works on the multi-host path too: every process
            # assembles [K, d_local, ...] superbatches that GlobalBatchLoader
            # turns into [K, d_global, ...] (spec P(None, dp)) for the
            # scanned step — K steps of cross-host psum per dispatch,
            # amortizing the per-dispatch host latency that multi-host runs
            # otherwise pay per step (docs/SCALING.md "Dispatch overhead")
            steps_per_dispatch = max(
                1, env_int("HYDRAGNN_STEPS_PER_DISPATCH", auto_k))
            train_step = make_dp_train_step(
                model, cfg, opt_spec, mesh, output_names, axis=dp_axes,
                zero_specs=zero_sh, steps=steps_per_dispatch,
                telemetry_metrics=telemetry.enabled,
                nonfinite_guard=res_cfg.nonfinite_guard,
                dtype_policy=train_dtype)
            eval_step = make_dp_eval_step(model, cfg, mesh, axis=dp_axes,
                                          zero=zero_sh)
            # staged resident only in one process (the wrappers below)
            fit_base = _align_bucket_group(
                train_loader, n_local_devices * steps_per_dispatch,
                fit=resident_on and single_proc)
            train_loader = DeviceStackLoader(
                train_loader, n_local_devices, drop_last=True)
            val_loader = DeviceStackLoader(
                val_loader, n_local_devices, drop_last=False)
            test_loader = DeviceStackLoader(
                test_loader, n_local_devices, drop_last=False)
            if steps_per_dispatch > 1:
                # second stack: [K, D, ...] superbatches for the scanned step
                train_loader = DeviceStackLoader(
                    train_loader, steps_per_dispatch, drop_last=True)
            if env_flag("HYDRAGNN_COMMS_PROBE") and single_proc:
                # opt-in comm-vs-compute attribution (docs/TELEMETRY.md
                # "Tracing"): A/B-time the annotated step vs a
                # collective-only replay on COPIES of the state, then fold
                # the split into the manifest `comms` block.  Single
                # process only — the replay is not a global collective
                # every rank could join.
                probe_b = next(iter(train_loader), None)
                if probe_b is not None:
                    from hydragnn_tpu.telemetry.comms import dp_comms_probe

                    telemetry.log_comms(dp_comms_probe(
                        model, cfg, opt_spec, mesh, state, probe_b,
                        output_names, zero_specs=zero_sh, axis=dp_axes,
                        steps=steps_per_dispatch))
        # per-device resident bytes under the chosen layout — the manifest
        # `sharding` block, so the ~1/N saving is a measured number; with
        # graph sharding active it also carries the partition stats
        # (cut-edge %, halo rows, imbalance, halo-buffer waste) teleview
        # renders
        from hydragnn_tpu.parallel.zero import sharding_report

        telemetry.log_sharding({
            "zero_stage_requested": zero_requested,
            **({"fallback": zero_fallback} if zero_fallback else {}),
            **sharding_report(state, zero_sh),
            **({"graph_shard": {
                "backend": graph_shard,
                "requested": gs_requested,
                **({"fallback": gs_fallback} if gs_fallback else {}),
                **gs_stats,
            }} if gs_requested != "off" else {}),
        })
        if graph_shard == "off" and not single_proc:
            train_loader = GlobalBatchLoader(
                train_loader, mesh, scan=steps_per_dispatch > 1)
            val_loader = GlobalBatchLoader(val_loader, mesh)
            test_loader = GlobalBatchLoader(test_loader, mesh)
        elif graph_shard != "gspmd":
            # single-process DP and halo-sharded batches alike are stacked
            # [D, ...] pytrees split along the mesh axis, so the prefetch /
            # device-resident wrappers apply to both; gspmd batches are
            # already committed-placed by GspmdBatchLoader
            from jax.sharding import NamedSharding, PartitionSpec as P

            # batch sharding: leading scan axis (if any) replicated, device
            # axis split over the mesh
            bspec = (P(None, dp_axes) if steps_per_dispatch > 1
                     else P(dp_axes))
            train_shard = NamedSharding(mesh, bspec)
            eval_shard = NamedSharding(mesh, P(dp_axes))
            if env_flag("HYDRAGNN_DEVICE_PREFETCH"):
                # async H2D of upcoming stacked batches while the current
                # step runs.  Opt-in: where the host link serializes
                # transfer with dispatch the background transfer contends
                # with it and HURTS (rounds 1-5, docs/PERF.md); not
                # re-measured on a locally attached chip.
                from hydragnn_tpu.data.prefetch import DevicePrefetcher

                train_loader = DevicePrefetcher(
                    train_loader, sharding=train_shard)
                val_loader = DevicePrefetcher(val_loader, sharding=eval_shard)
                test_loader = DevicePrefetcher(
                    test_loader, sharding=eval_shard)
            if resident_on:
                from hydragnn_tpu.data.prefetch import ResidentDeviceLoader

                train_loader = ResidentDeviceLoader(
                    train_loader, sharding=train_shard)
                val_loader = ResidentDeviceLoader(
                    val_loader, sharding=eval_shard)
                test_loader = ResidentDeviceLoader(
                    test_loader, sharding=eval_shard)
    else:
        zero_sh = None
        if graph_shard != "off":
            # graph sharding needs the mesh path (there is no axis to split
            # a graph across on the local-jit path) — warn-and-fall-back
            import warnings

            warnings.warn(
                f"graph sharding ({graph_shard}) requested but this run "
                "takes the single-device local-jit path — the graph must "
                "fit one device (sharding needs the mesh path: >1 local "
                "device, multi-process, or use_mesh_dp=True)", stacklevel=2)
            telemetry.health("graph_shard_fallback", requested=graph_shard,
                             reason="local_path")
            graph_shard = "off"
        if zero_stage > 0:
            # ZeRO needs the mesh path (there is no axis to shard along on
            # the local-jit path) — warn-and-fall-back, and record the
            # fallback so teleview can surface it loudly
            import warnings

            warnings.warn(
                f"ZeRO stage {zero_stage} requested but this run takes the "
                "single-device local-jit path — training REPLICATED "
                "(sharding needs the mesh path: >1 local device, "
                "multi-process, or use_mesh_dp=True)", stacklevel=2)
            zero_fallback = zero_fallback or "local_path"
            zero_stage = 0
        if zero_requested:
            telemetry.log_sharding({
                "zero_stage_requested": zero_requested,
                "fallback": zero_fallback,
                "zero_stage": 0, "axis": None, "axis_size": 1,
            })
        steps_per_dispatch = max(1, env_int("HYDRAGNN_STEPS_PER_DISPATCH", auto_k))
        if steps_per_dispatch > 1:
            # amortize per-step Python dispatch + arg-ingest latency by
            # scanning K train steps inside one executable (the batch
            # loader stacks K consecutive same-bucket batches)
            from hydragnn_tpu.parallel.mesh import DeviceStackLoader

            train_step = jax.jit(
                make_scan_train_step(model, cfg, opt_spec, output_names,
                                     steps_per_dispatch,
                                     telemetry_metrics=telemetry.enabled,
                                     nonfinite_guard=res_cfg.nonfinite_guard,
                                     dtype_policy=train_dtype),
                donate_argnums=0)
            fit_base = _align_bucket_group(
                train_loader, steps_per_dispatch, fit=resident_on)
            train_loader = DeviceStackLoader(
                train_loader, steps_per_dispatch, drop_last=True)
        else:
            train_step = jax.jit(
                make_train_step(model, cfg, opt_spec, output_names,
                                telemetry_metrics=telemetry.enabled,
                                nonfinite_guard=res_cfg.nonfinite_guard,
                                dtype_policy=train_dtype),
                donate_argnums=0)
        if env_flag("HYDRAGNN_DEVICE_PREFETCH"):
            # async H2D of upcoming (stacked) batches — AFTER stacking, so
            # the staged device arrays are consumed directly by the step
            # instead of round-tripping through np.stack
            from hydragnn_tpu.data.prefetch import DevicePrefetcher

            train_loader = DevicePrefetcher(train_loader)
            val_loader = DevicePrefetcher(val_loader)
            test_loader = DevicePrefetcher(test_loader)
        if resident_on:
            # stage each (stacked) batch to HBM once, replay thereafter —
            # removes steady-state H2D transfer for datasets that fit
            from hydragnn_tpu.data.prefetch import ResidentDeviceLoader

            train_loader = ResidentDeviceLoader(train_loader)
            val_loader = ResidentDeviceLoader(val_loader)
            test_loader = ResidentDeviceLoader(test_loader)
        # _run_epoch reads the losses only
        eval_step = jax.jit(make_eval_step(model, cfg, outputs=False))

    # the launched world shape as the elastic machinery defines it:
    # dp_extent is the number of batch shards per step — the extent the
    # stream split and the ZeRO padding actually depend on, not
    # world_size alone (resilience/elastic.py:world_block)
    dp_extent = int(mesh.devices.size) if use_mesh_dp else 1

    scheduler = ReduceLROnPlateau()
    earlystopper = None
    if training.get("EarlyStopping"):
        earlystopper = EarlyStopping(patience=training.get("patience", 10))
    # ZeRO-sharded optimizer state must be consolidated (all_gather over the
    # mesh — a collective EVERY process participates in) before any
    # serialization; one definition serves the pickle and orbax paths.
    consolidate = lambda s: s  # noqa: E731
    if use_mesh_dp and zero_sh is not None:
        from hydragnn_tpu.parallel.zero import consolidate_state

        consolidate = lambda s: consolidate_state(s, zero_sh, mesh)  # noqa: E731

    checkpointer = None
    if training.get("Checkpoint"):
        checkpointer = CheckpointTracker(
            log_name, warmup=training.get("checkpoint_warmup", 0),
            path=logs_dir, rank=rank)
        checkpointer.transform = consolidate

    # -- resilience wiring (docs/RESILIENCE.md) -----------------------------
    guard_monitor = None
    if res_cfg.nonfinite_guard:
        from hydragnn_tpu.resilience import NonFiniteGuardMonitor

        guard_monitor = NonFiniteGuardMonitor(
            max_consecutive=res_cfg.guard_max_consecutive,
            poll_every=res_cfg.guard_poll_every,
            steps_per_item=steps_per_dispatch,
            dump_path=os.path.join(logs_dir, log_name,
                                   "nonfinite_abort.json"),
            telemetry=telemetry)
    preempt = None
    if res_cfg.preemption:
        from hydragnn_tpu.resilience import PreemptionHandler

        # cross-rank agreement uses GLOBAL host collectives — an ensemble
        # branch (explicit sub-mesh) must not attempt them (same rule as
        # the telemetry cross-rank reduction)
        preempt = PreemptionHandler(
            sync_every=res_cfg.preempt_sync_every,
            cross_rank=(not explicit_mesh and world_size > 1)).install()
    # epoch-boundary elastic resize agreement (resilience/elastic.py) —
    # built only when something can arm a resize (the chaos knob today, a
    # capacity scheduler's drain hook tomorrow); None costs nothing
    from hydragnn_tpu.resilience import ElasticCoordinator

    elastic_coord = ElasticCoordinator.from_env(
        chaos=chaos, telemetry=telemetry, world_size=world_size,
        cross_rank=(not explicit_mesh and world_size > 1))

    # Orbax FULL-train-state checkpoint (step counter + params + batch stats
    # + opt state) every N epochs — beyond the reference's best-model pickle,
    # which restarts at epoch 0 (utils/model.py:58-103).  run_training's
    # ``continue`` path prefers this over the pickle when present.
    orbax_every = int(training.get("full_state_checkpoint", 0) or 0)
    orbax_dir = os.path.join(logs_dir, log_name, "orbax")

    from hydragnn_tpu.utils.print_utils import print_distributed
    from hydragnn_tpu.utils.profile import Profiler

    # per-batch wait/warmup/active trace schedule (reference wires
    # profiler.step() per train batch, train_validate_test.py:503)
    profiler = Profiler(profile_config, log_name, logs_dir)

    telemetry.attach_tensorboard(writer)
    telemetry.bind_step(
        train_step, state, steps_per_dispatch,
        cost_model=getattr(model, "cost_model_sees_flops", True))

    history: Dict[str, Any] = {
        "train": [], "val": [], "test": [], "lr": [], "epoch_time": [],
        # the fast-pipeline configuration THIS run actually used — exact
        # provenance for bench/telemetry (re-deriving it afterwards can
        # disagree near the residency budget boundary)
        "pipeline": {"steps_per_dispatch": steps_per_dispatch,
                     "resident": bool(resident_on),
                     "use_mesh_dp": bool(use_mesh_dp),
                     "dp_extent": dp_extent,
                     "zero_stage": zero_stage,
                     "graph_shard": graph_shard,
                     "train_dtype": train_dtype,
                     "train_dtype_requested": train_dtype_requested,
                     "auto_selected":
                         "HYDRAGNN_STEPS_PER_DISPATCH" not in os.environ,
                     # who shaped the train dispatch groups: the groups
                     # themselves (a resident run) or the ladder; the
                     # shapes, [nodes, edges, groups], once the first
                     # epoch's plan is made
                     "group_fit": fit_base is not None,
                     "group_shapes": []}}
    lr = get_learning_rate(state.opt_state)

    # -- mid-run resume (resilience/resume.py + resilience/elastic.py) ------
    # the bundle's items_consumed counts dispatch units of the FINAL wrapped
    # train loader, so a same-shape resume must match the preempted run's
    # pipeline shape — a silent mismatch would re-run or skip real optimizer
    # steps.  A WORLD-shape mismatch routes through resolve_resume: strict
    # (default) refuses loudly naming both shapes, `epoch` admits the
    # resize at an epoch boundary (docs/RESILIENCE.md "Elastic training").
    from hydragnn_tpu.resilience.elastic import resolve_resume, world_block

    def _launched_world():
        try:
            units = int(len(train_loader)) or None
        except TypeError:
            units = None
        return world_block(
            world_size=world_size, n_local_devices=n_local_devices,
            dp_extent=dp_extent, zero_stage=zero_stage, epoch_units=units,
            plan_fingerprint=(stream_base.plan().fingerprint()
                              if stream_base is not None else None))

    start_epoch = 0
    skip_first = 0
    if resume_meta:
        decision = resolve_resume(
            resume_meta, policy=res_cfg.elastic_resume,
            launched=_launched_world(), telemetry=telemetry)
        rp = resume_meta.get("pipeline") or {}
        if not decision.elastic:
            # same-shape path: EXACTLY the pre-elastic validation, so an
            # unchanged-world resume stays bit-identical (the elastic
            # machinery is provably dormant here — tests/test_elastic.py)
            if rp and (int(rp.get("steps_per_dispatch", steps_per_dispatch))
                       != steps_per_dispatch
                       or bool(rp.get("use_mesh_dp", use_mesh_dp))
                       != bool(use_mesh_dp)
                       or str(rp.get("graph_shard", graph_shard))
                       != str(graph_shard)):
                raise ValueError(
                    f"resume bundle was saved with pipeline {rp} but this "
                    f"run built steps_per_dispatch={steps_per_dispatch}, "
                    f"use_mesh_dp={use_mesh_dp}; resume with the same "
                    "pipeline knobs (HYDRAGNN_STEPS_PER_DISPATCH etc.) for "
                    "an exact continuation")
        else:
            # admitted resize: the position is epoch-granular (or an exact
            # unit conversion), so steps_per_dispatch / use_mesh_dp may
            # differ freely — but graph_shard changes what a dispatch unit
            # CONTAINS, so the stream is not comparable across backends
            if str(rp.get("graph_shard", graph_shard)) != str(graph_shard):
                raise ValueError(
                    "elastic resume: bundle was saved with graph_shard="
                    f"{rp.get('graph_shard')!r} but this run built "
                    f"{graph_shard!r}; the dispatch-unit stream is not "
                    "comparable across graph-shard backends")
            saved_ws = int(decision.saved.get("world_size", 1))
            telemetry.health(
                "elastic_resize", saved_world=saved_ws,
                world_size=world_size, epoch=decision.start_epoch,
                rounded=bool(decision.rounded), reason=decision.reason)
            telemetry.health(
                "elastic_admit", epoch=decision.start_epoch,
                items=decision.skip_first, saved_world=saved_ws,
                world_size=world_size, zero_stage=zero_stage,
                reason=decision.reason)
            if decision.rounded:
                import warnings

                warnings.warn(
                    "elastic resume rounded a mid-epoch position (epoch "
                    f"{int(resume_meta.get('epoch', 0))}, "
                    f"{int(resume_meta.get('items_consumed', 0))} unit(s) "
                    "consumed) up to the epoch "
                    f"{decision.start_epoch} boundary — the remainder of "
                    "the saved epoch is not replayed", stacklevel=2)
        start_epoch = decision.start_epoch
        skip_first = decision.skip_first
        if resume_meta.get("scheduler"):
            scheduler.load_state_dict(resume_meta["scheduler"])
        if earlystopper is not None and resume_meta.get("earlystop"):
            earlystopper.load_state_dict(resume_meta["earlystop"])
        if checkpointer is not None and resume_meta.get("checkpointer"):
            checkpointer.load_state_dict(resume_meta["checkpointer"])
        for k, v in (resume_meta.get("history") or {}).items():
            if k in history and isinstance(v, list):
                history[k] = list(v)
        lr = float(resume_meta.get("lr", lr))
        telemetry.resume_counts(int(resume_meta.get("saved_step", 0)))
        telemetry.health("resume_from", epoch=start_epoch,
                         items=skip_first,
                         step=resume_meta.get("saved_step"))

    def _save_resume(epoch_i: int, items: int, reason: str) -> bool:
        """Write the resume bundle (state + host control state); every
        rank enters (the consolidate transform and orbax save are
        collectives), rank 0 writes the meta."""
        from hydragnn_tpu.resilience import resume_dir, save_resume_bundle

        meta = {
            "epoch": epoch_i,
            "items_consumed": items,
            "scheduler": scheduler.state_dict(),
            "earlystop": (earlystopper.state_dict()
                          if earlystopper is not None else None),
            "checkpointer": (checkpointer.state_dict()
                             if checkpointer is not None else None),
            "history": {k: history[k]
                        for k in ("train", "val", "test", "lr",
                                  "epoch_time")},
            "lr": lr,
            "pipeline": {"steps_per_dispatch": steps_per_dispatch,
                         "resident": bool(resident_on),
                         "use_mesh_dp": bool(use_mesh_dp),
                         # the bundle's state is CONSOLIDATED (stage-
                         # agnostic) and the graph partition is DATA
                         # sharding only — a resume may re-shard the state
                         # under any stage exactly, but the batch stream
                         # position counts dispatch units of THIS loader
                         # stack, so graph_shard must match
                         "zero_stage": zero_stage,
                         "graph_shard": graph_shard,
                         # accept/reject verdict, not the request: a
                         # resumed run reuses it verbatim (no re-probe) so
                         # the continuation traces the SAME program
                         "train_dtype": train_dtype,
                         "n_local_devices": n_local_devices,
                         # provenance only: a resumed run plans anew
                         "group_fit": history["pipeline"]["group_fit"],
                         "group_shapes":
                             history["pipeline"]["group_shapes"]},
            "world_size": world_size,
            # the launched world shape + stream-plan identity: what a
            # resume at a DIFFERENT shape validates against and converts
            # the saved position with (resilience/elastic.py)
            "world": _launched_world(),
        }
        with tr.timer("checkpoint.save"):
            ok = save_resume_bundle(
                consolidate(state), meta, resume_dir(logs_dir, log_name),
                rank=rank, retries=res_cfg.ckpt_retries,
                backoff=res_cfg.ckpt_backoff, telemetry=telemetry,
                chaos=chaos, reason=reason,
                cross_rank=(not explicit_mesh and world_size > 1))
        telemetry.health(
            "walltime_save" if reason == "walltime" else "preempt_save",
            epoch=epoch_i, items=items, ok=ok,
            step=int(jax.device_get(state.step)))
        return ok

    # host regions are spans too while the flight recorder is on
    if telemetry.spans is not None:
        from hydragnn_tpu.telemetry.trace import RegionSpans

        tr.register("spans", RegionSpans(telemetry.spans))
    # while the regions are annotated for a profiler, leave it the map
    # from compiled instruction to scope, which a device trace does not
    # carry (telemetry/hlo_scopes.py); written once, after the first epoch
    step_notes = None
    if tr.has("jax") and telemetry.enabled and rank == 0:
        from hydragnn_tpu.telemetry.hlo_scopes import StepPrograms

        step_notes = StepPrograms()
        train_step = step_notes.watch(train_step)
        eval_step = step_notes.watch(eval_step)
    # epoch.tail: from the end of metrics_fetch to the next epoch's train
    in_tail = False
    regions_before = len(tr.open_regions())
    try:
        for epoch in range(start_epoch, num_epoch):
            t0 = time.time()
            telemetry.begin_epoch(epoch)
            train_loader.set_epoch(epoch)
            if stream_base is not None and stream_base.tail_grew:
                old_n, new_n = stream_base.tail_grew
                stream_base.tail_grew = None
                telemetry.health("stream_tail_grow", old=int(old_n),
                                 new=int(new_n))
            # mid-epoch resume: a streaming loader skips the already-
            # consumed units inside its plan (never decoding them); other
            # loaders fall back to _run_epoch's iterate-and-discard
            sf = skip_first if epoch == start_epoch else 0
            ff_base = 0
            if sf and try_fast_forward(train_loader, sf):
                ff_base, sf = sf, 0
            # train/val/test all DISPATCH without a device->host sync; ONE
            # combined device_get drains the queue per epoch (each separate
            # sync stalls dispatch until the device catches up).  The tr
            # regions therefore time dispatch, not execution; the fetch
            # region carries the wait.
            if in_tail:
                tr.stop("epoch.tail")
                in_tail = False
            tr.start("train")
            state, train_acc = _run_epoch(
                train_step, state, train_loader, True, profiler=profiler,
                steps_per_item=steps_per_dispatch,
                telemetry=telemetry if telemetry.enabled else None,
                guard=guard_monitor, preempt=preempt, chaos=chaos,
                skip_first=sf, consumed_base=ff_base)
            tr.stop("train")
            if epoch == start_epoch:
                if fit_base is not None:
                    # the plan that was staged is made: its shapes, which
                    # the eval loaders get as further rungs before their
                    # first batch is planned (one PadSpec set for the
                    # three loaders, as create_dataloaders builds them)
                    history["pipeline"]["group_shapes"] = \
                        fit_base.group_shapes
                    for eval_loader in (val_loader, test_loader):
                        eval_base = _base_loader(eval_loader, "add_specs")
                        if eval_base is not None:
                            eval_base.add_specs(fit_base.pad_specs)
                # model dispatch sites recorded any fell-off-the-fast-path
                # reasons at trace time (telemetry/pipeline.py); the first
                # epoch's dispatch is done, so surface them as health
                # events an operator (and teleview) will actually see
                from hydragnn_tpu.telemetry import pipeline as _pipe

                for fb in _pipe.pop_fallbacks("fused"):
                    telemetry.health("fused_fallback", **fb)
                    if fb.get("arch") == "EGNN":
                        # per-arch kind kept as an alias for one release
                        # (dashboards keyed on it migrate to
                        # fused_fallback + arch field)
                        legacy = {k: v for k, v in fb.items()
                                  if k != "arch"}
                        telemetry.health("egcl_fallback", **legacy)
            if preempt is not None and preempt.stop_requested:
                # preemption agreed mid-epoch: bundle the exact position
                # (epoch + items consumed) and stop; `continue` resumes here
                telemetry.flush_steps()
                _save_resume(epoch, preempt.consumed, reason="preempt")
                history["preempted"] = True
                print_distributed(
                    verbosity,
                    f"Preempted at epoch {epoch} after {preempt.consumed} "
                    "train dispatch(es); resume bundle saved")
                break
            if guard_monitor is not None:
                # drain buffered skip flags before val/test; raises
                # NonFiniteTrainingError past the consecutive-bad threshold
                guard_monitor.flush()
            # HYDRAGNN_VALTEST=0 skips the val/test epochs (reference knob)
            valtest = bool(int(os.getenv("HYDRAGNN_VALTEST", "1")))
            val_acc = test_acc = None
            if valtest:
                tr.start("validate")
                _, val_acc = _run_epoch(eval_step, state, val_loader, False)
                tr.stop("validate")
                tr.start("test")
                _, test_acc = _run_epoch(eval_step, state, test_loader, False)
                tr.stop("test")
            tr.start("metrics_fetch")
            # the drain: the host waits here for everything it dispatched
            tr.start("epoch.fetch")
            train_acc, val_acc, test_acc = jax.device_get(
                (train_acc, val_acc, test_acc))
            tr.stop("epoch.fetch")
            # drain the buffered per-step telemetry in the same sync window
            # (one device_get of tiny scalars; no-op when disabled)
            tr.start("telemetry.flush")
            telemetry.flush_steps()
            tr.stop("telemetry.flush")
            tr.stop("metrics_fetch")
            tr.start("epoch.tail")
            in_tail = True
            if step_notes is not None:
                step_notes.write(
                    os.path.join(telemetry.out_dir, "hlo_scopes.json"),
                    telemetry.log_program_memory)
                step_notes = None
            train_loss, train_tasks = _epoch_metrics(train_acc)
            if valtest:
                val_loss, _ = _epoch_metrics(val_acc)
                test_loss, _ = _epoch_metrics(test_acc)
            else:
                val_loss = test_loss = train_loss

            if world_size > 1 and not use_mesh_dp:
                # local-jit fallback only: the global-mesh step already psums
                # losses across every process's devices inside the jit.
                from hydragnn_tpu.parallel.comm import host_allreduce
                reduced = host_allreduce(
                    np.asarray([train_loss, val_loss, test_loss]), op="sum")
                train_loss, val_loss, test_loss = (reduced / world_size).tolist()

            new_lr = scheduler.step(val_loss, lr)
            if new_lr != lr:
                lr = new_lr
                state = state.replace(
                    opt_state=set_learning_rate(state.opt_state, lr))

            history["train"].append(train_loss)
            history["val"].append(val_loss)
            history["test"].append(test_loss)
            history["lr"].append(lr)
            # wall time per epoch (train + val/test + host bookkeeping): the
            # sustained-throughput evidence bench.py reports comes from here
            history["epoch_time"].append(time.time() - t0)

            # one epoch record through the telemetry spine: the TensorBoard
            # scalars ride TensorBoardSink (same tags as the old inline
            # add_scalar calls), JSONL/CSV/stdout sinks get the full record,
            # and cross-rank min/max/avg of the timing metrics reduce here
            epoch_scalars = {
                "train_loss": train_loss,
                "val_loss": val_loss,
                "test_loss": test_loss,
                "lr": lr,
                "epoch_time_s": history["epoch_time"][-1],
                "train_tasks": [float(t) for t in train_tasks],
            }
            # epoch-level throughput (the fetched accumulator's graph count
            # over the epoch wall clock) — the metric the cross-rank
            # min/max/avg reduction compares across hosts.  ALWAYS present
            # (0.0 for an empty epoch): the reduction's key list must be
            # identical on every rank or the collective shapes mismatch.
            epoch_scalars["graphs_per_s"] = (
                float(train_acc[2]) / history["epoch_time"][-1]
                if train_acc is not None and history["epoch_time"][-1] > 0
                else 0.0)
            telemetry.log_epoch(epoch, epoch_scalars,
                                train_loader=train_loader)

            print_distributed(
                verbosity,
                f"Epoch: {epoch:4d}, train loss: {train_loss:.8f}, "
                f"val loss: {val_loss:.8f}, test loss: {test_loss:.8f}, "
                f"lr: {lr:.2e}  ({time.time() - t0:.2f}s)",
            )

            if checkpointer is not None:
                checkpointer(state, val_loss)
            if orbax_every and (epoch + 1) % orbax_every == 0:
                # EVERY process calls this: the ZeRO consolidation jit and
                # orbax's CheckpointManager are both cross-process collectives —
                # a rank-0 gate would deadlock multi-host runs.  Retried with
                # backoff; a persistently failing filesystem warns and the
                # run KEEPS TRAINING (a periodic checkpoint is not worth the
                # run) — resilience/ckpt_io.py.
                from hydragnn_tpu.resilience.ckpt_io import with_retries
                from hydragnn_tpu.utils.checkpoint import save_checkpoint

                with tr.timer("checkpoint.save"):
                    consolidated = consolidate(state)
                    with_retries(
                        lambda: save_checkpoint(consolidated, orbax_dir),
                        retries=res_cfg.ckpt_retries,
                        backoff=res_cfg.ckpt_backoff,
                        what="periodic full-state checkpoint",
                        telemetry=telemetry, chaos=chaos, on_fail="warn",
                        cross_rank=(not explicit_mesh and world_size > 1))
            if earlystopper is not None and earlystopper(val_loss):
                print_distributed(verbosity, f"Early stopping at epoch {epoch}")
                break
            # SLURM walltime graceful stop (reference train_validate_test.py:229-235)
            # — now resumable: the full resume bundle is saved before
            # breaking, so `continue` picks up at epoch+1 instead of losing
            # everything since the last full_state_checkpoint epoch
            if os.getenv("SLURM_JOB_ID"):
                from hydragnn_tpu.utils.slurm import check_remaining

                if not check_remaining(time.time() - t0):
                    print_distributed(
                        verbosity,
                        f"Stopping at epoch {epoch}: insufficient SLURM walltime")
                    _save_resume(epoch + 1, 0, reason="walltime")
                    history["preempted"] = True
                    break
            # a signal delivered during val/test (or missed by the final
            # mid-train sync point) is caught at the epoch boundary; every
            # rank forces the agreement collective here, keeping it symmetric
            if preempt is not None and preempt.poll(force=True):
                _save_resume(epoch + 1, 0, reason="preempt")
                history["preempted"] = True
                print_distributed(
                    verbosity,
                    f"Preempted at end of epoch {epoch}; resume bundle saved")
                break
            # agreed elastic resize: the position is the single integer
            # epoch+1 — exactly what a different-shape relaunch can admit
            # — so save the boundary bundle and exit through the same
            # path a preemption takes; retiring hosts never relaunch,
            # the survivors/joiners `continue` at the new world size
            if elastic_coord is not None:
                resize = elastic_coord.poll(epoch)
                if resize is not None:
                    _save_resume(epoch + 1, 0, reason="elastic")
                    history["preempted"] = True
                    history["elastic"] = resize
                    print_distributed(
                        verbosity,
                        f"Elastic resize agreed at end of epoch {epoch}: "
                        f"world {resize['world_size']} -> "
                        f"{resize['target_world_size']}; resume bundle "
                        "saved")
                    break

    finally:
        # teardown runs on EVERY exit path — a crash mid-epoch must
        # still stop an active trace, write the (partial-history)
        # manifest, close the sinks and unlatch the module-global
        # pipeline counters, or the next run in this process (HPO
        # trial, test) inherits stale telemetry state
        if preempt is not None:
            preempt.uninstall()
        # release this run's cached orbax managers (background threads +
        # handles) — an HPO loop's trials use fresh directories and would
        # otherwise pin one manager per directory for the process lifetime
        from hydragnn_tpu.resilience import resume as _resume
        from hydragnn_tpu.utils.checkpoint import close_manager

        close_manager(orbax_dir)
        close_manager(os.path.join(
            _resume.resume_dir(logs_dir, log_name), _resume.STATE_DIRNAME))
        profiler.disable()
        # epoch.tail, and whatever an exception left open (train,
        # train.dispatch ...): a later build must not name them
        tr.close_to(regions_before)
        tr.unregister("spans")
        timer = tr.get("timer")
        telemetry.finalize(
            history, timers=timer.summary() if timer is not None else None)
    if use_mesh_dp and zero_sh is not None:
        # hand back a fully-replicated, unpadded state: callers (final
        # save_state, run_prediction, tests) are stage-agnostic
        state = consolidate(state)
    return state, history


def test(
    eval_step,
    state: TrainState,
    loader,
    num_heads: int,
    reduce_ranks: bool = True,
    world_size: int = 1,
    *,
    output_types: Sequence[str],
    classify: bool = False,
) -> Tuple[float, np.ndarray, List[np.ndarray], List[np.ndarray]]:
    """Full-dataset evaluation returning (error, per-task error, true, pred)
    per head with padding stripped (parity: reference test(),
    train_validate_test.py:565-664).  ``classify`` (a ``softmax_xent``
    model): a head's output is logits over classes and its prediction the
    class of the largest, beside the integer label."""
    total = 0.0
    n = 0.0
    tasks = np.zeros(num_heads)
    true_values: List[List[np.ndarray]] = [[] for _ in range(num_heads)]
    pred_values: List[List[np.ndarray]] = [[] for _ in range(num_heads)]
    dump_file = None
    if int(os.getenv("HYDRAGNN_DUMP_TESTDATA", "0")):
        # per-rank raw test dump (reference train_validate_test.py:580-623)
        from hydragnn_tpu.parallel.comm import process_index

        dump_file = open(f"testdata_rank{process_index()}.pickle", "wb")
    for g in loader:
        m = eval_step(state, g)
        ng = float(m["num_graphs"])
        total += float(m["loss"]) * ng
        tasks += np.asarray([float(t) for t in m["per_head"]]) * ng
        n += ng
        outputs = m["outputs"]
        gm = np.asarray(g.graph_mask) > 0
        nm = np.asarray(g.node_mask) > 0
        if hasattr(g, "send_idx") and gm.ndim == 2:
            # halo-sharded batch (graph/partition.py:HaloBatch): graph
            # arrays are REPLICATED per shard and stacked [D, G] — without
            # this, every real graph's label/prediction is collected D
            # times.  Node rows need no dedup: node_mask marks each real
            # node on exactly its owner shard.
            gm[1:] = False
        for ih in range(num_heads):
            out = np.asarray(outputs[ih])
            lab = np.asarray(g.labels[ih])
            # per-head type is required: shape inference is ambiguous when
            # padded node count equals padded graph count
            mask = gm if output_types[ih] == "graph" else nm
            true_values[ih].append(lab[mask])
            # gaussian_nll heads emit [mean, log_sigma] at 2x the label
            # width — the prediction is the mean block
            if classify:
                pred_values[ih].append(np.argmax(
                    out[mask], axis=-1)[:, None].astype(lab.dtype))
            else:
                pred_values[ih].append(out[mask][:, : lab.shape[-1]])
        if dump_file is not None:
            pickle.dump(
                {f"head{ih}": {"true": true_values[ih][-1],
                               "pred": pred_values[ih][-1]}
                 for ih in range(num_heads)},
                dump_file)
    if dump_file is not None:
        dump_file.close()
    n = max(n, 1.0)
    error = total / n
    tasks = tasks / n
    true_cat = [np.concatenate(v, axis=0) for v in true_values]
    pred_cat = [np.concatenate(v, axis=0) for v in pred_values]
    if reduce_ranks and world_size > 1:
        from hydragnn_tpu.parallel.comm import (
            host_allgather_variable,
            host_allreduce,
        )

        error = float(host_allreduce(np.asarray([error]), "sum")[0]) / world_size
        tasks = host_allreduce(tasks, "sum") / world_size
        # per-host sample counts differ: padded variable-size gather
        # (parity: reference gather_tensor_ranks, train_validate_test.py:381-419)
        true_cat = [host_allgather_variable(t) for t in true_cat]
        pred_cat = [host_allgather_variable(p) for p in pred_cat]
    return error, tasks, true_cat, pred_cat
