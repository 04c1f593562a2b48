"""Data-parallel training over a TPU device mesh.

TPU-native replacement of the reference's DDP/NCCL layer (reference
hydragnn/utils/distributed.py:113-244): instead of per-process NCCL process
groups, batches are stacked along a leading device axis and the train step is
``shard_map``-ped over a 1-axis ``jax.sharding.Mesh``.  Each device runs
message passing on its own padded shard (graphs never straddle devices, like
DDP's per-rank batches), and only the gradient/metric ``pmean`` crosses
ICI — exactly DDP's communication pattern, but inserted by XLA under one jit.

Batch-norm statistics are ``pmean``-ed across the axis, i.e. cross-replica
SyncBatchNorm (reference distributed.py:238-239) is the default rather than
an opt-in.

Multi-host bootstrap: :func:`setup_distributed` wraps
``jax.distributed.initialize`` with the reference's scheduler-env detection
(OMPI_*/SLURM_*, distributed.py:80-97).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import Base, ModelConfig
from hydragnn_tpu.train.optimizer import OptimizerSpec
from hydragnn_tpu.utils import tracer
from hydragnn_tpu.train.trainer import (
    TrainState,
    _force_head_indices,
    _loss_and_metrics,
    phase,
)

def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off (the metric
    dicts are replicated by construction via psum/pmean)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


DATA_AXIS = "data"
# multi-slice pods: outer axis crosses slices over DCN, inner axis stays on
# a slice's ICI.  DP spans both; ZeRO-1 shards along ICI only so its
# all_gather never rides the slow inter-slice links.
DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def setup_distributed() -> Tuple[int, int]:
    """Initialize the multi-host runtime; returns (world_size, rank).

    Parity with reference setup_ddp (distributed.py:113-173): rank/size come
    from the launcher env (OMPI_COMM_WORLD_*/SLURM_*) when present;
    single-process runs skip initialization entirely.
    """
    size = int(
        os.getenv(
            "OMPI_COMM_WORLD_SIZE",
            os.getenv("SLURM_NTASKS", os.getenv("JAX_NUM_PROCESSES", "1")),
        )
    )
    rank = int(
        os.getenv(
            "OMPI_COMM_WORLD_RANK",
            os.getenv("SLURM_PROCID", os.getenv("JAX_PROCESS_ID", "0")),
        )
    )
    if size > 1 and not _distributed_initialized():
        # jax.distributed.initialize must run before ANYTHING touches the
        # XLA backend — including jax.process_count(), which is why the
        # already-initialized probe below reads the distributed global
        # state instead of asking the backend
        coordinator = os.getenv("HYDRAGNN_MASTER_ADDR", "127.0.0.1")
        port = os.getenv("HYDRAGNN_MASTER_PORT", "8889")
        try:
            jax.distributed.initialize(
                coordinator_address=f"{coordinator}:{port}",
                num_processes=size,
                process_id=rank,
            )
        except RuntimeError as e:
            # the already-initialized probe reads a private API; if that API
            # moves, double-init must stay a no-op rather than a crash
            if "already" not in str(e).lower():
                raise
    return jax.process_count(), jax.process_index()


def _distributed_initialized() -> bool:
    """Whether jax.distributed.initialize has already run, WITHOUT
    initializing the XLA backend as jax.process_count() would."""
    try:
        from jax._src.distributed import global_state

        return global_state.client is not None
    except Exception:  # graftlint: disable=ROB001 (private-API probe; uninitialized is the safe answer)
        return False


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              axis: str = DATA_AXIS) -> Mesh:
    """1-axis data mesh over all (or given) devices.

    In a multi-process run the default covers EVERY process's devices — the
    train step is one global computation and gradients psum across hosts
    (DDP parity, reference train_validate_test.py:496), not per-host.
    """
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (axis,))


def make_multislice_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    num_slices: Optional[int] = None,
) -> Mesh:
    """2-axis (dcn, ici) mesh for multi-slice pods.

    The outer axis crosses slice boundaries (DCN), the inner axis stays
    within a slice (ICI).  Data parallelism spans both axes — XLA reduces
    gradients hierarchically (intra-slice first, then one exchange per slice
    over DCN) — while ZeRO-1 shards optimizer state along ``ici`` only, so
    its per-step all_gather of updated params never crosses DCN.

    Slices are inferred from each device's ``slice_index`` (real multi-slice
    TPU jobs expose it); pass ``num_slices`` explicitly to emulate slices on
    a flat device list (CPU tests, single-slice experiments).
    """
    devices = list(devices if devices is not None else jax.devices())
    groups: Dict[int, List[jax.Device]] = {}
    for d in devices:
        groups.setdefault(int(getattr(d, "slice_index", 0) or 0), []).append(d)
    if len(groups) > 1:
        # real multi-slice hardware: ALWAYS group by the physical
        # slice_index — a blind reshape of a non-slice-contiguous device
        # list would misalign "dcn" with the actual slice boundaries and
        # silently send the ZeRO all_gather over DCN
        ordered = [groups[k] for k in sorted(groups)]
        if num_slices is not None and num_slices != len(ordered):
            raise ValueError(
                f"num_slices={num_slices} but devices span {len(ordered)} "
                "physical slices")
        per = len(ordered[0])
        if any(len(g) != per for g in ordered):
            raise ValueError(
                f"uneven slices: {[len(g) for g in ordered]} devices per slice")
        arr = np.asarray(ordered)
    elif num_slices is not None:
        # flat device list (CPU tests, single-slice emulation)
        if num_slices < 1 or len(devices) % num_slices:
            raise ValueError(
                f"{len(devices)} devices do not divide into {num_slices} slices")
        arr = np.asarray(devices).reshape(num_slices, -1)
    else:
        raise ValueError(
            "devices report a single slice and no num_slices was given — "
            "use make_mesh for single-slice DP")
    return Mesh(arr, (DCN_AXIS, ICI_AXIS))


def _dp_axes(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def mesh_dp_axes(mesh: Mesh):
    """The DP axis argument matching a mesh: the plain data axis for 1-axis
    meshes, the (dcn, ici) tuple for multi-slice meshes."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def stack_batches(batches: Sequence[GraphBatch]) -> GraphBatch:
    """Stack per-device batches along a new leading device axis."""
    return jax.tree.map(lambda *xs: np.stack(xs, axis=0), *batches)


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place every state leaf replicated over the mesh.

    Works for meshes spanning non-addressable devices (multi-host): every
    process must call this with the same host values (params come from the
    same seed on every host).
    """
    repl = NamedSharding(mesh, P())

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, repl, lambda idx: x[idx])

    return jax.tree.map(put, state)


def mesh_process_count(mesh: Mesh) -> int:
    """Number of distinct processes owning this mesh's devices (== world size
    for the default global mesh, == group size for a HostGroup mesh)."""
    return len({d.process_index for d in mesh.devices.flat})


def global_batch(stacked: GraphBatch, mesh: Mesh,
                 axis=None, scan: bool = False) -> GraphBatch:
    """Assemble a host-local device-stacked batch [d_local, ...] into a global
    array [d_global, ...] sharded along ``axis`` (the multi-host analog of
    DDP's per-rank batches; one jit sees the whole global batch).  Works for
    group meshes spanning a subset of processes: the global shape covers only
    the mesh's processes.

    ``scan=True`` handles scan-chunked superbatches [K, d_local, ...]: the
    leading K (steps-per-dispatch) axis stays replicated, the device axis
    behind it is sharded — global shape [K, d_global, ...], spec
    P(None, axes)."""
    n_proc = mesh_process_count(mesh)
    axes = mesh_dp_axes(mesh) if axis is None else axis

    def conv(x):
        x = np.asarray(x)
        if scan:
            sharding = NamedSharding(mesh, P(None, axes))
            global_shape = (x.shape[0], x.shape[1] * n_proc) + x.shape[2:]
        else:
            sharding = NamedSharding(mesh, P(axes))
            global_shape = (x.shape[0] * n_proc,) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree.map(conv, stacked)


def _resolve_zero_request(zero_specs, zero_axis, axes, mesh):
    """Normalize the ``zero_specs`` argument the sharded step builders
    accept (a ZeroSharding, a raw PartitionSpec tree, or None) into
    ``(zero_sh, zero_specs, zero_axis, n_zero, zero_stage2)`` — one
    definition shared by the DP and halo train steps."""
    from hydragnn_tpu.parallel.zero import ZeroSharding

    zero_sh: Optional[ZeroSharding] = None
    if isinstance(zero_specs, ZeroSharding):
        zero_sh = zero_specs
        zero_specs = zero_sh.opt_specs
        if zero_axis is not None and zero_axis != zero_sh.axis:
            raise ValueError(
                f"zero_axis={zero_axis!r} but the ZeroSharding was built "
                f"for axis {zero_sh.axis!r}")
        zero_axis = zero_sh.axis
    zero_stage2 = zero_sh is not None and zero_sh.stage >= 2
    if zero_specs is not None:
        # derive the shard axis from the specs the opt state was ACTUALLY
        # placed with — a separately-guessed axis would slice gradients
        # along one axis into moments sharded along another, silently
        # corrupting every update
        spec_names = {
            s[0]
            for s in jax.tree_util.tree_leaves(
                zero_specs, is_leaf=lambda x: isinstance(x, P))
            if isinstance(s, P) and len(s) > 0 and s[0] is not None
        }
        if len(spec_names) > 1:
            raise ValueError(
                f"zero_specs shard along multiple axes: {spec_names}")
        if spec_names:
            derived = spec_names.pop()
            if zero_axis is not None and zero_axis != derived:
                raise ValueError(
                    f"zero_axis={zero_axis!r} but zero_specs were built "
                    f"for axis {derived!r}")
            zero_axis = derived
    zero_axis = zero_axis or axes[-1]
    n_zero = int(mesh.shape[zero_axis])
    return zero_sh, zero_specs, zero_axis, n_zero, zero_stage2


def _apply_sharded_update(state: TrainState, grads, params_full, opt_spec,
                          cfg, zero_specs, zero_stage2: bool,
                          zero_axis: str, n_zero: int):
    """The optimizer-update tail every sharded train step runs after its
    (replicated) gradients exist: plain full-tree update, or the ZeRO
    slice/update/gather dance.  Returns (new_params, new_opt_state,
    updates).  Runs inside shard_map."""
    import optax

    from hydragnn_tpu.models.base import encoder_freeze_mask

    if zero_specs is not None:
        from hydragnn_tpu.parallel import zero

        idx = jax.lax.axis_index(zero_axis)
        g_sh = zero.shard_tree(grads, idx, n_zero)
        # stage 2: the at-rest params ARE this device's (padded) slice
        p_sh = (state.params if zero_stage2
                else zero.shard_tree(state.params, idx, n_zero))
        updates, new_opt_state = opt_spec.tx.update(
            g_sh, state.opt_state, p_sh)
        updates = encoder_freeze_mask(updates, cfg.freeze_conv)
        new_p_sh = optax.apply_updates(p_sh, updates)
        # stage 2 keeps the updated slices sharded at rest; stage 1
        # gathers them back to the replicated layout
        new_params = (new_p_sh if zero_stage2 else
                      zero.unshard_tree(new_p_sh, params_full, zero_axis))
    else:
        updates, new_opt_state = opt_spec.tx.update(
            grads, state.opt_state, state.params)
        updates = encoder_freeze_mask(updates, cfg.freeze_conv)
        new_params = optax.apply_updates(state.params, updates)
    return new_params, new_opt_state, updates


def _zero_slice_norm(tree, zero_axis: str):
    """Global L2 norm of a ZeRO-sharded tree: psum of squared SLICE norms
    for rank>=1 leaves, replicated scalars (PReLU's alpha) added once
    OUTSIDE the psum (a psum would count them N times and make the metric
    stage-dependent); padded rows are zero and don't perturb anything."""
    zero = jnp.asarray(0.0, jnp.float32)
    sq_sl = sq_sc = zero
    for x in jax.tree_util.tree_leaves(tree):
        s = jnp.sum(jnp.square(x.astype(jnp.float32)))
        if jnp.ndim(x) >= 1:
            sq_sl = sq_sl + s
        else:
            sq_sc = sq_sc + s
    return jnp.sqrt(jax.lax.psum(sq_sl, zero_axis) + sq_sc)


def _zero_state_specs(zero_sh, zero_specs, zero_stage2: bool) -> TrainState:
    """shard_map in/out specs for a TrainState under the resolved ZeRO
    layout (replicated everywhere below stage 1)."""
    opt_spec_tree = P() if zero_specs is None else zero_specs
    param_spec_tree = zero_sh.param_specs if zero_stage2 else P()
    return TrainState(
        step=P(), params=param_spec_tree, batch_stats=P(),
        opt_state=opt_spec_tree)


def comm_region(name: str):
    """Collective-attribution region (docs/TELEMETRY.md "Tracing"): a
    ``jax.named_scope``, so every op it encloses carries the ``comm.*``
    name in lowered HLO metadata and device profiles — the handle xprof
    and the benchmark's trace reader use to attribute collective time.
    Metadata only, like the step phases (train/trainer.py:phase).
    Declared names: ``comm.dp_psum``, ``comm.zero_all_gather``,
    ``comm.halo_exchange`` (analysis/registry.py SCOPE_NAMES, lint
    REG006)."""
    return jax.named_scope(name)


def make_dp_train_step(
    model: Base,
    cfg: ModelConfig,
    opt_spec: OptimizerSpec,
    mesh: Mesh,
    output_names: Optional[Sequence[str]] = None,
    axis=DATA_AXIS,
    zero_specs=None,
    zero_axis: Optional[str] = None,
    steps: int = 1,
    telemetry_metrics: bool = False,
    nonfinite_guard: bool = False,
    dtype_policy: str = "f32",
):
    """jit'd DP train step over stacked batches [D, ...].

    ``steps`` > 1 scans that many consecutive stacked batches ([K, D, ...]
    input) inside one executable, amortizing per-step host dispatch
    (HYDRAGNN_STEPS_PER_DISPATCH; metrics come back graph-weighted over the
    K steps — same epoch-accumulation semantics as K dispatches).

    state is replicated; the batch is split along the device axis; gradients,
    metrics and batch-norm statistics are pmean-ed across the axis (DDP
    all-reduce parity, reference train_validate_test.py:496).  ``axis`` may
    be a tuple of mesh axes — e.g. ("dcn", "ici") from
    :func:`make_multislice_mesh` — in which case DP spans their product.

    ``zero_specs`` may be a :class:`parallel.zero.ZeroSharding` (from
    ``zero_shard_state`` — the production path, stages 1 and 2) or a raw
    PartitionSpec tree (from ``shard_opt_state``, legacy stage-1 callers).
    The optimizer state stays sharded along ``zero_axis`` (default: the
    innermost DP axis, so the ZeRO all_gather stays on ICI) — each device
    updates only its slice of params/moments and the new params are
    all_gather-ed (ZeRO-1, reference optimizer.py:43-103).  At stage 2 the
    params are sharded at rest too: the step all_gathers them into the
    transient full tree the forward needs and keeps the updated slices,
    and because the returned jit donates the state (``donate_argnums=0``)
    XLA reuses the sharded buffers — peak HBM is one full param tree plus
    the 1/N-resident state, not N replicas.

    ``nonfinite_guard`` adds the in-jit NaN/Inf step guard
    (resilience/guards.py).  The flag is derived AFTER the gradient pmean,
    so a non-finite shard on any device poisons the replicated check and
    every replica skips the same update — replicas can never diverge on a
    bad batch.  Default OFF: traces the exact pre-guard program.

    ``dtype_policy="bf16"`` runs each replica's forward/backward in bf16
    with f32 master params and optimizer state (trainer._loss_and_metrics);
    the gradient pmean and the update stay f32.  Default "f32" traces the
    exact pre-policy program.

    The collective sites (ZeRO all_gather, gradient pmean + metric psums)
    sit in named ``comm.*`` regions and the rest of the step in the
    trainer's phases (train/trainer.py:phase): metadata for the device
    trace, no op of their own.
    """
    energy_head, forces_head = _force_head_indices(output_names)
    axes = _dp_axes(axis)
    zero_sh, zero_specs, zero_axis, n_zero, zero_stage2 = \
        _resolve_zero_request(zero_specs, zero_axis, axes, mesh)

    def per_device(state: TrainState, g: GraphBatch):
        # leading device axis has size 1 inside the shard; drop it
        g = jax.tree.map(lambda x: x[0], g)
        dev_idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            dev_idx = dev_idx * mesh.shape[a] + jax.lax.axis_index(a)
        dropout_rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0xD0), state.step),
            dev_idx,
        )
        if zero_stage2:
            # stage 2: params arrive as this device's slice — all_gather the
            # transient full tree the forward needs (the per-step peak; the
            # at-rest copy stays 1/N)
            from hydragnn_tpu.parallel import zero

            with comm_region("comm.zero_all_gather"):
                params_full = zero.unshard_tree_dims(
                    state.params, zero_sh.param_dims, zero_axis)
        else:
            params_full = state.params

        def loss_fn(params):
            return _loss_and_metrics(
                model, cfg, params, state.batch_stats, g, True,
                energy_head, forces_head, dropout_rng,
                dtype_policy=dtype_policy)

        with phase("step.loss"):
            (loss, (per_head, new_stats, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_full)
        with comm_region("comm.dp_psum"):
            # gradient pmean across devices = DDP all-reduce parity (over
            # a multi-slice mesh XLA reduces hierarchically: ICI first,
            # then DCN)
            grads = jax.lax.pmean(grads, axes)
            new_stats = jax.lax.pmean(new_stats, axes)
            ng_local = g.n_real_graphs
            num_graphs = jax.lax.psum(ng_local, axes)
            denom = jnp.maximum(num_graphs, 1.0)
            loss = jax.lax.psum(loss * ng_local, axes) / denom
            per_head = [jax.lax.psum(p * ng_local, axes) / denom
                        for p in per_head]

        with phase("step.optimizer"):
            new_params, new_opt_state, updates = _apply_sharded_update(
                state, grads, params_full, opt_spec, cfg, zero_specs,
                zero_stage2, zero_axis, n_zero)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
        )
        metrics = {
            "loss": loss,
            "num_graphs": num_graphs,
            **{f"task_{i}": t for i, t in enumerate(per_head)},
        }
        if telemetry_metrics:
            from hydragnn_tpu.train.trainer import step_telemetry_metrics

            with phase("step.metrics"):
                tele = step_telemetry_metrics(g, grads, new_params, updates)
                # counts are per-shard — make them global like num_graphs
                tele["nodes_real"] = jax.lax.psum(tele["nodes_real"], axes)
                tele["edges_real"] = jax.lax.psum(tele["edges_real"], axes)
                if zero_specs is not None:
                    # ZeRO: updates live sharded along zero_axis — the
                    # global norm is the psum-of-slice-norms
                    # (_zero_slice_norm; grad/param norms at stage 1 are
                    # already replicated: pmean'd grads, all-gathered
                    # params)
                    tele["update_norm"] = _zero_slice_norm(
                        updates, zero_axis)
                    if zero_stage2:
                        # stage 2: new_params are slices too
                        tele["param_norm"] = _zero_slice_norm(
                            new_params, zero_axis)
            metrics.update(tele)
        if nonfinite_guard:
            from hydragnn_tpu.resilience.guards import (
                apply_step_guard,
                nonfinite_flag,
            )

            # grads are already pmean'd (replicated) and loss psum'd, so
            # `bad` is identical on every replica; the selects revert the
            # sharded (ZeRO) opt-state slices and replicated params alike
            with phase("step.guard"):
                bad = nonfinite_flag(loss, grads)
                new_state, metrics = apply_step_guard(
                    bad, state, new_state, metrics)
        return new_state, metrics

    state_specs = _zero_state_specs(zero_sh, zero_specs, zero_stage2)
    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(state_specs, P(axes)),
        out_specs=(state_specs, P()),
    )
    if steps > 1:
        from jax import lax

        from hydragnn_tpu.train.trainer import merge_scanned_metrics

        def multi(state, g):
            state, ms = lax.scan(sharded, state, g, length=steps)
            return state, merge_scanned_metrics(ms)

        return jax.jit(multi, donate_argnums=0)

    # the program's name in a device trace is the jitted function's
    def train_step(state, g):
        return sharded(state, g)

    return jax.jit(train_step, donate_argnums=0)


def make_dp_eval_step(
    model: Base,
    cfg: ModelConfig,
    mesh: Mesh,
    axis=DATA_AXIS,
    zero=None,
):
    """jit'd DP eval step over stacked batches [D, ...].  ``axis`` may be a
    tuple of mesh axes (multi-slice meshes).

    ``zero`` (a :class:`parallel.zero.ZeroSharding`) makes the in-specs
    match a ZeRO-sharded train state — without it, jit would silently
    re-replicate the sharded moments (and stage-2 param slices) on every
    eval call, materializing exactly the N copies ZeRO removed.  Stage 2
    all_gathers the param slices inside the step, like the train step."""
    axes = _dp_axes(axis)
    zero_stage2 = zero is not None and zero.stage >= 2

    def per_device(state: TrainState, g: GraphBatch):
        g = jax.tree.map(lambda x: x[0], g)
        params = state.params
        if zero_stage2:
            from hydragnn_tpu.parallel import zero as zero_mod

            params = zero_mod.unshard_tree_dims(
                state.params, zero.param_dims, zero.axis)
        with phase("step.eval"):
            loss, (per_head, _, outputs) = _loss_and_metrics(
                model, cfg, params, state.batch_stats, g, False)
            # weight by real graphs so empty wrap-padding shards don't
            # dilute
            ng_local = g.n_real_graphs
            num_graphs = jax.lax.psum(ng_local, axes)
            denom = jnp.maximum(num_graphs, 1.0)
            loss = jax.lax.psum(loss * ng_local, axes) / denom
            per_head = [jax.lax.psum(p * ng_local, axes) / denom
                        for p in per_head]
        # re-add the device axis so outputs gather across shards
        outputs = jax.tree.map(lambda x: x[None], outputs)
        return {
            "loss": loss,
            "num_graphs": num_graphs,
            "per_head": per_head,
            "outputs": outputs,
        }

    state_specs = P()
    if zero is not None:
        state_specs = TrainState(
            step=P(),
            params=zero.param_specs if zero_stage2 else P(),
            batch_stats=P(),
            opt_state=zero.opt_specs,
        )
    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(state_specs, P(axes)),
        out_specs={
            "loss": P(),
            "num_graphs": P(),
            "per_head": P(),
            "outputs": P(axes),
        },
    )

    def dp_eval_step(state, g):
        return sharded(state, g)

    return jax.jit(dp_eval_step)


def make_halo_train_step(
    model: Base,
    cfg: ModelConfig,
    opt_spec: OptimizerSpec,
    mesh: Mesh,
    output_names: Optional[Sequence[str]] = None,
    axis=DATA_AXIS,
    zero_specs=None,
    zero_axis: Optional[str] = None,
    telemetry_metrics: bool = False,
    nonfinite_guard: bool = False,
):
    """jit'd train step over a halo-sharded GIANT graph: the input is a
    stacked :class:`~hydragnn_tpu.graph.partition.HaloBatch` [D, ...] —
    each device holds ONLY its N/D local node rows plus the static halo
    plan (graph/partition.py).

    Inside the shard_map each device gathers its halo rows with one
    ``all_to_all`` into the bounded ``[D*halo_pair]`` buffer, runs the
    UNCHANGED model on local+halo rows (graph pooling / BatchNorm
    statistics / the masked-mean losses psum their partial sums through
    the :func:`~hydragnn_tpu.graph.partition.halo_context` hooks, so loss
    and batch statistics are exactly the single-device values), and
    ``psum``s the per-shard PARTIAL parameter gradients — shard
    contributions are disjoint node/edge subsets, so the psum is the DDP
    all-reduce's sum, not its mean.  Halo cotangents reduce-scatter back
    to their owner shards through the transpose of the exchange (jax AD).

    Composes with ZeRO exactly like :func:`make_dp_train_step`
    (``zero_specs`` may be a ZeroSharding of stage 1 or 2): parameters
    stay replicated-or-ZeRO-sharded while the DATA is graph-sharded.

    Unsupported (raises): energy-gradient force self-consistency
    (``total_energy`` + ``atomic_forces`` heads) — dE/dpos of a boundary
    node would miss the contributions of edges owned by neighbor shards;
    multi-axis (dcn, ici) meshes — the exchange is a single-axis
    all_to_all.
    """
    energy_head, forces_head = _force_head_indices(output_names)
    if energy_head >= 0 and forces_head >= 0:
        raise ValueError(
            "halo graph sharding does not support the energy-gradient "
            "force self-consistency term: dE/dpos of boundary nodes "
            "would miss cross-shard edge contributions")
    axes = _dp_axes(axis)
    if len(axes) != 1:
        raise ValueError(
            "halo graph sharding needs a 1-axis mesh (the halo exchange "
            "is a single-axis all_to_all); got axes " + repr(axes))
    zero_sh, zero_specs, zero_axis, n_zero, zero_stage2 = \
        _resolve_zero_request(zero_specs, zero_axis, axes, mesh)

    from hydragnn_tpu.graph.partition import assemble_extended, halo_context

    def per_device(state: TrainState, hb):
        hb = jax.tree.map(lambda x: x[0], hb)
        # SAME dropout stream on every shard (no dev_idx fold-in): a halo
        # row and its owner row still sit at different positions, so
        # dropout>0 training is approximate under sharding — documented in
        # docs/SCALING.md; the repo's models are dropout-free except GAT.
        dropout_rng = jax.random.fold_in(jax.random.PRNGKey(0xD0), state.step)
        if zero_stage2:
            from hydragnn_tpu.parallel import zero

            with comm_region("comm.zero_all_gather"):
                params_full = zero.unshard_tree_dims(
                    state.params, zero_sh.param_dims, zero_axis)
        else:
            params_full = state.params

        def loss_fn(params):
            with halo_context(axes[0]):
                with comm_region("comm.halo_exchange"):
                    g_ext = assemble_extended(hb, axes[0])
                return _loss_and_metrics(
                    model, cfg, params, state.batch_stats, g_ext, True,
                    energy_head, forces_head, dropout_rng)

        with phase("step.loss"):
            (loss, (per_head, new_stats, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_full)
        # per-shard grads are PARTIAL sums over disjoint owned subgraphs;
        # psum (not pmean) assembles the global gradient.  loss, per-head
        # losses and BN statistics came back GLOBAL already (the
        # halo-context psums ran inside the trace).  One wrinkle: taking
        # jax.grad INSIDE shard_map with check_vma=False scales the
        # per-shard cotangent of every in-trace psum by a factor T,
        # uniformly across leaves: jax 0.9.0 transposes psum to a psum of
        # the (replicated) seed, so T == D (measured: 4.0 on 4 devices);
        # a replication-tracked transpose (check_vma=True) would give 1.
        # Measure T with a one-op probe and divide it out, so the psum
        # below is the exact global gradient under either convention; the
        # parity tests pin this leaf-for-leaf (ROADMAP D4: drop the probe
        # when the step builders move to check_vma=True).
        cal = jax.grad(lambda s: jax.lax.psum(s, axes[0]))(
            jnp.asarray(1.0, jnp.float32))
        with comm_region("comm.dp_psum"):
            grads = jax.lax.psum(
                jax.tree.map(lambda g: g / cal, grads), axes)
        num_graphs = hb.n_real_graphs  # graph arrays replicated per shard
        with phase("step.optimizer"):
            new_params, new_opt_state, updates = _apply_sharded_update(
                state, grads, params_full, opt_spec, cfg, zero_specs,
                zero_stage2, zero_axis, n_zero)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
        )
        metrics = {
            "loss": loss,
            "num_graphs": num_graphs,
            **{f"task_{i}": t for i, t in enumerate(per_head)},
        }
        if telemetry_metrics:
            from hydragnn_tpu.train.trainer import tree_l2_norm

            owned = hb.extras.get("edge_owned_mask", hb.edge_mask)
            with phase("step.metrics"):
                metrics.update({
                    "grad_norm": tree_l2_norm(grads),
                    "param_norm": tree_l2_norm(new_params),
                    "update_norm": tree_l2_norm(updates),
                    # counts over OWNED rows/edges — halo duplicates
                    # excluded, so padding-waste accounting stays
                    # meaningful
                    "nodes_real": jax.lax.psum(
                        jnp.sum(hb.node_mask), axes),
                    "edges_real": jax.lax.psum(jnp.sum(owned), axes),
                })
                if zero_specs is not None:
                    metrics["update_norm"] = _zero_slice_norm(
                        updates, zero_axis)
                    if zero_stage2:
                        metrics["param_norm"] = _zero_slice_norm(
                            new_params, zero_axis)
        if nonfinite_guard:
            from hydragnn_tpu.resilience.guards import (
                apply_step_guard,
                nonfinite_flag,
            )

            # grads are psum'd (replicated) and the loss is global, so the
            # flag is identical on every shard
            with phase("step.guard"):
                bad = nonfinite_flag(loss, grads)
                new_state, metrics = apply_step_guard(
                    bad, state, new_state, metrics)
        return new_state, metrics

    state_specs = _zero_state_specs(zero_sh, zero_specs, zero_stage2)
    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(state_specs, P(axes)),
        out_specs=(state_specs, P()),
    )

    def halo_train_step(state, hb):
        return sharded(state, hb)

    return jax.jit(halo_train_step, donate_argnums=0)


def make_halo_eval_step(
    model: Base,
    cfg: ModelConfig,
    mesh: Mesh,
    axis=DATA_AXIS,
    zero=None,
):
    """jit'd eval step over a halo-sharded giant graph (stacked HaloBatch
    input).  Loss/per-head metrics come back global and replicated (the
    halo-context psums); per-shard node outputs come back stacked along
    the mesh axis [D, ext_n, .] with halo/pad rows masked by the stacked
    ``node_mask``.  ``zero`` matches ZeRO-sharded state like
    :func:`make_dp_eval_step`."""
    axes = _dp_axes(axis)
    if len(axes) != 1:
        raise ValueError("halo graph sharding needs a 1-axis mesh")
    zero_stage2 = zero is not None and zero.stage >= 2

    from hydragnn_tpu.graph.partition import assemble_extended, halo_context

    def per_device(state: TrainState, hb):
        hb = jax.tree.map(lambda x: x[0], hb)
        params = state.params
        if zero_stage2:
            from hydragnn_tpu.parallel import zero as zero_mod

            params = zero_mod.unshard_tree_dims(
                state.params, zero.param_dims, zero.axis)
        with phase("step.eval"), halo_context(axes[0]):
            g_ext = assemble_extended(hb, axes[0])
            loss, (per_head, _, outputs) = _loss_and_metrics(
                model, cfg, params, state.batch_stats, g_ext, False)
        outputs = jax.tree.map(lambda x: x[None], outputs)
        return {
            "loss": loss,
            "num_graphs": hb.n_real_graphs,
            "per_head": per_head,
            "outputs": outputs,
        }

    state_specs = P()
    if zero is not None:
        state_specs = TrainState(
            step=P(),
            params=zero.param_specs if zero_stage2 else P(),
            batch_stats=P(),
            opt_state=zero.opt_specs,
        )
    sharded = _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(state_specs, P(axes)),
        out_specs={
            "loss": P(),
            "num_graphs": P(),
            "per_head": P(),
            "outputs": P(axes),
        },
    )

    def halo_eval_step(state, hb):
        return sharded(state, hb)

    return jax.jit(halo_eval_step)


class DeviceStackLoader:
    """Wrap a GraphDataLoader to yield device-stacked batches [D, ...].

    Each step consumes ``n_devices`` consecutive padded micro-batches (the
    per-device batches of DDP ranks).  If the epoch length is not divisible,
    the tail is dropped on shuffled (train) loaders and wrap-padded on eval
    loaders so every sample is seen.
    """

    def __init__(self, loader, n_devices: int, drop_last: bool = True):
        self.loader = loader
        self.n_devices = n_devices
        self.drop_last = drop_last
        if drop_last and len(loader) < n_devices:
            import warnings

            warnings.warn(
                f"DeviceStackLoader: wrapped loader has {len(loader)} batches "
                f"per epoch but {n_devices} devices; with drop_last=True the "
                "epoch yields ZERO steps — shrink batch_size or the device "
                "count", stacklevel=2)

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        n = len(self.loader)
        if self.drop_last:
            return n // self.n_devices
        return -(-n // self.n_devices)

    def __iter__(self):
        group: List[GraphBatch] = []
        for g in self.loader:
            group.append(g)
            if len(group) == self.n_devices:
                with tracer.timer("data.stack"):
                    stacked = stack_batches(group)
                yield stacked
                group = []
        if group and not self.drop_last:
            # pad with empty copies shaped like THIS group (zero graph_mask);
            # with bucketing, earlier groups may use a different PadSpec
            empty = jax.tree.map(np.zeros_like, group[0])
            while len(group) < self.n_devices:
                group.append(empty)
            with tracer.timer("data.stack"):
                stacked = stack_batches(group)
            yield stacked


class GlobalBatchLoader:
    """Wrap a DeviceStackLoader so its host-local [d_local, ...] stacks become
    global arrays [d_global, ...] sharded over a multi-host mesh.  Every
    process must iterate in lockstep (per-rank batch counts are equalized by
    the loaders' wrap-padding)."""

    def __init__(self, loader, mesh: Mesh, axis=None, scan: bool = False):
        self.loader = loader
        self.mesh = mesh
        # None -> all the mesh's axes (works for 1-axis and multi-slice)
        self.axis = mesh_dp_axes(mesh) if axis is None else axis
        self.scan = scan  # loader yields [K, d_local, ...] superbatches

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self):
        for stacked in self.loader:
            yield global_batch(stacked, self.mesh, self.axis, scan=self.scan)
