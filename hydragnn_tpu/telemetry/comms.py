"""Comm-vs-compute attribution: the opt-in A/B probe behind
``HYDRAGNN_COMMS_PROBE`` and ``bench.py --comms`` (docs/TELEMETRY.md
"Tracing").

The question ROADMAP item 1 needs answered before the 2D pod mesh can be
designed: *what fraction of a DP / ZeRO / halo step is collective time?*
Per-op timers can't answer it inside one fused XLA program, so the probe
measures it differentially:

  - **A (step)** — the full train step as the trainer builds it; every
    collective sits in a named ``comm.*`` region
    (:func:`~hydragnn_tpu.parallel.mesh.comm_region`): HLO *metadata
    only*, and the xprof/Perfetto attribution handle when a device trace
    is captured (utils/profile.py).
  - **B (comm-only)** — a shard_map program that replays JUST the step's
    collectives on identically-shaped data: the gradient ``pmean`` over a
    param-shaped tree for DP, plus the ZeRO ``all_gather`` of the param
    slices when the state is ZeRO-sharded.

``comm_ms ~= B`` and ``compute_ms ~= A - B`` (overlap makes this an upper
bound on the collective's *critical-path* share — stated in the manifest
record so nobody mistakes it for an exact decomposition).  Both programs
are timed un-donated on COPIES of the live state, so probing never
invalidates the caller's training state (same discipline as the PR-15
``_train_dtype_gate``).

Everything lands in one dict: :meth:`MetricsLogger.log_comms` folds it
into the telemetry manifest's ``comms`` block, teleview renders it, and
``bench.py --comms`` prints it as a bench row.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

__all__ = ["time_fn_ms", "comm_split", "dp_comms_probe"]


def time_fn_ms(fn, args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall ms per call, synchronized via block_until_ready.
    ``fn`` must be donation-free OR pure in its args (the probe builders
    below re-jit without donation)."""
    import jax

    for _ in range(max(0, int(warmup))):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(1, int(iters))):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def comm_split(step_ms: float, comm_ms: float) -> Dict[str, float]:
    """The manifest/bench record for one measured path."""
    step_ms = max(float(step_ms), 1e-9)
    comm_ms = max(0.0, min(float(comm_ms), step_ms))
    return {
        "step_ms": round(step_ms, 4),
        "comm_ms": round(comm_ms, 4),
        "compute_ms": round(step_ms - comm_ms, 4),
        "comm_pct": round(100.0 * comm_ms / step_ms, 2),
    }


def _copy_tree(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.array, tree)


def dp_comms_probe(model, cfg, opt_spec, mesh, state, batches,
                   output_names=None, zero_specs=None,
                   axis: Optional[Any] = None, steps: int = 1,
                   iters: int = 3) -> Dict[str, Any]:
    """A/B comm-vs-compute split of the mesh DP (optionally ZeRO) step.

    ``state``/``batches`` are the live mesh-layout train state and one
    stacked batch in the step's exact input shape (``[D, ...]``, or
    ``[K, D, ...]`` when ``steps > 1``).  Both are copied before timing
    and the donated input is only ever the previous iteration's output,
    so the caller's state survives the probe.  Returns the
    :func:`comm_split` dict plus ``path``/``n_devices``/``parts``.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from hydragnn_tpu.parallel.mesh import (
        DATA_AXIS,
        _dp_axes,
        _resolve_zero_request,
        _shard_map,
        make_dp_train_step,
    )

    axes = _dp_axes(axis if axis is not None else DATA_AXIS)
    zero_sh, _zero_specs, zero_axis, _n_zero, zero_stage2 = \
        _resolve_zero_request(zero_specs, None, axes, mesh)

    # A: the production step.  It donates its state input, so
    # the probe feeds a COPY and only ever re-feeds the previous
    # iteration's output — the caller's state is never donated.
    step = make_dp_train_step(model, cfg, opt_spec, mesh, output_names,
                              axis=axis if axis is not None else DATA_AXIS,
                              zero_specs=zero_specs, steps=steps)
    st = _copy_tree(state)
    b = _copy_tree(batches)
    st, m = step(st, b)  # compile + warmup
    jax.block_until_ready(m["loss"])
    times = []
    for _ in range(max(1, int(iters))):
        t0 = time.perf_counter()
        st, m = step(st, b)
        jax.block_until_ready(m["loss"])
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    step_ms = times[len(times) // 2]

    # B: collective-only replicas of the step's comm volume
    parts: Dict[str, float] = {}

    def pmean_only(tree):
        return jax.lax.pmean(tree, axes)

    # grads have param shapes: a param-shaped pmean IS the DP all-reduce
    # volume (use the gathered full tree under ZeRO-2 — the grads the
    # step pmean-s are full-shaped there too)
    if zero_stage2:
        from hydragnn_tpu.parallel import zero

        full_params = jax.jit(_shard_map(
            lambda p: zero.unshard_tree_dims(
                p, zero_sh.param_dims, zero_axis),
            mesh=mesh, in_specs=(zero_sh.param_specs,),
            out_specs=P()))(_copy_tree(state.params))
    else:
        full_params = _copy_tree(state.params)
    pmean_fn = jax.jit(_shard_map(pmean_only, mesh=mesh,
                                  in_specs=(P(),), out_specs=P()))
    parts["comm.dp_psum_ms"] = time_fn_ms(
        pmean_fn, (full_params,), iters=iters)
    comm_ms = parts["comm.dp_psum_ms"]

    if zero_sh is not None and zero_stage2:
        from hydragnn_tpu.parallel import zero

        gather_fn = jax.jit(_shard_map(
            lambda p: zero.unshard_tree_dims(
                p, zero_sh.param_dims, zero_axis),
            mesh=mesh, in_specs=(zero_sh.param_specs,), out_specs=P()))
        parts["comm.zero_all_gather_ms"] = time_fn_ms(
            gather_fn, (_copy_tree(state.params),), iters=iters)
        comm_ms += parts["comm.zero_all_gather_ms"]

    path = "dp"
    if zero_sh is not None:
        path = "zero2" if zero_stage2 else "zero1"
    return {
        "path": path,
        "n_devices": int(mesh.devices.size),
        "method": "A/B differential: annotated full step vs collective-"
                  "only shard_map replay (upper bound on critical-path "
                  "comm share; overlap not subtracted)",
        **comm_split(step_ms, comm_ms),
        "parts": {k: round(v, 4) for k, v in parts.items()},
    }
