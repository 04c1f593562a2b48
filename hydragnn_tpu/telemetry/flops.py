"""Shared flops-basis helpers — ONE definition for bench.py and in-run telemetry.

MFU is only comparable when every reporter divides by the same flops basis
and the same peak.  bench.py's roofline and the telemetry subsystem's in-run
MFU estimate both import from here, so the two cannot drift (round-5 VERDICT
names honest-basis MFU as the top remaining gap — a gap we cannot close if
the bench harness and the training run disagree about what "100%" means).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.  A
# device that is not here has NO peak: telemetry then emits no
# ``mfu_est_pct`` and bench.py refuses to compute one — a utilization
# against somebody else's roofline is not a measurement.  The bf16 figure
# is also the right basis for JAX default-precision f32 (the default
# matmul precision runs f32 dots through the MXU as bf16 passes).
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peak_flops(device_kind: str) -> Optional[float]:
    """bf16 MXU peak of ``device_kind`` — the MFU basis — or None for a
    device outside :data:`DEVICE_PEAKS`."""
    row = DEVICE_PEAKS.get(device_kind)
    return None if row is None else float(row["bf16_flops"])


def require_peak_flops() -> float:
    """:func:`peak_flops` of this process's device for the measurement
    tools (bench.py, tools/mfu_attribution.py): a device outside the table
    is an error there, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind)
    if peak is None:
        raise RuntimeError(
            f"no published peak for device_kind {kind!r} "
            "(hydragnn_tpu/telemetry/flops.py:DEVICE_PEAKS) — MFU is "
            "undefined on this device")
    return peak


def step_cost_flops(step_fn, *args) -> float:
    """XLA cost-model flops of one compiled call of ``step_fn(*args)``.

    The cost model is fusion-invariant and reliable for flops (unlike its
    bytes figure — see bench.py's ``_roofline``).  ``args`` may be concrete
    arrays or ``jax.ShapeDtypeStruct`` pytrees: lowering only needs avals,
    so telemetry can compute the basis for a step whose buffers were donated
    away.  Caveat shared with bench.py: Pallas calls are opaque to the cost
    model — when a fused kernel hides matmul work, the composed-twin program
    is the honest basis (bench's dense phase builds that twin; in-run
    telemetry reports the timed program's basis and names the method in the
    manifest so the two are never silently conflated).
    """
    import jax

    compiled = jax.jit(step_fn).lower(*args).compile()
    return float(compiled.cost_analysis().get("flops", 0.0))


def mfu_pct(flops_per_step: float, step_s: float, peak: float) -> float:
    """Model-flops-utilization percent for one step against ``peak``
    (a :func:`peak_flops` value — callers decide what an unknown device
    means; this function never invents a denominator)."""
    if step_s <= 0.0 or flops_per_step <= 0.0:
        return 0.0
    return flops_per_step / step_s / peak * 100.0


def shape_struct_tree(tree):
    """Pytree of ``jax.ShapeDtypeStruct`` mirroring ``tree``'s array leaves
    (non-array leaves pass through) — avals survive buffer donation."""
    import jax

    def one(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(one, tree)
