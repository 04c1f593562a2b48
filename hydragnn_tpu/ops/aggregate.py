"""Aggregation backends for the message-passing scatter-add.

The reference exposes HYDRAGNN_AGGR_BACKEND to switch PyG's aggregation
between torch-scatter and its native fallback (reference
hydragnn/train/train_validate_test.py:373-378).  Here the same knob selects
how ``graph/segment.py:segment_sum`` lowers on the device:

- ``scatter`` (default): ``jax.ops.segment_sum`` — XLA's sort/scatter path.
- ``onehot``: one-hot × messages matmul in plain jnp.  O(E·N·F) FLOPs, but
  they run on the MXU systolic array at full rate, which on TPU often beats
  the scatter path for the padded static shapes this framework batches to.
- ``pallas``: hand-written Pallas kernel of the same one-hot contraction,
  blocked over edges so the one-hot tile is built on the fly in VMEM and
  never materialized in HBM (the jnp version materializes an [E, N] array).
- ``fused``: the full gather->multiply->segment-sum message-passing core in
  one sorted-receiver dense-schedule Pallas pass (ops/fused_mp.py,
  dispatched via graph/segment.py:gather_mul_segment) — +26% end-to-end on
  the flagship bench (docs/PERF.md); plain ``segment_sum`` calls under
  this backend use the scatter path.

All backends are exact (no atomics — deterministic accumulation order) and
differentiable; ``segment_sum``'s gradient is a gather, which the custom VJP
implements directly instead of differentiating through the kernel.

Measured on the real chip (v5e, f32): isolated segment_sum at
E=32768/N=2560/F=64 runs 0.9-1.5ms for onehot vs 1.2ms scatter vs 1.2ms
pallas; end-to-end on the flagship QM9-SchNet bench the XLA scatter path
wins (60.1k graphs/s vs 58.2k onehot, 38.4k pallas — the standalone kernel
can't fuse into neighboring elementwise ops the way XLA's scatter does), so
``scatter`` stays the default and the others are shape-dependent tuning
knobs, not a blanket win.

``segment_sum_sorted`` additionally exploits the collate invariant that
receivers are NONDECREASING with bounded in-degree: each output node-block
owns a contiguous scalar-prefetch-steered edge range, so there is no sort
and no full-N onehot tile.  Measured at flagship shapes
(E=82k/N=10.2k/F=64, degree<=20): 2.57ms vs scatter's 2.67ms — parity, not
a win, because the blocked onehot contraction spends ~BN redundant MACs
per edge that offset the sort savings.  Kept as the building block for
fused conv kernels, where skipping the sort AND the message
materialization could pay.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_EDGE_BLOCK = 256  # edges per grid step; onehot tile = _EDGE_BLOCK x N_pad


# THE backend vocabulary (config validation in run_training.py imports it
# — one definition, no drift between the two validation points)
KNOWN_BACKENDS = ("scatter", "onehot", "pallas", "fused")
_warned_unknown = set()


def aggr_backend() -> str:
    """Current backend name.  The env knob is read at TRACE time: a jitted
    caller (every real train/eval step) pins whichever backend was active
    when it was first traced, so set the knob before building the step —
    flipping it mid-process does not retrace cached executables.

    An unrecognized env value warns ONCE and behaves as ``scatter``
    (every backend check misses): a typo like ``fusd`` would otherwise
    silently lose the whole fused path AND evade the fallback telemetry,
    which only compares against the exact string ``fused``."""
    v = os.environ.get("HYDRAGNN_AGGR_BACKEND", "scatter").lower()
    if v not in KNOWN_BACKENDS and v not in _warned_unknown:
        _warned_unknown.add(v)
        import warnings

        warnings.warn(
            f"HYDRAGNN_AGGR_BACKEND={v!r} is not one of {KNOWN_BACKENDS};"
            " every aggregation will take the scatter path", stacklevel=2)
    return v


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_ranges(segment_ids, n_blocks: int, bn: int, be: int,
                 n_eblocks: int):
    """Per-node-block [start, end) EDGE-BLOCK ranges for nondecreasing
    ``segment_ids`` (shared by the sorted backend and ops/fused_mp.py):
    block i's segments span rows [i*bn, (i+1)*bn), located by searchsorted,
    then converted to edge-block indices (floor start, ceil end)."""
    bounds = jnp.arange(n_blocks + 1, dtype=jnp.int32) * bn
    v = jnp.searchsorted(segment_ids, bounds, side="left")
    lo, hi = v[:-1], v[1:]
    start = (lo // be).astype(jnp.int32)
    end = jnp.minimum((-(-hi // be)).astype(jnp.int32), n_eblocks)
    return start, end


# ---------------------------------------------------------------------------
# onehot backend: plain jnp, XLA fuses the one-hot build into the matmul
# ---------------------------------------------------------------------------

def segment_sum_onehot(data, segment_ids, num_segments):
    """sum_e onehot[e, n] * data[e, f] on the MXU.  data: [E, ...]."""
    shape = data.shape
    flat = data.reshape(shape[0], -1)
    onehot = jax.nn.one_hot(segment_ids, num_segments, dtype=flat.dtype)
    # HIGHEST matches scatter bit-accuracy (default bf16 passes round the
    # messages to 8 mantissa bits) and measured the same speed on-chip —
    # this contraction is HBM-bandwidth-bound, not MXU-bound
    out = jax.lax.dot_general(
        onehot, flat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(flat.dtype)
    return out.reshape((num_segments,) + shape[1:])


# ---------------------------------------------------------------------------
# pallas backend: blocked one-hot contraction, accumulated across grid steps
# ---------------------------------------------------------------------------

def _segment_kernel(seg_ref, data_ref, out_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    seg = seg_ref[:]                                   # [BE, 1] int32
    n_pad = out_ref.shape[0]
    # compute in f32 regardless of input dtype: bf16->f32 upcast is exact and
    # Mosaic rejects bf16 operands under an fp32 contract precision
    onehot = (seg == jax.lax.broadcasted_iota(
        jnp.int32, (seg.shape[0], n_pad), 1)).astype(jnp.float32)
    out_ref[:] += jax.lax.dot_general(
        onehot, data_ref[:].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _pallas_segment_sum_impl(data2d, segment_ids, n_pad: int,
                             interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data2d.shape
    e_pad = _round_up(max(e, 1), _EDGE_BLOCK)
    f_pad = _round_up(max(f, 1), 128)
    # padded edges carry zero data -> contribute zeros wherever they scatter
    data_p = jnp.zeros((e_pad, f_pad), data2d.dtype).at[:e, :f].set(data2d)
    seg_p = jnp.zeros((e_pad, 1), jnp.int32).at[:e, 0].set(
        segment_ids.astype(jnp.int32))

    # accumulator is ALWAYS f32 (bf16 inputs accumulate in f32 on the MXU;
    # a bf16 out_ref would both reject the f32 store and lose the guarantee)
    return pl.pallas_call(
        _segment_kernel,
        grid=(e_pad // _EDGE_BLOCK,),
        in_specs=[
            pl.BlockSpec((_EDGE_BLOCK, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_EDGE_BLOCK, f_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((n_pad, f_pad), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        interpret=interpret,
        name="seg_sum_pallas_fwd",
    )(seg_p, data_p)


# ---------------------------------------------------------------------------
# sorted backend: receivers are nondecreasing after collate (graph/batch.py
# concatenates per-sample KD-tree neighbor lists with node offsets), so each
# output node-block owns a CONTIGUOUS edge range — no sort, no full-N onehot.
# Grid = (node_blocks, K) where K edge-blocks per node block is statically
# bounded by the caller's max-in-degree contract; scalar-prefetched
# searchsorted offsets steer each step's edge-block DMA.
# ---------------------------------------------------------------------------

_SORT_NODE_BLOCK = 1024
_SORT_EDGE_BLOCK = 2048


def _sorted_kernel(start_ref, end_ref, seg_ref, data_ref, out_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # steps beyond this node block's edge range are pure no-ops (their DMA'd
    # block is a clamped re-read; accumulating it would double count)
    @pl.when(start_ref[i] + k < end_ref[i])
    def _acc():
        bn = out_ref.shape[0]
        local = seg_ref[:] - i * bn                      # [BE, 1] int32
        onehot = (local == jax.lax.broadcasted_iota(
            jnp.int32, (seg_ref.shape[0], bn), 1)).astype(jnp.float32)
        out_ref[:] += jax.lax.dot_general(
            onehot, data_ref[:].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)


def _sorted_impl(data2d, segment_ids, num_segments: int,
                 max_per_segment: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data2d.shape
    be, bn = _SORT_EDGE_BLOCK, _SORT_NODE_BLOCK
    e_pad = _round_up(max(e, 1), be)
    f_pad = _round_up(max(f, 1), 128)
    n_pad = _round_up(num_segments, bn)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    data_p = jnp.zeros((e_pad, f_pad), data2d.dtype).at[:e, :f].set(data2d)
    # padding edges get the out-of-every-window sentinel n_pad
    seg_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        segment_ids.astype(jnp.int32))

    start, end = block_ranges(segment_ids, n_blocks, bn, be, n_eblocks)
    # static bound on edge-blocks per node block: bn segments x
    # max_per_segment edges, +1 for a range not aligned to a block boundary
    k_max = min(n_eblocks, -(-bn * max_per_segment // be) + 1)

    def edge_index_map(i, k, start_ref, end_ref):
        return (jnp.minimum(start_ref[i] + k, n_eblocks - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks, k_max),
        in_specs=[
            pl.BlockSpec((be, 1), edge_index_map),
            pl.BlockSpec((be, f_pad), edge_index_map),
        ],
        out_specs=pl.BlockSpec((bn, f_pad), lambda i, k, s, e2: (i, 0)),
    )
    return pl.pallas_call(
        _sorted_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="seg_sum_sorted_fwd",
    )(start, end, seg_p, data_p)


def _gather_bwd(num_segments, segment_ids, g):
    """Shared VJP of any exact segment sum: d/d(data)[e] = g[ids[e]], with
    zeros where the forward DROPPED the row (out-of-range ids; a bare gather
    would clamp them onto the last segment)."""
    valid = (segment_ids >= 0) & (segment_ids < num_segments)
    safe = jnp.clip(segment_ids, 0, num_segments - 1)
    return jnp.where(valid[:, None], g[safe], 0.0), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sorted_segment_sum(data2d, segment_ids, num_segments, max_per_segment):
    interpret = jax.default_backend() != "tpu"
    out = _sorted_impl(data2d, segment_ids, num_segments,
                       max_per_segment, interpret)
    return out[:num_segments, :data2d.shape[1]].astype(data2d.dtype)


def _sorted_fwd(data2d, segment_ids, num_segments, max_per_segment):
    return (_sorted_segment_sum(data2d, segment_ids, num_segments,
                                max_per_segment), segment_ids)


_sorted_segment_sum.defvjp(
    _sorted_fwd,
    lambda num_segments, _mps, ids, g: _gather_bwd(num_segments, ids, g))


def segment_sum_sorted(data, segment_ids, num_segments: int,
                       max_per_segment: int):
    """Exact segment sum REQUIRING nondecreasing ``segment_ids`` and at most
    ``max_per_segment`` REAL entries per segment (collate's receivers are
    sorted with in-degree capped by max_neighbours).  Collate's PADDING
    edges all target node N-1 — far exceeding the cap — so edge data MUST
    be pre-masked (zeros at padded rows, as ``segment.segment_sum``'s mask
    argument does): overflow contributions beyond the cap are silently
    dropped, which is only harmless when they are zeros."""
    shape = data.shape
    out = _sorted_segment_sum(
        data.reshape(shape[0], -1), segment_ids, num_segments,
        int(max_per_segment))
    return out.reshape((num_segments,) + shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_segment_sum(data2d, segment_ids, num_segments):
    interpret = jax.default_backend() != "tpu"
    n_pad = _round_up(num_segments, 128)
    out = _pallas_segment_sum_impl(data2d, segment_ids, n_pad, interpret)
    return out[:num_segments, :data2d.shape[1]].astype(data2d.dtype)


def _fwd(data2d, segment_ids, num_segments):
    return _pallas_segment_sum(data2d, segment_ids, num_segments), segment_ids


_pallas_segment_sum.defvjp(_fwd, _gather_bwd)


def segment_sum_pallas(data, segment_ids, num_segments):
    shape = data.shape
    out = _pallas_segment_sum(
        data.reshape(shape[0], -1), segment_ids, num_segments)
    return out.reshape((num_segments,) + shape[1:])
