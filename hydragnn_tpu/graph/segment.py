"""Segment (scatter/gather) ops — the message-passing primitives.

TPU-native replacement for torch-scatter / torch-sparse (reference depends on
them for all PyG conv internals; see SURVEY.md §2.3).  XLA lowers
``jax.ops.segment_sum`` to efficient one-hot matmuls / scatter kernels on TPU,
so message passing is expressed as gather (``x[senders]``) + segment reduce at
``receivers`` with *static* ``num_segments``.

All ops take an optional mask (1.0 = valid) so padded edges/nodes contribute
nothing — this is what makes padded static-shape batching exact.

``segment_sum`` is the mask and ``jax.ops.segment_sum``, whatever the
aggregation backend; HYDRAGNN_AGGR_BACKEND=fused (parity: reference
train_validate_test.py:373-378; hydragnn_tpu/ops/aggregate.py) moves the
gather-multiply-segment core below into one Pallas pass when the batch
carries collate's marker.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

_BIG = 1e9


def _count(op: str, fused: bool) -> None:
    """Trace-time dispatch tally (fused fast path vs scatter fallback) —
    folded into the telemetry manifest and bench's per-arch records so a
    run that silently fell off the fast path is visible.  Runs once per
    trace (Python level), never inside compiled code."""
    from hydragnn_tpu.telemetry import pipeline

    pipeline.count_fused_choice(op, fused)


def segment_sum(data, segment_ids, num_segments, mask=None):
    if mask is not None:
        data = data * _bcast(mask, data)
    return jax.ops.segment_sum(data, segment_ids, num_segments)


class CFFilter(NamedTuple):
    """SchNet's continuous filter as its GENERATOR instead of as an
    ``[E, F]`` array: ``w[e] = (ssp(rbf[e] @ k0 + b0) @ k1 + b1) * cut[e]``.
    Handed to :func:`gather_mul_segment` in ``w``'s place, it lets the
    fused kernels make each block of ``w`` in VMEM (ops/scf_mp.py), so no
    edge-sized filter, pre-activation or filter cotangent ever exists in
    HBM; :meth:`dense` is the composed expression every other path (the
    ``scatter`` backend, an equivariant layer's coordinate MLP, a width
    the kernels cannot hold) evaluates."""
    rbf: jax.Array   # [E, G] radial basis
    cut: jax.Array   # [E] cutoff envelope (unmasked)
    k0: jax.Array    # [G, F]
    b0: jax.Array    # [F]
    k1: jax.Array    # [F, F]
    b1: jax.Array    # [F]

    def dense(self, edge_mask):
        """The masked ``[E, F]`` filter, composed in plain XLA."""
        # shifted softplus, as models/layers.shifted_softplus
        act = jax.nn.softplus(self.rbf @ self.k0 + self.b0) - jnp.log(2.0)
        filt = act @ self.k1 + self.b1
        return filt * self.cut[:, None] * edge_mask[:, None]

    def fits_kernel(self) -> bool:
        """Structural limits of the in-VMEM form: the basis (plus the
        cutoff and bias lanes) within the geometry tile, ``[F, F]`` weight
        blocks within VMEM."""
        from hydragnn_tpu.ops.scf_mp import SCF_F_LIMIT, SCF_G_LIMIT

        return (self.rbf.shape[1] <= SCF_G_LIMIT
                and self.k1.shape[1] <= SCF_F_LIMIT)


def gather_mul_segment(x, w, g):
    """The message-passing core ``out[n] = sum_{e: recv[e]=n}
    x[send[e]] * w[e]`` — gather, edge-multiply, segment-sum.

    When HYDRAGNN_AGGR_BACKEND=fused and the batch carries the
    collate-provided ``edge_perm_sender`` (graph/batch.py attaches it when
    the block-locality invariant holds) this lowers to the single fused
    Pallas pass (ops/fused_mp.py) that never materializes the gathered
    messages in HBM; otherwise the standard gather + masked segment_sum.

    ``w`` is the ``[E, F]`` multiplier or its generator, a
    :class:`CFFilter`: on the fused path the same two kernels then make
    ``w`` in VMEM, forward and backward (tallied ``gather_mul_filter``);
    everywhere else the generator is evaluated to the array first.
    """
    # the permutation's PRESENCE is the gate (collate's word that the
    # kernel's invariants hold); the kernel itself reads the edge list as
    # shipped, forward and backward, and never the permutation
    fused = bool(g.extras) and "edge_perm_sender" in g.extras
    _count("gather_mul", fused)
    if isinstance(w, CFFilter):
        in_vmem = fused and w.fits_kernel()
        _count("gather_mul_filter", in_vmem)
        if in_vmem:
            from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline

            # cm zeroes padding edges; em lets the schedule skip them
            return scf_edge_pipeline(
                x, w.rbf, w.cut * g.edge_mask,
                g.edge_mask.astype(jnp.int32), w.k0, w.b0, w.k1, w.b1,
                g.senders, g.receivers)
        w = w.dense(g.edge_mask)
    if fused:
        from hydragnn_tpu.ops.fused_mp import gather_mul_segment_sum

        w = w * _bcast(g.edge_mask, w)
        # edge_valid: the kernel's schedule skips masked-edge blocks
        # outright (~half the slots at flagship padding ratios)
        return gather_mul_segment_sum(x, w, g.senders, g.receivers,
                                      edge_valid=g.edge_mask)
    return segment_sum(
        x[g.senders] * w, g.receivers, x.shape[0], g.edge_mask)


def gather_segment(x, g):
    """Plain neighbor sum ``out[n] = sum_{e: recv[e]=n} x[send[e]]`` over
    real edges — fused-kernel path when available (same dispatch rules as
    :func:`gather_mul_segment`), else gather + masked segment_sum."""
    fused = bool(g.extras) and "edge_perm_sender" in g.extras
    _count("gather_sum", fused)
    if fused:
        from hydragnn_tpu.ops.fused_mp import gather_segment_sum

        return gather_segment_sum(x, g.senders, g.receivers, g.edge_mask)
    return segment_sum(
        x[g.senders], g.receivers, x.shape[0], g.edge_mask)


def gather_segment_mean(x, g):
    """Masked neighbor mean ``out[n] = mean_{e: recv[e]=n} x[send[e]]``
    (zero where a node has no real edges uses the max(count,1) convention
    of :func:`segment_mean`) — the sum lowers to the fused kernel when
    available."""
    total = gather_segment(x, g)
    deg = degree(g.receivers, x.shape[0], g.edge_mask)
    return total / jnp.maximum(deg, 1.0)[:, None]


def segment_count(segment_ids, num_segments, mask=None, dtype=jnp.float32):
    ones = jnp.ones((segment_ids.shape[0],), dtype)
    if mask is not None:
        ones = ones * mask.astype(dtype)
    return jax.ops.segment_sum(ones, segment_ids, num_segments)


def _mean_divide(total, count):
    """The one definition of the empty-segment convention: mean uses
    max(count, 1) so empty segments read zero, not NaN."""
    return total / _bcast(jnp.maximum(count, 1.0), total)


def segment_mean(data, segment_ids, num_segments, mask=None):
    total = segment_sum(data, segment_ids, num_segments, mask)
    count = segment_count(segment_ids, num_segments, mask)
    return _mean_divide(total, count)


def segment_max(data, segment_ids, num_segments, mask=None):
    """Max-reduce; empty/masked segments yield 0 (matching PyG conventions)."""
    if mask is not None:
        data = jnp.where(_bcast(mask, data) > 0, data, -_BIG)
    out = jax.ops.segment_max(data, segment_ids, num_segments)
    return jnp.where(out <= -_BIG * 0.5, 0.0, out)


def segment_min(data, segment_ids, num_segments, mask=None):
    if mask is not None:
        data = jnp.where(_bcast(mask, data) > 0, data, _BIG)
    out = jax.ops.segment_min(data, segment_ids, num_segments)
    return jnp.where(out >= _BIG * 0.5, 0.0, out)


def segment_std(data, segment_ids, num_segments, mask=None, eps=1e-5):
    """Per-segment standard deviation (PNA 'std' aggregator numerics)."""
    mean = segment_mean(data, segment_ids, num_segments, mask)
    sq_mean = segment_mean(data * data, segment_ids, num_segments, mask)
    var = jnp.maximum(sq_mean - mean * mean, 0.0)
    return jnp.sqrt(var + eps)


def segment_softmax(logits, segment_ids, num_segments, mask=None):
    """Numerically-stable softmax within segments (GATv2 attention).

    Padded entries (mask == 0) get zero weight.
    """
    if mask is not None:
        logits = jnp.where(_bcast(mask, logits) > 0, logits, -_BIG)
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments)
    seg_max = jnp.where(seg_max <= -_BIG * 0.5, 0.0, seg_max)
    logits = logits - seg_max[segment_ids]
    unnorm = jnp.exp(logits)
    if mask is not None:
        unnorm = unnorm * _bcast(mask, unnorm)
    denom = jax.ops.segment_sum(unnorm, segment_ids, num_segments)
    return unnorm / jnp.maximum(denom, 1e-16)[segment_ids]


def degree(receivers, num_nodes, mask=None):
    """In-degree per node (reference computes degree on edge_index[1];
    hydragnn/preprocess/utils.py:188)."""
    return segment_count(receivers, num_nodes, mask)


def sorted_segment_sum(data, segment_ids, num_segments, mask=None,
                       sorted_hint=False):
    """Masked segment sum that rides the dense-schedule sorted scatter
    kernel when the caller vouches (``sorted_hint``) that ``segment_ids``
    are nondecreasing; else the standard masked segment_sum.  Masking
    happens BEFORE the dense scatter — padding rows park on real slots, so
    an unmasked dense scatter would corrupt them."""
    _count("sorted_sum", bool(sorted_hint))
    if sorted_hint:
        from hydragnn_tpu.ops.fused_mp import segment_sum_dense

        if mask is not None:
            data = data * _bcast(mask, data)
        # masked rows park out of range -> their blocks are schedule-
        # skipped (collate/add_dimenet_extras keep padding tail-sorted)
        return segment_sum_dense(data, segment_ids, num_segments,
                                 valid=mask)
    return segment_sum(data, segment_ids, num_segments, mask)


def scatter_segment(data, g):
    """Receiver-side MASKED segment sum of already-edge-valued ``data``
    (CGCNN's gated messages, PNA aggregates): lowers to the dense-schedule
    sorted scatter kernel when the batch carries collate's
    verified-invariants marker (``edge_perm_sender``), else the masked
    segment_sum.  Always edge-masked — padding edges park on a real node
    slot, so an unmasked dense scatter would corrupt it."""
    _count("scatter_sum", bool(g.extras and "edge_perm_sender" in g.extras))
    if g.extras and "edge_perm_sender" in g.extras:
        from hydragnn_tpu.ops.fused_mp import segment_sum_dense

        data = data * _bcast(g.edge_mask, data)
        # valid: schedule-skips padding-edge blocks (collate parks them
        # zero-valued and tail-sorted)
        return segment_sum_dense(data, g.receivers, g.num_nodes,
                                 valid=g.edge_mask)
    return segment_sum(data, g.receivers, g.num_nodes, g.edge_mask)


def masked_mean_pool(x, node_gid, num_graphs, node_mask, sorted_hint=False):
    """Per-graph mean over *real* nodes — parity with PyG global_mean_pool
    (reference hydragnn/models/Base.py:296) under padding.  ``sorted_hint``
    (set by Base.forward when the batch carries collate's
    verified-invariants marker) routes the sum through the dense-schedule
    sorted scatter kernel — collate's node_gid is nondecreasing by
    construction.

    Shard-aware: under an active halo-sharding trace (graph/partition.py:
    halo_context) a graph's nodes span shards, so the per-shard partial
    sums and counts are psum-ed across the mesh axis before the divide —
    every shard sees the exact global per-graph means."""
    from hydragnn_tpu.graph.partition import halo_axes, halo_psum

    _count("mean_pool", bool(sorted_hint))
    if sorted_hint and halo_axes() is None:
        from hydragnn_tpu.ops.fused_mp import segment_sum_dense

        total = segment_sum_dense(
            x * _bcast(node_mask, x), node_gid, num_graphs)
        count = segment_count(node_gid, num_graphs, node_mask)
        return _mean_divide(total, count)
    total = halo_psum(segment_sum(x, node_gid, num_graphs, node_mask))
    count = halo_psum(segment_count(node_gid, num_graphs, node_mask))
    return _mean_divide(total, count)


def masked_sum_pool(x, node_gid, num_graphs, node_mask):
    from hydragnn_tpu.graph.partition import halo_psum

    return halo_psum(segment_sum(x, node_gid, num_graphs, node_mask))


# ---------------------------------------------------------------------------
# multi-moment (poly) aggregation: sum/sq-derived mean+std, max, min, count
# in ONE fused pass (ops/poly_mp.py) when the batch carries the collate
# marker — the PNA-class multi-aggregator archs' hot path
# ---------------------------------------------------------------------------

def _poly_public_keys():
    """Public moment vocabulary, DERIVED from the kernel's MOMENT_ORDER
    (ops/poly_mp.py owns the contract): the combined ``mxmn`` kernel
    output splits into the ``mx``/``mn`` keys callers consume."""
    from hydragnn_tpu.ops.poly_mp import MOMENT_ORDER

    keys = []
    for m in MOMENT_ORDER:
        keys.extend(("mx", "mn") if m == "mxmn" else (m,))
    return tuple(keys)


def _poly_kernel_moments(moments):
    from hydragnn_tpu.ops.poly_mp import MOMENT_ORDER

    want = set(moments)
    unknown = want - set(_poly_public_keys())
    if unknown or not want:
        raise ValueError(f"moments must be a nonempty subset of "
                         f"{_poly_public_keys()}, got {moments!r}")
    return tuple(
        m for m in MOMENT_ORDER
        if m in want or (m == "mxmn" and ("mx" in want or "mn" in want)))


def _poly_unpack(kern_moments, outs, moments, f):
    """Kernel tuple -> {requested key: cleaned array}.  mx/mn get the
    segment_max/min empty-segment zero-clean (same convention as
    :func:`segment_max` / :func:`segment_min`)."""
    res: Dict[str, jax.Array] = {}
    by = dict(zip(kern_moments, outs))
    if "sum" in moments:
        res["sum"] = by["sum"]
    if "sq" in moments:
        res["sq"] = by["sq"]
    if "mx" in moments or "mn" in moments:
        # clean threshold derives from the KERNEL's empty-segment
        # sentinel (poly_mp._NEG), not segment.py's _BIG — retuning one
        # must not silently break the other
        from hydragnn_tpu.ops.poly_mp import _NEG

        mxmn = by["mxmn"]
        if "mx" in moments:
            mx = mxmn[:, :f]
            res["mx"] = jnp.where(mx <= _NEG * 0.5, 0.0, mx)
        if "mn" in moments:
            neg = mxmn[:, f:]
            res["mn"] = jnp.where(neg <= _NEG * 0.5, 0.0, -neg)
    if "cnt" in moments:
        res["cnt"] = by["cnt"]
    return res


def _poly_composed(moments, g, data_fn, sum_fn):
    """Composed fallback shared by both poly dispatchers: ``data_fn``
    lazily yields the edge-valued messages (only materialized when a
    beyond-sum moment needs them), ``sum_fn`` the masked segment sum of
    the raw inputs (which may itself still ride a fused sum kernel when
    only the poly WIDTH gate failed)."""
    res: Dict[str, jax.Array] = {}
    if "sum" in moments:
        res["sum"] = sum_fn()
    data = (data_fn() if ("sq" in moments or "mx" in moments
                          or "mn" in moments) else None)
    n = g.num_nodes
    if "sq" in moments:
        # scatter_segment re-dispatches like the sum: still the dense
        # kernel when only the poly width gate failed (data is
        # edge-valued here in BOTH modes)
        res["sq"] = scatter_segment(data * data, g)
    if "mx" in moments or "mn" in moments:
        f = data.shape[-1]
        mxmn = segment_max(jnp.concatenate([data, -data], axis=-1),
                           g.receivers, n, g.edge_mask)
        if "mx" in moments:
            res["mx"] = mxmn[:, :f]
        if "mn" in moments:
            res["mn"] = -mxmn[:, f:]
    if "cnt" in moments:
        res["cnt"] = degree(g.receivers, n, g.edge_mask)
    return res


def _poly_fused_ok(g, f: int, moments) -> bool:
    from hydragnn_tpu.ops.poly_mp import POLY_MAX_F, POLY_MAX_F_MXMN

    if not (g.extras and "edge_perm_sender" in g.extras):
        return False
    limit = (POLY_MAX_F_MXMN if ("mx" in moments or "mn" in moments)
             else POLY_MAX_F)
    return f <= limit


def poly_scatter_segment(data, g, moments: Sequence[str]):
    """Multi-moment masked segment reduce of already-edge-valued ``data``
    [E, F] at receivers: returns a dict with the requested subset of

      sum [N, F], sq [N, F] (sum of squares), mx/mn [N, F] (max/min over
      REAL edges, 0 on empty nodes — the segment_max/min convention),
      cnt [N] (real in-edges, == :func:`degree`).

    One fused Pallas pass (ops/poly_mp.py) when the batch carries
    collate's verified-invariants marker AND F fits the kernel's width
    gate (POLY_MAX_F_MXMN with mx/mn, POLY_MAX_F otherwise); composed
    segment ops otherwise.  mean/std are elementwise outside:
    ``sum / max(cnt, 1)`` and the :func:`segment_std` formula."""
    kern = _poly_kernel_moments(moments)
    if kern == ("sum",):
        # pure sum: scatter_segment's single-moment dense kernel already
        # does this exact job (and is compiled in the same program for
        # pooling) — don't trace a second near-identical Pallas kernel
        return {"sum": scatter_segment(data, g)}
    f = data.shape[-1]
    fused = _poly_fused_ok(g, f, moments)
    _count("poly_scatter", fused)
    if fused:
        from hydragnn_tpu.ops.poly_mp import segment_poly_dense

        outs = segment_poly_dense(data, g.receivers, g.num_nodes, kern,
                                  valid=g.edge_mask)
        return _poly_unpack(kern, outs, moments, f)
    # scatter_segment re-dispatches the sum: still the dense kernel when
    # only the poly width gate failed
    return _poly_composed(moments, g, lambda: data,
                          lambda: scatter_segment(data, g))


def poly_gather_segment(x, g, moments: Sequence[str]):
    """Multi-moment reduce of the gathered neighbor messages
    ``x[senders]`` over REAL edges — same result dict as
    :func:`poly_scatter_segment`, but the fused path forms the messages
    in-VMEM (one-hot window gather) so the [E, F] tensor never hits HBM.
    The SAGE/MFC neighbor aggregation (sum + cnt in one pass replaces the
    separate neighbor-sum and degree scatters)."""
    kern = _poly_kernel_moments(moments)
    if kern == ("sum",):
        # pure sum: gather_segment's existing fused kernel is this job
        return {"sum": gather_segment(x, g)}
    f = x.shape[-1]
    perm = g.extras.get("edge_perm_sender") if g.extras else None
    fused = perm is not None and _poly_fused_ok(g, f, moments)
    _count("poly_gather", fused)
    if fused:
        from hydragnn_tpu.ops.poly_mp import gather_poly_segment

        outs = gather_poly_segment(x, g.senders, g.receivers, perm, kern,
                                   mask=g.edge_mask)
        return _poly_unpack(kern, outs, moments, f)
    # gather_segment re-dispatches the sum: a marker-present batch that
    # only failed the poly WIDTH gate still rides the fused sum kernel
    return _poly_composed(moments, g, lambda: x[g.senders],
                          lambda: gather_segment(x, g))


def _bcast(mask, data):
    """Broadcast a [E]/[N] mask against [E, ...] data."""
    if mask.ndim == data.ndim:
        return mask.astype(data.dtype)
    return mask.reshape(mask.shape + (1,) * (data.ndim - mask.ndim)).astype(data.dtype)

def gather_receiver_sorted(x, g):
    """``x[receivers]`` whose BACKWARD is the dense-schedule sorted scatter
    (receivers are nondecreasing by collate invariant) instead of XLA's
    scatter-add — marker-gated, plain gather otherwise."""
    if g.extras and "edge_perm_sender" in g.extras:
        return _gather_dense_bwd(x, g.receivers, None)
    return x[g.receivers]


def gather_perm(x, idx, perm):
    """``x[idx]`` for an ARBITRARY index vector whose backward rides the
    dense sorted-scatter kernel through a host-precomputed stable argsort
    ``perm`` of ``idx`` (DimeNet's triplet-side ``idx_kj`` gathers).  Same
    zero-cotangent requirement as the other dense-backward gathers (see
    :func:`_gather_dense_bwd`)."""
    return _gather_dense_bwd(x, idx, perm)


def gather_sender(x, g):
    """``x[senders]`` whose BACKWARD rides the dense scatter through
    collate's sender-sorted permutation — marker-gated."""
    perm = g.extras.get("edge_perm_sender") if g.extras else None
    if perm is not None:
        return _gather_dense_bwd(x, g.senders, perm)
    return x[g.senders]


@jax.custom_vjp
def _gather_dense_bwd(x, idx, perm):
    """Gather with a dense-sorted-scatter backward.

    ZERO-COTANGENT REQUIREMENT: the backward scatters the incoming
    cotangent UNMASKED.  Padding rows of ``idx`` park on a REAL slot
    (node N-1 / edge E-1 by collate convention), so every caller must
    guarantee the cotangent is exactly zero on padding rows — i.e. the
    gathered value must be multiplied by the edge/triplet mask somewhere
    downstream before any loss.  All current call sites
    (gather_sender/gather_receiver_sorted/gather_perm) satisfy this; a
    new unmasked consumer would silently corrupt the parked slot's
    gradient."""
    return x[idx]


def _gdb_fwd(x, idx, perm):
    return x[idx], (idx, perm, x.shape)


def _gdb_bwd(res, grad):
    idx, perm, shape = res
    from hydragnn_tpu.ops.fused_mp import segment_sum_dense

    g2 = grad.reshape(grad.shape[0], -1)
    if perm is not None:
        out = segment_sum_dense(g2[perm], idx[perm], shape[0])
    else:
        out = segment_sum_dense(g2, idx, shape[0])
    return out.reshape(shape), None, None


_gather_dense_bwd.defvjp(_gdb_fwd, _gdb_bwd)
