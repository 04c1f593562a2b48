"""Fused message-passing kernel: gather -> edge-multiply -> segment-sum in
one Pallas pass.

The CFConv-style core ``out[n] = sum_{e: recv[e]=n} x[send[e]] * w[e]`` is
the hot op of every conv stack.  XLA executes it as gather + multiply +
scatter; measured on the v5e the gather/scatter machinery dominates the
step's HBM traffic (cost model: 7.3 GB/step for the flagship SchNet, and
bf16-casting the features removes only ~3% of it), putting the step at the
bandwidth roofline.

This kernel exploits two invariants the collate layer guarantees
(graph/batch.py):

1. ``receivers`` are NONDECREASING (per-sample edge lists concatenated with
   node offsets), so each output node-block owns a contiguous edge range —
   scalar-prefetched searchsorted offsets steer the edge-block DMAs and no
   sort/scatter ever happens.
2. Edges are INTRA-GRAPH and graphs are stored contiguously, so the senders
   of a node block's edges lie within the adjacent node blocks — a 3-block
   x window (gathered as a block-local one-hot contraction on the MXU)
   replaces the global row gather, provided every graph fits in one node
   block (``max_nodes_per_graph <= _NODE_BLOCK``; callers must fall back to
   the XLA path otherwise).

Padding edges (parked on node N-1 by collate with edge_mask 0) contribute
nothing: the caller's pre-masked ``w`` zeroes them, and out-of-window
one-hot rows are all-zero anyway.

The grid is a DENSE CSR-style schedule: scalar-prefetched step tables map
each grid step to one populated (node-block, edge-block) pair, so no step
is a wasted DMA and — unlike a rectangular (block, k_max) grid bounded by a
declared max degree — ANY degree distribution is processed exactly (total
steps are unconditionally <= edge blocks + 2 * node blocks).

Backward: dL/dw = x[senders] * g[receivers] (two XLA gathers — the
receivers gather is sorted and cheap); dL/dx reuses THIS kernel on the
sender-sorted edge ordering (host-precomputed permutation: sorting edges by
sender turns the sender-scatter into another sorted-receiver segment sum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import (  # noqa: F401 — canonical home;
    _NODE_BLOCK, _dense_schedule)           # re-exported for back-compat


_EDGE_BLOCK = 512   # edges per inner step


def _fwd_kernel(has_w, window, si_ref, se_ref, av_ref, fi_ref, send_ref,
                recv_ref, *rest):
    from jax.experimental import pallas as pl

    if has_w:
        w_ref = rest[0]
    else:
        # w omitted: messages are the gathered features themselves, scaled
        # by the scalar edge mask (GIN/MFC-style sum aggregation)
        mask_ref = rest[0]
    xwin_refs = rest[1:1 + window]
    out_ref = rest[1 + window]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_ref.shape[0]
        be = send_ref.shape[0]
        # window rows are blocks [i-hw .. i+hw]; at the boundaries the
        # clamped duplicate slots are unreachable because the base stays
        # (i-hw)*bn (negative at the low edge is fine — senders then map
        # into the later window rows, never the duplicated ones)
        hw = window // 2
        base = (i - hw) * bn
        sloc = send_ref[:] - base                       # [BE, 1]
        onehot_s = (sloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, window * bn), 1)).astype(jnp.float32)
        xcat = jnp.concatenate(
            [r[:] for r in xwin_refs], axis=0).astype(jnp.float32)
        msgs = jax.lax.dot_general(
            onehot_s, xcat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [BE, F]
        if has_w:
            msgs = msgs * w_ref[:].astype(jnp.float32)
        else:
            msgs = msgs * mask_ref[:].astype(jnp.float32)
        rloc = recv_ref[:] - i * bn
        onehot_r = (rloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        out_ref[:] += jax.lax.dot_general(
            onehot_r, msgs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [BN, F]


def _fused_impl(x, w, senders, receivers, interpret, mask=None, window=3,
                edge_valid=None, kernel_name="gather_mul_seg_fwd"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    has_w = w is not None
    n, f = x.shape
    e = w.shape[0] if has_w else senders.shape[0]
    bn, be = _NODE_BLOCK, _EDGE_BLOCK
    n_pad = _round_up(n, bn)
    e_pad = _round_up(max(e, 1), be)
    f_pad = _round_up(max(f, 1), 128)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    x_p = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x)
    if has_w:
        w_p = jnp.zeros((e_pad, f_pad), w.dtype).at[:e, :f].set(w)
    else:
        m = (jnp.ones((e,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        w_p = jnp.zeros((e_pad, 1), jnp.float32).at[:e, 0].set(m)
    # shape-padding edges: park outside every block/window so they can't
    # contribute even with nonzero data (their w rows are zero anyway).
    # MASK-padding edges (edge_valid == 0 — the batch's own padding, ~half
    # the edge slots at flagship collate shapes) are parked the same way,
    # so the dense schedule assigns their edge blocks to NO node block and
    # never spends a step on them.  Contract (callers): masked edges carry
    # zero w/mask AND sort after all real edges in the current ordering
    # (collate parks them on node N-1, the maximum id, so both the
    # receiver sort and the stable sender argsort keep them last).
    if edge_valid is not None:
        ev = edge_valid != 0
        senders = jnp.where(ev, senders, n_pad)
        receivers = jnp.where(ev, receivers, n_pad)
    send_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        senders.astype(jnp.int32))
    recv_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        receivers.astype(jnp.int32))

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)

    def eix(s, si, se, av, fi):
        return (se[s], 0)

    def xoff(off):
        def f(s, si, se, av, fi):
            return (jnp.clip(si[s] + off, 0, n_blocks - 1), 0)
        return f

    assert window % 2 == 1, "window must be odd"
    hw = window // 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, f_pad if has_w else 1), eix),
        ] + [pl.BlockSpec((bn, f_pad), xoff(o))
             for o in range(-hw, hw + 1)],
        out_specs=pl.BlockSpec(
            (bn, f_pad), lambda s, si, se, av, fi: (si[s], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, has_w, window),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=kernel_name,
    )(step_i, step_eb, acc_valid, is_first, send_p, recv_p, w_p,
      *([x_p] * window))
    return out[:n, :f].astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gather_mul_segment_sum(x, w, senders, receivers, sender_perm,
                           window=3, edge_valid=None):
    """``out[n, f] = sum_{e: recv[e]=n} x[send[e], f] * w[e, f]``.

    REQUIRES (collate invariants — see module docstring): nondecreasing
    ``receivers``; intra-graph edges, graphs contiguous, every graph within
    ``_NODE_BLOCK`` nodes; ``w`` pre-masked (zero rows on padding edges).
    No degree bound: the dense schedule processes every populated
    (node-block, edge-block) pair exactly once.  ``sender_perm`` is the
    host-precomputed stable argsort of ``senders`` (collate emits it once
    per batch) used by the backward; pass None for a forward-only call.
    Exact (f32 accumulation, deterministic order); differentiable wrt x
    and w.

    ``window`` (odd, static) widens the sender one-hot window: segment i
    gathers from blocks i-w//2..i+w//2 — 3 suffices for node-space message
    passing (graphs within one node block); DimeNet's triplet interaction
    runs in EDGE space where graphs span up to ~2 blocks and needs 5.

    ``edge_valid`` (optional int mask, 1 = real) lets the schedule SKIP
    masked-edge blocks outright (halves scheduled work at flagship
    padding ratios).  Contract: edge_valid == 0 edges carry zero ``w``
    rows and sort after all real edges in BOTH edge orderings (collate
    guarantees this).  Their dw cotangent is computed densely and is
    GARBAGE: a skipped edge contributes nothing forward, so its true
    gradient is zero, but the dense ``x[send] * g[recv]`` formula reads
    the padding node's rows instead — callers must not consume dw on
    masked edges; the caller's w-premask multiply must kill it (same
    contract as :func:`~hydragnn_tpu.ops.scf_mp.scf_edge_pipeline`'s
    masked-edge grads).
    """
    interpret = jax.default_backend() != "tpu"
    return _fused_impl(x, w, senders, receivers, interpret, window=window,
                       edge_valid=edge_valid)


def _vjp_fwd(x, w, senders, receivers, sender_perm, window=3,
             edge_valid=None):
    out = gather_mul_segment_sum(x, w, senders, receivers, sender_perm,
                                 window, edge_valid)
    return out, (x, w, senders, receivers, sender_perm, edge_valid)


def _vjp_bwd(window, res, g):
    x, w, senders, receivers, sender_perm, edge_valid = res
    # dL/dw[e] = x[send[e]] * g[recv[e]] — plain gathers (recv gather is
    # over sorted indices)
    dw = (x[senders] * g[receivers]).astype(w.dtype)
    # dL/dx[n] = sum_{e: send[e]=n} w[e] * g[recv[e]]: on the sender-sorted
    # ordering this is the SAME fused sorted-receiver kernel with the edge
    # roles swapped
    if sender_perm is None:
        sender_perm = jnp.argsort(senders, stable=True)
    dx = _fused_impl(
        g.astype(jnp.float32), w[sender_perm].astype(jnp.float32),
        receivers[sender_perm], senders[sender_perm],
        jax.default_backend() != "tpu", window=window,
        edge_valid=None if edge_valid is None else edge_valid[sender_perm],
        kernel_name="gather_mul_seg_bwd")
    return dx.astype(x.dtype), dw, None, None, None, None


gather_mul_segment_sum.defvjp(_vjp_fwd, _vjp_bwd)


@jax.custom_vjp
def gather_segment_sum(x, senders, receivers, sender_perm, mask=None):
    """``out[n] = sum_{e: recv[e]=n} mask[e] * x[send[e]]`` — the w-less
    variant (GIN/MFC-style neighbor sum) with the same invariants as
    :func:`gather_mul_segment_sum`; ``mask`` is the [E] edge mask (padding
    edges contribute nothing — and their blocks are schedule-skipped, so
    mask == 0 edges must sort after all real edges, which collate
    guarantees).  Differentiable wrt ``x`` only."""
    interpret = jax.default_backend() != "tpu"
    return _fused_impl(x, None, senders, receivers, interpret, mask=mask,
                       edge_valid=mask)


def _gss_fwd(x, senders, receivers, sender_perm, mask=None):
    out = gather_segment_sum(x, senders, receivers, sender_perm, mask)
    return out, (senders, receivers, sender_perm, mask)


def _gss_bwd(res, g):
    senders, receivers, sender_perm, mask = res
    if sender_perm is None:
        sender_perm = jnp.argsort(senders, stable=True)
    interpret = jax.default_backend() != "tpu"
    mp = None if mask is None else mask[sender_perm]
    dx = _fused_impl(
        g.astype(jnp.float32), None, receivers[sender_perm],
        senders[sender_perm], interpret, mask=mp, edge_valid=mp,
        kernel_name="gather_mul_seg_bwd")
    return dx.astype(g.dtype), None, None, None, None


gather_segment_sum.defvjp(_gss_fwd, _gss_bwd)


# ---------------------------------------------------------------------------
# scatter-only variant: sorted segment sum on the dense schedule (no gather)
# — replaces XLA's sort-based scatter for already-edge-valued data (CGCNN's
# gated messages, PNA aggregates, masked pooling over node_gid)
# ---------------------------------------------------------------------------

def _scatter_kernel(si_ref, se_ref, av_ref, fi_ref, ids_ref, data_ref,
                    out_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_ref.shape[0]
        be = ids_ref.shape[0]
        loc = ids_ref[:] - i * bn
        onehot = (loc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        out_ref[:] += jax.lax.dot_general(
            onehot, data_ref[:].astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _scatter_impl(data2d, sorted_ids, num_segments, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data2d.shape
    bn, be = _NODE_BLOCK, _EDGE_BLOCK
    n_pad = _round_up(num_segments, bn)
    e_pad = _round_up(max(e, 1), be)
    f_pad = _round_up(max(f, 1), 128)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    data_p = jnp.zeros((e_pad, f_pad), data2d.dtype).at[:e, :f].set(data2d)
    ids_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        sorted_ids.astype(jnp.int32))

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        ids_p[:, 0], n_blocks, bn, be, n_eblocks)

    def eix(s, si, se, av, fi):
        return (se[s], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, f_pad), eix),
        ],
        out_specs=pl.BlockSpec(
            (bn, f_pad), lambda s, si, se, av, fi: (si[s], 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="seg_sum_dense_fwd",
    )(step_i, step_eb, acc_valid, is_first, ids_p, data_p)
    return out[:num_segments, :f].astype(data2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_dense(data, sorted_ids, num_segments, valid=None):
    """Exact segment sum REQUIRING nondecreasing ``sorted_ids`` (collate's
    receivers / node_gid invariant) — one dense-schedule Pallas pass
    instead of XLA's sort-based scatter.  Any id distribution is processed
    exactly (no degree bound); out-of-range ids contribute nothing.
    ``valid`` (optional int mask, 1 = real) parks masked rows out of
    range so the schedule skips their blocks; masked rows must carry zero
    ``data`` and sort last (collate guarantees both for padding edges).
    Differentiable wrt ``data``."""
    shape = data.shape
    interpret = jax.default_backend() != "tpu"
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    out = _scatter_impl(
        data.reshape(shape[0], -1), sorted_ids, num_segments, interpret)
    return out.reshape((num_segments,) + shape[1:])


def _ssd_fwd(data, sorted_ids, num_segments, valid=None):
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    return segment_sum_dense(data, sorted_ids, num_segments), (
        sorted_ids, data.shape)


def _ssd_bwd(num_segments, res, g):
    sorted_ids, shape = res
    g2 = g.reshape(num_segments, -1)
    ok = (sorted_ids >= 0) & (sorted_ids < num_segments)
    safe = jnp.clip(sorted_ids, 0, num_segments - 1)
    d = jnp.where(ok[:, None], g2[safe], 0.0)
    return d.reshape(shape), None, None


segment_sum_dense.defvjp(_ssd_fwd, _ssd_bwd)
