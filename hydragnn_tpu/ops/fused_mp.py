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
   sort/scatter ever happens.  Read the other way: each edge block belongs
   to a contiguous run of node blocks, so a per-edge OUTPUT stream can be
   written from the same pass.
2. Edges are INTRA-GRAPH and graphs are stored contiguously, so the senders
   of a node block's edges lie within the adjacent node blocks — a 3-block
   x window (gathered as a block-local one-hot contraction on the MXU)
   replaces the global row gather, provided every graph fits in one node
   block (``max_nodes_per_graph <= _NODE_BLOCK``; callers must fall back to
   the XLA path otherwise).  Read the other way: the edges of node block i
   scatter to their senders only inside blocks i-hw..i+hw, so the
   transposed window one-hot replaces the global row scatter.

Padding edges (parked on node N-1 by collate with edge_mask 0) contribute
nothing: the caller's pre-masked ``w`` zeroes them, and out-of-window
one-hot rows are all-zero anyway.

The grid is a DENSE CSR-style schedule: scalar-prefetched step tables map
each grid step to one populated (node-block, edge-block) pair, so no step
is a wasted DMA and — unlike a rectangular (block, k_max) grid bounded by a
declared max degree — ANY degree distribution is processed exactly (total
steps are unconditionally <= edge blocks + 2 * node blocks).

Backward: ONE pass (``gather_mul_seg_bwd``) on the same schedule, over the
edge list in the order collate ships it — nothing is sorted by sender and
XLA gathers, permutes or pads nothing E-sized.  Per (node block i, edge
block) step, with the two one-hots the forward builds:
``g_r = onehot_r @ g_i`` (block-local, zero rows for the edges of other
node blocks — the gate that counts every edge once), ``x_s = onehot_s @
x_window``, ``dw = x_s * g_r`` streamed out per edge (first accumulated
visit of an edge block overwrites, a boundary block's later visits add),
and ``P_i += onehot_s^T @ (w * g_r)`` — node block i's contributions to
``dx`` in window coordinates, ``[W * bn, F]`` per node block.  ``dx`` is
the overlap-add of the ``P_i`` in node space (slot k of block i lands on
block i - hw + k; slots off either end are dropped).  Edge blocks holding
only parked edges are never entered, so their ``dw`` rows are unwritten
memory, selected to exact zero.  The w-less op runs the same body without
the ``x`` window and the ``dw`` stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import (  # noqa: F401 — canonical home;
    _NODE_BLOCK, _dense_schedule)           # re-exported for back-compat
from hydragnn_tpu.ops.fused_block import _window_maps


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


def _pack(x, w, senders, receivers, mask=None, edge_valid=None):
    """Zero-pad the operands to whole blocks — ``(x_p, w_p, send_p,
    recv_p)``, the arrays both passes run on (the forward rule saves them
    as its residuals, so the backward pads nothing a second time).

    Shape-padding edges are parked outside every block/window so they
    can't contribute even with nonzero data (their w rows are zero
    anyway).  MASK-padding edges (edge_valid == 0 — the batch's own
    padding, ~half the edge slots at flagship collate shapes) are parked
    the same way, so the dense schedule assigns their edge blocks to NO
    node block and never accumulates them.  Contract (callers): masked
    edges carry zero w/mask AND sort after all real edges (collate parks
    them on node N-1, the maximum id)."""
    has_w = w is not None
    n, f = x.shape
    e = w.shape[0] if has_w else senders.shape[0]
    n_pad = _round_up(n, _NODE_BLOCK)
    e_pad = _round_up(max(e, 1), _EDGE_BLOCK)
    f_pad = _round_up(max(f, 1), 128)

    # lax.pad, not zeros().at[].set(): the same one copy forward, and its
    # transpose is a slice — the scatter's would be an [E, F] gather
    x_p = jnp.pad(x, ((0, n_pad - n), (0, f_pad - f)))
    if has_w:
        w_p = jnp.pad(w, ((0, e_pad - e), (0, f_pad - f)))
    else:
        # w omitted: the [E, 1] edge-mask column rides in w's slot
        m = (jnp.ones((e,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        w_p = jnp.pad(m, (0, e_pad - e))[:, None]
    if edge_valid is not None:
        ev = edge_valid != 0
        senders = jnp.where(ev, senders, n_pad)
        receivers = jnp.where(ev, receivers, n_pad)

    def ids(v):
        return jnp.pad(v.astype(jnp.int32), (0, e_pad - e),
                       constant_values=n_pad)[:, None]

    send_p, recv_p = ids(senders), ids(receivers)
    return x_p, w_p, send_p, recv_p


def _fwd_call(has_w, window, x_p, w_p, send_p, recv_p):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert window % 2 == 1, "window must be odd"
    bn, be = _NODE_BLOCK, _EDGE_BLOCK
    n_pad, f_pad = x_p.shape
    n_blocks, n_eblocks = n_pad // bn, send_p.shape[0] // be
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)
    eix, xoff, _, outx = _window_maps(n_blocks)

    hw = window // 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, w_p.shape[1]), eix),
        ] + [pl.BlockSpec((bn, f_pad), xoff(o))
             for o in range(-hw, hw + 1)],
        out_specs=pl.BlockSpec((bn, f_pad), outx),
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, has_w, window),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=jax.default_backend() != "tpu",
        name="gather_mul_seg_fwd",
    )(step_i, step_eb, acc_valid, is_first, send_p, recv_p, w_p,
      *([x_p] * window))


# ---------------------------------------------------------------------------
# backward: ONE pass over the edge list in the order collate ships it
# ---------------------------------------------------------------------------


def _bwd_kernel(has_w, window, si_ref, se_ref, av_ref, fi_ref, fe_ref,
                send_ref, recv_ref, w_ref, g_ref, *rest):
    from jax.experimental import pallas as pl

    xwin_refs = rest[:window] if has_w else ()
    dw_ref = rest[window] if has_w else None
    p_ref = rest[-1]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        p_ref[:] = jnp.zeros_like(p_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = g_ref.shape[0]
        be = send_ref.shape[0]
        # g[recv]: block-local, an edge of another node block gets an
        # all-zero row — it gates every product below, so a boundary edge
        # block contributes each edge exactly once (on its own block's
        # visit)
        rloc = recv_ref[:] - i * bn
        onehot_r = (rloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        g_r = jax.lax.dot_general(
            onehot_r, g_ref[:].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BE, F]
        # the forward's window one-hot (same base, same clamped slots)
        hw = window // 2
        sloc = send_ref[:] - (i - hw) * bn
        onehot_s = (sloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, window * bn), 1)).astype(jnp.float32)
        if has_w:
            xcat = jnp.concatenate(
                [r[:] for r in xwin_refs], axis=0).astype(jnp.float32)
            x_s = jax.lax.dot_general(
                onehot_s, xcat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [BE, F]
            dw = x_s * g_r
            # per-edge stream: overwrite on the edge block's first
            # accumulated visit (the memory is uninitialised — select,
            # never add), accumulate on a boundary block's later visits
            dw_ref[:] = jnp.where(fe_ref[s] == 1, dw, dw_ref[:] + dw)
            m = w_ref[:].astype(jnp.float32) * g_r
        else:
            m = w_ref[:] * g_r                            # mask column
        # dx contributions of node block i's edges, still in WINDOW
        # coordinates (blocks i-hw..i+hw); overlap-added outside
        p_ref[:] += jax.lax.dot_general(
            onehot_s, m, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [W*BN, F]


def _bwd_edge_block(f_pad, window):
    """Edge block of the backward pass: the ``w`` and ``dw`` blocks are
    ``[BE, F_pad]`` f32 each, double-buffered, beside ``[BE, F_pad]``
    temporaries and the ``[W * bn, F_pad]`` window blocks — at wide F a
    512-edge block overruns the default 16 MiB scoped VMEM (AOT-compiled
    for the v5e: 512 fits up to F_pad 1024 at window 3 and 512 at window 5,
    256 fits 1024 at window 5).  Halving divides ``_EDGE_BLOCK``, so the
    forward's padded operands serve unchanged."""
    return _EDGE_BLOCK if f_pad * window <= 2560 else _EDGE_BLOCK // 2


def _bwd_call(has_w, window, x_p, w_p, send_p, recv_p, g_p):
    """``(dx_p, dw_p)`` of the padded problem (``dw_p`` None without w)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn = _NODE_BLOCK
    n_pad, f_pad = g_p.shape
    e_pad = send_p.shape[0]
    be = _bwd_edge_block(f_pad, window)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be
    hw = window // 2
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)
    # every step that accumulates nothing (the forced step of an empty
    # node block, the trailing clamped steps) HOLDS the last accumulated
    # edge block: it fetches nothing and, above all, enters no dw block —
    # so a dw block is entered exactly once, on consecutive steps, and
    # initialised by its first accumulated visit
    held = jax.lax.cummax(jnp.where(acc_valid == 1, step_eb, -1))
    prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), held[:-1]])
    first_e = ((acc_valid == 1) & (step_eb != prev)).astype(jnp.int32)
    step_eb = jnp.maximum(held, 0)
    eix, xoff, _, outx = _window_maps(n_blocks)

    in_specs = [
        pl.BlockSpec((be, 1), eix),
        pl.BlockSpec((be, 1), eix),
        pl.BlockSpec((be, w_p.shape[1]), eix),
        pl.BlockSpec((bn, f_pad), outx),
    ]
    out_specs = [pl.BlockSpec((window * bn, f_pad), outx)]
    out_shape = [jax.ShapeDtypeStruct(
        (n_blocks * window * bn, f_pad), jnp.float32)]
    operands = [send_p, recv_p, w_p, g_p]
    if has_w:
        in_specs += [pl.BlockSpec((bn, f_pad), xoff(o))
                     for o in range(-hw, hw + 1)]
        operands += [x_p] * window
        out_specs.insert(0, pl.BlockSpec((be, f_pad), eix))
        out_shape.insert(0, jax.ShapeDtypeStruct(
            (e_pad, f_pad), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, has_w, window),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s_max,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        interpret=jax.default_backend() != "tpu",
        name="gather_mul_seg_bwd",
    )(step_i, step_eb, acc_valid, is_first, first_e, *operands)

    # overlap-add: slot k of node block i holds its edges' contributions
    # to block i - hw + k; slots that fall off either end hold nothing
    # (no sender is negative, parked edges are gated by g_r) and are
    # dropped, not wrapped
    p = outs[-1].reshape(n_blocks, window, bn, f_pad)
    dx_p = None
    for k in range(window):
        d = k - hw
        lo, hi = max(0, -d), n_blocks - max(0, d)
        if hi <= lo:
            continue
        part = jnp.pad(p[lo:hi, k],
                       ((lo + d, n_blocks - hi - d), (0, 0), (0, 0)))
        dx_p = part if dx_p is None else dx_p + part
    dx_p = dx_p.reshape(n_pad, f_pad)
    if not has_w:
        return dx_p, None
    # edge blocks the schedule never accumulates (parked edges only) are
    # UNINITIALISED memory: select, never multiply (0 * NaN = NaN)
    dw_p = jnp.where(recv_p < n_pad, outs[0], 0.0)
    return dx_p, dw_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gms_padded(has_w, window, x_p, w_p, send_p, recv_p):
    """The op on whole blocks: ``[N_pad, F_pad]`` f32 segment sums.  The
    public ops pad and slice round it in plain jnp, so AD zero-pads the
    cotangent and slices ``dx`` / ``dw`` by itself."""
    return _fwd_call(has_w, window, x_p, w_p, send_p, recv_p)


def _gms_fwd(has_w, window, x_p, w_p, send_p, recv_p):
    out = _fwd_call(has_w, window, x_p, w_p, send_p, recv_p)
    # the w-less backward reads no x: hold its dtype, none of its rows
    return out, (x_p if has_w else x_p[:0], w_p, send_p, recv_p)


def _gms_bwd(has_w, window, res, g_p):
    x_p, w_p, send_p, recv_p = res
    dx_p, dw_p = _bwd_call(has_w, window, x_p, w_p, send_p, recv_p, g_p)
    return (dx_p.astype(x_p.dtype),
            None if dw_p is None else dw_p.astype(w_p.dtype), None, None)


_gms_padded.defvjp(_gms_fwd, _gms_bwd)


def gather_mul_segment_sum(x, w, senders, receivers, window=3,
                           edge_valid=None):
    """``out[n, f] = sum_{e: recv[e]=n} x[send[e], f] * w[e, f]``.

    REQUIRES (collate invariants — see module docstring): nondecreasing
    ``receivers``; intra-graph edges, graphs contiguous, every graph within
    ``_NODE_BLOCK`` nodes; ``w`` pre-masked (zero rows on padding edges).
    No degree bound: the dense schedule processes every populated
    (node-block, edge-block) pair exactly once.  Exact (f32 accumulation,
    deterministic order); differentiable wrt x and w, both gradients from
    one pass over the edges in the order given (module docstring,
    "Backward").

    ``window`` (odd, static) widens the sender one-hot window: segment i
    gathers from blocks i-w//2..i+w//2 — 3 suffices for node-space message
    passing (graphs within one node block); DimeNet's triplet interaction
    runs in EDGE space where graphs span up to ~2 blocks and needs 5.

    ``edge_valid`` (optional int mask, 1 = real) lets the schedule SKIP
    masked-edge blocks outright (halves scheduled work at flagship
    padding ratios).  Contract: edge_valid == 0 edges carry zero ``w``
    rows and sort after all real edges (collate guarantees this).  A
    skipped edge contributes nothing forward, so its true gradient is
    zero, and its ``dw`` row is EXACTLY ZERO (the backward never visits
    its block and selects the unwritten memory away).  Without
    ``edge_valid`` a padding edge is an ordinary edge of the node it is
    parked on: its ``dw`` is that node's finite ``x * g``, which the
    caller's w-premask multiply kills.
    """
    n, f = x.shape
    out = _gms_padded(True, window,
                      *_pack(x, w, senders, receivers, None, edge_valid))
    return out[:n, :f].astype(x.dtype)


def gather_segment_sum(x, senders, receivers, mask=None):
    """``out[n] = sum_{e: recv[e]=n} mask[e] * x[send[e]]`` — the w-less
    variant (GIN/MFC-style neighbor sum) with the same invariants as
    :func:`gather_mul_segment_sum`; ``mask`` is the [E] edge mask (padding
    edges contribute nothing — and their blocks are schedule-skipped, so
    mask == 0 edges must sort after all real edges, which collate
    guarantees).  Differentiable wrt ``x`` only; the backward is the same
    receiver-order pass without the ``dw`` stream."""
    n, f = x.shape
    out = _gms_padded(False, 3,
                      *_pack(x, None, senders, receivers, mask, mask))
    return out[:n, :f].astype(x.dtype)


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
