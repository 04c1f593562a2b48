"""Fused multi-aggregator message passing: sum / sum-of-squares / max / min
/ count in ONE Pallas pass over the sorted-receiver edge blocks.

PNA — the reference framework's flagship conv — needs [mean, min, max, std]
per node.  Composed, that costs two scatter-sums (mean/std share a
sum/sum-of-squares pair), a double-width ``segment_max`` that XLA lowers to
a long sort pipeline, and a separate degree scatter: four passes over the
[E, F] message tensor, each streaming it through HBM.  This kernel rides
the same CSR-style dense schedule as ops/fused_mp.py (scalar-prefetched
step tables over (node-block, edge-block) pairs; see ``_dense_schedule``)
and emits every requested aggregation moment from a single read of each
edge block:

  sum    += onehot_r^T @ msgs                  (MXU)
  sq     += onehot_r^T @ msgs^2                (MXU)
  mxmn    = running max of [msgs, -msgs]       (segmented scan, see below)
  cnt    += column sums of onehot_r            (VPU)

mean and std are ordinary elementwise math OUTSIDE the kernel
(``sum / max(cnt, 1)``; ``sqrt(max(sq/cnt - mean^2, 0) + eps)`` — the
``segment_mean``/``segment_std`` numerics), min is ``-max(-msg)``.

In-kernel segment max WITHOUT a sort and WITHOUT the serial per-row loop
that was measured-and-rejected for the GAT logits max (docs/PERF.md
"measured and rejected", 6.5k g/s): receivers are NONDECREASING, so within
an edge block each node's edges form a contiguous run.  A Hillis-Steele
segmented max-scan (log2(BE) shifted maxima, gated on shifted-id equality
— valid precisely because equal ids are contiguous) leaves each run's LAST
row holding the run max; a 0/1 ``last-of-run`` selector turns the
placement into one onehot matmul (at most one selected row per node per
block, so SUM is exact placement), and a running ``jnp.maximum`` across
grid steps merges runs that span edge-block boundaries.

Modes:
  scatter  — ``data`` is already edge-valued (PNA's pre_nn messages,
             CGCNN's gated messages): moments of ``data`` at receivers.
  gather   — messages are ``x[senders] * mask`` formed in-VMEM via the
             3-block one-hot window (SAGE/MFC neighbor aggregation): the
             [E, F] message tensor never exists in HBM.

Masked/padding edges are parked on the out-of-range sentinel (same
contract as fused_mp: zero-data rows that sort after all real edges), so
the schedule never visits their blocks and they enter no node's max.

Backward (custom VJP, no kernel differentiation):
  d sum / d data[e]  = g_sum[ids[e]]                    (sorted gather)
  d sq  / d data[e]  = 2 data[e] g_sq[ids[e]]
  d mxmn / d data[e] = +- tie(e) g[ids[e]] / n_ties     (even tie split —
                       bit-parity with jax.ops.segment_max's VJP; the tie
                       counts ride ONE segment_sum_dense pass)
  cnt carries no data gradient.
Gather mode chains these through ``msgs = x[send] * mask`` and scatters at
senders via the sender-sorted permutation (collate's ``edge_perm_sender``);
the sum-only case rides fused_mp's receiver-order backward pass directly
with no [E, F] intermediate and no permutation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import _dense_schedule
from hydragnn_tpu.ops.fused_mp import segment_sum_dense

_NODE_BLOCK = 128
_EDGE_BLOCK = 512

# sentinel magnitude: rides matmuls (placement onehot) and exp-free maxima;
# 1e9 keeps reduced-precision contractions from rounding it into inf (the
# gat_mp sentinel rationale)
_NEG = -1e9

# canonical kernel-moment order; public dispatchers map mx/mn onto "mxmn"
MOMENT_ORDER = ("sum", "sq", "mxmn", "cnt")

# widest feature width (pre-padding) the kernel compiles for: the mxmn scan
# holds two [BE, 2*F_pad] f32 temporaries (y + its shift) next to the data
# block and the double-buffered outputs, so the concatenated width is the
# binding one.  Above these the dispatchers fall back to the composed path.
POLY_MAX_F_MXMN = 512
POLY_MAX_F = 1024


def _norm_moments(moments):
    ms = tuple(m for m in MOMENT_ORDER if m in moments)
    unknown = set(moments) - set(MOMENT_ORDER)
    if unknown or not ms:
        raise ValueError(f"moments must be a nonempty subset of "
                         f"{MOMENT_ORDER}, got {moments!r}")
    return ms


def _edge_block(f_pad: int, moments) -> int:
    """Edge-block size keeping the widest per-row temporary (2*f_pad when
    the mxmn scan runs) inside scoped VMEM next to the moment outputs."""
    widest = 2 * f_pad if "mxmn" in moments else f_pad
    return _EDGE_BLOCK if widest <= 512 else 256


def _shift_down(a, d, fill):
    """Rows shifted down by ``d`` (row e reads e-d), top filled."""
    pad = jnp.full((d,) + a.shape[1:], fill, a.dtype)
    return jnp.concatenate([pad, a[: a.shape[0] - d]], axis=0)


def _accum_moments(moments, msgs, onehot_r, rloc, out_refs):
    """Accumulate the requested moments of ``msgs`` [BE, F] into the node
    block's output refs.  ``onehot_r`` [BE, BN] is the receiver one-hot
    (all-zero rows for parked edges), ``rloc`` [BE, 1] the block-local
    receiver ids (>= BN for parked edges — never colliding with real
    locals, so scan runs of parked rows stay separate from real runs)."""
    o = 0
    if "sum" in moments:
        out_refs[o][:] += jax.lax.dot_general(
            onehot_r, msgs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o += 1
    if "sq" in moments:
        out_refs[o][:] += jax.lax.dot_general(
            onehot_r, msgs * msgs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o += 1
    if "mxmn" in moments:
        be = msgs.shape[0]
        y = jnp.concatenate([msgs, -msgs], axis=1)       # [BE, 2F]
        in_block = jnp.sum(onehot_r, axis=1, keepdims=True)  # [BE, 1]
        y = jnp.where(in_block > 0, y, _NEG)
        ids = rloc
        # Hillis-Steele segmented inclusive max-scan: equal ids are
        # CONTIGUOUS (sorted receivers), so gating each shifted max on
        # id equality is exact — after offset d, row e holds the max over
        # the last 2d rows of its run
        d = 1
        while d < be:
            ids_sh = _shift_down(ids, d, -1)
            y_sh = _shift_down(y, d, _NEG)
            y = jnp.where(ids_sh == ids, jnp.maximum(y, y_sh), y)
            d *= 2
        # last row of each id run now holds the run max; one selected row
        # per node per block makes the onehot SUM an exact placement
        ids_nx = jnp.concatenate(
            [ids[1:], jnp.full((1, 1), -2, jnp.int32)], axis=0)
        sel = (ids != ids_nx).astype(jnp.float32)        # [BE, 1]
        pick = onehot_r * sel                            # [BE, BN]
        contrib = jax.lax.dot_general(
            pick, y, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [BN, 2F]
        has = jnp.sum(pick, axis=0)[:, None]             # [BN, 1]
        contrib = jnp.where(has > 0, contrib, _NEG)
        out_refs[o][:] = jnp.maximum(out_refs[o][:], contrib)
        o += 1
    if "cnt" in moments:
        out_refs[o][:] += jnp.broadcast_to(
            jnp.sum(onehot_r, axis=0)[:, None], out_refs[o].shape)


def _init_outs(moments, out_refs):
    for m, ref in zip(moments, out_refs):
        ref[:] = (jnp.full_like(ref, _NEG) if m == "mxmn"
                  else jnp.zeros_like(ref))


def _poly_scatter_kernel(moments, si_ref, se_ref, av_ref, fi_ref,
                         ids_ref, data_ref, *out_refs):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        _init_outs(moments, out_refs)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_refs[0].shape[0]
        be = ids_ref.shape[0]
        rloc = ids_ref[:] - i * bn                       # [BE, 1]
        onehot_r = (rloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        _accum_moments(moments, data_ref[:].astype(jnp.float32),
                       onehot_r, rloc, out_refs)


def _poly_gather_kernel(moments, window, si_ref, se_ref, av_ref, fi_ref,
                        send_ref, recv_ref, mask_ref, *rest):
    from jax.experimental import pallas as pl

    xwin_refs = rest[:window]
    out_refs = rest[window:]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        _init_outs(moments, out_refs)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_refs[0].shape[0]
        be = send_ref.shape[0]
        hw = window // 2
        base = (i - hw) * bn
        sloc = send_ref[:] - base
        onehot_s = (sloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, window * bn), 1)).astype(jnp.float32)
        xcat = jnp.concatenate(
            [r[:] for r in xwin_refs], axis=0).astype(jnp.float32)
        msgs = jax.lax.dot_general(
            onehot_s, xcat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [BE, F]
        msgs = msgs * mask_ref[:].astype(jnp.float32)
        rloc = recv_ref[:] - i * bn
        onehot_r = (rloc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        _accum_moments(moments, msgs, onehot_r, rloc, out_refs)


def _out_layout(moments, f_pad):
    """(width per moment output, in kernel-moment order)."""
    return tuple(2 * f_pad if m == "mxmn" else (128 if m == "cnt" else f_pad)
                 for m in moments)


def _slice_outs(moments, outs, num_segments, f, f_pad, dtype):
    res = []
    for m, o in zip(moments, outs):
        if m == "mxmn":
            res.append(jnp.concatenate(
                [o[:num_segments, :f], o[:num_segments, f_pad:f_pad + f]],
                axis=1).astype(dtype))
        elif m == "cnt":
            res.append(o[:num_segments, 0])
        else:
            res.append(o[:num_segments, :f].astype(dtype))
    return tuple(res)


def _poly_scatter_impl(data2d, sorted_ids, num_segments, moments, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data2d.shape
    f_pad = _round_up(max(f, 1), 128)
    bn, be = _NODE_BLOCK, _edge_block(f_pad, moments)
    n_pad = _round_up(num_segments, bn)
    e_pad = _round_up(max(e, 1), be)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    data_p = jnp.zeros((e_pad, f_pad), data2d.dtype).at[:e, :f].set(data2d)
    ids_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        sorted_ids.astype(jnp.int32))

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        ids_p[:, 0], n_blocks, bn, be, n_eblocks)

    def eix(s, si, se, av, fi):
        return (se[s], 0)

    def oix(s, si, se, av, fi):
        return (si[s], 0)

    widths = _out_layout(moments, f_pad)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, f_pad), eix),
        ],
        out_specs=[pl.BlockSpec((bn, w), oix) for w in widths],
    )
    outs = pl.pallas_call(
        functools.partial(_poly_scatter_kernel, moments),
        out_shape=[jax.ShapeDtypeStruct((n_pad, w), jnp.float32)
                   for w in widths],
        grid_spec=grid_spec,
        interpret=interpret,
        name="poly_scatter_fwd",
    )(step_i, step_eb, acc_valid, is_first, ids_p, data_p)
    return _slice_outs(moments, outs, num_segments, f, f_pad, data2d.dtype)


def _poly_gather_impl(x, senders, receivers, moments, mask, interpret,
                      window=3):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, f = x.shape
    e = senders.shape[0]
    f_pad = _round_up(max(f, 1), 128)
    bn, be = _NODE_BLOCK, _edge_block(f_pad, moments)
    n_pad = _round_up(n, bn)
    e_pad = _round_up(max(e, 1), be)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    x_p = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x)
    m = (jnp.ones((e,), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    # masked edges park out of every block/window (fused_mp contract: they
    # sort after all real edges, so the schedule skips their blocks)
    ev = m != 0
    senders = jnp.where(ev, senders, n_pad)
    receivers = jnp.where(ev, receivers, n_pad)
    send_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        senders.astype(jnp.int32))
    recv_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        receivers.astype(jnp.int32))
    mask_p = jnp.zeros((e_pad, 1), jnp.float32).at[:e, 0].set(m)

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)

    def eix(s, si, se, av, fi):
        return (se[s], 0)

    def oix(s, si, se, av, fi):
        return (si[s], 0)

    def xoff(off):
        def fmap(s, si, se, av, fi):
            return (jnp.clip(si[s] + off, 0, n_blocks - 1), 0)
        return fmap

    assert window % 2 == 1, "window must be odd"
    hw = window // 2
    widths = _out_layout(moments, f_pad)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
        ] + [pl.BlockSpec((bn, f_pad), xoff(o)) for o in range(-hw, hw + 1)],
        out_specs=[pl.BlockSpec((bn, w), oix) for w in widths],
    )
    outs = pl.pallas_call(
        functools.partial(_poly_gather_kernel, moments, window),
        out_shape=[jax.ShapeDtypeStruct((n_pad, w), jnp.float32)
                   for w in widths],
        grid_spec=grid_spec,
        interpret=interpret,
        name="poly_gather_fwd",
    )(step_i, step_eb, acc_valid, is_first, send_p, recv_p, mask_p,
      *([x_p] * window))
    return _slice_outs(moments, outs, n, f, f_pad, x.dtype)


# ---------------------------------------------------------------------------
# scatter-mode public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def segment_poly_dense(data, sorted_ids, num_segments, moments, valid=None):
    """Multi-moment segment reduce of edge-valued ``data`` [E, F] at
    NONDECREASING ``sorted_ids`` — one dense-schedule Pallas pass returning
    a tuple in kernel-moment order (subset of ``MOMENT_ORDER``):

      sum [N, F], sq [N, F] (sum of squares), mxmn [N, 2F] (max of
      [data, -data]; -1e9 on empty segments — callers apply the
      segment_max zero-clean), cnt [N] (rows per segment).

    ``valid`` (optional, 1 = real) parks masked rows out of range so the
    schedule skips their blocks; masked rows must sort after all real rows
    (collate's padding-edge guarantee).  Masked/out-of-range rows get ZERO
    gradients.  Differentiable wrt ``data``; the max/min gradient splits
    evenly among ties, matching ``jax.ops.segment_max``'s VJP.
    """
    moments = _norm_moments(moments)
    interpret = jax.default_backend() != "tpu"
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    return _poly_scatter_impl(data, sorted_ids, num_segments, moments,
                              interpret)


def _spd_fwd(data, sorted_ids, num_segments, moments, valid=None):
    moments = _norm_moments(moments)
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    out = segment_poly_dense(data, sorted_ids, num_segments, moments)
    mxmn = out[moments.index("mxmn")] if "mxmn" in moments else None
    return out, (data, sorted_ids, mxmn)


def _spd_bwd(num_segments, moments, res, g):
    moments = _norm_moments(moments)
    data, ids, mxmn = res
    f = data.shape[1]
    ok = (ids >= 0) & (ids < num_segments)
    safe = jnp.clip(ids, 0, num_segments - 1)
    d = jnp.zeros(data.shape, jnp.float32)
    for m, gm in zip(moments, g):
        if m == "sum":
            d += jnp.where(ok[:, None], gm[safe].astype(jnp.float32), 0.0)
        elif m == "sq":
            d += 2.0 * data.astype(jnp.float32) * jnp.where(
                ok[:, None], gm[safe].astype(jnp.float32), 0.0)
        elif m == "mxmn":
            both = jnp.concatenate([data, -data], axis=1)
            tie = (both == mxmn[safe]) & ok[:, None]        # [E, 2F]
            # even tie split (jax.ops.segment_max VJP parity): tie counts
            # for max and min ride ONE sorted dense pass
            n_tie = segment_sum_dense(
                tie.astype(jnp.float32), ids, num_segments)
            gmx = jnp.where(ok[:, None], gm[safe].astype(jnp.float32), 0.0)
            term = jnp.where(
                tie, gmx / jnp.maximum(n_tie[safe], 1.0), 0.0)
            d += term[:, :f] - term[:, f:]
        # cnt: no data gradient
    return d.astype(data.dtype), None, None


segment_poly_dense.defvjp(_spd_fwd, _spd_bwd)


# ---------------------------------------------------------------------------
# gather-mode public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def gather_poly_segment(x, senders, receivers, sender_perm, moments,
                        mask=None):
    """Multi-moment reduce of the gathered messages ``x[senders] * mask``
    at NONDECREASING ``receivers``, without materializing the [E, F]
    message tensor (same collate invariants as
    :func:`~hydragnn_tpu.ops.fused_mp.gather_mul_segment_sum`: graphs
    contiguous and within one node block, masked edges zero-masked and
    tail-sorted).  Returns the same tuple layout as
    :func:`segment_poly_dense`.  ``sender_perm`` is collate's stable
    sender argsort (backward scatters dx at senders through it; pass None
    for a forward-only call).  Differentiable wrt ``x``.
    """
    moments = _norm_moments(moments)
    interpret = jax.default_backend() != "tpu"
    return _poly_gather_impl(x, senders, receivers, moments, mask,
                             interpret)


def _gps_fwd(x, senders, receivers, sender_perm, moments, mask=None):
    moments = _norm_moments(moments)
    out = gather_poly_segment(x, senders, receivers, sender_perm, moments,
                              mask)
    mxmn = out[moments.index("mxmn")] if "mxmn" in moments else None
    return out, (x, senders, receivers, sender_perm, mask, mxmn)


def _gps_bwd(moments, res, g):
    from hydragnn_tpu.ops.fused_mp import _bwd_call, _pack

    moments = _norm_moments(moments)
    x, senders, receivers, sender_perm, mask, mxmn = res
    n, f = x.shape
    m = (jnp.ones((senders.shape[0],), jnp.float32) if mask is None
         else mask.astype(jnp.float32))

    moms = dict(zip(moments, g))
    need_msgs = ("sq" in moments) or ("mxmn" in moments)
    if not need_msgs and "sum" not in moms:
        return jnp.zeros_like(x), None, None, None, None  # cnt-only
    if not need_msgs:
        # sum-only (cnt has no x-grad): dx[n] = sum_{e: send=n} m_e
        # g_sum[recv_e] — fused_mp's w-less backward pass over the edges
        # in receiver order, no [E, F] intermediate and no permutation
        g_p, _, ids_p, recv_f = _pack(
            moms["sum"].astype(jnp.float32), None, senders, receivers,
            m, m)
        dx_p, _ = _bwd_call(False, 3, None, None, ids_p, recv_f, g_p)
        return dx_p[:n, :f].astype(x.dtype), None, None, None, None
    if sender_perm is None:
        sender_perm = jnp.argsort(senders, stable=True)

    # sq/mxmn need the messages: recompute the gather (receivers gather of
    # g is sorted and cheap; senders gather of x is the one re-read)
    msgs = x[senders].astype(jnp.float32) * m[:, None]
    c = jnp.zeros(msgs.shape, jnp.float32)               # dL/dmsgs
    if "sum" in moments:
        c += moms["sum"][receivers].astype(jnp.float32)
    if "sq" in moments:
        c += 2.0 * msgs * moms["sq"][receivers].astype(jnp.float32)
    if "mxmn" in moments:
        both = jnp.concatenate([msgs, -msgs], axis=1)
        ids = jnp.where(m != 0, receivers, n)
        ok = m != 0
        safe = jnp.clip(ids, 0, n - 1)
        tie = (both == mxmn[safe]) & ok[:, None]
        n_tie = segment_sum_dense(tie.astype(jnp.float32), ids, n)
        gmx = jnp.where(ok[:, None],
                        moms["mxmn"][safe].astype(jnp.float32), 0.0)
        term = jnp.where(tie, gmx / jnp.maximum(n_tie[safe], 1.0), 0.0)
        c += term[:, :f] - term[:, f:]
    # dmsgs/dx[send] = m; scatter at senders over the sorted permutation
    c = c * m[:, None]
    perm = sender_perm
    dx = segment_sum_dense(c[perm], senders[perm], n,
                           valid=m[perm])
    return dx.astype(x.dtype), None, None, None, None


gather_poly_segment.defvjp(_gps_fwd, _gps_bwd)
